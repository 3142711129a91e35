"""What the readers of the layer "tree walk" share: the window's
`walk_pass` events (the device trace, the kernel by name, on the first
chip: the counters are its) and the counters that ride each `aligned.iter`
record of a run of `boosting=dart` on the aligned engine (`dart_dropped`,
`walk_passes`, `rows_walked`).

A program without the kernel or the counters (the parent of the PR that
brought them) gives nothing to read, and every reader `None`.
"""
from benchmark.layer_metrics import _seams

KERNEL = "walk_pass"


def walk_ns(ctx):
    """Device ns inside `walk_pass` over the traced window, or None where
    the trace holds no such event."""
    ops = ctx["trace"].get("ops") or {}
    if not ops:
        return None
    ns = [e - s for n, s, e in ops[sorted(ops)[0]] if n == KERNEL]
    return sum(ns) if ns else None


def dropped(ctx):
    """[trees dropped] of the window's iterations, or None where the ring
    does not hold the window or no iteration carries the counter."""
    win = _seams.window(_seams.ring(), ctx["iterations"])
    if win is None or not any("dart_dropped" in r for r in win["iters"]):
        return None
    return [r.get("dart_dropped", 0) for r in win["iters"]]


def least_bytes(rows: int, w_used: int, dropping_iterations: int) -> int:
    """The least HBM traffic any implementation of DART's score update
    needs: for each iteration that drops, one read of every row's used
    lanes (the bins that say which leaf a row reaches) and one read and
    one write of its score lane, 4 bytes a lane. It is the same work
    whether trees are walked one a pass or together, once out and once
    back or once in all."""
    return dropping_iterations * rows * 4 * (w_used + 2)


def roofline_pct(ctx):
    """100 x (`least_bytes` / the chip's HBM peak) / `walk_pass`
    seconds."""
    ns, drops = walk_ns(ctx), dropped(ctx)
    pack = _seams.named(_seams.ring(), "aligned.pack")
    peak = _seams.hbm_bytes_per_s() if ns else None
    if not ns or drops is None or not pack or not peak:
        return None
    least = least_bytes(pack[-1]["rows"], pack[-1]["w_used"],
                        sum(1 for k in drops if k > 0))
    return 100.0 * (least / peak) / (ns / 1e9)
