"""`walk_pass`'s share of the HBM roofline over the validation set: for
each iteration, one read of every validation row's used lanes and one
read and one write of its score lane (`least_bytes`, over the rows the
window's `aligned.iter` records say were walked) at the chip's peak, over
the kernel's device seconds in phase `valid.walk`. The walk is compute,
so the share is small, and an implementation that reads the records once
an iteration cannot pass 100. A program without the counters or the
phase gives nothing."""
from benchmark.layer_metrics import _phases, _seams


def least_bytes(rows_walked: int, w_used: int) -> int:
    """The least HBM traffic of walking one tree over `rows_walked` rows:
    the used lanes read once (the bins that say which leaf a row reaches)
    and the score lane read and written, 4 bytes a lane."""
    return rows_walked * 4 * (w_used + 2)


def read(ctx):
    recs = _seams.ring()
    win = _seams.window(recs, ctx["iterations"])
    pack = _seams.named(recs, "aligned.pack")
    walks = _phases.window(ctx)
    if win is None or not pack or walks is None:
        return None
    rows = sum(r.get("valid_rows_walked", 0) for r in win["iters"])
    ns = _phases.by_phase(walks, kernels=True).get("valid.walk", 0.0)
    peak = _seams.hbm_bytes_per_s() if ns else None
    if not rows or not peak:
        return None
    least = least_bytes(rows, pack[-1]["w_used"])
    return 100.0 * (least / peak) / (ns / 1e9)
