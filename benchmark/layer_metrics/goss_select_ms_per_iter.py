"""Device milliseconds per iteration in the XLA programs between two
trees of a run that samples its rows on the device (`boosting=goss` on
the aligned engine). The selection is XLA programs and no kernel, so it
has no name in the trace; this reads, as `rank_round_trip_ms_per_iter`
reads its gap, everything that is no kernel from the end of one
iteration's last `move_pass` (or the window's start) to the next
`slot_hist_pass`, which opens a tree. That is the selection's 16 counting
passes over the |g x h| and key arrays and its write of the multiplier
lane, AND what every aligned iteration has there: the build program's
tail (replay, leaf values, score-lane update) and its head (the
gradient lanes' write). So it is an upper reading of the selection; the
plain cell's same gap is the part that is not the selection (PERF.md
section 5 gives both). Read on the first chip. None unless the program's
seam ring holds a `goss.select` seam inside the window: a run that does
not sample, or a program without that seam, reports nothing."""
from benchmark.layer_metrics import _seams


def sampled_in_window(ctx) -> bool:
    recs = _seams.ring()
    win = _seams.window(recs, ctx["iterations"])
    return win is not None and any(
        r["t0"] >= win["t0"] and r["t1"] <= win["t1"]
        for r in _seams.named(recs, "goss.select"))


def gap_ms_per_iter(ctx):
    """The gap's reading on any aligned run, sampled or not."""
    trace = ctx["trace"]
    ops = trace.get("ops") or {}
    if not ops:
        return None
    kernels = trace["kernels"]
    total, gap = 0, 0
    for name, start, end in sorted(ops[sorted(ops)[0]],
                                   key=lambda ev: ev[1]):
        if name == "slot_hist_pass":
            total += gap
            gap = 0
        elif name == "move_pass":
            gap = 0
        elif name not in kernels:
            gap += end - start
    return total / 1e6 / ctx["iterations"] if total else None


def read(ctx):
    return gap_ms_per_iter(ctx) if sampled_in_window(ctx) else None
