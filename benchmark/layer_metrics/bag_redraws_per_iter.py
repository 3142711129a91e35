"""Bags drawn per iteration of the window: `bag.draw` seam records (the
host's enqueue of the device's draw, one a re-bag) whose iteration is one
of the window's, over the window's iterations. `bagging_freq=5` gives 0.2
over a long window and 0.333 over the traced three (iterations 4, 5, 6:
one re-bag). None where the program's seam ring holds no such record: a
run that draws no bag on the device, or a program without the seam."""
from benchmark.layer_metrics import _seams


def redrawn(ctx):
    """The window's iterations at which a bag was drawn, or None where
    the ring holds no window or no `bag.draw` record at all."""
    recs = _seams.ring()
    win = _seams.window(recs, ctx["iterations"])
    draws = _seams.named(recs, "bag.draw")
    if win is None or not draws:
        return None
    mine = {r["iter"] for r in win["iters"]}
    return sorted({r["iter"] for r in draws if r["iter"] in mine})


def read(ctx):
    found = redrawn(ctx)
    return None if found is None else len(found) / ctx["iterations"]
