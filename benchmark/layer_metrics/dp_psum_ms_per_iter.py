"""Device milliseconds per iteration in the data-parallel histogram
all-reduce: phase `dp.psum` (the root's histogram and each round's
children summed over the chips), averaged over the chips. The events'
own durations: compute the compiler runs between an asynchronous
collective's start and its end is not in it."""
from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.phase_ms_per_iter(ctx, "dp.psum")
