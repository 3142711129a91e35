"""Device milliseconds per iteration in a round's table arithmetic, phase
`build.layout`: splits chosen, left counts, the new layout, move
destinations, updated tables, per-chunk counts, the round's counters.
XLA operations only: `move_pass` and `count_pass`, called from there, are
kernels."""
from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.phase_ms_per_iter(ctx, "build.layout")
