"""Device milliseconds per iteration in the walk of the validation set,
phase `valid.walk`: the committed tree's tables and `walk_pass` over the
set's packed records, the kernel included (and, where a set takes the XLA
walkers, those). A program without the phase (the parent of the PR that
named it) gives nothing."""
from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.phase_ms_per_iter(ctx, "valid.walk", kernels=True)
