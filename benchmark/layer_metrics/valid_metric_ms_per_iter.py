"""Device milliseconds per iteration in the metric programs over the
validation set's scores, phase `valid.metric`: the score lane read as a
row-order view, AUC's sort with its scans over runs of equal scores, the
log loss. A program without the phase gives nothing."""
from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.phase_ms_per_iter(ctx, "valid.metric")
