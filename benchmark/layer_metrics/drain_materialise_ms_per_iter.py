"""Device milliseconds per iteration in the scores' way back to row order,
phase `drain.materialise` (the materialise scatter and the sort XLA puts
ahead of it): once a window, so a traced window of three pays a third of
it an iteration where an untraced one of fifteen pays a fifteenth. The
traced `xla_glue_ms_per_iter` carries that difference silently."""
from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.phase_ms_per_iter(ctx, "drain.materialise")
