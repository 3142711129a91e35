"""Device milliseconds per iteration in the leaf-wise replay on the
device, phase `build.replay`: the in-round replay (or its shortcut) and
the one behind the rounds."""
from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.phase_ms_per_iter(ctx, "build.replay")
