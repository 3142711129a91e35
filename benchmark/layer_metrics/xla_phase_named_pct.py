"""Per cent of the traced window's XLA (non-kernel) device time whose
event joined a registered phase of the program (`obs/phases.py`): the
yardstick of the phase readers. A stale or empty phase table reads 0; what
stays unnamed is compiler-made operations no `op_name` reaches and
programs the run did not remember."""
from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.named_share(ctx)
