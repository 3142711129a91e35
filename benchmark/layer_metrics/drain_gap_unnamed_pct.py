"""Per cent of `drain_gap_ms` in which the host was in `train.drain` and
in no seam inside it (`train.flag_pull`, `train.resolve`,
`train.materialise`): the part of the gap the program cannot name."""
from benchmark.layer_metrics import _phases


def read(ctx):
    found = _phases.drain_gap_ns(ctx)
    if found is None or not found[0]:
        return None
    return 100.0 * found[1] / found[0]
