"""Milliseconds, on the device's clock, in which no operation ran on the
chip while the host was inside the window's `train.drain` seam: the gap
`breakdown.idle_gaps` knows only by the harness's `bench.drain`."""
from benchmark.layer_metrics import _phases


def read(ctx):
    found = _phases.drain_gap_ns(ctx)
    return None if found is None else found[0] / 1e6
