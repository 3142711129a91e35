"""Microseconds of `move_pass` per chunk shifted whole by one DMA: the
other coefficient of `move_us_per_split_chunk`'s fit."""
from benchmark.layer_metrics import _seams


def read(ctx):
    costs = _seams.chunk_costs_us(ctx)
    return None if costs is None else costs[1]
