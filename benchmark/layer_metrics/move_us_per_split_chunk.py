"""Microseconds of `move_pass` per chunk on its compute path: least
squares, no intercept, of each traced `move_pass` event's duration on its
round's compute-path and copied chunk counts (`_seams.chunk_costs_us`)."""
from benchmark.layer_metrics import _seams


def read(ctx):
    costs = _seams.chunk_costs_us(ctx)
    return None if costs is None else costs[0]
