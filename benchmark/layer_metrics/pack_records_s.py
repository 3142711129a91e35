"""Seconds of the engine's host-side record pack (`aligned.pack`: layout
choice, `pack_records` over every row, padding to the chunk grid, score
fill): the first part of `first_update_s`."""
from benchmark.layer_metrics import _seams


def read(ctx):
    return _seams.total("aligned.pack")
