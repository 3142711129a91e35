"""Seconds inside the program's three ingest calls as its own seams time
them (`ingest.find_bins`, `ingest.push_rows`, `ingest.finish_load`): the
inside twin of `ingest_bin_s`, which holds a host clock around the same
calls from outside."""
from benchmark.layer_metrics import _seams


def read(ctx):
    return _seams.total("ingest.")
