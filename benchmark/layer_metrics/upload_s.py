"""Seconds the host spends handing the record matrix to the device
(`aligned.upload`). The seam is the enqueue: what the runtime has not
copied when the call returns is waited for in the first drain."""
from benchmark.layer_metrics import _seams


def read(ctx):
    return _seams.total("aligned.upload")
