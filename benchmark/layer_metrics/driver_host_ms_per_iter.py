"""Milliseconds per window iteration of the host's own work in the
boosting loop: the window, from its first `aligned.dispatch` to the end of
the `train.drain` that closes it, less the time the host is blocked on the
device inside `train.flag_pull` and `train.drain`. Dispatch, bookkeeping
and recording; no kernel's time is in it, so it moves with the driver
alone."""
from benchmark.layer_metrics import _seams


def read(ctx):
    return _seams.driver_host_ms_per_iter(ctx)
