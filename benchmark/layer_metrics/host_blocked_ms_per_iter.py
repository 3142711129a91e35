"""Milliseconds per window iteration in which the boosting loop is
blocked on the device: the seams `train.flag_pull` and `train.drain`
inside the window, a pull inside a drain counted once. It is what
`driver_host_ms_per_iter` subtracts. With a pipeline of depth 1 (a
validation set, bagging) the flags are pulled every iteration and this is
most of an iteration; in the plain loop it is the drain and one pull in
eight."""
from benchmark.layer_metrics import _seams


def read(ctx):
    found = _seams.blocked_s(ctx)
    return None if found is None else 1e3 * found[1] / ctx["iterations"]
