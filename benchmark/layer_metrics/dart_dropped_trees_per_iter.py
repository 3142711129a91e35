"""Trees dropped per iteration over the window, from the counter
`dart_dropped` on each `aligned.iter` record. A constant of the
configuration's drop schedule and the window's place in it."""
from benchmark.layer_metrics import _dart


def read(ctx):
    drops = _dart.dropped(ctx)
    return None if drops is None else sum(drops) / ctx["iterations"]
