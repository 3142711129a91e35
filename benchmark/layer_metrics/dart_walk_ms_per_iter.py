"""Device milliseconds per iteration inside `walk_pass`, the kernel that
takes dropped trees out of the score lane and puts them back, over the
traced window (`_dart.walk_ns`). The traced window (iterations 17-19)
drops more trees an iteration than a timed one: compare
`dart_walk_ms_per_dropped_tree`."""
from benchmark.layer_metrics import _dart


def read(ctx):
    ns = _dart.walk_ns(ctx)
    return None if ns is None else ns / 1e6 / ctx["iterations"]
