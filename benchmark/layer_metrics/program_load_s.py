"""Seconds of the first calls of the engine's programs before the window
(`aligned.program`: trace, lower, and compile or persistent-cache load,
all ahead of the enqueue). A first call inside a drain (the
score-materialise program's) is part of `first_drain_s`, not of this."""
from benchmark.layer_metrics import _seams


def read(ctx):
    return _seams.total("aligned.program", outside="train.drain",
                        before_window_of=ctx["iterations"])
