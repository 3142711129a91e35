"""Seconds of the first `update()` and its drain: the engine's host-side
record pack and upload, tracing, compile or cache load, iteration 1, and
the score-materialise program. From outside these cannot be told apart."""


def read(ctx):
    return ctx["walls"].get("first_update_s")
