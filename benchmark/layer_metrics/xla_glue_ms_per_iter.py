"""Device milliseconds per iteration outside the Pallas kernels: busy
time minus kernel time, which is the builder's XLA programs (count and
layout, split evaluation, replay, score update, materialise)."""
from benchmark.layer_metrics import pallas_ms_per_iter


def read(ctx):
    kernels = pallas_ms_per_iter.read(ctx)
    if kernels is None:
        return None
    busy_ms = 1e3 * ctx["trace"]["window"]["busy_s"]
    return busy_ms / ctx["iterations"] - kernels
