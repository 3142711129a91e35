"""The histogram all-reduce's share of its interconnect roofline: each
chip's least traffic for the window's all-reduces (a ring's 2 (n - 1) / n
x the bytes of the counter `psum_bytes`) at the chip's published ICI rate,
over the time a chip spent in them, from an all-reduce's first event to
its last (`_dp.roofline_pct`)."""
from benchmark.layer_metrics import _dp


def read(ctx):
    return _dp.roofline_pct(ctx)
