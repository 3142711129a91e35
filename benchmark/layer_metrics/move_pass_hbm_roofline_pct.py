"""`move_pass`'s share of the HBM roofline: the least traffic any
implementation needs (the rows of every split leaf, read once and written
once at 4 x `w_used` bytes) at the chip's peak, over the kernel's device
seconds (`_seams.move_roofline_pct`)."""
from benchmark.layer_metrics import _seams


def read(ctx):
    return _seams.move_roofline_pct(ctx)
