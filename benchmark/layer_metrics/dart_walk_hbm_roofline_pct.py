"""`walk_pass`'s share of the HBM roofline: for each dropping iteration
one read of every row's used lanes and one read and one write of its
score lane (`_dart.least_bytes`) at the chip's peak, over the kernel's
device seconds. The walk is compute (three MXU products a chunk a tree),
so the share is small, and an implementation that reads the records once
an iteration cannot pass 100."""
from benchmark.layer_metrics import _dart


def read(ctx):
    return _dart.roofline_pct(ctx)
