"""Leaves the build program split per window iteration, speculative
splits that never commit included (`leaves_split` of the window's
`aligned.iter` records). A tree commits `num_leaves - 1` of them; what is
above that is rows `move_pass` moved for nothing."""
from benchmark.layer_metrics import _seams


def read(ctx):
    return _seams.per_iter(ctx, "leaves_split")
