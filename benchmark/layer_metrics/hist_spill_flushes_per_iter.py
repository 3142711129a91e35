"""Histogram slots that `move_pass` flushed through its HBM spill ring
per window iteration (`spill_slots` of the window's `aligned.iter`
records): one per split leaf where the slot store does not fit VMEM, 0
where it does."""
from benchmark.layer_metrics import _seams


def read(ctx):
    return _seams.per_iter(ctx, "spill_slots")
