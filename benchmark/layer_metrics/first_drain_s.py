"""Seconds of the first `train.drain`: the host waiting for iteration 1 on
the device (and for what the upload left to copy), then for the
score-materialise program. The last part of `first_update_s`."""
from benchmark.layer_metrics import _seams


def read(ctx):
    drains = _seams.named(_seams.ring(), "train.drain")
    return _seams.seconds(drains[:1]) if drains else None
