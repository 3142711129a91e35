"""Rounds of the build program's loop per window iteration (`rounds` of the
window's `aligned.iter` records): each round is one `move_pass` over the
whole chunk grid."""
from benchmark.layer_metrics import _seams


def read(ctx):
    win = _seams.window(_seams.ring(), ctx["iterations"])
    if win is None:
        return None
    return sum(r["rounds"] for r in win["iters"]) / ctx["iterations"]
