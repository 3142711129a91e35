"""Device milliseconds inside `walk_pass` per dropped tree over the
window: the number that carries to a deployment's depth, where an
iteration drops dozens. A tree is walked out of the score lane and back
into it, and both walks are in it."""
from benchmark.layer_metrics import _dart


def read(ctx):
    ns, drops = _dart.walk_ns(ctx), _dart.dropped(ctx)
    if ns is None or not drops or not sum(drops):
        return None
    return ns / 1e6 / sum(drops)
