"""Of the live chunks `move_pass` handled in the window, the share that took
its compute path (`chunks_split`) and not the whole-chunk DMA
(`chunks_copied`)."""
from benchmark.layer_metrics import _seams


def read(ctx):
    win = _seams.window(_seams.ring(), ctx["iterations"])
    if win is None:
        return None
    split = sum(_seams.column(win["iters"], "chunks_split"))
    copied = sum(_seams.column(win["iters"], "chunks_copied"))
    return 100.0 * split / (split + copied) if split + copied else None
