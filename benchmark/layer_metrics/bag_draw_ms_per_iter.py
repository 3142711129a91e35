"""Device milliseconds per iteration that the bag's draw itself takes.
The draw is XLA programs and no kernel, so it has no name in the trace;
it runs, as GOSS's selection does, in the gap between the end of one
iteration's last `move_pass` (or the window's start) and the next
`slot_hist_pass`, which opens a tree, beside what every aligned iteration
has there: the build program's tail and head. A window under
`bagging_freq` > 1 has gaps of both kinds, so the gap of every iteration
at which a bag was drawn (seam `bag.draw`), LESS the median gap of the
window's iterations that held their bag, is the draw alone; summed, over
the window's iterations. Two things are kept out of the difference. The
window's first gap lacks the tail of the iteration before it, so it
stands for a held iteration only where no other does. And a tree that
took an odd number of rounds ends with a copy of the whole record matrix
back out of the round loop's second buffer (`copy.*`, 14.7 ms at the
cell's size, `aligned.iter`'s `norm_passes`), which falls into the next
tree's gap whatever that tree does: an operation named `copy` is no part
of a gap here (the draw's program has none: it writes one lane of a
donated matrix in place). Before that, two traced runs of one tree read
6.98 and 2.08 ms for a draw of 20.9 ms (my chip runs, PR 35). Read on the
first chip. None unless the window holds a
`bag.draw` record and an iteration without one, and the trace one gap an
iteration."""
import statistics

from benchmark.layer_metrics import _seams, bag_redraws_per_iter


def gaps_ns(ctx) -> list:
    """Nanoseconds of XLA programs ahead of every `slot_hist_pass` of the
    traced window, in execution order."""
    trace = ctx["trace"]
    ops = trace.get("ops") or {}
    if not ops:
        return []
    kernels = trace["kernels"]
    out, gap = [], 0
    for name, start, end in sorted(ops[sorted(ops)[0]],
                                   key=lambda ev: ev[1]):
        if name == "slot_hist_pass":
            out.append(gap)
            gap = 0
        elif name == "move_pass":
            gap = 0
        elif name not in kernels and not name.startswith("copy"):
            gap += end - start
    return out


def read(ctx):
    drawn = bag_redraws_per_iter.redrawn(ctx)
    gaps = gaps_ns(ctx)
    win = _seams.window(_seams.ring(), ctx["iterations"])
    if not drawn or win is None or len(gaps) != len(win["iters"]):
        return None
    by_iter = [(r["iter"], gap) for r, gap in zip(win["iters"], gaps)]
    held = [gap for it, gap in by_iter[1:] if it not in drawn] \
        or [gap for it, gap in by_iter[:1] if it not in drawn]
    if not held:
        return None
    base = statistics.median(held)
    return sum(gap - base for it, gap in by_iter if it in drawn) \
        / 1e6 / ctx["iterations"]
