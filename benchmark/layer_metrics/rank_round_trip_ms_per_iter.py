"""Device milliseconds per iteration in the XLA programs that carry a
ranking objective's gradients out of the record and back: everything that
is no kernel between the end of one iteration's last `move_pass` (or the
window's start) and the next `slot_hist_pass`, which opens a tree. That
is the materialise of the scores in row order, the glue around the rank
kernel and the write-back by row id; the kernel itself is
`rank_kernel_ms_per_iter`. Read on the first chip. None unless a kernel
that is not the engine's ran inside such a gap: a pointwise objective
makes no round trip."""
from benchmark.layer_metrics import rank_kernel_ms_per_iter


def read(ctx):
    trace = ctx["trace"]
    ops = trace.get("ops") or {}
    rank = rank_kernel_ms_per_iter.others(trace)
    if not ops or not rank:
        return None
    events = sorted(ops[sorted(ops)[0]], key=lambda ev: ev[1])
    kernels = trace["kernels"]
    total, gap, saw_rank = 0, 0, False
    for name, start, end in events:
        if name == "slot_hist_pass":
            if saw_rank:
                total += gap
            gap, saw_rank = 0, False
        elif name == "move_pass":
            gap, saw_rank = 0, False
        elif name in rank:
            saw_rank = True
        elif name not in kernels:
            gap += end - start
    return total / 1e6 / ctx["iterations"] if total else None
