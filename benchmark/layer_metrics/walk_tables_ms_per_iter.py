"""Device milliseconds per iteration in the XLA programs of the record
walk: phases `walk.tables` (a committed spec as a compact tree,
`walk_expand`) and `walk.apply` (the operands of `walk_pass`). The kernel
itself is `dart_walk_ms_per_iter`'s."""
from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.phase_ms_per_iter(ctx, "walk.tables", "walk.apply")
