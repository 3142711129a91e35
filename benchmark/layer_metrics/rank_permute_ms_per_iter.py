"""Device milliseconds per iteration in the permutations between the
records and the rank kernel's tile pack: phases `rank.scatter` (the score
lane into the pack, with the sort ahead of the scatter), `rank.glue` (the
kernel's operand pack and result masks) and `rank.gather` (`build_ext`'s
two gathers by the index lane). XLA operations only."""
from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.phase_ms_per_iter(ctx, "rank.scatter", "rank.glue",
                                     "rank.gather")
