"""Device milliseconds per iteration at the two ends of the build
program: phases `build.head` (the gradient lanes' write, the second
buffer's zero fill, the empty tables), `build.copy_back` (the rows out of
the second buffer after an odd round count: the copy itself, where
`aligned.iter`'s `norm_passes` only said that it happened) and
`build.tail` (cover values, committed chains, the score-lane update).
XLA operations only."""
from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.phase_ms_per_iter(ctx, "build.head", "build.copy_back",
                                     "build.tail")
