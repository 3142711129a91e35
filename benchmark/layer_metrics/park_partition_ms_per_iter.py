"""Device milliseconds per iteration in the partition by the bag, phase
`build.park`: the `move_pass` kernel that routes by the bag lane (told
from a round's by its call site's phase) AND the XLA operations around
it, the copy back into the first buffer among them."""
from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.phase_ms_per_iter(ctx, "build.park", kernels=True)
