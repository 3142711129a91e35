"""Device milliseconds per iteration in the build program's split
evaluation: phases `build.root` (the root histogram's expansion and
evaluation) and `build.eval` (child histograms and `eval_one` over the
changed slots of every round). XLA operations only; `slot_hist_pass` is
a kernel and has its own name."""
from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.phase_ms_per_iter(ctx, "build.root", "build.eval")
