"""Device milliseconds per iteration in the programs that sample rows on
the device, by the name they give themselves: phases `sample.goss`
(GOSS's two counting selects and the multiplier lane's write) and
`sample.bag` (plain bagging's draw). Whatever lies around them in the
trace, a partition by the bag or a copy back, is none of it."""
from benchmark.layer_metrics import _phases


def read(ctx):
    return _phases.phase_ms_per_iter(ctx, "sample.goss", "sample.bag")
