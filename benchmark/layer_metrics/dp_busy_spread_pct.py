"""How unevenly the chips of a mesh are busy over the traced window:
100 x (most busy chip - least busy chip) / mean busy. Every all-reduce
waits for the slowest shard, so this is the share of a chip's time the
imbalance of the shards can cost the others (`_dp.busy_spread_pct`)."""
from benchmark.layer_metrics import _dp


def read(ctx):
    return _dp.busy_spread_pct(ctx)
