"""Task `binary_dp`: task `binary` under the data-parallel learner
(`tree_learner=data`, `num_machines` shards of the rows, one a chip), as
upstream's parallel Criteo experiment trains.

The plain reference is upstream's data-parallel learner, which sums every
shard's histograms before it chooses a split and so grows the tree a
serial learner grows over all the rows (`benchmark/reference_dp.py`).
`correct` holds the timed run to it on what the timed path itself
produced, at the timed size:

- `root_left_count_err`, `root_gain_rel_err`: task `binary`'s root check
  over ALL the rows, the column made again from the seed, under its
  limits. A root split on one shard's histogram alone (no all-reduce)
  reads a left count off by about three quarters of the rows;
- `shard_score_walk_err`: the training scores the window left, pulled in
  row order, against the float64 numpy walk of the dumped trees over
  `WALK_ROWS` rows in one stretch inside each shard, the last shard's
  ending at the last row (`reference_dp.shard_stretches`). A shard whose
  score lane lags, or rows given to the wrong shard, read here;
- `shard_rows_err`: the rows the engine's pack wrote on each shard, as
  its pack program counted them (`AlignedEngine.rows_by_shard`), against
  each shard's share of the rows and their sum against the rows: the
  largest difference, limit 0. A shard that packed short reads here.

A benchmark laid over an older checkout runs this file with that
checkout's program. An engine that counts neither the rows each shard
packed nor the bytes its all-reduce moves leaves the cell's checks and
its per-layer metrics nothing to read: such a program is refused here, as
the module is imported and before a row is made.
"""
import concurrent.futures

import numpy as np

from benchmark import reference, reference_dp
from benchmark.tasks import binary
from benchmark.tasks.binary_dart import train_scores
from lightgbm_tpu.models.aligned_builder import AlignedEngine

if not hasattr(AlignedEngine, "psum_bytes"):
    raise SystemExit(
        "benchmark task binary_dp: this program's aligned engine counts "
        "neither the rows each shard packed nor the bytes its histogram "
        "all-reduce moves (lightgbm_tpu.models.aligned_builder.AlignedEngine "
        "has no psum_bytes); the cell's checks read both")

QUALITY = binary.QUALITY
GROUPED = binary.GROUPED
quality = binary.quality

WALK_ROWS = 81920       # as task `lambdarank`'s, a quarter in each shard
# f32 score lane, one rounding a tree; as `lambdarank`'s score walk. On
# four v5e chips the cell's sound runs read 2.3e-7 - 1.1e-6 (times a
# largest score of 1.3-3.3 in the limit); the walk in bf16, the precision
# under the configuration's f32, reads 4.2e-3 at toy size, and a lagging
# lane is off by a whole tree's leaf value, 1e-2 and more
SCORE_WALK_TOL = 1e-5
# row counts are whole numbers: a shard holds its share or it does not
SHARD_ROWS_TOL = 0.0


def shard_rows_err(rows_by_shard, rows: int, shards: int) -> float:
    """The largest difference between the rows each shard packed and its
    share, or between their sum and `rows`; the rows themselves where the
    engine gave another number of shards."""
    got = [int(r) for r in rows_by_shard]
    if len(got) != shards:
        return float(rows)
    want = [hi - lo for lo, hi in reference_dp.shard_bounds(rows, shards)]
    return float(max([abs(sum(got) - rows)]
                     + [abs(g - w) for g, w in zip(got, want)]))


def first_tree(run) -> tuple:
    """({name: (number, limit)}, detail): see the module's docstring."""
    compared, root = binary.first_tree(run)
    bst = run.booster
    shards = int(run.params["num_machines"])
    eng = bst._gbdt._aligned_eng_ref
    packed = [] if eng is None else eng.rows_by_shard
    score = train_scores(bst)
    stretches = reference_dp.shard_stretches(run.gen.seed, run.rows, shards,
                                             WALK_ROWS)
    with concurrent.futures.ThreadPoolExecutor(shards) as pool:
        walk = np.concatenate(list(pool.map(
            lambda r: reference.raw_scores(run.model, run.gen.rows(*r)[0]),
            stretches)))
    at = np.concatenate([np.arange(lo, hi) for lo, hi in stretches])
    compared.update({
        "shard_score_walk_err": (
            float(np.abs(score[at] - walk).max()),
            SCORE_WALK_TOL * max(1.0, float(np.abs(walk).max()))),
        "shard_rows_err": (shard_rows_err(packed, run.rows, shards),
                           SHARD_ROWS_TOL),
    })
    detail = {"root": root, "shards": shards, "rows_by_shard": packed,
              "walk_stretches": stretches,
              "shard_walk_err": [
                  float(np.abs(score[lo:hi] - walk[i:i + hi - lo]).max())
                  for (lo, hi), i in zip(stretches, np.cumsum(
                      [0] + [hi - lo for lo, hi in stretches[:-1]]))]}
    return compared, detail
