"""Task `binary_valid`: task `binary` trained as upstream's example config
trains it, watched on a held-out validation set whose AUC and log loss
are read after every iteration (`valid_data`, `metric =
binary_logloss,auc`, `metric_freq = 1`).

`correct` holds what the program said of the validation set to the plain
reference, the float64 walk of `dump_model()` over the same rows made
again from the seed. `benchmark/run.py` compares the first value it
reported, AUC, with numpy AUC of that walk (`valid_metric_err`). AUC
reads only the scores' order, so a walk that scales every tree alike (at
another shrinkage) keeps it; this task adds the second metric:

- `valid_logloss_err`: after the window, the program's `binary_logloss`
  of the validation set (one more `eval_valid()`, over the scores the
  window left) against the log loss of the float64 walk over every
  validation row, absolute. Limit `LOGLOSS_TOL` (PERF.md section 2 has
  both readings);
- tree 0's root, as task `binary` holds it.

The validation rows follow the holdout in the seed's stream (rows, then
`holdout_rows`, then `valid_rows`: `run.py`). A task is not handed its
configuration, so the holdout's length is the one `quality` was last
asked about: `run.py` asks for the holdout's quality just before the
first-tree check, and for the validation set's only after it.

A comparison with an earlier program lays this file over that program's
checkout too. An engine that walks a validation set by rows in XLA
programs takes about 13 s an iteration at 4.8M rows, where the cell
measures the packed walk, and its run compiling cold does not end inside
a run's time. So a program whose aligned engine cannot pack a validation
set into records is refused here, as the module is imported and before a
row is made.
"""
import time

import numpy as np

from benchmark.tasks import binary
from lightgbm_tpu.models.aligned_builder import AlignedEngine

if not hasattr(AlignedEngine, "pack_rows"):
    raise SystemExit(
        "benchmark task binary_valid: this program's aligned engine cannot "
        "pack a validation set into records (lightgbm_tpu.models."
        "aligned_builder.AlignedEngine has no pack_rows); the cell would "
        "time the validation walk by rows in XLA, which it does not measure")

QUALITY = binary.QUALITY
GROUPED = binary.GROUPED

# the program's f32 log loss (softplus, a sum by halves) of its f32 lane
# against the float64 walk's: sound runs read 9.9e-8 - 1.5e-6 on the chip,
# the planted faults 1e-2 and more (PERF.md section 2). It is no check of
# precision: scores rounded to bf16 read inside the sound runs' range,
# and `valid_metric_err` is what sees them
LOGLOSS_TOL = 5e-5

_asked = {}


def quality(pred, labels, groups) -> float:
    _asked["rows"] = len(labels)
    return binary.quality(pred, labels, groups)


def logloss(raw, labels) -> float:
    """Mean binary log loss of raw scores at sigmoid 1, float64: softplus
    of the score less the label times it."""
    raw = np.asarray(raw, np.float64)
    return float(np.mean(np.logaddexp(0.0, raw)
                         - np.asarray(labels, np.float64) * raw))


def said(bst, name: str) -> float:
    """The program's value of metric `name` on its (one) validation set
    now, or NaN where it reports none."""
    return next((float(value) for _, metric, value, _ in bst.eval_valid()
                 if metric == name), float("nan"))


def first_tree(run) -> tuple:
    """({name: (number, limit)}, detail): tree 0's root as task `binary`
    has it, and the validation set's log loss against the walk's."""
    from benchmark.run import walked
    from lightgbm_tpu.obs import trace
    compared, root = binary.first_tree(run)
    vs = run.booster._gbdt.valid_sets
    n_valid = int(vs[0].num_data) if vs else 0
    lo = run.rows + _asked.get("rows", 0)
    t = time.perf_counter()
    got = said(run.booster, "binary_logloss")
    # this evaluation is the check's, not the window's
    trace.forget_seams_since(t)
    raw, y = walked(run.gen, run.model, lo, n_valid)
    want = logloss(raw, y)
    compared["valid_logloss_err"] = (abs(got - want), LOGLOSS_TOL)
    return compared, {"root": root, "valid_rows": n_valid,
                      "valid_first_row": lo, "valid_logloss": got,
                      "valid_logloss_walk": want}
