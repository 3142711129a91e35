"""Task `binary_goss`: task `binary` under `boosting=goss`, where from
iteration int(1 / learning_rate) on every tree is grown on a sample that
the program draws on the device from its own scores.

`correct` is decided on what the timed path itself produced, at the
timed size:

- tree 0 is unsampled by the reference's own rule, so its root is held to
  the whole column as task `binary` holds it, under the same limits;
- `holdout_auc_15_shortfall`: `AUC_15_FLOOR` less the holdout AUC of the
  first 15 trees (the traffic's 12 of warm-up and the smallest window's
  3, of which 5 are sampled), limit 0. `holdout_auc_6` sees unsampled
  trees only;
- after the window, the training scores are read in row order, ONE MORE
  `update()` is made and drained, and the multiplier lane that update
  trained on is read in row order (`AlignedEngine.row_bag`). The plain
  reference (`benchmark/reference_goss.py`, float64) at those scores and
  the seed the program says it drew (seam `goss.select`) gives:
  `goss_multiplier_mismatch_rows`, the rows whose kind (left out, top set,
  sampled rest) differs, against the number of rows whose |g x h| lies
  within `BAND` relative of the threshold: the device rounds the sigmoid
  in f32 where the reference has float64, and only such a row can change
  sides for that (ten seeds on the chip read up to 0.52 of the count
  at 1e-6, the issue's band, with the threshold just over 2^-3, where
  f32 is coarsest; at 2e-6 that is a quarter of the limit, and the
  nearest fault reads 500 times it); `goss_multiplier_value_err`, the largest difference of
  the multiplier over the rows of one kind, limit 0 (8 is 8 in f32);
- that tree's root against the split column and the reference's
  gradients times the lane, over the kept rows (`root_from_gradients`):
  `sampled_root_left_count_err` limit 0 (a kept row counts once, and 14.4M
  kept rows are under 2^24) and `sampled_root_gain_rel_err` limit 1e-3.
  The lane is thereby tied to the reference and the tree to the lane.

The driver lays this file over the parent's checkout, where
`boosting=goss` at 48M rows would train on the fused leaf-wise loop for
many minutes. So a program whose aligned engine cannot run GOSS is
refused here, as the module is imported and before a row is made.
"""
import time

import numpy as np

from benchmark import reference, reference_goss
from benchmark.tasks import binary
from lightgbm_tpu.models.aligned_builder import AlignedEngine

if not hasattr(AlignedEngine, "goss_select"):
    raise SystemExit(
        "benchmark task binary_goss: this program's aligned engine cannot "
        "run boosting=goss (lightgbm_tpu.models.aligned_builder."
        "AlignedEngine has no goss_select); the cell would train on the "
        "fused leaf-wise loop, which it does not measure")

QUALITY = binary.QUALITY
GROUPED = binary.GROUPED
quality = binary.quality

HOLDOUT_ROWS = 200_000  # the configs' `holdout_rows`
SAMPLED_TREES = 15      # 12 of warm-up and the smallest window
AUC_15_FLOOR = 0.82     # the configs' `quality_floor_sampled`
BAND = 2e-6             # relative, around the threshold (PERF.md section 2)
SAMPLED_GAIN_RTOL = 1e-3    # f32 histogram sums, as `binary`'s


def gradients(score: np.ndarray, label: np.ndarray, sigmoid: float):
    """(g, h) of `objective=binary` in float64: with p = 1 / (1 +
    exp(-sigmoid x score)), g = sigmoid (p - y) and h = sigmoid^2 p (1 -
    p) (`binary_objective.hpp:GetGradients`, unweighted)."""
    p = 1.0 / (1.0 + np.exp(-sigmoid * np.asarray(score, np.float64)))
    return sigmoid * (p - label), sigmoid * sigmoid * p * (1.0 - p)


def kind(mult: np.ndarray) -> np.ndarray:
    """0 left out, 1 top set, 2 sampled rest."""
    return np.where(mult == 0, 0, np.where(mult == 1, 1, 2))


def sampled_tree(run) -> tuple:
    """One more iteration past the window, held to the reference."""
    from lightgbm_tpu.obs import trace
    bst, n = run.booster, run.rows
    y = np.asarray(run.labels, np.float64)
    bst.eval_train()                                    # drain
    score = np.asarray(bst._gbdt.train_score.score[0])  # row order, f32
    t = time.perf_counter()
    bst.update()
    bst.eval_train()
    # what the program says it drew; one that drew nothing is held to
    # the reference all the same, under a seed of 0
    drawn = ([r for r in trace.seams("goss.select") if r["t0"] >= t]
             or [{"seed": 0, "iter": None}])[-1]
    # this iteration is the check's, not the window's: its records leave
    # the ring, where the per-layer readers look for the window last
    trace.forget_seams_since(t)
    lane = np.asarray(bst._gbdt._aligned_eng_ref.row_bag(), np.float64)
    g, h = gradients(score, y, float(run.params.get("sigmoid", 1.0)))
    ref = reference_goss.goss_multipliers(
        g, h, np.arange(n), drawn["seed"], float(run.params["top_rate"]),
        float(run.params["other_rate"]))
    same = kind(lane) == kind(ref["multiplier"])
    thr = ref["threshold"]
    near = int((np.abs(ref["a"] - thr) <= BAND * thr).sum())
    value_err = float(np.abs(
        lane[same] - ref["multiplier"][same].astype(np.float32)).max())
    tree = bst.dump_model()["tree_info"][-1]
    kept = lane > 0
    root = reference.root_from_gradients(
        {"tree_info": [tree]},
        run.gen.column(tree["tree_structure"]["split_feature"], 0, n)[kept],
        (g * lane)[kept], (h * lane)[kept],
        lambda_l2=float(run.params.get("lambda_l2", 0.0)))
    compared = {
        "goss_multiplier_mismatch_rows": (float((~same).sum()), float(near)),
        "goss_multiplier_value_err": (value_err, 0.0),
        "sampled_root_left_count_err": (root["left_count_err"], 0.0),
        "sampled_root_gain_rel_err": (root["gain_rel_err"],
                                      SAMPLED_GAIN_RTOL),
    }
    return compared, {
        "sampled_root": root, "iteration": drawn["iter"],
        "seed": drawn["seed"], "threshold": thr,
        "rows_within_band": near, "mismatch_rows": int((~same).sum()),
        "kept_top": int((lane == 1).sum()),
        "kept_top_reference": ref["kept_top"],
        "kept_other": int((lane > 1).sum()), "top_k": ref["top_k"],
        "other_k": ref["other_k"],
        "multiplier": float(lane.max()),
        "multiplier_reference": (n - ref["top_k"]) / ref["other_k"]}


def first_tree(run) -> tuple:
    """({name: (number, limit)}, detail): see the module's docstring."""
    compared, root = binary.first_tree(run)
    x_hold, y_hold = run.gen.rows(run.rows, run.rows + HOLDOUT_ROWS)
    auc_15 = quality(run.booster.predict(x_hold, num_iteration=SAMPLED_TREES),
                     y_hold, None)
    compared["holdout_auc_15_shortfall"] = (AUC_15_FLOOR - auc_15, 0.0)
    sampled, detail = sampled_tree(run)
    compared.update(sampled)
    return compared, dict(root, holdout_auc_15=auc_15, sampled_tree=detail)
