"""Task `binary_dart`: task `binary` under `boosting=dart`, where every
iteration may take earlier trees out of the training scores, grow its
tree without them at a smaller shrinkage, and put them back lighter.

`correct` is decided on what the timed path itself produced, at the
timed size. The drop schedule depends on `drop_seed` and on nothing of
the data, so the plain reference (`benchmark/reference_dart.py`,
float64) writes the whole run's down before it is read:

- tree 0's root as task `binary` holds it (iteration 1 drops nothing);
- `dart_drop_set_mismatch_iters`: iterations of the whole run whose drop
  set or shrinkage, as the program recorded them (seam `dart.drop`),
  differ from the schedule's: limit 0;
- `dart_tree_weight_err`: every dumped tree's `shrinkage` against the
  schedule's weight after the last iteration, largest relative
  difference. Both are float64 products of the same factors, so the
  limit is a few of their roundings;
- `score_walk_err`, as task `lambdarank` has it: the training scores the
  window left against the numpy walk over the dumped trees, on
  `WALK_ROWS` rows (a stretch in the first block, one in the last, two
  drawn from the seed). The score lane is f32 and every dropped tree
  goes out of it and comes back, so it carries a few roundings a drop;
  a tree not put back, or put back whole, leaves 1e-2 or more;
- after the window, updates up to and including the schedule's next
  dropping iteration J (at most `MAX_EXTRA`), with a `dump_model()`
  before J: the gradient lane that iteration J trained on, read in row
  order, against float64 gradients at the walk of that dump less the
  dropped trees, on the same sampled rows (`dart_dropped_grad_err`);
  tree J's root left count and gain against its whole split column and
  the program's own gradient lanes summed in float64
  (`dart_root_left_count_err`, `dart_root_gain_rel_err`): the lane is
  tied to the reference and the tree to the lane; and `score_walk_err`
  once more after J (`score_walk_err_after`). These updates' seam
  records leave the ring, where the per-layer readers look for the
  window last.

The driver lays this file over the parent's checkout, where
`boosting=dart` at 48M rows would train on the fused leaf-wise loop for
many minutes. So a program whose aligned engine cannot walk trees over
its records is refused here, as the module is imported and before a row
is made.
"""
import concurrent.futures
import time

import numpy as np

from benchmark import reference, reference_dart
from benchmark.tasks import binary
from benchmark.tasks.binary_goss import gradients
from benchmark.tasks.lambdarank import walk_stretches
from lightgbm_tpu.models.aligned_builder import AlignedEngine

if not hasattr(AlignedEngine, "walk_trees"):
    raise SystemExit(
        "benchmark task binary_dart: this program's aligned engine cannot "
        "run boosting=dart (lightgbm_tpu.models.aligned_builder."
        "AlignedEngine has no walk_trees); the cell would train on the "
        "fused leaf-wise loop, which it does not measure")

QUALITY = binary.QUALITY
GROUPED = binary.GROUPED
quality = binary.quality

WALK_ROWS = 81920       # as task `lambdarank`'s, in WALK_BLOCKS stretches
WALK_BLOCKS = 4
MAX_EXTRA = 8           # updates past the window; no window of 5 to 20
#                         iterations needs more than 7 (PERF.md section 4)
WEIGHT_RTOL = 1e-12     # float64 products of a dozen factors, both sides
# PERF.md section 2 has the readings of the three below
SCORE_WALK_TOL = 2e-4   # f32 lane, a few roundings a dropped tree
GRAD_TOL = 1e-4         # f32 sigmoid of an f32 score against float64
ROOT_GAIN_RTOL = 1e-3   # f32 histogram sums, as `binary`'s


def schedule_of(params: dict, iterations: int) -> list:
    return reference_dart.drop_schedule(
        int(params["drop_seed"]), iterations, float(params["learning_rate"]),
        float(params["drop_rate"]), int(params["max_drop"]),
        float(params["skip_drop"]), bool(params["uniform_drop"]),
        bool(params["xgboost_dart_mode"]))


def train_scores(bst) -> np.ndarray:
    bst.eval_train()                                    # drain
    return np.asarray(bst._gbdt.train_score.score[0])   # row order, f32


def dropping_tree(run) -> tuple:
    """The window's state and one more dropping iteration, held to the
    reference: see the module's docstring."""
    from lightgbm_tpu.obs import trace
    bst, n = run.booster, run.rows
    trained = len(run.model["tree_info"])
    schedule = schedule_of(run.params, trained + MAX_EXTRA)
    stretches = walk_stretches(run.gen.seed, n, run.gen.block_rows,
                               WALK_BLOCKS, WALK_ROWS)
    with concurrent.futures.ThreadPoolExecutor(WALK_BLOCKS) as pool:
        x = np.concatenate(list(pool.map(
            lambda r: run.gen.rows(*r)[0], stretches)))
    at = np.concatenate([np.arange(lo, hi) for lo, hi in stretches])

    def walk_err(model, score):
        walk = reference.raw_scores(model, x)
        return (float(np.abs(score[at] - walk).max()),
                SCORE_WALK_TOL * max(1.0, float(np.abs(walk).max())))

    # what the program says it drew, the newest record an iteration
    said = {r["iter"]: r for r in trace.seams("dart.drop")}
    mismatch = sum(
        1 for s in schedule[:trained]
        if s["iter"] not in said
        or list(said[s["iter"]]["dropped"]) != s["dropped"]
        or said[s["iter"]]["shrinkage"] != s["shrinkage"])
    want = np.asarray(schedule[trained - 1]["weights"])
    got = np.asarray([t["shrinkage"] for t in run.model["tree_info"]])
    compared = {
        "dart_drop_set_mismatch_iters": (float(mismatch), 0.0),
        "dart_tree_weight_err": (float(np.abs(got / want - 1.0).max()),
                                 WEIGHT_RTOL),
        "score_walk_err": walk_err(run.model, train_scores(bst)),
    }

    # on to the next iteration that drops
    t = time.perf_counter()
    drop_at = next((s["iter"] for s in schedule[trained:] if s["dropped"]),
                   None)
    detail = {"trained": trained, "dropping_iteration": drop_at,
              "walk_stretches": stretches}
    if drop_at is not None:
        for _ in range(trained, drop_at):
            bst.update()
        before = bst.dump_model()
        bst.update()
        score = train_scores(bst)
        eng = bst._gbdt._aligned_eng_ref
        g_lane = np.asarray(eng.row_lane("grad"), np.float64)
        h_lane = np.asarray(eng.row_lane("hess"), np.float64)
        dropped = schedule[drop_at]["dropped"]
        kept = {"tree_info": [tree for i, tree in
                              enumerate(before["tree_info"])
                              if i not in dropped]}
        g, h = gradients(reference.raw_scores(kept, x),
                         np.asarray(run.labels, np.float64)[at],
                         float(run.params.get("sigmoid", 1.0)))
        after = bst.dump_model()
        tree = after["tree_info"][drop_at]
        root = reference.root_from_gradients(
            {"tree_info": [tree]},
            run.gen.column(tree["tree_structure"]["split_feature"], 0, n),
            g_lane, h_lane, lambda_l2=float(run.params.get("lambda_l2", 0.0)))
        compared.update({
            "dart_dropped_grad_err": (
                float(max(np.abs(g_lane[at] - g).max(),
                          np.abs(h_lane[at] - h).max())), GRAD_TOL),
            "dart_root_left_count_err": (
                root["left_count_err"],
                binary.ROOT_COUNT_TOL if n > 1 << 24 else 0.0),
            "dart_root_gain_rel_err": (root["gain_rel_err"], ROOT_GAIN_RTOL),
            "score_walk_err_after": walk_err(after, score),
        })
        detail.update(dropped=dropped, root=root, said=[
            r for r in trace.seams("dart.drop") if r["iter"] == drop_at])
    else:   # no window of the cell's lengths gets here
        compared["dart_dropped_grad_err"] = (float("inf"), GRAD_TOL)
    # these iterations are the check's, not the window's
    trace.forget_seams_since(t)
    return compared, detail


def first_tree(run) -> tuple:
    """({name: (number, limit)}, detail): see the module's docstring."""
    compared, root = binary.first_tree(run)
    more, detail = dropping_tree(run)
    compared.update(more)
    return compared, dict(root, dropping_tree=detail)
