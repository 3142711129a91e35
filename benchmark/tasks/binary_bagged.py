"""Task `binary_bagged`: task `binary` under row and column sampling
(`bagging_fraction`, `bagging_freq`, `feature_fraction`), where every tree
is grown on a bag of the rows that is drawn every `bagging_freq`
iterations and held in between, and on a sample of the features drawn
anew for every tree.

`correct` is decided on what the timed path itself produced, at the timed
size. Bags, schedule and masks depend on the seeds and on nothing of the
data, so the plain reference (`benchmark/reference_bagging.py`, whole
numbers) writes them down before they are read:

- `bag_schedule_mismatch_iters`: the re-bags the program recorded (seam
  `bag.draw`: iteration and seed) against `bag_schedule`, over the whole
  run: re-bags of either side that the other lacks, limit 0;
- `bag_mismatch_rows`, `bag_kept_count_err`: after the window the bag
  lane, read in row order (`AlignedEngine.row_bag`), against `bag_mask`
  under the seed the program says it drew last, over all rows; the
  lane's in-bag rows and every `bag_kept` counter of the window against
  `int(bagging_fraction x rows)`. Keys are whole numbers: limit 0 both.
  The first number and the schedule tie the lane to the reference;
- `feature_mask_violations`: splits of every dumped tree on a feature
  outside `feature_masks`' row for its iteration, limit 0;
- `bagged_root_left_count_err`, `bagged_root_gain_rel_err`: tree 0's root
  against the in-bag rows of its split column under the REFERENCE's bag 0
  and g = p0 - y, h = p0 (1 - p0) with p0 over ALL rows, as task
  `binary` holds an unbagged tree 0, under its limits;
- `score_walk_err`, as task `lambdarank` has it: the training scores the
  window left against the numpy walk over the dumped trees on `WALK_ROWS`
  rows in four stretches, in-bag and out-of-bag rows alike: an
  out-of-bag row trains nothing and still receives every tree's score;
- after the window: one more `update()` at a held iteration and the lane
  read again (`bag_held_changed_rows`, limit 0), and updates up to and
  including the next re-bag J (at most `bagging_freq` of them), with the
  training scores synced before J: the lane after J against `bag_mask`
  under the seed `bag_schedule` gives J, whatever the program says it
  drew (`bag_mismatch_rows_rebag`, `bag_kept_count_err_rebag`),
  and tree J's root against the in-bag rows of its column and float64
  gradients at the synced scores (`rebag_root_left_count_err`,
  `rebag_root_gain_rel_err`; `binary_goss.sampled_tree`'s way). Where the
  window ends just before a re-bag, the held iteration is the one after
  J. These updates' seam records leave the ring, where the per-layer
  readers look for the window last.

The driver lays this file over the parent's checkout, whose engine
trains this configuration on bags that the host draws, sorts and uploads
(another draw than the reference's, at pipeline depth 1): a cell that
measures the device's draw does not measure that. So a program whose
aligned engine cannot draw a bag is refused here, as the module is
imported and before a row is made.
`benchmark/selftest/control_bagging_on_chip.py host_drawn` runs such a
program through this task all the same, for the size of what it costs.
"""
import concurrent.futures
import time

import numpy as np

from benchmark import reference, reference_bagging
from benchmark.tasks import binary
from benchmark.tasks.binary_dart import train_scores
from benchmark.tasks.binary_goss import gradients
from benchmark.tasks.lambdarank import walk_stretches
from lightgbm_tpu.models.aligned_builder import AlignedEngine

if not hasattr(AlignedEngine, "bag_select"):
    raise SystemExit(
        "benchmark task binary_bagged: this program's aligned engine cannot "
        "draw a bag (lightgbm_tpu.models.aligned_builder.AlignedEngine has "
        "no bag_select); the cell would train on bags the host draws and "
        "uploads, which it does not measure")

QUALITY = binary.QUALITY
GROUPED = binary.GROUPED
quality = binary.quality

WALK_ROWS = 81920       # as task `lambdarank`'s, in WALK_BLOCKS stretches
WALK_BLOCKS = 4
SCORE_WALK_TOL = 1e-5   # f32 lane, one rounding a tree; as `lambdarank`'s
ROOT_GAIN_RTOL = 1e-3   # f32 histogram sums, as `binary`'s


def bag_lane(bst) -> np.ndarray:
    """bool[rows]: the bag lane in row order, as the device holds it."""
    return np.asarray(bst._gbdt._aligned_eng_ref.row_bag()) > 0.5


def this_run(trace, name: str) -> list:
    """The seam records of one name since this run's engine was packed:
    the ring is the process's, and the toy-size checks make several runs
    in one."""
    packed = trace.seams("aligned.pack")
    since = packed[-1]["t0"] if packed else 0.0
    return [r for r in trace.seams(name) if r["t0"] >= since]


def drawn(trace, upto: int) -> dict:
    """{iteration: seed} of the re-bags the program recorded at
    iterations under `upto`, the newest record an iteration."""
    return {r["iter"]: r["seed"] for r in this_run(trace, "bag.draw")
            if r["iter"] is not None and r["iter"] < upto}


def in_force(said: dict) -> int:
    """The seed of the newest re-bag of `said`; a program that recorded
    none is held to the reference all the same, under a seed of 0."""
    return said[max(said)] if said else 0


def root_of(tree: dict, run, kept: np.ndarray, g, h, count_tol) -> tuple:
    root = reference.root_from_gradients(
        {"tree_info": [tree]},
        run.gen.column(tree["tree_structure"]["split_feature"], 0,
                       run.rows)[kept],
        g, h, lambda_l2=float(run.params.get("lambda_l2", 0.0)))
    return root, (root["left_count_err"], count_tol), \
        (root["gain_rel_err"], ROOT_GAIN_RTOL)


def first_tree(run) -> tuple:
    """({name: (number, limit)}, detail): see the module's docstring."""
    from lightgbm_tpu.obs import trace
    bst, n, p = run.booster, run.rows, run.params
    y = np.asarray(run.labels, np.float64)
    freq, fraction = int(p["bagging_freq"]), float(p["bagging_fraction"])
    cnt = reference_bagging.bag_count(n, fraction)
    count_tol = binary.ROOT_COUNT_TOL if cnt > 1 << 24 else 0.0
    trained = len(run.model["tree_info"])
    next_rebag = -(-trained // freq) * freq         # J >= trained
    schedule = dict(reference_bagging.bag_schedule(
        int(p["bagging_seed"]), freq, next_rebag + 1))

    # ---- the window's state
    score = train_scores(bst)
    lane = bag_lane(bst)
    said = drawn(trace, trained)
    want = {it: s for it, s in schedule.items() if it < trained}
    mismatch = len(set(said.items()) ^ set(want.items()))
    seed_now = in_force(said)
    counters = [r["bag_kept"] for r in this_run(trace, "aligned.iter")
                if "bag_kept" in r]
    masks = reference_bagging.feature_masks(
        int(p["feature_fraction_seed"]), run.gen.features,
        float(p["feature_fraction"]), trained)
    outside = sum(not masks[i, f]
                  for i, tree in enumerate(run.model["tree_info"])
                  for f in reference_bagging.split_features(tree))
    p0 = y.mean()
    bag0 = reference_bagging.bag_mask(n, schedule[0], cnt)
    root0, count0, gain0 = root_of(
        run.model["tree_info"][0], run, bag0, (p0 - y)[bag0],
        np.full(int(bag0.sum()), p0 * (1.0 - p0)), count_tol)
    stretches = walk_stretches(run.gen.seed, n, run.gen.block_rows,
                               WALK_BLOCKS, WALK_ROWS)
    with concurrent.futures.ThreadPoolExecutor(WALK_BLOCKS) as pool:
        walk = np.concatenate(list(pool.map(
            lambda r: reference.raw_scores(run.model, run.gen.rows(*r)[0]),
            stretches)))
    at = np.concatenate([np.arange(lo, hi) for lo, hi in stretches])
    compared = {
        "bag_schedule_mismatch_iters": (float(mismatch), 0.0),
        "bag_mismatch_rows": (float(np.count_nonzero(
            lane != reference_bagging.bag_mask(n, seed_now, cnt))), 0.0),
        "bag_kept_count_err": (float(max(
            abs(int(k) - cnt) for k in [lane.sum(), *counters])), 0.0),
        "feature_mask_violations": (float(outside), 0.0),
        "bagged_root_left_count_err": count0,
        "bagged_root_gain_rel_err": gain0,
        "score_walk_err": (
            float(np.abs(score[at] - walk).max()),
            SCORE_WALK_TOL * max(1.0, float(np.abs(walk).max()))),
    }
    detail = {"trained": trained, "bag_rows": cnt, "seed_in_force": seed_now,
              "rebags_said": sorted(said.items()),
              "rebags_reference": sorted(want.items()),
              "bag_kept_counters": sorted(set(int(k) for k in counters)),
              "features_a_tree": int(masks[0].sum()), "root": root0,
              "out_of_bag_rows_walked": int((~lane[at]).sum()),
              "walk_stretches": stretches}

    # ---- on past the window: a held iteration, and the next re-bag
    t = time.perf_counter()
    changed, score_j = None, None
    for it in range(trained, next_rebag + 1):
        if it == next_rebag:
            score_j = train_scores(bst)
        bst.update()
        if it < next_rebag and changed is None:
            changed = int(np.count_nonzero(bag_lane(bst) != lane))
    bst.eval_train()
    lane_j = bag_lane(bst)
    seed_j = drawn(trace, next_rebag + 1).get(next_rebag, 0)
    if changed is None:     # the window ended just before a re-bag
        bst.update()
        bst.eval_train()
        changed = int(np.count_nonzero(bag_lane(bst) != lane_j))
    # these iterations are the check's, not the window's
    trace.forget_seams_since(t)
    g, h = gradients(score_j, y, float(p.get("sigmoid", 1.0)))
    tree_j = bst.dump_model()["tree_info"][next_rebag]
    root_j, count_j, gain_j = root_of(tree_j, run, lane_j, g[lane_j],
                                      h[lane_j], count_tol)
    compared.update({
        "bag_held_changed_rows": (float(changed), 0.0),
        "bag_mismatch_rows_rebag": (float(np.count_nonzero(
            lane_j != reference_bagging.bag_mask(
                n, schedule[next_rebag], cnt))), 0.0),
        "bag_kept_count_err_rebag": (float(abs(int(lane_j.sum()) - cnt)),
                                     0.0),
        "rebag_root_left_count_err": count_j,
        "rebag_root_gain_rel_err": gain_j,
    })
    detail.update(rebag={
        "iteration": next_rebag, "seed": seed_j,
        "seed_reference": schedule[next_rebag], "root": root_j,
        "rows_that_changed_bag": int(np.count_nonzero(lane_j != lane))})
    return compared, detail
