"""Task `binary_bagged` and the plain reference for row and column
sampling, at toy size on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/selftest/test_bagging.py -q

A toy run of `criteo67-255-bagged.train-rebagging` through `run_cell` that
is correct; the five faults of `control_bagging_on_chip.py`, each of which
has to come out not correct by the number it names, and its host-drawn
path likewise; the refusal of a program whose engine cannot draw a bag;
the reference on a hand-worked case; the three readers on made-up seams
and events.
"""
import importlib
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmark import reference_bagging, run  # noqa: E402
from benchmark.layer_metrics import (_seams, bag_draw_ms_per_iter,  # noqa: E402
                                     bag_kept_row_pct, bag_redraws_per_iter)
from benchmark.selftest import control_bagging_on_chip as control  # noqa: E402
from benchmark.tasks import binary_bagged  # noqa: E402

CELL = control.CELL
GEN = {"count_columns": 3, "continuous_columns": 7, "block_rows": 1024,
       "structure_seed": 67, "margin_terms": 6, "margin_bias": -0.5}
TOY = {"config": {"rows": 3000, "holdout_rows": 600, "quality_floor": 0.55,
                  "generator_params": GEN},
       "traffic": {"min_window_iterations": 3, "trace_iterations": 3},
       "params": {"num_leaves": 15, "tpu_grow_mode": "aligned",
                  "tpu_aligned_interpret": True, "tpu_chunk": 256}}


def test_toy_run_of_the_rebagging_cell_is_correct(tmp_path):
    res = run.run_cell(CELL, 2**31 + 31, 0.0, True, overrides=TOY,
                       trace_dir=str(tmp_path))
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] == 3 and res["failed"] == 0
    det = res["detail"]["first_tree"]
    assert det["trained"] == 7 and det["bag_rows"] == 2400
    assert det["rebags_said"] == det["rebags_reference"]
    assert [it for it, _ in det["rebags_said"]] == [0, 5]
    assert det["bag_kept_counters"] == [2400]
    assert det["features_a_tree"] == 8
    assert det["out_of_bag_rows_walked"] == 600
    assert det["rebag"]["iteration"] == 10
    assert det["rebag"]["seed"] == det["rebag"]["seed_reference"]
    assert det["rebag"]["rows_that_changed_bag"] > 0
    for name in ("bag_schedule_mismatch_iters", "bag_mismatch_rows",
                 "bag_kept_count_err", "feature_mask_violations",
                 "bagged_root_left_count_err", "bag_held_changed_rows",
                 "bag_mismatch_rows_rebag", "bag_kept_count_err_rebag",
                 "rebag_root_left_count_err"):
        assert res["compared"][name]["value"] == 0, name
    for name in ("bagged_root_gain_rel_err", "rebag_root_gain_rel_err",
                 "score_walk_err", "holdout_auc_6"):
        assert name in res["compared"]
    # the traced line carries the counters' readers on the CPU too; the
    # device's events exist only on the chip
    assert res["metrics"]["bag_kept_row_pct"]["value"] == 80.0
    assert res["metrics"]["bag_redraws_per_iter"]["value"] \
        == pytest.approx(1 / 3)
    assert "bag_draw_ms_per_iter" not in res["metrics"]
    assert res["detail"]["in_window"] == {
        "traces": 0, "cache_hits": 0, "cache_misses": 0}
    # the window holds no host seam but the enqueues and the two waits: no
    # N-row array is pulled or uploaded by an iteration
    recs = _seams.ring()
    win = _seams.window(recs, 3)
    # the window's own iterations, not the check's four more
    assert [r["iter"] for r in win["iters"]] == [4, 5, 6]
    inside = [r for r in recs
              if r["t0"] >= win["t0"] and r["t1"] <= win["t1"]]
    assert {r["name"] for r in inside} == {
        "aligned.dispatch", "bag.draw", "train.flag_pull", "train.drain",
        "aligned.iter"}
    assert [r["iter"] for r in inside if r["name"] == "bag.draw"] == [5]


@pytest.mark.parametrize("n, fault", enumerate(
    [*sorted(control.FAULTS), "host_drawn"]))
def test_a_planted_fault_is_not_correct_by_the_number_it_names(
        monkeypatch, n, fault):
    named = dict(control.FAULTS, host_drawn=control.host_drawn)[fault](
        monkeypatch.setattr)
    # a seed of its own: the programs are registered by the data's hash,
    # and a run on another run's data would find that run's programs
    res = run.run_cell(CELL, 2**31 + 40 + n, 0.0, False, overrides=TOY)
    assert res["correct"] is False
    assert named in control.failing(res["compared"]), res["compared"]


def test_a_program_whose_engine_cannot_draw_a_bag_is_refused(monkeypatch):
    from lightgbm_tpu.models.aligned_builder import AlignedEngine
    monkeypatch.delattr(AlignedEngine, "bag_select")
    with pytest.raises(SystemExit, match="cannot draw a bag"):
        importlib.reload(binary_bagged)
    monkeypatch.undo()
    importlib.reload(binary_bagged)


def test_reference_on_a_hand_worked_case():
    keys = reference_bagging.key(np.arange(10), 7)
    assert len(set(keys.tolist())) == 10 and keys.max() < 2**32
    mask = reference_bagging.bag_mask(10, 7, 4)
    assert mask.sum() == 4
    assert sorted(np.flatnonzero(mask)) == sorted(np.argsort(keys)[:4])
    assert reference_bagging.bag_count(48_000_000, 0.8) == 38_400_000
    assert reference_bagging.bag_count(3000, 0.8) == 2400
    # one draw of the stream a re-bag, at every multiple of the frequency
    rng = np.random.RandomState(3)
    want = [int(rng.randint(0, 2**31 - 1)) for _ in range(4)]
    assert reference_bagging.bag_schedule(3, 5, 16) == list(
        zip((0, 5, 10, 15), want))
    assert reference_bagging.bag_schedule(3, 5, 15) == list(
        zip((0, 5, 10), want))
    masks = reference_bagging.feature_masks(2, 67, 0.8, 6)
    assert masks.shape == (6, 67) and set(masks.sum(axis=1)) == {54}
    assert len({m.tobytes() for m in masks}) == 6
    assert reference_bagging.feature_masks(2, 67, 1.0, 3).all()
    tree = {"tree_structure": {
        "split_feature": 3, "left_child": {"leaf_value": 0.1},
        "right_child": {"split_feature": 5,
                        "left_child": {"leaf_value": 0.2},
                        "right_child": {"leaf_value": 0.3}}}}
    assert sorted(reference_bagging.split_features(tree)) == [3, 5]
    # a key is a bijection of the row ids, whatever the seed
    for seed in (0, 7, 2**31 - 2):
        assert len(np.unique(reference_bagging.key(
            np.arange(1 << 16), seed))) == 1 << 16


def made_up(monkeypatch, drawn_at=(5,)):
    ring = [dict(name="aligned.pack", rows=1000, t0=0., t1=1.),
            dict(name="aligned.dispatch", iter=4, t0=10., t1=10.1)]
    ring += [dict(name="bag.draw", iter=i, seed=5, t0=10.2, t1=10.3)
             for i in (0, *drawn_at)]
    ring += [dict(name="aligned.iter", iter=i, rounds=1, columns=[],
                  table=[], bag_kept=800, features_used=54)
             for i in (3, 4, 5, 6)]
    ring.append(dict(name="train.drain", iter=7, t0=11., t1=12.))
    for i, r in enumerate(ring):
        r.setdefault("id", 100 + i)
        r.setdefault("parent", None)
        r.setdefault("t0", 10.5)
        r.setdefault("t1", 10.5)
    monkeypatch.setattr(_seams, "ring", lambda: ring)
    ms = 10**6
    events = [("fusion.1", 0, 1 * ms),              # head of tree 4 only
              ("slot_hist_pass", 1 * ms, 2 * ms),
              ("fusion.2", 2 * ms, 3 * ms),         # inside the tree
              ("move_pass", 3 * ms, 4 * ms),
              ("fusion.3", 4 * ms, 6 * ms),         # tail + head
              ("fusion.9", 6 * ms, 13 * ms),        # the draw of bag 5
              ("slot_hist_pass", 13 * ms, 14 * ms),
              ("move_pass", 14 * ms, 15 * ms),
              ("copy.7", 15 * ms, 20 * ms),         # tree 5's copy back
              ("fusion.3", 20 * ms, 22 * ms),       # tail + head
              ("slot_hist_pass", 22 * ms, 23 * ms),
              ("move_pass", 23 * ms, 24 * ms),
              ("fusion.4", 24 * ms, 30 * ms)]       # after the last tree
    return {"iterations": 3, "trace": {
        "ops": {"/device:TPU:0": events},
        "kernels": {"slot_hist_pass", "count_pass", "move_pass"}}}


def test_readers_on_made_up_seams_and_events(monkeypatch):
    ctx = made_up(monkeypatch)
    assert bag_kept_row_pct.read(ctx) == pytest.approx(80.0)
    assert bag_redraws_per_iter.read(ctx) == pytest.approx(1 / 3)
    # gaps 1, 9 and 2 ms, the copy back of an odd round count set aside;
    # the held one that has its tail is 2
    assert bag_draw_ms_per_iter.read(ctx) == pytest.approx((9 - 2) / 3)
    # every iteration of the window drew: nothing to take the draw from
    assert bag_draw_ms_per_iter.read(
        made_up(monkeypatch, drawn_at=(4, 5, 6))) is None
    # no draw in the window: no redraws, and no milliseconds of them
    quiet = made_up(monkeypatch, drawn_at=())
    assert bag_redraws_per_iter.read(quiet) == 0
    assert bag_draw_ms_per_iter.read(quiet) is None
    # a program without the seams: nothing, and no reader raises
    monkeypatch.setattr(_seams, "ring", lambda: [])
    for reader in (bag_kept_row_pct, bag_redraws_per_iter,
                   bag_draw_ms_per_iter):
        assert reader.read(ctx) is None
