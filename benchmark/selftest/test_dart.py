"""Task `binary_dart`, the plain reference for DART and the readers of
the layer "tree walk", at toy size on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/selftest/test_dart.py -q

A toy run of `criteo67-255-dart.train-dropping` through `run_cell` that
is correct; the four faults of `control_dart_on_chip.py`, each of which
has to come out not correct by the number it names; the refusal of a
program whose engine cannot walk trees; the schedule the traffic mix
writes down; the readers on made-up seams and events.
"""
import importlib
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from benchmark import reference_dart, run  # noqa: E402
from benchmark.layer_metrics import (_dart, _seams,  # noqa: E402
                                     dart_dropped_trees_per_iter,
                                     dart_walk_hbm_roofline_pct,
                                     dart_walk_ms_per_dropped_tree,
                                     dart_walk_ms_per_iter)
from benchmark.selftest import control_dart_on_chip as control  # noqa: E402
from benchmark.tasks import binary_dart  # noqa: E402

CELL = control.CELL
GEN = {"count_columns": 3, "continuous_columns": 7, "block_rows": 1024,
       "structure_seed": 67, "margin_terms": 6, "margin_bias": -0.5}
# `tpu_force_big_n`: the standard record, whose gradient lanes the task
# reads; the toy's rows would otherwise take the compact one
TOY = {"config": {"rows": 3000, "holdout_rows": 600, "quality_floor": 0.55,
                  "generator_params": GEN},
       "traffic": {"min_window_iterations": 3, "trace_iterations": 3},
       "params": {"num_leaves": 15, "tpu_grow_mode": "aligned",
                  "tpu_aligned_interpret": True, "tpu_chunk": 256,
                  "tpu_force_big_n": True}}


def test_toy_run_of_the_dropping_cell_is_correct(tmp_path):
    res = run.run_cell(CELL, 2**31 + 11, 0.0, True, overrides=TOY,
                       trace_dir=str(tmp_path))
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] == 3 and res["failed"] == 0
    det = res["detail"]["first_tree"]["dropping_tree"]
    # iterations 17-19 (0-based 16-18), then on to 0-based 19, which
    # drops three trees
    assert det["trained"] == 19 and det["dropping_iteration"] == 19
    assert len(det["dropped"]) == 3
    assert [r["dropped"] for r in det["said"]] == [det["dropped"]]
    for name in ("dart_drop_set_mismatch_iters", "dart_tree_weight_err",
                 "score_walk_err", "dart_dropped_grad_err",
                 "dart_root_left_count_err", "dart_root_gain_rel_err",
                 "score_walk_err_after", "root_left_count_err"):
        assert name in res["compared"]
    assert res["compared"]["dart_drop_set_mismatch_iters"]["value"] == 0
    assert res["compared"]["dart_root_left_count_err"]["value"] == 0
    # the traced line carries the counter's reader on the CPU too; the
    # device's events exist only on the chip
    assert res["metrics"]["dart_dropped_trees_per_iter"]["value"] \
        == pytest.approx(5 / 3)
    assert res["detail"]["in_window"] == {
        "traces": 0, "cache_hits": 0, "cache_misses": 0}
    recs = _seams.ring()
    win = _seams.window(recs, 3)
    # the window's own iterations, not the check's
    assert [r["iter"] for r in win["iters"]] == [16, 17, 18]
    assert [r["dart_dropped"] for r in win["iters"]] == [2, 3, 0]
    assert {r["name"] for r in recs
            if r["t0"] >= win["t0"] and r["t1"] <= win["t1"]} == {
        "aligned.dispatch", "dart.drop", "train.flag_pull", "train.drain",
        "aligned.iter"}


@pytest.mark.parametrize("n, fault", enumerate(sorted(control.FAULTS)))
def test_a_planted_fault_is_not_correct_by_the_number_it_names(
        monkeypatch, n, fault):
    named = control.FAULTS[fault](monkeypatch.setattr)
    res = run.run_cell(CELL, 2**31 + 20 + n, 0.0, False, overrides=TOY)
    assert res["correct"] is False
    assert named in control.failing(res["compared"]), res["compared"]
    assert res["compared"]["dart_drop_set_mismatch_iters"]["value"] == 0
    assert res["compared"]["dart_tree_weight_err"]["value"] < 1e-12


def test_a_program_whose_engine_cannot_walk_trees_is_refused(monkeypatch):
    from lightgbm_tpu.models.aligned_builder import AlignedEngine
    monkeypatch.delattr(AlignedEngine, "walk_trees")
    with pytest.raises(SystemExit, match="cannot run boosting=dart"):
        importlib.reload(binary_dart)
    monkeypatch.undo()
    importlib.reload(binary_dart)


def test_the_schedule_the_cell_writes_down():
    """The traffic mix's `why_these` and PERF.md give the drop schedule
    from iteration 17 on; it is the reference's at the configuration's
    parameters."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "criteo67-255-dart.json")) as f:
        params = json.load(f)["params"]
    sched = binary_dart.schedule_of(params, 37)
    assert [len(s["dropped"]) for s in sched[16:]] == [
        2, 3, 0, 3, 0, 1, 0, 0, 0, 0, 0, 0, 1, 3, 5, 0, 0, 2, 4, 3, 4]
    assert not sched[0]["dropped"] and sched[0]["shrinkage"] == 0.1
    assert reference_dart.dropped_per_iteration(sched, 16, 14) \
        == pytest.approx(13 / 14)
    assert reference_dart.dropped_per_iteration(sched, 16, 3) \
        == pytest.approx(5 / 3)
    # how many updates past a window of N the task makes to reach a
    # dropping iteration: never more than MAX_EXTRA
    for n in range(5, 21):
        ahead = next(i for i, s in enumerate(sched[16 + n:]) if s["dropped"])
        assert ahead + 1 <= binary_dart.MAX_EXTRA


def made_up(monkeypatch, dropping: bool):
    extra = [dict(dart_dropped=k, walk_passes=2 * (k > 0),
                  rows_walked=2 * k * 1000) for k in (2, 0)] \
        if dropping else [{}, {}]
    iters = [dict(name="aligned.iter", iter=i, rounds=1, columns=[],
                  table=[], **e) for i, e in zip((16, 17), extra)]
    ring = [dict(name="aligned.pack", id=1, parent=None, rows=1000,
                 w_used=23, t0=0., t1=1.),
            dict(name="aligned.dispatch", id=2, parent=None, iter=16,
                 t0=10., t1=10.1)]
    ring += iters + [dict(name="train.drain", id=9, parent=None, iter=18,
                          t0=11., t1=12.)]
    for i, r in enumerate(ring):
        r.setdefault("id", 100 + i)
        r.setdefault("parent", None)
        r.setdefault("t0", 10.5)
        r.setdefault("t1", 10.5)
    monkeypatch.setattr(_seams, "ring", lambda: ring)
    monkeypatch.setattr(_seams, "hbm_bytes_per_s", lambda: 1e9)
    ms = 10**6
    events = [("walk_pass", 0, 3 * ms)] * dropping + [
        ("slot_hist_pass", 3 * ms, 4 * ms), ("move_pass", 4 * ms, 5 * ms)
    ] + [("walk_pass", 5 * ms, 6 * ms)] * dropping + [
        ("slot_hist_pass", 6 * ms, 7 * ms)]
    return {"iterations": 2, "trace": {
        "ops": {"/device:TPU:0": events},
        "kernels": {"slot_hist_pass", "move_pass", "walk_pass"}}}


def test_readers_on_made_up_seams_and_events(monkeypatch):
    ctx = made_up(monkeypatch, dropping=True)
    assert dart_walk_ms_per_iter.read(ctx) == pytest.approx(2.0)
    assert dart_dropped_trees_per_iter.read(ctx) == pytest.approx(1.0)
    assert dart_walk_ms_per_dropped_tree.read(ctx) == pytest.approx(2.0)
    # one dropping iteration: 1000 rows x 4 bytes x (23 + 2) lanes at
    # 1 GB/s is 0.1 ms of the kernel's 4
    assert _dart.least_bytes(1000, 23, 1) == 100_000
    assert dart_walk_hbm_roofline_pct.read(ctx) == pytest.approx(2.5)
    # a run that drops nothing, and a program without kernel or counters
    plain = made_up(monkeypatch, dropping=False)
    readers = (dart_walk_ms_per_iter, dart_dropped_trees_per_iter,
               dart_walk_ms_per_dropped_tree, dart_walk_hbm_roofline_pct)
    assert [r.read(plain) for r in readers] == [None] * 4
    monkeypatch.setattr(_seams, "ring", lambda: [])
    assert [r.read(ctx) for r in readers[1:]] == [None] * 3
