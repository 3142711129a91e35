"""Task `binary_valid` and the readers of the validation set, at toy size
on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/selftest/test_valid.py -q

A toy run of `criteo67-255.train-valid` through `run_cell` that is
correct; the three faults of `control_valid_on_chip.py`, each of which
has to come out not correct by the number it names; the refusal of a
program whose engine cannot pack a validation set; the readers on
made-up seams and phases.
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

from benchmark import run  # noqa: E402
from benchmark.layer_metrics import (_phases, _seams,  # noqa: E402
                                     valid_metric_ms_per_iter,
                                     valid_walk_hbm_roofline_pct,
                                     valid_walk_ms_per_iter)
from benchmark.selftest import control_valid_on_chip as control  # noqa: E402
from benchmark.tasks import binary_valid  # noqa: E402

CELL = control.CELL
GEN = {"count_columns": 3, "continuous_columns": 7, "block_rows": 1024,
       "structure_seed": 67, "margin_terms": 6, "margin_bias": -0.5}
# 1,300 validation rows: five whole chunks of 256 and a last one of 20,
# which start in the holdout's block and end in the next
TOY = {"config": {"rows": 3000, "holdout_rows": 600, "auc_floor": 0.55,
                  "generator_params": GEN},
       "traffic": {"valid_rows": 1300, "min_window_iterations": 3,
                   "trace_iterations": 3},
       "params": {"num_leaves": 15, "tpu_grow_mode": "aligned",
                  "tpu_aligned_interpret": True, "tpu_chunk": 256,
                  "tpu_force_big_n": True}}
NEW = ("valid_walk_ms_per_iter", "valid_metric_ms_per_iter",
       "valid_walk_hbm_roofline_pct")


def test_toy_run_of_the_watched_cell_is_correct(tmp_path):
    from lightgbm_tpu.obs import trace
    res = run.run_cell(CELL, 2**31 + 41, 0.0, True, overrides=TOY,
                       trace_dir=str(tmp_path))
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] == 3 and res["failed"] == 0
    det = res["detail"]["first_tree"]
    assert (det["valid_rows"], det["valid_first_row"]) == (1300, 3600)
    assert res["compared"]["valid_logloss_err"]["value"] < 1e-7
    assert res["compared"]["valid_metric_err"]["value"] < 1e-7
    assert res["detail"]["in_window"] == {
        "traces": 0, "cache_hits": 0, "cache_misses": 0}
    (pack,) = [r for r in trace.seams("valid.pack")
               if r["t0"] >= trace.seams("aligned.pack")[-1]["t0"]]
    assert (pack["walk"], pack["why"], pack["rows"], pack["chunks"]) == (
        "records", None, 1300, 6)
    # the window's records are the builds it ran: the round the drain kept
    # is recorded there, and each carries the validation walk
    win = _seams.window(_seams.ring(), 3)
    assert [r["iter"] for r in win["iters"]] == [4, 5, 6]
    assert all(r["valid_rows_walked"] == 1300 and r["valid_walk_passes"] == 1
               for r in win["iters"])


@pytest.mark.parametrize("n, fault", enumerate(
    ("newest_tree_left_out", "shrinkage_one", "out_of_row_order")))
def test_a_planted_fault_is_not_correct_by_the_number_it_names(
        monkeypatch, n, fault):
    named = control.FAULTS[fault](monkeypatch.setattr)
    res = run.run_cell(CELL, 2**31 + 50 + n, 0.0, False, overrides=TOY)
    assert res["correct"] is False
    assert named in control.failing(res["compared"]), res["compared"]
    # the training is the program's: only the validation checks see it
    assert set(control.failing(res["compared"])) <= {
        control.AUC, control.LOGLOSS}


def test_a_program_whose_engine_cannot_pack_a_set_is_refused(monkeypatch):
    from lightgbm_tpu.models.aligned_builder import AlignedEngine
    monkeypatch.delattr(AlignedEngine, "pack_rows")
    with pytest.raises(SystemExit, match="cannot pack a validation set"):
        importlib.reload(binary_valid)
    monkeypatch.undo()
    importlib.reload(binary_valid)


def test_the_reference_log_loss_is_upstreams():
    raw = np.array([-3.0, -0.5, 0.0, 0.7, 4.0])
    y = np.array([0.0, 1.0, 1.0, 0.0, 1.0])
    p = np.clip(1.0 / (1.0 + np.exp(-raw)), 1e-15, 1 - 1e-15)
    want = np.mean(-(y * np.log(p) + (1 - y) * np.log(1 - p)))
    assert binary_valid.logloss(raw, y) == pytest.approx(want, rel=1e-14)


def _ring(rows=4800000, w_used=23):
    recs = [{"name": "aligned.pack", "rows": 48000000, "w_used": w_used,
             "t0": 0.0, "t1": 1.0, "id": 1, "parent": None}]
    for i in range(3):
        recs += [{"name": "aligned.dispatch", "iter": 10 + i, "t0": 2.0 + i,
                  "t1": 2.1 + i, "id": 10 + i, "parent": None},
                 {"name": "aligned.iter", "iter": 10 + i, "rounds": 1,
                  "columns": [], "table": [], "valid_rows_walked": rows,
                  "valid_walk_passes": 1, "t0": 2.5 + i, "t1": 2.5 + i,
                  "id": 20 + i, "parent": None}]
    recs.append({"name": "train.drain", "t0": 6.0, "t1": 7.0, "id": 30,
                 "parent": None})
    return recs


def _window(ms_walk=2.0, ms_tables=0.4, ms_metric=6.0):
    """Three iterations of made-up device events, each phase's time in
    one operation an iteration."""
    evs = []
    for i in range(3):
        at = i * 1e8
        evs += [(("walk_pass", "f32[]", "kernel", "valid.walk"), at,
                 at + ms_walk * 1e6),
                (("fusion.1", "f32[8]", "xla", "valid.walk"), at + 1e7,
                 at + 1e7 + ms_tables * 1e6),
                (("sort.2", "f32[9]", "xla", "valid.metric"), at + 2e7,
                 at + 2e7 + ms_metric * 1e6),
                (("walk_pass", "f32[]", "kernel", "walk.apply"), at + 3e7,
                 at + 3e7 + 5e6)]
    return {"devices": {"/device:TPU:0": evs}, "seams": [], "t0": 0,
            "t1": 3e8, "table_s": 0.0, "table_rows": 4,
            "phases": {"valid.walk", "valid.metric", "walk.apply"},
            "programs": ["p"]}


def test_the_readers_on_made_up_seams_and_phases(monkeypatch):
    monkeypatch.setattr(_seams, "ring", _ring)
    monkeypatch.setattr(_seams, "hbm_bytes_per_s", lambda: 819e9)
    monkeypatch.setattr(_phases, "window", lambda ctx: _window())
    ctx = {"iterations": 3, "trace": {}, "walls": {}, "compiles": {}}
    assert valid_walk_ms_per_iter.read(ctx) == pytest.approx(2.4)
    assert valid_metric_ms_per_iter.read(ctx) == pytest.approx(6.0)
    least = valid_walk_hbm_roofline_pct.least_bytes(3 * 4800000, 23)
    assert least == 3 * 4800000 * 4 * 25
    assert valid_walk_hbm_roofline_pct.read(ctx) == pytest.approx(
        100 * least / 819e9 / 6e-3)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_phases_or_counters_gives_nothing(
        monkeypatch, name):
    """The parent of the PR that added them: a phase table without
    `valid.walk` / `valid.metric`, records without the counters."""
    win = _window()
    win["phases"] = {"walk.apply"}
    monkeypatch.setattr(_phases, "window", lambda ctx: win)
    monkeypatch.setattr(_seams, "ring", lambda: [
        {k: v for k, v in r.items() if not k.startswith("valid_")}
        for r in _ring()])
    monkeypatch.setattr(_seams, "hbm_bytes_per_s", lambda: 819e9)
    ctx = {"iterations": 3, "trace": {}, "walls": {}, "compiles": {}}
    reader = importlib.import_module("benchmark.layer_metrics." + name)
    assert reader.read(ctx) is None
    monkeypatch.setattr(_phases, "window", lambda ctx: None)
    monkeypatch.setattr(_seams, "ring", lambda: [])
    assert reader.read(ctx) is None
