"""Task `binary_goss` and the plain reference for GOSS, at toy size on
the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/selftest/test_goss.py -q

A toy run of `criteo67-255-goss.train-sampled` through `run_cell` that is
correct; the four faults of `control_goss_on_chip.py`, each of which has
to come out not correct by the number it names; the refusal of a program
whose engine cannot run GOSS; the reference on a hand-worked case; the two
readers on made-up seams and events.
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

from benchmark import reference_goss, run  # noqa: E402
from benchmark.layer_metrics import (_seams, goss_kept_row_pct,  # noqa: E402
                                     goss_select_ms_per_iter)
from benchmark.selftest import control_goss_on_chip as control  # noqa: E402
from benchmark.tasks import binary_goss  # noqa: E402

CELL = control.CELL
GEN = {"count_columns": 3, "continuous_columns": 7, "block_rows": 1024,
       "structure_seed": 67, "margin_terms": 6, "margin_bias": -0.5}
TOY = {"config": {"rows": 3000, "holdout_rows": 600, "auc_floor": 0.55,
                  "generator_params": GEN},
       "traffic": {"min_window_iterations": 3, "trace_iterations": 3},
       "params": {"num_leaves": 15, "tpu_grow_mode": "aligned",
                  "tpu_aligned_interpret": True, "tpu_chunk": 256}}


@pytest.fixture(autouse=True)
def toy_floor(monkeypatch):
    """The toy rows are easier to fit late than early: the cell's floor
    of the first 15 trees' AUC is the cell's, not the toy's."""
    monkeypatch.setattr(binary_goss, "AUC_15_FLOOR", 0.55)
    monkeypatch.setattr(binary_goss, "HOLDOUT_ROWS", 600)


def test_toy_run_of_the_sampled_cell_is_correct(tmp_path):
    res = run.run_cell(CELL, 2**31 + 11, 0.0, True, overrides=TOY,
                       trace_dir=str(tmp_path))
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] == 3 and res["failed"] == 0
    det = res["detail"]["first_tree"]["sampled_tree"]
    assert det["iteration"] == 15 and det["top_k"] == 600
    assert det["kept_other"] == det["other_k"] == 300
    assert det["kept_top"] == det["kept_top_reference"] >= 600
    assert det["multiplier"] == det["multiplier_reference"] == 8.0
    for name in ("goss_multiplier_mismatch_rows", "goss_multiplier_value_err",
                 "sampled_root_left_count_err", "sampled_root_gain_rel_err",
                 "holdout_auc_15_shortfall", "root_left_count_err"):
        assert name in res["compared"]
    assert res["compared"]["sampled_root_left_count_err"]["value"] == 0
    # the traced line carries the counter's reader on the CPU too; the
    # device's events exist only on the chip
    kept = res["metrics"]["goss_kept_row_pct"]["value"]
    assert 30.0 <= kept < 45.0
    assert res["detail"]["in_window"] == {
        "traces": 0, "cache_hits": 0, "cache_misses": 0}
    # the window holds no host seam but the two enqueues and the two
    # waits: no N-row array is pulled or uploaded by an iteration
    recs = _seams.ring()
    win = _seams.window(recs, 3)
    # the window's own iterations, not the check's one more
    assert [r["iter"] for r in win["iters"]] == [12, 13, 14]
    assert {r["name"] for r in recs
            if r["t0"] >= win["t0"] and r["t1"] <= win["t1"]} == {
        "aligned.dispatch", "goss.select", "train.flag_pull", "train.drain",
        "aligned.iter"}


@pytest.mark.parametrize("n, fault", enumerate(sorted(control.FAULTS)))
def test_a_planted_fault_is_not_correct_by_the_number_it_names(
        monkeypatch, n, fault):
    named = control.FAULTS[fault](monkeypatch.setattr)
    # a seed of its own: the programs are registered by the data's hash,
    # and a run on another run's data would find that run's selection
    res = run.run_cell(CELL, 2**31 + 20 + n, 0.0, False, overrides=TOY)
    assert res["correct"] is False
    assert named in control.failing(res["compared"]), res["compared"]


def test_a_program_whose_engine_cannot_run_goss_is_refused(monkeypatch):
    from lightgbm_tpu.models.aligned_builder import AlignedEngine
    monkeypatch.delattr(AlignedEngine, "goss_select")
    with pytest.raises(SystemExit, match="cannot run boosting=goss"):
        importlib.reload(binary_goss)
    monkeypatch.undo()
    importlib.reload(binary_goss)


def test_reference_on_a_hand_worked_case():
    # a = |g x h|: 10 rows, top_rate 0.2 -> top_k 2, other_rate 0.3 -> 3
    g = np.array([.9, .1, .5, .5, .2, .3, .05, .5, .4, .6])
    ref = reference_goss.goss_multipliers(g, np.ones(10), np.arange(10), 7,
                                          0.2, 0.3)
    assert ref["top_k"] == 2 and ref["other_k"] == 3
    assert ref["threshold"] == 0.6 and ref["kept_top"] == 2
    m = ref["multiplier"]
    assert m[0] == m[9] == 1.0
    rest = np.setdiff1d(np.arange(10), [0, 9])
    keys = reference_goss.key(rest, 7)
    want = rest[np.argsort(keys)[:3]]
    assert sorted(np.flatnonzero(m == 8 / 3)) == sorted(want)
    assert (m > 0).sum() == 5
    # ties at the threshold are all kept: three rows of 0.5 with top_k 4
    tied = reference_goss.goss_multipliers(g, np.ones(10), np.arange(10), 7,
                                           0.4, 0.1)
    assert tied["threshold"] == 0.5 and tied["kept_top"] == 5
    # a key is a bijection of the row ids, whatever the seed
    for seed in (0, 7, 2**31 - 2):
        assert len(np.unique(reference_goss.key(np.arange(1 << 16), seed))) \
            == 1 << 16


def made_up(monkeypatch, sampled: bool):
    iters = [dict(name="aligned.iter", iter=i, rounds=1, columns=[], table=[],
                  **({"goss_kept_top": 210, "goss_kept_other": 100}
                     if sampled else {})) for i in (12, 13)]
    ring = [dict(name="aligned.pack", id=1, parent=None, rows=1000, t0=0.,
                 t1=1.),
            dict(name="aligned.dispatch", id=2, parent=None, iter=12, t0=10.,
                 t1=10.1)]
    if sampled:
        ring.append(dict(name="goss.select", id=3, parent=None, iter=12,
                         t0=10.0, t1=10.05, seed=5))
    ring += iters + [dict(name="train.drain", id=9, parent=None, iter=14,
                          t0=11., t1=12.)]
    for i, r in enumerate(ring):
        r.setdefault("id", 100 + i)
        r.setdefault("parent", None)
        r.setdefault("t0", 10.5)
        r.setdefault("t1", 10.5)
    monkeypatch.setattr(_seams, "ring", lambda: ring)
    ms = 10**6
    events = [("fusion.1", 0, 2 * ms),              # head of tree 1: counts
              ("slot_hist_pass", 2 * ms, 3 * ms),
              ("fusion.2", 3 * ms, 4 * ms),         # inside the tree
              ("count_pass", 4 * ms, 5 * ms),
              ("move_pass", 5 * ms, 6 * ms),
              ("copy.3", 6 * ms, 9 * ms),           # tail + selection: counts
              ("slot_hist_pass", 9 * ms, 10 * ms),
              ("move_pass", 10 * ms, 11 * ms),
              ("fusion.4", 11 * ms, 15 * ms)]       # after the last tree
    return {"iterations": 2, "trace": {
        "ops": {"/device:TPU:0": events},
        "kernels": {"slot_hist_pass", "count_pass", "move_pass"}}}


def test_readers_on_made_up_seams_and_events(monkeypatch):
    ctx = made_up(monkeypatch, sampled=True)
    assert goss_kept_row_pct.read(ctx) == pytest.approx(31.0)
    assert goss_select_ms_per_iter.read(ctx) == pytest.approx((2 + 3) / 2)
    # a run that does not sample, and a program without the seams: nothing
    plain = made_up(monkeypatch, sampled=False)
    assert goss_kept_row_pct.read(plain) is None
    assert goss_select_ms_per_iter.read(plain) is None
    monkeypatch.setattr(_seams, "ring", lambda: [])
    assert goss_kept_row_pct.read(plain) is None
    assert goss_select_ms_per_iter.read(plain) is None
