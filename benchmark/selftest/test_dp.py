"""Task `binary_dp` and the data-parallel readers, at toy size on four
virtual CPU devices:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/selftest/test_dp.py -q

A toy run of `criteo67-255-dp4.train` through `run_cell` that is correct;
the three faults of `control_dp_on_chip.py`, each of which has to come
out not correct by the number it names; the refusal of a program whose
engine does not count what the checks read; the readers on made-up seams
and phases, and their silence where the program has neither.
"""
import importlib
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               "--xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from benchmark import reference_dp, run  # noqa: E402
from benchmark.layer_metrics import _dp, _phases, _seams  # noqa: E402
from benchmark.selftest import control_dp_on_chip as control  # noqa: E402
from benchmark.tasks import binary_dp  # noqa: E402

CELL = control.CELL
GEN = {"count_columns": 3, "continuous_columns": 7, "block_rows": 1024,
       "structure_seed": 67, "margin_terms": 6, "margin_bias": -0.5}
TOY = {"config": {"rows": 4000, "holdout_rows": 600, "auc_floor": 0.55,
                  "generator_params": GEN},
       "traffic": {"min_window_iterations": 3, "trace_iterations": 3},
       "params": {"num_leaves": 15, "tpu_grow_mode": "aligned",
                  "tpu_aligned_interpret": True, "tpu_chunk": 256,
                  "tpu_level_spec": 1.5}}
NEW = ("dp_psum_ms_per_iter", "dp_psum_ici_roofline_pct",
       "dp_busy_spread_pct")


@pytest.fixture(autouse=True)
def fresh_programs():
    """A planted fault lives in a program's trace: no program of another
    run may be handed out in its place."""
    from lightgbm_tpu import compile_cache
    from lightgbm_tpu.obs import phases
    compile_cache.clear_programs()
    phases.forget()
    yield
    compile_cache.clear_programs()
    phases.forget()


def test_toy_run_of_the_four_chip_cell_is_correct():
    res = run.run_cell(CELL, 2**31 + 61, 0.0, False, overrides=TOY)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] == 3 and res["failed"] == 0
    det = res["detail"]["first_tree"]
    assert det["shards"] == 4 and det["rows_by_shard"] == [1000] * 4
    assert [hi - lo for lo, hi in det["walk_stretches"]] == [1000] * 4
    assert det["walk_stretches"][-1][1] == 4000
    assert res["compared"]["shard_rows_err"]["value"] == 0.0
    assert res["compared"]["shard_score_walk_err"]["value"] < 1e-6
    assert res["compared"]["root_left_count_err"]["value"] == 0.0


@pytest.mark.parametrize("n, fault", enumerate(sorted(control.FAULTS)))
def test_a_planted_fault_is_not_correct_by_the_number_it_names(
        monkeypatch, n, fault):
    named = control.FAULTS[fault](monkeypatch.setattr)
    res = run.run_cell(CELL, 2**31 + 70 + n, 0.0, False, overrides=TOY)
    assert res["correct"] is False
    assert named in control.failing(res["compared"]), res["compared"]


def test_a_program_whose_engine_counts_neither_is_refused(monkeypatch):
    from lightgbm_tpu.models.aligned_builder import AlignedEngine
    monkeypatch.delattr(AlignedEngine, "psum_bytes")
    with pytest.raises(SystemExit, match="no psum_bytes"):
        importlib.reload(binary_dp)
    monkeypatch.undo()
    importlib.reload(binary_dp)


def test_shard_rows_err_reads_a_short_shard_and_a_wrong_count():
    assert binary_dp.shard_rows_err([25, 25, 25, 25], 100, 4) == 0.0
    assert binary_dp.shard_rows_err([25, 20, 25, 25], 100, 4) == 5.0
    assert binary_dp.shard_rows_err([34, 33, 33], 100, 4) == 100.0
    assert reference_dp.shard_bounds(10, 4) == [(0, 3), (3, 6), (6, 9),
                                                (9, 10)]


def _ring(psum=(1000, 2000, 3000), shards=4):
    recs = [{"name": "aligned.pack", "rows": 96000000, "shards": shards,
             "t0": 0.0, "t1": 1.0, "id": 1, "parent": None}]
    for i, p in enumerate(psum):
        recs += [{"name": "aligned.dispatch", "iter": 10 + i, "t0": 2.0 + i,
                  "t1": 2.1 + i, "id": 10 + i, "parent": None},
                 {"name": "aligned.iter", "iter": 10 + i, "rounds": 1,
                  "columns": [], "table": [], "psum_bytes": p,
                  "t0": 2.5 + i, "t1": 2.5 + i, "id": 20 + i,
                  "parent": None}]
    recs.append({"name": "train.drain", "t0": 6.0, "t1": 7.0, "id": 30,
                 "parent": None})
    return recs


def _window():
    """Two chips; on each, an asynchronous all-reduce (start 1 ms, done
    1 ms, 3 ms of compute between them), a synchronous one of 2 ms and a
    4 ms fusion of another phase, and a lagging second chip."""
    def chip(lag):
        ms = 1e6
        return [(("all-reduce-start.1", "f32[]", "op", "dp.psum"),
                 lag, lag + 1 * ms),
                (("fusion.3", "f32[]", "op", "build.eval"),
                 lag + 1 * ms, lag + 4 * ms),
                (("all-reduce-done.1", "f32[]", "op", "dp.psum"),
                 lag + 4 * ms, lag + 5 * ms),
                (("all-reduce.2", "f32[]", "op", "dp.psum"),
                 lag + 6 * ms, lag + 8 * ms)]
    return {"devices": {"/device:TPU:0": chip(0), "/device:TPU:1": chip(2e6)},
            "seams": [], "t0": 0, "t1": 1e8, "table_s": 0.0,
            "table_rows": 4, "phases": {"dp.psum", "build.eval"},
            "programs": ["build"]}


def test_the_readers_on_made_up_seams_and_phases(monkeypatch):
    monkeypatch.setattr(_seams, "ring", _ring)
    monkeypatch.setattr(_dp, "ici_bytes_per_s", lambda: 200e9)
    monkeypatch.setattr(_phases, "window", lambda ctx: _window())
    ctx = {"iterations": 3, "walls": {}, "compiles": {},
           "trace": {"ops": {"a": [("x", 0, 100), ("y", 50, 150)],
                             "b": [("x", 0, 50)]}}}
    read = {name: importlib.import_module(
        "benchmark.layer_metrics." + name).read for name in NEW}
    # events' own time: (1 + 1 + 2) ms a chip over 3 iterations
    assert read["dp_psum_ms_per_iter"](ctx) == pytest.approx(4.0 / 3)
    assert _dp.psum_bytes(ctx) == (6000, 4)
    assert _dp.ring_bytes(6000, 4) == 9000
    # a chip's span: 5 ms from start to done, and 2 ms
    assert _dp.psum_span_s(ctx) == pytest.approx(7e-3)
    assert read["dp_psum_ici_roofline_pct"](ctx) == pytest.approx(
        100 * 9000 / 200e9 / 7e-3)
    # busy 150 and 50 ns: (150 - 50) / 100
    assert read["dp_busy_spread_pct"](ctx) == pytest.approx(100.0)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_phase_or_counter_gives_nothing(
        monkeypatch, name):
    """The parent of the PR that added them: a phase table without
    `dp.psum`, records without `psum_bytes`, one chip."""
    win = _window()
    win["phases"] = {"build.eval"}
    monkeypatch.setattr(_phases, "window", lambda ctx: win)
    monkeypatch.setattr(_seams, "ring", lambda: [
        {k: v for k, v in r.items() if k != "psum_bytes"} for r in _ring()])
    monkeypatch.setattr(_dp, "ici_bytes_per_s", lambda: 200e9)
    ctx = {"iterations": 3, "walls": {}, "compiles": {},
           "trace": {"ops": {"a": [("x", 0, 100)]}}}
    reader = importlib.import_module("benchmark.layer_metrics." + name)
    assert reader.read(ctx) is None
    monkeypatch.setattr(_phases, "window", lambda ctx: None)
    monkeypatch.setattr(_seams, "ring", lambda: [])
    assert reader.read(dict(ctx, trace={})) is None
