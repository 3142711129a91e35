"""The benchmark's own checks, at toy size on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/selftest -q

They live under `benchmark/` because a benchmark PR may add files only
under its own paths; nothing here calls the TPU compiler, and nothing
leans on time. The aligned kernels and the rank kernel run under the
Pallas interpreter. In order: the toy runs of every kind of cell, the
controls and planted faults that have to come out not correct, the data
files and generators, the plain reference, the trace arithmetic, and the
seam readers on made-up seams.
"""
import importlib
import json
import os
import re
import sys
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmark import reference, run, sizing, xplane  # noqa: E402
from benchmark.generators import istella  # noqa: E402
from benchmark.generators.criteo import Generator  # noqa: E402
from benchmark.layer_metrics import _seams  # noqa: E402
from benchmark.tasks import binary as binary_task  # noqa: E402
from benchmark.tasks import lambdarank as rank_task  # noqa: E402

GEN = {"count_columns": 3, "continuous_columns": 7, "block_rows": 1024,
       "structure_seed": 67, "margin_terms": 6, "margin_bias": -0.5}
TOY = {"config": {"rows": 3000, "holdout_rows": 600, "auc_floor": 0.55,
                  "generator_params": GEN},
       "traffic": {"warmup_iterations": 7, "min_window_iterations": 2,
                   "trace_iterations": 2},
       "params": {"num_leaves": 15, "tpu_grow_mode": "aligned",
                  "tpu_aligned_interpret": True}}
RANK_GEN = {"count_columns": 3, "continuous_columns": 9, "block_rows": 1024,
            "structure_seed": 220, "margin_terms": 6, "margin_bias": -0.5,
            "grade_thresholds": [0.5, 1.5, 2.2, 3.0], "longest_query": 100,
            "query_segments": [[60, 3000], [12, 600]]}
RANK_TOY = {"config": {"rows": 3000, "holdout_rows": 600, "features": 12,
                       "quality_floor": 0.15, "generator_params": RANK_GEN},
            "traffic": {"warmup_iterations": 4, "min_window_iterations": 2,
                        "trace_iterations": 2},
            "params": {"num_leaves": 15, "tpu_grow_mode": "aligned",
                       "min_sum_hessian_in_leaf": 1e-3,
                       "tpu_aligned_interpret": True, "tpu_rank_fused": "on"}}
BENCH = run.load_json("BENCHMARK.json")
BINARY, RANK = "criteo67-255.train", "istella220-255.train"
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")


def holds(compared: dict) -> dict:
    return {k: (c["value"] <= c["limit"] if c["holds"] == "<="
                else c["value"] >= c["limit"]) for k, c in compared.items()}


# ---------------------------------------------------------------- toy runs

@pytest.fixture(scope="module")
def toy_run():
    """One untraced toy run of the first cell, shared by three tests."""
    return run.run_cell(BINARY, 2**31 + 5, 0.0, False, overrides=TOY)


def test_toy_run_prints_the_contracts_keys(toy_run):
    line = {k: v for k, v in toy_run.items() if k != "detail"}
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert all(holds(line["compared"]).values())
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 2
    assert set(line["metrics"]) == {"train_ms_per_iter", "holdout_auc_6",
                                    "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    json.dumps(line)
    assert toy_run["detail"]["in_window"] == {
        "traces": 0, "cache_hits": 0, "cache_misses": 0}


def test_toy_run_matches_the_sizing_arithmetic(toy_run):
    eng, size = toy_run["detail"]["engine"], toy_run["detail"]["sizing"]
    assert (eng["chunk"], eng["lanes"], eng["chunks"]) == (
        size["chunk"], size["lanes"], size["chunks"])
    assert eng["compact"] == (size["layout"] == "compact")
    assert toy_run["detail"]["peak_over_sizing_gib"] == pytest.approx(
        toy_run["detail"]["measured_peak_gib"]
        - toy_run["detail"]["sizing_persistent_gib"])


def test_the_binary_task_gives_the_numbers_root_check_gave(toy_run):
    """`tasks/binary.py` is the harness's old `root_check` call, moved:
    the same two numbers against the same two limits, from the same
    column and labels."""
    import lightgbm_tpu as lgb
    gen = Generator(GEN, 11)
    x, y = gen.rows(0, 3000)
    bst = lgb.train({"objective": "binary", "num_leaves": 15, "verbosity": -1,
                     "min_data_in_leaf": 20}, lgb.Dataset(x, label=y),
                    num_boost_round=2)
    model = bst.dump_model()
    j = model["tree_info"][0]["tree_structure"]["split_feature"]
    want = reference.root_check(model, gen.column(j, 0, 3000), y)
    compared, detail = binary_task.first_tree(types.SimpleNamespace(
        model=model, gen=gen, rows=3000, labels=y, groups=None,
        params={}, booster=bst))
    assert detail == want
    assert compared == {"root_left_count_err": (want["left_count_err"], 0.0),
                        "root_gain_rel_err": (want["gain_rel_err"], 1e-3)}
    assert (binary_task.ROOT_COUNT_TOL, binary_task.ROOT_GAIN_RTOL) == (
        4e-6, 1e-3)
    assert binary_task.quality(x[:, 0], y, None) == reference.auc(x[:, 0], y)
    assert toy_run["detail"]["first_tree"]["left_count_err"] == 0


def test_toy_run_with_a_validation_set():
    """A traffic mix with `valid_rows`, at toy size: `eval_valid()` every
    iteration, and the program's last AUC held to the walk's over the
    same rows. No cell has such a mix yet; the PR that admits one brings
    the traffic file, and in it the limit, `valid_metric_tol`, with the
    upper reading it measured (PERF.md section 7 has the file)."""
    mix = {"params": {"metric": "auc"}, "valid_rows": 500,
           "valid_metric_tol": 1e-4}
    res = run.run_cell(BINARY, 5, 0.0, False, overrides=dict(
        TOY, traffic=dict(TOY["traffic"], **mix)))
    assert res["correct"] is True and res["failed"] == 0
    assert res["compared"]["valid_metric_err"]["limit"] == 1e-4
    assert res["compared"]["valid_metric_err"]["value"] < 1e-6
    assert tuple(res["detail"]["valid_said"][0][:2]) == ("valid", "auc")


@pytest.fixture(scope="module")
def rank_toy_run():
    return run.run_cell(RANK, 2**31 + 7, 0.0, False, overrides=RANK_TOY)


def test_lambdarank_toy_run_needs_a_config_and_a_task_file_only(rank_toy_run):
    """Another objective goes through `run_cell` with no edit of `run.py`,
    which names no objective, metric or task."""
    res = rank_toy_run
    assert res["correct"] is True and res["failed"] == 0, res["compared"]
    assert set(res["metrics"]) == {"train_ms_per_iter", "holdout_ndcg10_6",
                                   "setup_s"}
    assert set(res["compared"]) >= {
        "holdout_ndcg10_6", "grad_err", "hess_err", "root_left_count_err",
        "root_gain_rel_err", "score_walk_err", "grad_err_trained",
        "hess_err_trained"}
    assert res["detail"]["sizing"]["layout"] == "ext"
    first = res["detail"]["first_tree"]
    assert first["grad_queries"] == 60 and first["grad_rows"] == 3000
    assert first["walk_stretches"] == [(0, 3000)]
    # reported, and no condition of `correct`
    assert first["rank_queries_off_kernel"] == 0
    assert "rank_queries_off_kernel" not in res["compared"]
    with open(os.path.join(ROOT, "benchmark", "run.py")) as f:
        text = f.read()
    for word in ("binary", "lambdarank", "ndcg", "holdout_auc", "root_check"):
        assert word not in text.replace('config.get("task", "binary")', ""), \
            word


def test_traced_toy_run_reads_the_programs_own_seams(tmp_path):
    """On the CPU there is no device plane: the host seams and the
    counters are on the line and agree with the clocks held from outside;
    what needs device events is left out."""
    from lightgbm_tpu.obs import trace
    trace.reset()       # a benchmark process holds one run; pytest's may not
    res = run.run_cell(BINARY, 2**31 + 9, 0.0, True, overrides=TOY,
                       trace_dir=str(tmp_path))
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 2
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(got) == {"cache_misses", *ON_CPU}
    assert res["device"]["window_s"] > 0 and res["device"]["busy_s"] == 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(res)[-2:] == ["compared", "detail"]
    spans = xplane.load(xplane.newest_xplane(str(tmp_path)), run.SPAN)["spans"]
    assert [n for n, _, _ in spans] == ["bench.update"] * 2 + ["bench.drain"]
    walls = res["detail"]["walls"]
    assert 0 < got["ingest_program_s"] <= walls["ingest_bin_s"]
    assert got["ingest_program_s"] >= 0.9 * walls["ingest_bin_s"]
    parts = (got["pack_records_s"] + got["upload_s"] + got["program_load_s"]
             + got["first_drain_s"])
    assert 0.8 * walls["first_update_s"] <= parts <= walls["first_update_s"]
    assert got["move_rounds_per_iter"] >= 2
    assert 0 < got["move_split_chunk_pct"] <= 100
    # the host's own share of the window and the blocked share make it up
    per_iter = 1e3 * res["detail"]["window_s"] / res["attempted"]
    assert 0 < got["driver_host_ms_per_iter"] < per_iter
    assert got["driver_host_ms_per_iter"] + got["host_blocked_ms_per_iter"] \
        == pytest.approx(per_iter, rel=0.05)
    # a tree commits its leaves less one; speculation may execute more
    grown = res["detail"]["leaves"][-res["attempted"]:]
    assert got["move_leaves_split_per_iter"] >= sum(
        n - 1 for n in grown) / len(grown)
    assert got["hist_spill_flushes_per_iter"] == 0
    assert res["detail"]["in_window"] == {
        "traces": 0, "cache_hits": 0, "cache_misses": 0}


# ------------------------------------- controls and faults: not correct

def test_control_sampled_rows_break_the_guarantee_and_the_root_check():
    """The configurations guarantee trees on every row, no sampling. The
    control switches the program's own bagging on (half of the rows a
    tree, the mean taken over the rest): tree 0's root no longer counts
    what the whole column gives, and the run is not correct."""
    res = run.run_cell(BINARY, 2**31 + 5, 0.0, False, overrides=dict(
        TOY, params=dict(TOY["params"], bagging_fraction=0.5,
                         bagging_freq=1)))
    assert res["correct"] is False
    assert holds(res["compared"])["root_left_count_err"] is False
    assert res["compared"]["root_left_count_err"]["value"] > 0.1


LONG_RANK_TOY = dict(RANK_TOY, config=dict(
    RANK_TOY["config"], quality_floor=0.0, generator_params=dict(
        RANK_GEN, longest_query=439, query_segments=[[10, 3000], [2, 600]])))


def test_control_gradients_summed_in_bf16_fail_the_gradient_limit(
        monkeypatch):
    """The rank kernel's stated arithmetic is pair factors in bf16 and
    sums in f32. The control is the reference put in the program's place
    one step below, pair factors and sums in bf16, and goes through the
    run's own comparison: `run_cell` has to say not correct, by the
    gradient limits, at the start scores and at the trained ones. Queries
    as long as the cell's: a sum of 300 pair factors in bf16 strays
    further than one of 50."""
    import ml_dtypes
    clean = run.run_cell(RANK, 3, 0.0, False, overrides=LONG_RANK_TOY)
    assert clean["correct"] is True, clean["compared"]

    def in_bf16(booster, score):
        obj = booster._gbdt.objective
        return reference.lambdarank_gradients(
            np.asarray(score, np.float64), np.asarray(obj.label),
            np.diff(np.asarray(obj.query_boundaries)),
            dtype=ml_dtypes.bfloat16)
    monkeypatch.setattr(rank_task, "program_gradients", in_bf16)
    res = run.run_cell(RANK, 3, 0.0, False, overrides=LONG_RANK_TOY)
    ok = holds(res["compared"])
    assert res["correct"] is False
    for name in ("grad_err", "hess_err", "grad_err_trained",
                 "hess_err_trained"):
        assert ok[name] is False, res["compared"]
        assert res["compared"][name]["value"] > 2 * rank_task.GRAD_TOL
        assert clean["compared"][name]["value"] < rank_task.GRAD_TOL / 2
    # the same reference in float32 is no control: it passes
    g, h = reference.lambdarank_gradients(
        np.zeros(6), np.array([0, 1, 2, 0, 4, 1.0]), np.array([6]))
    g32, _ = reference.lambdarank_gradients(
        np.zeros(6), np.array([0, 1, 2, 0, 4, 1.0]), np.array([6]),
        dtype=np.float32)
    assert rank_task.worst(g32, g) < 1e-5


def fault_state_unchanged(monkeypatch):
    """From the third call on, `update()` returns with the booster as it
    was: the step that changes nothing."""
    import lightgbm_tpu as lgb
    real, calls = lgb.Booster.update, []

    def update(self, *a, **kw):
        calls.append(1)
        return real(self, *a, **kw) if len(calls) <= 2 else False
    monkeypatch.setattr(lgb.Booster, "update", update)
    return {}, ("every_tree_split",)


def fault_half_of_the_rows(monkeypatch):
    """Every tree is grown on half of the rows (the program's bag lane),
    leaf values the mean over those."""
    return {"bagging_fraction": 0.5, "bagging_freq": 1}, (
        "root_left_count_err",)


def fault_tree_altered(monkeypatch):
    """Tree 0's root threshold is moved as the tree is produced, so that
    the dump and `predict` both carry it."""
    import lightgbm_tpu as lgb
    real, done = lgb.Booster.update, []

    def update(self, *a, **kw):
        out = real(self, *a, **kw)
        if not done:
            done.append(1)
            self.eval_train()       # drain: the tree exists on the host
            self.trees[0].threshold[0] += 0.75
        return out
    monkeypatch.setattr(lgb.Booster, "update", update)
    return {}, ("root_left_count_err", "predict_equals_walk")


def fault_scores_altered(monkeypatch):
    """`predict` answers with every raw score moved by a thousandth."""
    import lightgbm_tpu as lgb
    real = lgb.Booster.predict

    def predict(self, data, *a, **kw):
        out = real(self, data, *a, **kw)
        return out + 1e-3 if kw.get("raw_score") else out
    monkeypatch.setattr(lgb.Booster, "predict", predict)
    return {}, ("predict_equals_walk",)


@pytest.mark.parametrize("fault", [
    fault_state_unchanged, fault_half_of_the_rows, fault_tree_altered,
    fault_scores_altered])
def test_a_run_with_the_timed_path_broken_is_not_correct(monkeypatch, fault):
    """The rest of a run, past the look for a chip, with one fault planted
    under it: `correct` has to come out false, by a check that is named.
    (One chip, so there is no exchange between chips to leave out.)"""
    params, failing = fault(monkeypatch)
    res = run.run_cell(BINARY, 2**31 + 5, 0.0, False, overrides=dict(
        TOY, params=dict(TOY["params"], **params)))
    assert res["correct"] is False
    assert any(holds(res["compared"])[k] is False for k in failing), \
        res["compared"]


def fault_rank_gradients_halved(real, rows):
    """The objective's gradients halved where they are produced: trees
    still grow and the root still follows from the gradients, so only the
    comparison with the reference's gradients can see it."""
    def get_gradients(self, scores):
        g, h = real(self, scores)
        return g * 0.5, h
    return get_gradients


def fault_rank_gradients_dropped_past_the_head(real, rows):
    """Every query past the first twelve trains with no gradient: the
    batch cut to its head, as a kernel that skips its later tiles or a
    write-back that stops early would cut it. The first twelve queries
    read as they should, and tree 0 follows from the gradients that are
    left, so only a sample that reaches past the head can see it."""
    def get_gradients(self, scores):
        g, h = real(self, scores)
        keep = np.arange(g.shape[-1]) < rows
        return g * keep, h * keep
    return get_gradients


@pytest.mark.parametrize("fault", [
    fault_rank_gradients_halved, fault_rank_gradients_dropped_past_the_head])
def test_a_rank_run_with_its_gradients_broken_is_not_correct(monkeypatch,
                                                             fault):
    from lightgbm_tpu.ops import objectives
    gen = istella.Generator(RANK_GEN, 0)
    monkeypatch.setattr(
        objectives.LambdarankNDCG, "get_gradients",
        fault(objectives.LambdarankNDCG.get_gradients, int(gen.bounds[12])))
    # twelve of the sixty queries are compared, as 256 of 33,018 are
    monkeypatch.setattr(rank_task, "GRAD_QUERIES", 12)
    res = run.run_cell(RANK, 2**31 + 7, 0.0, False, overrides=RANK_TOY)
    ok = holds(res["compared"])
    first = res["detail"]["first_tree"]
    assert (first["grad_queries"], first["grad_first_query"],
            first["grad_last_query"]) == (12, 0, 59)
    assert res["correct"] is False and ok["grad_err"] is False, \
        res["compared"]
    assert ok["root_left_count_err"]


def test_the_sample_of_queries_and_rows_follows_the_seed_and_keeps_both_ends():
    a = rank_task.sampled_queries(2**31 + 5, 33018, 256)
    b = rank_task.sampled_queries(6, 33018, 256)
    assert len(a) == len(set(a)) == 256 and (np.diff(a) > 0).all()
    assert a[0] == b[0] == 0 and a[-1] == b[-1] == 33017
    assert not np.array_equal(a, b)
    assert np.array_equal(a, rank_task.sampled_queries(2**31 + 5, 33018, 256))
    assert (a > 16509).sum() > 100      # over the whole set, not its head
    assert np.array_equal(rank_task.sampled_queries(1, 60, 256),
                          np.arange(60))
    rows, b_rows = 10_454_629, 262_144
    got = rank_task.walk_stretches(2**31 + 5, rows, b_rows, 4, 81920)
    assert got == rank_task.walk_stretches(2**31 + 5, rows, b_rows, 4, 81920)
    assert got != rank_task.walk_stretches(6, rows, b_rows, 4, 81920)
    assert len(got) == 4 and got[0] == (0, 20480) and got[-1][1] == rows
    assert sum(hi - lo for lo, hi in got) == 81920
    for lo, hi in got:                  # each inside one block
        assert lo // b_rows == (hi - 1) // b_rows
    assert len({lo // b_rows for lo, _ in got}) == 4
    assert rank_task.walk_stretches(1, 3000, 1024, 4, 81920) == [(0, 3000)]
    small = rank_task.walk_stretches(1, 3000, 1024, 4, 1000)
    assert small[0] == (0, 333) and small[-1] == (2667, 3000)
    assert len(small) == 3 and 1024 <= small[1][0] < small[1][1] <= 2048


# ------------------------------------------- data files and generators

@pytest.mark.parametrize("rows,features,objective,records,store", [
    (10_500_000, 28, "binary", 0.70, 0.05),          # HIGGS
    (2_270_000, 137, "lambdarank", 0.43, 0.27),      # MS-LTR
    (473_000, 700, "lambdarank", 0.73, 1.37),        # Yahoo-LTR
    (10_450_000, 220, "lambdarank", 2.63, 0.43),     # Istella
    (40_960_000, 67, "binary", 3.77, 0.13),          # most that C=1024 holds
    (48_000_000, 67, "binary", 4.50, 0.13),          # this benchmark's rows
])
def test_sizing_reproduces_the_issues_table(rows, features, objective,
                                            records, store):
    size = sizing.persistent_bytes(rows, features, 255, objective)
    assert size["records_bytes"] / sizing.GIB == pytest.approx(records,
                                                               abs=0.006)
    assert size["spill_store_bytes"] / sizing.GIB == pytest.approx(store,
                                                                   abs=0.006)


def test_sizing_of_every_config_clears_the_floor():
    """The driver's floor: a quarter of the chip's memory, or an eighth
    where the chip is busy three quarters of the traced window. The
    arithmetic alone has to clear the eighth; the measured peak and the
    busy share are in PERF.md."""
    hbm = run.load_json("benchmark", "peaks.json")["devices"][
        "TPU v5 lite"]["hbm_bytes"]
    for c in BENCH["configs"]:
        cfg = run.load_json(c["file"])
        size = sizing.persistent_bytes(
            cfg["rows"], cfg["features"], cfg["params"]["max_bin"],
            cfg["params"]["objective"], cfg["params"]["num_leaves"])
        assert size["persistent_bytes"] > 1.05 * 0.125 * hbm, c["name"]
        assert size["spill"] == (cfg["params"]["max_bin"] > 128)
        if cfg.get("task", "binary") == "binary":
            assert size["persistent_bytes"] > 1.05 * 0.25 * hbm, c["name"]
            assert size["layout"] == "std" and size["chunk"] == 2048
        else:
            assert (size["layout"], size["chunk"], size["lanes"]) == (
                "ext", 512, 64)


def test_generator_is_seeded_and_its_shapes_are_not():
    a, b, other = Generator(GEN, 2**31 + 5), Generator(GEN, 2**31 + 5), \
        Generator(GEN, 6)
    xa, ya = a.rows(1000, 3100)
    xb, yb = b.rows(1000, 3100)
    xo, yo = other.rows(1000, 3100)
    assert np.array_equal(xa, xb) and np.array_equal(ya, yb)
    assert xo.shape == xa.shape == (2100, 10) and xo.dtype == np.float32
    assert not np.array_equal(xo, xa) and np.isfinite(xa).all()
    assert set(np.unique(ya)) == {0.0, 1.0}
    # the distributions belong to the config, not the seed
    assert abs(np.median(xo[:, 5]) - np.median(xa[:, 5])) < 0.3
    for j in (0, 4, 9):
        assert np.array_equal(a.column(j, 1000, 3100), xa[:, j])
    # bin boundaries come from rows that no seed changes
    assert np.array_equal(a.sample(500), other.sample(500))
    assert not np.array_equal(a.sample(500), xa[:500])


def test_ranking_generator_is_one_data_set_in_one_order():
    a, other = istella.Generator(RANK_GEN, 2**31 + 5), \
        istella.Generator(RANK_GEN, 6)
    # what a compiled program holds as a constant is the structure's: the
    # length at every place is the same under every seed
    assert np.array_equal(a.sizes, other.sizes)
    assert a.sizes[:60].sum() == 3000 and a.sizes[60:].sum() == 600
    assert 1 <= a.sizes.min() and a.sizes.max() <= 100
    assert np.array_equal(a.groups(0, 3000), a.sizes[:60])
    assert np.array_equal(a.groups(3000, 600), a.sizes[60:])
    for first, rows in ((0, 2999), (1, 2999), (0, 3601)):
        with pytest.raises(ValueError):
            a.groups(first, rows)
    # and so is every row: the seed is kept for the task's samples alone
    xa, ya = a.rows(0, 3600)
    xo, yo = other.rows(0, 3600)
    assert (a.seed, other.seed) == (2**31 + 5, 6)
    assert xa.shape == (3600, 12) and xa.dtype == np.float32
    assert np.array_equal(xa, xo) and np.array_equal(ya, yo)
    assert np.isfinite(xa).all()
    assert set(np.unique(ya)) <= {0.0, 1.0, 2.0, 3.0, 4.0}
    share = np.bincount(ya.astype(int), minlength=5) / 3600
    assert share[0] > 0.5 and share[4] > 0
    # blocks, ranges and single columns are the same rows, bit for bit
    assert np.array_equal(a.block(1)[0][:976], xa[1024:2000])
    assert np.array_equal(a.rows(100, 2100)[0], xa[100:2100])
    for j in (0, 2, 3, 7, 11):
        assert np.array_equal(a.column(j, 100, 2100), xa[100:2100, j])
    # bin boundaries come from a stream of their own
    assert np.array_equal(a.sample(500), other.sample(500))
    assert not np.array_equal(a.sample(500), xa[:500])
    full = istella.query_lengths(np.random.default_rng([220, 1]), 33018,
                                 10454629, 439)
    assert full.sum() == 10454629 and full.max() == 439 and full.min() >= 1
    with pytest.raises(ValueError):
        istella.query_lengths(np.random.default_rng(0), 10, 1001, 100)


# ------------------------------------------------ the plain reference

def test_reference_walk_root_and_auc():
    import lightgbm_tpu as lgb
    gen = Generator(GEN, 11)
    x, y = gen.rows(0, 4000)
    bst = lgb.train({"objective": "binary", "num_leaves": 15, "verbosity": -1,
                     "min_data_in_leaf": 20}, lgb.Dataset(x, label=y),
                    num_boost_round=3)
    model = bst.dump_model()
    np.testing.assert_allclose(reference.raw_scores(model, x[:500]),
                               bst.predict(x[:500], raw_score=True),
                               rtol=1e-5, atol=1e-6)
    j = model["tree_info"][0]["tree_structure"]["split_feature"]
    good = reference.root_check(model, x[:, j], y)
    assert good["left_count_err"] == 0 and good["gain_rel_err"] < 1e-4
    # the same root from the gradient vectors that the labels give
    p0 = y.mean()
    same = reference.root_from_gradients(
        model, x[:, j], p0 - y.astype(np.float64),
        np.full(len(y), p0 * (1 - p0)))
    assert same["left_count"] == good["left_count"]
    assert same["gain"] == pytest.approx(good["gain"], rel=1e-6)
    model["tree_info"][0]["tree_structure"]["threshold"] += 0.5
    bad = reference.root_check(model, x[:, j], y)
    assert bad["left_count_err"] > 1e-3 and bad["gain_rel_err"] > 1e-3
    score = np.array([0.1, 0.4, 0.4, 0.8])
    assert reference.auc(score, np.array([0, 0, 1, 1])) == 0.875
    assert reference.auc(-score, np.array([0, 0, 1, 1])) == 0.125


def by_the_headers_loops(score, label, groups, max_position=20):
    """`GetGradientsForOneQuery` as the header writes it: two loops over
    the sorted documents, one pair at a time."""
    grad, hess, at = np.zeros(len(score)), np.zeros(len(score)), 0
    for n in groups:
        s, lab = score[at:at + n], label[at:at + n]
        best = reference.max_dcg_at(max_position, lab)
        inv = 1.0 / best if best > 0 else 0.0
        order = sorted(range(n), key=lambda a: -s[a])       # stable
        for i, hi in enumerate(order):
            for j, lo in enumerate(order):
                if i == j or lab[hi] <= lab[lo]:
                    continue
                ds = s[hi] - s[lo]
                delta = ((2.0 ** lab[hi] - 2.0 ** lab[lo])
                         * abs(1 / np.log2(2 + i) - 1 / np.log2(2 + j)) * inv)
                if s[order[0]] != s[order[-1]]:
                    delta /= 0.01 + abs(ds)
                p = 2.0 / (1.0 + np.exp(2.0 * ds))
                grad[at + hi] -= p * delta
                grad[at + lo] += p * delta
                hess[at + hi] += p * (2 - p) * 2 * delta
                hess[at + lo] += p * (2 - p) * 2 * delta
        at += n
    return grad, hess


HAND = {
    # two documents, the relevant one scored lower: one pair, positions
    # 0 and 1. delta = (2^1 - 1) (1 - 1/log2 3) / 1 over 0.01 + 1;
    # p = 2 / (1 + exp(-2)) (the higher grade is the lower score)
    "one_pair": ([1.0, 0.0], [0, 1], [2]),
    # ties keep the documents' order: the grade-2 document comes first
    "ties": ([0.5, 0.5, 0.5, 0.1], [2, 0, 1, 1], [4]),
    # a query of one grade has no pair and no gradient
    "one_grade": ([0.3, 0.9, 0.1, 0.2, 0.7], [1, 1, 1, 0, 2], [3, 2]),
    # longer than max_position: every pair still counts, at any depth,
    # and only the inverse max DCG is cut at 20
    "long": (list(np.linspace(1.0, -1.0, 30)), [0] * 25 + [1, 0, 3, 0, 2],
             [30]),
}


@pytest.mark.parametrize("case", sorted(HAND))
def test_lambdarank_gradients_on_hand_worked_queries(case):
    score, label, groups = (np.asarray(v, np.float64) for v in HAND[case])
    groups = groups.astype(np.int64)
    g, h = reference.lambdarank_gradients(score, label, groups)
    want_g, want_h = by_the_headers_loops(score, label, groups)
    np.testing.assert_allclose(g, want_g, rtol=1e-12, atol=1e-15)
    np.testing.assert_allclose(h, want_h, rtol=1e-12, atol=1e-15)
    assert abs(g.sum()) < 1e-12 and (h >= 0).all()
    if case == "one_pair":
        delta = (1.0 - 1.0 / np.log2(3.0)) / 1.01
        p = 2.0 / (1.0 + np.exp(-2.0))
        assert g == pytest.approx([p * delta, -p * delta])
        assert h == pytest.approx([p * (2 - p) * 2 * delta] * 2)
    if case == "ties":
        # best != worst, so the pairs inside the tie divide by 0.01 alone
        assert g[0] < 0 < g[1]
    if case == "one_grade":
        assert not g[:3].any() and g[3] > 0 > g[4]
    if case == "long":
        # the grade-3 document at position 27 pairs with all 27 above it
        assert g[27] < 0 and (g[:25] > 0).all()
        deep = reference.lambdarank_gradients(score, label, groups,
                                              max_position=2)[0]
        assert deep[27] / g[27] == pytest.approx(
            reference.max_dcg_at(20, label) / reference.max_dcg_at(2, label))


@pytest.mark.parametrize("case,want", [
    # DCG@2 = 0 + 1/log2(3); best = 1
    ("one_pair", 1.0 / np.log2(3.0)),
    # order 0, 1, 2, 3 by the tie rule: gains 3, 0, 1, 1 against 3, 1, 1, 0
    ("ties", (3 + 1 / 2 + 1 / np.log2(5)) / (3 + 1 / np.log2(3) + 1 / 2)),
    # query 1 has only grade 1: any order is the best order; query 2 is
    # (0, 2) scored 0.2 < 0.7: perfect
    ("one_grade", 1.0),
    # nothing relevant in the first 10 of 30
    ("long", 0.0),
])
def test_ndcg_on_hand_worked_queries(case, want):
    score, label, groups = HAND[case]
    got = reference.ndcg_at(10, np.asarray(score), np.asarray(label),
                            np.asarray(groups))
    assert got == pytest.approx(want, abs=1e-12)
    assert rank_task.quality(np.asarray(score), np.asarray(label),
                             np.asarray(groups)) == got


def test_ndcg_counts_a_query_with_nothing_relevant_as_one():
    assert reference.ndcg_at(10, np.array([0.3, 0.1, 0.2, 0.9]),
                             np.array([0, 0, 0, 1]), np.array([2, 2])) \
        == pytest.approx((1.0 + 1.0) / 2)
    assert reference.max_dcg_at(2, np.array([0, 4, 1])) == pytest.approx(
        15 + 1 / np.log2(3))


# --------------------------------------------- the trace's arithmetic

def test_xplane_interval_arithmetic():
    assert xplane.parse_op(
        "%move_pass.15 = (s32[24588,24,2048]{2,1,0:T(8,128)}, f32[257,67,16,"
        "128]{3,2,1,0:T(8,128)}) custom-call(s32[24588]{0:T(1024)S(1)} "
        "%get-tuple-element.1), custom_call_target=\"tpu_custom_call\"") == (
            "move_pass", "kernel")
    assert xplane.parse_op(
        "%fusion.901 = f32[17152]{0:T(1024)S(1)} fusion(f32[256,67,255]{2,1,0"
        ":T(8,128)S(1)} %custom-call.498), kind=kLoop") == ("fusion.901", "op")
    assert xplane.parse_op(
        "%while.171 = (s32[]{:T(128)}, pred[1149]{0:T(1024)(128)(4,1)}) "
        "while((s32[]{:T(128)}) %tuple.954), condition=%c")[1] == "wrapper"
    ops = [("move_pass", 10, 30), ("fusion.2", 25, 40),
           ("fusion.2", 60, 70), ("copy.3", 95, 120)]
    spans = [("bench.update", 0, 50), ("bench.drain", 50, 100)]
    assert xplane.merge(ops) == [[10, 40], [60, 70], [95, 120]]
    inside = xplane.clip(ops, 0, 100)
    assert xplane.busy_ns(inside) == 30 + 10 + 5
    assert xplane.by_name(inside) == {"move_pass": 20, "fusion.2": 25,
                                      "copy.3": 5}
    assert xplane.idle_gaps(ops, spans, 0, 100) == {
        "bench.update": 10 + 10, "bench.drain": 10 + 25}
    assert xplane.idle_gaps([], spans[:1], 0, 100) == {
        "bench.update": 50, "outside": 50}
    trace = xplane.window({"devices": {"/device:TPU:0": ops},
                           "kernels": {"move_pass"}, "spans": spans})
    assert trace["window"] == {"busy_s": 45e-9, "window_s": 100e-9}
    assert trace["breakdown"]["device_ops"][0] == ["fusion.2", 25e-9]
    assert trace["counts"] == {"move_pass": 1.0}
    ctx = {"trace": trace, "iterations": 2}
    from benchmark.layer_metrics import (device_idle_pct, pallas_ms_per_iter,
                                         xla_glue_ms_per_iter)
    assert device_idle_pct.read(ctx) == pytest.approx(55.0)
    assert pallas_ms_per_iter.read(ctx) == pytest.approx(10e-6)
    assert xla_glue_ms_per_iter.read(ctx) == pytest.approx(12.5e-6)


def test_rank_readers_on_a_made_up_trace():
    """Two iterations of a ranking run on the device: between a tree's
    last `move_pass` and the next `slot_hist_pass` sit the materialise,
    the rank kernel, its glue and the write-back. A pointwise run has the
    same gaps and no such kernel, and reads nothing."""
    def iteration(at, rank):
        ev = [("fusion.mat", at, at + 30)]
        if rank:
            ev.append(("rank_kernel", at + 30, at + 130))
        ev += [("fusion.glue", at + 130, at + 150),
               ("scatter.back", at + 150, at + 200),
               ("slot_hist_pass", at + 200, at + 300),
               ("fusion.split", at + 300, at + 310),
               ("move_pass", at + 310, at + 500),
               ("fusion.split", at + 500, at + 510),
               ("move_pass", at + 510, at + 600),
               ("fusion.score", at + 600, at + 640)]
        return ev

    def ctx(rank):
        ev = iteration(0, rank) + iteration(1000, rank)
        names = {"move_pass", "slot_hist_pass"} | (
            {"rank_kernel"} if rank else set())
        return {"iterations": 2, "trace": {
            "ops": {"/device:TPU:0": ev}, "kernels": names}}
    read = {n: importlib.import_module("benchmark.layer_metrics." + n).read
            for n in ("rank_kernel_ms_per_iter",
                      "rank_round_trip_ms_per_iter")}
    assert read["rank_kernel_ms_per_iter"](ctx(True)) == pytest.approx(
        100e-6)
    # the first tree's gap is 30 + 20 + 50; the second's has the first
    # tree's tail after its last move_pass (40) in it too
    assert read["rank_round_trip_ms_per_iter"](ctx(True)) == pytest.approx(
        (100 + 140) / 2 * 1e-6)
    for name in read:
        assert read[name](ctx(False)) is None
        assert read[name]({"iterations": 2, "trace": {
            "ops": {}, "kernels": set()}}) is None


# --------------------------------- the seam readers, on made-up seams

COLUMNS = ["chunks_split", "chunks_copied", "rows_split", "leaves_split",
           "spill_slots", "chunks_dead"]
SEAM = ["ingest_program_s", "pack_records_s", "upload_s", "program_load_s",
        "first_drain_s", "driver_host_ms_per_iter", "move_rounds_per_iter",
        "move_split_chunk_pct", "move_us_per_split_chunk",
        "move_us_per_copied_chunk", "move_pass_hbm_roofline_pct",
        "move_leaves_split_per_iter", "hist_spill_flushes_per_iter",
        "host_blocked_ms_per_iter"]
ON_CPU = SEAM[:8] + SEAM[11:]     # the rest needs the device's events
SPLIT_US, COPIED_US = 40.0, 1.5     # what the made-up kernel costs a chunk
PEAK = 819e9


def read(name, ctx):
    return importlib.import_module(
        "benchmark.layer_metrics." + name).read(ctx)


def made_up_ring():
    """Two warm-up iterations and a window of two, as the program would
    leave them: ingest, pack, upload, a program's first call inside the
    first dispatch, a drain per phase with its flag pull inside."""
    ids = iter(range(1, 1000))
    ring = []

    def seam(name, t0, t1, it=None, parent=None, **attrs):
        rec = dict(kind="seam", name=name, id=next(ids), parent=parent,
                   iter=it, t0=float(t0), t1=float(t1), **attrs)
        ring.append(rec)
        return rec["id"]

    def iteration(it, tables):
        seam("aligned.iter", 0, 0, it=it, rounds=len(tables),
             columns=COLUMNS, table=tables)

    seam("ingest.find_bins", 0, 2, native=True)
    seam("ingest.push_rows", 2, 5, rows=10, native=True)
    seam("ingest.push_rows", 6, 8, rows=10, native=True)
    seam("ingest.finish_load", 8, 8.5)
    seam("aligned.pack", 10, 50, rows=20, bytes=1920, W=24, w_used=23,
         C=4, NC=9, bits=8)
    seam("aligned.upload", 50, 53, bytes=1920)
    ring.append(dict(kind="seam", name="aligned.dispatch", id=100,
                     parent=None, iter=0, t0=53.0, t1=60.0))
    seam("aligned.program", 54, 59.5, it=0, parent=100, key="build",
         cache="hit")
    drain = 200
    seam("train.flag_pull", 60.5, 70, it=1, parent=drain, queued=1,
         final=True)
    iteration(0, [[9, 0, 20, 1, 0, 0]])
    seam("aligned.program", 70, 71, it=1, parent=drain, key="mat",
         cache="miss")
    ring.append(dict(kind="seam", name="train.drain", id=drain, parent=None,
                     iter=1, t0=60.0, t1=72.0))
    seam("aligned.dispatch", 72, 72.1, it=1)
    # ---- the window: iterations 2 and 3
    seam("aligned.dispatch", 80, 80.1, it=2)
    seam("aligned.dispatch", 80.2, 80.3, it=3)
    seam("train.flag_pull", 80.4, 81.4, it=3, queued=2, final=False)
    iteration(1, [[9, 0, 20, 1, 0, 0], [2, 5, 8, 1, 1, 1]])
    drain = 300
    seam("train.flag_pull", 82, 90, it=4, parent=drain, queued=2,
         final=True)
    iteration(2, [[9, 0, 20, 1, 0, 0], [4, 3, 12, 2, 2, 1]])
    iteration(3, [[9, 0, 20, 1, 0, 0], [1, 6, 5, 1, 1, 2],
                  [3, 4, 9, 2, 2, 1]])
    ring.append(dict(kind="seam", name="train.drain", id=drain, parent=None,
                     iter=4, t0=81.5, t1=93.5))
    # ---- after the window: the checks drain an idle device
    seam("train.drain", 95, 99, it=4)
    return ring


def made_up_trace(ring, drop_last: bool = False):
    """`move_pass` events that cost exactly SPLIT_US a compute-path chunk
    and COPIED_US a copied one, in the window's round order, between
    other operations."""
    win = _seams.window(ring, 2)
    split = [s + d for s, d in zip(_seams.column(win["iters"], "chunks_split"),
                                   _seams.column(win["iters"], "chunks_dead"))]
    copied = _seams.column(win["iters"], "chunks_copied")
    events, at = [], 1000
    for s, c in zip(split, copied):
        ns = int(1e3 * (SPLIT_US * s + COPIED_US * c))
        events += [("fusion.7", at, at + 50), ("move_pass", at + 60,
                                               at + 60 + ns)]
        at += 100 + ns
    if drop_last:
        events = events[:-1]
    return {"ops": {"/device:TPU:0": events}, "kernels": {"move_pass"},
            "window": {"busy_s": 1.0, "window_s": 1.0}}


@pytest.fixture
def ring(monkeypatch):
    made = made_up_ring()
    monkeypatch.setattr(_seams, "ring", lambda: list(made))
    monkeypatch.setattr(_seams, "hbm_bytes_per_s", lambda: PEAK)
    return made


@pytest.mark.parametrize("name,expected", [
    ("ingest_program_s", 2 + 3 + 2 + 0.5),
    ("pack_records_s", 40.0),
    ("upload_s", 3.0),
    ("program_load_s", 5.5),    # the one in the drain is the drain's
    ("first_drain_s", 12.0),
    # the window less the pull in the loop and the drain, whose own pull
    # is counted once: what is left is the host's
    ("driver_host_ms_per_iter", 1e3 * (13.5 - (1.0 + 12.0)) / 2),
    ("host_blocked_ms_per_iter", 1e3 * (1.0 + 12.0) / 2),
    ("move_rounds_per_iter", (2 + 3) / 2),
    ("move_split_chunk_pct", 100.0 * 26 / (26 + 13)),
    ("move_us_per_split_chunk", SPLIT_US),
    ("move_us_per_copied_chunk", COPIED_US),
    ("move_leaves_split_per_iter", (1 + 2 + 1 + 1 + 2) / 2),
    ("hist_spill_flushes_per_iter", (0 + 2 + 0 + 1 + 2) / 2),
])
def test_reader_on_made_up_seams(ring, name, expected):
    ctx = {"iterations": 2, "trace": made_up_trace(ring), "walls": {},
           "compiles": {}}
    assert read(name, ctx) == pytest.approx(expected, rel=1e-6)


def test_roofline_counts_only_the_rows_that_had_to_move(ring):
    trace = made_up_trace(ring)
    ctx = {"iterations": 2, "trace": trace}
    rows = 20 + 12 + 20 + 5 + 9
    seconds = sum(e - s for n, s, e in trace["ops"]["/device:TPU:0"]
                  if n == "move_pass") / 1e9
    assert read("move_pass_hbm_roofline_pct", ctx) == pytest.approx(
        100.0 * (2 * rows * 4 * 23 / PEAK) / seconds)


def test_a_count_mismatch_gives_none(ring):
    """The fit pairs events with rounds by order: one event too few (a
    window cut inside an iteration) and there is no pairing to trust."""
    ctx = {"iterations": 2, "trace": made_up_trace(ring, drop_last=True)}
    assert read("move_us_per_split_chunk", ctx) is None
    assert read("move_us_per_copied_chunk", ctx) is None
    # what needs no pairing still reads
    assert read("move_rounds_per_iter", ctx) == 2.5
    assert read("move_pass_hbm_roofline_pct", ctx) > 0


def test_fit_recovers_known_costs_and_refuses_a_singular_system():
    x1, x2 = [9, 2, 9, 4, 9, 3, 4], [0, 5, 0, 3, 0, 6, 4]
    y = [7.0 * a + 0.25 * b for a, b in zip(x1, x2)]
    assert _seams.fit_two(x1, x2, y) == pytest.approx((7.0, 0.25))
    assert _seams.fit_two([1, 2, 3], [2, 4, 6], [1, 2, 3]) is None
    assert _seams.fit_two([3, 3], [0, 0], [1, 1]) is None


@pytest.mark.parametrize("name", SEAM)
def test_reader_finds_nothing_in_a_program_without_seams(monkeypatch, name):
    """The parent of the PR that added the seams: an empty ring, a trace
    that still has its `move_pass` events; no reader raises."""
    trace = made_up_trace(made_up_ring())
    monkeypatch.setattr(_seams, "ring", lambda: [])
    ctx = {"iterations": 2, "trace": trace, "walls": {}, "compiles": {}}
    assert read(name, ctx) is None
    assert read(name, dict(ctx, trace={"ops": {}, "kernels": set()})) is None


def test_too_few_iterations_in_the_ring_is_nothing(ring):
    ctx = {"iterations": 9, "trace": made_up_trace(ring)}
    for name in ("move_rounds_per_iter", "move_split_chunk_pct",
                 "program_load_s", "driver_host_ms_per_iter",
                 "host_blocked_ms_per_iter", "move_us_per_split_chunk",
                 "move_pass_hbm_roofline_pct", "move_leaves_split_per_iter"):
        assert read(name, ctx) is None


# -------------------------------------------------- BENCHMARK.json

def test_every_metric_has_its_reader_and_lists_cells_that_exist():
    cells = {w["name"] for w in BENCH["workloads"]}
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert set(SEAM) <= set(entries)
    assert not {"ingest_bin_s", "first_update_s"} & set(entries)
    for m in BENCH["per_layer"]:
        assert callable(importlib.import_module(
            "benchmark.layer_metrics." + m["name"]).read)
        assert set(m.get("workloads", cells)) <= cells
    for name in SEAM:
        assert entries[name]["workloads"] == sorted(
            cells, key=[w["name"] for w in BENCH["workloads"]].index)
    for name in ("rank_kernel_ms_per_iter", "rank_round_trip_ms_per_iter"):
        assert entries[name]["workloads"] == [RANK]
        assert entries[name]["layer"] == "rank gradients"


def test_benchmark_json_fits_the_drivers_rules():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and BENCH["run_seconds"] == 36
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py")), m
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert len({c["source"] for c in configs.values()}) == len(configs)
    assert len({c["file"] for c in configs.values()}) == len(configs)
    for c in configs.values():
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert len(c["source"]) <= 200
        assert c["reduced"] == run.load_json(c["file"])["reduced"]
        assert c["source"] == run.load_json(c["file"])["source"]
    assert {w["config"] for w in BENCH["workloads"]} == set(configs)
    assert len(BENCH["workloads"]) <= 24
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        cell, metrics, config, traffic = run.load_cell(w["name"])
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "generators", config["generator"] + ".py"))
        task = importlib.import_module(
            "benchmark.tasks." + config.get("task", "binary"))
        # the cell reports set-up, its task's quality and one more at least
        quality = f"holdout_{task.QUALITY}_{traffic['auc_trees']}"
        assert {m["name"] for m in metrics["end_to_end"]} > {"setup_s",
                                                             quality}
        assert metrics["per_layer"]
        assert ("quality_floor" in config) != ("auc_floor" in config)
        assert all(m["moves"] in {e["name"] for e in metrics["end_to_end"]}
                   for m in metrics["per_layer"])
    assert len(json.dumps(BENCH)) < 64 * 1024
