"""The benchmark's own checks, at toy size on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/selftest -q

They live under `benchmark/` because a benchmark PR may add files only
under its own paths; nothing here calls the TPU compiler, and nothing
leans on time. The aligned kernels run under the Pallas interpreter.
"""
import json
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmark import reference, run, sizing, xplane  # noqa: E402
from benchmark.generators.criteo import Generator  # noqa: E402

GEN = {"count_columns": 3, "continuous_columns": 7, "block_rows": 1024,
       "structure_seed": 67, "margin_terms": 6, "margin_bias": -0.5}
TOY = {"config": {"rows": 3000, "holdout_rows": 600, "auc_floor": 0.55,
                  "generator_params": GEN},
       "traffic": {"warmup_iterations": 7, "min_window_iterations": 2,
                   "trace_iterations": 2},
       "params": {"num_leaves": 15, "tpu_grow_mode": "aligned",
                  "tpu_aligned_interpret": True}}
BENCH = run.load_json("BENCHMARK.json")
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")


@pytest.fixture(scope="module")
def toy_run():
    """One untraced toy run of the first cell, shared by two tests."""
    return run.run_cell(BENCH["workloads"][0]["name"], 2**31 + 5, 0.0, False,
                        overrides=TOY)


def test_toy_run_prints_the_contracts_keys(toy_run):
    line = {k: v for k, v in toy_run.items() if k != "detail"}
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert toy_run["detail"]["checks"] == dict.fromkeys(
        toy_run["detail"]["checks"], True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 2
    assert set(line["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}
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


def test_traced_toy_run_leaves_out_what_it_cannot_read(tmp_path):
    """On the CPU there is no device plane: the host-clock and counter
    metrics are on the line, the three trace metrics are left out."""
    res = run.run_cell(BENCH["workloads"][1]["name"], 7, 0.0, True,
                       overrides=TOY, trace_dir=str(tmp_path))
    assert res["correct"] is True and res["attempted"] == 2
    assert set(res["metrics"]) == {"ingest_bin_s", "first_update_s",
                                   "cache_misses"}
    assert res["device"]["window_s"] > 0 and res["device"]["busy_s"] == 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    spans = xplane.load(xplane.newest_xplane(str(tmp_path)), run.SPAN)["spans"]
    assert [n for n, _, _ in spans] == ["bench.update"] * 2 + ["bench.drain"]


def test_toy_run_with_a_validation_set():
    """The traffic keys a later `train-valid` cell needs, so that it can
    be added as data files only."""
    res = run.run_cell(BENCH["workloads"][0]["name"], 5, 0.0, False, overrides=dict(
        TOY, traffic=dict(TOY["traffic"], valid_rows=500,
                          params={"metric": "auc"})))
    assert res["correct"] is True and res["failed"] == 0


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


def test_sizing_of_both_configs_clears_the_floor():
    floor = 0.25 * run.load_json("benchmark", "peaks.json")["devices"][
        "TPU v5 lite"]["hbm_bytes"]
    for c in BENCH["configs"]:
        cfg = run.load_json(c["file"])
        size = sizing.persistent_bytes(
            cfg["rows"], cfg["features"], cfg["params"]["max_bin"],
            cfg["params"]["objective"], cfg["params"]["num_leaves"])
        assert size["persistent_bytes"] > 1.05 * floor, c["name"]
        assert size["layout"] == "std" and size["chunk"] == 2048
        assert size["spill"] == (cfg["params"]["max_bin"] > 128)


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
    model["tree_info"][0]["tree_structure"]["threshold"] += 0.5
    bad = reference.root_check(model, x[:, j], y)
    assert bad["left_count_err"] > 1e-3 and bad["gain_rel_err"] > 1e-3
    score = np.array([0.1, 0.4, 0.4, 0.8])
    assert reference.auc(score, np.array([0, 0, 1, 1])) == 0.875
    assert reference.auc(-score, np.array([0, 0, 1, 1])) == 0.125


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


def test_benchmark_json_fits_the_drivers_rules():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and 1 <= BENCH["run_seconds"] <= 51
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) == {"name", "unit", "better", "bound", "source"}
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
    for c in configs.values():
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert len(c["source"]) <= 200
        assert c["reduced"] == run.load_json(c["file"])["reduced"]
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        cell, metrics, config, traffic = run.load_cell(w["name"])
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "generators", config["generator"] + ".py"))
        assert "holdout_auc_%d" % traffic["auc_trees"] in e2e
    assert len(json.dumps(BENCH)) < 64 * 1024
