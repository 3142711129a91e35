"""In-run bottleneck profiler (`lightgbm_tpu.obs.profiler`): sampled
per-term fenced rounds in the ledger, the two timing modes, XLA cost
attribution, zero-added-fence when off, the canonical term vocabulary
shared with the offline tools, and the ranked bottleneck report.
"""
import glob
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import compile_cache
from lightgbm_tpu.obs import ledger as obs_ledger
from lightgbm_tpu.obs import profiler as obs_profiler
from lightgbm_tpu.obs import trace as obs_trace
from lightgbm_tpu.obs.terms import (RANKING_OBJECTIVES, SITE_TERMS, TERMS,
                                    term_for_site, validate_terms_ms)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ALIGNED = {"tpu_grow_mode": "aligned", "tpu_aligned_interpret": True,
           "tpu_chunk": 256}


def _data(seed=3, n=900, f=8):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] * X[:, 2]
          + 0.3 * rng.standard_normal(n)) > 0).astype(np.float32)
    return X, y


def _train_profiled(tmp_path, extra=None, rounds=6, n=900):
    X, y = _data(n=n)
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "min_data_in_leaf": 20, "verbosity": -1, "metric": "none",
              "tpu_trace": True, "tpu_trace_dir": str(tmp_path),
              "tpu_profile": "on", "tpu_profile_every": 2}
    if extra:
        params.update(extra)
    ds = lgb.Dataset(X, label=y, params=params).construct()
    try:
        bst = lgb.train(params, ds, num_boost_round=rounds)
        led = bst.telemetry
        assert led is not None
        led.close()
        return bst, led
    finally:
        obs_trace.disable()
        obs_trace.reset()
        compile_cache.clear_captured()


def _disk_records(tmp_path):
    paths = sorted(glob.glob(os.path.join(str(tmp_path),
                                          "ledger-*.jsonl")))
    assert paths
    return obs_ledger.read_ledger(paths[-1])


# ---------------------------------------------------------------------------
# sampled rounds: fenced terms in the ledger, schema-valid, sum == device
# ---------------------------------------------------------------------------

def test_profiled_rounds_write_fenced_terms(tmp_path):
    bst, led = _train_profiled(tmp_path, extra=dict(ALIGNED))
    recs = _disk_records(tmp_path)
    for rec in recs:
        obs_ledger.validate_record(rec)
    rounds = [r for r in recs if r["kind"] == "round"]
    prof_rounds = [r for r in rounds if r.get("profiled")]
    # every=2 over 6 rounds samples rounds 2 and 4 (round 0 pays
    # compiles and is never sampled)
    assert [r["round"] for r in prof_rounds] == [2, 4]
    for r in prof_rounds:
        assert r["timing"] == "fenced"
        assert validate_terms_ms(r["terms_ms"]) is None
        # fenced mode: device_ms is the sum of the per-site terms by
        # construction — the decomposition is exhaustive
        assert sum(r["terms_ms"].values()) == \
            pytest.approx(r["device_ms"], abs=0.01)
        assert "build" in r["terms_ms"]
    # unprofiled rounds carry neither terms nor a timing tag (their
    # device_ms is the one-fence pipelined residual)
    for r in rounds:
        if not r.get("profiled"):
            assert "terms_ms" not in r and "timing" not in r
    # the one-time chained-k calibration note decomposes `build`
    notes = [r for r in recs if r.get("kind") == "note"
             and r.get("note") == "profile_calibration"]
    assert len(notes) == 1
    shares = notes[0]["shares"]
    assert shares and set(shares) <= set(TERMS)
    assert sum(shares.values()) == pytest.approx(1.0, abs=0.01)
    # profiler handle survives the engine_train booster round-trip
    prof = bst.profiler
    assert prof is not None
    assert [h["round"] for h in prof.history] == [2, 4]


def test_profiled_rounds_excluded_from_round_ms():
    """Fenced rounds never feed the round-wall histogram: per-site
    fencing inflates wall time vs the pipelined steady state, and mixing
    the two timing modes would corrupt p50/p99."""
    from lightgbm_tpu.obs import metrics as obs_metrics
    obs_metrics.reset()
    X, y = _data(n=400)
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "verbosity": -1, "metric": "none", "tpu_metrics": True,
              "tpu_profile": "on", "tpu_profile_every": 2}
    ds = lgb.Dataset(X, label=y, params=params).construct()
    try:
        bst = lgb.Booster(params=params, train_set=ds)
        for _ in range(5):
            bst.update()
        m = bst._gbdt._metrics
        assert m is not None
        # rounds 0,1,3 observed; 2,4 were fenced and skipped
        assert m.round_ms.count == 3
        assert m.rounds.value == 5       # but still counted as rounds
        # last sampled round's terms live in the per-term gauge family
        assert m.term_ms.labels(term="build").value > 0
    finally:
        obs_metrics.reset()
        compile_cache.clear_captured()


# ---------------------------------------------------------------------------
# off: zero added fences, no terms in the ledger
# ---------------------------------------------------------------------------

def test_profile_off_adds_zero_fences(monkeypatch):
    calls = []
    monkeypatch.setattr(obs_trace, "_block",
                        lambda x: calls.append(1) or x)
    obs_trace.reset()
    X, y = _data(n=400)
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "verbosity": -1, "metric": "none", "tpu_profile": "off"}
    ds = lgb.Dataset(X, label=y, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(3):
        bst.update()
    assert bst._gbdt._profiler is None
    assert calls == [], "tpu_profile=off issued a fence"
    assert obs_trace.fence_count == 0


def test_profile_off_no_terms_in_ledger(tmp_path):
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "verbosity": -1, "metric": "none", "tpu_trace": True,
              "tpu_trace_dir": str(tmp_path)}
    ds = lgb.Dataset(X, label=y, params=params).construct()
    try:
        bst = lgb.train(params, ds, num_boost_round=3)
        bst.telemetry.close()
    finally:
        obs_trace.disable()
        obs_trace.reset()
    for rec in _disk_records(tmp_path):
        assert "terms_ms" not in rec or rec["kind"] != "round"
        assert rec.get("timing") is None


def test_profile_auto_follows_observability(tmp_path):
    from lightgbm_tpu.config import Config
    cfg = Config()
    cfg.tpu_profile = "auto"
    assert obs_profiler.RoundProfiler.from_config(cfg) is None
    cfg.tpu_trace = True
    prof = obs_profiler.RoundProfiler.from_config(cfg)
    assert prof is not None and prof.every == cfg.tpu_profile_every


# ---------------------------------------------------------------------------
# timing-mode contract in the ledger schema
# ---------------------------------------------------------------------------

def test_ledger_timing_mode_validation():
    base = {"kind": "round", "round": 0, "wall_ms": 1.0,
            "device_ms": 0.5, "traces": 0, "path": "fused",
            "aligned": False, "fallbacks": 0, "trees": 1}
    obs_ledger.validate_record(dict(base, timing="residual"))
    obs_ledger.validate_record(dict(base, timing="fenced",
                                    profiled=True,
                                    terms_ms={"build": 0.5}))
    with pytest.raises(ValueError, match="timing"):
        obs_ledger.validate_record(dict(base, timing="banana"))
    with pytest.raises(ValueError, match="profiled"):
        obs_ledger.validate_record(dict(base, profiled="yes"))
    with pytest.raises(ValueError, match="terms_ms"):
        obs_ledger.validate_record(dict(base,
                                        terms_ms={"not_a_term": 1.0}))
    with pytest.raises(ValueError, match="terms_ms"):
        obs_ledger.validate_record(dict(base, terms_ms={"build": "x"}))


# ---------------------------------------------------------------------------
# one vocabulary: ledger terms == offline tool terms
# ---------------------------------------------------------------------------

def _tool_attr(name, attr):
    path = os.path.join(REPO, "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_tool_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    argv = sys.argv
    sys.argv = [path]       # tools parse sys.argv at import time
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.argv = argv
    return getattr(mod, attr)


@pytest.mark.parametrize("tool", ["device_time_r4", "device_time_255",
                                  "profile_mslr"])
def test_offline_tools_use_canonical_terms(tool):
    measured = _tool_attr(tool, "TERMS_MEASURED")
    assert measured, f"{tool} declares no TERMS_MEASURED"
    unknown = set(measured) - set(TERMS)
    assert not unknown, \
        f"{tool} measures non-canonical terms {sorted(unknown)}"


def test_site_map_is_canonical():
    assert set(SITE_TERMS.values()) <= set(TERMS)
    for obj in RANKING_OBJECTIVES:
        assert term_for_site("objective.grad", obj) == "rank_grad"
    assert term_for_site("objective.grad", "binary") == "grad"
    assert term_for_site("no.such.site", "binary") == "other"


def test_ingest_and_quant_terms_catalogued():
    """The streaming-ingest and quantized-hist planes publish through
    the same closed term vocabulary as the train loop: bench records an
    `ingest` term and the quant path a `quant_pack` term, so both must
    be catalogued and schema-valid."""
    for key in ("ingest", "quant_pack"):
        assert key in TERMS and TERMS[key], key
    assert validate_terms_ms({"ingest": 12.5, "quant_pack": None}) is None
    assert validate_terms_ms({"ingest": "fast"}) is not None


# ---------------------------------------------------------------------------
# XLA cost attribution (CPU smoke)
# ---------------------------------------------------------------------------

def test_cost_analysis_smoke(tmp_path):
    import jax
    import jax.numpy as jnp
    compile_cache.enable_arg_capture()
    try:
        f = compile_cache.program(
            ("test.cost_smoke", 32),
            lambda: jax.jit(lambda x: jnp.sin(x) @ x.T))
        for _ in range(2):
            f(jnp.ones((32, 32), jnp.float32))
        progs = compile_cache.captured_programs()
        ent = next(e for e in progs.values()
                   if e["tag"].startswith("test.cost_smoke:"))
        assert ent["calls"] == 2 and ent["dispatch_ms"] > 0
        # live buffers are never retained — only abstract specs
        assert all(isinstance(s, jax.ShapeDtypeStruct)
                   for s in ent["spec_args"])
        costs = obs_profiler.collect_program_costs()
        # the CPU backend gets NO roofline (never a nominal one), so
        # the rows carry XLA's counts but no bound classification
        assert costs["device"] is None
        tag = ent["tag"]
        row = costs["programs"][tag]
        assert "error" not in row, row
        assert row["flops"] > 0 and row["bytes_accessed"] > 0
        assert "bound" not in row
        assert row["dispatch_ms_per_call"] > 0
        path = obs_profiler.write_program_costs(
            str(tmp_path / "program_costs.json"))
        doc = json.load(open(path))
        assert doc["schema"] == 1 and tag in doc["programs"]
    finally:
        compile_cache.clear_captured()


def test_roofline_classification():
    roof = {"kind": "test", "peak_tflops": 1.0,    # 1e12 flop/s
            "hbm_gbps": 100.0}                     # 1e11 B/s
    # 1e9 flops, 1e6 bytes -> compute-bound (1 ms compute vs 0.01 ms bw)
    c = obs_profiler.classify_program(1e9, 1e6, roof)
    assert c["bound"] == "compute"
    assert c["est_ms"] == pytest.approx(1.0, rel=0.01)
    # 1e6 flops, 1e9 bytes -> bandwidth-bound (10 ms bw)
    b = obs_profiler.classify_program(1e6, 1e9, roof)
    assert b["bound"] == "bandwidth"
    assert b["est_ms"] == pytest.approx(10.0, rel=0.01)
    assert b["arithmetic_intensity"] == pytest.approx(1e-3)


# ---------------------------------------------------------------------------
# the ranked report: MSLR-shaped run names rank_grad
# ---------------------------------------------------------------------------

def test_bottleneck_report_names_rank_grad(tmp_path):
    """The acceptance path: a lambdarank run profiled on CPU, report
    ranks rank_grad as the top term."""
    rng = np.random.default_rng(5)
    n, f, qs = 6000, 4, 120
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = rng.integers(0, 5, n).astype(np.float64)
    group = np.full(n // qs, qs, dtype=np.int64)
    params = {"objective": "lambdarank", "num_leaves": 4, "max_bin": 15,
              "min_data_in_leaf": 20, "verbosity": -1, "metric": "none",
              "tpu_trace": True, "tpu_trace_dir": str(tmp_path),
              "tpu_profile": "on", "tpu_profile_every": 2}
    ds = lgb.Dataset(X, label=y, group=group, params=params).construct()
    try:
        bst = lgb.train(params, ds, num_boost_round=5)
        prof = bst.profiler
        assert prof is not None
        prof.summary(str(tmp_path))       # writes program_costs.json
        bst.telemetry.close()
    finally:
        obs_trace.disable()
        obs_trace.reset()
        compile_cache.clear_captured()

    out = str(tmp_path / "report.json")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "bottleneck_report.py"),
         "--trace-dir", str(tmp_path), "--json", out],
        capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0, r.stderr
    report = json.load(open(out))
    ranked = report["ranked_terms"]
    assert ranked, "no ranked terms in report"
    assert ranked[0]["term"] == "rank_grad", \
        f"expected rank_grad on top, got {ranked}"
    assert "bottleneck report" in r.stdout
    assert report["programs"], "program_costs.json not merged"


def test_bottleneck_report_golden_bench_record():
    """Committed BENCH fixture alone produces a ranked report."""
    bench = os.path.join(REPO, "tests", "data",
                         "BENCH_profiler_golden.json")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "bottleneck_report.py"),
         "--bench", bench],
        capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0, r.stderr
    assert "mslr" in r.stdout and "rank_grad" in r.stdout


def test_bottleneck_report_no_input_exits_2(tmp_path):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "bottleneck_report.py"),
         "--trace-dir", str(tmp_path / "empty")],
        capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 2


# ---------------------------------------------------------------------------
# bench_compare attributes a regression to a term (informational only)
# ---------------------------------------------------------------------------

def test_bench_compare_terms_attribution(tmp_path):
    base = {"metric": "higgs_500iter_s", "value": 100.0,
            "terms_by_stage": {"mslr": {"rank_grad": 100.0,
                                        "build": 50.0}}}
    cand = {"metric": "higgs_500iter_s", "value": 101.0,
            "terms_by_stage": {"mslr": {"rank_grad": 118.0,
                                        "build": 51.0}}}
    pa, pb = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    json.dump(base, open(pa, "w"))
    json.dump(cand, open(pb, "w"))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "bench_compare.py"),
         pa, pb, "--gate"],
        capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0, r.stderr        # terms never gate
    v = json.loads(r.stdout)
    mslr = v["terms_by_stage"]["mslr"]
    assert mslr["verdict"] == "informational"
    assert mslr["attribution"] == "mslr: rank_grad +18%"
    assert "terms_by_stage" not in v["metrics"]
