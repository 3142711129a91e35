"""Telemetry subsystem (`lightgbm_tpu.obs`): ledger schema, per-round
records on both training paths and the zero-fence disabled guarantee.
"""
import glob
import json
import os
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import ledger as obs_ledger
from lightgbm_tpu.obs import trace as obs_trace

ALIGNED = {"tpu_grow_mode": "aligned", "tpu_aligned_interpret": True,
           "tpu_chunk": 256}


def _data(seed=3, n=900, f=8):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] * X[:, 2]
          + 0.3 * rng.standard_normal(n)) > 0).astype(np.float32)
    return X, y


def _train_traced(tmp_path, extra=None, rounds=5, valid=False):
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "min_data_in_leaf": 20, "verbosity": -1, "metric": "binary_logloss",
              "tpu_trace": True, "tpu_trace_dir": str(tmp_path)}
    if extra:
        params.update(extra)
    ds = lgb.Dataset(X, label=y, params=params).construct()
    kw = {}
    if valid:
        kw = {"valid_sets": [ds], "valid_names": ["train"]}
    try:
        bst = lgb.train(params, ds, num_boost_round=rounds, **kw)
        led = bst.telemetry
        assert led is not None
        led.close()
        return bst, led
    finally:
        obs_trace.disable()
        obs_trace.reset()


# ---------------------------------------------------------------------------
# ledger schema
# ---------------------------------------------------------------------------

def test_ledger_schema_roundtrip(tmp_path):
    path = str(tmp_path / "led.jsonl")
    led = obs_ledger.RoundLedger(path, meta={"config_sig": "abc"})
    for i in range(3):
        led.commit({"kind": "round", "round": i, "wall_ms": 1.5,
                    "device_ms": 0.2, "traces": 0, "path": "fused",
                    "aligned": False, "fallbacks": 0, "trees": i + 1})
    led.record_eval(2, [("train", "auc", 0.9, True)])
    led.commit({"kind": "note", "stage": "demo", "t_s": 1.0})
    led.close()

    recs = obs_ledger.read_ledger(path)
    for rec in recs:
        obs_ledger.validate_record(rec)
    assert [r["kind"] for r in recs] == \
        ["run", "round", "round", "round", "eval", "note"]
    assert recs[0]["schema"] == obs_ledger.SCHEMA_VERSION
    assert recs[4] == {"kind": "eval", "round": 2,
                       "values": {"train:auc": 0.9}}
    # eval also folded into the in-memory mirror for the callback seam
    assert led.last_round()["eval"] == {"train:auc": 0.9}


def test_ledger_rejects_malformed_records(tmp_path):
    led = obs_ledger.RoundLedger(str(tmp_path / "bad.jsonl"))
    with pytest.raises(ValueError, match="kind"):
        led.commit({"round": 0})
    with pytest.raises(ValueError, match="missing fields"):
        led.commit({"kind": "round", "round": 0})
    with pytest.raises(ValueError, match="aligned"):
        led.commit({"kind": "round", "round": 0, "wall_ms": 1.0,
                    "device_ms": 0.0, "traces": 0, "path": "x",
                    "aligned": "yes", "fallbacks": 0, "trees": 1})
    with pytest.raises(ValueError, match="round index"):
        led.commit({"kind": "eval", "values": {}})
    led.close()


# ---------------------------------------------------------------------------
# per-round records from real training, both paths
# ---------------------------------------------------------------------------

def _check_rounds(tmp_path, led, rounds, aligned):
    rr = led.round_records()
    assert [r["round"] for r in rr] == list(range(rounds))
    for r in rr:
        for k in obs_ledger.ROUND_REQUIRED:
            assert k in r, f"round record missing {k}: {r}"
        assert r["aligned"] is aligned
        assert r["wall_ms"] >= 0 and r["device_ms"] >= 0
    # every record is already durable on disk (one JSONL line per round)
    paths = sorted(glob.glob(os.path.join(str(tmp_path),
                                          "ledger-*.jsonl")))
    assert paths
    disk = obs_ledger.read_ledger(paths[-1])
    for rec in disk:
        obs_ledger.validate_record(rec)
    assert disk[0]["kind"] == "run" and "config_sig" in disk[0]
    assert [r["round"] for r in disk if r["kind"] == "round"] == \
        list(range(rounds))
    return rr, disk


def test_ledger_timing_mode_validation():
    base = {"kind": "round", "round": 0, "wall_ms": 1.0,
            "device_ms": 0.5, "traces": 0, "path": "fused",
            "aligned": False, "fallbacks": 0, "trees": 1}
    obs_ledger.validate_record(dict(base, timing="residual"))
    obs_ledger.validate_record(dict(base, timing="fenced",
                                    profiled=True,
                                    terms_ms={"sweep": 0.5}))
    with pytest.raises(ValueError, match="timing"):
        obs_ledger.validate_record(dict(base, timing="banana"))
    with pytest.raises(ValueError, match="profiled"):
        obs_ledger.validate_record(dict(base, profiled="yes"))
    with pytest.raises(ValueError, match="terms_ms"):
        obs_ledger.validate_record(dict(base,
                                        terms_ms={"not_a_term": 1.0}))
    with pytest.raises(ValueError, match="terms_ms"):
        obs_ledger.validate_record(dict(base, terms_ms={"sweep": "x"}))


def test_round_records_fused_path(tmp_path):
    _, led = _train_traced(
        tmp_path, {"bagging_fraction": 0.8, "bagging_freq": 1},
        rounds=5, valid=True)
    rr, disk = _check_rounds(tmp_path, led, 5, aligned=False)
    # eval values folded in by the auto-attached log_telemetry callback
    assert all("eval" in r for r in rr)
    evals = [r for r in disk if r["kind"] == "eval"]
    assert [e["round"] for e in evals] == list(range(5))
    assert all("train:binary_logloss" in e["values"] for e in evals)
    assert all(r["traces"] >= 0 for r in rr)


def test_round_records_aligned_path(tmp_path):
    _, led = _train_traced(tmp_path, ALIGNED, rounds=3)
    rr, _disk = _check_rounds(tmp_path, led, 3, aligned=True)
    assert all(r["path"].startswith("aligned") for r in rr)
    # first round traces the programs; identical later rounds reuse them
    assert rr[0]["traces"] > 0
    assert rr[1]["traces"] == 0 and rr[2]["traces"] == 0


def test_traced_run_emits_spans_and_fences(tmp_path):
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "min_data_in_leaf": 20, "verbosity": -1, "metric": "none",
              "tpu_trace": True, "tpu_trace_dir": str(tmp_path)}
    params.update(ALIGNED)
    ds = lgb.Dataset(X, label=y, params=params).construct()
    try:
        obs_trace.reset()
        lgb.train(params, ds, num_boost_round=3)
        names = {s["name"] for s in obs_trace.spans()}
    finally:
        obs_trace.disable()
    assert {"train.round", "train.round.fence",
            "aligned.dispatch"} <= names
    assert obs_trace.fence_count >= 3
    # span JSONL mirrors the in-memory records line by line
    span_files = glob.glob(os.path.join(str(tmp_path), "spans-*.jsonl"))
    assert span_files
    with open(span_files[-1]) as fh:
        on_disk = [json.loads(ln) for ln in fh if ln.strip()]
    assert {s["name"] for s in on_disk} >= {"train.round"}
    # the end-of-run dump aggregates per span name
    out = obs_trace.write(str(tmp_path / "trace_summary.json"))
    doc = json.load(open(out))
    assert doc["summary"]["train.round"]["count"] == 3
    obs_trace.reset()


# ---------------------------------------------------------------------------
# the disabled path adds ZERO fences
# ---------------------------------------------------------------------------

def test_disabled_training_issues_zero_fences(monkeypatch):
    calls = []
    monkeypatch.setattr(obs_trace, "_block",
                        lambda x: calls.append(1) or x)
    obs_trace.reset()
    X, y = _data(n=400)
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "verbosity": -1, "metric": "none"}
    ds = lgb.Dataset(X, label=y, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(3):
        bst.update()
    assert bst._gbdt.telemetry is None
    assert calls == [], "untraced training called the tracing fence"
    assert obs_trace.fence_count == 0
    assert obs_trace.spans() == []


# ---------------------------------------------------------------------------
# enabled-mode overhead stays small (slow tier; 2% is the TPU HIGGS
# mb=63 budget — CPU wall clock is noisier, so the gate here is looser)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_traced_overhead_small(tmp_path):
    X, y = _data(seed=11, n=20_000, f=16)
    params = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
              "verbosity": -1, "metric": "none"}

    def run(extra):
        p = dict(params, **extra)
        ds = lgb.Dataset(X, label=y, params=p).construct()
        bst = lgb.Booster(params=p, train_set=ds)
        for _ in range(5):   # warm: compile everything first
            bst.update()
        t0 = time.perf_counter()
        for _ in range(30):
            bst.update()
        np.asarray(bst.predict(X[:64], raw_score=True))
        return time.perf_counter() - t0

    try:
        base = min(run({}) for _ in range(2))
        traced = min(run({"tpu_trace": True,
                          "tpu_trace_dir": str(tmp_path)})
                     for _ in range(2))
    finally:
        obs_trace.disable()
        obs_trace.reset()
    assert traced <= base * 1.25, \
        f"tracing overhead {traced / base - 1:.1%} (base {base:.3f}s)"
