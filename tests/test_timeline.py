"""Unified run timeline (`obs/timeline.py`) + straggler/anomaly watches
(`obs/straggler.py`).

Covers: the exactly-once stream merge into valid Chrome-trace JSON,
watch hysteresis and anomaly-detector units, the zero-fence guarantee
with the timeline on and the tracer off, and the export CLI's exit
contract.

The real-training legs (forced anomaly, export CLI on a trace dir) are
marked slow to keep the quick tier at its wall; the quick tier keeps the
synthetic exactly-once merge, the watch units, and the zero-fence
assertion.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import ledger as obs_ledger
from lightgbm_tpu.obs import timeline as obs_timeline
from lightgbm_tpu.obs import trace as obs_trace
from lightgbm_tpu.obs.straggler import (AnomalyWatch, ImbalanceWatch,
                                        imbalance_ratio)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _data(seed=3, n=400, f=8):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] * X[:, 2]
          + 0.3 * rng.standard_normal(n)) > 0).astype(np.float32)
    return X, y


# ---------------------------------------------------------------------------
# watch units
# ---------------------------------------------------------------------------

def test_imbalance_ratio():
    assert imbalance_ratio([10.0, 10.0, 10.0, 30.0]) == 3.0
    assert imbalance_ratio([5.0]) is None          # nothing to compare
    assert imbalance_ratio([0.0, 0.0]) is None     # degenerate median
    assert imbalance_ratio([2.0, 2.0, 2.0]) == 1.0


def test_straggler_hysteresis_raise_then_clear():
    w = ImbalanceWatch(threshold=1.5, rounds=2)
    # two hot rounds raise once; two cool rounds clear once; repeats
    # of either state stay silent (edge-triggered, not level)
    edges = [w.update(r) for r in (2.0, 2.0, 2.0, 1.0, 1.0, 1.0)]
    assert edges == [None, "raised", None, None, "cleared", None]
    assert w.raised is False
    # a single hot blip below the K-round requirement never raises
    w2 = ImbalanceWatch(threshold=1.5, rounds=3)
    assert [w2.update(r) for r in (9.0, 1.0, 9.0, 1.0)] == [None] * 4


def test_straggler_clear_level_is_hysteretic():
    # clear threshold sits BELOW the raise threshold: ratios oscillating
    # between them neither re-raise nor clear
    w = ImbalanceWatch(threshold=2.0, rounds=1)
    assert w.update(3.0) == "raised"
    assert w.clear < 2.0
    assert w.update(1.8) is None          # below raise, above clear
    assert w.update(1.0) == "cleared"


def test_anomaly_watch_fires_on_spike_edge():
    w = AnomalyWatch(factor=2.0, window=8, min_rounds=3)
    hits = [w.update(ms) for ms in (10, 10, 10, 50, 50, 10, 10)]
    fired = [h for h in hits if h]
    assert len(fired) == 1                 # edge: the spike fires once
    assert hits[3] is not None
    assert hits[3]["ratio"] == pytest.approx(5.0)
    assert hits[3]["median_ms"] == pytest.approx(10.0)
    # anomalous walls never enter the window: the median is still 10
    assert w.update(50)["median_ms"] == pytest.approx(10.0)


def test_anomaly_watch_needs_baseline():
    w = AnomalyWatch(factor=2.0, window=8, min_rounds=3)
    # the first rounds build the baseline; nothing can fire yet
    assert w.update(100.0) is None
    assert w.update(1.0) is None


# ---------------------------------------------------------------------------
# the merge: exactly-once, valid Chrome trace
# ---------------------------------------------------------------------------

def _write_jsonl(path, rows):
    with open(path, "w") as fh:
        for r in rows:
            fh.write(json.dumps(r) + "\n")


def _synth_trace_dir(tmp_path):
    t = 1000.0
    spans = [
        {"kind": "span", "name": "train.round", "round": i,
         "t0": t + i, "dur_ms": 500.0, "depth": 0}
        for i in range(3)
    ] + [{"kind": "span", "name": "train.round.fence", "round": 0,
          "t0": t + 0.4, "dur_ms": 1.0, "depth": 1}]
    ledger = [
        {"kind": "run", "schema": obs_ledger.SCHEMA_VERSION,
         "config_sig": "x", "pid": 1},
        {"kind": "round", "round": 0, "wall_ms": 500.0,
         "device_ms": 1.0, "traces": 2, "path": "fused",
         "aligned": False, "fallbacks": 0, "trees": 1, "t0": t},
        {"kind": "round", "round": 1, "wall_ms": 480.0,
         "device_ms": 400.0, "traces": 0, "path": "fused",
         "aligned": False, "fallbacks": 0, "trees": 2, "t0": t + 1,
         "timing": "fenced", "terms_ms": {"build": 400.0}},
        {"kind": "round", "round": 0, "wall_ms": 50.0, "device_ms": 0.0,
         "traces": 0, "path": "sweep", "aligned": False, "fallbacks": 0,
         "trees": 1, "t0": t + 2, "subfleet": 1, "model": 3},
        {"kind": "note", "note": "round_anomaly", "round": 2,
         "wall_ms": 900.0, "ratio": 3.1, "t0": t + 2.5},
    ]
    reqtrace = [
        {"kind": "request", "trace_id": "r1", "model": "m", "rows": 16,
         "t_submit": t + 3, "total_ms": 12.0, "status": "done"},
        {"kind": "batch", "batch_id": "b1"},        # not a request row
    ]
    events = [
        {"kind": "event", "event": "train_path", "path": "fused",
         "t0": t + 0.1},
        {"kind": "event", "event": "dist_stream", "t0": t + 0.9,
         "rows": 100, "wall_ms": 800.0, "t_start": t + 0.1,
         "parse_ms": 500.0, "bin_ms": 600.0},
    ]
    _write_jsonl(tmp_path / "spans-1.jsonl", spans)
    _write_jsonl(tmp_path / "ledger-1.jsonl", ledger)
    _write_jsonl(tmp_path / "reqtrace-1.jsonl", reqtrace)
    _write_jsonl(tmp_path / "events-1.jsonl", events)
    return {"spans": 4, "train_rounds": 2, "sweep_rounds": 1,
            "requests": 1, "events": 2, "notes": 1}


def test_timeline_exactly_once_roundtrip(tmp_path):
    want = _synth_trace_dir(tmp_path)
    doc = obs_timeline.build_timeline(str(tmp_path))
    evs = doc["traceEvents"]
    # valid Chrome-trace JSON: serializable, every event has the
    # required keys, X events carry numeric ts+dur
    json.loads(json.dumps(doc))
    for e in evs:
        assert e["ph"] in ("X", "i", "M")
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float))
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
    # exactly-once: each source row appears as exactly one event,
    # tagged with its stream in args.src
    by_src = {}
    for e in evs:
        src = (e.get("args") or {}).get("src")
        if src:
            by_src[src] = by_src.get(src, 0) + 1
    assert by_src["spans"] == want["spans"]
    assert by_src["ledger"] == want["train_rounds"] + want["sweep_rounds"]
    assert by_src["ledger.note"] == want["notes"]
    assert by_src["reqtrace"] == want["requests"]
    assert by_src["events"] == want["events"]
    # dist_stream expands into wall+parse+bin pipeline bars
    assert by_src["ingest"] == 3
    lanes = obs_timeline.lane_counts(doc)
    assert lanes == {"spans": 4, "train": 2, "sweep": 1, "serving": 1,
                     "events": 2, "ingest": 3}
    assert obs_timeline.has_data(doc)
    # one shared clock: the anchor is the earliest t0 and every placed
    # event is non-negative relative to it
    assert doc["otherData"]["anchor_t0"] == pytest.approx(1000.0)
    assert all(e["ts"] >= 0 for e in evs if e["ph"] != "M")
    # lane metadata names each populated process lane
    pnames = {e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "process_name"}
    assert {"train", "spans", "serving", "ingest", "sweep",
            "events"} == pnames


def test_timeline_empty_inputs(tmp_path):
    doc = obs_timeline.build_timeline(str(tmp_path / "missing"))
    assert not obs_timeline.has_data(doc)
    assert doc["traceEvents"] == []


def test_timeline_torn_tail_tolerated(tmp_path):
    with open(tmp_path / "spans-1.jsonl", "w") as fh:
        fh.write(json.dumps({"kind": "span", "name": "a", "t0": 5.0,
                             "dur_ms": 1.0, "depth": 0}) + "\n")
        fh.write('{"kind": "span", "name": "b", "t0"')   # torn flush
    doc = obs_timeline.build_timeline(str(tmp_path))
    assert obs_timeline.lane_counts(doc)["spans"] == 1


# ---------------------------------------------------------------------------
# anomaly watch on a real run + zero-overhead-off
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_forced_round_anomaly_commits_note(tmp_path):
    # factor<1 makes any round "anomalous" the moment the baseline
    # exists — deterministic without timing games
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "metric": "none", "min_data_in_leaf": 5,
              "tpu_trace": True, "tpu_trace_dir": str(tmp_path),
              "tpu_anomaly_factor": 0.5, "tpu_anomaly_window": 4}
    ds = lgb.Dataset(X, label=y, params=params).construct()
    try:
        bst = lgb.train(params, ds, num_boost_round=8)
        led = bst.telemetry
        led.close()
        notes = [r for r in obs_ledger.read_ledger(
            sorted(__import__("glob").glob(
                str(tmp_path / "ledger-*.jsonl")))[-1])
            if r.get("kind") == "note"
            and r.get("note") == "round_anomaly"]
    finally:
        obs_trace.disable()
        obs_trace.reset()
    assert notes, "forced anomaly never committed a ledger note"
    n = notes[0]
    assert n["ratio"] >= 0.0 and n["wall_ms"] >= 0.0 and "round" in n
    # and it lands on the timeline as an instant
    doc = obs_timeline.build_timeline(str(tmp_path))
    anoms = [e for e in doc["traceEvents"]
             if e.get("name") == "round_anomaly"]
    assert anoms


def test_timeline_on_without_trace_adds_zero_fences(monkeypatch):
    # tpu_timeline=on arms the host-side watches; without tpu_trace
    # there must still be ZERO device fences
    calls = []
    monkeypatch.setattr(obs_trace, "_block",
                        lambda x: calls.append(1) or x)
    obs_trace.reset()
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "metric": "none", "min_data_in_leaf": 5,
              "tpu_timeline": "on"}
    ds = lgb.Dataset(X, label=y, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(3):
        bst.update()
    assert calls == [], "tpu_timeline=on fenced an untraced run"
    assert obs_trace.fence_count == 0


def test_timeline_knob_runtime_only_and_validated(tmp_path):
    from lightgbm_tpu.models.model_text import _RUNTIME_ONLY_PARAMS
    for k in ("tpu_timeline", "tpu_straggler_threshold",
              "tpu_straggler_rounds", "tpu_anomaly_factor",
              "tpu_anomaly_window"):
        assert k in _RUNTIME_ONLY_PARAMS
    X, y = _data(n=200)
    params = {"objective": "binary", "num_leaves": 4, "verbosity": -1,
              "metric": "none", "tpu_timeline": "on"}
    ds = lgb.Dataset(X, label=y, params=params).construct()
    bst = lgb.train(params, ds, num_boost_round=2)
    assert "tpu_timeline" not in bst.model_to_string()
    with pytest.raises(Exception, match="tpu_timeline"):
        lgb.train(dict(params, tpu_timeline="sideways"), ds,
                  num_boost_round=1)


# ---------------------------------------------------------------------------
# export CLI
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_timeline_export_cli(tmp_path):
    _synth_trace_dir(tmp_path)
    out = tmp_path / "tl.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "timeline_export.py"),
         "--trace-dir", str(tmp_path), "--out", str(out)],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr
    doc = json.load(open(out))
    assert doc["traceEvents"]
    # empty dir: artifact still written, exit 2 signals "nothing there"
    empty = tmp_path / "empty"
    empty.mkdir()
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "timeline_export.py"),
         "--trace-dir", str(empty)],
        capture_output=True, text=True, env=env, timeout=120)
    assert r2.returncode == 2, r2.stderr
    assert json.load(open(empty / "timeline.json"))["traceEvents"] == []


# ---------------------------------------------------------------------------
# exporter endpoint
# ---------------------------------------------------------------------------

def test_debug_timeline_endpoint(tmp_path):
    import urllib.request
    from lightgbm_tpu.serving.exporter import MetricsExporter
    _synth_trace_dir(tmp_path)
    with MetricsExporter(0, trace_dir=str(tmp_path)) as exp:
        doc = json.loads(urllib.request.urlopen(
            exp.url + "/debug/timeline", timeout=10).read())
        assert doc["traceEvents"]
        assert doc["otherData"]["lanes"]["train"] == 2
    with MetricsExporter(0) as exp2:
        doc = json.loads(urllib.request.urlopen(
            exp2.url + "/debug/timeline", timeout=10).read())
        assert doc == {"schema": 1, "enabled": False}
