"""Unified run timeline (`obs/timeline.py`) + per-device distributed
attribution + straggler/anomaly watches (`obs/straggler.py`).

Covers: the exactly-once stream merge into valid Chrome-trace JSON,
per-device terms summing to the aggregate fenced terms on a 4-shard
run, watch hysteresis and anomaly-detector units, the zero-fence
guarantee with the timeline off, the export CLI's exit contract, the
interrupted-BENCH regression (BENCH_r05), the bench-record START emit
and bench_compare's informational per-device block.

The three real-training legs (4-shard per-device sums, forced anomaly,
export CLI on a live trace dir) are marked slow to keep the quick tier
at its wall — the full tier and the ci/test.sh timeline smoke run them
on every CI pass; the quick tier keeps the synthetic exactly-once
merge, the watch units, and the zero-fence-off assertion.
"""
import importlib.util
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import ledger as obs_ledger
from lightgbm_tpu.obs import timeline as obs_timeline
from lightgbm_tpu.obs import trace as obs_trace
from lightgbm_tpu.obs.straggler import (AnomalyWatch, ImbalanceWatch,
                                        imbalance_ratio)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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
         "timing": "fenced", "terms_ms": {"build": 400.0},
         "device_ids": [0, 1], "device_round_ms": [300.0, 100.0],
         "device_terms_ms": {"build": [300.0, 100.0]},
         "imbalance": 1.5},
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
    bench = [
        {"kind": "note", "stage": "datagen", "t_s": 4.0, "t0": t,
         "t1": t + 4.0, "wall_s": 4.0},
    ]
    _write_jsonl(tmp_path / "spans-1.jsonl", spans)
    _write_jsonl(tmp_path / "ledger-1.jsonl", ledger)
    _write_jsonl(tmp_path / "reqtrace-1.jsonl", reqtrace)
    _write_jsonl(tmp_path / "events-1.jsonl", events)
    _write_jsonl(tmp_path / "bench-1.jsonl", bench)
    return {"spans": 4, "train_rounds": 2, "sweep_rounds": 1,
            "requests": 1, "events": 2, "bench": 1, "notes": 1,
            "device_segments": 2}


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
    assert by_src["ledger.device"] == want["device_segments"]
    assert by_src["ledger.note"] == want["notes"]
    assert by_src["reqtrace"] == want["requests"]
    assert by_src["events"] == want["events"]
    # dist_stream expands into wall+parse+bin pipeline bars
    assert by_src["ingest"] == 3
    assert by_src["bench"] == want["bench"]
    lanes = obs_timeline.lane_counts(doc)
    assert lanes == {"spans": 4, "train": 2, "sweep": 1, "serving": 1,
                     "events": 2, "ingest": 3, "bench": 1}
    assert doc["otherData"]["device_lanes"] == 2
    assert obs_timeline.has_data(doc)
    # one shared clock: the anchor is the earliest t0 and every placed
    # event is non-negative relative to it
    assert doc["otherData"]["anchor_t0"] == pytest.approx(1000.0)
    assert all(e["ts"] >= 0 for e in evs if e["ph"] != "M")
    # lane metadata names each populated process lane
    pnames = {e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "process_name"}
    assert {"train", "spans", "serving", "ingest", "sweep", "bench",
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
# per-device attribution on a real 4-shard run
# ---------------------------------------------------------------------------

DIST = {"objective": "binary", "num_leaves": 7, "learning_rate": 0.2,
        "min_data_in_leaf": 5, "verbosity": -1, "metric": "none",
        "tree_learner": "data", "num_machines": 4,
        "tpu_profile": "on", "tpu_profile_every": 2}


def _train_dist_profiled(tmp_path, rounds=6, extra=None):
    X, y = _data()
    params = dict(DIST, tpu_trace=True, tpu_trace_dir=str(tmp_path))
    if extra:
        params.update(extra)
    ds = lgb.Dataset(X, label=y, params=params).construct()
    try:
        bst = lgb.train(params, ds, num_boost_round=rounds)
        led = bst.telemetry
        led.close()
        return [r for r in led.round_records()
                if r.get("timing") == "fenced"]
    finally:
        obs_trace.disable()
        obs_trace.reset()


@pytest.mark.slow
def test_per_device_terms_sum_to_aggregate(tmp_path):
    profiled = _train_dist_profiled(tmp_path)
    assert profiled, "no profiled rounds sampled"
    # skip the first sample (aggregate includes trace/compile); later
    # samples must tile: per-term device columns sum to the fenced
    # aggregate term, and the device totals to the summed terms
    rec = profiled[-1]
    assert rec["device_ids"] == [0, 1, 2, 3]
    dterms = rec["device_terms_ms"]
    assert set(dterms) == set(rec["terms_ms"])
    for term, cols in dterms.items():
        assert len(cols) == 4
        agg = rec["terms_ms"][term]
        assert sum(cols) <= agg * 1.05 + 0.5
        assert sum(cols) >= agg * 0.5 - 0.5, \
            f"{term}: device columns {cols} lost too much of {agg}"
    total_dev = sum(rec["device_round_ms"])
    total_agg = sum(rec["terms_ms"].values())
    assert total_dev == pytest.approx(total_agg, rel=0.5, abs=2.0)
    assert rec["imbalance"] >= 1.0
    split = rec["allreduce_split_ms"]
    assert set(split) == {"compute", "wait"}
    assert split["compute"] >= 0 and split["wait"] >= 0
    # the on-disk records re-validate (schema covers the new columns)
    import glob as _glob
    path = sorted(_glob.glob(str(tmp_path / "ledger-*.jsonl")))[-1]
    for r in obs_ledger.read_ledger(path):
        obs_ledger.validate_record(r)
    # and the timeline grows one lane per device
    doc = obs_timeline.build_timeline(str(tmp_path))
    assert doc["otherData"]["device_lanes"] == 4
    tnames = {e["args"]["name"] for e in doc["traceEvents"]
              if e["ph"] == "M" and e["name"] == "thread_name"
              and e["pid"] == obs_timeline.LANES["train"]}
    assert {"device 0", "device 1", "device 2", "device 3"} <= tnames


def test_ledger_validates_device_terms(tmp_path):
    led = obs_ledger.RoundLedger(str(tmp_path / "led.jsonl"))
    base = {"kind": "round", "round": 0, "wall_ms": 1.0,
            "device_ms": 1.0, "traces": 0, "path": "fused",
            "aligned": False, "fallbacks": 0, "trees": 1}
    with pytest.raises(ValueError, match="device_terms_ms"):
        led.commit(dict(base, device_terms_ms={"nonsense_term": [1.0]}))
    with pytest.raises(ValueError, match="device_terms_ms"):
        led.commit(dict(base,
                        device_terms_ms={"build": [1.0], "grad": [1.0,
                                                                  2.0]}))
    with pytest.raises(ValueError, match="imbalance"):
        led.commit(dict(base, imbalance=-2.0))
    led.commit(dict(base, device_terms_ms={"build": [1.0, 2.0],
                                           "grad": [0.1, 0.2]},
                    imbalance=1.5))
    led.close()


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
    # tpu_timeline=on arms the host-side watches; without tpu_trace or
    # tpu_profile there must still be ZERO device fences
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


# ---------------------------------------------------------------------------
# satellite: interrupted BENCH records
# ---------------------------------------------------------------------------

def test_bottleneck_report_accepts_truncated_wrapper(tmp_path):
    """Regression: a timeout-truncated driver wrapper record (rc=124,
    parsed:null) must produce a report and exit 0, not rc 2."""
    rec = tmp_path / "bench_truncated.json"
    rec.write_text(json.dumps({
        "n": 5, "cmd": "python bench.py", "rc": 124, "parsed": None,
        "tail": "# gen=23.1s rows=10500000 features=28 leaves=255\n"
                "#   continue to 500 iters: 250.2s\n"}))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "bottleneck_report.py"),
         "--bench", str(rec)],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "INTERRUPTED RUN" in r.stdout
    assert "rc=124" in r.stdout


def test_bottleneck_report_incomplete_info_units():
    br = _load_tool("bottleneck_report")
    # new-style BenchRecorder record killed mid-mslr
    rec = {"incomplete": True, "stage_reached": "mslr",
           "elapsed_s": 100.0, "stages_done": ["datagen", "higgs63"],
           "stage_wall_s": {"datagen": 10.0, "higgs63": 60.0},
           "interrupted_by": "SIGTERM",
           "terms_by_stage": {"higgs63": {"build": 400.0}}}
    info = br.incomplete_info(rec)
    assert info["stage_reached"] == "mslr"
    assert info["time_in_stage_s"] == pytest.approx(30.0)
    assert info["interrupted_by"] == "SIGTERM"
    # wrapper with rc but a complete parsed record still flags the rc
    assert br.incomplete_info(
        {"rc": 124, "parsed": None, "tail": "# gen=1s",
         "n": 5, "cmd": "x"})["killed_by_timeout"] is True
    # complete records stay silent
    assert br.incomplete_info({"value": 1.0, "incomplete": False}) is None
    assert br.incomplete_info(
        {"rc": 0, "parsed": {"value": 1.0}, "n": 1, "cmd": "x"}) is None
    # ranked terms gathered so far still report alongside
    stages, _ = br.stage_rows(rec)
    assert stages["higgs63"][0]["term"] == "build"


# ---------------------------------------------------------------------------
# satellite: bench-record START emit
# ---------------------------------------------------------------------------

def test_bench_recorder_start_emit_carries_elapsed(tmp_path, capsys):
    from lightgbm_tpu.obs.bench_record import BenchRecorder, BudgetGate
    t0 = time.perf_counter()
    gate = BudgetGate(0, t0=t0)
    out = {"metric": "demo_s", "value": None}
    rec = BenchRecorder(out, path=str(tmp_path / "r.json"),
                        install_traps=False, gate=gate)
    gate.start("datagen")
    time.sleep(0.01)
    gate.done("datagen")
    rec.stage_done("datagen")
    rec.start_stage("mslr")
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    start = lines[-1]
    assert start["stage_reached"] == "mslr"
    assert start["elapsed_s"] >= 0.0
    # cumulative walls of COMPLETED stages ride in the START emit: a
    # kill inside mslr still says what datagen cost
    assert start["stage_wall_s"]["datagen"] > 0.0
    sidecar = json.load(open(tmp_path / "r.json"))
    assert sidecar["stage_reached"] == "mslr"
    assert sidecar["incomplete"] is True
    assert "elapsed_s" in sidecar


# ---------------------------------------------------------------------------
# satellite: bench_compare per-device block
# ---------------------------------------------------------------------------

def _mc_record(per_dev, imb, per_iter=100.0):
    return {"metric": "higgs_synth_500iter_s", "value": 200.0,
            "unit": "s", "mc_device_imbalance": imb,
            "multichip": {"rows": 1000, "iters": 4,
                          "curve": [
                              {"devices": 1, "per_iter_ms": 300.0},
                              {"devices": 4, "per_iter_ms": per_iter,
                               "device_ids": [0, 1, 2, 3],
                               "device_round_ms": per_dev,
                               "device_imbalance": imb}]}}


def test_bench_compare_device_imbalance_informational():
    bc = _load_tool("bench_compare")
    assert bc.DIRECTION["mc_device_imbalance"] == -1
    assert bc.METRIC_STAGE["mc_device_imbalance"] == "multichip"
    base = _mc_record([25.0, 25.0, 25.0, 25.0], 1.0)
    cand = _mc_record([10.0, 10.0, 10.0, 70.0], 7.0)
    verdict = bc.compare([("r01", base), ("r02", cand)])
    dev = verdict["device_imbalance"]
    assert dev["verdict"] == "informational"
    assert dev["devices"]["d3"]["delta_pct"] == pytest.approx(180.0)
    assert dev["imbalance"] == {"base": 1.0, "new": 7.0}
    assert "d3" in dev["attribution"]
    # the scalar gates (lower-is-better), the per-device block never
    # counts toward the verdict tallies
    row = verdict["metrics"]["mc_device_imbalance"]
    assert row["direction"] == "lower_better"
    assert row["verdict"] == "regressed"
    n_rows = sum(verdict["counts"].values())
    assert n_rows == len(verdict["metrics"])
    # absent per-device data: no block, no crash
    v2 = bc.compare([("a", {"value": 1.0}), ("b", {"value": 1.0})])
    assert "device_imbalance" not in v2
