"""Metrics plane (`obs/metrics.py`, `obs/memory.py`,
`serving/exporter.py`): registry semantics, HBM accounting with
reconciliation, the scrape endpoint, the zero-overhead-when-off
guarantee and the torn-tail ledger read.
"""
import gc
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import compile_cache
from lightgbm_tpu.obs import ledger as obs_ledger
from lightgbm_tpu.obs import memory as obs_memory
from lightgbm_tpu.obs import metrics as obs_metrics
from lightgbm_tpu.obs import trace as obs_trace
from lightgbm_tpu.serving.exporter import MetricsExporter, PROM_CONTENT_TYPE

@pytest.fixture(autouse=True)
def _clean_plane():
    obs_metrics.reset()
    obs_memory.reset()
    yield
    obs_metrics.reset()
    obs_memory.reset()


def _data(seed=7, n=600, f=6):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = ((X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
          + 0.3 * rng.standard_normal(n)) > 0).astype(np.float32)
    return X, y


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_monotone():
    c = obs_metrics.registry().counter("t_total", "help text")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)


def test_gauge_set_and_callback():
    g = obs_metrics.registry().gauge("t_gauge")
    g.set(4)
    assert g.value == 4.0
    g.inc(1)
    assert g.value == 5.0
    g.set_fn(lambda: 41 + 1)
    assert g.value == 42.0
    g.set_fn(lambda: 1 / 0)          # broken callback must not raise
    assert np.isnan(g.value)


def test_histogram_buckets_and_quantiles():
    h = obs_metrics.registry().histogram("t_ms")
    h.observe(3.0)                    # lands in (2, 4]
    assert h.count == 1 and h.sum == 3.0
    # linear interpolation inside the covering bucket
    assert h.quantile(0.5) == pytest.approx(3.0)
    for _ in range(99):
        h.observe(3.0)
    assert h.quantile(0.99) == pytest.approx(2.0 + 2.0 * 0.99)
    # beyond the largest finite bound clamps, never returns inf
    h2 = obs_metrics.registry().histogram("t2_ms")
    h2.observe(1e9)
    assert h2.quantile(0.5) == obs_metrics.BUCKET_BOUNDS_MS[-1]
    assert h2.cumulative()[-1] == (float("inf"), 1)
    # empty histogram has no quantile
    assert obs_metrics.registry().histogram("t3_ms").quantile(0.5) is None


def test_labeled_family_children_cached():
    fam = obs_metrics.registry().counter("req_total", "r",
                                         labelnames=("model",))
    a = fam.labels(model="ctr")
    a.inc(2)
    assert fam.labels(model="ctr") is a
    fam.labels(model="cvr").inc()
    assert {k: c.value for k, c in fam.children().items()} == {
        ("ctr",): 2.0, ("cvr",): 1.0}
    with pytest.raises(ValueError, match="labels"):
        fam.labels(wrong="x")


def test_registry_get_or_create_and_type_conflict():
    r = obs_metrics.registry()
    assert r.counter("same_total") is r.counter("same_total")
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("same_total")
    with pytest.raises(ValueError, match="already registered"):
        r.counter("same_total", labelnames=("x",))


def test_snapshot_schema_and_prometheus_text():
    r = obs_metrics.registry()
    r.counter("c_total", "a counter").inc(3)
    r.gauge("g_bytes").set(17)
    h = r.histogram("lat_ms", "latency", )
    h.observe(1.0)
    h.observe(100.0)
    snap = obs_metrics.snapshot()
    assert snap["schema"] == obs_metrics.SCHEMA_VERSION
    assert snap["counters"]["c_total"] == 3.0
    assert snap["gauges"]["g_bytes"] == 17.0
    hs = snap["histograms"]["lat_ms"]
    assert hs["count"] == 2 and hs["sum_ms"] == 101.0
    assert hs["p50_ms"] is not None and hs["p99_ms"] is not None
    assert hs["buckets"]["+Inf"] == 2
    text = obs_metrics.to_prometheus()
    assert "# TYPE c_total counter" in text
    assert "# HELP c_total a counter" in text
    assert "g_bytes 17" in text
    assert 'lat_ms_bucket{le="1"} 1' in text
    assert 'lat_ms_bucket{le="+Inf"} 2' in text
    assert "lat_ms_count 2" in text
    assert "lat_ms_p50" in text and "lat_ms_p99" in text
    # snapshot is JSON-clean
    json.dumps(snap)


def test_note_retry_event_respects_enable():
    obs_metrics.note_retry_event("retry")      # disabled: no-op
    assert obs_metrics.snapshot()["counters"] == {}
    obs_metrics.enable()
    obs_metrics.note_retry_event("recovered")
    assert obs_metrics.snapshot()["counters"][
        'train_retry_events_total{event="recovered"}'] == 1.0


# ---------------------------------------------------------------------------
# HBM accountant
# ---------------------------------------------------------------------------

class _Owner:
    def __init__(self, n):
        self.n = n


def test_memory_owners_and_aggregate_exclusion():
    a, b = _Owner(100), _Owner(28)
    obs_memory.track("train/a", a, lambda o: o.n)
    obs_memory.track("serve/b", b, lambda o: o.n)
    # the pool SUMS a+b: reported but excluded from the claimed total
    obs_memory.track("pool", None, lambda: 128, aggregate=True)
    owners = obs_memory.owners_bytes()
    assert owners["train/a"] == {"bytes": 100, "aggregate": False}
    assert owners["pool"] == {"bytes": 128, "aggregate": True}
    assert obs_memory.claimed_total() == 128
    snap = obs_memory.snapshot()
    assert snap["claimed_bytes"] == 128
    assert snap["aggregates"] == ["pool"]
    assert snap["owners"]["pool"] == 128


def test_memory_weakref_pruning_and_dedup():
    a = _Owner(10)
    name_a = obs_memory.track("x", a, lambda o: o.n)
    b = _Owner(20)
    name_b = obs_memory.track("x", b, lambda o: o.n)   # distinct live obj
    assert name_a == "x" and name_b == "x#2"
    # re-tracking the SAME object replaces in place
    assert obs_memory.track("x", a, lambda o: o.n * 2) == "x"
    assert obs_memory.owners_bytes()["x"]["bytes"] == 20
    del a
    gc.collect()
    owners = obs_memory.owners_bytes()                # dead row pruned
    assert set(owners) == {"x#2"}
    # a dead slot is reused by the next same-named registration
    assert obs_memory.track("x#2", _Owner(1), lambda o: o.n) == "x#2#2"


def test_memory_snapshot_reconciliation_and_peaks():
    big = _Owner(1 << 20)
    obs_memory.track("big", big, lambda o: o.n)
    snap = obs_memory.snapshot()
    assert snap["schema"] == 1
    assert snap["claimed_bytes"] == 1 << 20
    assert snap["peak_claimed_bytes"] == 1 << 20
    # device stats are backend-dependent: None on CPU, ints on TPU —
    # either way the residual is consistent
    if snap["device_bytes_in_use"] is None:
        assert snap["hbm_unattributed_bytes"] is None
    else:
        assert snap["hbm_unattributed_bytes"] == \
            snap["device_bytes_in_use"] - snap["claimed_bytes"]
    obs_memory.untrack("big")
    snap2 = obs_memory.snapshot()
    assert snap2["claimed_bytes"] == 0
    assert snap2["peak_claimed_bytes"] == 1 << 20      # high-water holds
    # gauges published into the metrics registry on every snapshot
    gauges = obs_metrics.snapshot()["gauges"]
    assert gauges["hbm_claimed_total_bytes"] == 0.0
    assert gauges["hbm_peak_claimed_bytes"] == float(1 << 20)


def test_memory_broken_callback_is_zero_not_fatal():
    keep = _Owner(0)
    obs_memory.track("bad", keep, lambda o: 1 / 0)
    assert obs_memory.owners_bytes()["bad"]["bytes"] == 0
    assert obs_memory.snapshot()["claimed_bytes"] == 0


def test_dataset_and_training_register_owners():
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "verbosity": -1, "metric": "none"}
    ds = lgb.Dataset(X, label=y, params=params).construct()
    owners = obs_memory.owners_bytes()
    assert any(n.startswith("dataset/bins") for n in owners)
    bins_bytes = next(v["bytes"] for n, v in owners.items()
                      if n.startswith("dataset/bins"))
    assert bins_bytes == ds._handle.bins.nbytes
    bst = lgb.Booster(params=params, train_set=ds)
    bst.update()
    owners = obs_memory.owners_bytes()
    assert any(n.startswith("train/scores") for n in owners)
    assert obs_memory.claimed_total() > 0


# ---------------------------------------------------------------------------
# torn-tail ledger read (satellite a)
# ---------------------------------------------------------------------------

def test_read_ledger_tolerates_torn_tail(tmp_path):
    path = str(tmp_path / "led.jsonl")
    led = obs_ledger.RoundLedger(path, meta={"config_sig": "s"})
    led.commit({"kind": "note", "note": "x"})
    led.close()
    with open(path) as fh:
        clean = fh.read()
    rows = obs_ledger.read_ledger(path)
    assert rows.torn_tail is False and len(rows) == 2
    # a crash mid-append leaves a torn final line
    with open(path, "w") as fh:
        fh.write(clean + '{"kind": "round", "round": 3, "wal')
    rows = obs_ledger.read_ledger(path)
    assert rows.torn_tail is True
    assert [r["kind"] for r in rows] == ["run", "note"]
    assert isinstance(rows, list)      # callers keep list semantics
    # torn in the MIDDLE is corruption, not a crash artifact
    with open(path, "w") as fh:
        fh.write('{"kind": "run", "schema": 1}\n{bad\n{"kind": "note"}\n')
    with pytest.raises(ValueError):
        obs_ledger.read_ledger(path)


# ---------------------------------------------------------------------------
# zero-overhead-when-off (satellite c)
# ---------------------------------------------------------------------------

def _train(params_extra, rounds=4):
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "verbosity": -1, "metric": "none"}
    params.update(params_extra)
    ds = lgb.Dataset(X, label=y, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(rounds):
        bst.update()
    return bst


def test_metrics_off_is_off(monkeypatch):
    fences = []
    monkeypatch.setattr(obs_trace, "_block",
                        lambda x: fences.append(1) or x)
    bst = _train({})
    assert bst._gbdt._metrics is None      # hot path holds no handle
    assert fences == []
    assert obs_metrics.enabled() is False
    assert obs_metrics.snapshot()["counters"] == {}


def test_metrics_on_untraced_counts_without_fences(monkeypatch):
    fences = []
    monkeypatch.setattr(obs_trace, "_block",
                        lambda x: fences.append(1) or x)
    bst = _train({"tpu_metrics": True}, rounds=4)
    assert fences == [], "metered round path issued a device fence"
    assert bst._gbdt._metrics is not None
    snap = obs_metrics.snapshot()
    assert snap["counters"]["train_rounds_total"] == 4.0
    assert snap["counters"]["train_trees_total"] == 4.0
    hs = snap["histograms"]["train_round_ms"]
    assert hs["count"] == 4 and hs["sum_ms"] > 0
    # booster-level parked snapshot (mirrors bst.telemetry)
    ms = bst.metrics_snapshot()
    assert ms["metrics"]["counters"]["train_rounds_total"] == 4.0
    assert "claimed_bytes" in ms["memory"]


@pytest.mark.slow
def test_metrics_enabled_overhead_under_two_percent():
    """min-of-3 wall over 25 rounds: the metered path (perf_counter +
    a few counter incs per round) must cost < 2% over the default."""
    X, y = _data(n=2000, f=10)
    base = {"objective": "binary", "num_leaves": 16, "max_bin": 63,
            "verbosity": -1, "metric": "none"}

    def run(extra):
        params = dict(base, **extra)
        ds = lgb.Dataset(X, label=y, params=params).construct()
        bst = lgb.Booster(params=params, train_set=ds)
        bst.update()                       # compile outside the window
        t0 = time.perf_counter()
        for _ in range(25):
            bst.update()
        return time.perf_counter() - t0

    run({})                                # shared warmup
    offs, ons = [], []
    for _ in range(4):                     # interleave to cancel drift
        offs.append(run({}))
        ons.append(run({"tpu_metrics": True}))
    t_off, t_on = min(offs), min(ons)
    assert t_on <= t_off * 1.02 + 0.050, \
        f"metrics overhead {t_on / t_off - 1:.2%} (off={t_off:.3f}s)"


# ---------------------------------------------------------------------------
# exporter
# ---------------------------------------------------------------------------

def _get(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, resp.headers.get("Content-Type"), resp.read()


def test_exporter_serves_prometheus_and_json():
    r = obs_metrics.registry()
    r.counter("serve_requests_total", "r").inc(5)
    r.histogram("serve_request_latency_ms", "l",
                ).observe(2.5)
    obs_memory.track("fixture", None, lambda: 4096)
    with MetricsExporter(port=0) as exp:      # ephemeral port, no races
        assert obs_metrics.enabled()
        status, ctype, body = _get(exp.url + "/metrics")
        assert status == 200 and ctype == PROM_CONTENT_TYPE
        text = body.decode()
        assert "serve_requests_total 5" in text
        assert "serve_request_latency_ms_bucket" in text
        assert "serve_request_latency_ms_p99" in text
        assert "hbm_claimed_total_bytes 4096" in text
        status, ctype, body = _get(exp.url + "/metrics.json")
        assert status == 200 and ctype == "application/json"
        doc = json.loads(body)
        assert doc["schema"] == obs_metrics.SCHEMA_VERSION
        assert doc["metrics"]["counters"]["serve_requests_total"] == 5.0
        assert doc["memory"]["claimed_bytes"] == 4096
        status, _, body = _get(exp.url + "/healthz")
        assert status == 200 and body == b"ok\n"
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(exp.url + "/nope")
        assert ei.value.code == 404
    # closed: the port no longer answers
    with pytest.raises(Exception):
        _get(f"http://127.0.0.1:{exp.port}/healthz")


# ---------------------------------------------------------------------------
# trace summary compile-cache attribution (satellite b)
# ---------------------------------------------------------------------------

def test_trace_write_extra_and_miss_attribution(tmp_path):
    obs_trace.reset()
    obs_trace.enable(str(tmp_path))
    try:
        with obs_trace.span("demo"):
            pass
        extra = {"compile_cache": {
            "miss_by_program": compile_cache.miss_attribution(),
            "traces": compile_cache.trace_count()}}
        out = obs_trace.write(str(tmp_path / "trace_summary.json"),
                              extra=extra)
    finally:
        obs_trace.disable()
        obs_trace.reset()
    doc = json.load(open(out))
    assert "compile_cache" in doc
    assert isinstance(doc["compile_cache"]["miss_by_program"], dict)
    assert doc["summary"]["demo"]["count"] == 1
