"""Serving service (lightgbm_tpu.serving): model registry with HBM-budget
LRU eviction, request coalescer SLO behavior, checkpoint watcher under a
concurrent writer and zero-downtime hot swap.
"""
import json
import os
import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import compile_cache
from lightgbm_tpu.obs.ledger import RoundLedger
from lightgbm_tpu.serving import (CheckpointWatcher, ModelRegistry,
                                  RequestCoalescer, ServingService)
from lightgbm_tpu.serving.registry import load_checkpoint_model_text
from lightgbm_tpu.utils.log import (parse_event, register_callback,
                                    set_verbosity)

PARAMS = {"objective": "binary", "num_leaves": 7, "learning_rate": 0.1,
          "min_data_in_leaf": 5, "verbosity": -1}


def _data(seed=0, n=400, f=8):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f)
    y = (X[:, 0] + 0.3 * rng.rand(n) > 0.6).astype(np.float64)
    return X, y


def _booster(seed=0, rounds=8, params=None):
    X, y = _data(seed)
    p = dict(PARAMS, seed=seed, **(params or {}))
    return lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=rounds), X


@pytest.fixture
def events():
    """Capture structured [Event] lines. Training with verbosity=-1
    lowers the global log level (silencing events), so tests that train
    boosters mid-test must call set_verbosity(1) again before the
    event-emitting operation under test."""
    lines = []
    register_callback(lines.append)
    set_verbosity(1)
    yield lambda kind: [r for r in map(parse_event, lines)
                        if r and r["event"] == kind]
    register_callback(None)
    set_verbosity(1)


# ---------------------------------------------------------------- registry

def test_registry_parity_and_byte_accounting():
    bst, X = _booster()
    reg = ModelRegistry()
    entry = reg.load("m", model_str=bst.model_to_string())
    margins, _ = entry.engine.predict(X)
    np.testing.assert_allclose(margins[:, 0],
                               bst.predict(X, raw_score=True), rtol=1e-6)
    # byte accounting == the engine's actual device-resident arrays
    expect = sum(int(v.nbytes) for v in entry.engine._stk.values())
    if entry.engine._route is not None:
        expect += sum(int(v.nbytes)
                      for v in entry.engine._route.values())
    assert entry.bytes == expect > 0
    assert reg.total_bytes() == entry.bytes
    assert reg.stats()["models"]["m"]["bytes"] == expect


def test_registry_multiclass_shapes():
    rng = np.random.RandomState(3)
    X = rng.rand(300, 6)
    y = np.floor(X[:, 0] * 2.999)
    p = dict(PARAMS, objective="multiclass", num_class=3)
    bst = lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=5)
    reg = ModelRegistry()
    entry = reg.load("mc", model_str=bst.model_to_string())
    assert entry.num_class == 3
    margins, _ = entry.engine.predict(X)
    np.testing.assert_allclose(margins, bst.predict(X, raw_score=True),
                               rtol=1e-6)


def test_registry_load_sources(tmp_path):
    bst, X = _booster()
    path = tmp_path / "m.txt"
    bst.save_model(str(path))
    reg = ModelRegistry()
    e1 = reg.load("from_file", model_file=str(path))
    # checkpoint source: resolved ONLY through the MANIFEST.json pointer
    Xt, yt = _data(seed=5)
    ckdir = str(tmp_path / "ck")
    lgb.train(dict(PARAMS, tpu_checkpoint_dir=ckdir, tpu_checkpoint_freq=2),
              lgb.Dataset(Xt, label=yt), num_boost_round=6)
    e2 = reg.load("from_ckpt", checkpoint_dir=ckdir)
    assert e2.version.startswith("ckpt_")
    assert e2.source == ckdir
    m1, _ = e1.engine.predict(X)
    np.testing.assert_allclose(m1[:, 0], bst.predict(X, raw_score=True),
                               rtol=1e-6)
    with pytest.raises(ValueError):
        reg.load("bad", model_str="x", model_file="y")
    with pytest.raises(KeyError):
        reg.acquire("never_loaded")


def test_lru_eviction_order(events):
    texts = [_booster(seed=s)[0].model_to_string() for s in range(4)]
    set_verbosity(1)
    reg = ModelRegistry()
    probe = reg.load("probe", model_str=texts[0])
    one = probe.bytes
    # budget fits 2.5 models of this size: the third load must evict
    reg = ModelRegistry(hbm_budget_mb=one * 2.5 / 2**20)
    reg.load("m1", model_str=texts[0])
    reg.load("m2", model_str=texts[1])
    reg.load("m3", model_str=texts[2])          # evicts LRU = m1
    assert reg.names() == ["m2", "m3"]
    reg.acquire("m2")                            # m2 now most recent
    reg.load("m4", model_str=texts[3])          # evicts LRU = m3, NOT m2
    assert reg.names() == ["m2", "m4"]
    assert reg.evicted == ["m1", "m3"]
    assert reg.stats()["evictions"] == 2
    assert len(events("serve_evict")) == 2
    # evicted models are gone for real
    with pytest.raises(KeyError):
        reg.acquire("m1")


def test_oversized_model_is_protected(events):
    bst, X = _booster()
    set_verbosity(1)
    reg = ModelRegistry(hbm_budget_mb=1.0 / 2**20)   # 1 byte: nothing fits
    reg.load("big", model_str=bst.model_to_string())
    # the entry being loaded is never the victim — budget shapes
    # eviction, it is not an admission gate
    assert reg.names() == ["big"]
    assert events("serve_over_budget")


def test_hot_swap_identical_to_cold_load(tmp_path, events):
    led_path = str(tmp_path / "led.jsonl")
    ledger = RoundLedger(led_path, {"test": "serving"})
    b1, X = _booster(seed=0)
    b2, _ = _booster(seed=1)
    set_verbosity(1)
    reg = ModelRegistry(ledger=ledger)
    reg.load("m", model_str=b1.model_to_string())
    old_engine = reg.acquire("m").engine
    entry = reg.swap("m", b2.model_to_string(), version="v2")
    cold = ModelRegistry().load("cold", model_str=b2.model_to_string())
    hot, _ = entry.engine.predict(X)
    want, _ = cold.engine.predict(X)
    np.testing.assert_array_equal(hot, want)
    # the displaced engine still scores for whoever holds it
    m_old, _ = old_engine.predict(X)
    np.testing.assert_allclose(m_old[:, 0], b1.predict(X, raw_score=True),
                               rtol=1e-6)
    assert reg.acquire("m").version == "v2"
    swaps = events("serve_swap")
    assert len(swaps) == 1 and swaps[0]["version"] == "v2"
    ledger.close()
    notes = [json.loads(ln) for ln in open(led_path)]
    assert sum(1 for r in notes
               if r.get("note") == "serve_swap") == 1    # exactly once


# --------------------------------------------------------------- coalescer

def test_coalescer_parity_and_never_split():
    b1, X = _booster(seed=0)
    b2, _ = _booster(seed=1)
    reg = ModelRegistry()
    reg.load("a", model_str=b1.model_to_string())
    reg.load("b", model_str=b2.model_to_string())
    with RequestCoalescer(reg, max_batch_wait_ms=2.0,
                          max_batch_rows=64) as co:
        futs = []
        rng = np.random.RandomState(9)
        for i in range(30):
            rows = int(rng.randint(1, 20))
            Xi = X[rng.randint(0, len(X), rows)]
            name = "a" if i % 2 == 0 else "b"
            futs.append((name, Xi, co.submit(name, Xi)))
        # one request larger than max_batch_rows: flushes alone, unsplit
        big = X[rng.randint(0, len(X), 100)]
        futs.append(("a", big, co.submit("a", big)))
        for name, Xi, fut in futs:
            got = fut.result(timeout=60)
            bst = b1 if name == "a" else b2
            assert got.shape == (len(Xi),)    # whole request, one answer
            np.testing.assert_allclose(got, bst.predict(Xi, raw_score=True),
                                       rtol=1e-6)
        st = co.stats()
    assert st["requests"] == 31 and st["failures"] == 0
    assert st["rows"] == sum(len(Xi) for _, Xi, _ in futs)
    assert st["batches"] < st["requests"]     # coalescing actually happened


def test_coalescer_respects_wait_slo():
    bst, X = _booster()
    reg = ModelRegistry()
    reg.load("m", model_str=bst.model_to_string())
    with RequestCoalescer(reg, max_batch_wait_ms=150.0,
                          max_batch_rows=4096) as co:
        co.submit("m", X[:4]).result(timeout=60)   # warm the program
        t0 = time.perf_counter()
        co.submit("m", X[:4]).result(timeout=60)
        dt = time.perf_counter() - t0
        st = co.stats()
    # a lone request flushes on the deadline: not (much) before the SLO,
    # and certainly not unboundedly after
    assert 0.10 <= dt < 10.0
    assert st["flush_deadline"] >= 1


def test_coalescer_full_bucket_flushes_early():
    bst, X = _booster()
    reg = ModelRegistry()
    reg.load("m", model_str=bst.model_to_string())
    with RequestCoalescer(reg, max_batch_wait_ms=5000.0,
                          max_batch_rows=256) as co:
        co.submit("m", X[:1]).result(timeout=60)   # warm (deadline... no:
        # 1-row request under a 5 s SLO would block; use a full bucket)
        t0 = time.perf_counter()
        f1 = co.submit("m", X[:128])
        f2 = co.submit("m", X[128:256])
        f1.result(timeout=60)
        f2.result(timeout=60)
        dt = time.perf_counter() - t0
        st = co.stats()
    assert dt < 4.0                       # did NOT wait out the 5 s SLO
    assert st["flush_full"] >= 1


def test_coalescer_error_delivery_and_close():
    bst, X = _booster()
    reg = ModelRegistry()
    reg.load("m", model_str=bst.model_to_string())
    co = RequestCoalescer(reg, max_batch_wait_ms=1.0)
    bad = co.submit("nope", X[:2])
    with pytest.raises(KeyError):
        bad.result(timeout=60)
    with pytest.raises(ValueError):
        co.submit("m", X[0])              # 1-D request matrix
    assert co.stats()["failures"] == 1
    co.close()
    with pytest.raises(RuntimeError):
        co.submit("m", X[:2])


def test_coalescer_error_batches_kept_in_request_accounting():
    """Regression: requests that die in a failed batch must still show
    up in the per-model completion counters — completed ok + error
    equals requests submitted, even under injected engine errors."""
    from lightgbm_tpu.obs import metrics as obs_metrics
    obs_metrics.reset()
    obs_metrics.enable()
    try:
        bst, X = _booster()
        reg = ModelRegistry()
        reg.load("m", model_str=bst.model_to_string())
        co = RequestCoalescer(reg, max_batch_wait_ms=1.0)
        futs_bad = [co.submit("nope", X[:2]) for _ in range(3)]
        futs_ok = [co.submit("m", X[:2]) for _ in range(2)]
        for f in futs_bad:
            with pytest.raises(KeyError):
                f.result(timeout=60)
        for f in futs_ok:
            f.result(timeout=60)
        co.close()
        snap = obs_metrics.snapshot()["counters"]
        ok = snap.get('serve_requests_completed_total'
                      '{model="m",status="ok"}', 0.0)
        err = snap.get('serve_requests_completed_total'
                       '{model="nope",status="error"}', 0.0)
        assert ok == 2.0 and err == 3.0
        assert ok + err == snap["serve_requests_total"] == 5.0
        assert snap["serve_failures_total"] == 3.0
    finally:
        obs_metrics.reset()


def test_coalescer_wait_slo_is_not_a_floor():
    """5 s SLO must not make a 1-row request take 5 s when close() drains
    (regression guard for shutdown hangs)."""
    bst, X = _booster()
    reg = ModelRegistry()
    reg.load("m", model_str=bst.model_to_string())
    co = RequestCoalescer(reg, max_batch_wait_ms=5000.0)
    fut = co.submit("m", X[:1])
    t0 = time.perf_counter()
    co.close(drain=True)                  # drain flushes the queue now
    assert fut.result(timeout=60).shape == (1,)
    assert time.perf_counter() - t0 < 4.0


# ----------------------------------------------------------------- watcher

def _write_ckpt(directory, version, model_text, atomic=True):
    d = os.path.join(directory, version)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "model.txt"), "w") as fh:
        fh.write(model_text)
    man = json.dumps({"latest": version, "round": 1})
    path = os.path.join(directory, "MANIFEST.json")
    if atomic:
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            fh.write(man)
        os.replace(tmp, path)
    else:
        with open(path, "w") as fh:
            fh.write(man)


def test_watcher_reads_pointer_only(tmp_path, events):
    bst, X = _booster()
    d = str(tmp_path)
    # garbage ckpt dir that no manifest points to: globbing would see it
    os.makedirs(os.path.join(d, "ckpt_999999"))
    with open(os.path.join(d, "ckpt_999999", "model.txt"), "w") as fh:
        fh.write("NOT A MODEL")
    reg = ModelRegistry()
    w = CheckpointWatcher(reg, "m", d, interval_s=0.01)
    assert w.poll_once() is False          # no manifest yet -> no model
    _write_ckpt(d, "ckpt_000001", bst.model_to_string())
    assert w.poll_once() is True
    assert w.poll_once() is False          # same version: no re-load
    assert reg.acquire("m").version == "ckpt_000001"
    assert reg.stats()["loads"] == 1


def test_watcher_tolerates_torn_manifest_and_model(tmp_path, events):
    bst, X = _booster(seed=0)
    set_verbosity(1)
    d = str(tmp_path)
    reg = ModelRegistry()
    w = CheckpointWatcher(reg, "m", d, interval_s=0.01)
    # torn manifest (half a JSON object, non-atomic writer mid-write)
    with open(os.path.join(d, "MANIFEST.json"), "w") as fh:
        fh.write('{"latest": "ckpt_0')
    assert w.poll_once() is False          # unreadable -> retry, no raise
    # manifest pointing at a torn model.txt
    _write_ckpt(d, "ckpt_000001", "")      # zero-length model text
    assert w.poll_once() is False
    assert events("serve_watch_bad_model")
    assert reg.get("m") is None
    # writer finishes: the same pointer now resolves
    _write_ckpt(d, "ckpt_000002", bst.model_to_string())
    assert w.poll_once() is True
    assert reg.acquire("m").version == "ckpt_000002"


def test_watcher_concurrent_writer_hot_swaps(tmp_path):
    """A writer thread publishing versions (with torn intermediate
    states) while the watcher polls and clients predict: no request ever
    fails, the watcher converges on the final version, and each distinct
    version is installed at most once."""
    boosters = [_booster(seed=s, rounds=4)[0] for s in range(4)]
    X = _data()[0][:16]
    d = str(tmp_path)
    versions = [f"ckpt_{i:06d}" for i in range(1, len(boosters) + 1)]

    def writer():
        for i, (v, b) in enumerate(zip(versions, boosters)):
            # torn manifest precedes every good publish
            with open(os.path.join(d, "MANIFEST.json"), "w") as fh:
                fh.write('{"latest"')
            time.sleep(0.005)
            _write_ckpt(d, v, b.model_to_string())
            time.sleep(0.03)

    reg = ModelRegistry()
    w = CheckpointWatcher(reg, "m", d, interval_s=0.005)
    wt = threading.Thread(target=writer)
    wt.start()
    w.start()
    # first version may take a few ticks to land
    deadline = time.time() + 30
    while reg.get("m") is None and time.time() < deadline:
        time.sleep(0.005)
    assert reg.get("m") is not None
    failures = 0
    while wt.is_alive():
        try:
            reg.acquire("m").engine.predict(X)
        except Exception:
            failures += 1
    wt.join()
    deadline = time.time() + 30
    while (reg.acquire("m").version != versions[-1]
           and time.time() < deadline):
        time.sleep(0.01)
    w.stop()
    assert failures == 0
    assert reg.acquire("m").version == versions[-1]
    assert w.swapped == sorted(set(w.swapped))     # each version once, in order
    margins, _ = reg.acquire("m").engine.predict(X)
    np.testing.assert_allclose(margins[:, 0],
                               boosters[-1].predict(X, raw_score=True),
                               rtol=1e-6)


# ----------------------------------------------------------------- service

def test_service_end_to_end(tmp_path):
    b1, X = _booster(seed=0)
    b2, _ = _booster(seed=1)
    with ServingService(params={"tpu_serve_max_batch_wait_ms": 1.0}) as svc:
        svc.load_model("a", model_str=b1.model_to_string())
        svc.load_model("b", model_str=b2.model_to_string())
        got_a = svc.predict("a", X[:32], timeout=60)
        got_b = svc.predict("b", X[:32], timeout=60)
        np.testing.assert_allclose(got_a, b1.predict(X[:32], raw_score=True),
                                   rtol=1e-6)
        np.testing.assert_allclose(got_b, b2.predict(X[:32], raw_score=True),
                                   rtol=1e-6)
        st = svc.stats()
        assert set(st) == {"registry", "coalescer", "watchers"}
        assert st["registry"]["loads"] == 2
    svc.close()                            # idempotent


def test_service_watch_checkpoint(tmp_path):
    X, y = _data(seed=2)
    ckdir = str(tmp_path / "ck")
    lgb.train(dict(PARAMS, tpu_checkpoint_dir=ckdir, tpu_checkpoint_freq=2),
              lgb.Dataset(X, label=y), num_boost_round=4)
    with ServingService() as svc:
        w = svc.watch("ck", ckdir)
        assert svc.registry.get("ck") is not None    # initial sync load
        out = svc.predict("ck", X[:8], timeout=60)
        assert out.shape == (8,)
        assert svc.stats()["watchers"]["ck"]["versions"] == w.swapped


# ------------------------------------------------------------------- CLI

def test_cli_serve_matches_raw_predict(tmp_path):
    from lightgbm_tpu.cli import Application
    bst, X = _booster()
    model = tmp_path / "m.txt"
    bst.save_model(str(model))
    data = tmp_path / "score.tsv"
    y = np.zeros(len(X))                   # label column (stripped)
    with open(data, "w") as fh:
        for lab, row in zip(y, X):
            fh.write("\t".join(f"{v:.8g}" for v in [lab, *row]) + "\n")
    out_serve = tmp_path / "serve.txt"
    out_pred = tmp_path / "pred.txt"
    rc = Application([
        "task=serve", f"input_model=ctr={model}", f"data={data}",
        f"output_result={out_serve}", "verbosity=-1",
        "tpu_serve_max_batch_wait_ms=1",
    ]).run()
    assert rc == 0
    Application([
        "task=predict", f"input_model={model}", f"data={data}",
        f"output_result={out_pred}", "predict_raw_score=true",
        "verbosity=-1",
    ]).run()
    # both files carry ~1e-6 text precision: compare absolutely
    np.testing.assert_allclose(np.loadtxt(out_serve),
                               np.loadtxt(out_pred), atol=1e-6)


def test_cli_serve_requires_a_model_source():
    from lightgbm_tpu.basic import LightGBMError
    from lightgbm_tpu.cli import Application
    with pytest.raises(LightGBMError):
        Application(["task=serve", "verbosity=-1"]).run()


# ------------------------------------------------- compile-cache miss events

def test_persistent_cache_miss_event_attribution(events):
    compile_cache.install_cache_event_hooks()
    from jax._src import compiler as jax_compiler
    before = compile_cache.persistent_cache_events()["misses"]
    with compile_cache.attribution("unit:probe"):
        jax_compiler.log_persistent_cache_miss("jit_probe", "abc123def")
    after = compile_cache.persistent_cache_events()
    assert after["misses"] == before + 1
    recs = events("compile_cache_miss")
    assert recs and recs[-1]["module"] == "jit_probe"
    assert recs[-1]["program"] == "unit:probe"
    # hits count without emitting an event
    jax_compiler.log_persistent_cache_hit("jit_probe", "abc123def")
    assert compile_cache.persistent_cache_events()["hits"] >= 1


def test_program_registry_attribution_tag():
    key = ("unit_prog", 1, 2)
    fn = compile_cache.program(key, lambda: (
        lambda: compile_cache.current_attribution()))
    # inside the registered program, misses are blamed on its tag
    assert fn() == compile_cache.program_tag(key)
    assert fn().startswith("unit_prog:")
    assert compile_cache.current_attribution() is None   # restored
