#!/usr/bin/env python
"""Traffic simulation for the serving service (lightgbm_tpu/serving/).

Loads >= 2 real boosters into a `ServingService` and measures, on the
current backend:

* **closed-loop throughput**, coalesced vs per-request: N client threads
  hammer small (`rows_per_req`) requests round-robin across the resident
  models, once through the request coalescer and once dispatching
  `ForestEngine.predict` directly per request. The engine pads every
  batch to a pow2 bucket of >= 256 rows, so per-request dispatch of
  16-row requests wastes ~94% of each device call — the coalesced/direct
  ratio is the service's whole reason to exist and is recorded as
  `coalesced_vs_direct`.
* **open-loop QPS sweep**: requests submitted on a fixed schedule
  (arrival times don't wait for completions) for each target QPS;
  records p50/p99 submit-to-result latency, achieved QPS, and batch
  fill.
* **hot-swap under load**: client threads keep scoring model 0 while a
  retrained version is `registry.swap`ped in; asserts ZERO failed
  requests and that post-swap predictions changed to the new model.
* **front-door socket legs** (serving/frontend/): the same traffic
  through a real `POST /v1/score/<model>` socket — 1-client vs N-client
  closed loops (`http_vs_direct` is the coalescing win measured at the
  wire), an open-loop HTTP QPS sweep with p50/p99, a swap-under-load
  leg asserting zero non-200s, and a shed-under-overload leg against a
  deliberately-unmeetable SLO asserting that load shedding trips
  (shed ratio recorded) and that gold traffic is NEVER shed.

Importable as `run(...)` (the CI smoke calls it) or a CLI:

    JAX_PLATFORMS=cpu python tools/bench_serve_traffic.py

Env overrides: BENCH_SMOKE=1 (tiny sizes), BENCH_SERVE_QPS (comma list),
BENCH_SERVE_SECS, BENCH_SERVE_CLIENTS, BENCH_SERVE_MODELS.
"""
import http.client
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _train_models(count, rows, num_features, rounds, seed=0):
    """`count` small real boosters (plus a retrained v2 of model 0 for
    the hot-swap leg) on shared synthetic data. Returns
    (model_texts, v2_text, X)."""
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(seed)
    X = rng.rand(rows, num_features)
    y = (X[:, 0] + 0.3 * rng.randn(rows) > 0.5).astype(float)
    texts = []
    for i in range(count + 1):               # last one is v2 of model 0
        params = {"objective": "binary", "num_leaves": 15,
                  "verbosity": -1, "seed": seed + i,
                  "feature_fraction": 0.9, "feature_fraction_seed": i + 1}
        bst = lgb.train(params, lgb.Dataset(X, label=y),
                        num_boost_round=rounds)
        texts.append(bst.model_to_string())
    return texts[:count], texts[count], X


def _percentiles(lat_s):
    if not lat_s:
        return None, None
    a = np.asarray(lat_s, np.float64) * 1e3
    return round(float(np.percentile(a, 50)), 3), \
        round(float(np.percentile(a, 99)), 3)


def _closed_loop(fn, names, reqs, clients, secs):
    """`clients` threads call fn(name, X) as fast as completions allow
    for `secs`. Returns (requests_done, failures, wall_s, latencies)."""
    stop = time.perf_counter() + secs
    done = [0] * clients
    fails = [0] * clients
    lats = [[] for _ in range(clients)]

    def worker(ci):
        i = ci
        while time.perf_counter() < stop:
            name = names[i % len(names)]
            X = reqs[i % len(reqs)]
            t0 = time.perf_counter()
            try:
                fn(name, X)
                lats[ci].append(time.perf_counter() - t0)
                done[ci] += 1
            except Exception:
                fails[ci] += 1
            i += 1

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(c,))
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return sum(done), sum(fails), wall, [v for ls in lats for v in ls]


def _open_loop(svc, names, reqs, qps, secs):
    """Submit on the arrival schedule regardless of completions; latency
    is submit -> future-done. Returns a per-QPS record dict."""
    interval = 1.0 / qps
    lats = []
    fails = [0]
    lock = threading.Lock()
    futs = []
    t_start = time.perf_counter()
    n_target = max(int(qps * secs), 1)
    for i in range(n_target):
        due = t_start + i * interval
        now = time.perf_counter()
        if due > now:
            time.sleep(due - now)
        t0 = time.perf_counter()
        fut = svc.predict_async(names[i % len(names)],
                                reqs[i % len(reqs)])

        def _done(f, t0=t0):
            with lock:
                if f.exception() is not None:
                    fails[0] += 1
                else:
                    lats.append(time.perf_counter() - t0)
        fut.add_done_callback(_done)
        futs.append(fut)
    for f in futs:
        f.exception(timeout=600)      # wait without re-raising
    wall = time.perf_counter() - t_start
    p50, p99 = _percentiles(lats)
    return {"qps_target": qps,
            "qps_achieved": round(len(futs) / wall, 1),
            "requests": len(futs),
            "failures": fails[0],
            "p50_ms": p50, "p99_ms": p99}


def _hot_swap_under_load(svc, name, v2_text, reqs, clients, secs):
    """Concurrent traffic on `name` while a new version swaps in."""
    stop_at = time.perf_counter() + secs
    counts = {"ok": 0, "fail": 0}
    lock = threading.Lock()

    def worker(ci):
        i = ci
        while time.perf_counter() < stop_at:
            try:
                svc.predict(name, reqs[i % len(reqs)], timeout=600)
                with lock:
                    counts["ok"] += 1
            except Exception:
                with lock:
                    counts["fail"] += 1
            i += 1

    threads = [threading.Thread(target=worker, args=(c,))
               for c in range(clients)]
    for t in threads:
        t.start()
    time.sleep(secs * 0.3)            # traffic established mid-flight
    t0 = time.perf_counter()
    svc.registry.swap(name, v2_text, version="v2", source="traffic-bench")
    swap_s = time.perf_counter() - t0
    for t in threads:
        t.join()
    return {"requests_ok": counts["ok"], "requests_failed": counts["fail"],
            "swap_s": round(swap_s, 3),
            "version_after": svc.registry.acquire(name).version}


# -- front-door socket legs (serving/frontend/) ---------------------------

def _http_post(conn, model, body, headers=None):
    """One scoring POST on a keep-alive connection; returns
    (status, decoded-json-or-None). Reconnects on a dropped socket."""
    hdrs = {"Content-Type": "application/json"}
    if headers:
        hdrs.update(headers)
    for attempt in (0, 1):
        try:
            conn.request("POST", f"/v1/score/{model}", body=body,
                         headers=hdrs)
            resp = conn.getresponse()
            data = resp.read()
            return resp.status, (json.loads(data) if data else None)
        except (http.client.HTTPException, OSError):
            conn.close()
            if attempt:
                raise
    raise RuntimeError("unreachable")


def _http_closed_loop(port, names, bodies, clients, secs):
    """`clients` threads, one keep-alive connection each, POST as fast
    as completions allow. Returns (done, codes{status: n}, wall_s,
    latencies_s)."""
    stop = time.perf_counter() + secs
    codes = {}
    lats = [[] for _ in range(clients)]
    lock = threading.Lock()

    def worker(ci):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        i = ci
        try:
            while time.perf_counter() < stop:
                t0 = time.perf_counter()
                status, _ = _http_post(conn, names[i % len(names)],
                                       bodies[i % len(bodies)])
                lats[ci].append(time.perf_counter() - t0)
                with lock:
                    codes[status] = codes.get(status, 0) + 1
                i += 1
        finally:
            conn.close()

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(c,))
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    flat = [v for ls in lats for v in ls]
    return len(flat), codes, wall, flat


def _http_open_loop(port, names, bodies, qps, secs, workers):
    """Open loop at the wire: request i is DUE at t_start + i/qps and a
    worker pool posts it as soon as it can; latency is measured from
    the scheduled arrival, so pool/queue delay shows up in p99 exactly
    as a real late answer would."""
    interval = 1.0 / qps
    n_target = max(int(qps * secs), 1)
    idx = [0]
    fails = [0]
    lats = []
    lock = threading.Lock()
    t_start = time.perf_counter()

    def worker():
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        try:
            while True:
                with lock:
                    i = idx[0]
                    if i >= n_target:
                        return
                    idx[0] += 1
                due = t_start + i * interval
                now = time.perf_counter()
                if due > now:
                    time.sleep(due - now)
                status, _ = _http_post(conn, names[i % len(names)],
                                       bodies[i % len(bodies)])
                end = time.perf_counter()
                with lock:
                    if status == 200:
                        lats.append(end - due)
                    else:
                        fails[0] += 1
        finally:
            conn.close()

    threads = [threading.Thread(target=worker) for _ in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    p50, p99 = _percentiles(lats)
    return {"qps_target": qps,
            "qps_achieved": round(n_target / wall, 1),
            "requests": n_target, "failures": fails[0],
            "p50_ms": p50, "p99_ms": p99}


def _http_swap_under_load(svc, port, name, v2_text, bodies, clients,
                          secs):
    """Threaded POSTs on `name` while a retrained version swaps in;
    every response through the live swap must be a 200."""
    stop_at = time.perf_counter() + secs
    codes = {}
    lock = threading.Lock()

    def worker(ci):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        i = ci
        try:
            while time.perf_counter() < stop_at:
                status, _ = _http_post(conn, name, bodies[i % len(bodies)])
                with lock:
                    codes[status] = codes.get(status, 0) + 1
                i += 1
        finally:
            conn.close()

    threads = [threading.Thread(target=worker, args=(c,))
               for c in range(clients)]
    for t in threads:
        t.start()
    time.sleep(secs * 0.3)            # traffic established mid-flight
    t0 = time.perf_counter()
    svc.registry.swap(name, v2_text, version="v2",
                      source="traffic-bench-http")
    swap_s = time.perf_counter() - t0
    for t in threads:
        t.join()
    ok = codes.get(200, 0)
    bad = sum(n for c, n in codes.items() if c != 200)
    return {"requests_ok": ok, "requests_failed": bad,
            "swap_s": round(swap_s, 3),
            "version_after": svc.registry.acquire(name).version}


def _http_shed_leg(texts, bodies, clients, secs, say):
    """Overload a bronze model against an unmeetable SLO (every request
    breaches 0.05ms, so its burn rate saturates) while gold traffic
    rides along; sheds must trip for bronze and NEVER for gold."""
    from lightgbm_tpu.serving import ServingService
    from lightgbm_tpu.serving.frontend import ScoringFrontend

    svc = ServingService(params={
        "tpu_serve_max_batch_wait_ms": 1.0,
        "tpu_serve_max_batch_rows": 2048,
        "tpu_serve_warm_rows": 256,
        "tpu_serve_trace": True,
        "tpu_serve_slo_ms": 0.05,
        "tpu_serve_qos": "gold_m:gold,bulk_m:bronze",
    })
    try:
        svc.load_model("gold_m", model_str=texts[0])
        svc.load_model("bulk_m", model_str=texts[-1])
        fe = ScoringFrontend(svc, port=0)
        try:
            conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                              timeout=120)
            # warm the burn window past _BURN_MIN_N finished outcomes
            # (every one breaches the 0.05ms SLO), then let the 50ms
            # shed-state refresh observe the saturated rate
            for i in range(24):
                _http_post(conn, "bulk_m", bodies[i % len(bodies)])
            conn.close()
            time.sleep(0.1)

            codes = {"gold_m": {}, "bulk_m": {}}
            lock = threading.Lock()
            stop_at = time.perf_counter() + max(secs, 1.0)

            def worker(ci, model):
                c = http.client.HTTPConnection("127.0.0.1", fe.port,
                                               timeout=120)
                i = ci
                try:
                    while time.perf_counter() < stop_at:
                        status, _ = _http_post(c, model,
                                               bodies[i % len(bodies)])
                        with lock:
                            codes[model][status] = \
                                codes[model].get(status, 0) + 1
                        i += 1
                finally:
                    c.close()

            threads = ([threading.Thread(target=worker,
                                         args=(c, "bulk_m"))
                        for c in range(max(clients - 2, 2))]
                       + [threading.Thread(target=worker,
                                           args=(c, "gold_m"))
                          for c in range(2)])
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            astats = svc.admission.stats()
        finally:
            fe.close()
    finally:
        svc.close()

    bulk_ok = codes["bulk_m"].get(200, 0)
    bulk_shed = codes["bulk_m"].get(429, 0)
    gold_shed = codes["gold_m"].get(429, 0)
    rec = {
        "sheds": astats["sheds"],
        "sheds_by_class": astats["sheds_by_class"],
        "bulk_ok": bulk_ok, "bulk_shed_429": bulk_shed,
        "gold_ok": codes["gold_m"].get(200, 0),
        "gold_shed_429": gold_shed,
        "shed_ratio": round(bulk_shed / max(bulk_ok + bulk_shed, 1), 4),
    }
    say(f"http shed: {rec}")
    # the leg's whole point: overload sheds SOME bronze traffic and
    # ZERO gold traffic — gold starvation would be a policy bug
    assert rec["sheds"] > 0 and bulk_shed > 0, rec
    assert gold_shed == 0, rec
    assert "gold" not in astats["sheds_by_class"], rec
    return rec


def _frontdoor_legs(texts, v2_text, reqs, rows_per_req, clients, secs,
                    qps_list, wait_ms, max_batch_rows, say):
    """All four socket legs; returns the http_* record fields."""
    from lightgbm_tpu.serving import ServingService
    from lightgbm_tpu.serving.frontend import ScoringFrontend

    bodies = [json.dumps({"rows": r.tolist()}).encode()
              for r in reqs[:16]]
    names = [f"m{i}" for i in range(len(texts))]
    svc = ServingService(params={
        "tpu_serve_max_batch_wait_ms": wait_ms,
        "tpu_serve_max_batch_rows": max_batch_rows,
        "tpu_serve_warm_rows": 256,
        "tpu_serve_qos": f"{names[0]}:gold,default:bronze",
    })
    try:
        for name, text in zip(names, texts):
            svc.load_model(name, model_str=text)
        for name in names:
            svc.registry.acquire(name).warm(512)
        fe = ScoringFrontend(svc, port=0)
        try:
            # single-request socket baseline: one request in flight at
            # a time means the coalescer can never merge anything
            n_dir, codes_dir, wall_dir, _ = _http_closed_loop(
                fe.port, names, bodies, 1, secs)
            direct_rows_s = n_dir * rows_per_req / wall_dir
            say(f"http direct (1 client): {n_dir} reqs in "
                f"{wall_dir:.2f}s ({direct_rows_s:,.0f} rows/s)")

            n_co, codes_co, wall_co, lat_co = _http_closed_loop(
                fe.port, names, bodies, clients, secs)
            coalesced_rows_s = n_co * rows_per_req / wall_co
            say(f"http coalesced ({clients} clients): {n_co} reqs in "
                f"{wall_co:.2f}s ({coalesced_rows_s:,.0f} rows/s)")

            sweep = []
            for qps in qps_list:
                rec = _http_open_loop(fe.port, names, bodies, qps, secs,
                                      workers=clients)
                say(f"http open loop qps={qps}: "
                    f"achieved={rec['qps_achieved']} "
                    f"p50={rec['p50_ms']}ms p99={rec['p99_ms']}ms "
                    f"failures={rec['failures']}")
                sweep.append(rec)

            swap = _http_swap_under_load(svc, fe.port, names[0], v2_text,
                                         bodies, clients, max(secs, 1.0))
            say(f"http hot swap: {swap}")
            assert swap["requests_failed"] == 0, swap
            assert swap["version_after"] == "v2", swap
        finally:
            fe.close()
    finally:
        svc.close()

    shed = _http_shed_leg(texts, bodies, clients, secs, say)
    p50, p99 = _percentiles(lat_co)
    fails = sum(n for c, n in list(codes_dir.items())
                + list(codes_co.items()) if c != 200)
    return {
        "http_direct_rows_s": round(direct_rows_s, 1),
        "http_coalesced_rows_s": round(coalesced_rows_s, 1),
        "http_vs_direct": round(
            coalesced_rows_s / max(direct_rows_s, 1e-9), 2),
        "http_p50_ms": p50, "http_p99_ms": p99,
        "http_closed_failures": fails,
        "http_qps_sweep": sweep,
        "http_swap": swap,
        "http_shed": shed,
        "http_shed_ratio": shed["shed_ratio"],
    }


def run(models: int = 2, rows_per_req: int = 16, qps_list=(50, 200, 800),
        open_secs: float = 2.0, closed_secs: float = 2.0, clients: int = 32,
        train_rows: int = 8000, train_rounds: int = 60,
        num_features: int = 20, wait_ms: float = 1.0,
        max_batch_rows: int = 2048, hbm_budget_mb: float = 0.0,
        seed: int = 0, ledger=None, verbose: bool = False,
        trace_dir=None, trace_sample: float = 1.0,
        slo_ms: float = 0.0, frontdoor: bool = True) -> dict:
    from lightgbm_tpu.serving import ServingService

    def say(msg):
        if verbose:
            print(f"[bench_serve] {msg}", file=sys.stderr, flush=True)

    t_all = time.perf_counter()
    texts, v2_text, X = _train_models(models, train_rows, num_features,
                                      train_rounds, seed)
    say(f"trained {models} models (+1 swap candidate) "
        f"in {time.perf_counter() - t_all:.1f}s")

    svc_params = {
        "tpu_serve_max_batch_wait_ms": wait_ms,
        "tpu_serve_max_batch_rows": max_batch_rows,
        "tpu_serve_hbm_budget_mb": hbm_budget_mb,
        "tpu_serve_warm_rows": 256,
    }
    if trace_dir is not None:
        # request-tracing leg: every request spans through obs/reqtrace
        svc_params.update({
            "tpu_serve_trace": True,
            "tpu_serve_trace_dir": str(trace_dir),
            "tpu_serve_trace_sample": trace_sample,
            "tpu_serve_slo_ms": slo_ms,
        })
    svc = ServingService(params=svc_params, ledger=ledger)
    names = [f"m{i}" for i in range(models)]
    try:
        t0 = time.perf_counter()
        for name, text in zip(names, texts):
            svc.load_model(name, model_str=text)
        # pre-warm every pow2 bucket the coalescer can dispatch, so the
        # measurement sees steady-state programs (and the swap leg
        # inherits the warmed bucket set)
        for name in names:
            entry = svc.registry.acquire(name)
            b = 512
            while b <= max_batch_rows:
                entry.warm(b)
                b *= 2
        warm_s = time.perf_counter() - t0
        say(f"load+warm: {warm_s:.1f}s "
            f"({svc.registry.total_bytes()} bytes resident)")

        rng = np.random.default_rng(seed + 99)
        reqs = [np.ascontiguousarray(
                    X[rng.integers(0, len(X), rows_per_req)])
                for _ in range(64)]

        # -- closed loop: direct per-request dispatch baseline -------------
        def direct(name, Xr):
            svc.registry.acquire(name).engine.predict(Xr)
        n_dir, f_dir, wall_dir, lat_dir = _closed_loop(
            direct, names, reqs, clients, closed_secs)
        direct_rows_s = n_dir * rows_per_req / wall_dir
        say(f"direct: {n_dir} reqs in {wall_dir:.2f}s "
            f"({direct_rows_s:,.0f} rows/s)")

        # -- closed loop: coalesced through the service --------------------
        def coalesced(name, Xr):
            svc.predict(name, Xr, timeout=600)
        n_co, f_co, wall_co, lat_co = _closed_loop(
            coalesced, names, reqs, clients, closed_secs)
        coalesced_rows_s = n_co * rows_per_req / wall_co
        say(f"coalesced: {n_co} reqs in {wall_co:.2f}s "
            f"({coalesced_rows_s:,.0f} rows/s)")

        # -- open-loop QPS sweep -------------------------------------------
        sweep = []
        for qps in qps_list:
            rec = _open_loop(svc, names, reqs, qps, open_secs)
            say(f"open loop qps={qps}: achieved={rec['qps_achieved']} "
                f"p50={rec['p50_ms']}ms p99={rec['p99_ms']}ms "
                f"failures={rec['failures']}")
            sweep.append(rec)

        # -- hot swap under load -------------------------------------------
        swap = _hot_swap_under_load(svc, names[0], v2_text, reqs,
                                    clients, max(closed_secs, 1.0))
        say(f"hot swap: {swap}")

        # -- front-door socket legs (fresh services on ephemeral ports;
        # the main svc and its ledger/tracer stay untouched) ---------------
        fd = {}
        if frontdoor:
            fd = _frontdoor_legs(texts, v2_text, reqs, rows_per_req,
                                 clients, closed_secs, qps_list, wait_ms,
                                 max_batch_rows, say)

        p50d, p99d = _percentiles(lat_dir)
        p50c, p99c = _percentiles(lat_co)
        stats = svc.stats()
        trace_rec = {}
        if svc.tracer is not None:
            # drain in-flight batches so started == finished before the
            # totals are read (close() is idempotent; the finally-close
            # below is then a no-op)
            svc.coalescer.close()
            trace_rec["serve_trace"] = svc.tracer.totals()
        return dict(trace_rec, **fd, **{
            "serve_models": models,
            "serve_rows_per_req": rows_per_req,
            "serve_clients": clients,
            "serve_warm_s": round(warm_s, 2),
            "serve_direct_rows_s": round(direct_rows_s, 1),
            "serve_coalesced_rows_s": round(coalesced_rows_s, 1),
            "coalesced_vs_direct": round(
                coalesced_rows_s / max(direct_rows_s, 1e-9), 2),
            "serve_direct_p50_ms": p50d, "serve_direct_p99_ms": p99d,
            "serve_coalesced_p50_ms": p50c, "serve_coalesced_p99_ms": p99c,
            "serve_closed_failures": f_dir + f_co,
            "serve_qps_sweep": sweep,
            "serve_hot_swap": swap,
            "serve_fill_ratio": stats["coalescer"]["fill_ratio"],
            "serve_batches": stats["coalescer"]["batches"],
            "serve_requests": stats["coalescer"]["requests"],
            "serve_flush_full": stats["coalescer"]["flush_full"],
            "serve_flush_deadline": stats["coalescer"]["flush_deadline"],
            "serve_evictions": stats["registry"]["evictions"],
            "serve_swaps": stats["registry"]["swaps"],
            "serve_resident_bytes": stats["registry"]["total_bytes"],
            "serve_wall_s": round(time.perf_counter() - t_all, 1),
        })
    finally:
        svc.close()


def main() -> int:
    smoke = os.environ.get("BENCH_SMOKE", "") not in ("", "0")
    env = os.environ.get
    qps = tuple(int(q) for q in
                env("BENCH_SERVE_QPS",
                    "25,100" if smoke else "50,200,800").split(","))
    res = run(
        models=int(env("BENCH_SERVE_MODELS", 2)),
        qps_list=qps,
        open_secs=float(env("BENCH_SERVE_SECS", 1.0 if smoke else 2.0)),
        closed_secs=float(env("BENCH_SERVE_SECS", 1.0 if smoke else 2.0)),
        clients=int(env("BENCH_SERVE_CLIENTS", 16 if smoke else 32)),
        train_rows=1500 if smoke else 8000,
        train_rounds=20 if smoke else 60,
        verbose=True)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
