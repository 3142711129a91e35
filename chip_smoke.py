#!/usr/bin/env python
"""Chip smoke: train, rank and score end to end on the TPU, once.

    python chip_smoke.py        (only ever through the chip tool)

The quickest proof that the system still starts on the chip. One process
drives the user-facing entry points at the full width of the two
reference experiments and fails the moment anything is off:

  train      HIGGS shape (10.5M x 28, binary, 255 bins, 255 leaves)
             through lgb.Dataset + lgb.train for 10 iterations. Asserts
             the run took the aligned path and never left it, the native
             library built, scores are finite, every tree split, and the
             holdout AUC is sanely above 0.5.
  rank       MS-LTR shape (2.27M x 137, lambdarank, 255 bins) for 3
             iterations. Asserts the aligned path with the HBM hist
             spill, the segment-fused rank kernel still active after its
             first dispatch, and that its gradients agree with the
             bucketed oracle on the chip.
  score      Booster.predict on 100k holdout rows must run on
             serve/ForestEngine and agree with the host walk; then
             ServingService + ScoringFrontend answer JSON and packed
             binary POST /v1/score requests with the same margins.
  multichip  with >= 4 devices: the train leg again, data-parallel over
             4 chips. Asserts 4 shards on 4 distinct TPU devices, a
             quarter of the bins each, and the compiled aligned-DP route.

There is no CPU mode: a backend other than "tpu" exits non-zero before
any leg runs. Depth is cut (10 / 3 iterations, not 500); widths are not.
The legs are plain functions of their sizes so tests/test_chip_smoke.py
can drive them at toy size under the Pallas interpreter.

The last stdout line is the verdict, one JSON object with exactly these
keys: {"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}
("ok": false, and a non-zero exit, when a leg failed). The line before it,
prefixed "summary: ", is the JSON summary of every leg, ending
"claim": null. Without a TPU nothing is printed as a verdict at all.
"""
import http.client
import json
import sys
import time
import traceback

import numpy as np

HIGGS_ROWS, HIGGS_FEATURES = 10_500_000, 28
MSLR_ROWS, MSLR_FEATURES = 2_270_000, 137
HOLDOUT_ROWS = 100_000


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond, what: str) -> None:
    """An assertion that survives `python -O`."""
    if not cond:
        raise AssertionError(what)


# ------------------------------------------------------------ fixtures
def synth_higgs(n: int, f: int, seed: int = 7):
    """Dense float features with a noisy nonlinear boundary (HIGGS-like:
    kinematic features + derived high-level features)."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f), dtype=np.float32)
    k = min(7, f // 4)
    for j in range(k):
        X[:, f - 1 - j] = np.abs(X[:, 2 * j] * X[:, 2 * j + 1]) \
            + 0.1 * X[:, f - 1 - j]
    w = rng.standard_normal(f).astype(np.float32) / np.sqrt(f)
    margin = X @ w + 0.5 * np.sin(X[:, 0] * 2.0) * X[:, 1] \
        - 0.4 * (np.abs(X[:, 2]) > 1.0)
    p = 1.0 / (1.0 + np.exp(-margin))
    y = (rng.random(n) < p).astype(np.int8)
    return X, y


def synth_mslr(n: int, f: int, seed: int = 11):
    """MSLR-shaped ranking data: ~120 docs/query, graded 0-4 relevance
    correlated with a sparse linear signal."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f), dtype=np.float32)
    w = np.zeros(f, np.float32)
    k = min(25, f)
    idx = rng.choice(f, k, replace=False)
    w[idx] = rng.standard_normal(k).astype(np.float32)
    s = X @ w / 5.0 + 0.8 * rng.standard_normal(n).astype(np.float32)
    # graded labels by within-query quantile
    sizes = []
    left = n
    while left > 0:
        q = int(rng.integers(80, 160))
        q = min(q, left)
        sizes.append(q)
        left -= q
    group = np.asarray(sizes, np.int32)
    y = np.zeros(n, np.float32)
    pos = 0
    for q in sizes:
        sl = s[pos:pos + q]
        ranks = sl.argsort().argsort() / max(q - 1, 1)
        y[pos:pos + q] = np.digitize(ranks, [0.55, 0.75, 0.9, 0.97])
        pos += q
    return X, y, group


def auc_of(pred, y):
    """Rank-sum AUC; ties take the order `argsort` leaves them in."""
    order = np.argsort(pred)
    r = np.empty(len(pred))
    r[order] = np.arange(len(pred)) + 1
    pos = y > 0
    npos, nneg = pos.sum(), (~pos).sum()
    return float((r[pos].sum() - npos * (npos + 1) / 2) / (npos * nneg))


class _Events:
    """Capture the library's structured events (utils/log.py) for the
    duration of a leg; human log lines pass through to stderr."""

    def __enter__(self):
        from lightgbm_tpu.utils import log
        self._log = log
        self.records = []

        def sink(line):
            rec = log.parse_event(line)
            if rec is not None:
                self.records.append(rec)
            else:
                print(line, file=sys.stderr, flush=True)
        log.register_callback(sink)
        log.set_verbosity(1)
        return self

    def __exit__(self, *exc):
        self._log.register_callback(None)

    def of(self, kind):
        return [r for r in self.records if r["event"] == kind]


class _IterClock:
    """lgb.train callback: host wall at the end of each iteration. The
    first iteration blocks on trace + compile; later ones only enqueue
    (the host blocks again where exactness flags are pulled, every 8
    iterations), so the device's work is only over after the drain."""

    def __init__(self):
        self.marks = []

    def __call__(self, env):
        self.marks.append(time.perf_counter())


def _train(params, make_data, iters, extra_params):
    """data -> lgb.Dataset -> lgb.train; returns (booster, events,
    walls, data). The booster is the TRAINING booster (engine state
    attached) so the caller can inspect the path it took."""
    import lightgbm_tpu as lgb
    walls = {}
    t0 = time.perf_counter()
    data = make_data()
    walls["data_s"] = time.perf_counter() - t0
    params = dict(params, **(extra_params or {}))
    with _Events() as ev:
        t0 = time.perf_counter()
        ds = lgb.Dataset(data["X"], label=data["y"], group=data.get("group"),
                         params=params).construct()
        walls["bin_s"] = time.perf_counter() - t0
        clock = _IterClock()
        t0 = time.perf_counter()
        bst = lgb.train(params, ds, num_boost_round=iters,
                        verbose_eval=False, keep_training_booster=True,
                        callbacks=[clock])
        g = bst._gbdt
        g._sync_train_score()       # drain the device before the clock
        t1 = time.perf_counter()
    walls["first_iter_s"] = clock.marks[0] - t0
    walls["steady_s"] = clock.marks[-1] - clock.marks[0]
    walls["steady_iters"] = iters - 1
    walls["drain_s"] = t1 - clock.marks[-1]
    # where the host blocked, iteration by iteration (a late compile,
    # the batched exactness-flag pull)
    walls["iter_host_s"] = [round(b - a, 3) for a, b in
                            zip([t0] + clock.marks[:-1], clock.marks)]
    return bst, ev, walls, data


def _interpreted() -> bool:
    """Pallas kernels run under the interpreter exactly when the backend
    is not a TPU (the toy-size CPU test); on the chip they must be the
    compiled ones."""
    import jax
    return jax.default_backend() != "tpu"


def _aligned_facts(bst, ev, iters):
    """Assert the run took the aligned path, stayed on it and built real
    trees; returns the facts the smoke prints."""
    from lightgbm_tpu import native
    g = bst._gbdt
    paths = ev.of("train_path")
    check(len(paths) == 1, f"expected one train_path event, got {paths}")
    path = paths[0]["path"]
    check(path.startswith("aligned"),
          f"training path is {path!r} (rejected: {paths[0]['rejected']})")
    check(not getattr(g, "_aligned_disabled", False),
          "the aligned engine was dropped mid-run")
    eng = getattr(g, "_aligned_eng_ref", None)
    check(eng is not None, "no aligned engine on the booster")
    check(bool(eng.interpret) == _interpreted(),
          f"aligned kernels interpret={eng.interpret} on this backend")
    check(native.native_available(), "native library did not build")
    trees = bst.trees
    check(len(trees) == iters, f"{len(trees)} trees for {iters} iterations")
    leaves = [int(t.num_leaves) for t in trees]
    check(min(leaves) > 1, f"a tree did not split: leaves={leaves}")
    score = np.asarray(g.train_score.score)
    check(np.isfinite(score).all(), "non-finite training scores")
    return {"path": path, "gate_notes": paths[0]["gate_notes"],
            "fallbacks": int(getattr(eng, "fallbacks", 0)),
            "hist_spill": bool(eng.hist_spill),
            "hist_subbin": bool(eng.hist_subbin),
            "chunk": int(eng.C), "record_lanes": int(eng.W),
            "leaves_min_max": [min(leaves), max(leaves)]}, eng


def _fmt_walls(w):
    per = (w["steady_s"] + w["drain_s"]) / max(w["steady_iters"], 1)
    return (f"data={w['data_s']:.1f}s bin={w['bin_s']:.1f}s "
            f"first_iter(compile)={w['first_iter_s']:.1f}s "
            f"steady={w['steady_s']:.1f}s/{w['steady_iters']}it "
            f"drain={w['drain_s']:.1f}s "
            f"({per * 1e3:.0f} ms/it with the drain)")


def _round_walls(w):
    return {k: (round(v, 2) if isinstance(v, float) else v)
            for k, v in w.items()}


def _params(objective, min_data_in_leaf, num_leaves, max_bin, **more):
    """The reference experiments' parameters, at INFO
    verbosity so the structured events reach `_Events`."""
    return dict(objective=objective, num_leaves=num_leaves, max_bin=max_bin,
                learning_rate=0.1, min_data_in_leaf=min_data_in_leaf,
                verbosity=1, metric="none", **more)


# ---------------------------------------------------------------- legs
def leg_train(rows, features, iters=10, num_leaves=255, max_bin=255,
              holdout_rows=HOLDOUT_ROWS, extra_params=None):
    """Binary training at HIGGS width. Returns (result, booster,
    holdout_X) — the score leg reuses the model and the holdout."""
    params = _params("binary", 20, num_leaves, max_bin)
    hold = {}

    def make_data():
        X, y = synth_higgs(rows + holdout_rows, features)
        hold["X"], hold["y"] = X[rows:], y[rows:]
        return {"X": X[:rows], "y": y[:rows]}

    bst, ev, walls, _ = _train(params, make_data, iters, extra_params)
    facts, _eng = _aligned_facts(bst, ev, iters)
    auc = auc_of(bst.predict(hold["X"]), hold["y"])
    check(np.isfinite(auc) and auc > 0.6,
          f"holdout AUC {auc:.4f} is not sanely above 0.5")
    res = dict(facts, rows=rows, features=features, iters=iters,
               auc=round(float(auc), 5), walls=_round_walls(walls))
    say(f"train: rows={rows} F={features} path={facts['path']} "
        f"fallbacks={facts['fallbacks']} hist_spill={facts['hist_spill']} "
        f"leaves={facts['leaves_min_max']} auc={auc:.4f}")
    say(f"train walls: {_fmt_walls(walls)}")
    return res, bst, hold["X"]


def leg_rank(rows, features, iters=3, num_leaves=255, max_bin=255,
             extra_params=None):
    """Lambdarank at MS-LTR width: the leg that compiles the HBM spill
    ring and the segment-fused rank kernel."""
    params = _params("lambdarank", 50, num_leaves, max_bin)

    def make_data():
        X, y, group = synth_mslr(rows, features)
        return {"X": X, "y": y, "group": group}

    bst, ev, walls, data = _train(params, make_data, iters, extra_params)
    facts, _eng = _aligned_facts(bst, ev, iters)
    check(facts["hist_spill"] and any("spill" in n.lower()
                                      for n in facts["gate_notes"]),
          f"no hist-spill note on the train_path event: {facts}")
    obj = bst._gbdt.objective
    check(obj.rank_fused_active,
          "the fused rank kernel is not active after its first dispatch")
    fused = ev.of("rank_fused")
    check(len(fused) >= 1 and fused[0]["interpret"] == _interpreted(),
          f"rank_fused event: {fused}")
    err = _rank_grad_parity(data, params, extra_params)
    res = dict(facts, rows=rows, features=features, iters=iters,
               rank_fused_active=True, rank_tiles=fused[0]["tiles"],
               rank_fill_pct=fused[0]["fill_pct"],
               rank_grad_rel_err=err, walls=_round_walls(walls))
    say(f"rank: rows={rows} F={features} path={facts['path']} "
        f"fallbacks={facts['fallbacks']} hist_spill={facts['hist_spill']} "
        f"rank_fused_active=True tiles={fused[0]['tiles']} "
        f"fill={fused[0]['fill_pct']}% grad_err_vs_bucketed={err:.2e}")
    say(f"rank walls: {_fmt_walls(walls)}")
    return res


def _rank_grad_parity(data, params, extra_params, queries=64):
    """Fused-kernel gradients against the bucketed oracle on the first
    `queries` queries, on whatever backend this runs on. Returns the
    max abs difference over g and h as a fraction of the largest oracle
    gradient (asserted at the kernel's bf16 pair-factor tolerance)."""
    import jax.numpy as jnp

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ops.objectives import create_objective
    group = np.asarray(data["group"][:queries], np.int64)
    n = int(group.sum())
    qb = np.concatenate([[0], np.cumsum(group)])
    meta = type("Meta", (), {"query_boundaries": qb,
                             "label": np.asarray(data["y"][:n], np.float64),
                             "weight": None})()
    score = jnp.asarray(np.random.default_rng(5).standard_normal(n),
                        jnp.float32)[None, :]
    out = {}
    for mode in ("on", "off"):
        cfg = Config.from_params({**params, **(extra_params or {}),
                                  "tpu_rank_fused": mode})
        obj = create_objective(cfg)
        obj.init(meta, n)
        g, h = obj.get_gradients(score)
        out[mode] = np.stack([np.asarray(g[0]), np.asarray(h[0])])
    check(np.isfinite(out["on"]).all(), "non-finite fused rank gradients")
    scale = float(np.max(np.abs(out["off"]))) or 1.0
    err = float(np.max(np.abs(out["on"] - out["off"]))) / scale
    check(err <= 2e-2,
          f"fused rank gradients are {err:.3g} of the largest gradient "
          f"off the bucketed oracle")
    return err


def _post(port, model, body, headers):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request("POST", f"/v1/score/{model}", body=body,
                     headers=headers)
        resp = conn.getresponse()
        return resp.status, resp.read(), dict(resp.getheaders())
    finally:
        conn.close()


def leg_score(bst, hold_X, subset=1000, requests=3, request_rows=64,
              predict_params=None):
    """Booster.predict through serve/ForestEngine vs the host walk, then
    the HTTP front door answering the same margins."""
    from lightgbm_tpu.serving import ServingService
    from lightgbm_tpu.serving.frontend import ScoringFrontend
    kw = dict(predict_params or {})
    t0 = time.perf_counter()
    raw = bst.predict(hold_X, raw_score=True, **kw)
    t_engine = time.perf_counter() - t0
    check(bst._predict_engine_calls > 0,
          "Booster.predict did not route through serve/ForestEngine")
    check(raw.shape == (len(hold_X),) and np.isfinite(raw).all(),
          f"bad engine margins: shape {raw.shape}")
    host = bst.predict(hold_X[:subset], raw_score=True,
                       tpu_predict_device="off")
    np.testing.assert_allclose(raw[:subset], host, rtol=1e-5, atol=1e-7)

    svc = ServingService(params={"tpu_serve_qos": "smoke:gold",
                                 "tpu_serve_max_batch_wait_ms": 1.0})
    codes = []
    try:
        svc.load_model("smoke", model_str=bst.model_to_string())
        fe = ScoringFrontend(svc, port=0)
        try:
            for i in range(requests):
                rows = np.asarray(
                    hold_X[i * request_rows:(i + 1) * request_rows],
                    np.float64)
                want = raw[i * request_rows:(i + 1) * request_rows]
                status, body, _ = _post(
                    fe.port, "smoke",
                    json.dumps({"rows": rows.tolist()}).encode(),
                    {"Content-Type": "application/json"})
                codes.append(status)
                check(status == 200, f"JSON request -> {status}: {body[:200]}")
                np.testing.assert_allclose(
                    json.loads(body)["predictions"], want,
                    rtol=1e-5, atol=1e-6)
                status, body, hdrs = _post(
                    fe.port, "smoke", rows.astype("<f4").tobytes(),
                    {"Content-Type": "application/octet-stream",
                     "X-Num-Features": str(rows.shape[1]),
                     "X-Dtype": "f32",
                     "Accept": "application/octet-stream"})
                codes.append(status)
                check(status == 200,
                      f"binary request -> {status}: {body[:200]}")
                check(hdrs["X-Shape"] == str(len(rows)), hdrs["X-Shape"])
                np.testing.assert_allclose(
                    np.frombuffer(body, "<f4"), want, rtol=1e-5, atol=1e-6)
        finally:
            fe.close()
    finally:
        svc.close()
    res = {"rows": int(len(hold_X)), "engine_calls":
           int(bst._predict_engine_calls), "engine_predict_s":
           round(t_engine, 2), "host_subset": int(min(subset, len(hold_X))),
           "http_codes": codes}
    say(f"score: rows={len(hold_X)} engine_calls="
        f"{bst._predict_engine_calls} engine_predict={t_engine:.1f}s "
        f"host_walk_agree=yes http={codes}")
    return res


def leg_multichip(rows, features, shards=4, iters=10, num_leaves=255,
                  max_bin=255, extra_params=None):
    """The train leg data-parallel over `shards` devices: real mesh,
    real shards, the aligned-DP route."""
    import jax

    from lightgbm_tpu.obs import memory as obs_memory
    params = _params("binary", 20, num_leaves, max_bin,
                     tree_learner="data", num_machines=shards)

    def make_data():
        X, y = synth_higgs(rows, features)
        return {"X": X, "y": y}

    owners_before = set(obs_memory.owners_bytes())
    bst, ev, walls, _ = _train(params, make_data, iters, extra_params)
    facts, eng = _aligned_facts(bst, ev, iters)
    init = ev.of("dist_init")
    check(len(init) == 1 and init[0]["shards"] == shards,
          f"dist_init does not say shards={shards}: {init}")
    g = bst._gbdt
    mesh_devs = list(g.learner.mesh.devices.flat)
    check(len({d.id for d in mesh_devs}) == shards,
          f"mesh does not hold {shards} distinct devices: {mesh_devs}")
    platform = jax.default_backend()
    check(all(d.platform == platform for d in mesh_devs),
          f"mesh devices are not all {platform}: {mesh_devs}")
    # the accountant's view ...
    owners = {name.split("/")[-1]: info["bytes"]
              for name, info in obs_memory.owners_bytes().items()
              if name.startswith("dist/shard_bytes/")
              and name not in owners_before}
    check(len(owners) == shards, f"shard owners: {owners}")
    total = sum(owners.values())
    check(all(abs(b - total / shards) <= 0.02 * total
              for b in owners.values()),
          f"shard owners are not ~1/{shards} each: {owners}")
    # ... and the buffers themselves: bins and aligned records must sit
    # on `shards` distinct devices in equal parts, not on device 0
    placed = {}
    for name, arr in (("bins", g.train_data._shard_cache["bins"]),
                      ("records", eng.rec)):
        per_dev = {int(s.device.id): int(s.data.nbytes)
                   for s in arr.addressable_shards}
        check(len(per_dev) == shards
              and len(set(per_dev.values())) == 1,
              f"{name} not spread evenly over {shards} devices: {per_dev}")
        placed[name] = per_dev
    check(eng.nd == shards and eng.axis is not None,
          f"aligned engine is not on the DP route: nd={eng.nd} "
          f"axis={eng.axis}")
    res = dict(facts, rows=rows, features=features, iters=iters,
               shards=shards,
               devices=[f"{d.platform}:{d.id}" for d in mesh_devs],
               shard_owner_bytes=owners, placed_bytes=placed,
               aligned_dp=True, interpret=bool(eng.interpret),
               walls=_round_walls(walls))
    say(f"multichip: shards={shards} devices={res['devices']} "
        f"path={facts['path']} fallbacks={facts['fallbacks']} "
        f"eng.nd={eng.nd} eng.axis={eng.axis} interpret={eng.interpret} "
        f"bins_bytes_per_device={sorted(placed['bins'].values())}")
    say(f"multichip walls: {_fmt_walls(walls)}")
    return res


# ---------------------------------------------------------------- main
def main() -> int:
    import jax

    from lightgbm_tpu import compile_cache
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"platform={device['platform']} device_kind={device['kind']} "
        f"device_count={device['count']}")
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU (jax found platform "
              f"{device['platform']!r}); this script only runs on the "
              f"chip", file=sys.stderr)
        return 1
    cache = compile_cache.init_persistent_cache()
    entries0 = compile_cache.cache_dir_entries(cache)
    say(f"compile_cache: dir={cache} entries_before={entries0}")
    out = {"ok": False, "device": device}
    try:
        _run_legs(out, cache, entries0)
        out["ok"] = True
    except Exception:
        # the first failed leg ends the run; the verdict still goes out
        traceback.print_exc()
        say("chip_smoke: FAILED (traceback on stderr)")
    return report(out)


def report(out) -> int:
    """Print the summary of every leg, then the verdict — exactly
    {"ok", "device"} — as the last line of stdout; returns the exit code."""
    out["claim"] = None
    say("summary: " + json.dumps(out))
    say(json.dumps({"ok": out["ok"], "device": out["device"]}))
    return 0 if out["ok"] else 1


def _run_legs(out, cache, entries0) -> None:
    from lightgbm_tpu import compile_cache
    t_all = time.perf_counter()
    out["train"], bst, hold_X = leg_train(HIGGS_ROWS, HIGGS_FEATURES)
    out["score"] = leg_score(bst, hold_X)
    del bst, hold_X
    out["rank"] = leg_rank(MSLR_ROWS, MSLR_FEATURES)
    if out["device"]["count"] >= 4:
        out["multichip"] = leg_multichip(HIGGS_ROWS, HIGGS_FEATURES)
    else:
        out["multichip"] = None
        say(f"multichip: not run ({out['device']['count']} device)")
    events = compile_cache.persistent_cache_events()
    out["compile_cache"] = {
        "dir": cache, "entries_before": entries0,
        "entries_after": compile_cache.cache_dir_entries(cache),
        "hits": events["hits"], "misses": events["misses"]}
    say(f"compile_cache: hits={events['hits']} misses={events['misses']} "
        f"entries_after={out['compile_cache']['entries_after']}")
    out["wall_s"] = round(time.perf_counter() - t_all, 1)


if __name__ == "__main__":
    sys.exit(main())
