#!/usr/bin/env python
"""Benchmark: HIGGS-shaped GBDT training + holdout AUC on the current
backend, plus an MSLR-shaped lambdarank run reporting NDCG@10.

Mirrors the reference's headline experiments:
- HIGGS (docs/Experiments.rst:106): 10.5M rows x 28 dense features, 500
  iterations, 255 leaves -> 238.5 s wall-clock on 2x E5-2670v3 (CPU,
  max_bin=255), test AUC 0.845154 (:127). The reference's own GPU
  guidance benches at max_bin=63 (docs/GPU-Performance.rst:110-128,170;
  63-bin AUC 0.845209 at :139), which is what the TPU run uses too; a
  255-bin timing is reported alongside for the apples-to-apples row.
- MS-LTR (docs/Experiments.rst:110,143): 2.27M x 137 with query groups,
  500 iterations -> 215.3 s, NDCG@10 0.527371.

Data is synthetic at the same shapes (the 2.6 GB HIGGS csv is not
vendored); the measured quantity — boosting-iteration throughput on a
binned dataset plus ranking quality — is the same hot loop.

Prints the cumulative JSON summary line after EVERY stage (the last line
is the full record; a killed run still leaves the stages that finished):
  {"metric": "higgs_synth_500iter_s", "value": <projected 500-iter s>,
   "unit": "s", "vs_baseline": <238.5 / value>, "auc": <holdout AUC>,
   "value_255bin": <projected s at max_bin=255>,
   "ndcg10": <lambdarank NDCG@10>, "mslr_500iter_s": <projected s>,
   "predict_speedup": <serve engine vs seed TreePredictor>}

Stages run in value order (63-bin -> 255-bin -> MSLR -> predict ->
serve-traffic -> valid-overhead -> resume -> sweep -> reference
parity LAST) and BENCH_BUDGET_S sets a wall-clock budget enforced by an
obs BudgetGate: a stage is skipped not only once the budget is
exhausted but also ADAPTIVELY, when its estimated cost (derived from
the measured walls of earlier stages, recorded under "stage_wall_s")
no longer fits what remains — and iteration-count stages shrink via
scale_iters before giving up entirely. A reserve slice is held back so
finalize always lands a complete record (the r05 rc=124 failure mode).
EVERY skipped stage records its reason (budget/adaptive skip or the env
knob that disabled it) under "stage_skips" {stage: reason} — and the
summary line re-emits at the moment of the skip, so a later hard kill
can never produce rc=124 with nothing parseable. "budget_skipped"
(name-only list) stays for older parsers.

The serve-traffic stage (tools/bench_serve_traffic.py) loads two real
boosters into the serving/ service and records open-loop p50/p99
latency per target QPS, closed-loop coalesced-vs-direct throughput,
batch fill, and a hot-swap-under-load leg with zero tolerated failures.

Compile-cost accounting (first-class JSON fields): "warmup_s" /
"warmup_s_255bin" (wall seconds of the warmup iterations, compile
included), "compile_s" / "compile_s_255bin" (warmup minus steady-state
iteration cost), "compile_cache_hit" (persistent cache had entries
before this process compiled), "compile_cache" {dir, entries_before,
entries_after}.
"compile_cache_misses" {stage: count} attributes persistent-cache
misses to the stage that paid them — each miss also emits a structured
compile_cache_miss [Event] naming the traced program signature
(compile_cache.install_cache_event_hooks), so a long warm-up despite
compile_cache_hit=true is now a lookup, not an investigation.

Aligned-path accounting: the 255-bin and MSLR stages record whether the
run stayed on the aligned engine ("aligned_255bin" / "mslr_aligned"),
its host-fallback count ("fallbacks_255bin" / "mslr_fallbacks"), and
whether the slot-hist store spilled to HBM through the DMA ring
("hist_spill_255bin" / "mslr_hist_spill").

Per-term device time: "terms_by_stage" {stage: {term: ms}} — the
training stages run with the in-run profiler armed (obs/profiler.py,
tpu_profile=on at an unreachable cadence) and force ONE sampled round
AFTER each timed loop, so the per_iter window never contains a fence;
the sampled round's canonical terms_ms (obs/terms.py vocabulary:
rank_grad, build, score_update, ...) lands here, the per-term twin of
"hbm_by_stage". tools/bench_compare.py diffs it to attribute a stage
timing regression to a term; tools/bottleneck_report.py merges it with
a ledger + program_costs.json into the ranked report. BENCH_PROFILE=0
disables the plane entirely.

Crash-proofing (obs/bench_record.py): the cumulative record exists from
second zero and every stage completion re-emits it AND atomically
rewrites the BENCH_OUT sidecar file (default ./BENCH_partial.json, tmp +
rename). SIGTERM/SIGINT traps and an exit hook flush one final record
with "incomplete": true plus "stage_reached"/"stages_done", so a driver
timeout (rc=124, SIGTERM-then-SIGKILL) can never again produce
parsed: null. A completed run's final line carries "incomplete": false —
every pre-existing key is unchanged, so BENCH_r01–r05 parsers keep
working.

Env knobs: BENCH_ROWS, BENCH_FEATURES, BENCH_ITERS (measured), BENCH_WARMUP,
BENCH_LEAVES, BENCH_SMOKE=1 (tiny CPU config), BENCH_BUDGET_S,
BENCH_SKIP_RANK=1, BENCH_SKIP_255=1, BENCH_SKIP_PREDICT=1,
BENCH_SKIP_VALID=1, BENCH_SKIP_REF=1,
BENCH_SKIP_RESUME=1, BENCH_SKIP_SERVE=1, BENCH_SKIP_SWEEP=1,
BENCH_PROFILE=0 (disable the
per-term profiler rounds), BENCH_OUT=<path> (sidecar record),
BENCH_TRACE=1 + BENCH_TRACE_DIR (obs span tracer + per-stage ledger
records).
JAX_COMPILATION_CACHE_DIR overrides the persistent-cache location
(default: <checkout>/.jax_cache; see compile_cache.cache_dir).
"""
import json
import os
import shutil
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu import compile_cache  # noqa: E402
from lightgbm_tpu.obs.bench_record import BenchRecorder, BudgetGate  # noqa: E402

BASELINE_S = 238.5       # docs/Experiments.rst:106 (CPU, 16 threads)
BASELINE_MSLR_S = 215.3  # docs/Experiments.rst:110
BASELINE_ITERS = 500

_T0 = time.perf_counter()
BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "0") or 0)
_GATE = BudgetGate(BUDGET_S, t0=_T0)
_REC = None       # BenchRecorder owning the cumulative record (main only)
_LEDGER = None    # optional obs RoundLedger for per-stage records
_STAGE_MISS0 = {}  # persistent-cache miss count at each stage's start


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def emit(out):
    """Print the cumulative summary line NOW: a budget kill or crash later
    still leaves every stage that finished on stdout. When the recorder
    owns `out` (main run), the same flush atomically rewrites the
    BENCH_OUT sidecar file — a SIGKILL between stages loses nothing."""
    if _REC is not None and _REC.out is out:
        _REC.emit()
    else:
        print(json.dumps(out), flush=True)


def _stage(name):
    """Mark a stage as reached (the interruption record names it), start
    its wall clock, and snapshot the persistent-cache miss counter so
    _stage_done can attribute recompiles to the stage."""
    _GATE.start(name)
    _STAGE_MISS0[name] = compile_cache.persistent_cache_events()["misses"]
    if _REC is not None:
        _REC.start_stage(name)


def _stage_done(name, out):
    """Stage completed: record its wall + compile-cache misses + an HBM
    accountant snapshot, re-emit the cumulative record, flush the
    sidecar, and append a stage record to the obs ledger when one is
    attached."""
    wall = _GATE.done(name)
    out.setdefault("stage_wall_s", {})[name] = round(wall, 2)
    miss = compile_cache.persistent_cache_events()["misses"] \
        - _STAGE_MISS0.pop(name, 0)
    # which stage recompiled despite the warm cache — each miss also
    # emitted a compile_cache_miss [Event] naming the exact program
    out.setdefault("compile_cache_misses", {})[name] = miss
    try:
        from lightgbm_tpu.obs import memory as obs_memory
        snap = obs_memory.snapshot()
        mb = 1 << 20
        hbm = {"claimed_mb": round(snap["claimed_bytes"] / mb, 1),
               # process-lifetime high-water mark as of this stage's end
               # (backend peak where the platform reports one, else the
               # claimed-bytes peak over snapshots)
               "peak_mb": round(snap["peak_bytes"] / mb, 1)}
        if snap["device_bytes_in_use"] is not None:
            hbm["in_use_mb"] = round(snap["device_bytes_in_use"] / mb, 1)
        if snap["hbm_unattributed_bytes"] is not None:
            hbm["unattributed_mb"] = round(
                snap["hbm_unattributed_bytes"] / mb, 1)
        out.setdefault("hbm_by_stage", {})[name] = hbm
    except Exception:
        pass  # accounting must never void a bench record
    if _REC is not None:
        _REC.stage_done(name)
    else:
        emit(out)
    if _LEDGER is not None:
        # t0/t1 on the shared perf_counter clock: the timeline merger
        # (obs/timeline.py) places the bench lane span from these
        t_now = time.perf_counter()
        _LEDGER.commit({"kind": "note", "stage": name,
                        "t_s": round(t_now - _T0, 1),
                        "t0": round(t_now - wall, 6),
                        "t1": round(t_now, 6),
                        "wall_s": round(wall, 3)})


def _stage_failed(out, name, err):
    """A stage raised: log the traceback and record it under
    "stage_errors". Later stages still run; main() exits non-zero."""
    import traceback
    log(f"# {name} stage FAILED: {type(err).__name__}: {err}")
    traceback.print_exc(file=sys.stderr)
    out.setdefault("stage_errors", {})[name] = \
        f"{type(err).__name__}: {err}"[:300]


def budget_left():
    """Usable seconds until the BENCH_BUDGET_S wall budget runs out
    (None = unbounded). A finalize reserve is already held back."""
    return _GATE.left()


def stage_gate(out, stage, env_knob=None, est_s=0.0):
    """True when the stage should run. A skipped stage records WHY under
    out["stage_skips"][stage] — the env knob that disabled it, budget
    exhaustion, or an adaptive skip (est_s, usually derived from earlier
    stages' measured walls, no longer fits the remaining budget) — and
    re-emits the summary line immediately, so a later hard kill still
    leaves the skip reasons parseable on stdout."""
    if env_knob and os.environ.get(env_knob) == "1":
        out.setdefault("stage_skips", {})[stage] = f"{env_knob}=1"
        emit(out)
        return False
    ok, reason = _GATE.allow(stage, est_s=est_s)
    if ok:
        return True
    log(f"# {reason}: skipping {stage}")
    out.setdefault("budget_skipped", []).append(stage)
    out.setdefault("stage_skips", {})[stage] = reason
    emit(out)
    return False


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


def ndcg_at(preds, y, group, k=10):
    pos = 0
    total, cnt = 0.0, 0
    for q in group:
        p = preds[pos:pos + q]
        lab = y[pos:pos + q]
        order = np.argsort(-p)[:k]
        dcg = np.sum((2.0 ** lab[order] - 1) / np.log2(np.arange(len(order)) + 2))
        ideal = np.sort(lab)[::-1][:k]
        idcg = np.sum((2.0 ** ideal - 1) / np.log2(np.arange(len(ideal)) + 2))
        if idcg > 0:
            total += dcg / idcg
            cnt += 1
        pos += q
    return total / max(cnt, 1)


def auc_of(pred, y):
    order = np.argsort(pred)
    r = np.empty(len(pred))
    r[order] = np.arange(len(pred)) + 1
    pos = y > 0
    npos, nneg = pos.sum(), (~pos).sum()
    return float((r[pos].sum() - npos * (npos + 1) / 2) / (npos * nneg))


def _sync(bst):
    g = bst._gbdt
    eng = getattr(g, "_aligned_eng_ref", None)
    if eng is not None:
        np.asarray(eng.rec[0, 0, :1])
    else:
        np.asarray(g.train_score.score.reshape(-1)[:1])


# in-run profiler on the stage boosters (obs/profiler.py): the stage
# params carry tpu_profile=on with an unreachable cadence, so the
# warmup/timed loops never sample (zero fences in the measured window);
# after each timed loop ONE forced sampled round decomposes a
# representative round into terms_ms, folded into the bench record as
# terms_by_stage (the per-term twin of hbm_by_stage). BENCH_PROFILE=0
# disables the whole plane.
BENCH_PROFILE = os.environ.get("BENCH_PROFILE", "1") != "0"

# streaming out-of-core ingest for the training-stage dataset builds
# (io/stream.py): chunked device-side binning instead of the one-shot
# host matrix — the model is byte-equal either way (same sample draw),
# so only the stage walls move. BENCH_STREAM_CHUNK=0 restores the
# in-memory construct.
BENCH_STREAM_CHUNK = int(os.environ.get("BENCH_STREAM_CHUNK", 1_000_000))


def _stream_params():
    if BENCH_STREAM_CHUNK <= 0:
        return {}
    return {"tpu_stream_chunk_rows": BENCH_STREAM_CHUNK}


def _ingest_stats(ds, stats):
    """Fold the construct-time ingest breakdown into a stage's stats:
    ``bin_s`` is the whole construct wall (already measured by the
    caller); ``ingest_s`` is the streaming pipeline's own clock when the
    streamed path ran (sample pass + device binning + HBM append)."""
    h = getattr(ds, "_handle", None)
    ms = getattr(h, "_ingest_ms", None)
    if ms is not None:
        stats["ingest_s"] = round(ms / 1e3, 2)
        # construction-time term for the ranked bottleneck report (the
        # canonical obs/terms.py "ingest" vocabulary entry)
        terms = stats.setdefault("construct_terms_ms", {})
        terms["ingest"] = round(ms, 1)
        st = getattr(h, "_ingest_stats", None) or {}
        if st.get("sharded"):
            # stream-to-shard pipeline breakdown: parse and bin walls
            # overlap, so they can sum to MORE than the ingest wall —
            # the bottleneck report ranks them as pipeline legs
            terms["ingest_parse"] = st["parse_ms"]
            terms["ingest_bin"] = st["bin_ms"]
            stats["ingest_overlap_eff"] = st["overlap_eff"]
    return stats


def _profile_params():
    if not BENCH_PROFILE:
        return {}
    return {"tpu_profile": "on", "tpu_profile_every": 10 ** 9}


def _profile_terms(bst):
    """Force-sample one round NOW (after the timed loop) and return its
    canonical terms_ms, or None when profiling is off/failed. The extra
    update() grows one extra tree — call only after the stage's quality
    numbers are computed."""
    prof = getattr(getattr(bst, "_gbdt", None), "_profiler", None)
    if prof is None:
        return None
    try:
        prof.force_next()
        bst.update()
        _sync(bst)
        terms = prof.last_terms
        if terms:
            log("# terms_ms: " + " ".join(
                f"{k}={v:.1f}" for k, v in sorted(
                    terms.items(), key=lambda kv: -(kv[1] or 0))))
        return terms
    except Exception as e:  # profiling must never void a bench record
        log(f"# profile round FAILED: {type(e).__name__}: {e}")
        return None


def run_higgs(n, f, leaves, iters, warmup, max_bin, holdout_X, holdout_y,
              X, y, full_iters=0):
    """Timed window (warmup + iters, projected to 500) plus, when
    full_iters > 0, training CONTINUES to that many total iterations so
    the reported AUC is the true full-model quality — the number the
    full-scale reference head-to-head (tools/ref_full_headtohead.py)
    compares against. The continue loop respects the BENCH_BUDGET_S
    deadline: it stops at a round iteration count instead of letting the
    whole bench get killed with nothing reported."""
    params = {
        "objective": "binary",
        "num_leaves": leaves,
        "max_bin": max_bin,
        "learning_rate": 0.1,
        "min_data_in_leaf": 20,
        "verbosity": -1,
        "metric": "none",
    }
    params.update(_profile_params())
    params.update(_stream_params())
    t0 = time.perf_counter()
    train_set = lgb.Dataset(X, label=y, params=params).construct()
    t_bin = time.perf_counter() - t0
    bst = lgb.Booster(params=params, train_set=train_set)
    t0 = time.perf_counter()
    for _ in range(warmup):
        bst.update()
    _sync(bst)
    t_warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(iters):
        bst.update()
    _sync(bst)
    per_iter = (time.perf_counter() - t0) / max(iters, 1)
    done = warmup + iters
    if full_iters > done:
        t0 = time.perf_counter()
        block = 25
        while done < full_iters:
            left = budget_left()
            if left is not None and left <= 0:
                log(f"#   budget exhausted: stopping full-AUC continue at "
                    f"{done}/{full_iters} iters")
                break
            step = min(block, full_iters - done)
            for _ in range(step):
                bst.update()
            done += step
            _sync(bst)
        log(f"#   continue to {done} iters: "
            f"{time.perf_counter() - t0:.1f}s")
    auc = None
    if holdout_X is not None:
        t0 = time.perf_counter()
        auc = auc_of(bst.predict(holdout_X), holdout_y)
        log(f"#   predict+auc: {time.perf_counter() - t0:.1f}s")
    eng = getattr(bst._gbdt, "_aligned_eng_ref", None)
    fb = getattr(eng, "fallbacks", 0) if eng is not None else -1
    log(f"# higgs mb={max_bin}: bin={t_bin:.1f}s warmup({warmup})="
        f"{t_warm:.1f}s per_iter={per_iter * 1e3:.1f}ms "
        f"aligned={'yes' if eng is not None else 'no'} fallbacks={fb}")
    stats = {
        "bin_s": round(t_bin, 2),
        "warmup_s": round(t_warm, 2),
        # warmup time minus the steady-state cost of those iterations —
        # i.e. the trace + XLA-compile (or cache-load) bill of the stage
        "compile_s": round(max(t_warm - warmup * per_iter, 0.0), 2),
        "per_iter_ms": round(per_iter * 1e3, 2),
        "aligned": eng is not None,
        "fallbacks": fb if eng is not None else None,
        "hist_spill": bool(getattr(eng, "hist_spill", False))
        if eng is not None else False,
    }
    _ingest_stats(train_set, stats)
    terms = _profile_terms(bst)
    if terms:
        stats["terms_ms"] = terms
    if stats.get("terms_ms") is not None \
            and "ingest" in stats.get("construct_terms_ms", {}):
        stats["terms_ms"]["ingest"] = \
            stats["construct_terms_ms"]["ingest"]
    return per_iter * BASELINE_ITERS, auc, done, stats


def run_mslr(n, f, iters, warmup, max_bin=255, ab_iters=0):
    """MSLR-shaped lambdarank run. Defaults to max_bin=255 — the
    reference table's configuration (docs/Experiments.rst:110), and the
    wide-F x 255-bin shape that exercises the HBM slot-hist spill ring on
    the aligned path (F=137 slot blocks no longer fit the VMEM budget).

    With ab_iters > 0 and the segment-fused rank kernel active, a second
    booster runs `tpu_rank_fused=off` on the same dataset for a
    fused-vs-bucketed per-iter A/B (per_iter_fused_ms /
    per_iter_bucketed_ms / rank_fused_speedup in the returned info)."""
    X, y, group = synth_mslr(n, f)
    params = {
        "objective": "lambdarank",
        "num_leaves": 255,
        "max_bin": max_bin,
        "learning_rate": 0.1,
        "min_data_in_leaf": 50,
        "verbosity": -1,
        "metric": "none",
    }
    params.update(_profile_params())
    params.update(_stream_params())
    t0 = time.perf_counter()
    ds = lgb.Dataset(X, label=y, group=group, params=params).construct()
    t_bin = time.perf_counter() - t0
    bst = lgb.Booster(params=params, train_set=ds)
    t0 = time.perf_counter()
    for _ in range(warmup):
        bst.update()
    _sync(bst)
    t_warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(iters):
        bst.update()
    _sync(bst)
    per_iter = (time.perf_counter() - t0) / iters
    # NDCG@10 on the TRAIN queries (the reference table's protocol uses a
    # test fold; synthetic data has no canonical fold — this reports the
    # learned ranking quality signal at the trained point)
    preds = bst.predict(X[:200_000])
    gsub = []
    tot = 0
    for q in group:
        if tot + q > 200_000:
            break
        gsub.append(q)
        tot += q
    nd = ndcg_at(preds[:tot], y[:tot], gsub, 10)
    eng = getattr(bst._gbdt, "_aligned_eng_ref", None)
    obj = getattr(bst._gbdt, "objective", None)
    info = {
        "max_bin": max_bin,
        "bin_s": round(t_bin, 2),
        "aligned": eng is not None,
        "fallbacks": getattr(eng, "fallbacks", 0)
        if eng is not None else None,
        "hist_spill": bool(getattr(eng, "hist_spill", False))
        if eng is not None else False,
        "rank_fused": bool(getattr(obj, "rank_fused_active", False)),
        "rank_fused_fallback_queries": int(
            getattr(obj, "rank_fused_fallback_queries", 0)),
    }
    log(f"# mslr mb={max_bin}: bin={t_bin:.1f}s warmup({warmup})="
        f"{t_warm:.1f}s per_iter={per_iter * 1e3:.1f}ms ndcg10={nd:.5f} "
        f"aligned={'yes' if info['aligned'] else 'no'} "
        f"spill={'yes' if info['hist_spill'] else 'no'} "
        f"fallbacks={info['fallbacks']} "
        f"rank_fused={'yes' if info['rank_fused'] else 'no'}")
    if ab_iters and info["rank_fused"]:
        # fused-vs-bucketed A/B: same dataset, bucketed grad path
        pb = dict(params)
        pb["tpu_rank_fused"] = "off"
        bstb = lgb.Booster(params=pb, train_set=ds)
        for _ in range(2):          # compile + warm the bucket ladder
            bstb.update()
        _sync(bstb)
        t0 = time.perf_counter()
        for _ in range(ab_iters):
            bstb.update()
        _sync(bstb)
        per_b = (time.perf_counter() - t0) / ab_iters
        info["per_iter_fused_ms"] = round(per_iter * 1e3, 1)
        info["per_iter_bucketed_ms"] = round(per_b * 1e3, 1)
        info["rank_fused_speedup"] = round(per_b / max(per_iter, 1e-9), 2)
        log(f"# mslr A/B: fused={per_iter * 1e3:.1f}ms "
            f"bucketed={per_b * 1e3:.1f}ms "
            f"speedup={info['rank_fused_speedup']}x")
    _ingest_stats(ds, info)
    terms = _profile_terms(bst)
    if terms:
        info["terms_ms"] = terms
    if info.get("terms_ms") is not None \
            and "ingest" in info.get("construct_terms_ms", {}):
        info["terms_ms"]["ingest"] = info["construct_terms_ms"]["ingest"]
    return per_iter * BASELINE_ITERS, nd, info


def run_valid_overhead(X, y, hX, hy, leaves, iters, warmup):
    """Per-iter cost WITH a valid set + per-iter AUC vs without (VERDICT
    r3 #2: the device walker + device AUC must keep this <10%)."""
    params = {"objective": "binary", "num_leaves": leaves, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 20,
              "verbosity": -1, "metric": "auc"}
    ds = lgb.Dataset(X, label=y, params=params).construct()
    vs = lgb.Dataset(hX, label=hy, reference=ds, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    bst.add_valid(vs, "v")
    g = bst._gbdt
    for _ in range(warmup):
        bst.update()
        g.eval_valid()
    t0 = time.perf_counter()
    last = None
    for _ in range(iters):
        bst.update()
        last = g.eval_valid()
    per_iter = (time.perf_counter() - t0) / iters
    log(f"# valid-attached per_iter={per_iter * 1e3:.1f}ms "
        f"(auc={last[0][2]:.6f})")
    return per_iter


def _fmt_tsv(path, y, X, t0):
    with open(path, "w") as fh:
        blk = 100_000
        for s in range(0, len(y), blk):
            e = min(s + blk, len(y))
            body = np.column_stack([y[s:e], X[s:e]])
            fh.write("\n".join(
                "\t".join(f"{v:.6g}" for v in row) for row in body))
            fh.write("\n")
    log(f"#   tsv write {path}: {time.perf_counter() - t0:.1f}s")


def run_ref_parity(X, y, hX, hy, leaves):
    """Side-by-side quality vs the ACTUAL reference binary on identical
    1M-row data, 100 iterations, max_bin=63 (VERDICT r3 #7). Returns
    (auc_ours, auc_ref) or (None, None) when the CLI can't be built."""
    import subprocess
    import tempfile
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    try:
        from test_reference_parity import _ensure_cli, CLI
    except Exception:
        return None, None
    if not _ensure_cli():
        log("# ref parity: reference CLI unavailable")
        return None, None
    n1 = min(len(y), 1_000_000)
    nh = min(len(hy), 100_000)
    td = tempfile.mkdtemp(prefix="refpar_")
    t0 = time.perf_counter()
    train_p = os.path.join(td, "train.tsv")
    hold_p = os.path.join(td, "hold.tsv")
    _fmt_tsv(train_p, y[:n1], X[:n1], t0)
    _fmt_tsv(hold_p, hy[:nh], hX[:nh], time.perf_counter())
    conf = [
        "task = train", "objective = binary", f"num_leaves = {leaves}",
        "max_bin = 63", "learning_rate = 0.1", "min_data_in_leaf = 20",
        "num_trees = 100", "verbosity = -1", "metric = auc",
        f"data = {train_p}",
        f"output_model = {os.path.join(td, 'ref.txt')}",
    ]
    cpath = os.path.join(td, "t.conf")
    with open(cpath, "w") as fh:
        fh.write("\n".join(conf))
    try:
        t0 = time.perf_counter()
        subprocess.run([CLI, f"config={cpath}"], check=True,
                       capture_output=True, timeout=1800)
        log(f"#   ref train: {time.perf_counter() - t0:.1f}s")
        pconf = [
            "task = predict", f"data = {hold_p}",
            f"input_model = {os.path.join(td, 'ref.txt')}",
            f"output_result = {os.path.join(td, 'ref_pred.txt')}",
        ]
        with open(cpath, "w") as fh:
            fh.write("\n".join(pconf))
        subprocess.run([CLI, f"config={cpath}"], check=True,
                       capture_output=True, timeout=600)
        ref_pred = np.loadtxt(os.path.join(td, "ref_pred.txt"))
        auc_ref = auc_of(ref_pred, hy[:nh])
    finally:
        shutil.rmtree(td, ignore_errors=True)
    # ours: same data, same config, on the TPU path
    params = {"objective": "binary", "num_leaves": leaves, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 20,
              "verbosity": -1, "metric": "none"}
    t0 = time.perf_counter()
    ds = lgb.Dataset(X[:n1], label=y[:n1], params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(100):
        bst.update()
    auc_ours = auc_of(bst.predict(hX[:nh]), hy[:nh])
    log(f"#   ours train+predict: {time.perf_counter() - t0:.1f}s")
    log(f"# ref parity (1M rows, 100 iters, 63-bin): "
        f"ours={auc_ours:.6f} ref={auc_ref:.6f}")
    return auc_ours, auc_ref


def run_resume(X, y, leaves, iters):
    """Checkpoint-write overhead + resume warm-up (resilience/): train
    with tpu_checkpoint_freq=10 against a plain run of the same length,
    then resume the final checkpoint into a fresh booster."""
    import shutil
    import tempfile
    params = {"objective": "binary", "num_leaves": leaves, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 20,
              "verbosity": -1}
    ckdir = tempfile.mkdtemp(prefix="bench_ck_")
    try:
        ds = lgb.Dataset(X, label=y, params=params).construct()
        t0 = time.perf_counter()
        lgb.train(dict(params), ds, num_boost_round=iters)
        base_s = time.perf_counter() - t0
        pc = dict(params, tpu_checkpoint_dir=ckdir, tpu_checkpoint_freq=10)
        ds2 = lgb.Dataset(X, label=y, params=params).construct()
        bst = lgb.train(pc, ds2, num_boost_round=iters)
        stats = bst._resilience
        overhead_pct = round(100.0 * stats["ckpt_write_s"]
                             / max(base_s, 1e-9), 2)
        # resume warm-up: restore the final checkpoint into a fresh run
        # (one extra round so the loop body executes once)
        ds3 = lgb.Dataset(X, label=y, params=params).construct()
        res = lgb.train(pc, ds3, num_boost_round=iters + 1)
        warm_s = round(res._resilience["resume_warmup_s"], 4)
        log(f"# resume: ckpt_writes={stats['ckpt_writes']} "
            f"write_s={stats['ckpt_write_s']:.3f} "
            f"overhead={overhead_pct}% warmup_s={warm_s} "
            f"(resumed_from={res._resilience['resumed_from']})")
        return {"ckpt_write_overhead_pct": overhead_pct,
                "resume_warmup_s": warm_s,
                "ckpt_writes": stats["ckpt_writes"]}
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)


def run_sweep(X, y, leaves, iters, M):
    """Many-model fleet throughput (sweep/train_many): one batched
    vmapped round program for M boosters vs M sequential engine.train
    runs over the same grid and the same constructed Dataset. Models
    are trained under tpu_use_f64_hist so the fleet/sequential pair is
    asserted byte-equal — the speedup is never quoted over diverging
    models. One trace warm-up run precedes each arm (the sweep_round
    program for the batched arm, the per-tree programs for the
    sequential arm), so both walls are steady-state."""
    from lightgbm_tpu.obs import memory as obs_memory
    from lightgbm_tpu.sweep import train_many
    params = {"objective": "binary", "num_leaves": leaves, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 20,
              "tpu_use_f64_hist": True, "verbosity": -1}
    lrs = np.linspace(0.05, 0.3, M)
    l2s = np.linspace(0.0, 3.0, M)
    grids = [dict(params, learning_rate=round(float(lr), 4),
                  lambda_l2=round(float(l2), 4))
             for lr, l2 in zip(lrs, l2s)]
    ds = lgb.Dataset(X, label=y, params=params).construct()

    train_many([dict(p) for p in grids], ds, num_boost_round=1)
    t0 = time.perf_counter()
    fleet = train_many([dict(p) for p in grids], ds,
                       num_boost_round=iters)
    bat_s = time.perf_counter() - t0
    # the fleet's live sweep/scores owner row dies with train_many's
    # frame, so the measured stack size rides out on the boosters
    owners = obs_memory.snapshot().get("owners", {})
    stack_bytes = getattr(
        fleet[0], "_sweep_scores_bytes",
        owners.get("sweep/scores", {}).get("bytes", 0))
    hbm_mb = stack_bytes / 1e6 / M

    lgb.train(dict(grids[0]), ds, num_boost_round=1)
    t0 = time.perf_counter()
    seq = [lgb.train(dict(p), ds, num_boost_round=iters) for p in grids]
    seq_s = time.perf_counter() - t0

    equal = all(a.model_to_string() == b.model_to_string()
                for a, b in zip(fleet, seq))
    models_per_s = round(M / max(bat_s, 1e-9), 3)
    speedup = round(seq_s / max(bat_s, 1e-9), 2)
    log(f"# sweep m={M}: batched {bat_s:.2f}s vs sequential "
        f"{seq_s:.2f}s -> {speedup}x, {models_per_s} models/s, "
        f"{hbm_mb:.2f} MB scores/model, byte_equal={equal}")
    return {f"sweep_models_per_s_m{M}": models_per_s,
            f"sweep_speedup_m{M}": speedup,
            f"sweep_hbm_per_model_mb_m{M}": round(hbm_mb, 3),
            f"sweep_byte_equal_m{M}": bool(equal)}


def run_sweep_variant(X, y, leaves, iters, M, variant):
    """Boosting-variant fleet throughput (GOSS or DART): the batched
    vmapped round program vs the interleaved round-robin fallback those
    fleets used before the variant gate opened. Same fleet, same
    Dataset, byte-equal asserted between the two modes (both are
    byte-equal to sequential by the tier-1 parity tests; here the
    cheaper interleaved arm doubles as the oracle)."""
    from lightgbm_tpu.sweep import train_many
    params = {"objective": "binary", "num_leaves": leaves, "max_bin": 63,
              "min_data_in_leaf": 20, "tpu_use_f64_hist": True,
              "verbosity": -1, "boosting": variant}
    if variant == "goss":
        params.update(top_rate=0.2, other_rate=0.1)
    else:
        params.update(drop_rate=0.3, skip_drop=0.5)
    # rates past the GOSS warm-up ramp so the select program runs
    lrs = np.linspace(0.25, 0.6, M)
    grids = [dict(params, learning_rate=round(float(lr), 4))
             for lr in lrs]
    ds = lgb.Dataset(X, label=y, params=params).construct()

    train_many([dict(p) for p in grids], ds, num_boost_round=1)
    t0 = time.perf_counter()
    fleet = train_many([dict(p) for p in grids], ds,
                       num_boost_round=iters)
    bat_s = time.perf_counter() - t0

    inter_grids = [dict(p, tpu_sweep_mode="interleaved") for p in grids]
    train_many([dict(p) for p in inter_grids], ds, num_boost_round=1)
    t0 = time.perf_counter()
    inter = train_many(inter_grids, ds, num_boost_round=iters)
    inter_s = time.perf_counter() - t0

    equal = all(a.model_to_string() == b.model_to_string()
                for a, b in zip(fleet, inter))
    models_per_s = round(M / max(bat_s, 1e-9), 3)
    inter_per_s = round(M / max(inter_s, 1e-9), 3)
    speedup = round(inter_s / max(bat_s, 1e-9), 2)
    log(f"# sweep {variant} m={M}: batched {bat_s:.2f}s vs interleaved "
        f"{inter_s:.2f}s -> {speedup}x, {models_per_s} vs {inter_per_s} "
        f"models/s, byte_equal={equal}")
    return {f"sweep_models_per_s_{variant}_m{M}": models_per_s,
            f"sweep_models_per_s_{variant}_interleaved_m{M}": inter_per_s,
            f"sweep_speedup_{variant}_m{M}": speedup,
            f"sweep_byte_equal_{variant}_m{M}": bool(equal)}


def run_sweep_hetero(X, y, iters, M):
    """Heterogeneous M-in-the-hundreds fleet: mixed num_leaves configs
    partitioned into shape-bucketed sub-fleets (sweep/subfleet.py), each
    its own batched program, interleaved dispatch. Reports fleet
    throughput and the sub-fleet count actually planned — the leg the
    uniform-shape gate used to force through M sequential-ish rounds."""
    from lightgbm_tpu.sweep import plan_subfleets, train_many
    params = {"objective": "binary", "max_bin": 63, "learning_rate": 0.1,
              "min_data_in_leaf": 20, "tpu_use_f64_hist": True,
              "verbosity": -1}
    shapes = (15, 31, 63)
    grids = [dict(params, num_leaves=shapes[m % len(shapes)],
                  learning_rate=round(0.05 + 0.25 * m / M, 4))
             for m in range(M)]
    ds = lgb.Dataset(X, label=y, params=params).construct()

    probes = [lgb.Booster(params=dict(p), train_set=ds) for p in grids]
    plans = plan_subfleets([b._gbdt for b in probes],
                           [b._cfg for b in probes])
    del probes

    train_many([dict(p) for p in grids], ds, num_boost_round=1)
    t0 = time.perf_counter()
    train_many([dict(p) for p in grids], ds, num_boost_round=iters)
    bat_s = time.perf_counter() - t0
    models_per_s = round(M / max(bat_s, 1e-9), 3)
    log(f"# sweep hetero m={M}: {bat_s:.2f}s across {len(plans)} "
        f"sub-fleets -> {models_per_s} models/s")
    return {f"sweep_models_per_s_hetero_m{M}": models_per_s,
            f"sweep_subfleets_m{M}": len(plans)}


def main() -> None:
    global _REC, _LEDGER
    # persistent XLA compilation cache at THE one location
    # (compile_cache.cache_dir()), wired before the first trace: repeat
    # bench runs load compiled executables instead of recompiling
    compile_cache.init_persistent_cache()
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    n = int(os.environ.get("BENCH_ROWS", 20_000 if smoke else 10_500_000))
    f = int(os.environ.get("BENCH_FEATURES", 28))
    iters = int(os.environ.get("BENCH_ITERS", 5 if smoke else 40))
    warmup = int(os.environ.get("BENCH_WARMUP", 2 if smoke else 5))
    leaves = int(os.environ.get("BENCH_LEAVES", 31 if smoke else 255))
    n_hold = 4_000 if smoke else 500_000
    entries_before = compile_cache.cache_dir_entries(
        compile_cache.persistent_cache_dir())

    # the cumulative record exists from second zero: a kill at ANY later
    # point — data gen, first compile, mid-stage — leaves a parseable
    # record on stdout and in the BENCH_OUT sidecar with incomplete:true
    # and the stage reached (round-5's rc=124/parsed:null failure mode)
    out = {"metric": "higgs_synth_500iter_s", "value": None, "unit": "s"}
    _REC = BenchRecorder(out, path=os.environ.get("BENCH_OUT",
                                                  "BENCH_partial.json"),
                         gate=_GATE)
    if os.environ.get("BENCH_TRACE") == "1":
        from lightgbm_tpu.obs import ledger as obs_ledger
        from lightgbm_tpu.obs import trace as obs_trace
        tdir = os.environ.get("BENCH_TRACE_DIR", "lgbt_trace")
        obs_trace.enable(tdir)
        _LEDGER = obs_ledger.RoundLedger(
            os.path.join(tdir, f"bench-{os.getpid()}.jsonl"),
            {"bench": "bench.py", "smoke": smoke})
    _stage("datagen")

    t0 = time.perf_counter()
    Xall, yall = synth_higgs(n + n_hold, f)
    X, y = Xall[:n], yall[:n]
    hX, hy = Xall[n:], yall[n:]
    log(f"# gen={time.perf_counter() - t0:.1f}s rows={n} features={f} "
        f"leaves={leaves}")

    # ---- stage 1: 63-bin HIGGS (the headline throughput number) --------
    # full-model AUCs (500 iterations) for the reference head-to-head:
    # tools/ref_full_headtohead.py caches the reference binary's AUCs on
    # this exact data (the 1-core host makes the ref run an hours-long
    # out-of-band job); ours compute live here
    _stage("higgs63")
    full = 0 if (smoke or os.environ.get("BENCH_SKIP_FULLAUC") == "1") \
        else BASELINE_ITERS
    projected, auc, done63, stats63 = run_higgs(n, f, leaves, iters, warmup,
                                                63, hX, hy, X, y,
                                                full_iters=full)
    cache_dir = compile_cache.persistent_cache_dir()
    entries_after = compile_cache.cache_dir_entries(cache_dir)
    out.update({
        "value": round(projected, 2),
        "vs_baseline": round(BASELINE_S / projected, 3),
        "auc": round(auc, 6) if auc is not None else None,
        "warmup_s": stats63["warmup_s"],
        "compile_s": stats63["compile_s"],
        "bin_s": stats63["bin_s"],
        "ingest_s": stats63.get("ingest_s"),
        "stream_chunk_rows": BENCH_STREAM_CHUNK
        if BENCH_STREAM_CHUNK > 0 else None,
        # warm start = the persistent cache already held programs when
        # this process compiled its first one
        "compile_cache_hit": entries_before > 0,
        "compile_cache": {
            "dir": cache_dir,
            "entries_before": entries_before,
            "entries_after": entries_after,
        },
    })
    if stats63.get("terms_ms"):
        out.setdefault("terms_by_stage", {})["higgs63"] = \
            stats63["terms_ms"]
    if full:
        out["auc_ours_full_63bin"] = out["auc"]
        if done63 < full:
            out["full_iters_done_63bin"] = done63
    ref_cache = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "docs", "ref_full_auc.json")
    if os.path.isfile(ref_cache):
        try:
            rc = json.load(open(ref_cache))
            for k in ("auc_ref_full_63bin", "auc_ref_full_255bin"):
                if k in rc:
                    out[k] = rc[k]
        except Exception:
            pass
    _stage_done("higgs63", out)

    # ---- stage 2: 255-bin HIGGS (apples-to-apples vs the CPU table;
    # runs BEFORE the warm rerun / parity extras — it is the headline
    # gap this repo is closing, so a budget kill must not eat it) -------
    if stage_gate(out, "255bin", "BENCH_SKIP_255",
                  est_s=_GATE.wall("higgs63") * 0.8):
        _stage("255bin")
        projected255, auc255, done255, stats255 = run_higgs(
            n, f, leaves, max(iters // 2, 2), warmup, 255,
            hX if full else None, hy if full else None, X, y,
            full_iters=full)
        out["value_255bin"] = round(projected255, 2)
        out["warmup_s_255bin"] = stats255["warmup_s"]
        out["compile_s_255bin"] = stats255["compile_s"]
        out["bin_s_255bin"] = stats255["bin_s"]
        out["ingest_s_255bin"] = stats255.get("ingest_s")
        out["aligned_255bin"] = stats255["aligned"]
        out["fallbacks_255bin"] = stats255["fallbacks"]
        out["hist_spill_255bin"] = stats255["hist_spill"]
        if stats255.get("terms_ms"):
            out.setdefault("terms_by_stage", {})["255bin"] = \
                stats255["terms_ms"]
        if full and auc255 is not None:
            out["auc_ours_full_255bin"] = round(auc255, 6)
            if done255 < full:
                out["full_iters_done_255bin"] = done255
        _stage_done("255bin", out)

    # ---- stage 3: MSLR lambdarank (second headline experiment; 255-bin
    # x F=137 — the aligned-path spill-ring shape) -----------------------
    if stage_gate(out, "mslr", "BENCH_SKIP_RANK",
                  est_s=_GATE.wall("255bin", _GATE.wall("higgs63")) * 0.9):
        _stage("mslr")
        nm = 30_000 if smoke else 2_270_000
        fm = 20 if smoke else 137
        rit = 4 if smoke else 25
        # shrink the measured window when the budget is tight (per-iter
        # estimated from the 255-bin HIGGS wall scaled to MSLR's rows)
        per_est = _GATE.wall("255bin", _GATE.wall("higgs63")) \
            / max(iters // 2 + warmup, 1) * (nm / max(n, 1))
        rit = _GATE.scale_iters(rit, per_est, overhead_s=per_est * 3,
                                floor=2)
        # fused-vs-bucketed A/B rides along only when its extra booster
        # (bucket-ladder compile + a few iterations) fits the budget
        ab = 3 if _GATE.allow("mslr_ab",
                              est_s=per_est * 8 + (5 if smoke else 60))[0] \
            else 0
        mslr_s, nd, minfo = run_mslr(nm, fm, rit, 2, max_bin=255,
                                     ab_iters=ab)
        out["ndcg10"] = round(nd, 6)
        out["mslr_500iter_s"] = round(mslr_s, 2)
        out["mslr_vs_baseline"] = round(BASELINE_MSLR_S / mslr_s, 3)
        out["mslr_max_bin"] = minfo["max_bin"]
        out["mslr_bin_s"] = minfo["bin_s"]
        out["mslr_ingest_s"] = minfo.get("ingest_s")
        out["mslr_aligned"] = minfo["aligned"]
        out["mslr_fallbacks"] = minfo["fallbacks"]
        out["mslr_hist_spill"] = minfo["hist_spill"]
        out["mslr_rank_fused"] = minfo["rank_fused"]
        out["mslr_rank_fused_fallback_queries"] = \
            minfo["rank_fused_fallback_queries"]
        for k in ("per_iter_fused_ms", "per_iter_bucketed_ms",
                  "rank_fused_speedup"):
            if k in minfo:
                out[f"mslr_{k}"] = minfo[k]
        if minfo.get("terms_ms"):
            out.setdefault("terms_by_stage", {})["mslr"] = \
                minfo["terms_ms"]
        _stage_done("mslr", out)

    # ---- stage 4: serving throughput (serve.ForestEngine vs the seed) --
    if stage_gate(out, "predict", "BENCH_SKIP_PREDICT",
                  est_s=15 if smoke else 90):
        _stage("predict")
        try:
            from tools.bench_predict import run as bench_predict_run
            pred = bench_predict_run(
                num_trees=50 if smoke else 500,
                rows=5_000 if smoke else 100_000,
                repeats=2 if smoke else 3)
            for k in ("predict_seed_rows_s", "predict_engine_rows_s",
                      "predict_speedup"):
                out[k] = pred[k]
        except Exception as e:
            _stage_failed(out, "predict", e)
        _stage_done("predict", out)

    # ---- stage 4.5: serving traffic simulation (serving/ service:
    # model registry + request coalescer + hot swap under load) ----------
    if stage_gate(out, "serve_traffic", "BENCH_SKIP_SERVE",
                  est_s=45 if smoke else 180):
        _stage("serve_traffic")
        try:
            from tools.bench_serve_traffic import run as bench_serve_run
            out.update(bench_serve_run(
                models=2,
                qps_list=(25, 100) if smoke else (50, 200, 800),
                open_secs=1.0 if smoke else 2.0,
                closed_secs=1.0 if smoke else 2.0,
                clients=16 if smoke else 32,
                train_rows=1_500 if smoke else 8_000,
                train_rounds=20 if smoke else 60,
                ledger=_LEDGER, verbose=True))
        except Exception as e:
            _stage_failed(out, "serve_traffic", e)
        _stage_done("serve_traffic", out)

    # ---- stage 5: valid-set overhead (diagnostic) ----------------------
    if stage_gate(out, "valid_overhead", "BENCH_SKIP_VALID",
                  est_s=projected / BASELINE_ITERS * (5 if smoke else 14)):
        _stage("valid_overhead")
        vo_iters = 3 if smoke else 10
        vo_iters = _GATE.scale_iters(
            vo_iters, projected / BASELINE_ITERS * 1.2, floor=2)
        per_valid = run_valid_overhead(X, y, hX[:100_000], hy[:100_000],
                                       leaves, vo_iters, 2)
        base_per = projected / BASELINE_ITERS
        out["valid_overhead_pct"] = round(
            (per_valid / base_per - 1.0) * 100.0, 1)
        _stage_done("valid_overhead", out)

    # ---- stage 5.5: checkpoint/resume cost (resilience/) ---------------
    if stage_gate(out, "resume", "BENCH_SKIP_RESUME",
                  est_s=_GATE.wall("higgs63") * 0.4):
        _stage("resume")
        try:
            rr = run_resume(X[:200_000], y[:200_000], leaves,
                            20 if smoke else 60)
            out.update(rr)
        except Exception as e:
            _stage_failed(out, "resume", e)
        _stage_done("resume", out)

    # ---- stage 5.6: many-model sweep (sweep/train_many): one batched
    # program for the fleet vs M sequential runs, byte-equal asserted --
    if stage_gate(out, "sweep", "BENCH_SKIP_SWEEP",
                  est_s=_GATE.wall("higgs63") * (0.8 if smoke else 2.0)):
        _stage("sweep")
        try:
            sw_iters = 10 if smoke else 30
            sw_rows = min(len(X), 20_000 if smoke else 100_000)
            t8 = time.perf_counter()
            out.update(run_sweep(X[:sw_rows], y[:sw_rows], leaves,
                                 sw_iters, 8))
            t8 = time.perf_counter() - t8
            # M=32 scales the sequential arm 4x; run it only when the
            # measured M=8 wall says it still fits the budget
            left = budget_left()
            if smoke:
                out.setdefault("stage_skips", {})["sweep_m32"] = \
                    "BENCH_SMOKE=1"
            elif left is not None and left < t8 * 3.5:
                out.setdefault("stage_skips", {})["sweep_m32"] = (
                    f"adaptive skip: m32 needs ~{t8 * 3.5:.0f}s, "
                    f"{left:.0f}s left")
            else:
                out.update(run_sweep(X[:sw_rows], y[:sw_rows], leaves,
                                     sw_iters, 32))
            # variant fleets: batched vs the interleaved fallback they
            # used before the gate admitted them. The ratio is a
            # device property — the batched program wins where the
            # histogram build is an MXU one-hot contraction; on CPU
            # emulation the vmapped scatter thrashes past a few
            # thousand rows (the plain M=8 leg above degrades the same
            # way), so smoke keeps the variant legs at a row count the
            # emulated build handles in seconds
            var_m = 4 if smoke else 8
            var_rows = min(sw_rows, 2_000 if smoke else sw_rows)
            for variant in ("goss", "dart"):
                out.update(run_sweep_variant(
                    X[:var_rows], y[:var_rows], leaves, sw_iters, var_m,
                    variant))
            # M=128 mixed-shape fleet via shape-bucketed sub-fleets;
            # smoke keeps the fleet small but still multi-bucket
            het_m, het_iters = (12, 5) if smoke else (128, 10)
            het_rows = min(sw_rows, 2_000 if smoke else 20_000)
            out.update(run_sweep_hetero(X[:het_rows], y[:het_rows],
                                        het_iters, het_m))
        except Exception as e:
            _stage_failed(out, "sweep", e)
        _stage_done("sweep", out)

    # ---- stage 7: reference-binary parity (slowest, least perishable) --
    if smoke:
        out.setdefault("stage_skips", {})["ref_parity"] = "BENCH_SMOKE=1"
    elif stage_gate(out, "ref_parity", "BENCH_SKIP_REF",
                    est_s=max(_GATE.wall("higgs63") * 2.0, 300)):
        _stage("ref_parity")
        try:
            auc_ours_1m, auc_ref = run_ref_parity(X, y, hX, hy, leaves)
            if auc_ref is not None:
                out["auc_ours_1m_100it"] = round(auc_ours_1m, 6)
                out["auc_ref"] = round(auc_ref, 6)
        except Exception as e:
            _stage_failed(out, "ref_parity", e)
        _stage_done("ref_parity", out)

    out["wall_s"] = round(time.perf_counter() - _T0, 1)
    _REC.finalize()
    if _LEDGER is not None:
        _LEDGER.close()
    if os.environ.get("BENCH_TRACE") == "1":
        # merge every stream this run produced (spans, ledgers, events,
        # the bench stage notes) into the Perfetto-openable timeline,
        # next to trace_summary.json — same artifact the CLI writes
        try:
            from lightgbm_tpu.obs import timeline as obs_timeline
            tdir = os.environ.get("BENCH_TRACE_DIR", "lgbt_trace")
            doc = obs_timeline.build_timeline(tdir, bench=out)
            path = obs_timeline.write_timeline(
                os.path.join(tdir, "timeline.json"), doc)
            log(f"# timeline: {path}")
        except Exception as e:  # the record on stdout already landed
            log(f"# timeline export FAILED: {type(e).__name__}: {e}")
    if out.get("stage_errors"):
        sys.exit(1)


if __name__ == "__main__":
    main()
