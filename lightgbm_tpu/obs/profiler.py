"""In-run bottleneck profiler: sampled per-term device time, static
XLA cost/roofline attribution, and programmatic capture windows.

Three planes, all opt-in via ``tpu_profile`` (see ``config.py``):

**Sampled per-term device time.** On a sampled round (``round > 0`` and
``round % tpu_profile_every == 0`` — round 0 pays XLA compiles and
would report them as kernel time) the round loop fences EVERY device
dispatch site individually instead of issuing the single end-of-round
residual fence: ``GBDT._dispatch_device`` routes each dispatch through
``RoundSample.timed`` (dispatch, then ``trace.force_fence`` on the
output pytree), and the gradient / score-update / eval sites do the
same. Site times aggregate into a ``terms_ms`` dict over the canonical
vocabulary (``obs/terms.py``) which lands in the ledger round record
(``timing: "fenced"``), in per-term gauges on the metrics registry
(scraped by the serving ``/metrics`` exporter), and — via bench.py —
in ``terms_by_stage`` in bench JSON. Because fencing serializes the
pipelined round, a sampled round's ``device_ms`` is the SUM of fenced
site times, not the residual drain; sampled rounds are excluded from
the ``train_round_ms`` histogram so they cannot pollute p50/p99, and
the record carries ``profiled: true`` so readers never mix the two
timing modes (see docs/Profiling.md).

**Chained-k build calibration.** The aligned path's whole-tree build
is ONE fused program, so fencing can only see its total. On the first
sampled round the profiler reuses the ``obs/devicetime.py`` chained-k
protocol to measure the per-pass cost of the build's constituent
kernels (``hist`` / ``route`` / ``flush`` / ``split_eval``) over the
LIVE engine's record store at its real shapes — the same closures
``tools/device_time_255.py`` runs offline at guessed shapes. The
calibration lands once as a ledger note (``profile_calibration``) and
``tools/bottleneck_report.py`` uses its shares to decompose the fenced
``build`` total in the ranked report. A calibration failure degrades
to the unsplit ``build`` term — it never voids the fenced numbers.

**Static cost attribution.** With the profiler on, ``compile_cache``
captures the abstract arg shapes of every registered program at first
dispatch; ``write_program_costs`` lowers each against those specs and
records XLA ``cost_analysis()`` (flops, bytes accessed) into
``program_costs.json``, classifying each program compute- vs
bandwidth-bound against the device roofline and pairing the estimate
with the measured per-call dispatch wall.

**Capture windows.** ``tpu_profile_capture=start:stop`` brackets those
rounds in a programmatic ``jax.profiler`` trace whose artifact path
lands in ``trace_summary.json``.

Off (``tpu_profile=off``, the default) the round loop pays one is-None
attribute check and adds ZERO fences — asserted by tier-1 alongside
the ``tpu_metrics`` discipline.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import trace
from .terms import TERMS, term_for_site

# ---------------------------------------------------------------------------
# device roofline table: (peak dense bf16 TFLOP/s, HBM GB/s) by
# device_kind substring — published per-chip peaks (Google Cloud TPU
# documentation); program_costs.json records which row was used. A TPU
# that is not in the table is an error, not a default.
_ROOFLINES: Tuple[Tuple[str, float, float], ...] = (
    ("v6", 918.0, 1640.0),         # Trillium
    ("v5p", 459.0, 2765.0),
    ("v5", 197.0, 819.0),          # v5e ("TPU v5 lite")
    ("v4", 275.0, 1228.0),
    ("v3", 123.0, 900.0),
    ("v2", 45.0, 700.0),
)


def device_roofline() -> Optional[Dict[str, Any]]:
    """{kind, matched, peak_tflops, hbm_gbps, ridge_flops_per_byte} for
    the first visible jax device. None on the CPU backend (a host core
    has no published roofline worth classifying against); an accelerator
    whose ``device_kind`` matches no table row raises."""
    import jax
    if jax.default_backend() == "cpu":
        return None
    kind = str(jax.devices()[0].device_kind).lower()
    for sub, tflops, gbps in _ROOFLINES:
        if sub in kind:
            return {"kind": kind, "matched": sub, "peak_tflops": tflops,
                    "hbm_gbps": gbps,
                    "ridge_flops_per_byte": round(
                        tflops * 1e12 / (gbps * 1e9), 2)}
    raise ValueError(f"no roofline row for device_kind {kind!r}; add its "
                     f"published peaks to obs/profiler._ROOFLINES")


def classify_program(flops: float, bytes_accessed: float,
                     roofline: Dict[str, Any]) -> Dict[str, Any]:
    """Roofline classification of one program: estimated compute and
    bandwidth times, arithmetic intensity, and which bound wins."""
    t_compute_ms = flops / (roofline["peak_tflops"] * 1e12) * 1e3
    t_bw_ms = bytes_accessed / (roofline["hbm_gbps"] * 1e9) * 1e3
    ai = flops / bytes_accessed if bytes_accessed > 0 else float("inf")
    return {
        "est_compute_ms": round(t_compute_ms, 4),
        "est_bandwidth_ms": round(t_bw_ms, 4),
        "est_ms": round(max(t_compute_ms, t_bw_ms), 4),
        "arithmetic_intensity": (round(ai, 3)
                                 if ai != float("inf") else None),
        "bound": ("compute" if t_compute_ms >= t_bw_ms
                  else "bandwidth"),
    }


def _cost_scalars(cost: Any) -> Dict[str, float]:
    """Normalize jax `compiled.cost_analysis()` across versions (dict
    or [dict]) to {flops, bytes_accessed, transcendentals}."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    if not isinstance(cost, dict):
        return {}
    out = {}
    for want, keys in (("flops", ("flops",)),
                       ("bytes_accessed", ("bytes accessed",
                                           "bytes_accessed")),
                       ("transcendentals", ("transcendentals",))):
        for k in keys:
            v = cost.get(k)
            if isinstance(v, (int, float)):
                out[want] = float(v)
                break
    return out


def collect_program_costs() -> Dict[str, Any]:
    """XLA ``cost_analysis()`` for every compile_cache program whose
    arg specs were captured (the profiler enables capture at
    construction): ``{schema, device, programs: {tag: {...}}}``.
    Programs that fail to lower record an ``error`` entry instead of
    voiding the artifact."""
    from .. import compile_cache
    roofline = device_roofline()
    doc: Dict[str, Any] = {"schema": 1, "device": roofline,
                           "programs": {}}
    for ent in compile_cache.captured_programs().values():
        tag = ent["tag"]
        row: Dict[str, Any] = {
            "calls": ent["calls"],
            # host-side dispatch wall (async on TPU — a lower bound on
            # nothing, an upper bound on host cost; on CPU effectively
            # the measured run time). Paired with est_ms below.
            "dispatch_ms_total": round(ent["dispatch_ms"], 2),
            "dispatch_ms_per_call": round(
                ent["dispatch_ms"] / max(ent["calls"], 1), 3),
        }
        try:
            lowered = ent["fn"].lower(*ent["spec_args"],
                                      **ent["spec_kwargs"])
            cost = _cost_scalars(lowered.compile().cost_analysis())
            flops = cost.get("flops", 0.0)
            byts = cost.get("bytes_accessed", 0.0)
            row.update({"flops": flops, "bytes_accessed": byts})
            if roofline is not None and (flops or byts):
                row.update(classify_program(flops, byts, roofline))
        except Exception as e:  # noqa: BLE001 — per-program, keep going
            row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
        doc["programs"][tag] = row
    return doc


def write_program_costs(path: str) -> str:
    """Write the ``collect_program_costs`` artifact atomically."""
    doc = collect_program_costs()
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True, default=str)
    os.replace(tmp, path)
    return path


# ---------------------------------------------------------------------------
def _per_device_segments(out: Any, t_start: float
                         ) -> Optional[List[Tuple[int, float]]]:
    """Per-shard wait-attribution of one dispatch: find the first
    multi-shard jax.Array in the output pytree and block its
    addressable shards one by one in device-id order, charging each
    device the INCREMENT of wall spent until its shard was ready
    (the first segment starts at `t_start`, the site's dispatch start,
    so host dispatch wall lands in the first-ready device's column).

    The increments tile the site's wall — device k's column is
    "additional wall spent waiting on shard k after shard k-1 was
    ready", so the columns SUM to the aggregate fenced site time by
    construction (the straggler shard absorbs the skew; earlier-ready
    shards read ~0 once the slowest has been paid for). None when the
    output has no multi-shard array (single-device run, host-only
    site) — the caller falls back to the aggregate fence."""
    try:
        import jax
        leaves = jax.tree_util.tree_leaves(out)
        target = None
        for leaf in leaves:
            if isinstance(leaf, jax.Array):
                try:
                    shards = leaf.addressable_shards
                except Exception:  # noqa: BLE001 — committed-elsewhere
                    continue
                if len(shards) > 1:
                    target = shards
                    break
        if target is None:
            return None
        segs: List[Tuple[int, float]] = []
        t_prev = t_start
        for sh in sorted(target, key=lambda s: s.device.id):
            trace.force_fence(sh.data)
            now = time.perf_counter()
            segs.append((int(sh.device.id), (now - t_prev) * 1e3))
            t_prev = now
        return segs
    except Exception:  # noqa: BLE001 — attribution must not break a round
        return None


class RoundSample:
    """Per-site fenced times of ONE sampled round. ``timed`` is the
    seam ``GBDT._dispatch_device`` (and the gradient / score-update /
    eval sites) routes through while ``_prof_round`` is set. With
    ``per_device`` (profiled distributed rounds under the timeline),
    each site's drain is additionally attributed per shard — see
    ``_per_device_segments``."""

    __slots__ = ("round", "sites", "t0", "per_device", "device_sites")

    def __init__(self, rnd: int, per_device: bool = False) -> None:
        self.round = rnd
        self.sites: Dict[str, float] = {}
        self.t0 = time.perf_counter()
        self.per_device = per_device
        # site -> {device_id: ms} (only sites whose output was sharded)
        self.device_sites: Dict[str, Dict[int, float]] = {}

    def timed(self, site: str, fn: Callable, *args):
        """Run one dispatch, fence its output pytree, and charge the
        dispatch+drain wall to `site` (sites accumulate — the aligned
        valid walk hits score_update once per valid set)."""
        t0 = time.perf_counter()
        out = fn(*args)
        if self.per_device:
            segs = _per_device_segments(out, t0)
            if segs is not None:
                acc = self.device_sites.setdefault(site, {})
                for did, ms in segs:
                    acc[did] = acc.get(did, 0.0) + ms
        trace.force_fence(out)
        self.sites[site] = self.sites.get(site, 0.0) \
            + (time.perf_counter() - t0) * 1e3
        return out

    def device_total_ms(self) -> float:
        return sum(self.sites.values())

    def device_columns(self, objective: str = ""
                       ) -> Optional[Dict[str, Any]]:
        """Fold ``device_sites`` into the ledger's per-device block:
        ``{device_ids, device_terms_ms, device_round_ms, imbalance,
        allreduce_split_ms?}`` — or None when no site produced
        shard-level segments."""
        if not self.device_sites:
            return None
        ids = sorted({did for per in self.device_sites.values()
                      for did in per})
        dterms: Dict[str, List[float]] = {}
        for site, per in self.device_sites.items():
            term = term_for_site(site, objective)
            col = dterms.setdefault(term, [0.0] * len(ids))
            for k, did in enumerate(ids):
                col[k] += per.get(did, 0.0)
        dterms = {t: [round(v, 3) for v in col]
                  for t, col in dterms.items()}
        totals = [round(sum(col[k] for col in dterms.values()), 3)
                  for k in range(len(ids))]
        out: Dict[str, Any] = {"device_ids": ids,
                               "device_terms_ms": dterms,
                               "device_round_ms": totals}
        med = sorted(totals)[len(totals) // 2] if len(totals) % 2 \
            else sum(sorted(totals)[len(totals) // 2 - 1:
                                    len(totals) // 2 + 1]) / 2.0
        if med > 0:
            out["imbalance"] = round(max(totals) / med, 3)
        ar = self.device_sites.get("dist.allreduce")
        if ar:
            # first-ready shard ~ everyone computing; the rest is the
            # skew the slow shard made the collective wait for
            vals = [ar.get(d, 0.0) for d in ids]
            compute = min(v for v in vals if v > 0) if any(
                v > 0 for v in vals) else 0.0
            out["allreduce_split_ms"] = {
                "compute": round(compute, 3),
                "wait": round(max(sum(vals) - compute, 0.0), 3)}
        return out


class RoundProfiler:
    """The booster-held profiler object (``GBDT._profiler``; None when
    off). Holds sampling state, the one-time build calibration, capture
    windows, and the last sampled ``terms_ms`` (bench reads it)."""

    def __init__(self, every: int = 50,
                 capture: Optional[Tuple[int, int]] = None,
                 capture_dir: str = "", objective: str = "") -> None:
        self.every = max(int(every), 1)
        self.capture = capture
        self.capture_dir = capture_dir
        self.objective = objective
        self.calibration: Optional[Dict[str, Any]] = None
        self.calibration_committed = False   # ledger-note latch (gbdt)
        self._calibrated = False
        self.history: List[Dict[str, Any]] = []   # [{round, terms_ms}]
        self.last_terms: Optional[Dict[str, float]] = None
        self._capturing = False
        self.capture_paths: List[str] = []
        self._force_next = False

    # -- construction -------------------------------------------------
    @classmethod
    def from_config(cls, cfg: Any) -> Optional["RoundProfiler"]:
        """None unless profiling should be live for this booster:
        ``on`` is unconditional, ``auto`` piggybacks on an observability
        plane already being enabled (tpu_trace or tpu_metrics), ``off``
        never."""
        mode = str(getattr(cfg, "tpu_profile", "off")).lower()
        if mode not in ("on", "auto"):
            return None
        if mode == "auto" and not (getattr(cfg, "tpu_trace", False)
                                   or getattr(cfg, "tpu_metrics",
                                              False)):
            return None
        capture = None
        spec = str(getattr(cfg, "tpu_profile_capture", "") or "")
        if spec:
            try:
                a, b = spec.split(":")
                capture = (int(a), int(b))
                if capture[1] <= capture[0]:
                    raise ValueError(spec)
            except ValueError:
                from ..utils import log
                log.warning(f"tpu_profile_capture={spec!r} is not "
                            f"'start:stop'; capture disabled")
                capture = None
        every = int(getattr(cfg, "tpu_profile_every", 0) or 0) or 50
        cdir = getattr(cfg, "tpu_trace_dir", "") or "lgbt_trace"
        return cls(every=every, capture=capture, capture_dir=cdir,
                   objective=getattr(cfg, "objective", ""))

    # -- sampling -----------------------------------------------------
    def should_sample(self, rnd: int) -> bool:
        """Round 0 is never sampled: it pays the XLA compiles, and a
        fence there would book compile wall as kernel time."""
        if self._force_next:
            return True
        return rnd > 0 and rnd % self.every == 0

    def force_next(self) -> None:
        """Make the next round a sampled round regardless of cadence
        (bench profiles ONE round after its timed loop so the timed
        loop itself stays fence-free)."""
        self._force_next = True

    def begin_round(self, rnd: int,
                    per_device: bool = False) -> RoundSample:
        self._force_next = False
        return RoundSample(rnd, per_device=per_device)

    def finish_round(self, sample: RoundSample,
                     engine: Any = None,
                     cfg: Any = None) -> Dict[str, Optional[float]]:
        """Fold a completed sample into canonical ``terms_ms`` (site ->
        term aggregation) and run the one-time build calibration while
        the engine is live."""
        terms: Dict[str, float] = {}
        for site, ms in sample.sites.items():
            term = term_for_site(site, self.objective)
            terms[term] = terms.get(term, 0.0) + ms
        out = {k: round(v, 3) for k, v in terms.items()}
        self.last_terms = out
        self.history.append({"round": sample.round, "terms_ms": out})
        if engine is not None and not self._calibrated:
            self._calibrated = True
            self.calibration = calibrate_build_terms(engine, cfg)
        return out

    # -- capture windows ----------------------------------------------
    def maybe_capture(self, rnd: int) -> None:
        """Start/stop the programmatic ``jax.profiler`` trace at the
        configured round window. Failures disable capture rather than
        break training (the profiler is observability, not the
        product)."""
        if self.capture is None:
            return
        start, stop = self.capture
        if not self._capturing and rnd == start:
            path = os.path.join(self.capture_dir,
                                f"xprof-r{start}-r{stop}")
            try:
                import jax
                os.makedirs(path, exist_ok=True)
                jax.profiler.start_trace(path)
                self._capturing = True
                self.capture_paths.append(path)
            except Exception as e:  # noqa: BLE001
                from ..utils import log
                log.warning(f"profiler capture failed to start: {e}")
                self.capture = None
        elif self._capturing and rnd >= stop:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._capturing = False

    def close(self) -> None:
        """End-of-training hook: close a still-open capture window
        (stop round beyond num_iterations)."""
        if self._capturing:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._capturing = False

    # -- artifacts ----------------------------------------------------
    def summary(self, out_dir: str) -> Dict[str, Any]:
        """Write ``program_costs.json`` under `out_dir` and return the
        summary block the CLI folds into ``trace_summary.json``."""
        self.close()
        os.makedirs(out_dir, exist_ok=True)
        costs_path = os.path.join(out_dir, "program_costs.json")
        try:
            write_program_costs(costs_path)
        except Exception as e:  # noqa: BLE001
            costs_path = None
            from ..utils import log
            log.warning(f"program_costs.json failed: {e}")
        return {
            "sampled_rounds": [h["round"] for h in self.history],
            "every": self.every,
            "last_terms_ms": self.last_terms,
            "calibration": self.calibration,
            "program_costs": costs_path,
            "captures": list(self.capture_paths),
        }

    def mean_terms(self) -> Dict[str, float]:
        """Mean per-term ms over all sampled rounds (bench's
        ``terms_by_stage`` entry)."""
        acc: Dict[str, List[float]] = {}
        for h in self.history:
            for k, v in h["terms_ms"].items():
                if v is not None:
                    acc.setdefault(k, []).append(v)
        return {k: round(sum(v) / len(v), 3) for k, v in acc.items()}


# ---------------------------------------------------------------------------
def calibrate_build_terms(eng: Any, cfg: Any = None,
                          chain: int = 4, reps: int = 2
                          ) -> Optional[Dict[str, Any]]:
    """Chained-k per-pass cost of the fused build's constituent kernels
    over the LIVE aligned engine's record store — the in-process
    version of ``tools/device_time_255.py`` at the REAL shapes instead
    of guessed ones. Returns ``{terms_ms: {hist, route, flush,
    split_eval}, shares: {...}, shapes: {...}}`` or None when the
    engine's layout defeats the closures (every term measured under
    ``TermTimer`` — individual failures go null, a total failure
    returns None)."""
    try:
        return _calibrate_build_terms(eng, cfg, chain, reps)
    except Exception as e:  # noqa: BLE001 — calibration must not break
        from ..utils import log
        log.warning(f"profiler build calibration failed: "
                    f"{type(e).__name__}: {str(e)[:200]}")
        return None


def _calibrate_build_terms(eng: Any, cfg: Any, chain: int,
                           reps: int) -> Optional[Dict[str, Any]]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from ..ops.aligned import move_pass, pack_route2, slot_hist_pass
    from .devicetime import TermTimer

    lr = eng.learner
    C, W, wcnt, NC = eng.C, eng.W, eng.wcnt, eng.NC
    G = eng.ncols
    BH = lr.hist_bins if lr.bundled else lr.max_bin_global
    group = 8 if BH <= 64 else 4
    K = min(max(eng.S - 1, 1), 256)
    subbin = bool(getattr(eng, "hist_subbin", False))
    spill = bool(getattr(eng, "hist_spill", False))
    gfn = eng._pgrad if eng.compact else None
    bag_lane = (-2 if eng.compact
                else eng.lanes.get("bag", -1)) if eng.bagged else -1
    nc_data = int(jax.device_get(jnp.sum(eng.cnts > 0))) or 1
    mid_bin = max(BH // 2, 1)

    tt = TermTimer({"shapes": {"NC": NC, "W": W, "C": C, "G": G,
                               "BH": BH, "K": K, "subbin": subbin,
                               "spill": spill}},
                   chain=chain, reps=reps, catalog=TERMS)

    meta_cnt = np.asarray(jax.device_get(eng.cnts), np.int32)
    # every data chunk splits at mid-bin of feature 0 — the same
    # synthetic routing device_time_255 uses, now over the live store
    r1 = np.full(NC, mid_bin | (1 << 13), np.int32)
    meta = meta_cnt.copy()
    meta[0] |= 1 << 20
    meta[max(nc_data - 1, 0)] |= 1 << 21
    r2 = np.full(NC, pack_route2(0, BH), np.int32)
    basel = np.zeros(NC, np.int32)
    baser = np.full(NC, max(nc_data // 2, 1), np.int32)
    wsel = np.zeros(NC, np.int32)
    nohist = np.full(NC, K, np.int32)
    cb0 = jnp.zeros((eng.S + 2) * 8, jnp.int32)
    rec0 = eng.rec        # read-only input; move_pass returns a copy

    def mk_move(hsl):
        a = tuple(jnp.asarray(x) for x in
                  (r1, r2, basel, baser, meta, wsel, hsl))

        def mk(k):
            @jax.jit
            def f(r):
                def body(i, bufs):
                    # pass i reads buffer i % 2 and writes the other, as
                    # the build program's round loop does
                    return move_pass(
                        *bufs, i % 2, *a, cb0, C, W, wcnt, K, G, BH, group,
                        bag_lane=bag_lane, bits=eng.bits, grad_fn=gfn,
                        num_class=eng.num_class, w_used=eng.w_used,
                        gh_off=eng.gh_off, bundled=lr.bundled,
                        interpret=eng.interpret, subbin=subbin,
                        spill=spill)[:2]
                return lax.fori_loop(0, k, body, (r, jnp.zeros_like(r)))
            return f
        return mk

    tt.measure("route", mk_move(nohist), rec0, rows=eng.n)
    tt.measure("hist_move", mk_move(np.zeros(NC, np.int32)), rec0,
               rows=eng.n)
    tt.derive("flush", "hist_move", "route")

    slots = np.where(meta_cnt > 0, 0, 1).astype(np.int32)
    sl_j = jnp.asarray(slots)
    mc_j = jnp.asarray(meta_cnt)

    def mk_hist(k):
        @jax.jit
        def f(r):
            def body(i, carry):
                r, acc = carry
                h = slot_hist_pass(
                    r, sl_j, mc_j, 1, G, BH, C, group, wcnt,
                    bag_lane=bag_lane, bits=eng.bits, grad_fn=gfn,
                    num_class=eng.num_class, gh_off=eng.gh_off,
                    interpret=eng.interpret, subbin=subbin)
                r = r.at[0, 0, 0].add(1)
                return (r, acc + h[0, 0, 0, 0])
            return lax.fori_loop(0, k, body, (r, jnp.float32(0.0)))
        return f

    tt.measure("hist", mk_hist, rec0, rows=eng.n)

    # split finder over a changed-children histogram batch (the
    # learner's REAL finder, random histograms at its real [F, B])
    try:
        F = lr.num_features
        B = lr.max_bin_global
        finder = lr.finder
        rng = np.random.RandomState(0)
        splitk = 8
        hist_b = jnp.asarray(
            rng.rand(splitk, F, B, 3).astype(np.float32))
        sg = jnp.sum(hist_b[..., 0], axis=(1, 2)) / F
        sh = jnp.sum(hist_b[..., 1], axis=(1, 2)) / F
        cntv = jnp.full((splitk,), np.float32(eng.n))
        minc = jnp.full((splitk,), np.float32(-1e30))
        maxc = jnp.full((splitk,), np.float32(1e30))
        vf = jax.vmap(lambda h, g, hh, c, lo, hi:
                      finder(h, g, hh, c, lo, hi)["gain"])

        def mk_split(k):
            @jax.jit
            def f(h):
                def body(i, carry):
                    h, acc = carry
                    gain = vf(h, sg, sh, cntv, minc, maxc)
                    return (h + 1e-6, acc + gain[0, 0])
                return lax.fori_loop(0, k, body, (h, jnp.float32(0.0)))
            return f

        tt.measure("split_eval", mk_split, hist_b)
    except Exception as e:  # noqa: BLE001
        tt.out["terms_ms"]["split_eval"] = None
        tt.out["split_eval_error"] = f"{type(e).__name__}"

    terms = {k: v for k, v in tt.out["terms_ms"].items()}
    measured = {k: v for k, v in terms.items() if v}
    if not measured:
        return None
    total = sum(measured.values())
    return {
        "terms_ms": terms,
        "shares": {k: round(v / total, 4) for k, v in measured.items()},
        "shapes": tt.out["shapes"],
        "protocol": {"chain": chain, "reps": reps},
    }
