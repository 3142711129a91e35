#!/usr/bin/env python
"""Per-term DEVICE-time breakdown of the 255-bin aligned round.

Thin CLI over ``lightgbm_tpu.obs.devicetime`` — the chained-k protocol
(kernel chained k times inside one jitted fori_loop, per-exec seconds =
(t_K - t_1) / (K - 1), so host dispatch overhead cancels)
lives there; this file only builds the 255-bin term closures:

  hist        slot_hist_pass over the full record store (root-shape,
              sub-binned accumulation when the layout enables it)
  route       move_pass with every block splitting and NO hist slots
              (pure routing: decode + partition + compact store)
  flush       hist-accumulating move_pass minus `route` — the marginal
              cost of the fused sub-binned accumulate + slot flush
              (through the HBM DMA ring when the layout spills)
  split_eval  the jitted split finder over a [SPLITK, F, B, 3] batch
              (the per-round changed-children evaluation)
  rank_grad   the lambdarank gradient pass over an MSLR-like query
              distribution (segment-fused Pallas kernel when available,
              bucketed pair tensors otherwise; "rank_fused" in the JSON
              says which was measured)

Emits ONE JSON line on stdout:
  {"n": ..., "features": ..., "max_bin": 255, "chunk": ...,
   "subbin": ..., "spill": ..., "rank_docs": ..., "rank_queries": ...,
   "rank_fused": ...,
   "terms_ms": {"hist": ..., "route": ..., "flush": ...,
                "split_eval": ..., "rank_grad": ...}}

Env knobs: DT255_ROWS (default 10_500_000), DT255_FEATURES (28),
DT255_CHUNK (1024), DT255_SPLITK (16), DT255_REPS (3), DT255_CHAIN (8),
DT255_RANK_DOCS (2_270_000; 0 skips the rank_grad term),
DT255_INTERPRET=1 (CPU interpret-mode kernels — the -m slow smoke test
in tests/test_subbin_spill.py runs a tiny shape this way).

Term names come from the canonical vocabulary in
``lightgbm_tpu.obs.terms.TERMS`` (the TermTimer runs with the catalog,
so a drifted name is a crash, not quiet JSON): a "rank_grad" in this
tool's output and one in a profiler ledger are the same quantity.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# what this tool measures, in canonical obs/terms.py vocabulary
# (asserted against TERMS by tests/test_profiler.py)
TERMS_MEASURED = ("route", "flush", "hist", "split_eval", "rank_grad")

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

N = int(os.environ.get("DT255_ROWS", 10_500_000))
F = int(os.environ.get("DT255_FEATURES", 28))
C = int(os.environ.get("DT255_CHUNK", 1024))
SPLITK = int(os.environ.get("DT255_SPLITK", 16))
REPS = int(os.environ.get("DT255_REPS", 3))
CHAIN = int(os.environ.get("DT255_CHAIN", 8))
INTERPRET = os.environ.get("DT255_INTERPRET") == "1"
MB = 255
S = 64


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def main():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.obs.devicetime import TermTimer
    from lightgbm_tpu.obs.terms import TERMS
    from lightgbm_tpu.ops.aligned import hist_layout, move_pass, \
        pack_records, pack_route2, slot_hist_pass
    from lightgbm_tpu.ops.split import SplitHyper, make_split_finder

    cfg = Config()
    rng = np.random.RandomState(3)
    bins = rng.randint(0, MB, (N, F)).astype(np.uint8)
    label = rng.randint(0, 2, N).astype(np.float32)
    group = 4
    B = 256

    rec_np, wcnt, W, cnts, _bits = pack_records(bins, label, None, C)
    nc_data = rec_np.shape[0]
    NC = nc_data + 4
    fullr = np.zeros((NC, W, C), np.int32)
    fullr[:nc_data] = rec_np
    rec = jnp.asarray(fullr)
    del fullr
    meta_cnt = np.zeros(NC, np.int32)
    meta_cnt[:nc_data] = cnts
    subbin, spill, slot_bytes, budget = hist_layout(cfg, F, B, S)
    log(f"# n={N} F={F} C={C} chunks={nc_data} subbin={subbin} "
        f"spill={spill} ({slot_bytes >> 10} KB/slot, "
        f"budget {budget >> 20} MB)")

    tt = TermTimer({"n": N, "features": F, "max_bin": MB, "chunk": C,
                    "subbin": subbin, "spill": spill},
                   chain=CHAIN, reps=REPS, log=log, catalog=TERMS)

    # ---- route / flush: every block splits at mid-bin -----------------
    r1 = np.full(NC, (MB // 2) | (1 << 13), np.int32)
    meta = meta_cnt.copy()
    meta[0] |= 1 << 20
    meta[nc_data - 1] |= 1 << 21
    r2 = np.full(NC, pack_route2(0, B), np.int32)
    basel = np.zeros(NC, np.int32)
    baser = np.full(NC, nc_data // 2, np.int32)
    wsel = np.zeros(NC, np.int32)
    nohist = np.full(NC, S + 1, np.int32)
    cb0 = jnp.zeros((S + 2) * 8, jnp.int32)

    def mk_move(hsl):
        a = tuple(jnp.asarray(x) for x in
                  (r1, r2, basel, baser, meta, wsel, hsl))

        def mk(k):
            @jax.jit
            def f(r):
                def body(i, bufs):     # read buffer i % 2, write the other
                    return move_pass(*bufs, i % 2, *a, cb0, C, W, wcnt,
                                     S + 1, F, B, group,
                                     interpret=INTERPRET, subbin=subbin,
                                     spill=spill)[:2]
                return lax.fori_loop(0, k, body, (r, jnp.zeros_like(r)))
            return f
        return mk

    tt.measure("route", mk_move(nohist), rec, rows=N)
    tt.measure("hist_move", mk_move(np.zeros(NC, np.int32)), rec, rows=N)
    tt.derive("flush", "hist_move", "route")

    # ---- hist: the full root-shape slot_hist_pass ---------------------
    slots = np.zeros(NC, np.int32)
    slots[nc_data:] = S + 1
    sl_j = jnp.asarray(slots)
    mc_j = jnp.asarray(meta_cnt)

    def mk_hist(k):
        @jax.jit
        def f(r):
            def body(i, carry):
                r, acc = carry
                h = slot_hist_pass(r, sl_j, mc_j, S + 1, F, B, C, group,
                                   wcnt, interpret=INTERPRET,
                                   subbin=subbin)
                r = r.at[0, 0, 0].add(1)
                return (r, acc + h[0, 0, 0, 0])
            return lax.fori_loop(0, k, body, (r, jnp.float32(0.0)))
        return f

    tt.measure("hist", mk_hist, rec, rows=N)

    # ---- split_eval: the finder over a changed-children batch ---------
    fmeta = {
        "num_bin": np.full(F, B, np.int32),
        "default_bin": np.zeros(F, np.int32),
        "missing_type": np.zeros(F, np.int32),
        "bin_type": np.zeros(F, np.int32),
        "monotone": np.zeros(F, np.int32),
        "penalty": np.ones(F, np.float32),
    }
    finder = make_split_finder(SplitHyper.from_config(cfg), fmeta, B)
    hist_b = jnp.asarray(
        rng.rand(SPLITK, F, B, 3).astype(np.float32))
    sg = jnp.sum(hist_b[..., 0], axis=(1, 2)) / F
    sh = jnp.sum(hist_b[..., 1], axis=(1, 2)) / F
    cnt = jnp.full((SPLITK,), np.float32(N))
    minc = jnp.full((SPLITK,), np.float32(-1e30))
    maxc = jnp.full((SPLITK,), np.float32(1e30))
    vf = jax.vmap(lambda h, g, hh, c, lo, hi:
                  finder(h, g, hh, c, lo, hi)["gain"])

    def mk_split(k):
        @jax.jit
        def f(h):
            def body(i, carry):
                h, acc = carry
                gain = vf(h, sg, sh, cnt, minc, maxc)
                return (h + 1e-6, acc + gain[0, 0])
            return lax.fori_loop(0, k, body, (h, jnp.float32(0.0)))
        return f

    tt.measure("split_eval", mk_split, hist_b)

    # ---- rank_grad: lambdarank gradients at MSLR-like queries ---------
    RD = int(os.environ.get("DT255_RANK_DOCS", 2_270_000))
    if RD > 0:
        from lightgbm_tpu.ops.objectives import LambdarankNDCG
        from lightgbm_tpu.ops.pallas_hist import pallas_available
        qsizes = []
        tot = 0
        while tot < RD:                 # MSLR concentrates at 40..200
            c = int(rng.randint(40, 201))
            qsizes.append(c)
            tot += c
        qb = np.concatenate([[0], np.cumsum(qsizes)]).astype(np.int64)
        nd = int(qb[-1])
        rcfg = Config()
        rcfg.objective = "lambdarank"
        rcfg.label_gain = [float((1 << i) - 1) for i in range(31)]
        rcfg.tpu_rank_fused = \
            "on" if (pallas_available() or INTERPRET) else "off"
        rlab = rng.randint(0, 5, nd).astype(np.float64)
        obj = LambdarankNDCG(rcfg)
        obj.init(type("M", (), {"query_boundaries": qb, "label": rlab,
                                "weight": None})(), nd)
        tt.out["rank_docs"] = nd
        tt.out["rank_queries"] = len(qsizes)
        tt.out["rank_fused"] = bool(obj.rank_fused_active)
        sc0 = jnp.asarray(rng.randn(nd).astype(np.float32))

        def mk_rank(k):
            @jax.jit
            def f(s):
                def body(i, s):
                    g, h = obj.get_gradients(s[None, :])
                    # data dependence so the loop body survives DCE
                    return s + g[0] * 1e-9 + h[0] * 1e-12
                return lax.fori_loop(0, k, body, s)
            return f

        tt.measure("rank_grad", mk_rank, sc0, rows=nd)

    print(json.dumps(tt.out), flush=True)


if __name__ == "__main__":
    main()
