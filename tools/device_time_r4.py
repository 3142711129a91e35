#!/usr/bin/env python
"""Honest DEVICE-time kernel measurement: chain k executions inside one
jitted program (fori_loop), time via device_get deltas between k=1 and
k=K. Removes host dispatch overhead from the numbers.

Thin CLI over ``lightgbm_tpu.obs.devicetime.TermTimer`` (the shared
chained-k protocol); this file only builds the move/hist closures for a
sweep over chunk sizes. Term names come from the canonical vocabulary
in ``lightgbm_tpu.obs.terms.TERMS`` — the same names the in-run
profiler writes to ledger ``terms_ms``:

  route   move_pass, every block splitting, NO hist slots
  flush   hist-accumulating move_pass minus route (marginal fused
          accumulate + slot flush; derived, minuend hist_move)
  copy    move_pass with every block copied whole (no split, no hist)
  hist    slot_hist_pass over the full record store

Prints the human per-C lines on stderr and ONE JSON line per C on
stdout: {"n": ..., "max_bin": ..., "chunk": C, "terms_ms": {...}}.

python tools/device_time_r4.py [n] [max_bin] [C ...]
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# what this tool measures, in canonical obs/terms.py vocabulary
# (asserted against TERMS by tests/test_profiler.py)
TERMS_MEASURED = ("route", "flush", "copy", "hist")

N = int(sys.argv[1]) if len(sys.argv) > 1 else 10_500_000
MB = int(sys.argv[2]) if len(sys.argv) > 2 else 63
CS = [int(c) for c in sys.argv[3:]] or [512, 1024, 2048]
F = 28
S = 64
K = 8


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def main():
    from lightgbm_tpu.obs.devicetime import TermTimer
    from lightgbm_tpu.obs.terms import TERMS
    from lightgbm_tpu.ops.aligned import move_pass, pack_records, \
        pack_route2, slot_hist_pass

    rng = np.random.RandomState(3)
    bins = rng.randint(0, MB, (N, F)).astype(np.uint8)
    label = rng.randint(0, 2, N).astype(np.float32)
    group = 8 if MB <= 64 else 4
    B = MB + 1 if MB % 2 else MB

    for C in CS:
        rec_np, wcnt, W, cnts, _bits = pack_records(bins, label, None, C)
        nc_data = rec_np.shape[0]
        NC = nc_data + 4
        fullr = np.zeros((NC, W, C), np.int32)
        fullr[:nc_data] = rec_np
        rec = jnp.asarray(fullr)
        del fullr
        meta_cnt = np.zeros(NC, np.int32)
        meta_cnt[:nc_data] = cnts
        iota = np.arange(NC, dtype=np.int32)
        r2 = np.full(NC, pack_route2(0, B), np.int32)
        wsel = np.zeros(NC, np.int32)
        nohist = np.full(NC, S + 1, np.int32)

        # split-everything routing: block = whole data at mid-bin
        r1 = np.full(NC, (MB // 2) | (1 << 13), np.int32)
        meta = meta_cnt.copy()
        meta[0] |= 1 << 20
        meta[nc_data - 1] |= 1 << 21
        basel = np.zeros(NC, np.int32)
        baser = np.full(NC, nc_data // 2, np.int32)

        tt = TermTimer({"n": N, "max_bin": MB, "chunk": C},
                       chain=K,
                       log=lambda m, C=C: log(f"C={C} {m}"),
                       catalog=TERMS)

        def mk_move(hsl, r1v, metav, blv, brv):
            cb0 = jnp.zeros((S + 2) * 8, jnp.int32)
            a = tuple(jnp.asarray(x) for x in
                      (r1v, r2, blv, brv, metav, wsel, hsl))

            def mk(k):
                @jax.jit
                def f(r):
                    def body(i, bufs):  # read buffer i % 2, write the other
                        return move_pass(*bufs, i % 2, *a, cb0, C, W,
                                         wcnt, S + 1, F, B, group)[:2]
                    return lax.fori_loop(0, k, body,
                                         (r, jnp.zeros_like(r)))
                return f
            return mk

        tt.measure("route", mk_move(nohist, r1, meta, basel, baser),
                   rec, rows=N)
        tt.measure("hist_move",
                   mk_move(np.zeros(NC, np.int32), r1, meta, basel,
                           baser), rec, rows=N)
        tt.derive("flush", "hist_move", "route")
        r1c = np.full(NC, (1 << 16), np.int32)
        metac = (meta_cnt | (1 << 20) | (1 << 21)).astype(np.int32)
        tt.measure("copy", mk_move(nohist, r1c, metac, iota, iota),
                   rec, rows=N)

        # hist full pass (chained via a tiny record perturbation so the
        # loop body cannot be hoisted)
        slots = np.zeros(NC, np.int32)
        slots[nc_data:] = S + 1
        sl_j = jnp.asarray(slots)
        mc_j = jnp.asarray(meta_cnt)

        def mk_hist(k):
            @jax.jit
            def f(r):
                def body(i, carry):
                    r, acc = carry
                    h = slot_hist_pass(r, sl_j, mc_j, S + 1, F, B, C,
                                       group, wcnt)
                    r = r.at[0, 0, 0].add(1)
                    return (r, acc + h[0, 0, 0, 0])
                return lax.fori_loop(0, k, body, (r, jnp.float32(0.0)))
            return f

        tt.measure("hist", mk_hist, rec, rows=N)
        print(json.dumps(tt.out), flush=True)
        del rec
    log("done")


if __name__ == "__main__":
    main()
