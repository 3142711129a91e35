#!/usr/bin/env python
"""Round-4 kernel variant sweep: chunk size C for move/hist, chunk-batched
hist (multiple chunks per grid step), no-hist move.

python tools/variants_r4.py [n] [max_bin]
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu.obs import trace as obs_trace

N = int(sys.argv[1]) if len(sys.argv) > 1 else 10_500_000
MB = int(sys.argv[2]) if len(sys.argv) > 2 else 63
F = 28
S = 64     # slots for the bench (small store)


def timeit(fn, reps=4):
    out = fn()
    obs_trace.force_fence(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
        obs_trace.force_fence(out)
    dt = (time.perf_counter() - t0) / reps
    leaf = jax.tree_util.tree_leaves(out)[0]
    chk = float(jnp.sum(leaf[:2].astype(jnp.float32)))
    return dt, chk


def main():
    from lightgbm_tpu.ops.aligned import move_pass, pack_records, \
        pack_route2, slot_hist_pass

    rng = np.random.RandomState(3)
    bins = rng.randint(0, MB, (N, F)).astype(np.uint8)
    label = rng.randint(0, 2, N).astype(np.float32)
    group = 8 if MB <= 64 else 4
    B = MB + 1 if MB % 2 else MB

    for C in (512, 1024, 2048):
        rec_np, wcnt, W, cnts, _bits = pack_records(bins, label, None, C)
        nc_data = rec_np.shape[0]
        NC = nc_data + 4
        full = np.zeros((NC, W, C), np.int32)
        full[:nc_data] = rec_np
        rec = jnp.asarray(full)
        del full
        meta_cnt = np.zeros(NC, np.int32)
        meta_cnt[:nc_data] = cnts
        iota = np.arange(NC, dtype=np.int32)

        # --- move all-split, no hist
        r1 = np.full(NC, (MB // 2) | (1 << 13), np.int32)
        meta = meta_cnt.copy()
        meta[0] |= 1 << 20
        meta[nc_data - 1] |= 1 << 21
        r2 = np.full(NC, pack_route2(0, B), np.int32)
        basel = np.zeros(NC, np.int32)
        baser = np.full(NC, nc_data // 2, np.int32)
        wsel = np.zeros(NC, np.int32)
        nohist = np.full(NC, S + 1, np.int32)
        withhist = np.zeros(NC, np.int32)
        a_nh = [jnp.asarray(x) for x in
                (r1, r2, basel, baser, meta, wsel, nohist)]
        a_wh = [jnp.asarray(x) for x in
                (r1, r2, basel, baser, meta, wsel, withhist)]
        try:
            cb0 = jnp.zeros((S + 2) * 8, jnp.int32)
            # both buffers donated and handed back, so that no copy of an
            # operand is timed with the pass; every call reads the first
            step = jax.jit(
                lambda a, b, *route: move_pass(a, b, 0, *route, cb0, C, W,
                                               wcnt, S + 1, F, B, group),
                donate_argnums=(0, 1))
            bufs = [rec, jnp.zeros_like(rec)]

            def timed(route):
                def call():
                    a, b, hist = step(*bufs, *route)
                    bufs[:] = [a, b]
                    return hist
                return timeit(call)

            t_nh, c1 = timed(a_nh)
            t_wh, c2 = timed(a_wh)
            # all-copy
            r1c = np.full(NC, (1 << 16), np.int32)
            metac = (meta_cnt | (1 << 20) | (1 << 21)).astype(np.int32)
            a_cp = [jnp.asarray(x) for x in
                    (r1c, r2, iota, iota, metac, wsel, nohist)]
            t_cp, c3 = timed(a_cp)
            print(f"C={C}: move_split_nohist={t_nh*1e3:.1f}ms "
                  f"({t_nh/N*1e9:.2f}ns) move_split_hist={t_wh*1e3:.1f}ms "
                  f"({t_wh/N*1e9:.2f}ns) copy={t_cp*1e3:.1f}ms "
                  f"({t_cp/N*1e9:.2f}ns) chk={c1:.0f}/{c2:.0f}/{c3:.0f}",
                  flush=True)
        except Exception as e:
            print(f"C={C}: move FAILED: {type(e).__name__} "
                  f"{str(e)[:160]}", flush=True)

        # --- hist full pass
        slots = np.zeros(NC, np.int32)
        slots[nc_data:] = S + 1
        try:
            t_h, c4 = timeit(lambda: slot_hist_pass(
                rec, jnp.asarray(slots), jnp.asarray(meta_cnt), S + 1, F,
                B, C, group, wcnt))
            print(f"C={C}: hist={t_h*1e3:.1f}ms ({t_h/N*1e9:.2f}ns) "
                  f"chk={c4:.0f}", flush=True)
        except Exception as e:
            print(f"C={C}: hist FAILED: {type(e).__name__} "
                  f"{str(e)[:160]}", flush=True)
        del rec
    print("done", flush=True)


if __name__ == "__main__":
    main()
