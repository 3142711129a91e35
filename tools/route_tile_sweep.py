#!/usr/bin/env python
"""Which ROUTE_TILE? One `move_pass` call with every chunk on the split
path (the root round's shape), timed on the chip for each candidate tile.

python tools/route_tile_sweep.py [shape ...] [tile ...] [unroll=N ...]

Shapes: criteo255 / criteo63 (the benchmark cells' records, W=24,
C=2048, fused histogram of the smaller child), istella255 (the ranking
cell's EXT record, W=64 with 59 lanes used, C=512, 220 features) and
higgs (compact W=16, C=1024, no histogram), and two with a bag lane, the
24th of 24 (U = 24, where `criteo255` routes 23): criteo255bag, the
bagged cells' record through the same split with its in-bag histogram,
and criteo255park, the partition that parks the rows a bag leaves out
(`park_pass`: `move_pass` routing by the bag, 30% of the rows in it, no
histogram compiled in), so the partition round's us a chunk is read
alone. A tile equal to the chunk is the untiled kernel; `unroll=N` sets
ROUTE_UNROLL, the tiles that share one trip of the route's loop (default:
the module's). Prints one JSON line per (shape, tile, unroll): ms a call
and us a live chunk, and the staging form the kernel ran (`route_stage`).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from lightgbm_tpu.obs import trace as obs_trace
from lightgbm_tpu.ops import aligned

NC = 4096          # 0.8 GB of records at W=24, C=2048; 0.5 GB at W=64, C=512
LIVE = NC - 64
SHAPES = {   # W, C, wcnt, w_used, features, b_pad, bits, spill, hist, gh_off
    "criteo255": (24, 2048, 17, 23, 67, 256, 8, True, True, 2),
    "criteo63": (24, 2048, 14, 20, 67, 64, 6, False, True, 2),
    "istella255": (64, 512, 55, 59, 220, 256, 8, True, True, 1),
    "higgs": (16, 1024, 7, 9, 28, 256, 8, False, False, 2),
    "criteo255bag": (24, 2048, 17, 24, 67, 256, 8, True, True, 2),
    "criteo255park": (24, 2048, 17, 24, 67, 256, 8, False, False, 2),
}
BAG_LANE = 23      # of the two shapes that have one
IN_BAG = 0.3
K = 256


def one(shape, tile, unroll, reps=3):
    W, C, wcnt, w_used, F, b_pad, bits, spill, hist, gh_off = SHAPES[shape]
    aligned.ROUTE_TILE = tile
    aligned.ROUTE_UNROLL = unroll
    jax.clear_caches()
    rec = jax.random.bits(jax.random.PRNGKey(tile), (NC, W, C),
                          jnp.uint32).astype(jnp.int32)
    bag_lane = BAG_LANE if w_used > BAG_LANE else -1
    if bag_lane >= 0:      # a 0/1 lane, IN_BAG of the rows in the bag
        draw = jax.random.uniform(jax.random.PRNGKey(7), (NC, C)) < IN_BAG
        rec = rec.at[:, bag_lane, :].set(jax.lax.bitcast_convert_type(
            draw.astype(jnp.float32), jnp.int32))
    iota = jnp.arange(NC, dtype=jnp.int32)
    # one block over the first LIVE chunks, split at the middle bin of
    # feature 0: half the rows go left, to chunk 0 on, half right, to
    # the chunk past the middle on; the grid's tail is dead chunks, the
    # room a split needs
    thr = (1 << bits) // 2 - 1
    meta = (jnp.where(iota < LIVE, C, 0) | ((iota == 0) << 20)
            | ((iota == LIVE - 1) << 21))
    zeros = jnp.zeros(NC, jnp.int32)
    args = (jnp.full(NC, thr, jnp.int32),
            jnp.full(NC, aligned.pack_route2(0, 1 << bits), jnp.int32),
            zeros, zeros + NC // 2, meta.astype(jnp.int32), zeros,
            zeros if hist else zeros + K, jnp.zeros((K + 1) * 8, jnp.int32))

    # both buffers are donated and handed back: an operand that is not
    # donated is copied before the aliased kernel may write it, and the
    # copy would be timed with the pass. Every call reads the first
    # buffer, which no pass writes: the same work each time
    if shape.endswith("park"):
        # the partition as the build program calls it: every live row
        # by its bag, the count of the in-bag ones the caller's
        cnts = jnp.where(iota < LIVE, C, 0).astype(jnp.int32)
        kept = jnp.sum(jnp.where(iota[:, None] < LIVE, draw, False),
                       dtype=jnp.int32)

        def run(a, b):
            a, b, new, _ = aligned.park_pass(a, b, 0, cnts, kept, C, W,
                                             wcnt, bag_lane, bits=bits,
                                             w_used=w_used)
            return a, b, new
    else:
        def run(a, b):
            return aligned.move_pass(
                a, b, 0, *args, C, W, wcnt, K, F, b_pad,
                4 if b_pad > 64 else 8, bag_lane=bag_lane, bits=bits,
                w_used=w_used, gh_off=gh_off, subbin=True, spill=spill)
    step = jax.jit(run, donate_argnums=(0, 1))
    bufs = [rec, jnp.zeros_like(rec)]

    def call():
        a, b, out = step(*bufs)
        bufs[:] = [a, b]
        obs_trace.force_fence(out)

    t0 = time.perf_counter()
    call()
    first = time.perf_counter() - t0
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        call()
        walls.append(time.perf_counter() - t0)
    best = min(walls)
    print(json.dumps({
        "shape": shape, "C": C, "tile": aligned.route_tile(C),
        "route_selectors": aligned.ROUTE_SELECTORS,
        "route_stage": aligned.ROUTE_STAGE,
        "route_unroll": aligned.route_unroll(C),
        "hist": hist,
        "ms_per_call": round(best * 1e3, 2),
        "us_per_chunk": round(best * 1e6 / LIVE, 2),
        "first_call_s": round(first, 1),
        "device": jax.devices()[0].device_kind}), flush=True)


if __name__ == "__main__":
    shapes = [a for a in sys.argv[1:] if a in SHAPES] or list(SHAPES)
    tiles = [int(a) for a in sys.argv[1:] if a.isdigit()] \
        or [256, 512, 1024, 2048]
    unrolls = [int(a[7:]) for a in sys.argv[1:] if a.startswith("unroll=")] \
        or [aligned.ROUTE_UNROLL]
    if jax.default_backend() != "tpu":
        sys.exit("route_tile_sweep: no TPU; a CPU time is not a "
                 "device metric")
    for unroll in unrolls:
        for shape in shapes:
            for tile in tiles:
                if tile <= SHAPES[shape][1]:
                    one(shape, tile, unroll)
