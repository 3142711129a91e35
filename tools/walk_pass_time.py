#!/usr/bin/env python
"""What a tree costs `walk_pass`: the kernel alone, on the chip, over
random records of the Criteo cell's shape (W=24, C=2048, 67 features at
8 bits) and random full trees of 255 leaves.

python tools/walk_pass_time.py [chunks] [trees ...]

Prints one JSON line per tree count (default 0 1 2 4 8): ms a call, and
us a chunk a tree once the call of no tree (the read of the records and
the grid's bookkeeping) is taken off.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu.obs import trace as obs_trace
from lightgbm_tpu.ops import aligned

W, C, WCNT, BITS, LANE, FEATURES, LEAVES = 24, 2048, 17, 8, 17, 67, 255


def random_tree(rng, np_, lp):
    """A leaf-wise grown tree of LEAVES leaves in the walk's compact
    form: node n splits a random leaf, keeps it on the left and hangs
    leaf n + 1 on the right."""
    nodes = np.zeros((5, np_), np.int32)
    leaves = np.zeros((2, lp), np.int32)
    nodes[3], leaves[0] = -1, -1
    at = {0: (-1, 0)}
    for n in range(LEAVES - 1):
        leaf = int(rng.choice(len(at)))
        nodes[:, n] = (rng.integers(FEATURES), rng.integers(255),
                       rng.integers(2), *at[leaf])
        at[leaf], at[n + 1] = (n, 1), (n, -1)
    for leaf, (parent, side) in at.items():
        leaves[:, leaf] = parent, side
    return nodes, leaves


def main(argv):
    nc = int(argv[1]) if len(argv) > 1 else 4096
    counts = [int(a) for a in argv[2:]] or [0, 1, 2, 4, 8]
    rng = np.random.default_rng(0)
    np_, lp, w8, fp = aligned.walk_dims(LEAVES, WCNT, BITS)
    trees = [random_tree(rng, np_, lp) for _ in range(aligned.WALK_TREES)]
    meta = [jnp.full(FEATURES, v, jnp.int32) for v in (255, 0, 0)]
    tabs = jax.jit(jax.vmap(lambda n, l, k: aligned.walk_expand(
        n, l, k, *meta, w8=w8, bits=BITS, fp=fp)))(
            jnp.asarray(np.stack([t[0] for t in trees])),
            jnp.asarray(np.stack([t[1] for t in trees])),
            jnp.full(len(trees), LEAVES - 1, jnp.int32))
    vals = jnp.asarray(rng.standard_normal(
        (len(trees), lp, 1)).astype(np.float32))
    rec = jax.random.bits(jax.random.PRNGKey(0), (nc, W, C),
                          jnp.uint32).astype(jnp.int32)
    cnts = jnp.full(nc, C, jnp.int32)
    run = jax.jit(lambda r, k: aligned.walk_pass(
        r, cnts, k, *tabs, vals, chunk=C, wcnt=WCNT, bits=BITS, lane=LANE),
        donate_argnums=(0,))
    rec = obs_trace.force_fence(run(rec, jnp.int32(1)))
    base = None
    for k in counts:
        best = float("inf")
        for _ in range(3):
            t = time.perf_counter()
            rec = obs_trace.force_fence(run(rec, jnp.int32(k)))
            best = min(best, time.perf_counter() - t)
        base = best if k == 0 else base
        line = {"chunks": nc, "trees": k, "ms": 1e3 * best,
                "device": jax.devices()[0].device_kind}
        if k and base is not None:
            line["us_per_chunk_tree"] = 1e6 * (best - base) / nc / k
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main(sys.argv)
