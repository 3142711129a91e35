"""Row sampling as SELECTS over arrays of any shape and order. GOSS
(goss.hpp:96-134) takes two, plain bagging (gbdt.cpp:209-275,
`bag_multipliers`) the second of them alone with multiplier 1. GOSS's
is the one definition behind the fused leaf-wise path, the sweep
trainer's vmapped fleet select and the aligned engine's device program
over its permuted record matrix.

A row is kept at multiplier 1 when its a = |g x h| is at least the
`top_k`-th largest a (ties at the threshold are all kept); of the rest,
the `other_k` rows with the smallest KEY are kept at `multiply` =
(n - top_k) / other_k; every other row gets 0. The key is an integer
function of (row id, the iteration's seed) alone, so it is computed
wherever the row id is, in whatever order the rows lie, and the plain
reference (`benchmark/reference_goss.py`) chooses the same rows.

Both order statistics are found by counting, `DIGIT_BITS` bits of the
answer a pass, most significant first: 2 x `PASSES` fused
compare-and-count reductions that each read one 4-byte array once. No
sort, no payload, no scatter, and nothing leaves the device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

DIGIT_BITS = 4
PASSES = 32 // DIGIT_BITS       # counting passes per order statistic


def goss_key(rid, seed):
    """uint32 sampling key of row `rid` under `seed`. For every seed a
    bijection of the 32-bit row ids (odd multiply, add, murmur3's 32-bit
    finaliser), so no two rows tie and "the other_k smallest" names
    exactly other_k rows. `benchmark/reference_goss.py` has its numpy
    twin; the two must stay bit-identical."""
    x = rid.astype(jnp.uint32) * jnp.uint32(0x9E3779B1) \
        + jnp.asarray(seed).astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _kth(x, live, k, largest: bool):
    """The k-th largest (or smallest) of the uint32 array `x` over the
    rows where `live`, without sorting: the greatest t with
    #(x >= t) >= k (or #(x < t) < k) is built DIGIT_BITS bits a pass
    from one count per candidate digit, which is monotone in the digit.
    Fewer than k live rows give 0 (largest) or 2^32 - 1 (smallest):
    every live row then passes the caller's comparison."""
    digits = jnp.arange(1, 1 << DIGIT_BITS, dtype=jnp.uint32).reshape(
        (-1,) + (1,) * x.ndim)
    over = tuple(range(1, x.ndim + 1))
    t = jnp.uint32(0)
    for shift in range(32 - DIGIT_BITS, -1, -DIGIT_BITS):
        cand = t | (digits << shift)
        hit = (x[None] >= cand) if largest else (x[None] < cand)
        cnt = jnp.sum(hit & live[None], axis=over, dtype=jnp.int32)
        ok = (cnt >= k) if largest else (cnt < k)
        t = t | (jnp.sum(ok, dtype=jnp.uint32) << shift)
    return t


def goss_multipliers(a, rid, live, seed, top_k: int, other_k: int,
                     multiply: float):
    """(multiplier f32 like `a`, (kept_top i32, kept_other i32,
    threshold f32)) for non-negative f32 `a`, int32 row ids `rid` and a
    bool `live` (False: a slot that holds no row) of one shape."""
    bits = lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
    thr = _kth(bits, live, top_k, largest=True)
    # for non-negative floats the bit patterns order as the values do
    big = live & (bits >= thr)
    rest = live & ~big
    key = goss_key(rid, seed)
    sampled = rest & (key <= _kth(key, rest, other_k, largest=False))
    mult = jnp.where(big, jnp.float32(1.0),
                     jnp.where(sampled, jnp.float32(multiply),
                               jnp.float32(0.0)))
    stats = (jnp.sum(big, dtype=jnp.int32),
             jnp.sum(sampled, dtype=jnp.int32),
             lax.bitcast_convert_type(thr, jnp.float32))
    return mult, stats


def bag_multipliers(rid, live, seed, cnt: int):
    """Plain bagging's uniform draw as the same kind of select: (f32 1.0
    for the `cnt` live rows with the smallest `goss_key(rid, seed)`, 0.0
    for every other cell; kept i32). One order statistic, `PASSES`
    counting passes over the key array; keys are distinct, so exactly
    `cnt` rows are kept wherever at least `cnt` are live."""
    key = goss_key(rid, seed)
    kept = live & (key <= _kth(key, live, cnt, largest=False))
    return kept.astype(jnp.float32), jnp.sum(kept, dtype=jnp.int32)


def bag_rows(n: int, seed: int, cnt: int) -> np.ndarray:
    """`bag_multipliers` in ROW order, for the host: the sorted int32 ids
    of the `cnt` rows of `n` with the smallest key under `seed`. What the
    fused leaf-wise loop, the host learner and the engine's row-order
    fallback train on, so every path draws the bag the engine drew."""
    cnt = min(int(cnt), n)
    if cnt <= 0:
        return np.zeros(0, np.int32)
    key = np.asarray(_row_keys(n, jnp.uint32(seed)))
    return np.flatnonzero(
        key <= np.partition(key, cnt - 1)[cnt - 1]).astype(np.int32)


@functools.partial(jax.jit, static_argnums=0)
def _row_keys(n: int, seed):
    return goss_key(jnp.arange(n, dtype=jnp.int32), seed)
