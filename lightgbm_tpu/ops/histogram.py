"""Histogram construction — the hottest loop of the framework.

Reference semantics: `DenseBin::ConstructHistogram` (4-way unrolled CPU
scatter-add, `src/io/dense_bin.hpp:71-137`) and the OpenCL kernels with
local-memory float atomics (`src/treelearner/ocl/histogram256.cl:100-125`).

TPU has no fast scatter-add, so the formulation is flipped into an MXU
contraction: for a chunk of rows, build the exact {0,1} one-hot of
(feature, bin) and contract it against the per-row payload
``[grad, hess, 1]``.  ``hist[f, b, w] = Σ_rows onehot[row, f, b] * w[row, w]``
— a batched matmul XLA tiles onto the systolic array.  bf16 one-hots are
exact; payload precision is recovered with a hi/lo split (two bf16 matmuls
≈ f32 accuracy), the TPU analogue of the reference's `gpu_use_dp` choice
(`gpu_tree_learner.cpp:306`).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

# payload columns: gradient, hessian, count
NUM_HIST_STATS = 3


def _chunk_histogram(bins_chunk: jax.Array, payload: jax.Array,
                     max_bin: int, precision: str) -> jax.Array:
    """Histogram of one row-chunk.

    bins_chunk: int32 [K, F] (out-of-range bin == masked row)
    payload:    f32 [K, 3]  (grad, hess, 1/0-mask)
    returns     f32 [F, max_bin, 3]
    """
    iota = lax.broadcasted_iota(jnp.int32, (1, 1, max_bin), 2)
    onehot = (bins_chunk[:, :, None] == iota)  # [K, F, B] bool
    if precision == "f64":
        # Exact accumulation: f64 sums of f32 payloads are order-independent
        # at any realistic leaf size (24-bit mantissa + log2(n) << 53 bits),
        # so psum-of-shard-partials == serial total bit-for-bit. This is the
        # topology-invariance anchor of the distributed runtime (the
        # reference's hist_t is double for the same reason).
        with jax.enable_x64(True):
            oh = onehot.astype(jnp.float64)
            return jnp.einsum("kfb,kw->fbw", oh,
                              payload.astype(jnp.float64),
                              precision=lax.Precision.HIGHEST)
    if precision == "f32":
        oh = onehot.astype(jnp.float32)
        return jnp.einsum("kfb,kw->fbw", oh, payload,
                          precision=lax.Precision.HIGHEST)
    oh = onehot.astype(jnp.bfloat16)
    if precision == "bf16":
        return jnp.einsum("kfb,kw->fbw", oh, payload.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    # bf16x2 (default): split payload into hi + lo bf16 parts; the one-hot is
    # exact in bf16, so two MXU passes recover ~f32 accuracy. The parts ride
    # as extra payload columns of ONE matmul and are summed in f32 afterwards
    # — two separate einsums would be re-fused by XLA's algebraic simplifier
    # into a single bf16 contraction, silently dropping the low part.
    p_hi = payload.astype(jnp.bfloat16)
    p_lo = (payload - p_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    both = jnp.concatenate([p_hi, p_lo], axis=1)            # [K, 2W]
    res = jnp.einsum("kfb,kw->fbw", oh, both,
                     preferred_element_type=jnp.float32)     # [F, B, 2W]
    w = payload.shape[1]
    return res[..., :w] + res[..., w:]


@functools.partial(jax.jit, static_argnames=("max_bin", "chunk", "precision"))
def histogram_from_gathered(bins_rows: jax.Array, grad: jax.Array,
                            hess: jax.Array, valid: jax.Array,
                            max_bin: int, chunk: int = 1 << 13,
                            precision: str = "bf16x2") -> jax.Array:
    """Build hist[F, max_bin, 3] from already-gathered (padded) leaf rows.

    bins_rows: uint8/int32 [P, F] — rows of the leaf, padded
    grad/hess: f32 [P]
    valid:     bool [P] — False for padding
    """
    return histogram_from_gathered_gh(
        bins_rows, jnp.stack([grad, hess], axis=1), valid, max_bin, chunk,
        precision)


@functools.partial(jax.jit, static_argnames=("max_bin", "chunk", "precision"))
def histogram_from_gathered_gh(bins_rows: jax.Array, gh: jax.Array,
                               valid: jax.Array, max_bin: int,
                               chunk: int = 1 << 13,
                               precision: str = "bf16x2") -> jax.Array:
    """Like `histogram_from_gathered` but with a pre-packed [P, 2]
    grad/hess payload — the caller gathers ONE wide array per leaf instead
    of two (random row gathers are the dominant cost on TPU)."""
    if jnp.issubdtype(gh.dtype, jnp.integer):
        # quantized payload (quantize_gh): the int8/int16 rows were
        # gathered at quarter/half the f32 bytes; accumulation runs in
        # f32 on the exact integer values (int16 |q| <= 32767 is exact
        # under the bf16 hi/lo split, int8 in a single bf16 pass), and
        # the caller rescales the finished histogram by the pack scale
        gh = gh.astype(jnp.float32)
    if precision == "pallas":
        from .pallas_hist import pallas_histogram
        return pallas_histogram(bins_rows, gh, valid, max_bin)
    p, f = bins_rows.shape
    bins_i = bins_rows.astype(jnp.int32)
    vmask = valid[:, None]
    payload = jnp.concatenate(
        [jnp.where(vmask, gh, 0.0),
         valid[:, None].astype(jnp.float32)], axis=1)  # [P, 3]
    if p <= chunk:
        return _chunk_histogram(bins_i, payload, max_bin, precision)
    # pad rows to a multiple of chunk, then accumulate chunk-wise so the
    # one-hot is only ever materialized chunk-wise
    n_chunks = (p + chunk - 1) // chunk
    pad = n_chunks * chunk - p
    if pad:
        bins_i = jnp.pad(bins_i, ((0, pad), (0, 0)), constant_values=-1)
        payload = jnp.pad(payload, ((0, pad), (0, 0)))
    bins_c = bins_i.reshape(n_chunks, chunk, f)
    pay_c = payload.reshape(n_chunks, chunk, NUM_HIST_STATS)

    def body(acc, xs):
        b, w = xs
        return acc + _chunk_histogram(b, w, max_bin, precision), None

    if precision == "f64":
        # the scan carry must be f64 too — a f32 carry would round every
        # chunk boundary and break the order-independence argument above
        with jax.enable_x64(True):
            init = jnp.zeros((f, max_bin, NUM_HIST_STATS), dtype=jnp.float64)
            acc, _ = lax.scan(body, init, (bins_c, pay_c))
        return acc
    init = jnp.zeros((f, max_bin, NUM_HIST_STATS), dtype=jnp.float32)
    acc, _ = lax.scan(body, init, (bins_c, pay_c))
    return acc


@functools.partial(jax.jit, static_argnames=("padded", "max_bin", "chunk",
                                             "precision"))
def leaf_histogram(bins: jax.Array, indices: jax.Array, begin: jax.Array,
                   count: jax.Array, grad: jax.Array, hess: jax.Array,
                   padded: int, max_bin: int, chunk: int = 1 << 13,
                   precision: str = "bf16x2") -> jax.Array:
    """Histogram of one leaf's rows out of the global partition.

    Mirrors the reference's ordered-gradient gather + per-group construct
    (`Dataset::ConstructHistograms`, `dataset.cpp:758-926`): gather the
    leaf's row ids from the partition ``indices[begin:begin+padded]``, then
    gather grad/hess/bins by row id and contract.

    bins:    uint8 [N_pad, F] full binned matrix in HBM
    indices: int32 [N_pad] partition array (leaf rows contiguous)
    begin:   scalar int32 — leaf start offset in `indices`
    count:   scalar int32 — actual number of rows in the leaf (≤ padded)
    padded:  static python int — padded slice length
    """
    idx = lax.dynamic_slice(indices, (begin,), (padded,))
    pos = jnp.arange(padded, dtype=jnp.int32)
    valid = pos < count
    safe_idx = jnp.where(valid, idx, 0)
    rows = bins[safe_idx]                      # [P, F]
    g = grad[safe_idx]
    h = hess[safe_idx]
    return histogram_from_gathered(rows, g, h, valid, max_bin, chunk,
                                   precision)


@functools.partial(jax.jit, static_argnames=("bits",))
def quantize_gh(gh: jax.Array, bits: int, key: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Stochastic-rounded per-column quantization of the [N, 2]
    grad/hess payload (the TPU analogue of the reference's quantized
    gradient work, `gradient_discretizer.cpp`): ``q = clip(floor(gh /
    scale + u), -qmax, qmax)`` with ``u ~ U[0, 1)`` per element, so
    ``E[q * scale] == gh`` — the rounding noise is unbiased and a fresh
    key per tree keeps it independent across boosting rounds.

    Returns ``(q int8/int16 [N, 2], scale f32 [2])``. Scales are the
    per-column absmax over qmax (floored so all-zero hessians stay
    finite); the caller multiplies finished histograms and leaf sums by
    ``scale`` to return to f32 gradient units.
    """
    qmax = 127.0 if bits == 8 else 32767.0
    absmax = jnp.max(jnp.abs(gh), axis=0)
    scale = jnp.maximum(absmax / qmax, 1e-30).astype(jnp.float32)
    u = jax.random.uniform(key, gh.shape, dtype=jnp.float32)
    q = jnp.clip(jnp.floor(gh / scale + u), -qmax, qmax)
    return q.astype(jnp.int8 if bits == 8 else jnp.int16), scale


def subtract_histogram(parent: jax.Array, child: jax.Array) -> jax.Array:
    """larger-child = parent − smaller-child (reference
    `FeatureHistogram::Subtract`, `feature_histogram.hpp:75`)."""
    return parent - child


def histogram_from_words(words, g: jax.Array, h: jax.Array,
                         valid: jax.Array, num_features: int, max_bin: int,
                         chunk: int = 1 << 16,
                         precision: str = "bf16x2") -> jax.Array:
    """Histogram over PACKED bin words (level builder record layout:
    4 uint8 bins per int32, word w bits 8j..8j+7 = feature 4w+j).

    words: list of int32 [P] (ceil(F/4) arrays); g/h: f32 [P];
    valid: bool [P]. Returns f32 [F, max_bin, 3].

    On TPU this runs as a Pallas kernel that unpacks the words in VMEM
    (contiguous lane-oriented reads — the replacement for the leaf-wise
    path's random row gather); elsewhere the words are unpacked in XLA and
    the einsum path is reused.
    """
    if precision == "pallas":
        from .pallas_hist import pallas_histogram_words
        return pallas_histogram_words(words, g, h, valid, num_features,
                                      max_bin)
    cols = []
    for f in range(num_features):
        w = words[f >> 2]
        cols.append((w >> ((f & 3) * 8)) & 255)
    bins = jnp.stack(cols, axis=1)
    gh = jnp.stack([g, h], axis=1)
    return histogram_from_gathered_gh(bins, gh, valid, max_bin, chunk,
                                      precision)
