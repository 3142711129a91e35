"""Pallas TPU histogram kernel — the `ocl/histogram256.cl` analogue.

The reference GPU learner builds per-leaf gradient/hessian histograms with
hand-written OpenCL kernels using workgroup-local memory and float atomics
(`src/treelearner/ocl/histogram256.cl:100-125,350`). TPU has no fast
scatter-add, so the kernel keeps the histogram accumulator **resident in
VMEM across the whole row stream** and converts the scatter into per-feature
one-hot contractions on the MXU:

    for each row-chunk (grid dim, pipelined HBM->VMEM by pallas):
        for each feature f (static unroll):
            onehot[c, b] = (bins[c, f] == b)          # VPU compare vs iota
            hist[f] += onehot^T @ payload[c, {g,h,1}]  # MXU [B,C]x[C,W]

Unlike the XLA einsum formulation (`ops/histogram.py`), the one-hot tile
never leaves VMEM and the accumulator is written to HBM exactly once, at the
last grid step. Numerics: the one-hot is exact in bf16; payload rides as
hi/lo bf16 pairs (two extra columns) so the f32-accumulated result matches
the reference's single-precision GPU histograms (`gpu_use_dp=0`) or better.

Used via `Config.tpu_use_pallas`; the einsum path stays the fallback (and
the only path on CPU test meshes, where pallas TPU kernels can't lower).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NUM_STATS = 3  # grad, hess, count


def _hist_kernel(bins_ref, pay_ref, out_ref, *, num_features: int,
                 max_bin: int, payload_width: int):
    """One grid step: accumulate a row-chunk into the VMEM-resident
    histogram. bins_ref [C, F] uint8; pay_ref [C, W]; out_ref [F, B, W].

    Invalid rows need no bin masking: their payload columns (g, h, count)
    are all zero, so whatever bin they land in receives zeros.
    """
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bins = bins_ref[...].astype(jnp.int32)
    pay_f32 = pay_ref[...]                      # [C, 3] f32 (g, h, cnt)
    # hi/lo bf16 split INSIDE the kernel: done outside, XLA's algebraic
    # simplifier cancels the f32->bf16->f32 round-trip and silently drops
    # the low parts; Mosaic keeps the conversions explicit
    p_hi = pay_f32.astype(jnp.bfloat16)
    p_lo = (pay_f32 - p_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    pay = jnp.concatenate([p_hi, p_lo], axis=1)  # [C, 6] bf16
    chunk = bins.shape[0]
    iota = lax.broadcasted_iota(jnp.int32, (chunk, max_bin), 1)
    for f in range(num_features):
        onehot = (bins[:, f][:, None] == iota).astype(jnp.bfloat16)
        # [B, 2W] = [C, B]^T x [C, 2W] on the MXU, f32 accumulation
        contrib = lax.dot_general(
            onehot, pay, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        out_ref[f, :, :] += contrib


def _subbin_body(bin_of, pay_ref, out_ref, num_features: int):
    """Shared sub-binned accumulation body (max_bin > 128): bin =
    hi*16 + lo. Instead of a B-wide one-hot (256 VPU compares per
    row/feature), the payload rides the 16-wide HI one-hot
    (Z = pay6 x oh_hi -> [96, C], zero-padded to a full [128, C] tile)
    and ONE MXU contraction against the 16-wide LO one-hot lands the
    whole [16, 128] = [lo, pay*16 + hi] sub-bin tile — 32 compares and
    exactly two f32 VMEM tiles per feature. `bin_of(f)` -> [C] i32
    lane-oriented bin values; pay_ref [3, C] (payload TRANSPOSED so the
    hi/lo split concatenates on sublanes, no in-kernel relayout)."""
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    pay_f32 = pay_ref[...]                       # [3, C]
    p_hi = pay_f32.astype(jnp.bfloat16)
    p_lo = (pay_f32 - p_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    pay6 = jnp.concatenate([p_hi, p_lo], axis=0)  # [6, C]
    C = pay_f32.shape[1]
    iota16 = lax.broadcasted_iota(jnp.int32, (16, C), 0)
    for f in range(num_features):
        bv = bin_of(f)
        oh_hi = ((bv >> 4)[None, :] == iota16).astype(jnp.bfloat16)
        oh_lo = ((bv & 15)[None, :] == iota16).astype(jnp.bfloat16)
        Z = (pay6[:, None, :] * oh_hi[None, :, :]).reshape(96, C)
        Zp = jnp.concatenate(
            [Z, jnp.zeros((32, C), jnp.bfloat16)], axis=0)
        contrib = lax.dot_general(oh_lo, Zp, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        out_ref[f] += contrib


def _subbin_rows_kernel(bins_ref, pay_ref, out_ref, *,
                        num_features: int):
    """Sub-binned kernel over gathered [C, F] uint8 rows."""
    bins = bins_ref[...].astype(jnp.int32)
    _subbin_body(lambda f: bins[:, f], pay_ref, out_ref, num_features)


def _subbin_words_kernel(*refs, num_features: int, wcnt: int):
    """Sub-binned kernel over packed lane-oriented bin words."""
    word_refs = refs[:wcnt]
    pay_ref = refs[wcnt]
    out_ref = refs[wcnt + 1]

    def bin_of(f):
        w = word_refs[f >> 2][0, :]
        return (w >> ((f & 3) * 8)) & 255

    _subbin_body(bin_of, pay_ref, out_ref, num_features)


def _subbin_finalize(out, num_features: int, max_bin: int) -> jax.Array:
    """[F, 16, 128] = [lo, pay*16 + hi] sub-bin tiles -> [F, max_bin, 3]
    (fold hi/lo payload halves, land bin = hi*16 + lo) — once per call,
    not per chunk."""
    h = out[..., :96].reshape(num_features, 16, 6, 16)
    h = h[:, :, :NUM_STATS] + h[:, :, NUM_STATS:]    # [F, lo, 3, hi]
    h = jnp.transpose(h, (0, 3, 1, 2))               # [F, hi, lo, 3]
    return h.reshape(num_features, 256, NUM_STATS)[:, :max_bin]


@functools.partial(jax.jit,
                   static_argnames=("max_bin", "chunk", "subbin",
                                    "interpret"))
def pallas_histogram(bins_rows: jax.Array, gh: jax.Array, valid: jax.Array,
                     max_bin: int, chunk: int = 1 << 11,
                     subbin: bool = True, interpret: bool = False
                     ) -> jax.Array:
    """hist[F, max_bin, 3] over contiguous (already gathered) rows.

    bins_rows: uint8 [P, F]; gh: f32 [P, 2]; valid: bool [P].
    Same contract as `histogram_from_gathered_gh`. The kernel reads the
    uint8 matrix directly (no int32 copy of the full array — at 10M rows
    that copy alone quadruples HBM traffic and can OOM); rows are processed
    in VMEM-sized chunks with the accumulator resident in VMEM.
    """
    p, f = bins_rows.shape
    if bins_rows.dtype != jnp.uint8:
        bins_rows = bins_rows.astype(jnp.uint8)
    if jnp.issubdtype(gh.dtype, jnp.integer):
        # quantized int8/int16 payload (ops/histogram.quantize_gh): the
        # bandwidth win already happened at the per-leaf gather; the
        # kernel accumulates the exact integer values in f32
        gh = gh.astype(jnp.float32)
    g = jnp.where(valid, gh[:, 0], 0.0)
    h = jnp.where(valid, gh[:, 1], 0.0)
    cnt = valid.astype(jnp.float32)
    pay = jnp.stack([g, h, cnt], axis=1)         # f32; hi/lo split in-kernel
    # bin axis padded to a 128-lane multiple: unaligned one-hot tiles force
    # awkward VMEM layouts (scoped-vmem OOM at max_bin=255)
    b_pad = max(128, ((max_bin + 127) // 128) * 128)
    n_chunks = max(1, (p + chunk - 1) // chunk)
    pad = n_chunks * chunk - p
    if pad:
        # pad rows as INVALID (zero payload) — bins may be any in-range value
        bins_rows = jnp.pad(bins_rows, ((0, pad), (0, 0)))
        pay = jnp.pad(pay, ((0, pad), (0, 0)))

    if subbin and b_pad > 128:
        kernel = functools.partial(_subbin_rows_kernel, num_features=f)
        out = pl.pallas_call(
            kernel,
            grid=(n_chunks,),
            in_specs=[
                pl.BlockSpec((chunk, f), lambda i: (i, 0)),
                pl.BlockSpec((NUM_STATS, chunk), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((f, 16, 128), lambda i: (0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((f, 16, 128), jnp.float32),
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=100 << 20),
            interpret=interpret,
        )(bins_rows, pay.T)
        return _subbin_finalize(out, f, max_bin)

    w = 2 * NUM_STATS
    kernel = functools.partial(_hist_kernel, num_features=f, max_bin=b_pad,
                               payload_width=w)
    out = pl.pallas_call(
        kernel,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((chunk, f), lambda i: (i, 0)),
            pl.BlockSpec((chunk, NUM_STATS), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((f, b_pad, w), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((f, b_pad, w), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=100 << 20),
        interpret=interpret,
    )(bins_rows, pay)
    # fold the lo-parts back into the hi sums; drop the bin padding
    return (out[..., :NUM_STATS] + out[..., NUM_STATS:])[:, :max_bin, :]


def _hist_words_kernel(*refs, num_features: int, max_bin: int,
                       wcnt: int):
    """Transposed-layout word kernel: per feature, a lane-oriented row
    slice of the packed words is unpacked with shift/mask (no column
    relayout), compared against a sublane iota into a [B, C] one-hot, and
    contracted on the MXU against the [C, 6] hi/lo payload."""
    word_refs = refs[:wcnt]
    pay_ref = refs[wcnt]
    out_ref = refs[wcnt + 1]
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    pay_f32 = pay_ref[...]                       # [C, 3]
    p_hi = pay_f32.astype(jnp.bfloat16)
    p_lo = (pay_f32 - p_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    pay = jnp.concatenate([p_hi, p_lo], axis=1)  # [C, 6]
    chunk = pay_f32.shape[0]
    iota = lax.broadcasted_iota(jnp.int32, (max_bin, chunk), 0)
    for f in range(num_features):
        w = word_refs[f >> 2][0, :]              # [C] int32, lane-oriented
        col = (w >> ((f & 3) * 8)) & 255
        onehot = (col[None, :] == iota).astype(jnp.bfloat16)   # [B, C]
        contrib = lax.dot_general(onehot, pay, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        out_ref[f] += contrib                    # [B, 6]


@functools.partial(jax.jit, static_argnames=("num_features", "max_bin",
                                             "chunk", "subbin",
                                             "interpret"))
def pallas_histogram_words(words, g: jax.Array, h: jax.Array,
                           valid: jax.Array, num_features: int,
                           max_bin: int, chunk: int = 1 << 11,
                           subbin: bool = True, interpret: bool = False
                           ) -> jax.Array:
    """hist[F, max_bin, 3] over packed bin words (see
    `histogram.histogram_from_words` for the layout contract)."""
    p = g.shape[0]
    wcnt = len(words)
    gm = jnp.where(valid, g, 0.0)
    hm = jnp.where(valid, h, 0.0)
    pay = jnp.stack([gm, hm, valid.astype(jnp.float32)], axis=1)
    b_pad = max(128, ((max_bin + 127) // 128) * 128)
    n_chunks = max(1, (p + chunk - 1) // chunk)
    pad = n_chunks * chunk - p
    words2 = [w.reshape(1, p) for w in words]
    if pad:
        words2 = [jnp.pad(w, ((0, 0), (0, pad))) for w in words2]
        pay = jnp.pad(pay, ((0, pad), (0, 0)))
    if subbin and b_pad > 128:
        kernel = functools.partial(_subbin_words_kernel,
                                   num_features=num_features, wcnt=wcnt)
        out = pl.pallas_call(
            kernel,
            grid=(n_chunks,),
            in_specs=[pl.BlockSpec((1, chunk), lambda i: (0, i))
                      for _ in range(wcnt)]
            + [pl.BlockSpec((NUM_STATS, chunk), lambda i: (0, i))],
            out_specs=pl.BlockSpec((num_features, 16, 128),
                                   lambda i: (0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((num_features, 16, 128),
                                           jnp.float32),
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=100 << 20),
            interpret=interpret,
        )(*words2, pay.T)
        return _subbin_finalize(out, num_features, max_bin)
    kernel = functools.partial(_hist_words_kernel,
                               num_features=num_features, max_bin=b_pad,
                               wcnt=wcnt)
    out = pl.pallas_call(
        kernel,
        grid=(n_chunks,),
        in_specs=[pl.BlockSpec((1, chunk), lambda i: (0, i))
                  for _ in range(wcnt)]
        + [pl.BlockSpec((chunk, NUM_STATS), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((num_features, b_pad, 6),
                               lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_features, b_pad, 6),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=100 << 20),
        interpret=interpret,
    )(*words2, pay)
    return (out[..., :NUM_STATS] + out[..., NUM_STATS:])[:, :max_bin, :]


def pallas_available() -> bool:
    """True when the Pallas TPU kernels compile natively."""
    return jax.default_backend() == "tpu"
