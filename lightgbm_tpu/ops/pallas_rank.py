"""Segment-fused lambdarank gradient kernel (Pallas TPU).

The bucketed lambdarank path (`ops/objectives.py` + `ops/ranking.py`)
pads every query to a size ladder and materializes `[Q, S, S]` pair
tensors per bucket — O(S^2) HBM traffic, up to ~1.8x pure padding waste,
and one compiled program per ladder size. This module is the reference's
fused per-query pair loop (`rank_objective.hpp:GetGradientsForOneQuery`,
with its quantized sigmoid table at `rank_objective.hpp:71`) recast for
the TPU's vector memory:

* queries (CSR doc offsets) are packed host-side into fixed-size row
  TILES of `tile` doc slots, aligned so that no query straddles a
  128-slot SUBTILE boundary unless it is itself longer than a subtile
  (long queries get an exclusive, boundary-aligned run of subtiles);
* one Pallas program per dataset streams one packed [8, tile] block
  (score / label-gain / label / query-id / inverse-max-DCG rows) of
  each tile through VMEM: rank positions come
  from a stable descending pair-count (no sort), DCG discounts from an
  exact one-hot MXU lookup against the same f64-derived table as the
  bucketed path, sigmoid pair factors are bf16 with f32 accumulation
  (score DIFFERENCES are formed in f32 first — bf16 subtraction of
  near-equal scores cancels catastrophically), and per-doc
  lambda/hessian column+row sums are scatter-accumulated once;
* pair math runs only on the static block BAND |subtile_i - subtile_j|
  < band implied by the packing (band = the longest packed query's
  subtile span), so cross-query slots cost a masked compare, not a
  padded pair tensor — and nothing `[Q, S, S]`-shaped ever exists in
  HBM.

`tpu_rank_sigmoid_bins > 0` reproduces the reference's quantized sigmoid
table semantics exactly: the sigmoid *input* is clamped to the table
range [-50, 50] and floored to the left edge of one of `bins` cells
before the (exact) sigmoid evaluates — identical values to looking up a
table built at cell left edges, without a memory-bound gather.

Used via `Config.tpu_rank_fused`; the bucketed path stays the
fallback/oracle (and handles queries longer than `tpu_rank_tile`).
Interpret mode (`interpret=True`) runs the kernel on CPU for tier-1.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import compile_cache
from ..obs import phases
from .ranking import dcg_discounts

SUBTILE = 128   # query alignment quantum = one lane register width


class QueryTilePack(NamedTuple):
    """Host-side tile packing of a query CSR layout.

    doc_idx  [NT, tile] int32 — global row ids (pads point at row 0)
    qid      [NT, tile] int32 — global query id per slot, -1 for pads
    band     int — max subtile span of any packed query (static kernel
             constant: pair math runs on block pairs |a - b| < band)
    leftover [num_queries] bool — queries LONGER than a tile, left for
             the bucketed fallback path
    fill     float — fraction of slots holding real docs
    """
    doc_idx: np.ndarray
    qid: np.ndarray
    band: int
    leftover: np.ndarray
    fill: float

    @property
    def num_tiles(self) -> int:
        return int(self.doc_idx.shape[0])

    @property
    def tile(self) -> int:
        return int(self.doc_idx.shape[1])

    def slot_of_row(self, num_data: int) -> np.ndarray:
        """[num_data] int32: each row's flat slot in the pack (the
        inverse of `doc_idx` over real slots), -1 for a row of a
        leftover query."""
        real = (self.qid >= 0).reshape(-1)
        out = np.full(num_data, -1, np.int32)
        out[self.doc_idx.reshape(-1)[real]] = np.flatnonzero(real)
        return out


def pack_query_tiles(query_boundaries: np.ndarray, tile: int,
                     sub: int = SUBTILE) -> QueryTilePack:
    """Greedy in-order packing of queries into fixed `tile`-slot tiles.

    Placement rules (they are what make the kernel's static block band
    correct): a query that fits the current subtile's remaining space is
    appended; one that does not starts at the next subtile boundary; one
    longer than a subtile starts at a boundary and owns ceil(c/sub)
    subtiles exclusively. Queries longer than `tile` are returned in
    `leftover` for the bucketed path.
    """
    assert tile % sub == 0 and tile >= sub, (tile, sub)
    qb = np.asarray(query_boundaries, np.int64)
    counts = np.diff(qb)
    nq = len(counts)
    leftover = counts > tile
    tiles_doc, tiles_qid = [], []
    cur_doc = np.zeros(tile, np.int32)
    cur_qid = np.full(tile, -1, np.int32)
    p = 0
    used = False
    band = 1
    docs_packed = 0

    def _flush():
        nonlocal cur_doc, cur_qid, p, used
        tiles_doc.append(cur_doc)
        tiles_qid.append(cur_qid)
        cur_doc = np.zeros(tile, np.int32)
        cur_qid = np.full(tile, -1, np.int32)
        p = 0
        used = False

    for q in range(nq):
        c = int(counts[q])
        if c <= 0 or leftover[q]:
            continue
        if c > sub:
            start = -(-p // sub) * sub          # align up to a subtile
        elif (p % sub) + c <= sub:
            start = p                           # fits the current subtile
        else:
            start = -(-p // sub) * sub
        if start + c > tile:
            _flush()
            start = 0
        cur_doc[start:start + c] = np.arange(qb[q], qb[q + 1],
                                             dtype=np.int32)
        cur_qid[start:start + c] = q
        band = max(band, -(-c // sub))
        p = start + c
        if c > sub:                             # exclusive subtile run
            p = -(-p // sub) * sub
        used = True
        docs_packed += c
    if used:
        _flush()
    if not tiles_doc:
        return QueryTilePack(np.zeros((0, tile), np.int32),
                             np.full((0, tile), -1, np.int32),
                             1, leftover, 0.0)
    doc_idx = np.stack(tiles_doc)
    qid = np.stack(tiles_qid)
    return QueryTilePack(doc_idx, qid, band, leftover,
                         docs_packed / float(doc_idx.size))


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------
def _band_range(c: int, nb: int, band: int):
    return range(max(0, c - band + 1), min(nb, c + band))


# sublane rows of the packed per-tile input block (all bitcast to i32)
_ROW_SCORE, _ROW_GAIN, _ROW_LABEL, _ROW_QID, _ROW_INV = range(5)
_PACK_ROWS = 8      # one full 8-sublane tile per grid step


def _rank_tile_kernel(in_ref, disc_ref, out_ref, *,
                      tile: int, sub: int, band: int, sigmoid: float,
                      lut_bins: int):
    """One grid step = one tile. in_ref is the [8, tile] packed block
    (rows _ROW_*: score/gain/inv as f32 bits, label/qid as i32), every
    doc a LANE. The pair math needs each doc both as a lane (the lower
    pair member, [1, sub] rows) and as a sublane (the higher member,
    [sub, 1] columns); Mosaic only takes blocks whose last two dims are
    (8, 128)-aligned, so a [tile, 1] column input cannot exist — the
    column forms are derived in-kernel by an identity-masked lane
    reduce, which moves single terms and is therefore exact. out_ref is
    [2, tile]: per-doc gradient and hessian sums, lane-oriented.

    Numerics mirror the bucketed oracle op-for-op: bf16 pair factors,
    f32 score differences and f32 accumulation, exact discount values
    via a one-hot MXU lookup of the f64-derived table.
    """
    f32 = jnp.float32
    bf = jnp.bfloat16
    nb = tile // sub

    def rows(k, dtype):
        """Per-subtile [1, sub] row forms of packed row k — sliced off
        the REF (a lane-offset slice of a loaded value picks up a layout
        Mosaic rejects)."""
        out = [in_ref[k:k + 1, b * sub:(b + 1) * sub] for b in range(nb)]
        if dtype != jnp.int32:
            out = [lax.bitcast_convert_type(x, dtype) for x in out]
        return out

    s_rows = rows(_ROW_SCORE, f32)
    g_rows = rows(_ROW_GAIN, f32)
    inv_rows = rows(_ROW_INV, f32)
    l_rows = rows(_ROW_LABEL, jnp.int32)
    q_rows = rows(_ROW_QID, jnp.int32)
    disc_tab = disc_ref[...]                      # [1, tile] f32

    iota_i = lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
    iota_j = lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
    eye = iota_i == iota_j
    NEG = jnp.float32(-3.4e38)
    POS = jnp.float32(3.4e38)

    def cols(xs):
        """[sub, 1] column forms of per-subtile [1, sub] rows."""
        zero = jnp.zeros((), xs[0].dtype)
        return [jnp.sum(jnp.where(eye, x, zero), axis=1, keepdims=True)
                for x in xs]

    def to_row(c):                                # [sub, 1] -> [1, sub]
        return jnp.sum(jnp.where(eye, c, 0.0), axis=0, keepdims=True)

    s_cols, q_cols, l_cols = cols(s_rows), cols(q_rows), cols(l_rows)
    g_cols, inv_cols = cols(g_rows), cols(inv_rows)

    # ---- pass 1a: rank / best / worst per COLUMN block (doc as i) ----
    ranks_c, norm_c = [], []
    for a in range(nb):
        sa = s_cols[a]
        qa = q_cols[a]
        rank = jnp.zeros((sub, 1), jnp.int32)
        best = jnp.full((sub, 1), NEG, f32)
        worst = jnp.full((sub, 1), POS, f32)
        for b in _band_range(a, nb, band):
            sb = s_rows[b]
            qb = q_rows[b]
            same = (qa == qb) & (qa >= 0)
            gi = iota_i + a * sub
            gj = iota_j + b * sub
            # "j sorts before i" under stable descending order (pads
            # have qid -1 and never match)
            before = same & ((sb > sa) | ((sb == sa) & (gj < gi)))
            rank = rank + jnp.sum(before.astype(jnp.int32), axis=1,
                                  keepdims=True)
            best = jnp.maximum(best, jnp.max(
                jnp.where(same, sb, NEG), axis=1, keepdims=True))
            worst = jnp.minimum(worst, jnp.min(
                jnp.where(same, sb, POS), axis=1, keepdims=True))
        ranks_c.append(rank)
        norm_c.append(best != worst)

    # ---- pass 1b: rank per ROW block (doc as j) ----------------------
    ranks_r = []
    for b in range(nb):
        sb = s_rows[b]
        qb = q_rows[b]
        rank = jnp.zeros((1, sub), jnp.int32)
        for a in _band_range(b, nb, band):
            sa = s_cols[a]
            qa = q_cols[a]
            same = (qa == qb) & (qb >= 0)
            gi = iota_i + a * sub
            gj = iota_j + b * sub
            before = same & ((sa > sb) | ((sa == sb) & (gi < gj)))
            rank = rank + jnp.sum(before.astype(jnp.int32), axis=0,
                                  keepdims=True)
        ranks_r.append(rank)

    # ---- exact discount lookup (one-hot against the f64-derived
    # table: bitwise-identical values to the bucketed path) ------------
    iota_lane = lax.broadcasted_iota(jnp.int32, (sub, tile), 1)
    iota_subl = lax.broadcasted_iota(jnp.int32, (tile, sub), 0)
    disc_c = []
    for a in range(nb):
        oh = (ranks_c[a] == iota_lane).astype(f32)          # [sub, tile]
        disc_c.append(lax.dot_general(
            oh, disc_tab, (((1,), (1,)), ((), ())),
            preferred_element_type=f32))                    # [sub, 1]
    disc_r = []
    for b in range(nb):
        oh = (ranks_r[b] == iota_subl).astype(f32)          # [tile, sub]
        disc_r.append(lax.dot_general(
            disc_tab, oh, (((1,), (0,)), ((), ())),
            preferred_element_type=f32))                    # [1, sub]

    # ---- pass 2: banded pair math, bf16 factors / f32 sums -----------
    two_sig = jnp.float32(2.0 * sigmoid)
    zero = jnp.asarray(0.0, bf)
    acc_ga = [jnp.zeros((sub, 1), f32) for _ in range(nb)]
    acc_ha = [jnp.zeros((sub, 1), f32) for _ in range(nb)]
    acc_gb = [jnp.zeros((1, sub), f32) for _ in range(nb)]
    acc_hb = [jnp.zeros((1, sub), f32) for _ in range(nb)]
    for a in range(nb):
        sa = s_cols[a]
        qa = q_cols[a]
        la = l_cols[a]
        gna = g_cols[a].astype(bf)
        inva = inv_cols[a].astype(bf)
        dca = disc_c[a]
        na = norm_c[a]
        for b in _band_range(a, nb, band):
            sb = s_rows[b]
            qb = q_rows[b]
            lb = l_rows[b]
            gnb = g_rows[b].astype(bf)
            same = (qa == qb) & (qa >= 0)
            ds = (sa - sb).astype(bf)             # diff in f32 FIRST
            dgap = gna - gnb
            pd = jnp.abs(dca - disc_r[b]).astype(bf)
            delta = dgap * pd * inva
            delta = jnp.where(na, delta / (0.01 + jnp.abs(ds)), delta)
            x = ds.astype(f32)
            if lut_bins > 0:
                # reference quantized sigmoid table semantics
                # (rank_objective.hpp:71): clamp to [-50, 50], floor to
                # the cell's left edge, then evaluate exactly there
                factor = jnp.float32(lut_bins / 100.0)
                idx = jnp.clip(jnp.floor((jnp.clip(x, -50.0, 50.0)
                                          + 50.0) * factor),
                               0.0, float(lut_bins - 1))
                x = idx / factor - 50.0
            p_lambda = (2.0 / (1.0 + jnp.exp(two_sig * x))).astype(bf)
            p_hess = p_lambda * (2.0 - p_lambda)
            pv = (la > lb) & same
            lam = jnp.where(pv, -p_lambda * delta, zero)
            hes = jnp.where(pv, p_hess * 2.0 * delta, zero)
            acc_ga[a] = acc_ga[a] + jnp.sum(lam.astype(f32), axis=1,
                                            keepdims=True)
            acc_ha[a] = acc_ha[a] + jnp.sum(hes.astype(f32), axis=1,
                                            keepdims=True)
            acc_gb[b] = acc_gb[b] + jnp.sum(lam.astype(f32), axis=0,
                                            keepdims=True)
            acc_hb[b] = acc_hb[b] + jnp.sum(hes.astype(f32), axis=0,
                                            keepdims=True)
    # doc as the HIGHER-labelled member gets +lam (column sums), as the
    # lower member -lam (row sums); both get +hes
    out_ref[0:1, :] = jnp.concatenate(
        [to_row(acc_ga[b]) - acc_gb[b] for b in range(nb)], axis=1)
    out_ref[1:2, :] = jnp.concatenate(
        [to_row(acc_ha[b]) + acc_hb[b] for b in range(nb)], axis=1)


def make_fused_grad_fn(num_tiles: int, tile: int, band: int,
                       sigmoid: float, lut_bins: int = 0,
                       sub: int = SUBTILE, interpret: bool = False,
                       rows: bool = False):
    """The jitted gradient program, in the kernel's own layout:

    (score_t[NT, T], qid, gain, label, inv, disc_tab[, w_t])
        -> (g_t[NT, T], h_t[NT, T]); pad slots give 0 whatever they hold,
        `w_t` is the per-slot weight where the objective has weights;

    or, with `rows`, its row-order wrapper:

    (score[n], doc_idx, slot_of_row, qid, gain, label, inv, disc_tab)
        -> (g[n], h[n]): one gather into the tile pack, the same kernel,
        one gather a lane back out (`slot_of_row` < 0: the row is in no
        tile and reads 0).

    All tables are runtime args, so one compiled program serves every
    booster at the same shapes; register the result under
    `compile_cache.program` keyed by `fused_program_key(...)`."""
    kernel = functools.partial(
        _rank_tile_kernel, tile=tile, sub=sub, band=band,
        sigmoid=float(sigmoid), lut_bins=int(lut_bins))
    NT, T = num_tiles, tile

    @phases.scoped("rank.glue")
    def slot_grads(score_t, qid, gain, label, inv, disc_tab, w_t=None):
        sc = jnp.where(qid >= 0, score_t, 0.0).astype(jnp.float32)
        bits = functools.partial(lax.bitcast_convert_type,
                                 new_dtype=jnp.int32)
        pad = jnp.zeros_like(qid)
        packed = jnp.stack(
            [bits(sc), bits(gain), label, qid, bits(inv)]
            + [pad] * (_PACK_ROWS - 5), axis=1)       # [NT, 8, T] i32
        gh = pl.pallas_call(
            kernel,
            grid=(NT,),
            in_specs=[pl.BlockSpec((None, _PACK_ROWS, T),
                                   lambda i: (i, 0, 0)),
                      pl.BlockSpec((1, T), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((None, 2, T), lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((NT, 2, T), jnp.float32),
            interpret=interpret,
            name="rank_grad_pass",
        )(packed, disc_tab)
        g_t = jnp.where(qid >= 0, gh[:, 0, :], 0.0)
        h_t = jnp.where(qid >= 0, gh[:, 1, :], 0.0)
        if w_t is not None:
            g_t, h_t = g_t * w_t, h_t * w_t
        return g_t, h_t

    def grad_fn(score_t, qid, gain, label, inv, disc_tab, w_t=None):
        compile_cache.note_trace()
        return slot_grads(score_t, qid, gain, label, inv, disc_tab, w_t)

    def grad_rows_fn(score, doc_idx, slot_of_row, *tables):
        compile_cache.note_trace()
        with phases.scope("rank.scatter"):
            score_t = score[doc_idx]
        g_t, h_t = slot_grads(score_t, *tables)
        with phases.scope("rank.gather"):
            at = jnp.maximum(slot_of_row, 0)
            return (jnp.where(slot_of_row >= 0, g_t.reshape(-1)[at], 0.0),
                    jnp.where(slot_of_row >= 0, h_t.reshape(-1)[at], 0.0))

    return jax.jit(grad_rows_fn if rows else grad_fn)


def fused_program_key(pack: QueryTilePack, sigmoid: float, lut_bins: int,
                      interpret: bool, rows: bool):
    return ("rank_fused", pack.num_tiles, pack.tile, int(pack.band),
            SUBTILE, float(sigmoid), int(lut_bins), bool(interpret),
            bool(rows))


def discount_table(tile: int) -> np.ndarray:
    """[1, tile] f32 rank-position discounts — the same f64-derived
    values the bucketed path tabulates (dcg_calculator.cpp:Init)."""
    return dcg_discounts(tile).astype(np.float32)[None, :]
