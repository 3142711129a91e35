"""Speculative level-batched tree builder with exact leaf-wise replay.

The leaf-wise builder (`device_learner._make_build_fn`) grows one split per
device step: a partition sort of the parent slice plus a RANDOM GATHER of
the smaller child's rows (reference analogue: the ordered-gradient gather,
`dataset.cpp:789-803`). On TPU v5e the gather dominates (~29 ns/row
measured, vs ~14 ns/row for a wide-payload sort and ~16 ns/row for the
histogram itself), and 254 sequential steps serialize poorly.

This builder splits the work differently:

1. **Speculative level growth (device, one jitted program).** Each round
   splits EVERY positive-gain leaf (up to a speculation budget of
   ~1.5x `num_leaves`): per-row routing parameters arrive via
   difference-array prefix sums over the contiguous leaf blocks, the
   partition for the whole round is ONE stable `lax.sort` whose payload
   operands carry full row RECORDS — ceil(F/4) packed bin words (4 uint8
   bins per int32), gradient, hessian, row id — through the
   compare-exchange network (no gathers anywhere), and smaller-child
   histograms read CONTIGUOUS record slices (`lax.dynamic_slice`),
   unpacking bins inside the kernel. Split finding is one vmapped scan
   over all leaf slots per round.

2. **Leaf-wise replay (host, microseconds).** The reference's growth
   order is a strict priority queue on split gain
   (`serial_tree_learner.cpp:173-237`). With every speculated gain known,
   the replay re-runs that queue exactly and keeps only the splits
   sequential leaf-wise growth would have made; over-speculated splits
   are discarded. The replay is exact unless it picks a speculation-
   frontier split while budget remains (the path was speculated too
   shallow) — with the 1.5x budget this is rare, and the deviation is
   bounded: that path is truncated exactly where speculation stopped.

3. **Score update over physical blocks.** The partition on device is
   finer than the committed tree (discarded splits still partitioned
   rows). Each physical block maps to its covering committed leaf, so the
   existing fill + unpermute score update runs unchanged on the
   (block_begin, block_cnt, covering value) tables.

Used for serial and data-parallel modes when bins fit uint8; bagged
iterations and >256-bin features fall back to the leaf-wise builder.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.histogram import NUM_HIST_STATS, histogram_from_words
from ..ops.partition import numerical_goes_left
from .device_learner import (BF_GAIN, BF_LOUT, BF_RG, BF_RH, BF_LG, BF_LH,
                             BF_ROUT, BF_W, BI_DEFLEFT, BI_FEAT, BI_ISCAT,
                             BI_W,
                             BI_LC, BI_RC, BI_THR, LF_MAXC, LF_MINC,
                             LF_SG, LF_SH, LF_VALUE, LF_W, LI_BEGIN,
                             LI_COUNT, LI_COUNTG, LI_DEPTH, LI_W, NEG_INF,
                             TreeRecord, bucket_table, pack_best_payload)

# speculated-split record lanes (execution order e; right child slot e+1)
SF_GAIN, SF_LOUT, SF_ROUT, SF_IVAL = range(4)
SF_W = 4
SI_SLOT, SI_FEAT, SI_THR, SI_DEFLEFT, SI_ISCAT, SI_LC, SI_RC = range(7)
SI_W = 8


class SpecResult(NamedTuple):
    """Device outputs of one speculative build (small [S]-sized arrays
    except rid). block_begin/block_cnt are the LOCAL physical partition
    blocks (per shard under data-parallel); everything else is identical
    on every shard."""
    rid: jax.Array         # i32[n] final row-id permutation
    n_exec: jax.Array      # i32 scalar: executed speculative splits
    execF: jax.Array       # f32[S-1, SF_W]
    execI: jax.Array       # i32[S-1, SI_W]
    execB: jax.Array       # u32[S-1, 8]
    bestF: jax.Array       # f32[S, BF_W] frontier candidates
    bestI: jax.Array       # i32[S, BI_W]
    bestB: jax.Array       # u32[S, 8]
    leafF: jax.Array       # f32[S, LF_W]
    leafI: jax.Array       # i32[S, LI_W] (global count/depth lanes)
    block_begin: jax.Array  # i32[S] local partition block starts
    block_cnt: jax.Array    # i32[S] local partition block counts


def pack_bin_words(bins: np.ndarray) -> np.ndarray:
    """uint8 bins [N, F] -> packed int32 words [ceil(F/4), N].

    Word w holds features 4w..4w+3, feature 4w+j in bits 8j..8j+7. The
    word-major layout keeps each word array contiguous for the per-level
    sort operands and lane-oriented for the histogram kernel."""
    n, f = bins.shape
    wcnt = (f + 3) // 4
    padded = np.zeros((n, wcnt * 4), np.uint8)
    padded[:, :f] = bins
    words = padded.reshape(n, wcnt, 4).astype(np.uint32)
    packed = (words[:, :, 0] | (words[:, :, 1] << 8)
              | (words[:, :, 2] << 16) | (words[:, :, 3] << 24))
    return np.ascontiguousarray(
        packed.T.astype(np.int64).astype(np.int32))


def extract_bin(words, word_idx: jax.Array, shift: jax.Array) -> jax.Array:
    """Per-row bin of a per-row feature: select the word, shift, mask."""
    acc = jnp.zeros_like(word_idx)
    for w, arr in enumerate(words):
        acc = jnp.where(word_idx == w, arr, acc)
    return (acc >> shift) & 255


def spec_slots(num_leaves: int, factor: float) -> int:
    """Speculation slot count S: ~factor x num_leaves, min num_leaves+1."""
    return max(int(np.ceil(factor * num_leaves)), num_leaves + 1)


def make_level_build_fn(learner):
    """Build the jitted speculative level program for a DeviceTreeLearner.

    Returns fn(words2d, grad, hess, fmask) -> SpecResult. Host-side
    `replay_leafwise` turns a pulled SpecResult into the final TreeRecord.
    """
    cfg = learner.cfg
    L = cfg.num_leaves
    S = spec_slots(L, float(getattr(cfg, "tpu_level_spec", 1.5)))
    Sm1 = S - 1
    F = learner.num_features
    B = learner.max_bin_global
    finder = learner.finder
    depth_limit = learner._depth_limit
    mono_dev = jnp.asarray(learner.meta["monotone"], jnp.int32)
    mono_any = learner._mono_any
    nb_dev, db_dev, mt_dev = learner._nb_dev, learner._db_dev, learner._mt_dev
    wcnt = (F + 3) // 4
    axis = learner.axis_name
    mode = learner.parallel_mode
    chunk = int(cfg.tpu_hist_chunk)
    precision = learner.hist_precision
    rows_sharded = axis is not None and mode == "data"
    n_global = learner.n
    n = (int(np.ceil(n_global / max(learner.mesh_size, 1)))
         if rows_sharded else n_global)

    def _gsum(x):
        if axis is not None and mode == "data":
            x = lax.psum(x, axis)
        if x.dtype == jnp.float64:
            # single f64→f32 rounding after the reduce (same seam as the
            # leaf-wise builder's _gsum_hist) — topology-invariant values
            x = x.astype(jnp.float32)
        return x

    def _hist_slice(words, gw, hw, begin, padded: int, count):
        """Histogram of a CONTIGUOUS record slice. `begin` is clamped so
        the static window fits; the leaf's rows then sit at offset
        begin - clamped inside the window and the mask follows them."""
        size = min(padded, n)
        cb = jnp.clip(begin, 0, max(n - size, 0))
        off = begin - cb
        ws = [lax.dynamic_slice(w, (cb,), (size,)) for w in words]
        g = lax.dynamic_slice(gw, (cb,), (size,))
        h = lax.dynamic_slice(hw, (cb,), (size,))
        pos = jnp.arange(size, dtype=jnp.int32)
        valid = (pos >= off) & (pos < off + count)
        return histogram_from_words(ws, g, h, valid, F, B, chunk, precision)

    _payload = pack_best_payload

    def eval_one(fmask, hist, sg, sh, cnt, minc, maxc, depth, exists):
        out = finder(hist, sg, sh, cnt, minc, maxc)
        gain = jnp.where(fmask > 0, out["gain"], NEG_INF)
        gain = jnp.where((depth >= depth_limit) | ~exists,
                         jnp.full_like(gain, NEG_INF), gain)
        return _payload(out, gain)

    eval_all = jax.vmap(eval_one, in_axes=(None, 0, 0, 0, 0, 0, 0, 0, 0))

    # bucket sizes for the smaller-child hist slices (shared table)
    min_pad = max(int(cfg.tpu_min_pad), 1024)
    buckets = bucket_table(min_pad, n)
    nbk = len(buckets)
    bucket_tbl = jnp.asarray(buckets, jnp.int32)

    def _bucket_index(count):
        return jnp.clip(jnp.sum((count > bucket_tbl).astype(jnp.int32)),
                        0, nbk - 1)

    def build(words2d, grad, hess, feature_mask_f32):
        """words2d: int32 [wcnt, n]; grad/hess: f32 [n]."""
        words0 = [words2d[w] for w in range(wcnt)]
        if rows_sharded:
            shard = lax.axis_index(axis)
            local_cnt = jnp.clip(n_global - shard * n, 0, n).astype(jnp.int32)
        else:
            local_cnt = jnp.int32(n)
        pos0 = jnp.arange(n, dtype=jnp.int32)
        live = pos0 < local_cnt
        gw = jnp.where(live, grad, 0.0)
        hw = jnp.where(live, hess, 0.0)
        rid = pos0

        # ---------- root ----------
        root_hist = _gsum(histogram_from_words(words0, gw, hw, live, F, B,
                                               chunk, precision))
        if precision == "f64":
            with jax.enable_x64(True):
                root_g = _gsum(jnp.sum(gw.astype(jnp.float64)))
                root_h = _gsum(jnp.sum(hw.astype(jnp.float64)))
        else:
            root_g = _gsum(jnp.sum(gw))
            root_h = _gsum(jnp.sum(hw))
        root_cnt_g = _gsum(local_cnt)

        # slot S and exec row Sm1 are DUMP targets: scatters from
        # unselected leaves write their old values there instead of
        # colliding with the final round's real slots
        leafF = jnp.zeros((S + 1, LF_W), jnp.float32)
        leafF = leafF.at[:, LF_MINC].set(-jnp.inf)
        leafF = leafF.at[:, LF_MAXC].set(jnp.inf)
        leafF = leafF.at[0, LF_SG].set(root_g)
        leafF = leafF.at[0, LF_SH].set(root_h)
        leafI = jnp.zeros((S + 1, LI_W), jnp.int32)
        leafI = leafI.at[:, LI_BEGIN].set(
            jnp.full((S + 1,), n, jnp.int32).at[0].set(0))
        leafI = leafI.at[0, LI_COUNT].set(local_cnt)
        leafI = leafI.at[0, LI_COUNTG].set(root_cnt_g)

        hist_store = jnp.zeros((S + 1, F, B, NUM_HIST_STATS), jnp.float32)
        hist_store = hist_store.at[0].set(root_hist)
        execF = jnp.zeros((Sm1 + 1, SF_W), jnp.float32)
        execI = jnp.zeros((Sm1 + 1, SI_W), jnp.int32)
        execB = jnp.zeros((Sm1 + 1, 8), jnp.uint32)

        exists0 = jnp.zeros((S + 1,), bool).at[0].set(True)
        bF, bI, bB = eval_all(feature_mask_f32, hist_store,
                              leafF[:, LF_SG], leafF[:, LF_SH],
                              leafI[:, LI_COUNTG], leafF[:, LF_MINC],
                              leafF[:, LF_MAXC], leafI[:, LI_DEPTH], exists0)
        bestF = jnp.where(exists0[:, None], bF,
                          jnp.full((S + 1, BF_W), NEG_INF, jnp.float32))
        bestI = bI
        bestB = bB

        state = (jnp.int32(0), tuple(words0), gw, hw, rid, leafF, leafI,
                 bestF, bestI, bestB, hist_store, execF, execI, execB)

        def cond(state):
            done, bestF = state[0], state[7]
            return (done < Sm1) & (jnp.max(bestF[:, BF_GAIN]) > 0.0)

        def body(state):
            (done, words_t, gw, hw, rid, leafF, leafI, bestF, bestI, bestB,
             hist_store, execF, execI, execB) = state
            words = list(words_t)
            s_ids = jnp.arange(S + 1, dtype=jnp.int32)
            gains = bestF[:, BF_GAIN]
            budget = Sm1 - done
            cand = gains > 0.0
            # round order by (-gain, slot); also the speculation-budget trim
            order = jnp.argsort(-gains, stable=True)
            rank_of = jnp.zeros(S + 1, jnp.int32).at[order].set(s_ids)
            n_cand = jnp.sum(cand.astype(jnp.int32))
            k = jnp.minimum(n_cand, budget)
            sel = cand & (rank_of < k)
            seq = done + rank_of                    # exec index per slot
            right_slot = seq + 1                    # new slot for right child

            # ---- record the k executed splits
            safe_seq = jnp.where(sel, seq, Sm1)
            rowF = jnp.stack([bestF[:, BF_GAIN], bestF[:, BF_LOUT],
                              bestF[:, BF_ROUT], leafF[:, LF_VALUE]], axis=1)
            rowI = jnp.zeros((S + 1, SI_W), jnp.int32)
            rowI = rowI.at[:, SI_SLOT].set(s_ids)
            rowI = rowI.at[:, SI_FEAT].set(bestI[:, BI_FEAT])
            rowI = rowI.at[:, SI_THR].set(bestI[:, BI_THR])
            rowI = rowI.at[:, SI_DEFLEFT].set(bestI[:, BI_DEFLEFT])
            rowI = rowI.at[:, SI_ISCAT].set(bestI[:, BI_ISCAT])
            rowI = rowI.at[:, SI_LC].set(bestI[:, BI_LC])
            rowI = rowI.at[:, SI_RC].set(bestI[:, BI_RC])
            selF = sel[:, None]
            execF = execF.at[safe_seq].set(
                jnp.where(selF, rowF, execF[safe_seq]))
            execI = execI.at[safe_seq].set(
                jnp.where(selF, rowI, execI[safe_seq]))
            execB = execB.at[safe_seq].set(
                jnp.where(selF, bestB, execB[safe_seq]))

            # ---- per-position routing via difference-array fills.
            # Empty LOCAL blocks (possible per shard under data-parallel)
            # share their begin with the covering non-empty block; ties
            # must resolve so the covering block's delta lands LAST, or
            # its rows would route with the empty sibling's parameters.
            begins = leafI[:, LI_BEGIN]
            fill_begins = jnp.where(begins < n, begins, n)
            order_b = jnp.argsort(
                fill_begins * 2 + (leafI[:, LI_COUNT] > 0), stable=True)
            bb = fill_begins[order_b]
            diff_i = jnp.zeros((n + 1,), jnp.int32)

            def fill_i32(table):
                tb = table[order_b]
                delta = tb - jnp.concatenate(
                    [jnp.zeros(1, tb.dtype), tb[:-1]])
                return jnp.cumsum(diff_i.at[bb].add(delta)[:-1])

            feat = bestI[:, BI_FEAT]
            packed = (jnp.clip(bestI[:, BI_THR], 0, 255)
                      | ((feat >> 2) << 8)
                      | ((feat & 3) << 16)
                      | (bestI[:, BI_DEFLEFT] << 19)
                      | (mt_dev[feat] << 20)
                      | (bestI[:, BI_ISCAT] << 22)
                      | (sel.astype(jnp.int32) << 23))
            packed2 = (jnp.clip(nb_dev[feat], 0, 65535)
                       | (jnp.clip(db_dev[feat], 0, 65535) << 16))
            p1 = fill_i32(packed)
            p2 = fill_i32(packed2)
            beg_pos = fill_i32(fill_begins)

            thr_pos = p1 & 255
            w_pos = (p1 >> 8) & 255
            sh_pos = ((p1 >> 16) & 3) * 8
            dl_pos = (p1 >> 19) & 1
            mt_pos = (p1 >> 20) & 3
            cat_pos = (p1 >> 22) & 1
            act_pos = (p1 >> 23) & 1
            binv = extract_bin(words, w_pos, sh_pos)

            gl_num = numerical_goes_left(binv, thr_pos, dl_pos != 0, mt_pos,
                                         p2 >> 16, p2 & 65535)
            any_cat = jnp.any(sel & (bestI[:, BI_ISCAT] != 0))

            def with_cat(_):
                bits = [fill_i32(bestB[:, wj].astype(jnp.int32))
                        for wj in range(8)]
                word = binv >> 5
                acc = jnp.zeros_like(binv)
                for wj in range(8):
                    acc = jnp.where(word == wj, bits[wj], acc)
                hit = ((acc.astype(jnp.uint32)
                        >> (binv & 31).astype(jnp.uint32)) & 1) != 0
                gl_cat = hit & (word < 8)
                return jnp.where(cat_pos != 0, gl_cat, gl_num)

            goes_left = lax.cond(any_cat, with_cat, lambda _: gl_num,
                                 operand=None)
            goes_left = goes_left & (act_pos != 0) & live
            side = jnp.where((act_pos != 0) & live,
                             (~goes_left).astype(jnp.int32), 0)
            key = jnp.where(live, (beg_pos << 1) | side,
                            jnp.int32(2 * n + 2))

            # local left counts per leaf (exact segment sums via cumsum)
            cl = jnp.cumsum(goes_left.astype(jnp.int32))
            begs = jnp.clip(leafI[:, LI_BEGIN], 0, n - 1)
            ends = jnp.clip(leafI[:, LI_BEGIN] + leafI[:, LI_COUNT] - 1,
                            0, n - 1)
            excl_beg = cl[begs] - goes_left[begs].astype(jnp.int32)
            left_cnt = jnp.where(sel & (leafI[:, LI_COUNT] > 0),
                                 cl[ends] - excl_beg, 0)

            sorted_ops = lax.sort([key, *words, gw, hw, rid], num_keys=1,
                                  is_stable=True)
            words = list(sorted_ops[1:1 + wcnt])
            gw2 = sorted_ops[1 + wcnt]
            hw2 = sorted_ops[2 + wcnt]
            rid2 = sorted_ops[3 + wcnt]

            # ---- leaf bookkeeping (vectorized over [S])
            safe_right = jnp.where(sel, right_slot, S)
            depth_new = leafI[:, LI_DEPTH] + 1
            if mono_any:
                mono = mono_dev[bestI[:, BI_FEAT]]
                mid = (bestF[:, BF_LOUT] + bestF[:, BF_ROUT]) / 2.0
                minc0 = leafF[:, LF_MINC]
                maxc0 = leafF[:, LF_MAXC]
                lmax = jnp.where(mono > 0, jnp.minimum(maxc0, mid), maxc0)
                rmin = jnp.where(mono > 0, jnp.maximum(minc0, mid), minc0)
                lmin = jnp.where(mono < 0, jnp.maximum(minc0, mid), minc0)
                rmax = jnp.where(mono < 0, jnp.minimum(maxc0, mid), maxc0)
            else:
                lmin = rmin = leafF[:, LF_MINC]
                lmax = rmax = leafF[:, LF_MAXC]

            rrowF = jnp.zeros((S + 1, LF_W), jnp.float32)
            rrowF = rrowF.at[:, LF_SG].set(bestF[:, BF_RG])
            rrowF = rrowF.at[:, LF_SH].set(bestF[:, BF_RH])
            rrowF = rrowF.at[:, LF_MINC].set(rmin)
            rrowF = rrowF.at[:, LF_MAXC].set(rmax)
            rrowF = rrowF.at[:, LF_VALUE].set(bestF[:, BF_ROUT])
            rrowI = jnp.zeros((S + 1, LI_W), jnp.int32)
            rrowI = rrowI.at[:, LI_BEGIN].set(leafI[:, LI_BEGIN] + left_cnt)
            rrowI = rrowI.at[:, LI_COUNT].set(leafI[:, LI_COUNT] - left_cnt)
            rrowI = rrowI.at[:, LI_COUNTG].set(bestI[:, BI_RC])
            rrowI = rrowI.at[:, LI_DEPTH].set(depth_new)
            leafF = leafF.at[safe_right].set(
                jnp.where(selF, rrowF, leafF[safe_right]))
            leafI = leafI.at[safe_right].set(
                jnp.where(selF, rrowI, leafI[safe_right]))
            leafF = leafF.at[:, LF_SG].set(
                jnp.where(sel, bestF[:, BF_LG], leafF[:, LF_SG]))
            leafF = leafF.at[:, LF_SH].set(
                jnp.where(sel, bestF[:, BF_LH], leafF[:, LF_SH]))
            leafF = leafF.at[:, LF_MINC].set(
                jnp.where(sel, lmin, leafF[:, LF_MINC]))
            leafF = leafF.at[:, LF_MAXC].set(
                jnp.where(sel, lmax, leafF[:, LF_MAXC]))
            leafF = leafF.at[:, LF_VALUE].set(
                jnp.where(sel, bestF[:, BF_LOUT], leafF[:, LF_VALUE]))
            leafI = leafI.at[:, LI_COUNT].set(
                jnp.where(sel, left_cnt, leafI[:, LI_COUNT]))
            leafI = leafI.at[:, LI_COUNTG].set(
                jnp.where(sel, bestI[:, BI_LC], leafI[:, LI_COUNTG]))
            leafI = leafI.at[:, LI_DEPTH].set(
                jnp.where(sel, depth_new, leafI[:, LI_DEPTH]))

            # ---- histograms for the round's children: smaller child from
            # its contiguous slice, larger by parent subtraction
            def hist_child(j, carry):
                leafI_c, hist_store = carry
                bl = order[j]                       # parent (= left child)
                rl = done + j + 1                   # right child slot
                l_beg = leafI_c[bl, LI_BEGIN]
                l_cnt = leafI_c[bl, LI_COUNT]
                r_beg = leafI_c[rl, LI_BEGIN]
                r_cnt = leafI_c[rl, LI_COUNT]
                smaller_is_left = \
                    leafI_c[bl, LI_COUNTG] <= leafI_c[rl, LI_COUNTG]
                sm_beg = jnp.where(smaller_is_left, l_beg, r_beg)
                sm_cnt = jnp.where(smaller_is_left, l_cnt, r_cnt)
                bk = _bucket_index(jnp.maximum(sm_cnt, 1))

                def mk(size):
                    def fn(ws, g, h, b, c):
                        return _hist_slice(ws, g, h, b, size, c)
                    return fn

                sm_hist = _gsum(lax.switch(
                    bk, [mk(sz) for sz in buckets], list(words), gw2, hw2,
                    sm_beg, sm_cnt))
                lg_hist = hist_store[bl] - sm_hist
                left_hist = jnp.where(smaller_is_left, sm_hist, lg_hist)
                right_hist = jnp.where(smaller_is_left, lg_hist, sm_hist)
                hist_store = hist_store.at[bl].set(left_hist)
                hist_store = hist_store.at[rl].set(right_hist)
                return (leafI_c, hist_store)

            _, hist_store = lax.fori_loop(0, k, hist_child,
                                          (leafI, hist_store))

            # ---- one vmapped split search over ALL existing slots
            exists = s_ids <= done + k
            bF, bI, bB = eval_all(feature_mask_f32, hist_store,
                                  leafF[:, LF_SG], leafF[:, LF_SH],
                                  leafI[:, LI_COUNTG], leafF[:, LF_MINC],
                                  leafF[:, LF_MAXC], leafI[:, LI_DEPTH],
                                  exists)
            bestF = jnp.where(exists[:, None], bF, bestF)
            bestI = jnp.where(exists[:, None], bI, bestI)
            bestB = jnp.where(exists[:, None], bB, bestB)

            return (done + k, tuple(words), gw2, hw2, rid2, leafF, leafI,
                    bestF, bestI, bestB, hist_store, execF, execI, execB)

        (n_exec, _, _, _, rid, leafF, leafI, bestF, bestI, bestB,
         _, execF, execI, execB) = lax.while_loop(cond, body, state)

        return SpecResult(rid=rid, n_exec=n_exec, execF=execF[:Sm1],
                          execI=execI[:Sm1], execB=execB[:Sm1],
                          bestF=bestF[:S], bestI=bestI[:S], bestB=bestB[:S],
                          leafF=leafF[:S], leafI=leafI[:S],
                          block_begin=leafI[:S, LI_BEGIN],
                          block_cnt=leafI[:S, LI_COUNT])

    if axis is not None:
        return build
    return jax.jit(build)


# ---------------------------------------------------------------------------
# host-side exact leaf-wise replay
# ---------------------------------------------------------------------------
def replay_leafwise(spec, num_leaves: int):
    """Replay the reference's priority-queue growth
    (`serial_tree_learner.cpp:173-237`) over the speculated splits (NumPy,
    host, microseconds). Returns (TreeRecord, exact: bool).

    Only EXECUTED speculative splits can be committed — this keeps the
    device partition consistent with the committed tree for the block
    score update. `exact` is False when the replay would have needed a
    split beyond the speculation frontier while budget remained (the
    caller then falls back to the strictly sequential leaf-wise builder
    for this tree).
    """
    import heapq

    n_exec = int(spec.n_exec)
    execF = np.asarray(spec.execF)
    execI = np.asarray(spec.execI)
    execB = np.asarray(spec.execB)
    bestF = np.asarray(spec.bestF)
    leafI = np.asarray(spec.leafI)
    S = bestF.shape[0]
    Lm1 = max(num_leaves - 1, 1)

    # per-slot chain of executed splits, in execution order
    nxt = np.full(max(n_exec, 1), -1, np.int64)
    first_exec_of_slot = np.full(S, -1, np.int64)
    for e in range(n_exec - 1, -1, -1):
        sl = int(execI[e, SI_SLOT])
        nxt[e] = first_exec_of_slot[sl]
        first_exec_of_slot[sl] = e

    exact = True
    heap = []

    def push(slot: int, e_after: int):
        nonlocal exact
        e = first_exec_of_slot[slot]
        while e != -1 and e < e_after:
            e = nxt[e]
        if e != -1:
            gain = float(execF[e, SF_GAIN])
            if gain > 0.0:
                heapq.heappush(heap, (-gain, slot, e))
        else:
            # frontier: an unexecuted candidate — if positive it may have
            # deserved the budget; mark inexact so the caller can decide
            if float(bestF[slot, BF_GAIN]) > 0.0:
                heapq.heappush(heap, (-float(bestF[slot, BF_GAIN]),
                                      slot, -1))

    push(0, 0)
    chosen = []          # (slot, exec_idx) in replay order
    budget = Lm1 if num_leaves > 1 else 0
    while heap and len(chosen) < budget:
        _, slot, e = heapq.heappop(heap)
        if e == -1:
            exact = False      # speculation too shallow for this path
            continue           # truncate the path; keep scoring consistent
        chosen.append((slot, e))
        push(slot, e + 1)
        push(e + 1, e + 1)

    n_splits = len(chosen)
    recF = np.zeros((Lm1, 4), np.float32)
    recI = np.zeros((Lm1, 8), np.int32)
    recB = np.zeros((Lm1, 8), np.uint32)
    leaf_value = np.zeros(max(num_leaves, 1), np.float32)
    leaf_count = np.zeros(max(num_leaves, 1), np.int32)
    leaf_count[0] = int(leafI[0, LI_COUNTG]) if S else 0
    committed = np.zeros(max(n_exec, 1), bool)
    final_of_slot = np.full(S, -1, np.int64)
    final_of_slot[0] = 0
    for s_idx, (slot, e) in enumerate(chosen):
        fl = int(final_of_slot[slot])
        committed[e] = True
        final_of_slot[e + 1] = s_idx + 1
        recF[s_idx] = (execF[e, SF_LOUT], execF[e, SF_ROUT],
                       execF[e, SF_GAIN], execF[e, SF_IVAL])
        recI[s_idx] = (fl, execI[e, SI_FEAT], execI[e, SI_THR],
                       execI[e, SI_DEFLEFT], execI[e, SI_ISCAT],
                       execI[e, SI_LC], execI[e, SI_RC], 0)
        recB[s_idx] = execB[e]
        leaf_value[fl] = execF[e, SF_LOUT]
        leaf_value[s_idx + 1] = execF[e, SF_ROUT]
        leaf_count[fl] = execI[e, SI_LC]
        leaf_count[s_idx + 1] = execI[e, SI_RC]

    # covering committed value per physical block (slot): walk executed
    # splits in order; committed splits set their children's values,
    # discarded splits pass the parent's covering value through. Splits of
    # any slot occur in increasing exec order, so later committed splits
    # correctly overwrite.
    cover = np.zeros(S, np.float32)
    cover[0] = leaf_value[0]
    for e in range(n_exec):
        sl = int(execI[e, SI_SLOT])
        if committed[e]:
            cover[sl] = float(execF[e, SF_LOUT])
            cover[e + 1] = float(execF[e, SF_ROUT])
        else:
            cover[e + 1] = cover[sl]

    record = TreeRecord(
        num_splits=np.int32(n_splits),
        leaf=recI[:, 0], feature=recI[:, 1], threshold_bin=recI[:, 2],
        default_left=recI[:, 3] != 0, is_cat=recI[:, 4] != 0,
        cat_bitset=recB,
        left_output=recF[:, 0], right_output=recF[:, 1],
        left_count=recI[:, 5], right_count=recI[:, 6],
        gain=recF[:, 2], internal_value=recF[:, 3],
        leaf_value=leaf_value, leaf_count_arr=leaf_count,
        leaf_begin=leafI[:max(num_leaves, 1), LI_BEGIN].astype(np.int32),
        leaf_cnt_part=leafI[:max(num_leaves, 1), LI_COUNT].astype(np.int32),
        block_begin=leafI[:, LI_BEGIN].astype(np.int32),
        block_cnt=leafI[:, LI_COUNT].astype(np.int32),
        block_value=cover)
    return record, exact
