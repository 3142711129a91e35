"""Vectorized best-split search over per-feature histograms.

Re-creates the reference `FeatureHistogram` split-gain machinery
(`src/treelearner/feature_histogram.hpp:91-644`) as one XLA program over all
features at once: the two sequential threshold scans (dir=-1 / dir=+1 with
missing-value routing) become masked prefix/suffix sums over the bin axis,
and the scan's `continue`/`break` guards become validity masks (they are
monotone along the scan, so masking is exactly equivalent).

Semantics carried over exactly:
- threshold t means "bin <= t goes left"; `default_left` = (chosen dir == -1)
  (`feature_histogram.hpp:560-561,642`)
- missing Zero: the default bin is excluded from the accumulating side and
  from the candidate set (`:529,:587` — note the skipped *candidate* is
  threshold `default_bin-1` in dir=-1 and `default_bin` in dir=+1)
- missing NaN: the last bin (NaN bin) is excluded from the dir=-1 accumulation
  range so NaN rows ride with the leaf-total remainder (`:523,571-583`)
- two scans only when num_bin > 2 and missing != None; otherwise a single
  dir=-1 scan, with default_left forced False for NaN (`:97-111`)
- kEpsilon hessian seeding: parent hessian + 2e-15, each side + 1e-15
  (`:87,520,567`)
- L1-thresholded leaf outputs, max_delta_step clamp, monotone-constraint veto
  with constraint-clamped outputs (`:446-506`)
- categorical one-hot and CTR-sorted subset scans with cat_smooth / cat_l2 /
  max_cat_threshold / min_data_per_group (`:118-258`)
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

K_EPSILON = 1e-15
NEG_INF = -jnp.inf
# device category bitsets are 8 u32 words; categorical split candidates are
# limited to the first (most frequent) 256 category bins
CAT_BITSET_BINS = 256


class SplitHyper(NamedTuple):
    """Static split hyper-parameters (subset of Config used by the finder)."""
    lambda_l1: float
    lambda_l2: float
    max_delta_step: float
    min_data_in_leaf: int
    min_sum_hessian_in_leaf: float
    min_gain_to_split: float
    cat_smooth: float
    cat_l2: float
    max_cat_threshold: int
    max_cat_to_onehot: int
    min_data_per_group: int
    # lambda_l2 + cat_l2, precomputed in double so the sorted-categorical
    # path sees the same rounding whether the lambdas are static floats or
    # per-model traced scalars (sweep mode threads all three as operands).
    lambda_l2_cat: float = 0.0

    @classmethod
    def from_config(cls, cfg) -> "SplitHyper":
        return cls(
            lambda_l1=float(cfg.lambda_l1),
            lambda_l2=float(cfg.lambda_l2),
            max_delta_step=float(cfg.max_delta_step),
            min_data_in_leaf=int(cfg.min_data_in_leaf),
            min_sum_hessian_in_leaf=float(cfg.min_sum_hessian_in_leaf),
            min_gain_to_split=float(cfg.min_gain_to_split),
            cat_smooth=float(cfg.cat_smooth),
            cat_l2=float(cfg.cat_l2),
            max_cat_threshold=int(cfg.max_cat_threshold),
            max_cat_to_onehot=int(cfg.max_cat_to_onehot),
            min_data_per_group=int(cfg.min_data_per_group),
            lambda_l2_cat=float(cfg.lambda_l2) + float(cfg.cat_l2),
        )


def _threshold_l1(s, l1):
    """reference ThresholdL1 (feature_histogram.hpp:446)."""
    reg = jnp.maximum(jnp.abs(s) - l1, 0.0)
    return jnp.sign(s) * reg


def threshold_l1_host(s: "np.ndarray", l1: float):
    """NumPy twin of `_threshold_l1` for host-side paths (refit)."""
    import numpy as np
    return np.sign(s) * np.maximum(np.abs(s) - l1, 0.0)


def _leaf_output(sg, sh, l1, l2, mds):
    """reference CalculateSplittedLeafOutput (feature_histogram.hpp:451)."""
    ret = -_threshold_l1(sg, l1) / (sh + l2)
    if mds > 0.0:
        ret = jnp.clip(ret, -mds, mds)
    return ret


def _leaf_gain_given_output(sg, sh, l1, l2, out):
    """reference GetLeafSplitGainGivenOutput (feature_histogram.hpp:503)."""
    reg = _threshold_l1(sg, l1)
    return -(2.0 * reg * out + (sh + l2) * out * out)


def _leaf_gain(sg, sh, l1, l2, mds):
    out = _leaf_output(sg, sh, l1, l2, mds)
    return _leaf_gain_given_output(sg, sh, l1, l2, out)


def _split_gains(lg, lh, rg, rh, l1, l2, mds, min_c, max_c, mono):
    """reference GetSplitGains (feature_histogram.hpp:461-473): clamped
    outputs, monotone veto -> gain 0."""
    lo = jnp.clip(_leaf_output(lg, lh, l1, l2, mds), min_c, max_c)
    ro = jnp.clip(_leaf_output(rg, rh, l1, l2, mds), min_c, max_c)
    gain = (_leaf_gain_given_output(lg, lh, l1, l2, lo)
            + _leaf_gain_given_output(rg, rh, l1, l2, ro))
    veto = ((mono > 0) & (lo > ro)) | ((mono < 0) & (lo < ro))
    return jnp.where(veto, 0.0, gain)


def _first_argmax(values, axis=-1):
    """argmax returning the first occurrence (ties -> lowest index)."""
    return jnp.argmax(values, axis=axis)


def _last_argmax(values, axis=-1):
    """argmax returning the last occurrence (ties -> highest index)."""
    b = values.shape[axis]
    rev = jnp.flip(values, axis=axis)
    return b - 1 - jnp.argmax(rev, axis=axis)


def make_split_finder(hyper: SplitHyper, feature_meta: Dict[str, np.ndarray],
                      max_bin: int):
    """Build the jitted split finder for a fixed dataset + config.

    feature_meta arrays (length F): num_bin, default_bin, missing_type
    (0 none / 1 zero / 2 nan), bin_type (0 numerical / 1 categorical),
    monotone, penalty.

    Returns fn(hist[F,B,3], sum_grad, sum_hess, num_data, min_constr,
    max_constr, counts=None) -> dict of per-feature arrays +
    'best_feature'. `counts` (i32 [F, B]), where given, are the bins'
    exact counts and take the place of the count channel: the left and
    right counts are then summed as i32 and exact at any row count, and
    the min_data tests compare the same integers. Without it the counts
    are the f32 channel's.
    """
    nb = jnp.asarray(feature_meta["num_bin"], jnp.int32)[:, None]     # [F,1]
    db = jnp.asarray(feature_meta["default_bin"], jnp.int32)[:, None]
    mt = jnp.asarray(feature_meta["missing_type"], jnp.int32)[:, None]
    bt = jnp.asarray(feature_meta["bin_type"], jnp.int32)[:, None]
    mono = jnp.asarray(feature_meta["monotone"], jnp.int32)
    penalty = jnp.asarray(feature_meta["penalty"], jnp.float32)
    F = int(nb.shape[0])
    has_cat = bool((feature_meta["bin_type"] == 1).any())
    h = hyper

    bins = jnp.arange(max_bin, dtype=jnp.int32)[None, :]              # [1,B]
    in_range = bins < nb

    # effective flags (reference feature_histogram.hpp:97-111)
    two_scan = (nb > 2) & (mt != 0)
    skip_def = (mt == 1) & two_scan
    use_na = (mt == 2) & two_scan
    is_cat = bt == 1

    min_data_f = float(h.min_data_in_leaf)
    min_hess = float(h.min_sum_hessian_in_leaf)

    def _numerical(hist, c, sum_grad, sum_hess, num_data_c, min_data_c,
                   min_c, max_c, min_gain_shift):
        # c, num_data_c, min_data_c: all f32, or (exact counts) all i32
        g = hist[..., 0]
        hs = hist[..., 1]

        # ---- dir = +1: accumulate from the left; missing/default -> right
        inc1 = in_range & ~(skip_def & (bins == db))
        pg = jnp.cumsum(jnp.where(inc1, g, 0.0), axis=1)
        ph = jnp.cumsum(jnp.where(inc1, hs, 0.0), axis=1)
        pc = jnp.cumsum(jnp.where(inc1, c, 0), axis=1)
        lg1, lh1, lc1 = pg, ph + K_EPSILON, pc
        rg1 = sum_grad - lg1
        rh1 = sum_hess - lh1          # sum_hess already carries +2*kEps
        rc1 = num_data_c - lc1
        valid1 = (two_scan & (bins <= nb - 2) & ~(skip_def & (bins == db))
                  & (lc1 >= min_data_c) & (rc1 >= min_data_c)
                  & (lh1 >= min_hess) & (rh1 >= min_hess))
        gain1 = _split_gains(lg1, lh1, rg1, rh1, h.lambda_l1, h.lambda_l2,
                             h.max_delta_step, min_c, max_c, mono[:, None])
        gain1 = jnp.where(valid1 & (gain1 > min_gain_shift), gain1, NEG_INF)

        # ---- dir = -1: accumulate from the right; missing/default -> left
        # NaN bin (last) excluded from the accumulation range; candidate
        # threshold default_bin-1 is skipped under missing-Zero
        inc2 = (in_range & ~(skip_def & (bins == db))
                & (bins <= nb - 1 - use_na.astype(jnp.int32)))
        pg2 = jnp.cumsum(jnp.where(inc2, g, 0.0), axis=1)
        ph2 = jnp.cumsum(jnp.where(inc2, hs, 0.0), axis=1)
        pc2 = jnp.cumsum(jnp.where(inc2, c, 0), axis=1)
        tg2, th2, tc2 = pg2[:, -1:], ph2[:, -1:], pc2[:, -1:]
        rg2 = tg2 - pg2
        rh2 = (th2 - ph2) + K_EPSILON
        rc2 = tc2 - pc2
        lg2 = sum_grad - rg2
        lh2 = sum_hess - rh2
        lc2 = num_data_c - rc2
        valid2 = ((bins <= nb - 2 - use_na.astype(jnp.int32))
                  & ~(skip_def & (bins + 1 == db))
                  & (rc2 >= min_data_c) & (lc2 >= min_data_c)
                  & (rh2 >= min_hess) & (lh2 >= min_hess))
        gain2 = _split_gains(lg2, lh2, rg2, rh2, h.lambda_l1, h.lambda_l2,
                             h.max_delta_step, min_c, max_c, mono[:, None])
        gain2 = jnp.where(valid2 & (gain2 > min_gain_shift), gain2, NEG_INF)

        # ---- per-direction winners with the reference tie-break order
        t1 = _first_argmax(gain1, axis=1)       # dir=+1 scans low->high
        t2 = _last_argmax(gain2, axis=1)        # dir=-1 scans high->low
        g1b = jnp.take_along_axis(gain1, t1[:, None], 1)[:, 0]
        g2b = jnp.take_along_axis(gain2, t2[:, None], 1)[:, 0]
        use1 = g1b > g2b                        # dir=-1 first, strict >
        thr = jnp.where(use1, t1, t2).astype(jnp.int32)
        best_gain = jnp.where(use1, g1b, g2b)
        default_left = ~use1
        # NaN-with-2-bins direction fix (feature_histogram.hpp:108-110)
        default_left = jnp.where((nb[:, 0] <= 2) & (mt[:, 0] == 2),
                                 False, default_left)

        def pick(arr1, arr2, t1=t1, t2=t2, use1=use1):
            a1 = jnp.take_along_axis(arr1, t1[:, None], 1)[:, 0]
            a2 = jnp.take_along_axis(arr2, t2[:, None], 1)[:, 0]
            return jnp.where(use1, a1, a2)

        lg = pick(lg1, lg2)
        lh = pick(lh1, lh2)
        lc = pick(lc1, lc2)
        lo = jnp.clip(_leaf_output(lg, lh, h.lambda_l1, h.lambda_l2,
                                   h.max_delta_step), min_c, max_c)
        ro = jnp.clip(_leaf_output(sum_grad - lg, sum_hess - lh, h.lambda_l1,
                                   h.lambda_l2, h.max_delta_step),
                      min_c, max_c)
        return dict(gain=best_gain, threshold=thr, default_left=default_left,
                    left_g=lg, left_h=lh, left_c=lc,
                    left_output=lo, right_output=ro)

    def _categorical(hist, c, sum_grad, sum_hess, num_data_c, min_data_c,
                     min_c, max_c, min_gain_shift):
        """One-hot and CTR-sorted categorical splits
        (feature_histogram.hpp:118-240); counts as in `_numerical`."""
        g = hist[..., 0]
        hs = hist[..., 1]
        # used_bin = num_bin - 1 + (missing == none)  (:129-130)
        used_bin = nb - 1 + (mt == 0).astype(jnp.int32)
        # the device-side category bitset spans 8 u32 words = 256 bins
        # (mirroring the reference GPU path's <=256-bins-per-group
        # constraint, dataset.cpp:78); bins beyond it — categories rarer
        # than the 256 most frequent — are not split candidates, keeping
        # the chosen-left stats consistent with the partition routing
        cand = (bins < used_bin) & (bins < CAT_BITSET_BINS)

        # ---- one-hot: left = single bin t (:138-169); uses plain lambda_l2
        lh_oh = hs + K_EPSILON
        rg_oh = sum_grad - g
        rh_oh = sum_hess - hs - K_EPSILON
        rc_oh = num_data_c - c
        valid_oh = (cand & (c >= min_data_c) & (hs >= min_hess)
                    & (rc_oh >= min_data_c) & (rh_oh >= min_hess))
        # gain computed as (other, t) but symmetric without monotone
        gain_oh = _split_gains(rg_oh, rh_oh, g, lh_oh, h.lambda_l1,
                               h.lambda_l2, h.max_delta_step, min_c, max_c, 0)
        gain_oh = jnp.where(valid_oh & (gain_oh > min_gain_shift),
                            gain_oh, NEG_INF)
        t_oh = _first_argmax(gain_oh, axis=1)
        gain_oh_best = jnp.take_along_axis(gain_oh, t_oh[:, None], 1)[:, 0]
        lg_oh_best = jnp.take_along_axis(g, t_oh[:, None], 1)[:, 0]
        lh_oh_best = jnp.take_along_axis(lh_oh, t_oh[:, None], 1)[:, 0]
        lc_oh_best = jnp.take_along_axis(c, t_oh[:, None], 1)[:, 0]

        # ---- CTR-sorted many-vs-many (:170-240); l2 += cat_l2
        l2c = h.lambda_l2_cat
        elig = cand & (c >= h.cat_smooth)
        ctr = g / (hs + h.cat_smooth)
        sort_key = jnp.where(elig, ctr, jnp.inf)
        order = jnp.argsort(sort_key, axis=1)                  # [F,B]
        sg = jnp.take_along_axis(g, order, 1)
        sh_ = jnp.take_along_axis(hs, order, 1)
        sc = jnp.take_along_axis(c, order, 1)
        n_elig = jnp.sum(elig, axis=1).astype(jnp.int32)       # [F]
        max_num_cat = jnp.minimum(h.max_cat_threshold,
                                  (n_elig + 1) // 2)           # [F]
        pos = jnp.arange(max_bin, dtype=jnp.int32)[None, :]

        def scan_dir(fwd: bool):
            # forward: positions 0..; backward: from n_elig-1 downward
            if fwd:
                gg, hh, cc = sg, sh_, sc
                in_elig = pos < n_elig[:, None]
            else:
                # reverse the eligible prefix per feature: position i reads
                # sorted index n_elig-1-i
                ridx = jnp.clip(n_elig[:, None] - 1 - pos, 0, max_bin - 1)
                gg = jnp.take_along_axis(sg, ridx, 1)
                hh = jnp.take_along_axis(sh_, ridx, 1)
                cc = jnp.take_along_axis(sc, ridx, 1)
                in_elig = pos < n_elig[:, None]
            step_ok = in_elig & (pos < max_num_cat[:, None])
            lg = jnp.cumsum(jnp.where(step_ok, gg, 0.0), axis=1)
            lh = jnp.cumsum(jnp.where(step_ok, hh, 0.0), axis=1) + K_EPSILON
            lc = jnp.cumsum(jnp.where(step_ok, cc, 0), axis=1)
            rg = sum_grad - lg
            rh = sum_hess - lh
            rc = num_data_c - lc
            left_ok = (lc >= min_data_c) & (lh >= min_hess)
            right_ok = (rc >= min_data_c) & (rc >= h.min_data_per_group) \
                & (rh >= min_hess)

            # sequential min_data_per_group grouping (:198-222): a candidate
            # is evaluated only when the count accumulated since the last
            # evaluated candidate reaches min_data_per_group
            def body(cnt_group, xs):
                cc_i, lok, rok, sok = xs
                cnt_group = cnt_group + jnp.where(sok, cc_i, 0)
                evalable = lok & rok & sok
                do_eval = evalable & (cnt_group >= h.min_data_per_group)
                cnt_group = jnp.where(do_eval, 0, cnt_group)
                return cnt_group, do_eval

            xs = (cc.T, left_ok.T, right_ok.T, step_ok.T)
            _, do_eval_T = lax.scan(body, jnp.zeros((F,), c.dtype), xs)
            do_eval = do_eval_T.T
            gain = _split_gains(lg, lh, rg, rh, h.lambda_l1, l2c,
                                h.max_delta_step, min_c, max_c, 0)
            gain = jnp.where(do_eval & (gain > min_gain_shift), gain, NEG_INF)
            t = _first_argmax(gain, axis=1)
            gb = jnp.take_along_axis(gain, t[:, None], 1)[:, 0]
            lgb = jnp.take_along_axis(lg, t[:, None], 1)[:, 0]
            lhb = jnp.take_along_axis(lh, t[:, None], 1)[:, 0]
            lcb = jnp.take_along_axis(lc, t[:, None], 1)[:, 0]
            return dict(gain=gb, t=t, lg=lgb, lh=lhb, lc=lcb)

        fw = scan_dir(True)
        bw = scan_dir(False)
        use_bw = bw["gain"] > fw["gain"]   # forward evaluated first (:188-195)
        gain_sorted = jnp.where(use_bw, bw["gain"], fw["gain"])
        t_sorted = jnp.where(use_bw, bw["t"], fw["t"]).astype(jnp.int32)

        use_onehot = nb[:, 0] <= h.max_cat_to_onehot
        gain_cat = jnp.where(use_onehot, gain_oh_best, gain_sorted)
        lg = jnp.where(use_onehot, lg_oh_best,
                       jnp.where(use_bw, bw["lg"], fw["lg"]))
        lh = jnp.where(use_onehot, lh_oh_best,
                       jnp.where(use_bw, bw["lh"], fw["lh"]))
        lc = jnp.where(use_onehot, lc_oh_best,
                       jnp.where(use_bw, bw["lc"], fw["lc"]))
        # device-side bitset over BINS for the chosen threshold set — used by
        # the fused on-device learner's partition step (8 u32 words = 256
        # bins). Bins are unique, so a sum equals the bitwise OR.
        k_sel = (jnp.where(use_onehot, 1, t_sorted + 1))[:, None]  # [F,1]
        sorted_sel = jnp.where(
            use_bw[:, None],
            (pos >= (n_elig[:, None] - k_sel)) & (pos < n_elig[:, None]),
            pos < k_sel)
        sel_bins = jnp.where(use_onehot[:, None],
                             jnp.where(pos == t_oh[:, None], bins, -1),
                             jnp.where(sorted_sel, order, -1))  # [F,B]
        word_oh = (sel_bins >> 5)[:, :, None] == jnp.arange(8)[None, None, :]
        bitval = jnp.where(sel_bins >= 0,
                           jnp.uint32(1) << (sel_bins & 31).astype(jnp.uint32),
                           jnp.uint32(0))
        cat_bitset = jnp.sum(
            jnp.where(word_oh, bitval[:, :, None], jnp.uint32(0)),
            axis=1, dtype=jnp.uint32)  # [F, 8]
        # outputs use plain lambda_l2 for one-hot, lambda_l2 + cat_l2 for the
        # sorted path (feature_histogram.hpp:133,178,243-252)
        l2_eff = jnp.where(use_onehot, h.lambda_l2, l2c)
        lo = jnp.clip(_leaf_output(lg, lh, h.lambda_l1, l2_eff,
                                   h.max_delta_step), min_c, max_c)
        ro = jnp.clip(_leaf_output(sum_grad - lg, sum_hess - lh, h.lambda_l1,
                                   l2_eff, h.max_delta_step), min_c, max_c)
        return dict(
            gain=gain_cat,
            threshold=jnp.where(use_onehot, t_oh.astype(jnp.int32), t_sorted),
            default_left=jnp.zeros((F,), bool),
            left_g=lg, left_h=lh, left_c=lc,
            left_output=lo, right_output=ro,
            cat_dir=jnp.where(use_bw, -1, 1).astype(jnp.int32),
            sort_order=order,
            n_elig=n_elig,
            use_onehot=use_onehot,
            cat_bitset=cat_bitset,
        )

    @jax.jit
    def find_best_splits(hist, sum_grad, sum_hess, num_data, min_constraint,
                         max_constraint, counts=None):
        sum_grad = sum_grad.astype(jnp.float32)
        sum_hess = sum_hess.astype(jnp.float32) + 2 * K_EPSILON
        if counts is None:
            c = hist[..., 2]
            num_data_c = num_data.astype(jnp.float32)
            min_data_c = min_data_f
        else:
            c = counts.astype(jnp.int32)
            num_data_c = num_data.astype(jnp.int32)
            min_data_c = int(h.min_data_in_leaf)
        min_c = min_constraint.astype(jnp.float32)
        max_c = max_constraint.astype(jnp.float32)
        # gain_shift from the epsilon-adjusted parent hessian and plain L2
        # (feature_histogram.hpp:94-96); categorical gain_shift is identical
        # (:126-128)
        gain_shift = _leaf_gain(sum_grad, sum_hess, h.lambda_l1, h.lambda_l2,
                                h.max_delta_step)
        min_gain_shift = gain_shift + h.min_gain_to_split

        num = _numerical(hist, c, sum_grad, sum_hess, num_data_c,
                         min_data_c, min_c, max_c, min_gain_shift)
        if has_cat:
            cat = _categorical(hist, c, sum_grad, sum_hess, num_data_c,
                               min_data_c, min_c, max_c, min_gain_shift)
            sel = lambda k: jnp.where(is_cat[:, 0], cat[k], num[k])
        else:
            cat = None
            sel = lambda k: num[k]

        gain = sel("gain")
        out = {
            "gain": jnp.where(jnp.isfinite(gain),
                              (gain - min_gain_shift) * penalty, NEG_INF),
            "threshold": sel("threshold"),
            "default_left": sel("default_left"),
            "left_g": sel("left_g"),
            "left_h": sel("left_h") - K_EPSILON,
            "left_c": sel("left_c").astype(jnp.int32),
        }
        out["right_g"] = sum_grad - sel("left_g")
        out["right_h"] = sum_hess - sel("left_h") - K_EPSILON
        out["right_c"] = num_data - out["left_c"]
        out["left_output"] = sel("left_output")
        out["right_output"] = sel("right_output")
        if cat is not None:
            out["cat_dir"] = cat["cat_dir"]
            out["sort_order"] = cat["sort_order"]
            out["n_elig"] = cat["n_elig"]
            out["use_onehot"] = cat["use_onehot"]
            out["cat_bitset"] = cat["cat_bitset"]
        else:
            out["cat_bitset"] = jnp.zeros((F, 8), jnp.uint32)
        out["is_cat"] = is_cat[:, 0]
        out["best_feature"] = jnp.argmax(out["gain"]).astype(jnp.int32)
        return out

    return find_best_splits
