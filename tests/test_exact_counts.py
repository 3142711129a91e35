"""Exact integer counts in the aligned engine's histograms (CPU: Pallas
interpret mode).

Each chunk's MXU count is an integer of at most C in f32, exact; a sum
over chunks rounds once it passes 2^24. The kernels' stores therefore
fold the multiples of COUNT_UNIT out of the count's f32 hi half into its
lo half (`ops/aligned.py:_fold_counts`), `_hist_store_finalize` joins
the halves into an i32 whose bits the count channel holds, and the
finder sums those as i32 (`make_split_finder`'s `counts`). The layout
then takes its left counts from the finder wherever the histogram counts
every row the rounds place: held here against `count_pass`, which
counts the rows themselves, on the records of a plain, a parked bagged
and a GOSS engine.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.ops import aligned
from lightgbm_tpu.ops.aligned import (R_DL, R_MT, R_SHIFT, _bpw_for_bits,
                                      count_pass, hist_counts, pack_records,
                                      pack_route2, slot_hist_pass)
from lightgbm_tpu.ops.split import SplitHyper, make_split_finder

BIG = 1 << 24


# ---- the finder ----------------------------------------------------------
def _finder_case(kind, seed=0):
    """(hist f32 [F, B, 3], exact counts int64 [F, B], meta, total) with
    every feature's bins summing to one total past 2^24, and bins and
    prefix sums that f32 cannot hold."""
    rng = np.random.default_rng(seed)
    F, B = 3, 16
    total = 3 * BIG + 12345
    counts = np.zeros((F, B), np.int64)
    num_bin = np.array([16, 4, 11] if kind == "categorical"
                       else [16, 12, 9], np.int32)
    for f in range(F):
        nb = num_bin[f]
        share = rng.dirichlet(np.ones(nb))
        c = np.floor(share * (total - BIG)).astype(np.int64) | 1
        c[rng.integers(nb)] += BIG + 6          # one bin past 2^24
        c[0] += total - c.sum()
        counts[f, :nb] = c
    hist = np.zeros((F, B, 3), np.float32)
    hist[..., 2] = counts
    hist[..., 0] = rng.standard_normal((F, B)) * np.sqrt(counts) * 1e-2
    hist[..., 1] = counts * 0.25
    meta = {"num_bin": num_bin,
            "default_bin": np.array([0, 2, 5], np.int32),
            "missing_type": (np.zeros(F, np.int32) if kind == "categorical"
                             else np.array([0, 1, 2], np.int32)),
            "bin_type": np.full(F, kind == "categorical", np.int32),
            "monotone": np.zeros(F, np.int32),
            "penalty": np.ones(F, np.float32)}
    return hist, counts, meta, total


def _left_bins(out, meta, f):
    """[B] bools: the bins of feature f the chosen split sends left."""
    nb = int(meta["num_bin"][f])
    b = np.arange(16)
    if meta["bin_type"][f]:
        words = np.asarray(out["cat_bitset"][f]).astype(np.int64)
        return ((words[b >> 5] >> (b & 31)) & 1).astype(bool) & (b < nb)
    thr = int(out["threshold"][f])
    dl = bool(out["default_left"][f])
    mt, db = int(meta["missing_type"][f]), int(meta["default_bin"][f])
    is_def = ((mt == 1) & (b == db)) | ((mt == 2) & (b == nb - 1))
    return np.where(is_def, dl, b <= thr) & (b < nb)


@pytest.mark.parametrize("kind", ("numerical", "categorical"))
def test_finder_left_count_is_exact_past_2_24(kind):
    """With the exact counts the finder's left and right counts are the
    integer sums of the bins the split sends each way; the f32 channel
    misses some of them by a few rows, and picks the same splits (the
    gains read g and h only)."""
    hist, counts, meta, total = _finder_case(kind)
    cfg = Config.from_params({"min_data_in_leaf": 20, "lambda_l2": 0.1,
                              "max_cat_to_onehot": 4, "cat_smooth": 1.0})
    finder = make_split_finder(SplitHyper.from_config(cfg), meta, 16)
    args = (jnp.asarray(hist), jnp.float32(hist[0, :, 0].sum()),
            jnp.float32(hist[0, :, 1].sum()), jnp.int32(total),
            jnp.float32(-np.inf), jnp.float32(np.inf))
    exact = jax.tree.map(np.asarray, finder(
        *args, counts=jnp.asarray(counts.astype(np.int32))))
    fuzzy = jax.tree.map(np.asarray, finder(*args))
    assert exact["left_c"].dtype == np.int32
    missed = 0
    for f in range(3):
        assert np.isfinite(exact["gain"][f])
        want = int(counts[f][_left_bins(exact, meta, f)].sum())
        assert int(exact["left_c"][f]) == want
        assert int(exact["right_c"][f]) == total - want
        assert int(exact["threshold"][f]) == int(fuzzy["threshold"][f])
        missed += int(fuzzy["left_c"][f]) != want
    assert missed


# ---- the fold ------------------------------------------------------------
MODES = {"subbin": (256, True), "nibble": (256, False), "group": (64, False)}


def _fold(store, mode):
    """`_fold_counts` over a whole store, run as a kernel."""
    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]
        aligned._fold_counts(o_ref, mode)
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(store.shape, store.dtype),
        interpret=True)(store)


def _chunk_tile(mode, b_pad, subbin, F, C, rng):
    """One chunk's store tile, accumulated as the kernels do, and its
    exact per-bin counts [F, b_pad]: three in four of the rows in one
    bin of each feature, an odd number."""
    group = 8 if b_pad <= 64 else 4
    shape = aligned._hist_store_shape(0, F, b_pad, group, subbin)[1:]
    bins = rng.integers(0, b_pad, (F, C))
    bins[:, :3 * C // 4 + 1] = 5
    tile = {"t": jnp.zeros(shape, jnp.float32)}

    def accum(idx, contrib):
        tile["t"] = tile["t"].at[idx].add(contrib)

    pay = jnp.stack([jnp.asarray(rng.standard_normal(C), jnp.float32),
                     jnp.asarray(rng.random(C), jnp.float32),
                     jnp.ones(C, jnp.float32)])
    aligned._hist_accum(aligned._hi_lo6(pay), lambda f: jnp.asarray(bins[f]),
                        accum, F, b_pad, group, C, subbin)
    want = np.stack([np.bincount(bins[f], minlength=b_pad)
                     for f in range(F)])
    return tile["t"], want, group


@pytest.mark.parametrize("mode", sorted(MODES))
def test_fold_keeps_counts_exact_past_2_24(mode):
    """A store slot that takes 3 x `count_fold` + 5 chunks of C = 2,048
    rows, folded after every `count_fold` of them as the kernels fold,
    ends with each bin's exact count (one bin past 2^24, odd); the same
    sums without the folds round. The fold leaves g and h as they
    were."""
    b_pad, subbin = MODES[mode]
    assert aligned._hist_mode(b_pad, subbin) == mode
    F, C = 3, 2048
    rng = np.random.default_rng(len(mode))
    tile, per_chunk, group = _chunk_tile(mode, b_pad, subbin, F, C, rng)
    every = aligned.count_fold(C, 1 << 20)
    assert every == (BIG - aligned.COUNT_UNIT) // C == 8190
    steps = [every] * 3 + [5]
    folded = jnp.zeros((2,) + tile.shape, jnp.float32)
    plain = folded
    for k in steps:
        # k chunks of the same counts: exact in f32 while under 2^24
        add = jnp.stack([tile * float(k), jnp.zeros_like(tile)])
        folded = _fold(folded + add, mode)
        plain = plain + add
    want = per_chunk * sum(steps)
    past = want[want > BIG]
    assert past.size and np.any(past % 2)
    fin = aligned._hist_store_finalize(folded, 1, F, b_pad, group, subbin)
    np.testing.assert_array_equal(np.asarray(hist_counts(fin))[0], want)
    ref = aligned._hist_store_finalize(plain, 1, F, b_pad, group, subbin)
    assert np.any(np.asarray(hist_counts(ref))[0] != want)
    np.testing.assert_array_equal(np.asarray(fin)[..., :2],
                                  np.asarray(ref)[..., :2])


@pytest.fixture
def small_unit():
    """COUNT_UNIT = 8, traced afresh (it is read at trace time): a fold
    then moves counts at toy size."""
    keep = aligned.COUNT_UNIT
    aligned.COUNT_UNIT = 8
    jax.clear_caches()
    yield
    aligned.COUNT_UNIT = keep
    jax.clear_caches()


@pytest.mark.parametrize("fold_every", (1, 3))
@pytest.mark.parametrize("mode", sorted(MODES))
def test_slot_hist_pass_folds_every_few_chunks(mode, fold_every,
                                               small_unit):
    """The root pass folds its whole store every `fold_every` grid steps:
    the counts are numpy's and g and h the unfolded pass's, bit for
    bit."""
    b_pad, subbin = MODES[mode]
    max_bin = 255 if b_pad > 64 else 63
    rng = np.random.default_rng(fold_every)
    C, n, F = 256, 1900, 5
    bins = rng.integers(0, max_bin, (n, F)).astype(np.uint8)
    bins[: n // 2, 1] = 7
    rec, wcnt, W, cnts, bits = pack_records(
        bins, rng.integers(0, 2, n).astype(np.float32), None, C,
        max_bin=max_bin)
    rec = np.array(rec)
    lanes, _ = aligned.lane_layout(wcnt)
    live = np.arange(C)[None, :] < cnts[:, None]
    for name in ("grad", "hess"):
        v = rng.standard_normal(rec[:, 0, :].shape).astype(np.float32)
        rec[:, lanes[name], :] = np.where(live, v.view(np.int32), 0)
    nc = rec.shape[0]
    group = 8 if b_pad <= 64 else 4

    def run(k):
        return np.asarray(slot_hist_pass(
            jnp.asarray(rec), jnp.zeros(nc, jnp.int32), jnp.asarray(cnts),
            1, F, b_pad, C, group, wcnt, bits=bits, interpret=True,
            subbin=subbin, fold_every=k))

    hist, plain = run(fold_every), run(0)
    np.testing.assert_array_equal(hist, plain)
    want = np.stack([np.bincount(bins[:, f], minlength=b_pad)[:b_pad]
                     for f in range(F)])
    np.testing.assert_array_equal(np.asarray(hist_counts(hist))[0], want)


# ---- the layout's left counts -----------------------------------------
BASE = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
        "learning_rate": 0.5, "min_data_in_leaf": 20, "verbosity": -1,
        "metric": "none", "tpu_chunk": 256, "tpu_grow_mode": "aligned",
        "tpu_aligned_interpret": True}
ENGINES = {
    "plain": {},
    "bagged": {"bagging_fraction": 0.7, "bagging_freq": 3,
               "bagging_seed": 11},
    "goss": {"boosting": "goss", "top_rate": 0.2, "other_rate": 0.1,
             "bagging_seed": 5},
}


def _engine(kind):
    rng = np.random.default_rng(4)
    X = rng.standard_normal((3000, 6)).astype(np.float32)
    X[rng.random(3000) < 0.1, 2] = np.nan
    y = ((X[:, 0] + X[:, 1] * np.nan_to_num(X[:, 2])
          + 0.3 * rng.standard_normal(3000)) > 0).astype(np.float32)
    params = dict(BASE, **ENGINES[kind])
    ds = lgb.Dataset(X, label=y, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(3):      # GOSS samples from iteration 1 / lr = 2
        bst.update()
    bst.eval_train()
    return bst._gbdt._aligned_eng_ref


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_histogram_left_counts_are_count_pass_counts(kind):
    """A root round over the records as the engine holds them: the
    finder's left count of every feature's best split, summed from the
    histogram's exact counts, is what `count_pass` counts of the same
    rows under the same route words. Under a bag the rounds run over
    the in-bag chunks only, so the histogram, which counts in-bag rows,
    counts every row they place."""
    eng = _engine(kind)
    assert eng.count_why is None and not eng.count_pass
    lr, C, NC = eng.learner, eng.C, eng.NC
    assert not eng._park_stale
    pb = int(eng.park_begin)
    cnts = np.asarray(eng.cnts)
    live = np.arange(NC) < pb
    rows = int(cnts[live].sum())
    if kind == "plain":
        assert pb == NC and rows == lr.n
    else:
        assert eng.parks and 0 < rows < lr.n
    BH, G = lr.max_bin_global, eng.ncols
    group = 8 if BH <= 64 else 4
    subbin = aligned.hist_layout(eng.cfg, G, BH, 1)[0]
    bag_lane = (-2 if eng.compact else eng.lanes["bag"]) \
        if eng.bagged else -1
    hist = slot_hist_pass(
        eng.rec, jnp.asarray(np.where(live, 0, 1), jnp.int32),
        jnp.asarray(np.where(live, cnts, 0), jnp.int32), 1, G, BH, C,
        group, eng.wcnt, bag_lane=bag_lane, bits=eng.bits,
        grad_fn=eng._pgrad if eng.compact else None, gh_off=eng.gh_off,
        interpret=True, subbin=subbin)[0]
    cnt = hist_counts(hist)
    assert int(jnp.sum(cnt[0])) == rows
    out = jax.tree.map(np.asarray, lr.finder(
        hist, jnp.sum(hist[0, :, 0]), jnp.sum(hist[0, :, 1]),
        jnp.int32(rows), jnp.float32(-np.inf), jnp.float32(np.inf),
        counts=cnt))
    meta = {k: np.asarray(v) for k, v in lr.meta.items()}
    bpw = _bpw_for_bits(eng.bits)
    iota = np.arange(NC)
    chunk_meta = jnp.asarray(
        np.where(live, cnts, 0) | ((iota == 0) << 20)
        | ((iota == pb - 1) << 21), jnp.int32)
    ks = jnp.asarray(np.where(live, 0, 1), jnp.int32)
    checked = 0
    for f in range(lr.num_features):
        if not np.isfinite(out["gain"][f]):
            continue
        r1 = (int(out["threshold"][f]) | ((f % bpw) * eng.bits) << R_SHIFT
              | int(out["default_left"][f]) << R_DL
              | int(meta["missing_type"][f]) << R_MT)
        r2 = int(pack_route2(int(meta["default_bin"][f]),
                             int(meta["num_bin"][f])))
        left = count_pass(
            eng.rec, eng.rec, jnp.int32(0), jnp.full(NC, r1, jnp.int32),
            jnp.full(NC, r2, jnp.int32), chunk_meta,
            jnp.full(NC, f // bpw, jnp.int32), ks,
            jnp.zeros(16, jnp.int32), 1, C, bits=eng.bits,
            interpret=True)
        assert int(left[0]) == int(out["left_c"][f]), f
        checked += 1
    assert checked >= 4
