"""The device pack (`ops/aligned.py:pack_device`): the records built on
the device from the uint8 bins, block by block, bit for bit what the
numpy pack gave. `_oracle` is that numpy pack as it was; every layout
branch of it is a case here, and the engine's records (its own rows, a
validation set's, a two-device mesh's) are held to it too.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import compile_cache
from lightgbm_tpu.obs import trace as obs_trace
from lightgbm_tpu.ops import aligned
from lightgbm_tpu.ops.aligned import (META_BAG, META_LABEL, META_LABEL_MASK,
                                      META_RID_MASK, _bpw_for_bits,
                                      lane_layout, pack_device, pack_records)


def _oracle(bins, label, weight, chunk, with_bag=False, compact=False,
            num_class=1, with_prob=False, max_bin=0, ext=False,
            rid_base=0, index=None, cols=0):
    """[N, F] uint8 bins -> [NC, W, C] int32 records in numpy."""
    n, f = bins.shape
    f = max(f, cols)
    bmax = max(int(bins.max(initial=0)), max_bin - 1)
    if bmax < 16:
        bits = 4
    elif bmax < 64:
        bits = 6
    else:
        bits = 8
    bpw = _bpw_for_bits(bits)
    wcnt = (f + bpw - 1) // bpw
    lanes, w_pad = lane_layout(wcnt, with_bag, compact, num_class,
                               with_prob, ext=ext)
    nc = (n + chunk - 1) // chunk
    n_pad = nc * chunk
    padded = np.zeros((n_pad, wcnt * bpw), np.uint8)
    padded[:n, :bins.shape[1]] = bins
    words = padded.reshape(n_pad, wcnt, bpw).astype(np.uint32)
    packed = np.zeros((n_pad, wcnt), np.uint32)
    for i in range(bpw):
        packed |= words[:, :, i] << (bits * i)
    rec = np.zeros((n_pad, w_pad), np.int32)
    rec[:, :wcnt] = packed.astype(np.int64).astype(np.int32)
    if ext:
        if index is None:
            rec[:, lanes["rid"]] = rid_base + np.arange(n_pad,
                                                        dtype=np.int32)
        else:
            rec[:n, lanes["rid"]] = index[0]
            rec[n:, lanes["rid"]] = index[1]
        if with_bag:
            rec[:n, lanes["bag"]] = np.ones(n, np.float32).view(np.int32)
    elif compact:
        if num_class > 1:
            lab = np.asarray(label).astype(np.int64) & META_LABEL_MASK
        else:
            lab = (np.asarray(label) > 0).astype(np.int64)
        meta = (rid_base + np.arange(n_pad, dtype=np.int64)) \
            & META_RID_MASK
        meta[:n] |= lab << META_LABEL
        meta[:n] |= 1 << META_BAG     # all rows in-bag initially
        rec[:, lanes["meta"]] = meta.astype(np.int64).astype(np.uint32) \
            .view(np.int32)
    else:
        rec[:n, lanes["label"]] = np.asarray(label, np.float32) \
            .view(np.int32)
        rec[:, lanes["rid"]] = rid_base + np.arange(n_pad, dtype=np.int32)
        wv = np.ones(n, np.float32) if weight is None \
            else np.asarray(weight, np.float32)
        rec[:n, lanes["weight"]] = wv.view(np.int32)
        if with_bag:
            rec[:n, lanes["bag"]] = np.ones(n, np.float32).view(np.int32)
    rec3 = np.ascontiguousarray(
        rec.reshape(nc, chunk, w_pad).transpose(0, 2, 1))
    cnts = np.full(nc, chunk, np.int32)
    if nc:
        cnts[-1] = n - (nc - 1) * chunk
    return rec3, wcnt, w_pad, cnts, bits, lanes


def _with_scores(rec, lanes, scores, chunk):
    """The oracle's records with row-order `scores` [K, n] in the score
    lanes (what the engine filled on the host after the pack)."""
    rec = rec.copy()
    for k, sc in enumerate(np.asarray(scores, np.float32)):
        flat = np.zeros(rec.shape[0] * chunk, np.float32)
        flat[:sc.size] = sc
        rec[:, lanes["score"] + k, :] = flat.reshape(-1, chunk).view(np.int32)
    return rec


C = 128
# case -> (rows, columns, max_bin, pack_records' keywords)
CASES = {
    "standard-8": (1000, 7, 255, {}),
    "standard-8-bag-weight": (1000, 7, 255, {"with_bag": True,
                                             "weight": True}),
    "standard-6": (777, 9, 63, {}),
    "standard-6-bag": (777, 9, 63, {"with_bag": True}),
    "compact-4": (640, 11, 15, {"compact": True}),
    "compact-6": (641, 11, 63, {"compact": True}),
    "compact-8": (300, 5, 255, {"compact": True}),
    "compact-multiclass-prob": (500, 6, 63, {"compact": True,
                                             "num_class": 3,
                                             "with_prob": True}),
    "compact-multiclass-score": (500, 6, 255, {"compact": True,
                                               "num_class": 4}),
    "ext": (900, 8, 255, {"ext": True}),
    "ext-index": (900, 8, 255, {"ext": True, "index": True}),
    "ext-index-bag": (900, 8, 63, {"ext": True, "index": True,
                                   "with_bag": True}),
    "rid-base": (700, 6, 255, {"rid_base": 5000}),
    "rid-base-compact": (700, 6, 63, {"compact": True, "rid_base": 123}),
    "ragged-last-chunk": (C * 5 + 1, 6, 255, {}),
    "zero-rows": (0, 6, 255, {}),
    "data-max-sets-width": (400, 6, 0, {}),
}


def _inputs(case, seed=0):
    n, f, max_bin, kw = CASES[case]
    kw = dict(kw)
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, max_bin or 40, (n, f)).astype(np.uint8)
    k = kw.get("num_class", 1)
    label = (rng.integers(0, k, n).astype(np.float32) if k > 1
             else rng.integers(0, 2, n).astype(np.float32)
             if kw.get("compact") else rng.standard_normal(n)
             .astype(np.float32))
    weight = (rng.random(n).astype(np.float32) + 0.5
              if kw.pop("weight", False) else None)
    if kw.pop("index", False):
        kw["index"] = (rng.permutation(n + 37)[:n].astype(np.int32), n + 37)
    return bins, label, weight, max_bin, kw


@pytest.mark.parametrize("blocks", ["one", "several"])
@pytest.mark.parametrize("case", list(CASES))
def test_device_pack_is_the_numpy_pack_bit_for_bit(case, blocks,
                                                   monkeypatch):
    """Each layout branch of the numpy pack, at one block and at blocks
    of two chunks (the last part-filled where the chunks are odd)."""
    if blocks == "several":
        monkeypatch.setattr(aligned, "PACK_BLOCK_BYTES", 2 * C)
    bins, label, weight, max_bin, kw = _inputs(case)
    got = pack_records(bins, label, weight, C, max_bin=max_bin, **kw)
    want = _oracle(bins, label, weight, C, max_bin=max_bin, **kw)
    assert (got[1], got[2], got[4]) == (want[1], want[2], want[4])
    assert got[0].dtype == np.int32 and got[0].shape == want[0].shape
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[3], want[3])


def _mesh(shards):
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:shards]), ("data",))


@pytest.mark.parametrize("scores", ["host", "device"])
@pytest.mark.parametrize("shards", [1, 3])
def test_shards_scores_columns_and_fresh_chunks(shards, scores,
                                                monkeypatch):
    """What the engine asks beyond `pack_records`: rows split over shards
    (the last one short), each shard's chunks followed by
    fresh zero chunks, more word columns than bin columns (the
    feature-parallel pad), init scores in the score lanes, and a block
    size that leaves the last block part-filled."""
    monkeypatch.setattr(aligned, "PACK_BLOCK_BYTES", 3 * C * 10)
    n, f, K, bits = 2 * (4 * C + 17), 10, 2, 6
    rng = np.random.default_rng(1)
    bins = rng.integers(0, 60, (n, f)).astype(np.uint8)
    label = rng.integers(0, K, n).astype(np.float32)
    isc = rng.standard_normal((K, n)).astype(np.float32)
    per = -(-n // shards)
    nc_data = -(-per // C)
    nc = nc_data + 3
    rec, wcnt, w_pad, cnts, info = pack_device(
        bins, label, None, C, nc, bits=bits, cols=f + 3, compact=True,
        num_class=K, with_prob=True,
        scores=jnp.asarray(isc) if scores == "device" else isc,
        mesh=_mesh(shards) if shards > 1 else None,
        axis="data")
    rec = np.asarray(rec)
    assert info["blocks"] == -(-nc_data // 3)
    assert info["upload_bytes"] >= bins.nbytes
    for s in range(shards):
        lo, hi = min(n, s * per), min(n, s * per + per)
        want, wc, wp, wcn, wbits, lanes = _oracle(
            bins[lo:hi], label[lo:hi], None, C, compact=True, num_class=K,
            with_prob=True, max_bin=64, rid_base=lo, cols=f + 3)
        want = _with_scores(want, lanes, isc[:, lo:hi], C)
        assert (wc, wp, wbits) == (wcnt, w_pad, bits)
        mine = rec[s * nc:(s + 1) * nc]
        np.testing.assert_array_equal(mine[:len(want)], want)
        assert not mine[len(want):].any()
        np.testing.assert_array_equal(cnts[s * nc:s * nc + len(wcn)], wcn)
        assert not cnts[s * nc + len(wcn):(s + 1) * nc].any()


def test_empty_shard_of_a_mesh_packs_nothing():
    """Four rows over three shards of two: the last shard has no rows,
    and its chunks stay zero."""
    bins = np.arange(4 * 3, dtype=np.uint8).reshape(4, 3) + 1
    rec, _, _, cnts, info = pack_device(
        bins, np.ones(4), None, C, 2, bits=8, mesh=_mesh(3),
        axis="data")
    rec = np.asarray(rec)
    assert rec[0].any() and rec[2].any()
    assert not rec[1].any() and not rec[3:].any()
    assert list(cnts) == [2, 0, 2, 0, 0, 0] and info["blocks"] == 1


# ---------------------------------------------------------------------------
# the engine's records
# ---------------------------------------------------------------------------

ALIGNED = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
           "learning_rate": 0.3, "min_data_in_leaf": 10, "verbosity": -1,
           "metric": "none", "tpu_grow_mode": "aligned",
           "tpu_aligned_interpret": True, "tpu_chunk": C}


def _data(n, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] * X[:, 2]) > 0).astype(np.float32)
    return X, y


def _sparse(n, f=20, dense=4, seed=3):
    """Rows whose last f - dense features are one-hot: EFB bundles them."""
    rng = np.random.default_rng(seed)
    X = np.zeros((n, f), np.float32)
    X[:, :dense] = rng.standard_normal((n, dense))
    pick = rng.integers(0, f - dense + 1, n)
    on = pick < f - dense
    X[np.arange(n)[on], dense + pick[on]] = 1.0 + rng.random(on.sum())
    y = ((X[:, 0] + X[:, dense]) > 0.3).astype(np.float32)
    return X, y


def _engine(params, n=1000, valid=0):
    X, y = (_sparse if params.get("enable_bundle") else _data)(n)
    ds = lgb.Dataset(X, label=y, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    if valid:
        Xv, yv = _data(valid, seed=4)
        bst.add_valid(lgb.Dataset(Xv, label=yv, reference=ds,
                                  params=params).construct(), "v")
    g = bst._gbdt
    isc = g.train_score.score[0] + 0.25
    eng = g.learner.aligned_engine(g.objective, init_row_scores=isc)
    return g, eng, np.asarray(isc)[None]


@pytest.mark.parametrize("extra", [
    {}, {"tree_learner": "data", "num_machines": 2},
    {"enable_bundle": True}],
    ids=["one-device", "two-device-mesh", "bundled"])
def test_engine_records_are_the_oracles(extra):
    obs_trace.reset()
    g, eng, isc = _engine(dict(ALIGNED, **extra), n=1000)
    assert eng.nd == (2 if "num_machines" in extra else 1)
    lr = g.learner
    # bundled storage: [N, G] bins at the bundles' bin count
    assert lr.bundled == ("enable_bundle" in extra)
    bins = np.asarray(lr.ds.bins)
    if lr.bundled:
        assert bins.shape[1] < lr.num_features
    rec = np.asarray(eng.rec)
    for s in range(eng.nd):
        lo, hi = min(eng.n, s * eng.per_shard), min(eng.n, (s + 1)
                                                    * eng.per_shard)
        want, *_, lanes = _oracle(
            bins[lo:hi], g.objective._label_np[lo:hi], None, C,
            compact=eng.compact,
            max_bin=lr.hist_bins if lr.bundled else lr.max_bin_global,
            rid_base=lo)
        want = _with_scores(want, lanes, isc[:, lo:hi], C)
        mine = rec[s * eng.NC:(s + 1) * eng.NC]
        np.testing.assert_array_equal(mine[:len(want)], want)
        assert not mine[len(want):].any()
    if eng.nd > 1:      # each shard's chunks on its own device
        assert len({s.device for s in eng.rec.addressable_shards}) == 2
    (pack,) = obs_trace.seams("aligned.pack")
    assert pack["pack"] == "device"
    nc_data = -(-eng.per_shard // C)
    blk = aligned.pack_block_chunks(C, bins.shape[1], nc_data)
    assert pack["pack_blocks"] == -(-nc_data // blk)
    assert pack["upload_bytes"] >= bins.nbytes


def test_two_engines_of_one_shape_trace_the_pack_once(monkeypatch):
    monkeypatch.setattr(aligned, "PACK_BLOCK_BYTES", 2 * C * 6)
    traced = []
    real = aligned._pack_block
    monkeypatch.setattr(aligned, "_pack_block",
                        lambda *a, **k: traced.append(1) or real(*a, **k))
    compile_cache.clear_programs()
    obs_trace.reset()
    params = dict(ALIGNED, tpu_chunk=C)
    _engine(params, n=1111)
    _engine(params, n=1111)
    assert len(traced) == 1
    packs = obs_trace.seams("aligned.pack")
    assert len(packs) == 2
    # 1111 rows are 9 chunks of 128: four blocks of two and a fifth that
    # starts a chunk early
    assert [p["pack_blocks"] for p in packs] == [5, 5]
    assert all(p["pack"] == "device" for p in packs)


def test_pack_rows_is_the_oracles_block():
    """A validation set's block (`AlignedEngine.pack_rows`): its rows in
    records as the engine packs its own, their scores in the score lane,
    on the device."""
    g, eng, _ = _engine(dict(ALIGNED), n=900, valid=333)
    vs = g.valid_sets[0]
    scores = jnp.asarray(np.linspace(-1, 1, 333, dtype=np.float32)[None])
    rec, cnts, info = eng.pack_rows(vs.bins, scores)
    assert isinstance(rec, jax.Array) and isinstance(cnts, jax.Array)
    want, _, _, wcnts, _, lanes = _oracle(
        np.asarray(vs.bins), np.zeros(333), None, C, compact=eng.compact,
        max_bin=g.learner.max_bin_global)
    want = _with_scores(want, lanes, scores, C)
    np.testing.assert_array_equal(np.asarray(rec), want)
    np.testing.assert_array_equal(np.asarray(cnts), wcnts)
