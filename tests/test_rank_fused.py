"""Segment-fused lambdarank kernel (ops/pallas_rank.py): packing
invariants, fused-vs-bucketed gradient parity, NDCG parity on a real
train, interpret-mode smoke, and trace-once across boosters."""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import compile_cache
from lightgbm_tpu.config import Config
from lightgbm_tpu.ops import pallas_rank
from lightgbm_tpu.ops.objectives import LambdarankNDCG


def _boundaries(counts):
    return np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)


def _objective(qb, labels, mode, tile=None, lut_bins=None):
    cfg = Config()
    cfg.objective = "lambdarank"
    cfg.tpu_rank_fused = mode
    if tile is not None:
        cfg.tpu_rank_tile = tile
    if lut_bins is not None:
        cfg.tpu_rank_sigmoid_bins = lut_bins
    cfg.label_gain = [float((1 << i) - 1) for i in range(31)]
    obj = LambdarankNDCG(cfg)
    meta = type("M", (), {"query_boundaries": qb,
                          "label": np.asarray(labels, np.float64),
                          "weight": None})()
    obj.init(meta, int(qb[-1]))
    return obj


def _grads(obj, score):
    import jax.numpy as jnp
    g, h = obj.get_gradients(jnp.asarray(score, jnp.float32)[None, :])
    return np.asarray(g[0]), np.asarray(h[0])


# ---------------------------------------------------------------------------
# packing
# ---------------------------------------------------------------------------
def test_pack_invariants():
    rng = np.random.default_rng(3)
    counts = list(rng.integers(1, 400, 60)) + [1, 128, 129, 512, 513, 300]
    qb = _boundaries(counts)
    tile, sub = 512, pallas_rank.SUBTILE
    pack = pallas_rank.pack_query_tiles(qb, tile)
    counts = np.asarray(counts)
    assert pack.leftover.tolist() == (counts > tile).tolist()
    # every non-leftover doc appears exactly once, in order, within one
    # aligned subtile span no wider than the band
    seen = pack.doc_idx[pack.qid >= 0]
    expect = np.concatenate([
        np.arange(qb[q], qb[q + 1])
        for q in range(len(counts)) if not pack.leftover[q]])
    assert sorted(seen.tolist()) == sorted(expect.tolist())
    for t in range(pack.num_tiles):
        qid = pack.qid[t]
        for q in np.unique(qid[qid >= 0]):
            slots = np.nonzero(qid == q)[0]
            assert slots.tolist() == list(range(slots[0], slots[-1] + 1))
            c = len(slots)
            span = slots[-1] // sub - slots[0] // sub + 1
            assert span <= pack.band
            if c <= sub:        # short queries never straddle a subtile
                assert span == 1
            else:               # long ones start at a subtile boundary
                assert slots[0] % sub == 0
    # a query id never spans two tiles
    per_tile = [set(np.unique(t[t >= 0])) for t in pack.qid]
    for i in range(len(per_tile)):
        for j in range(i + 1, len(per_tile)):
            assert not (per_tile[i] & per_tile[j])


def test_pack_all_leftover():
    pack = pallas_rank.pack_query_tiles(_boundaries([600, 700]), 512)
    assert pack.num_tiles == 0 and pack.leftover.all()


# ---------------------------------------------------------------------------
# gradient parity (fused interpret kernel vs bucketed oracle)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed,counts", [
    (0, [1, 7, 40, 130, 200, 300, 520, 3, 64, 128, 129]),
    (1, [17] * 23),
    (2, [1, 1, 2, 257, 511, 512, 5]),
])
def test_fused_parity(seed, counts):
    rng = np.random.default_rng(seed)
    qb = _boundaries(counts)
    n = int(qb[-1])
    labels = rng.integers(0, 5, n)
    score = rng.normal(size=n).astype(np.float32)
    ob0 = _objective(qb, labels, "off")
    ob1 = _objective(qb, labels, "on")
    assert ob1.rank_fused_active
    assert ob1.rank_fused_fallback_queries == int(
        (np.diff(qb) > 512).sum())
    g0, h0 = _grads(ob0, score)
    g1, h1 = _grads(ob1, score)
    assert ob1.rank_fused_active, "kernel fell back at dispatch"
    # both paths share bf16 pair factors; residual diff is f32
    # accumulation order
    tol = 1e-4 * max(1.0, np.abs(g0).max())
    np.testing.assert_allclose(g1, g0, atol=tol, rtol=1e-5)
    np.testing.assert_allclose(h1, h0,
                               atol=1e-4 * max(1.0, np.abs(h0).max()),
                               rtol=1e-5)


def test_fused_parity_random_distribution():
    rng = np.random.default_rng(7)
    counts = rng.integers(1, 300, 40)
    qb = _boundaries(counts)
    n = int(qb[-1])
    labels = rng.integers(0, 4, n)
    score = (rng.normal(size=n) * 3).astype(np.float32)
    g0, h0 = _grads(_objective(qb, labels, "off"), score)
    ob1 = _objective(qb, labels, "on")
    g1, h1 = _grads(ob1, score)
    assert ob1.rank_fused_fallback_queries == 0
    np.testing.assert_allclose(g1, g0, atol=1e-4 * np.abs(g0).max(),
                               rtol=1e-5)
    np.testing.assert_allclose(h1, h0, atol=1e-4 * np.abs(h0).max(),
                               rtol=1e-5)


def test_sigmoid_lut_close_to_exact():
    rng = np.random.default_rng(11)
    counts = [30, 60, 90]
    qb = _boundaries(counts)
    n = int(qb[-1])
    labels = rng.integers(0, 3, n)
    score = rng.normal(size=n).astype(np.float32)
    g0, h0 = _grads(_objective(qb, labels, "on"), score)
    g1, h1 = _grads(_objective(qb, labels, "on", lut_bins=1024 * 1024),
                    score)
    # 2^20 bins over [-50, 50]: quantization error far below bf16 noise
    np.testing.assert_allclose(g1, g0, atol=2e-2 * np.abs(g0).max())
    np.testing.assert_allclose(h1, h0, atol=2e-2 * np.abs(h0).max())


# ---------------------------------------------------------------------------
# end-to-end train
# ---------------------------------------------------------------------------
def _rank_data(nq=40, qsize=25, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(nq * qsize, 6)
    y = rng.randint(0, 4, nq * qsize)
    return X, y, [qsize] * nq


def _train_ndcg(extra, rounds=5):
    X, y, group = _rank_data()
    params = {"objective": "lambdarank", "metric": "ndcg",
              "eval_at": [10], "verbose": -1, "num_leaves": 15,
              "min_data_in_leaf": 5, **extra}
    ds = lgb.Dataset(X, label=y, group=group, params=params)
    evals = {}
    bst = lgb.train(params, ds, num_boost_round=rounds,
                    valid_sets=[ds], valid_names=["train"],
                    evals_result=evals)
    key = next(k for k in evals["train"] if k.startswith("ndcg"))
    return bst, evals["train"][key][-1]


def test_train_ndcg_parity():
    bst0, nd0 = _train_ndcg({"tpu_rank_fused": "off"})
    bst1, nd1 = _train_ndcg({"tpu_rank_fused": "on",
                             "tpu_rank_tile": 128})
    # assert fused stayed active through real updates on a live booster
    X, y, group = _rank_data()
    params = {"objective": "lambdarank", "verbose": -1, "num_leaves": 15,
              "min_data_in_leaf": 5, "metric": "none",
              "tpu_rank_fused": "on", "tpu_rank_tile": 128}
    live = lgb.Booster(params=params,
                       train_set=lgb.Dataset(X, label=y, group=group,
                                             params=params).construct())
    live.update()
    obj = live._gbdt.objective
    assert obj.rank_fused_active
    assert obj.rank_fused_fallback_queries == 0
    # bf16 pair factors are shared; trees may still diverge on f32-level
    # split ties, so compare the metric, not the model text
    assert nd1 == pytest.approx(nd0, abs=5e-3)
    assert np.isfinite(bst1.predict(np.random.RandomState(1)
                                    .randn(8, 6))).all()


def test_interpret_smoke_and_trace_once():
    extra = {"tpu_rank_fused": "on", "tpu_rank_tile": 128}
    _train_ndcg(extra, rounds=3)
    before = compile_cache.trace_count()
    _train_ndcg(extra, rounds=3)   # identical shapes: zero new traces
    assert compile_cache.trace_count() == before


def test_auto_mode_off_device_uses_buckets():
    # on CPU "auto" must resolve to the bucketed path
    qb = _boundaries([10, 20])
    obj = _objective(qb, np.zeros(30, np.int64), "auto")
    from lightgbm_tpu.ops.pallas_hist import pallas_available
    if not pallas_available():
        assert not obj.rank_fused_active
        assert len(obj._buckets) > 0
