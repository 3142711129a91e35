"""`boosting=goss` on the aligned engine (CPU: Pallas interpret mode).

The engine's bag lane holds a per-row multiplier that a device program
writes from the record's own score and label lanes (`ops/goss.py`, two
counting selects, no sort); the plain reference is
`benchmark/reference_goss.py`. Held here: the lane against the reference
row for row (ties, permuted records), the trees against the host
learner's on the same sample, the unsampled head against plain gbdt,
in-bag counts, an inexact round's replay, checkpoint and resume.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmark import reference_goss
from lightgbm_tpu.models.boosting_variants import GOSS, goss_sizes
from lightgbm_tpu.obs import trace
from lightgbm_tpu.ops import goss as goss_ops
from lightgbm_tpu.utils import log

BASE = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
        "learning_rate": 0.5, "min_data_in_leaf": 20, "verbosity": -1,
        "metric": "none", "tpu_chunk": 256, "boosting": "goss",
        "top_rate": 0.2, "other_rate": 0.1, "bagging_seed": 5}
ALIGNED = {"tpu_grow_mode": "aligned", "tpu_aligned_interpret": True}
LEAFWISE = {"tpu_grow_mode": "leafwise"}


def _data(n=1800, f=6, seed=0, coarse=False):
    """`coarse`: few distinct feature values, so whole groups of rows
    share every score and |g x h| ties in thousands."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    if coarse:
        X = np.round(X)
    y = ((X[:, 0] + X[:, 1] * X[:, 2]
          + 0.3 * rng.standard_normal(n)) > 0).astype(np.float32)
    return X, y


def _train(X, y, extra, iters):
    params = dict(BASE, **extra)
    ds = lgb.Dataset(X, label=y, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(iters):
        bst.update()
    bst._gbdt.materialized_models()
    return bst


def _same_trees(a, b, rtol=1e-4, atol=1e-5):
    assert len(a.trees) == len(b.trees)
    for ta, tb in zip(a.trees, b.trees):
        assert ta.num_leaves == tb.num_leaves
        k = ta.num_leaves - 1
        assert list(ta.split_feature[:k]) == list(tb.split_feature[:k])
        assert list(ta.threshold_in_bin[:k]) == list(tb.threshold_in_bin[:k])
        np.testing.assert_array_equal(ta.leaf_count[:ta.num_leaves],
                                      tb.leaf_count[:tb.num_leaves])
        np.testing.assert_allclose(ta.leaf_value[:ta.num_leaves],
                                   tb.leaf_value[:tb.num_leaves],
                                   rtol=rtol, atol=atol)


def test_key_is_the_references_key():
    rid = np.concatenate([np.arange(5000), [2**24, 2**31 - 1]])
    for seed in (0, 1, 123456789, 2**31 - 2):
        np.testing.assert_array_equal(
            np.asarray(goss_ops.goss_key(jnp.asarray(rid, jnp.int32),
                                         jnp.uint32(seed))),
            reference_goss.key(rid, seed).astype(np.uint32))
        assert len(np.unique(reference_goss.key(rid, seed))) == len(rid)


@pytest.mark.parametrize("coarse", [False, True])
def test_multiplier_lane_equals_reference_row_for_row(coarse):
    """After trees have moved the rows the records lie in another order
    than the rows; the lane, read back by row id, is the reference's."""
    X, y = _data(coarse=coarse)
    bst = _train(X, y, ALIGNED, iters=3)
    g = bst._gbdt
    eng = g._aligned_eng_ref
    assert eng is not None and eng.bag_multiplier and not eng.compact
    rid = np.asarray(eng.rec[:, eng.lanes["rid"], :]).reshape(-1)[:len(y)]
    assert not np.array_equal(rid, np.arange(len(y)))     # permuted
    n = len(y)
    top_k, other_k, mult = goss_sizes(g.cfg, n)
    scores = eng.row_scores()
    gr, he = eng._pgrad(jnp.asarray(scores), jnp.asarray(y), None)
    a32 = np.asarray(jnp.abs(gr * he))
    seed = 987654321
    kept_top, kept_other, thr = eng.goss_select(seed, top_k, other_k, mult)
    lane = eng.row_bag()
    # the reference at the device's own f32 products: a x 1
    want = reference_goss.goss_multipliers(
        a32, np.ones(n), np.arange(n), seed, g.cfg.top_rate,
        g.cfg.other_rate)
    np.testing.assert_array_equal(lane,
                                  want["multiplier"].astype(np.float32))
    assert int(kept_top) == want["kept_top"] >= top_k
    assert int(kept_other) == other_k
    assert float(thr) == np.float32(want["threshold"])
    if coarse:      # ties at the threshold are all kept
        assert want["kept_top"] > top_k


def test_goss_trees_equal_host_learners_on_the_same_sample(monkeypatch):
    X, y = _data(coarse=True)
    seen = []
    log.register_callback(lambda line: seen.append(log.parse_event(line)))
    a = _train(X, y, dict(ALIGNED, verbosity=1), iters=5)
    paths = [e for e in seen if e and e["event"] == "train_path"]
    falls = [e for e in seen if e and e["event"] == "aligned_fallback"]
    assert [e["path"] for e in paths] == ["aligned"] and not falls
    # no host seam in the loop but the dispatch, the selection's enqueue
    # and the pulls: nothing of N rows crosses
    names = {r["name"] for r in trace.seams()}
    assert "goss.select" in names
    monkeypatch.setattr(GOSS, "_fused_ok", False)     # SerialTreeLearner
    b = _train(X, y, dict(LEAFWISE, tpu_use_f64_hist=True), iters=5)
    assert not b._gbdt.use_fused
    _same_trees(a, b)


def test_unsampled_head_equals_plain_gbdt_and_counts_are_in_bag():
    X, y = _data()
    trace.reset()
    a = _train(X, y, dict(ALIGNED, learning_rate=0.34), iters=4)  # 2 warm
    b = _train(X, y, dict(ALIGNED, learning_rate=0.34, boosting="gbdt"),
               iters=2)
    for ta, tb in zip(a.trees[:2], b.trees):
        k = ta.num_leaves - 1
        assert list(ta.split_feature[:k]) == list(tb.split_feature[:k])
        assert list(ta.threshold_in_bin[:k]) == list(tb.threshold_in_bin[:k])
        np.testing.assert_allclose(ta.leaf_value[:ta.num_leaves],
                                   tb.leaf_value[:tb.num_leaves],
                                   rtol=1e-5, atol=1e-7)
    iters = {r["iter"]: r for r in trace.seams("aligned.iter")}
    assert "goss_kept_top" not in iters[0] and "goss_kept_top" not in iters[1]
    top_k, other_k, _ = goss_sizes(a._gbdt.cfg, len(y))
    for it in (2, 3):
        rec = iters[it]
        assert rec["goss_kept_top"] >= top_k
        assert rec["goss_kept_other"] == other_k
        tree = a.trees[it]
        # a kept row counts once, whatever its multiplier
        assert int(tree.leaf_count[:tree.num_leaves].sum()) \
            == rec["goss_kept_top"] + rec["goss_kept_other"]
    assert int(a.trees[0].leaf_count[:a.trees[0].num_leaves].sum()) == len(y)


def test_inexact_round_replays_on_the_same_sample():
    """A starved speculation budget makes rounds inexact inside the
    8-deep queue: the fallback and the replays rebuild on the samples the
    discarded dispatches drew, so the trees are the leaf-wise path's."""
    X, y = _data(n=2000)
    extra = {"tpu_level_spec": 0.6, "num_leaves": 31, "min_data_in_leaf": 5}
    a = _train(X, y, dict(ALIGNED, **extra), iters=6)
    eng = a._gbdt._aligned_eng_ref
    assert a._gbdt._aligned_pipeline_depth() == 8
    assert getattr(eng, "fallbacks", 0) > 0, "needs a fallback to mean much"
    b = _train(X, y, dict(LEAFWISE, **extra), iters=6)
    _same_trees(a, b)
    np.testing.assert_allclose(
        np.asarray(a._gbdt.get_training_score())[0],
        a.predict(X, raw_score=True), atol=2e-4)


def test_goss_on_the_ext_record_selects_from_row_order_gradients():
    """A ranking objective's gradients reach the engine in row order;
    the selection gathers them by row id, and the trees are the
    leaf-wise path's on the same sample."""
    rng = np.random.default_rng(3)
    sizes = rng.integers(10, 40, 60)
    n = int(sizes.sum())
    X = rng.standard_normal((n, 6)).astype(np.float32)
    y = np.clip(np.round(X[:, 0] + 0.5 * X[:, 1] + 1
                         + 0.5 * rng.standard_normal(n)), 0, 4)
    extra = {"objective": "lambdarank", "min_data_in_leaf": 5,
             "min_sum_hessian_in_leaf": 1e-3, "top_rate": 0.3,
             "other_rate": 0.2}

    def train(mode):
        params = dict(BASE, **extra, **mode)
        ds = lgb.Dataset(X, label=y, group=sizes, params=params).construct()
        bst = lgb.Booster(params=params, train_set=ds)
        for _ in range(4):
            bst.update()
        bst._gbdt.materialized_models()
        return bst
    a, b = train(ALIGNED), train(LEAFWISE)
    eng = a._gbdt._aligned_eng_ref
    assert eng is not None and eng.ext and eng.bag_sampled
    _same_trees(a, b)
    assert int(a.trees[3].leaf_count[:a.trees[3].num_leaves].sum()) \
        == int(n * 0.3) + int(n * 0.2)


def test_checkpoint_and_resume_equal_the_uninterrupted_run(tmp_path):
    X, y = _data()
    params = dict(BASE, **ALIGNED)
    ref = lgb.train(dict(params), lgb.Dataset(X, y), num_boost_round=6)
    ck = str(tmp_path / "ck")
    part = lgb.train(dict(params, tpu_checkpoint_dir=ck,
                          tpu_checkpoint_freq=2, tpu_fault_spec="kill@3"),
                     lgb.Dataset(X, y), num_boost_round=6)
    assert part._preempted
    res = lgb.train(dict(params, tpu_checkpoint_dir=ck,
                         tpu_checkpoint_freq=2),
                    lgb.Dataset(X, y), num_boost_round=6)
    assert res._resilience["resumed_from"] == 4
    # the resumed engine packs its records in row order again, so f32
    # histogram sums add up in another order: same trees, values to f32
    _same_trees(ref, res, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("extra, names", [
    # (boosting=dart rides the engine since PR 33: what still keeps it
    # off is named the same way)
    ({"boosting": "dart", "objective": "multiclass", "num_class": 3},
     "boosting=dart with multiclass"),
    ({"boosting": "rf", "bagging_fraction": 0.7, "bagging_freq": 1},
     "boosting=rf"),
    ({"objective": "multiclass", "num_class": 3}, "boosting=goss with "
                                                  "multiclass"),
    ({"tree_learner": "data", "num_machines": 2},
     "boosting=goss under tree_learner=data"),
])
def test_variants_that_stay_out_are_named(extra, names):
    X, y = _data(n=600)
    if "num_class" in extra:
        y = np.floor(np.abs(X[:, 0]) * 1.4).clip(0, 2)
    seen = []
    log.register_callback(lambda line: seen.append(log.parse_event(line)))
    bst = _train(X, y, dict(ALIGNED, verbosity=1, **extra), iters=2)
    assert getattr(bst._gbdt, "_aligned_eng_ref", None) is None
    paths = [e for e in seen if e and e["event"] == "train_path"]
    assert len(paths) == 1 and not paths[0]["path"].startswith("aligned")
    assert names in paths[0]["rejected"]
