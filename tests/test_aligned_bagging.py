"""Bagging and feature sampling on the aligned engine (CPU: Pallas
interpret mode).

A bag lane masks gradients and histogram counts (in-bag statistics,
gbdt.cpp:209-275) while the exact physical count pass drives the layout
over ALL rows. Plain bagging's bag is a function of (row id, the re-bag's
seed): the engine draws it on the device from the index lane
(`AlignedEngine.bag_select`, `ops/goss.py:bag_multipliers`), holds it
untouched between re-bags and runs its pipeline 8 deep; every row-order
path draws the same bag from the same seed (`ops/goss.py:bag_rows`), and
the plain reference is `benchmark/reference_bagging.py`. Balanced bags,
bags under a mesh, on the multiclass engine and under DART stay
host-drawn, and the `train_path` event says which and why.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmark import reference_bagging
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.obs import trace
from lightgbm_tpu.ops import goss as goss_ops
from lightgbm_tpu.utils import log

BAGGED = {"bagging_fraction": 0.7, "bagging_freq": 2, "bagging_seed": 11}


def _make(n=4000, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] * X[:, 2]
          + 0.3 * rng.standard_normal(n)) > 0).astype(np.float32)
    return X, y


def _params(mode, extra=None):
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 20,
              "verbosity": -1, "metric": "none", "tpu_grow_mode": mode,
              "tpu_aligned_interpret": mode == "aligned",
              "tpu_chunk": 256, **BAGGED}
    if mode == "leafwise":
        # f32 histogram sums, as the engine's are. The leaf-wise
        # learner's default payload is a bf16 hi/lo pair (16 mantissa
        # bits), and a 32-row leaf it reaches by subtraction reads
        # 1.6e-4 off the float64 value where the engine reads 6e-6
        # (PERF.md section 6, PR 35): that, not the engine, is what a
        # draw moves across the 1e-4 below
        params["gpu_use_dp"] = True
    params.update(extra or {})
    return params


def _train(X, y, mode, iters=6, extra=None):
    params = _params(mode, extra)
    ds = lgb.Dataset(X, label=y, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(iters):
        bst.update()
    return bst


def _tree_tuples(bst):
    g = bst._gbdt
    g.materialized_models()
    out = []
    for t in g.models:
        k = t.num_leaves - 1
        out.append((list(t.split_feature_inner[:k]),
                    list(t.threshold_in_bin[:k]),
                    np.asarray(t.leaf_value[:t.num_leaves])))
    return out


def _same_trees(a, b):
    ta, tb = _tree_tuples(a), _tree_tuples(b)
    assert len(ta) == len(tb)
    for (fa, tha, va), (fb, thb, vb) in zip(ta, tb):
        assert fa == fb
        assert tha == thb
        np.testing.assert_allclose(va, vb, rtol=1e-4, atol=1e-6)


def test_aligned_bagging_matches_leafwise():
    X, y = _make()
    a = _train(X, y, "aligned")
    assert a._gbdt._aligned_eligible()
    assert a._gbdt._aligned_pipeline_depth() == 8
    b = _train(X, y, "leafwise")
    assert a._gbdt._aligned_eng_ref.compact     # the record as it was
    assert a._gbdt.bag_data_cnt == b._gbdt.bag_data_cnt == 2800
    _same_trees(a, b)


@pytest.mark.slow
def test_aligned_balanced_bagging():
    X, y = _make(3000)
    extra = {"bagging_fraction": 1.0, "pos_bagging_fraction": 0.6,
             "neg_bagging_fraction": 0.8}
    a = _train(X, y, "aligned", extra=extra)
    b = _train(X, y, "leafwise", extra=extra)
    ta, tb = _tree_tuples(a), _tree_tuples(b)
    for (fa, tha, va), (fb, thb, vb) in zip(ta, tb):
        assert fa == fb
        np.testing.assert_allclose(va, vb, rtol=1e-4, atol=1e-6)


@pytest.mark.slow
def test_aligned_bagging_with_valid():
    X, y = _make(3000)
    Xv, yv = _make(1000, seed=3)
    params = _params("aligned", {"learning_rate": 0.2, "metric": "auc",
                                 "bagging_fraction": 0.8,
                                 "bagging_freq": 1})
    ds = lgb.Dataset(X, label=y, params=params).construct()
    vs = lgb.Dataset(Xv, label=yv, reference=ds, params=params).construct()
    res = {}
    lgb.train(params, ds, 8, valid_sets=[vs], valid_names=["v"],
              evals_result=res, verbose_eval=False)
    auc = res["v"]["auc"]
    assert auc[-1] > 0.75, auc


# ---- the draw itself: engine, numpy twin and the plain reference
_ENGINES = {}


def _engine(n, lane):
    """One engine a (row count, record), its records moved by two trees:
    the compact record, whose bag is a bit of the meta lane, or the
    standard one a run past 2^24 rows gets, whose bag is a lane."""
    if (n, lane) not in _ENGINES:
        X, y = _make(n)
        bst = _train(X, y, "aligned", iters=2,
                     extra={"tpu_force_big_n": lane})
        _ENGINES[n, lane] = bst, bst._gbdt._aligned_eng_ref
    return _ENGINES[n, lane][1]


@pytest.mark.parametrize("n, seed, fraction, lane", [
    (1800, 0, 0.8, False), (1800, 2**31 - 2, 0.5, False),
    (2003, 774252441, 0.8, True), (2003, 7, 0.05, True),
    (4000, 123456789, 0.999, False), (4000, 5, 1.0 / 3, False),
    (2003, 774252441, 0.8, False), (1800, 3, 0.8, True)])
def test_device_bag_equals_the_reference_bit_for_bit(n, seed, fraction,
                                                     lane):
    """2003 is no multiple of the chunk (256): the last chunk is part
    full and the spare chunks hold no row."""
    eng = _engine(n, lane)
    assert eng.bag_device and not eng.bag_multiplier
    assert eng.compact != lane and ("bag" in eng.lanes) == lane
    rid = np.asarray(eng._rid_lanes(eng.rec)).reshape(-1)[:n]
    assert not np.array_equal(rid, np.arange(n))      # rows have moved
    cnt = reference_bagging.bag_count(n, fraction)
    kept = eng.bag_select(seed, cnt)
    want = reference_bagging.bag_mask(n, seed, cnt)
    np.testing.assert_array_equal(eng.row_bag(), want.astype(np.float32))
    assert int(kept) == cnt == int(want.sum())
    np.testing.assert_array_equal(goss_ops.bag_rows(n, seed, cnt),
                                  np.flatnonzero(want))
    np.testing.assert_array_equal(
        np.asarray(goss_ops.goss_key(jnp.arange(n, dtype=jnp.int32),
                                     jnp.uint32(seed))),
        reference_bagging.key(np.arange(n), seed).astype(np.uint32))


def test_bag_is_held_between_rebags_and_drawn_only_there():
    X, y = _make(2003)
    trace.reset()
    seen = []
    log.register_callback(lambda line: seen.append(log.parse_event(line)))
    params = _params("aligned", {"bagging_freq": 3, "verbosity": 1,
                                 "feature_fraction": 0.5})
    ds = lgb.Dataset(X, label=y, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    g = bst._gbdt
    lanes = []
    for _ in range(7):
        bst.update()
        lanes.append(g._aligned_eng_ref.row_bag())
    bst.eval_train()
    n, cnt = len(y), int(0.7 * len(y))
    schedule = reference_bagging.bag_schedule(11, 3, 7)
    draws = trace.seams("bag.draw")
    assert [(r["iter"], r["seed"]) for r in draws] == schedule
    assert [r["iter"] for r in draws] == [0, 3, 6]
    assert all(r["cnt"] == cnt and r["freq"] == 3 for r in draws)
    for it, lane in enumerate(lanes):
        seed = dict(schedule)[it - it % 3]
        np.testing.assert_array_equal(
            lane, reference_bagging.bag_mask(n, seed, cnt))
    assert not np.array_equal(lanes[2], lanes[3])
    # nothing of N rows was uploaded and no host mask was made
    assert not [r for r in trace.seams() if r["name"] == "aligned.upload"
                ][1:]
    assert g.bag_data_indices is None and g._aligned_pipeline_depth() == 8
    pack = trace.seams("aligned.pack")[-1]
    assert pack["bag"] == "device"
    iters = trace.seams("aligned.iter")
    assert [r["iter"] for r in iters] == list(range(7))
    assert all(r["bag_kept"] == cnt and r["features_used"] == 3
               for r in iters)
    for t in bst.trees:     # in-bag counts
        assert int(t.leaf_count[:t.num_leaves].sum()) == cnt
    paths = [e for e in seen if e and e["event"] == "train_path"]
    falls = [e for e in seen if e and e["event"] == "aligned_fallback"]
    assert [e["path"] for e in paths] == ["aligned"] and not falls
    assert not [s for s in paths[0]["gate_notes"] if "bag" in s]


@pytest.mark.parametrize("extra, note", [
    ({}, None),
    ({"bagging_fraction": 1.0, "pos_bagging_fraction": 0.6,
      "neg_bagging_fraction": 0.8}, "balanced bagging"),
    ({"tree_learner": "data", "num_machines": 2},
     "bagging under tree_learner=data"),
    ({"objective": "multiclass", "num_class": 3},
     "bagging on the multiclass engine"),
    ({"boosting": "dart"}, "bagging under boosting=dart"),
])
def test_depth_is_8_and_what_stays_host_drawn_is_named(extra, note):
    X, y = _make(1500)
    if "num_class" in extra:
        y = np.floor(np.abs(X[:, 0]) * 1.4).clip(0, 2)
    seen = []
    log.register_callback(lambda line: seen.append(log.parse_event(line)))
    trace.reset()
    bst = _train(X, y, "aligned", iters=2,
                 extra=dict(extra, verbosity=1, num_leaves=4))
    g = bst._gbdt
    paths = [e for e in seen if e and e["event"] == "train_path"]
    assert len(paths) == 1 and paths[0]["path"].startswith("aligned")
    notes = [s for s in paths[0]["gate_notes"] if "bag drawn on the host"
             in s]
    pack = trace.seams("aligned.pack")[-1]
    if note is None:
        assert g._aligned_pipeline_depth() == 8 and g._bag_on_device
        assert not notes and pack["bag"] == "device"
        assert not trace.seams("bag.draw")[1:]
    else:
        assert g._aligned_pipeline_depth() == 1 and not g._bag_on_device
        assert len(notes) == 1 and note in notes[0]
        assert pack["bag"] == "host" and not trace.seams("bag.draw")


def test_inexact_round_mid_queue_replays_to_the_depth_1_model(monkeypatch):
    """A starved speculation budget makes rounds inexact inside the
    8-deep queue, across re-bags: the fallback and the replays rebuild on
    the bag and the feature mask each discarded dispatch was queued with,
    so the model is the one-behind pipeline's byte for byte, and the
    leaf-wise path's."""
    X, y = _make(2000)
    extra = {"tpu_level_spec": 0.6, "num_leaves": 31, "min_data_in_leaf": 5,
             "feature_fraction": 0.7}
    a = _train(X, y, "aligned", iters=9, extra=extra)
    eng = a._gbdt._aligned_eng_ref
    assert a._gbdt._aligned_pipeline_depth() == 8
    a._gbdt.materialized_models()
    assert getattr(eng, "fallbacks", 0) > 0, "needs a fallback to mean much"
    monkeypatch.setattr(GBDT, "_aligned_pipeline_depth", lambda self: 1)
    b = _train(X, y, "aligned", iters=9, extra=extra)
    b._gbdt.materialized_models()
    assert getattr(b._gbdt._aligned_eng_ref, "fallbacks", 0) > 0
    assert a.model_to_string() == b.model_to_string()
    monkeypatch.undo()
    c = _train(X, y, "leafwise", iters=9, extra=extra)
    _same_trees(a, c)
    np.testing.assert_allclose(
        np.asarray(a._gbdt.get_training_score())[0],
        a.predict(X, raw_score=True), atol=2e-4)


def test_no_split_lies_outside_its_trees_feature_mask():
    X, y = _make(2500, f=10)
    trace.reset()
    bst = _train(X, y, "aligned", iters=20,
                 extra={"feature_fraction": 0.5, "feature_fraction_seed": 9,
                        "num_leaves": 15})
    masks = reference_bagging.feature_masks(9, 10, 0.5, 20)
    trees = bst.dump_model()["tree_info"]
    assert len(trees) == 20
    used = set()
    for tree, mask in zip(trees, masks):
        feats = reference_bagging.split_features(tree)
        assert feats and all(mask[f] for f in feats), (feats, mask)
        used |= set(feats)
    assert len(used) > 5            # the masks do differ tree by tree
    assert all(r["features_used"] == 5
               for r in trace.seams("aligned.iter"))


def test_checkpoint_inside_a_held_bag_resumes_on_it(tmp_path):
    X, y = _make(1800)
    params = _params("aligned", {"bagging_freq": 3})
    ref = lgb.train(dict(params), lgb.Dataset(X, y), num_boost_round=6)
    ck = str(tmp_path / "ck")
    part = lgb.train(dict(params, tpu_checkpoint_dir=ck,
                          tpu_checkpoint_freq=2, tpu_fault_spec="kill@3"),
                     lgb.Dataset(X, y), num_boost_round=6)
    assert part._preempted
    res = lgb.train(dict(params, tpu_checkpoint_dir=ck,
                         tpu_checkpoint_freq=2),
                    lgb.Dataset(X, y), num_boost_round=6)
    assert res._resilience["resumed_from"] == 4     # bag 3 is in force
    for ta, tb in zip(ref.trees, res.trees):
        k = ta.num_leaves - 1
        assert list(ta.split_feature[:k]) == list(tb.split_feature[:k])
        assert list(ta.threshold_in_bin[:k]) == list(tb.threshold_in_bin[:k])
        np.testing.assert_array_equal(ta.leaf_count[:ta.num_leaves],
                                      tb.leaf_count[:tb.num_leaves])
