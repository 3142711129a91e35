"""Aligned-pipeline parity tests (CPU: Pallas interpret mode).

The chunk-aligned builder must reproduce the leaf-wise reference path
exactly (same splits, same leaf values within float noise) — the same
contract the sort-based level builder carries (tests/test_level.py). Here
the full builder + GBDT integration runs in interpret mode; the compiled
kernels run on the chip through chip_smoke.py.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.aligned import ROUTE_TILE

pytestmark = pytest.mark.slow


def _make(n=3000, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] * X[:, 2]
          + 0.3 * rng.standard_normal(n)) > 0).astype(np.float32)
    return X, y


def _train(X, y, mode, iters=4, objective="binary", extra=None):
    params = {"objective": objective, "num_leaves": 8, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 20,
              "verbosity": -1, "metric": "none", "tpu_grow_mode": mode,
              "tpu_aligned_interpret": mode == "aligned",
              "tpu_chunk": 256}
    if extra:
        params.update(extra)
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
                    list(t.threshold_in_bin[:k])
                    if hasattr(t, "threshold_in_bin") else None,
                    np.asarray(t.leaf_value[:t.num_leaves])))
    return out


def _same_trees(a, b):
    """Both boosters hold the same trees (splits equal, leaf values
    within float noise); returns how many."""
    ta, tb = _tree_tuples(a), _tree_tuples(b)
    assert len(ta) == len(tb)
    for (fa, tha, va), (fb, thb, vb) in zip(ta, tb):
        assert fa == fb
        assert tha == thb
        np.testing.assert_allclose(va, vb, rtol=1e-4, atol=1e-5)
    return len(ta)


@pytest.mark.parametrize("chunk", [256, 2 * ROUTE_TILE])
def test_aligned_matches_leafwise_binary(chunk):
    """At 2 x ROUTE_TILE move_pass routes every chunk in two sub-tiles:
    chunk map, flush, histogram and replay run on a multi-tile kernel."""
    X, y = _make()
    a = _train(X, y, "aligned", extra={"tpu_chunk": chunk})
    assert a._gbdt._aligned_eng_ref.C == chunk
    b = _train(X, y, "leafwise")
    _same_trees(a, b)


def test_rows_end_in_the_first_buffer_after_odd_and_even_round_counts():
    """The round loop ping-pongs between two record buffers, so a tree
    of an odd number of rounds ends in the second one and is copied back
    once (phase `build.copy_back` of the build program: a loop of no or
    one trip on the parity of the rounds); after an even number nothing
    is copied. Every other program reads the engine's one record matrix:
    after each tree, the scores it holds are the walk over the model so
    far."""
    from benchmark import reference
    from lightgbm_tpu.obs import trace as obs_trace
    X, y = _make()
    obs_trace.reset()
    iters = 6
    a = _train(X, y, "aligned", iters=0)
    for it in range(iters):
        a.update()
        eng = a._gbdt._aligned_eng_ref
        np.testing.assert_allclose(
            eng.row_scores(), reference.raw_scores(a.dump_model(), X),
            rtol=1e-5, atol=1e-6, err_msg=f"after tree {it}")
    a.eval_train()
    recs = obs_trace.seams("aligned.iter")
    assert [r["iter"] for r in recs] == list(range(iters))
    assert all("norm_passes" not in r for r in recs)    # gone with PR 37
    assert {r["rounds"] % 2 for r in recs} == {0, 1}    # both were run
    from lightgbm_tpu.obs import phases
    back = [r for r in phases.table(only=["build"])
            if r["phase"] == "build.copy_back"]
    assert any(r["opcode"] == "while" for r in back)
    assert _same_trees(a, _train(X, y, "leafwise", iters=iters)) == iters


def test_aligned_matches_leafwise_255bin():
    """max_bin=255 exercises the SUB-BINNED histogram factorization
    (b_pad=256: hi/lo 4-bit one-hots contracted into a [16, 128] tile
    on the MXU, folded to [256, 3] at pass finalize)."""
    X, y = _make()
    a = _train(X, y, "aligned", extra={"max_bin": 255})
    b = _train(X, y, "leafwise", extra={"max_bin": 255})
    _same_trees(a, b)


def test_aligned_matches_leafwise_15bin():
    """max_bin=15 exercises the 4-BIT packing (8 bins/word, the
    reference's dense_nbits 2-bins/byte analogue)."""
    X, y = _make()
    a = _train(X, y, "aligned", extra={"max_bin": 15})
    b = _train(X, y, "leafwise", extra={"max_bin": 15})
    from lightgbm_tpu.models.aligned_builder import AlignedEngine  # noqa
    eng = a._gbdt._aligned_eng_ref
    assert eng is not None and eng.bits == 4 and eng.W == 8
    _same_trees(a, b)


def test_aligned_matches_leafwise_regression():
    X, y = _make()
    y = X[:, 0] * 2.0 + np.sin(X[:, 1]) + y
    a = _train(X, y, "aligned", objective="regression")
    b = _train(X, y, "leafwise", objective="regression")
    pa = a.predict(X[:500])
    pb = b.predict(X[:500])
    np.testing.assert_allclose(pa, pb, rtol=1e-3, atol=1e-4)


def test_aligned_missing_values():
    X, y = _make()
    X[::7, 1] = np.nan
    X[::5, 3] = 0.0
    a = _train(X, y, "aligned")
    b = _train(X, y, "leafwise")
    pa = a.predict(X[:500])
    pb = b.predict(X[:500])
    np.testing.assert_allclose(pa, pb, rtol=1e-3, atol=1e-4)


def test_aligned_train_score_sync():
    X, y = _make(n=2000)
    a = _train(X, y, "aligned", iters=3,
               extra={"metric": "binary_logloss"})
    b = _train(X, y, "leafwise", iters=3,
               extra={"metric": "binary_logloss"})
    ra = a.eval_train()
    rb = b.eval_train()
    assert ra[0][1] == rb[0][1]
    assert abs(ra[0][2] - rb[0][2]) < 1e-4


def test_aligned_fallbacks_to_leafwise_when_ineligible():
    X, y = _make(n=1500)
    # DART drops trees out of the training score every iteration, which
    # the engine's score lane cannot follow; training must still work on
    # the leafwise path (bagging and GOSS are aligned-supported:
    # tests/test_aligned_bagging.py, tests/test_aligned_goss.py)
    bst = _train(X, y, "aligned", iters=3,
                 extra={"boosting": "dart", "drop_rate": 0.5})
    assert bst._gbdt.iter == 3
    assert getattr(bst._gbdt, "_aligned_eng_ref", None) is None


def test_aligned_early_stop_tree_commits():
    """A tree whose gains dry up before num_leaves must still commit its
    real splits and update the score lane (regression: the in-loop replay
    shortcut must not zero the final commit set)."""
    rng = np.random.default_rng(0)
    n = 2000
    X = np.zeros((n, 3), np.float32)
    X[:, 0] = (rng.random(n) > 0.5).astype(np.float32)
    y = (X[:, 0] + 0.01 * rng.standard_normal(n) > 0.5).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
              "tpu_grow_mode": "aligned", "tpu_aligned_interpret": True,
              "tpu_chunk": 256, "metric": "binary_logloss"}
    ds = lgb.Dataset(X, label=y, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(3):
        bst.update()
    g = bst._gbdt
    g.materialized_models()
    assert g.models[0].num_leaves >= 2
    assert g.eval_train()[0][2] < 0.55


def test_aligned_categorical_matches_leafwise():
    """Round 4: categorical bitset routing on the aligned engine (the
    compact per-round bitset table + R_CAT route bit)."""
    rng = np.random.default_rng(9)
    n = 3000
    Xc = rng.integers(0, 12, n).astype(np.float32)
    Xn = rng.standard_normal((n, 4)).astype(np.float32)
    X = np.column_stack([Xc, Xn])
    y = ((np.isin(Xc, [1, 3, 7]) * 1.0 + Xn[:, 0]
          + 0.3 * rng.standard_normal(n)) > 0.5).astype(np.float32)
    extra = {"categorical_feature": "0", "max_cat_to_onehot": 1,
             "cat_smooth": 1.0, "min_data_per_group": 5}
    a = _train(X, y, "aligned", iters=5, extra=extra)
    assert a._gbdt._aligned_eligible()
    b = _train(X, y, "leafwise", iters=5, extra=extra)
    ta, tb = _tree_tuples(a), _tree_tuples(b)
    assert len(ta) == len(tb)
    for (fa, tha, va), (fb, thb, vb) in zip(ta, tb):
        assert fa == fb
        np.testing.assert_allclose(va, vb, rtol=1e-4, atol=1e-6)


def test_aligned_categorical_bagging():
    rng = np.random.default_rng(10)
    n = 3000
    Xc = rng.integers(0, 9, n).astype(np.float32)
    Xn = rng.standard_normal((n, 4)).astype(np.float32)
    X = np.column_stack([Xc, Xn])
    # noisy labels: a pure threshold function degenerates the deep splits
    # to zero-gain ties that f32 noise resolves arbitrarily
    y = ((np.isin(Xc, [2, 5]) * 1.2 + Xn[:, 1]
          + 0.4 * rng.standard_normal(n)) > 0.6).astype(np.float32)
    extra = {"categorical_feature": "0", "max_cat_to_onehot": 1,
             "bagging_fraction": 0.7, "bagging_freq": 1}
    a = _train(X, y, "aligned", iters=5, extra=extra)
    assert a._gbdt._aligned_eligible()
    b = _train(X, y, "leafwise", iters=5, extra=extra)
    ta, tb = _tree_tuples(a), _tree_tuples(b)
    for (fa, tha, va), (fb, thb, vb) in zip(ta, tb):
        assert fa == fb
        np.testing.assert_allclose(va, vb, rtol=1e-4, atol=1e-6)
