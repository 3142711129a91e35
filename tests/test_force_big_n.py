"""`tpu_force_big_n` parity: the big-n record (the standard layout and
its route-word repack) only engages naturally above 2^24 rows, where no
tier-1 test can reach it. The knob forces that record at small n; the
trees it grows must match the default layout exactly. It no longer
forces the `count_pass` kernel: the histograms count in exact i32 at any
row count, so the layout takes its left counts from the finder, and
those counts must place every row where the trees send it.
"""
import numpy as np

import lightgbm_tpu as lgb


def _train(X, y, force_big_n, iters=2):
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 20,
              "verbosity": -1, "metric": "none", "tpu_grow_mode": "aligned",
              "tpu_aligned_interpret": True, "tpu_chunk": 256,
              "tpu_force_big_n": force_big_n}
    ds = lgb.Dataset(X, label=y, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(iters):
        bst.update()
    return bst


def test_force_big_n_matches_default_layout():
    rng = np.random.default_rng(7)
    X = rng.standard_normal((900, 5)).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] * X[:, 2]
          + 0.3 * rng.standard_normal(900)) > 0).astype(np.float32)
    on = _train(X, y, True)
    off = _train(X, y, False)
    for ta, tb in zip(on.trees, off.trees):
        assert ta.num_leaves == tb.num_leaves
        k = ta.num_leaves - 1
        assert list(ta.split_feature[:k]) == list(tb.split_feature[:k])
        assert list(ta.threshold_in_bin[:k]) == list(tb.threshold_in_bin[:k])
        np.testing.assert_allclose(ta.leaf_value[:ta.num_leaves],
                                   tb.leaf_value[:tb.num_leaves],
                                   rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(on.predict(X[:128], raw_score=True),
                               off.predict(X[:128], raw_score=True),
                               rtol=1e-6, atol=1e-9)


def test_force_big_n_leaf_counts_exact_i32():
    """The big-n record must deliver EXACT integer leaf populations, now
    from the histograms' exact i32 counts (an f32 count sum loses integer
    exactness past 2^24 rows). Certify by routing every training row
    through the finished trees host-side and demanding integer equality
    with the recorded per-leaf counts."""
    rng = np.random.default_rng(19)
    n = 900
    X = rng.standard_normal((n, 6)).astype(np.float32)
    y = ((X[:, 0] - X[:, 1] * X[:, 2]
          + 0.3 * rng.standard_normal(n)) > 0).astype(np.float32)
    bst = _train(X, y, True, iters=3)
    leaf_idx = bst.predict(X, pred_leaf=True).astype(np.int64)
    if leaf_idx.ndim == 1:
        leaf_idx = leaf_idx[:, None]
    assert leaf_idx.shape[1] == len(bst.trees)
    for t, tree in enumerate(bst.trees):
        counts = np.bincount(leaf_idx[:, t], minlength=tree.num_leaves)
        recorded = tree.leaf_count[:tree.num_leaves]
        assert recorded.dtype == np.int32
        assert int(recorded.sum()) == n
        np.testing.assert_array_equal(recorded, counts)
