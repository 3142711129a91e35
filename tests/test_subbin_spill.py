"""Sub-binned 255-bin histogram + HBM slot-hist spill-ring tests.

The sub-binned accumulation (hi/lo 4-bit one-hots contracted on the MXU
into a [16, 128] tile, folded to [256, 3] once per pass) replaces the
nibble flush above 128 bins; it must stay EXACTLY equivalent to the
einsum formulation (ops/histogram.py) — same contract the nibble form
carried. The HBM spill ring (2-deep staging DMA in move_pass when the
[K+1]-slot store exceeds tpu_hist_spill_vmem_mb) must not change any
split: aligned training with a forced-tiny budget reproduces the
leaf-wise reference bit-for-bit at the tree level.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.histogram import (histogram_from_gathered_gh,
                                        histogram_from_words)
from lightgbm_tpu.ops.pallas_hist import (pallas_histogram,
                                          pallas_histogram_words)


def _mk(n, f, seed=0, int_payload=False):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, 255, (n, f)).astype(np.uint8)
    if int_payload:
        # integer-valued payloads are exact in the hi-bf16 part (lo = 0)
        # and their f32 sums are order-independent -> bitwise assertions
        g = rng.randint(-8, 9, n).astype(np.float32)
        h = rng.randint(0, 9, n).astype(np.float32)
    else:
        g = rng.randn(n).astype(np.float32)
        h = rng.rand(n).astype(np.float32)
    valid = np.ones(n, bool)
    valid[rng.choice(n, n // 10, replace=False)] = False
    return bins, g, h, valid


def _pack_words(bins):
    """level-builder record layout: 4 uint8 bins per int32, word w bits
    8j..8j+7 = feature 4w+j (histogram_from_words contract)."""
    n, f = bins.shape
    words = []
    for w in range((f + 3) // 4):
        acc = np.zeros(n, np.int32)
        for j in range(4):
            fi = 4 * w + j
            if fi < f:
                acc |= bins[:, fi].astype(np.int32) << (8 * j)
        words.append(jnp.asarray(acc))
    return words


def test_subbin_rows_exact_vs_einsum_255():
    """Integer payloads: the sub-binned pallas kernel (interpret mode)
    is BITWISE equal to the f32 einsum path at max_bin=255."""
    bins, g, h, valid = _mk(2048, 5, int_payload=True)
    gh = jnp.stack([jnp.asarray(g), jnp.asarray(h)], axis=1)
    got = np.asarray(pallas_histogram(
        jnp.asarray(bins), gh, jnp.asarray(valid), max_bin=255,
        chunk=512, subbin=True, interpret=True))
    ref = np.asarray(histogram_from_gathered_gh(
        jnp.asarray(bins), gh, jnp.asarray(valid), max_bin=255,
        chunk=512, precision="f32"))
    np.testing.assert_array_equal(got, ref)


def test_subbin_rows_float_vs_einsum_255():
    """Float payloads: hi/lo bf16 split recovers ~f32 accuracy; counts
    stay exact."""
    bins, g, h, valid = _mk(3000, 4, seed=1)
    gh = jnp.stack([jnp.asarray(g), jnp.asarray(h)], axis=1)
    got = np.asarray(pallas_histogram(
        jnp.asarray(bins), gh, jnp.asarray(valid), max_bin=255,
        chunk=1024, subbin=True, interpret=True))
    ref = np.asarray(histogram_from_gathered_gh(
        jnp.asarray(bins), gh, jnp.asarray(valid), max_bin=255,
        chunk=1024, precision="f32"))
    np.testing.assert_array_equal(got[..., 2], ref[..., 2])
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=1e-3)


def test_subbin_words_exact_vs_einsum_255():
    """The packed-word sub-binned kernel (the EFB/aligned record layout)
    against the einsum path unpacking the same words."""
    bins, g, h, valid = _mk(1536, 7, seed=2, int_payload=True)
    words = _pack_words(bins)
    got = np.asarray(pallas_histogram_words(
        words, jnp.asarray(g), jnp.asarray(h), jnp.asarray(valid),
        num_features=7, max_bin=255, chunk=512, subbin=True,
        interpret=True))
    ref = np.asarray(histogram_from_words(
        words, jnp.asarray(g), jnp.asarray(h), jnp.asarray(valid),
        num_features=7, max_bin=255, precision="f32"))
    np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# training-level parity (aligned interpret mode)

def _sparse_data(n=4000, f=60, dense=4, seed=3):
    """One-hot blocks + dense drivers (the EFB shape; test_efb.py)."""
    rng = np.random.default_rng(seed)
    X = np.zeros((n, f), np.float32)
    X[:, :dense] = rng.standard_normal((n, dense))
    block = 8
    j = dense
    while j < f:
        width = min(block, f - j)
        pick = rng.integers(0, width + 1, n)
        rows = np.arange(n)
        active = pick < width
        X[rows[active], j + pick[active]] = \
            rng.standard_normal(active.sum()) + 1.0
        j += width
    y = ((X[:, 0] + X[:, dense] * 0.5 + X[:, dense + 1]
          + 0.2 * rng.standard_normal(n)) > 0.3).astype(np.float32)
    return X, y


def _train(X, y, mode, iters=4, extra=None):
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 255,
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


def _assert_same_trees(a, b):
    ta, tb = _tree_tuples(a), _tree_tuples(b)
    assert len(ta) == len(tb)
    for (fa, tha, va), (fb, thb, vb) in zip(ta, tb):
        assert fa == fb
        assert tha == thb
        np.testing.assert_allclose(va, vb, rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_spill_ring_matches_vmem_store_255bin():
    """A forced-tiny tpu_hist_spill_vmem_mb pushes the slot-hist store
    to HBM through the 2-deep DMA ring; trees must match both the
    VMEM-resident aligned run and the leaf-wise reference."""
    rng = np.random.default_rng(0)
    X = rng.standard_normal((3000, 6)).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] * X[:, 2]
          + 0.3 * rng.standard_normal(3000)) > 0).astype(np.float32)
    spill = _train(X, y, "aligned",
                   extra={"tpu_hist_spill_vmem_mb": 0.001})
    eng = spill._gbdt._aligned_eng_ref
    assert eng is not None and eng.hist_spill, "spill ring not engaged"
    assert getattr(eng, "fallbacks", 0) == 0
    vmem = _train(X, y, "aligned")
    eng_v = vmem._gbdt._aligned_eng_ref
    assert eng_v is not None and not eng_v.hist_spill
    leaf = _train(X, y, "leafwise")
    _assert_same_trees(spill, vmem)
    _assert_same_trees(spill, leaf)


@pytest.mark.slow
def test_subbin_efb_aligned_matches_leafwise_255bin():
    """EFB bundles + 255 bins on the aligned path (sub-binned in-kernel
    unpack through the 8-bit route word) vs the leaf-wise builder."""
    X, y = _sparse_data()
    preds = {}
    for mode in ("aligned", "leafwise"):
        bst = _train(X, y, mode, iters=6,
                     extra={"num_leaves": 15, "enable_bundle": True,
                            "learning_rate": 0.2})
        if mode == "aligned":
            eng = bst._gbdt._aligned_eng_ref
            assert eng is not None, "aligned engine not engaged"
            assert getattr(eng, "fallbacks", 0) == 0
        preds[mode] = bst.predict(X[:800], raw_score=True)
    np.testing.assert_allclose(preds["aligned"], preds["leafwise"],
                               rtol=1e-4, atol=1e-5)
