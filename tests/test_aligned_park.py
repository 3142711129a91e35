"""The parked block of the aligned engine (CPU: Pallas interpret mode).

Where an engine has a bag, one score lane and one chip, the rows the bag
leaves out are PARKED: when the bag lane was written since the last
partition, the build program's first round is `ops/aligned.py:park_pass`
(one `move_pass` that routes by the bag: in-bag rows from chunk 0 on, the
others as one block at the buffer's end), the tree's rounds run over the
in-bag chunks only, and the parked rows take the tree by one `walk_pass`
behind it. No row's arithmetic changes: held here are the partition
against numpy bit for bit, the trees against the leaf-wise learner's on
the same bag, every row's score against the walk of the dump, the
accessors that read rows where they lie, the counters of `aligned.iter`,
and the engines that keep the path they had.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import trace
from lightgbm_tpu.ops import aligned

C, NC, WCNT = 256, 12, 3


def _data(n=3000, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] * X[:, 2]
          + 0.3 * rng.standard_normal(n)) > 0).astype(np.float32)
    return X, y


BASE = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
        "learning_rate": 0.5, "min_data_in_leaf": 20, "verbosity": -1,
        "metric": "none", "tpu_chunk": C}
ALIGNED = {"tpu_grow_mode": "aligned", "tpu_aligned_interpret": True}
# the leaf-wise learner with f32 histogram sums, as the engine's are
# (tests/test_aligned_bagging.py says why)
LEAFWISE = {"tpu_grow_mode": "leafwise", "gpu_use_dp": True}
BAG = {"bagging_fraction": 0.7, "bagging_freq": 3, "bagging_seed": 11}
SAMPLING = {
    "bag_bit": BAG,                                    # compact record
    "bag_lane": dict(BAG, tpu_force_big_n=True),       # standard record
    "goss": {"boosting": "goss", "top_rate": 0.2, "other_rate": 0.1,
             "bagging_seed": 5},
}


def _train(X, y, extra, iters):
    params = dict(BASE, **extra)
    ds = lgb.Dataset(X, label=y, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(iters):
        bst.update()
    return bst


def _iters():
    return trace.seams("aligned.iter")


def _walk(bst, X):
    """The numpy walk over `dump_model()`: every tree's leaf value for
    every row, summed in float64."""
    out = np.zeros(len(X))
    for info in bst.dump_model()["tree_info"]:
        for i, row in enumerate(X):
            node = info["tree_structure"]
            while "leaf_value" not in node:
                left = row[node["split_feature"]] <= node["threshold"]
                node = node["left_child" if left else "right_child"]
            out[i] += node["leaf_value"]
    return out


# ---- the kernel alone --------------------------------------------------
def _records(layout, scenario, seed=0):
    """(records [NC, W, C], cnts, in-bag mask [NC, C], bag_lane, W,
    w_used) with rows in chunks 0-6 and 9 (a gap between them, as a tree
    leaves), and under `scenario` a chunk with no in-bag row and one with
    no out-of-bag row among them."""
    rng = np.random.default_rng(seed)
    lanes, W = aligned.lane_layout(WCNT, with_bag=True,
                                   compact=layout == "bit")
    w_used = max(lanes.values()) + 1
    rec = rng.integers(0, 1 << 31, (NC, W, C), dtype=np.int64) \
        .astype(np.int32)
    cnts = np.array([C, C, C, 17, C, C, 1, 0, 0, 200, 0, 0], np.int32)
    bag = rng.random((NC, C)) < 0.4
    if scenario == "empty_and_full_chunks":
        bag[1], bag[4], bag[3] = False, True, False
    elif scenario == "everything_in":
        bag[:] = True
    if layout == "bit":
        meta = rec[:, lanes["meta"], :] & 0x7FFFFFFF
        rec[:, lanes["meta"], :] = np.where(bag, meta | -(1 << 31), meta)
        return rec, cnts, bag, -2, W, w_used
    # a multiplier lane: 0 out of the sample, 1 or 8 in it
    mult = np.where(bag, np.where(rng.random((NC, C)) < 0.5, 1.0, 8.0), 0.0)
    rec[:, lanes["bag"], :] = mult.astype(np.float32).view(np.int32)
    return rec, cnts, bag, lanes["bag"], W, w_used


@pytest.mark.parametrize("src", (0, 1))
@pytest.mark.parametrize("scenario", ("random", "empty_and_full_chunks",
                                      "everything_in"))
@pytest.mark.parametrize("layout", ("lane", "bit"))
def test_park_pass_is_numpys_stable_partition_by_the_bag(layout, scenario,
                                                          src):
    rec, cnts, bag, bag_lane, W, w_used = _records(layout, scenario)
    live = np.arange(C)[None, :] < cnts[:, None]
    rows = rec.transpose(0, 2, 1)[live]                 # [n, W] as they lie
    inb = bag[live]
    kept = int(inb.sum())
    junk = np.full_like(rec, 7)
    bufs = (rec, junk) if src == 0 else (junk, rec)
    a, b, new, park_begin = jax.jit(
        lambda a, b: aligned.park_pass(
            a, b, src, jnp.asarray(cnts), jnp.int32(kept), C, W, WCNT,
            bag_lane, bits=8, w_used=w_used, interpret=True))(*bufs)
    out = np.asarray(b if src == 0 else a)
    np.testing.assert_array_equal(np.asarray(a if src == 0 else b), rec)
    n_out = len(rows) - kept
    pb = NC - -(-n_out // C)
    assert int(park_begin) == pb
    want = np.zeros(NC, np.int32)
    want[:-(-kept // C)] = C
    if kept % C:
        want[kept // C] = kept % C
    if n_out:
        want[pb:] = C
        if n_out % C:
            want[-1] = n_out % C
    np.testing.assert_array_equal(np.asarray(new), want)
    got = out.transpose(0, 2, 1)[:, :, :w_used]
    left = got[:pb][np.arange(C)[None, :] < want[:pb, None]]
    right = got[pb:][np.arange(C)[None, :] < want[pb:, None]]
    # bit for bit, in the order the rows lay
    np.testing.assert_array_equal(left, rows[inb][:, :w_used])
    np.testing.assert_array_equal(right, rows[~inb][:, :w_used])


# ---- the trees ---------------------------------------------------------
def _same_trees(a, b):
    a._gbdt.materialized_models()
    b._gbdt.materialized_models()
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
                                   rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("sampling", sorted(SAMPLING))
def test_parked_trees_are_the_leafwise_learners_and_every_row_is_scored(
        sampling):
    """Seven iterations: under bagging a re-bag (0), two that hold it, the
    re-bag behind them (3), two more, and another (6); under GOSS two
    unsampled and five sampled. The trees are the leaf-wise learner's on
    the same bag, and the training score of EVERY row, in the bag or out
    of it, is the walk of the dumped trees."""
    X, y = _data()
    trace.reset()
    a = _train(X, y, dict(ALIGNED, **SAMPLING[sampling]), 7)
    eng = a._gbdt._aligned_eng_ref
    assert eng.parks and eng.compact == (sampling == "bag_bit")
    score = np.asarray(a._gbdt.get_training_score())[0]
    recs = _iters()
    n = len(y)
    parts = [r["park_rounds"] for r in recs]
    if sampling == "goss":
        assert parts == [0, 0, 1, 1, 1, 1, 1]
        assert [r["rows_parked"] for r in recs[:2]] == [0, 0]
        assert all(r["rows_parked"] == n - r["goss_kept_top"]
                   - r["goss_kept_other"] > 0 for r in recs[2:])
    else:
        assert parts == [1, 0, 0, 1, 0, 0, 1]
        assert all(r["rows_parked"] == n - int(0.7 * n) for r in recs)
    for r in recs:
        cols = r["columns"]
        # the partition is a row of the table like any other: all rows,
        # one leaf; the tree's root round behind it holds the in-bag rows
        assert len(r["table"]) == r["rounds"]
        root = r["table"][r["park_rounds"]]
        assert r["rows_parked"] + root[cols.index("rows_split")] == n
        assert r["chunks_parked"] == -(-r["rows_parked"] // C)
        if r["park_rounds"]:
            first = r["table"][0]
            assert first[cols.index("rows_split")] == n
            assert first[cols.index("leaves_split")] == 1
            assert first[cols.index("chunks_copied")] == 0
    b = _train(X, y, dict(LEAFWISE, **SAMPLING[sampling]), 7)
    _same_trees(a, b)
    np.testing.assert_allclose(score, _walk(a, X), rtol=1e-5, atol=2e-6)
    bag = eng.row_bag()
    assert 0 < int((bag > 0).sum()) < n     # rows of both kinds were read


def test_a_round_behind_an_inexact_one_leaves_every_row_as_it_was():
    """A build dispatched under a false chain flag (its predecessor was
    inexact) partitions by the new bag all the same and scores nothing:
    parked rows stay parked and unscored, as live rows stay unscored,
    and the bag lane is what the draw wrote."""
    X, y = _data(2000)
    a = _train(X, y, dict(ALIGNED, **SAMPLING["bag_lane"]), 2)
    g = a._gbdt
    g._sync_train_score()
    eng = g._aligned_eng_ref
    before = eng.row_scores().copy()
    eng.bag_select(12345, 1200)         # another bag, of another size
    bag = eng.row_bag().copy()
    assert int(bag.sum()) == 1200
    eng._last_exact = jnp.asarray(False)
    _, _, exact, applied = eng.train_iter(0.5)
    assert bool(exact) and not bool(applied)
    assert int(eng.park_counters["park_rounds"]) == 1
    assert int(eng.park_counters["rows_parked"]) == 800
    np.testing.assert_array_equal(eng.row_scores(), before)
    np.testing.assert_array_equal(eng.row_bag(), bag)
    # and the next build, under a true flag, scores parked rows too
    eng._last_exact = jnp.asarray(True)
    _, _, _, applied = eng.train_iter(0.5)
    assert bool(applied)
    assert int(eng.park_counters["park_rounds"]) == 0
    after = eng.row_scores()
    assert np.all(after[bag == 0] != before[bag == 0])


def test_row_accessors_round_trip_with_rows_parked():
    X, y = _data(2000)
    a = _train(X, y, dict(ALIGNED, **SAMPLING["bag_lane"]), 2)
    g = a._gbdt
    g._sync_train_score()
    eng = g._aligned_eng_ref
    assert int(eng.park_begin) < eng.NC
    assert int(jnp.sum(eng.cnts)) == len(y)
    n = len(y)
    bag = eng.row_bag()
    assert int(bag.sum()) == int(0.7 * n)
    fresh = np.random.default_rng(3).standard_normal(n).astype(np.float32)
    eng.set_row_scores(fresh)
    np.testing.assert_array_equal(eng.row_scores(), fresh)
    np.testing.assert_array_equal(eng.row_bag(), bag)
    # a host-drawn bag over parked rows: the next build parks by it
    mask = (np.arange(n) % 4 != 0).astype(np.float32)
    eng.set_bag(mask)
    np.testing.assert_array_equal(eng.row_bag(), mask)
    eng.train_iter(0.5)
    assert int(eng.park_counters["park_rounds"]) == 1
    assert int(eng.park_counters["rows_parked"]) == n - int(mask.sum())
    np.testing.assert_array_equal(eng.row_bag(), mask)
    assert np.all(eng.row_scores() != fresh)


def test_a_bag_of_everything_parks_nothing_and_unparks_what_was():
    X, y = _data(1500)
    trace.reset()
    _train(X, y, dict(ALIGNED, bagging_fraction=1.0, bagging_freq=1,
                      tpu_force_big_n=True, feature_fraction=0.9), 2)
    assert not _iters()     # no bag at all: the engine is not bagged
    a = _train(X, y, dict(ALIGNED, **SAMPLING["bag_lane"]), 1)
    eng = a._gbdt._aligned_eng_ref
    assert int(eng.park_counters["rows_parked"]) == 450
    eng.set_bag(np.ones(len(y), np.float32))
    eng.train_iter(0.5)     # the partition that takes them back in
    assert int(eng.park_counters["park_rounds"]) == 1
    assert int(eng.park_counters["rows_parked"]) == 0
    assert int(eng.park_begin) == eng.NC
    eng.set_bag(np.ones(len(y), np.float32))
    eng.train_iter(0.5)
    # nothing is out and nothing was: no partition round is run
    assert int(eng.park_counters["park_rounds"]) == 0
    assert int(eng.park_counters["chunks_parked"]) == 0


@pytest.mark.parametrize("extra, why", [
    ({"objective": "multiclass", "num_class": 3}, "classes"),
    ({"tree_learner": "data", "num_machines": 2}, "mesh"),
    ({"categorical_feature": "0"}, "categorical"),
])
def test_engines_that_keep_the_path_they_had(extra, why):
    X, y = _data(1500)
    if why == "classes":
        y = np.floor(np.abs(X[:, 0]) * 1.4).clip(0, 2)
    if why == "categorical":
        X[:, 0] = np.floor(np.abs(X[:, 0]) * 3).clip(0, 7)
    trace.reset()
    a = _train(X, y, dict(ALIGNED, **BAG, **extra, num_leaves=4), 2)
    g = a._gbdt
    g._sync_train_score()
    eng = g._aligned_eng_ref
    assert eng is not None and eng.bagged and not eng.parks
    assert eng.park_counters == {}
    assert all("rows_parked" not in r for r in _iters())
    assert int(eng.park_begin) == eng.NC


def test_a_discarded_eager_round_is_undone_for_parked_rows_too():
    """With a valid set the next round is dispatched ahead of the eval
    and undone when training ends: the parked rows' walk goes out as the
    live rows' leaf values do."""
    X, y = _data(2000)
    Xv, yv = _data(500, seed=3)
    params = dict(BASE, **ALIGNED, **SAMPLING["bag_lane"], metric="auc")
    ds = lgb.Dataset(X, label=y, params=params).construct()
    vs = lgb.Dataset(Xv, label=yv, reference=ds, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    bst.add_valid(vs, "v")
    for _ in range(4):
        bst.update()
        bst.eval_valid()
    g = bst._gbdt
    assert g._aligned_eng_ref.parks and g._aligned_next is not None
    score = np.asarray(g.get_training_score())[0]
    np.testing.assert_allclose(score, _walk(bst, X), rtol=1e-5, atol=2e-6)


def test_dart_over_a_host_drawn_bag_walks_live_and_parked_rows_alike():
    X, y = _data(2000)
    trace.reset()
    a = _train(X, y, dict(ALIGNED, **BAG, boosting="dart", drop_rate=0.5,
                          skip_drop=0.3, learning_rate=0.3), 6)
    g = a._gbdt
    eng = g._aligned_eng_ref
    assert eng.parks and not g._bag_on_device
    a.eval_train()          # DART's own training score drops trees
    score = np.asarray(g.train_score.score[0])
    recs = _iters()
    assert sum(r["dart_dropped"] for r in recs) > 0
    assert [r["park_rounds"] for r in recs] == [1, 0, 0, 1, 0, 0]
    np.testing.assert_allclose(score, a.predict(X, raw_score=True),
                               rtol=1e-5, atol=5e-6)
