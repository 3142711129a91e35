"""`boosting=dart` on the aligned engine (CPU: Pallas interpret mode).

The engine's score is a lane of records that lie in another order after
every tree, so taking a dropped tree out and putting it back is a walk of
the committed tree over the records as they lie: `ops.aligned.walk_pass`.
Held here: the kernel against a numpy walk bit for bit, the engine's DART
against the fused loop's (same drop sets, same model within f32, training
scores equal to `predict`), the replay of an inexact round and of a
discarded eager dispatch, and the plain reference's schedule
(`benchmark/reference_dart.py`) against `DART`'s own bookkeeping.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmark import reference_dart
from lightgbm_tpu.config import Config
from lightgbm_tpu.models.boosting_variants import DART
from lightgbm_tpu.obs import trace
from lightgbm_tpu.ops import aligned
from lightgbm_tpu.utils import log

BASE = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
        "learning_rate": 0.3, "min_data_in_leaf": 20, "verbosity": -1,
        "metric": "none", "tpu_chunk": 256, "boosting": "dart",
        "drop_rate": 0.5, "skip_drop": 0.3, "drop_seed": 4}
ALIGNED = {"tpu_grow_mode": "aligned", "tpu_aligned_interpret": True}
LEAFWISE = {"tpu_grow_mode": "leafwise"}


# ---- the kernel -----------------------------------------------------------
def _random_tree(rng, nn, features, bins, np_, lp):
    """A leaf-wise grown tree in the walk's compact form: node n splits a
    random leaf, keeps it on the left and hangs leaf n + 1 on the right."""
    nodes = np.zeros((5, np_), np.int32)
    leaves = np.zeros((2, lp), np.int32)
    nodes[3], leaves[0] = -1, -1
    at = {0: (-1, 0)}
    for n in range(nn):
        leaf = int(rng.choice(len(at)))
        nodes[:, n] = (rng.integers(features), rng.integers(bins),
                       rng.integers(2), *at[leaf])
        at[leaf], at[n + 1] = (n, 1), (n, -1)
    for leaf, (parent, side) in at.items():
        leaves[:, leaf] = parent, side
    return nodes, leaves


def _numpy_walk(bins, nodes, leaves, nn, nb, db, mt, vals):
    """Row by row, node by node: DenseBin::Split's numerical routing."""
    kids = {}
    for n in range(nn):
        if nodes[3, n] >= 0:
            kids[nodes[3, n], nodes[4, n]] = n
    for leaf in range(nn + 1):
        if leaves[0, leaf] >= 0:
            kids[leaves[0, leaf], leaves[1, leaf]] = ~leaf
    out = np.zeros(len(bins), np.float32)
    for r, row in enumerate(bins):
        node = 0 if nn else ~0
        while node >= 0:
            f = nodes[0, node]
            b = int(row[f])
            default = (mt[f] == 1 and b == db[f]) \
                or (mt[f] == 2 and b == nb[f] - 1)
            left = bool(nodes[2, node]) if default else b <= nodes[1, node]
            node = kids[node, 1 if left else -1]
        out[r] = vals[~node]
    return out


@pytest.mark.parametrize("rows,features,bins,chunk,trees,leaves,missing", [
    (512, 6, 63, 256, 1, 8, 0),     # full chunks, 6-bit words, no missing
    (700, 6, 63, 256, 1, 8, 1),     # a part-filled chunk, zero as missing
    (700, 13, 255, 256, 3, 31, 2),  # 8-bit words, NaN bins, three trees
    (500, 5, 15, 128, 8, 5, None),  # 4-bit words, every type, eight trees
], ids=["full-none", "part-zero", "part-nan-3trees", "mixed-8trees"])
def test_walk_pass_equals_numpy_walk_bit_for_bit(rows, features, bins,
                                                 chunk, trees, leaves,
                                                 missing):
    rng = np.random.default_rng(rows + trees)
    x = rng.integers(0, bins, (rows, features)).astype(np.uint8)
    rec, wcnt, _, cnts, bits = aligned.pack_records(
        x, np.zeros(rows, np.float32), None, chunk, max_bin=bins)
    lane = aligned.lane_layout(wcnt)[0]["score"]
    # two chunks of no row behind the data: they stay as they are
    rec = np.concatenate([rec, rng.integers(
        -9, 9, (2,) + rec.shape[1:]).astype(np.int32)])
    cnts = np.concatenate([cnts, [0, 0]]).astype(np.int32)
    score = rng.standard_normal((len(rec), chunk)).astype(np.float32)
    rec[:, lane, :] = score.view(np.int32)
    nb = np.full(features, bins, np.int32)
    db = rng.integers(0, bins, features).astype(np.int32)
    mt = rng.integers(0, 3, features).astype(np.int32) if missing is None \
        else np.full(features, missing, np.int32)
    np_, lp, w8, fp = aligned.walk_dims(leaves, wcnt, bits)
    held = []
    for t in range(aligned.WALK_TREES):
        nn = leaves - 1 if t == 0 else int(rng.integers(0, leaves))
        vals = np.zeros(lp, np.float32)
        vals[:nn + 1] = rng.standard_normal(nn + 1) \
            * 10.0 ** rng.integers(-3, 3)
        held.append((*_random_tree(rng, nn, features, bins, np_, lp), nn,
                     vals))
    tabs = jax.vmap(lambda n, l, k: aligned.walk_expand(
        n, l, k, jnp.asarray(nb), jnp.asarray(db), jnp.asarray(mt), w8=w8,
        bits=bits, fp=fp))(
            jnp.asarray(np.stack([h[0] for h in held])),
            jnp.asarray(np.stack([h[1] for h in held])),
            jnp.asarray(np.array([h[2] for h in held], np.int32)))
    got = np.asarray(aligned.walk_pass(
        jnp.asarray(rec), jnp.asarray(cnts), jnp.int32(trees), *tabs,
        jnp.asarray(np.stack([h[3] for h in held]))[:, :, None],
        chunk=chunk, wcnt=wcnt, bits=bits, lane=lane, interpret=True))
    want_score = score.copy().reshape(-1)
    for nodes, leaf_tab, nn, vals in held[:trees]:
        want_score[:rows] += _numpy_walk(x, nodes, leaf_tab, nn, nb, db,
                                         mt, vals)
    want = rec.copy()
    want[:, lane, :] = want_score.reshape(score.shape).view(np.int32)
    np.testing.assert_array_equal(got, want)


# ---- the engine against the fused loop ------------------------------------
def _data(n=1800, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] * X[:, 2]
          + 0.3 * rng.standard_normal(n)) > 0).astype(np.float32)
    X[rng.random((n, f)) < 0.1] = np.nan     # bins that take the default side
    return X, y


def _train(X, y, extra, iters, valid=None):
    params = dict(BASE, **extra)
    ds = lgb.Dataset(X, label=y, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    if valid is not None:
        bst.add_valid(lgb.Dataset(valid[0], label=valid[1], params=params,
                                  reference=ds).construct(), "valid")
    trace.reset()
    for _ in range(iters):
        bst.update()
    return bst


def _drops():
    return [(r["iter"], r["skipped"], r["dropped"], r["shrinkage"])
            for r in trace.seams("dart.drop")]


def _same_model(a, b, rtol=2e-4, atol=2e-6):
    assert len(a.trees) == len(b.trees)
    for ta, tb in zip(a.trees, b.trees):
        k = ta.num_leaves - 1
        assert ta.num_leaves == tb.num_leaves
        assert list(ta.split_feature[:k]) == list(tb.split_feature[:k])
        assert list(ta.threshold_in_bin[:k]) == list(tb.threshold_in_bin[:k])
        assert ta.shrinkage == pytest.approx(tb.shrinkage, rel=1e-12)
        np.testing.assert_allclose(ta.leaf_value[:ta.num_leaves],
                                   tb.leaf_value[:tb.num_leaves],
                                   rtol=rtol, atol=atol)


def _scores(bst):
    bst._gbdt._sync_train_score()
    return np.asarray(bst._gbdt.train_score.score[0])


@pytest.mark.parametrize("mode,seed", [
    ({}, 0), ({"xgboost_dart_mode": True}, 2), ({"uniform_drop": True}, 0)],
    ids=["default", "xgboost", "uniform"])
def test_engine_dart_equals_the_fused_loops(mode, seed):
    # (seed 0 under xgboost_dart_mode has two thresholds of one gain in
    # its tree 7, and f32 noise picks between them)
    X, y = _data(seed=seed)
    seen = []
    log.register_callback(lambda line: seen.append(log.parse_event(line)))
    try:
        a = _train(X, y, dict(ALIGNED, verbosity=1, **mode), iters=10)
    finally:
        log.register_callback(None)
        log.set_verbosity(1)
    drops_a, score_a = _drops(), _scores(a)
    assert [e["path"] for e in seen
            if e and e["event"] == "train_path"] == ["aligned"]
    assert not [e for e in seen if e and e["event"] == "aligned_fallback"]
    assert a._gbdt._aligned_pipeline_depth() == 8
    # trees stayed device specs all the way: nothing pulled them
    assert sum(len(d[2]) for d in drops_a) >= 8
    iters = {r["iter"]: r for r in trace.seams("aligned.iter")}
    for it, _, dropped, _ in drops_a:
        assert iters[it]["dart_dropped"] == len(dropped)
        assert iters[it]["rows_walked"] == 2 * len(dropped) * len(y)
        assert iters[it]["walk_passes"] \
            == 2 * -(-len(dropped) // aligned.WALK_TREES)
    b = _train(X, y, dict(LEAFWISE, **mode), iters=10)
    assert drops_a == _drops()
    assert a._gbdt.tree_weight == b._gbdt.tree_weight
    _same_model(a, b)
    # tests/test_variants.py's contract: the scores training kept are
    # the model's
    np.testing.assert_allclose(score_a, a.predict(X, raw_score=True),
                               atol=5e-6)
    np.testing.assert_allclose(a.predict(X, raw_score=True),
                               b.predict(X, raw_score=True), atol=5e-5)


def test_dump_in_the_middle_walks_host_trees_from_then_on():
    """`dump_model()` turns every spec into a host tree; the trees dropped
    after it are walked from their leaf values, the rest as before."""
    X, y = _data()
    a = _train(X, y, ALIGNED, iters=5)
    assert a.dump_model()["tree_info"][0]["shrinkage"] < 0.3
    for _ in range(5):
        a.update()
    b = _train(X, y, LEAFWISE, iters=10)
    _same_model(a, b)
    np.testing.assert_allclose(_scores(a), a.predict(X, raw_score=True),
                               atol=5e-6)


def test_valid_sets_follow_the_dropped_trees():
    X, y = _data()
    Xv, yv = _data(n=500, seed=1)
    a = _train(X, y, dict(ALIGNED, metric="binary_logloss"), 9, (Xv, yv))
    got = a.eval_valid()
    assert a._gbdt._iter_path == "aligned"
    assert a._gbdt._aligned_pipeline_depth() == 1
    np.testing.assert_allclose(
        np.asarray(a._gbdt.valid_scores[0].score[0]),
        a.predict(Xv, raw_score=True), atol=5e-6)
    np.testing.assert_allclose(_scores(a), a.predict(X, raw_score=True),
                               atol=5e-6)
    b = _train(X, y, dict(LEAFWISE, metric="binary_logloss"), 9, (Xv, yv))
    assert got[0][2] == pytest.approx(b.eval_valid()[0][2], abs=1e-5)


def test_inexact_round_replays_the_stashed_drop_set():
    """A starved speculation budget makes rounds inexact inside the
    8-deep queue: the walks of such a round and of those behind it add
    nothing, the fallback and the replays drop what the discarded
    dispatches drew, and the model is the fused loop's."""
    X, y = _data(n=2000)
    extra = {"tpu_level_spec": 0.6, "num_leaves": 31, "min_data_in_leaf": 5}
    a = _train(X, y, dict(ALIGNED, **extra), iters=9)
    drops_a = _drops()
    eng = a._gbdt._aligned_eng_ref
    assert getattr(eng, "fallbacks", 0) > 0, "needs a fallback to mean much"
    b = _train(X, y, dict(LEAFWISE, **extra), iters=9)
    # a replayed round is drawn once and recorded once
    assert drops_a == _drops()
    assert a._gbdt.tree_weight == pytest.approx(b._gbdt.tree_weight)
    _same_model(a, b)
    np.testing.assert_allclose(_scores(a), a.predict(X, raw_score=True),
                               atol=2e-5)


def test_discarded_eager_dispatch_leaves_the_lane_as_it_was():
    """With a valid set the next round is dispatched before the metric is
    read; a drain discards it. Its walks and its build come out of the
    lane, its draw goes back into the stream, and the round drawn again
    is the same round."""
    X, y = _data()
    Xv, yv = _data(n=400, seed=1)
    a = _train(X, y, dict(ALIGNED, metric="binary_logloss"), 6, (Xv, yv))
    g = a._gbdt
    assert g._aligned_next is not None
    eager = g._aligned_sample
    assert eager.iter == 6 and eager.dropped, "wants a dropping round"
    weights = list(g.tree_weight)
    np.testing.assert_allclose(_scores(a), a.predict(X, raw_score=True),
                               atol=5e-6)       # the drain discarded it
    assert g._aligned_next is None
    assert len(g.tree_weight) == 6 and g.tree_weight != weights
    a.update()
    again = [d for d in _drops() if d[0] == 6]
    assert len(again) == 2 and again[0] == again[1] \
        == (6, eager.skipped, list(eager.dropped), eager.shrinkage)
    b = _train(X, y, dict(LEAFWISE, metric="binary_logloss"), 7, (Xv, yv))
    _same_model(a, b)


# ---- what the gate still refuses -----------------------------------------
@pytest.mark.parametrize("extra,names", [
    (dict(objective="multiclass", num_class=3), "multiclass"),
    (dict(tree_learner="data", num_machines=2), "tree_learner=data"),
    (dict(num_leaves=1025), "1024 leaves"),
], ids=["multiclass", "data-parallel", "leaves"])
def test_what_stays_off_the_engine_is_named(extra, names):
    X, y = _data(n=600)
    if "num_class" in extra:
        y = np.digitize(X[:, 0], [-0.5, 0.5]).astype(np.float32)
    params = dict(BASE, **ALIGNED, **extra)
    ds = lgb.Dataset(np.nan_to_num(X), label=y, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    assert names in bst._gbdt._aligned_variant_gate()


# ---- the plain reference --------------------------------------------------
@pytest.mark.parametrize("uniform", [False, True], ids=["weighted", "uniform"])
@pytest.mark.parametrize("xgboost", [False, True], ids=["dart", "xgboost"])
def test_reference_schedule_is_darts_own_bookkeeping(uniform, xgboost):
    """200 iterations of `DART`'s draws and weights with no tree built,
    against `reference_dart.drop_schedule`."""
    params = dict(boosting="dart", learning_rate=0.1, drop_seed=4,
                  uniform_drop=uniform, xgboost_dart_mode=xgboost,
                  max_drop=7)
    cfg = Config.from_params(params)
    dart = DART.__new__(DART)
    dart.cfg, dart.iter, dart.num_init_iteration = cfg, 0, 0
    dart.tree_weight, dart.sum_weight = [], 0.0
    dart._drop_rng = np.random.RandomState(cfg.drop_seed)
    want = reference_dart.drop_schedule(
        4, 200, 0.1, cfg.drop_rate, cfg.max_drop, cfg.skip_drop, uniform,
        xgboost)
    assert max(len(w["dropped"]) for w in want) == 7
    weight = []
    for w in want:
        s = dart._draw_drop()
        assert (s.iter, s.skipped, list(s.dropped), s.shrinkage) \
            == (w["iter"], w["skipped"], w["dropped"], w["shrinkage"])
        for i in s.dropped:
            weight[i] *= s.keep
            dart._reweigh_dropped(i, float(len(s.dropped)))
        weight.append(s.shrinkage)
        dart.shrinkage_rate = s.shrinkage
        dart._weigh_new_tree()
        dart.iter += 1
        assert weight == w["weights"]
        if not uniform:
            assert dart.tree_weight == w["weights"]


def test_reference_imports_nothing_of_the_program():
    with open(reference_dart.__file__) as f:
        assert "lightgbm_tpu" not in f.read().replace(
            "`lightgbm_tpu", "")
