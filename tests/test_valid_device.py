"""Device valid-set scoring + device metrics.

The aligned engine packs a validation set's rows into records once and
walks each committed tree over them with `walk_pass` (the XLA walkers
over row-order bins stay for the engines the record walk cannot follow:
K trees an iteration, a mesh, bundles, categorical splits, over 1,024
leaves). These tests run the aligned builder in interpret mode on CPU and
hold the valid scores and metrics to the host traversal path and to a
float64 numpy walk of the dumped model (`benchmark/reference.py`).
"""
import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmark import reference
from lightgbm_tpu.obs import trace as obs_trace


def _make(n=3000, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] * X[:, 2]
          + 0.3 * rng.standard_normal(n)) > 0).astype(np.float32)
    return X, y


def _train_with_valid(mode, iters=6):
    X, y = _make()
    Xv, yv = _make(1200, seed=1)
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 20,
              "verbosity": -1, "metric": "auc,binary_logloss",
              "tpu_grow_mode": mode,
              "tpu_aligned_interpret": mode == "aligned",
              "tpu_chunk": 256}
    ds = lgb.Dataset(X, label=y, params=params).construct()
    vs = lgb.Dataset(Xv, label=yv, reference=ds, params=params).construct()
    res = {}
    bst = lgb.train(params, ds, iters, valid_sets=[vs],
                    valid_names=["v"], evals_result=res,
                    verbose_eval=False)
    return bst, res


def test_device_valid_scores_match_host_traversal():
    bst_a, res_a = _train_with_valid("aligned")
    bst_l, res_l = _train_with_valid("leafwise")
    # identical trees => identical valid AUC curves (device walk vs the
    # leafwise host-side traversal application)
    auc_a = np.asarray(res_a["v"]["auc"])
    auc_l = np.asarray(res_l["v"]["auc"])
    assert np.allclose(auc_a, auc_l, atol=2e-6), (auc_a, auc_l)
    ll_a = np.asarray(res_a["v"]["binary_logloss"])
    ll_l = np.asarray(res_l["v"]["binary_logloss"])
    assert np.allclose(ll_a, ll_l, atol=1e-5), (ll_a, ll_l)


def test_device_auc_matches_host_auc():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ops.metrics import AUCMetric

    class Meta:
        weight = None
        init_score = None

    rng = np.random.default_rng(3)
    n = 30000
    score = np.round(rng.standard_normal(n), 2)  # many ties
    label = (rng.random(n) < 1 / (1 + np.exp(-score))).astype(np.float64)
    cfg = Config.from_params({"objective": "binary"})
    m = AUCMetric(cfg)
    meta = Meta()
    meta.label = label
    m.init(meta, n)
    scores = score[None, :].astype(np.float64)
    host = m.eval(scores, None)[0][1]
    import jax.numpy as jnp
    dev = float(m.eval_dev(jnp.asarray(scores, jnp.float32), None)[0][1])
    assert abs(host - dev) < 1e-5, (host, dev)


def test_device_auc_weighted():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ops.metrics import AUCMetric

    class Meta:
        init_score = None

    rng = np.random.default_rng(5)
    n = 20000
    score = np.round(rng.standard_normal(n), 2)
    label = (rng.random(n) < 0.4).astype(np.float64)
    w = rng.random(n).astype(np.float64) + 0.1
    cfg = Config.from_params({"objective": "binary"})
    m = AUCMetric(cfg)
    meta = Meta()
    meta.label = label
    meta.weight = w
    m.init(meta, n)
    scores = score[None, :].astype(np.float64)
    host = m.eval(scores, None)[0][1]
    import jax.numpy as jnp
    dev = float(m.eval_dev(jnp.asarray(scores, jnp.float32), None)[0][1])
    assert abs(host - dev) < 5e-5, (host, dev)


def test_valid_with_early_stopping_aligned():
    X, y = _make(4000)
    Xv, yv = _make(1500, seed=2)
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "learning_rate": 0.3, "min_data_in_leaf": 20,
              "verbosity": -1, "metric": "auc",
              "tpu_grow_mode": "aligned", "tpu_aligned_interpret": True,
              "tpu_chunk": 256}
    ds = lgb.Dataset(X, label=y, params=params).construct()
    vs = lgb.Dataset(Xv, label=yv, reference=ds, params=params).construct()
    bst = lgb.train(params, ds, 40, valid_sets=[vs], valid_names=["v"],
                    early_stopping_rounds=5, verbose_eval=False)
    assert bst.best_iteration >= 1


def test_eager_discard_restores_state_and_determinism():
    """An eagerly-dispatched next iteration that gets discarded
    (mid-training sync) must leave NO trace: undo_spec_scores restores
    the score lane and the column/bag sampling RNGs rewind, so training
    continues bit-identically to a run that never synced."""
    X, y = _make(3000)
    Xv, yv = _make(1000, seed=2)
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 20,
              "verbosity": -1, "metric": "auc",
              "tpu_grow_mode": "aligned", "tpu_aligned_interpret": True,
              "tpu_chunk": 256, "feature_fraction": 0.7,
              "bagging_fraction": 0.8, "bagging_freq": 1}

    def run(interrupt):
        ds = lgb.Dataset(X, label=y, params=params).construct()
        vs = lgb.Dataset(Xv, label=yv, reference=ds,
                         params=params).construct()
        bst = lgb.Booster(params=params, train_set=ds)
        bst.add_valid(vs, "v")
        g = bst._gbdt
        for i in range(6):
            bst.update()
            g.eval_valid()
            if interrupt and i == 3:
                g._sync_train_score()   # discards the eager dispatch
        g.materialized_models()
        return [(list(t.split_feature_inner[:t.num_leaves - 1]),
                 np.asarray(t.leaf_value[:t.num_leaves]))
                for t in g.models]

    a = run(False)
    b = run(True)
    assert len(a) == len(b)
    for (fa, va), (fb, vb) in zip(a, b):
        assert fa == fb
        np.testing.assert_allclose(va, vb, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the packed validation set against the float64 reference
# ---------------------------------------------------------------------------

BASE = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
        "learning_rate": 0.3, "min_data_in_leaf": 20, "verbosity": -1,
        "metric": "auc,binary_logloss", "tpu_grow_mode": "aligned",
        "tpu_aligned_interpret": True, "tpu_chunk": 256}
# 1,300 valid rows: five whole chunks of 256 and a last one of 20
VALID_ROWS = 1300
KINDS = {
    "plain": {},
    "bagged": {"bagging_fraction": 0.8, "bagging_freq": 2,
               "feature_fraction": 0.8},
    "goss": {"boosting": "goss", "top_rate": 0.2, "other_rate": 0.1,
             "learning_rate": 0.5},
    # drops from iteration 3 on (tests/test_aligned_dart.py)
    "dart": {"boosting": "dart", "drop_rate": 0.5, "skip_drop": 0.3},
}


def _logloss(raw, y):
    p = 1.0 / (1.0 + np.exp(-raw))
    p = np.clip(p, 1e-15, 1 - 1e-15)
    return float(np.mean(-(y * np.log(p) + (1 - y) * np.log(1 - p))))


def _booster(params, n=2000, valid=True):
    X, y = _make(n)
    Xv, yv = _make(VALID_ROWS, seed=1)
    ds = lgb.Dataset(X, label=y, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    vs = lgb.Dataset(Xv, label=yv, reference=ds, params=params).construct()
    if valid:
        bst.add_valid(vs, "v")
    return bst, vs, Xv, yv


def _held_to_the_reference(bst, Xv, yv, said):
    raw = reference.raw_scores(bst.dump_model(), Xv.astype(np.float64))
    got = np.asarray(bst._gbdt.valid_scores[0].score[0], np.float64)
    np.testing.assert_allclose(got, raw, rtol=1e-6, atol=1e-6)
    vals = {name: v for _, name, v, _ in said}
    assert vals["auc"] == pytest.approx(reference.auc(raw, yv), abs=1e-6)
    assert vals["binary_logloss"] == pytest.approx(_logloss(raw, yv),
                                                   rel=1e-6)


@pytest.mark.parametrize("kind", list(KINDS))
def test_valid_scores_and_metrics_follow_the_reference(kind):
    obs_trace.reset()
    bst, _, Xv, yv = _booster(dict(BASE, **KINDS[kind]))
    for _ in range(5):
        bst.update()
        _held_to_the_reference(bst, Xv, yv, bst.eval_valid())
    g = bst._gbdt
    assert g._iter_path == "aligned"
    assert type(g.valid_scores[0]).__name__ == "_RecordScores"
    (pack,) = obs_trace.seams("valid.pack")
    assert (pack["walk"], pack["why"]) == ("records", None)
    if kind == "dart":
        assert any(r["dropped"] for r in obs_trace.seams("dart.drop"))


def test_a_valid_set_added_after_training_started_is_replayed():
    obs_trace.reset()
    bst, vs, Xv, yv = _booster(BASE, valid=False)
    for _ in range(3):
        bst.update()
    bst.add_valid(vs, "v")
    _held_to_the_reference(bst, Xv, yv, bst.eval_valid())
    for _ in range(2):
        bst.update()
        _held_to_the_reference(bst, Xv, yv, bst.eval_valid())
    (pack,) = obs_trace.seams("valid.pack")
    assert pack["walk"] == "records" and pack["iter"] is None


def test_the_packed_block_keeps_row_order_across_chunks():
    """Record order is row order: row r sits at position r % C of chunk
    r // C, the last chunk holds the 20 rows left over, and the bin words
    decode to the set's own bins."""
    bst, vs, _, _ = _booster(BASE)
    bst.update()
    su = bst._gbdt.valid_scores[0]
    eng = su.eng
    rec = np.asarray(su.rec)
    assert rec.shape == (6, eng.W, 256)
    assert np.asarray(su.cnts).tolist() == [256] * 5 + [20]
    bins = np.asarray(vs._handle.bins)
    bpw = 32 // eng.bits
    rows = rec.transpose(0, 2, 1).reshape(-1, eng.W)[:VALID_ROWS]
    for f in range(bins.shape[1]):
        word = rows[:, f // bpw].astype(np.uint32)
        got = (word >> (eng.bits * (f % bpw))) & ((1 << eng.bits) - 1)
        np.testing.assert_array_equal(got, bins[:, f])
    lane = rows[:, eng.lanes["score"]].view(np.float32)
    np.testing.assert_array_equal(np.asarray(su.score[0]), lane)
    raw = bst.predict(_make(VALID_ROWS, seed=1)[0], raw_score=True)
    np.testing.assert_allclose(lane, raw, rtol=1e-5, atol=1e-6)


def test_early_stopping_on_the_engine_stops_where_the_host_path_does():
    X, y = _make(3000)
    Xv, yv = _make(VALID_ROWS, seed=2)
    best = {}
    for mode in ("aligned", "leafwise"):
        params = dict(BASE, metric="binary_logloss", learning_rate=0.5,
                      tpu_grow_mode=mode,
                      tpu_aligned_interpret=mode == "aligned")
        ds = lgb.Dataset(X, label=y, params=params).construct()
        vs = lgb.Dataset(Xv, label=yv, reference=ds,
                         params=params).construct()
        bst = lgb.train(params, ds, 40, valid_sets=[vs], valid_names=["v"],
                        early_stopping_rounds=3, verbose_eval=False)
        best[mode] = (bst.best_iteration, bst.num_trees())
    assert best["aligned"] == best["leafwise"]
    assert best["aligned"][0] < 40


def _sparse(n=2400, f=20, dense=4, seed=3):
    rng = np.random.default_rng(seed)
    X = np.zeros((n, f), np.float32)
    X[:, :dense] = rng.standard_normal((n, dense))
    pick = rng.integers(0, f - dense + 1, n)    # one-hot block, or none
    on = pick < f - dense
    X[np.arange(n)[on], dense + pick[on]] = 1.0 + rng.random(on.sum())
    y = ((X[:, 0] + X[:, dense] + 0.2 * rng.standard_normal(n))
         > 0.3).astype(np.float32)
    return X, y


@pytest.mark.parametrize("kind,why", [
    ("multiclass", "K trees an iteration"),
    ("bundled", "bundled features")])
def test_a_set_the_record_walk_cannot_follow_takes_the_xla_walker(kind, why):
    if kind == "multiclass":
        X, y = _make(2000)
        y = (y + (X[:, 3] > 0.5)).astype(np.float32)
        params = dict(BASE, objective="multiclass", num_class=3,
                      metric="multi_logloss")
    else:
        X, y = _sparse()
        params = dict(BASE, metric="auc", enable_bundle=True)
    ds = lgb.Dataset(X[:1800], label=y[:1800], params=params).construct()
    vs = lgb.Dataset(X[1800:], label=y[1800:], reference=ds,
                     params=params).construct()
    obs_trace.reset()
    bst = lgb.Booster(params=params, train_set=ds)
    bst.add_valid(vs, "v")
    for _ in range(3):
        bst.update()
        said = bst.eval_valid()
    g = bst._gbdt
    assert g._iter_path.startswith("aligned")
    (pack,) = obs_trace.seams("valid.pack")
    assert pack["walk"] == "rows" and pack["why"].startswith(why)
    assert pack["chunks"] == 0 and pack["rows"] == len(X) - 1800
    assert type(g.valid_scores[0]).__name__ == "_ScoreUpdater"
    if kind == "bundled":
        np.testing.assert_allclose(
            np.asarray(g.valid_scores[0].score[0]),
            bst.predict(X[1800:], raw_score=True), rtol=1e-5, atol=1e-6)
    assert np.isfinite(said[0][2])


def test_eval_valid_pulls_once_a_round(monkeypatch):
    """The round's exactness flag and both metric values come in ONE
    `jax.device_get`, and nothing else of `eval_valid` reaches the
    device."""
    bst, _, _, _ = _booster(BASE)
    pulls = []
    real = jax.device_get
    for i in range(4):
        bst.update()
        monkeypatch.setattr(jax, "device_get",
                            lambda x: pulls.append(1) or real(x))
        said = bst.eval_valid()
        monkeypatch.setattr(jax, "device_get", real)
        assert len(pulls) == 1, i
        assert [n for _, n, _, _ in said] == ["auc", "binary_logloss"]
        pulls.clear()


def test_training_metrics_ride_the_round_for_a_caller_that_asks_each_time():
    """`eval_train` after every update gets its metrics queued with each
    round (no drain, no discarded round); at a drain now and then they
    are not queued, and no round pays a full materialisation for them."""
    bst, _, _, _ = _booster(BASE)
    g = bst._gbdt
    X, y = _make(2000)
    for _ in range(2):
        bst.update()
        bst.eval_valid()
        assert g._train_eval_stash is None
    bst.eval_train()                    # a drain
    bst.update()
    assert g._train_eval_stash is None
    bst.eval_train()
    bst.update()                        # the second in a row
    assert g._train_eval_stash is not None
    said = {name: v for _, name, v, _ in bst.eval_train()}
    raw = bst.predict(X, raw_score=True)
    assert said["binary_logloss"] == pytest.approx(_logloss(raw, y),
                                                   rel=1e-6)


@pytest.mark.parametrize("kind", ["plain", "bagged"])
def test_a_drain_keeps_the_round_dispatched_ahead_where_it_can(kind):
    """`eval_train` at a drain reads the training scores without the
    round dispatched ahead of its turn and leaves that round for the next
    update (plain boosting: nothing of it but its tree's score-lane
    update is on the records); a bagged engine, whose round also walks
    its parked rows, throws it away. Either way the model and every
    metric are a run's that never drained."""
    params = dict(BASE, **KINDS[kind])
    X, y = _make(2000)

    def run(drain):
        bst, _, Xv, yv = _booster(params)
        said = []
        for i in range(6):
            bst.update()
            said.append(bst.eval_valid())
            if drain and i in (1, 3):
                before = bst._gbdt._aligned_next
                got = {n: v for _, n, v, _ in bst.eval_train()}
                kept = bst._gbdt._aligned_next
                assert (kept is before) == (kind == "plain")
                # the log loss: a few trees' scores tie in many rows,
                # and AUC splits such ties by the last bit of the sums
                raw = bst.predict(X, raw_score=True)
                assert got["binary_logloss"] == pytest.approx(
                    _logloss(raw, y), rel=1e-6)
        _held_to_the_reference(bst, Xv, yv, said[-1])
        return bst.model_to_string(), said
    model_a, said_a = run(False)
    model_b, said_b = run(True)
    assert model_a == model_b
    np.testing.assert_allclose([[v for _, _, v, _ in s] for s in said_a],
                               [[v for _, _, v, _ in s] for s in said_b],
                               rtol=1e-6)


def test_a_round_kept_by_a_drain_is_recorded_there_once():
    """A window that ends in a drain holds the `aligned.iter` records of
    the builds it ran: the round the drain keeps for its turn is recorded
    at the drain, where its build is over, with the validation walk its
    tree is queued for, and not again at its turn."""
    obs_trace.reset()
    bst, _, _, _ = _booster(BASE)

    def step(n):
        for _ in range(n):
            bst.update()
            bst.eval_valid()
        bst.eval_train()
    step(3)
    (drain,) = obs_trace.seams("train.drain")
    step(3)
    recs = obs_trace.seams("aligned.iter")
    assert [r["iter"] for r in recs] == list(range(7))
    assert bst.num_trees() == 6 and bst._gbdt._aligned_next is not None
    after = [r for r in obs_trace.seams("aligned.dispatch")
             if r["t0"] > drain["t1"]]
    assert [r["iter"] for r in after] == [4, 5, 6]
    assert [r["iter"] for r in recs if r["t0"] > drain["t1"]] == [4, 5, 6]
    for r in recs:
        assert (r["valid_rows_walked"], r["valid_walk_passes"]) \
            == (VALID_ROWS, 1)
