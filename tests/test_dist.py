"""Distributed training runtime (`lightgbm_tpu.dist`): topology
resolution, mesh-sharded dataset placement, global-sync bin finding, and
the byte-equal model contract — a 4-shard ``tree_learner=data`` run under
the 8-device virtual CPU mesh (conftest.py) must serialize to the SAME
bytes as the single-device learner when ``tpu_use_f64_hist`` pins
histogram accumulation to order-independent f64.
"""
import numpy as np
import pytest

import jax
import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.dist import binning as dist_binning
from lightgbm_tpu.dist import runtime as dist_runtime
from lightgbm_tpu.io.dataset import Dataset
from lightgbm_tpu.parallel import (DataParallelTreeLearner,
                                   FeatureParallelTreeLearner,
                                   VotingParallelTreeLearner,
                                   make_parallel_learner)
from lightgbm_tpu.utils import log as lgb_log


def _make_problem(n=700, f=6, seed=5, classes=2):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float64)
    margin = X[:, 0] + 0.6 * X[:, 1] * X[:, 2] - 0.4 * np.abs(X[:, 3])
    if classes == 2:
        y = (margin + 0.2 * rng.standard_normal(n) > 0).astype(np.float64)
    else:
        y = np.floor((1.0 / (1.0 + np.exp(-margin)))
                     * classes * 0.999).astype(np.float64)
    return X, y


def _train(X, y, params, num_round=6):
    ds = lgb.Dataset(X, label=y, params=params).construct()
    booster = lgb.Booster(params=dict(params), train_set=ds)
    for _ in range(num_round):
        booster.update()
    return booster


BASE = {"objective": "binary", "num_leaves": 7, "learning_rate": 0.2,
        "min_data_in_leaf": 5, "verbosity": -1, "metric": "none",
        # the topology-parity contract: f64 accumulation of f32 payloads
        # is exact, so the single f64->f32 rounding after the psum gives
        # identical values on every mesh width
        "tpu_use_f64_hist": True}


# ---------------------------------------------------------------------------
# topology resolution + factory
# ---------------------------------------------------------------------------

def test_num_shards_resolution():
    nd = len(jax.devices())
    assert nd == 8, "conftest must force an 8-device mesh"
    assert dist_runtime.num_shards(Config(tree_learner="data")) == nd
    assert dist_runtime.num_shards(
        Config(tree_learner="data", num_machines=4)) == 4
    # the explicit device carve-out wins over num_machines
    assert dist_runtime.num_shards(
        Config(tree_learner="data", num_machines=4,
               tpu_dist_devices=2)) == 2
    # asking for more shards than devices is an error, never a
    # silently narrower mesh
    for over in (dict(num_machines=64), dict(tpu_dist_devices=9)):
        with pytest.raises(ValueError, match="more shards than the 8 visible"):
            dist_runtime.num_shards(Config(tree_learner="data", **over))
    assert not dist_runtime.active(Config())           # serial
    assert not dist_runtime.active(
        Config(tree_learner="data", tpu_dist_devices=1))
    assert dist_runtime.active(Config(tree_learner="voting"))


def test_make_parallel_learner_factory():
    X, y = _make_problem(n=300)
    cfg = Config(tree_learner="data", num_machines=2,
                 min_data_in_leaf=5, verbosity=-1)
    ds = Dataset.from_matrix(X, label=y, config=cfg)
    cases = {"data": DataParallelTreeLearner,
             "feature": FeatureParallelTreeLearner,
             "voting": VotingParallelTreeLearner}
    for mode, cls in cases.items():
        c = Config(tree_learner=mode, num_machines=2,
                   min_data_in_leaf=5, verbosity=-1)
        learner = make_parallel_learner(c, ds)
        assert type(learner) is cls
    with pytest.raises(ValueError, match="serial"):
        make_parallel_learner(Config(), ds)


# ---------------------------------------------------------------------------
# distributed bin finding
# ---------------------------------------------------------------------------

def test_merged_sample_reconstructs_single_host_draw():
    X, _ = _make_problem(n=997, f=4)
    seed, cnt = 11, 400
    rng = np.random.RandomState(seed)
    ref = X[np.sort(rng.choice(len(X), cnt, replace=False))]
    for shards in (1, 3, 4, 8):
        got = dist_binning.merged_sample(X, cnt, seed, shards)
        np.testing.assert_array_equal(got, ref)


def test_distributed_bin_boundaries_bitwise_equal():
    X, y = _make_problem(n=900, f=5)
    # sample_cnt < n so the sampled path (not the trivial all-rows one)
    # is what the shards must reconstruct
    serial_cfg = Config(bin_construct_sample_cnt=500, verbosity=-1)
    ds_serial = Dataset.from_matrix(X, label=y, config=serial_cfg)
    dist_cfg = Config.from_params(
        {"bin_construct_sample_cnt": 500, "verbosity": -1,
         "tree_learner": "data", "num_machines": 4})
    assert dist_cfg.is_parallel_find_bin    # auto-set by _check_conflicts
    ds_dist = Dataset.from_matrix(X, label=y, config=dist_cfg)
    assert len(ds_serial.mappers) == len(ds_dist.mappers)
    for ms, md in zip(ds_serial.mappers, ds_dist.mappers):
        assert ms.to_dict() == md.to_dict()   # repr'd f64 bounds: bitwise
    np.testing.assert_array_equal(ds_serial.bins, ds_dist.bins)
    assert ds_dist._bin_sync_ms >= 0.0


# ---------------------------------------------------------------------------
# mesh-sharded dataset placement
# ---------------------------------------------------------------------------

def test_dataset_shard_cache_and_hbm_owners():
    from lightgbm_tpu.obs import memory as obs_memory
    X, y = _make_problem(n=500)
    cfg = Config(tree_learner="data", num_machines=4, verbosity=-1)
    ds = Dataset.from_matrix(X, label=y, config=cfg)
    mesh = dist_runtime.build_mesh(cfg)
    placed = ds.shard(mesh)
    assert placed["nd"] == 4
    assert placed["per_shard"] == 125
    assert ds.shard(mesh) is placed          # cached per mesh
    owners = obs_memory.owners_bytes()
    expect = 2 * 125 * ds.bins.shape[1] * ds.bins.itemsize
    # this dataset's own rows, by the names the ledger gave them: another
    # dataset still alive in the process (a test before this one in the
    # same worker) keeps the plain names, and this one's get a `#k`
    assert len(placed["owners"]) == 4
    for i, name in enumerate(placed["owners"]):
        assert name.partition("#")[0] == f"dist/shard_bytes/d{i}"
        assert owners[name]["bytes"] == expect, owners


def test_learner_reuses_dataset_shard_cache():
    X, y = _make_problem(n=600)
    params = dict(BASE, tree_learner="data", num_machines=4)
    ds = lgb.Dataset(X, label=y, params=params).construct()
    booster = lgb.Booster(params=dict(params), train_set=ds)
    learner = booster._gbdt.learner
    assert isinstance(learner, DataParallelTreeLearner)
    cache = ds._handle._shard_cache
    assert learner.bins_sharded is cache["bins"]
    assert learner.bins_T_sharded is cache["bins_T"]


def test_dist_events_emitted():
    lines = []
    lgb_log.register_callback(lines.append)
    try:
        X, y = _make_problem(n=400)
        params = dict(BASE, tree_learner="data", num_machines=4,
                      verbosity=2)
        _train(X, y, params, num_round=2)
    finally:
        lgb_log.register_callback(None)
    events = [e for e in (lgb_log.parse_event(ln) for ln in lines) if e]
    kinds = {e["event"] for e in events}
    assert "dist_shard" in kinds
    assert "dist_init" in kinds
    init = next(e for e in events if e["event"] == "dist_init")
    assert init["tree_learner"] == "data"
    assert init["shards"] == 4
    shard_ev = next(e for e in events if e["event"] == "dist_shard")
    assert shard_ev["rows_per_shard"] == 100


# ---------------------------------------------------------------------------
# the byte-equal model contract at 4 shards
# ---------------------------------------------------------------------------

def _byte_equal_case(params, classes=2, n=700, num_round=6):
    X, y = _make_problem(n=n, classes=classes)
    serial = _train(X, y, dict(params, tree_learner="serial"),
                    num_round=num_round)
    dist = _train(X, y, dict(params, tree_learner="data", num_machines=4),
                  num_round=num_round)
    assert isinstance(dist._gbdt.learner, DataParallelTreeLearner)
    assert dist._gbdt.learner.nd == 4
    assert dist.model_to_string() == serial.model_to_string()


def test_byte_equal_model_plain():
    _byte_equal_case(BASE)


def test_byte_equal_model_bagging():
    _byte_equal_case(dict(BASE, bagging_fraction=0.7, bagging_freq=1,
                          bagging_seed=3, feature_fraction=0.8))


def test_byte_equal_model_multiclass():
    _byte_equal_case(dict(BASE, objective="multiclass", num_class=3,
                          metric="none"), classes=3, n=750)
