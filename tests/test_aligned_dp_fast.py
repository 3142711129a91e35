"""The aligned engine under data-parallel (`tree_learner=data`) on four
virtual CPU devices, at toy size: the tier-1 guard of the path the
four-chip benchmark cell runs (`tests/test_aligned_dp.py` holds more
shapes and is slow).

Upstream's data-parallel learner sums every shard's histograms before it
chooses a split, so it grows the tree a serial learner grows over all
the rows: the mesh has to give the serial engine's trees and scores, and
tree 0's root has to be the split of the numpy data-parallel reference
(`benchmark/reference_dp.py`: per-shard histograms summed, then the
largest gain). The counters by shard and the all-reduce's phase are what
the cell's per-layer metrics read.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmark import reference_dp
from lightgbm_tpu.obs import phases, trace

SHARDS = 4
ROWS = 4000
PARAMS = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.2,
          "min_data_in_leaf": 5, "verbosity": -1, "metric": "none",
          "tpu_grow_mode": "aligned", "tpu_aligned_interpret": True,
          "tpu_chunk": 256, "tpu_level_spec": 1.5}
ITERATIONS = 3


def _problem():
    rng = np.random.default_rng(7)
    x = rng.standard_normal((ROWS, 8))
    margin = x[:, 0] + 0.7 * x[:, 1] * x[:, 2] - 0.5 * np.abs(x[:, 3])
    y = (margin + 0.2 * rng.standard_normal(ROWS) > 0).astype(np.float64)
    return x, y


def _train(x, y, params):
    """(booster, its `aligned.iter` records, the build's phase table,
    the core dataset)."""
    trace.reset()
    phases.forget()
    ds = lgb.Dataset(x, label=y, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(ITERATIONS):
        bst.update()
    bst.eval_train()
    table = phases.table(only=["build"])
    return bst, trace.seams("aligned.iter"), table, ds._handle


@pytest.fixture(scope="module")
def runs():
    x, y = _problem()
    serial = _train(x, y, dict(PARAMS, tree_learner="serial"))
    mesh = _train(x, y, dict(PARAMS, tree_learner="data",
                             num_machines=SHARDS))
    return x, y, serial, mesh


def test_the_mesh_grows_the_serial_engines_trees_and_scores(runs):
    x, _, (serial, *_), (mesh, *_) = runs
    eng = mesh._gbdt._aligned_eng_ref
    assert eng is not None and eng.axis == "data" and eng.nd == SHARDS
    trees_s = serial.dump_model()["tree_info"]
    trees_m = mesh.dump_model()["tree_info"]
    assert len(trees_s) == len(trees_m) == ITERATIONS

    def splits(node):
        if "leaf_value" in node:
            return [("leaf", node["leaf_count"])]
        return ([(node["split_feature"], node["threshold"],
                  node["internal_count"])]
                + splits(node["left_child"]) + splits(node["right_child"]))
    for ts, tm in zip(trees_s, trees_m):
        assert splits(ts["tree_structure"]) == splits(tm["tree_structure"])
    np.testing.assert_allclose(mesh.predict(x, raw_score=True),
                               serial.predict(x, raw_score=True),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(mesh._gbdt.train_score.score[0]),
        np.asarray(serial._gbdt.train_score.score[0]), rtol=1e-5, atol=1e-7)


def test_tree_0s_root_is_the_numpy_data_parallel_split(runs):
    _, y, _, (mesh, _, _, core) = runs
    bins = np.asarray(core.bins)
    want = reference_dp.root_split(
        bins, y, SHARDS, int(bins.max()) + 1,
        min_data_in_leaf=PARAMS["min_data_in_leaf"])
    root = mesh.dump_model()["tree_info"][0]["tree_structure"]
    assert root["split_feature"] == int(core.real_feature_idx[want["feature"]])
    left = root["left_child"]
    assert left.get("internal_count", left.get("leaf_count")) \
        == want["left_count"]
    assert root["split_gain"] == pytest.approx(want["gain"], rel=1e-4)


def test_the_counters_by_shard_add_up_to_the_serial_engines(runs):
    _, _, (_, iters_s, *_), (mesh, iters_m, *_) = runs
    eng = mesh._gbdt._aligned_eng_ref
    per = -(-ROWS // SHARDS)
    assert eng.rows_by_shard == [per] * SHARDS
    (pack,) = trace.seams("aligned.pack")
    assert pack["rows_by_shard"] == [per] * SHARDS
    assert len(pack["upload_bytes_by_shard"]) == SHARDS
    assert sum(pack["upload_bytes_by_shard"]) == pack["upload_bytes"]
    assert len(iters_s) == len(iters_m) == ITERATIONS
    root_bytes, round_bytes = eng.psum_bytes
    for s, m in zip(iters_s, iters_m):
        assert s["rounds"] == m["rounds"]
        col = {name: i for i, name in enumerate(m["columns"])}
        serial_rows = [row[col["rows_split"]] for row in s["table"]]
        shard_rows = m["rows_split_by_shard"]
        assert [sum(r) for r in shard_rows] == serial_rows
        # the existing key keeps a chip's share: the mean over shards
        assert [row[col["rows_split"]] for row in m["table"]] == \
            pytest.approx([sum(r) / SHARDS for r in shard_rows])
        # every shard splits the same leaves
        assert m["leaves_split_by_shard"] == [
            [row[col["leaves_split"]]] * SHARDS for row in s["table"]]
        assert m["psum_bytes"] == root_bytes + m["rounds"] * round_bytes
        assert "psum_bytes" not in s and "rows_split_by_shard" not in s
    # a round all-reduces the [K] children's histograms the root's shape
    assert round_bytes == min(eng.S - 1, 256) * root_bytes


def test_the_all_reduce_has_its_phase_on_the_mesh_alone(runs):
    _, _, (_, _, table_s, _), (_, _, table_m, _) = runs
    in_mesh = {r["phase"] for r in table_m}
    assert "dp.psum" in in_mesh
    assert "dp.psum" not in {r["phase"] for r in table_s}
    assert any(r["opcode"].startswith("all-reduce") for r in table_m
               if r["phase"] == "dp.psum")
    assert not any(r["opcode"].startswith("all-reduce") for r in table_s)
