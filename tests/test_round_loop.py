"""The boosting driver's one observed round (`GBDT._train_one_iter_observed`).

`train_one_iter` runs the implementation directly when neither
`tpu_trace` nor `tpu_metrics` is set, else through ONE wrapper. Its
contract, over observation mode x training path: observing changes no
model byte; the tracer commits exactly one round record an iteration
with a fixed set of keys; the metrics count every round; and only the
tracer fences.
"""
import functools

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import memory as obs_memory
from lightgbm_tpu.obs import metrics as obs_metrics
from lightgbm_tpu.obs import trace as obs_trace

ITERS = 3
PATHS = {
    "aligned": {"tpu_grow_mode": "aligned", "tpu_aligned_interpret": True,
                "tpu_chunk": 256},
    "fused": {"tpu_grow_mode": "leafwise"},
}
MODES = {
    "off": {},
    "metrics": {"tpu_metrics": True},
    "trace": {"tpu_trace": True},
    "both": {"tpu_metrics": True, "tpu_trace": True},
}
ROUND_KEYS = {"kind", "round", "wall_ms", "device_ms", "traces", "path",
              "aligned", "fallbacks", "trees", "bag_cnt", "finished", "t0"}
# present only on a round whose gate left notes (both or neither)
NOTE_KEYS = {"gate_notes", "hist_spill"}


@pytest.fixture(autouse=True)
def _clean_planes():
    obs_metrics.reset()
    obs_memory.reset()
    obs_trace.reset()
    yield
    obs_trace.disable()
    obs_trace.reset()
    obs_metrics.reset()
    obs_memory.reset()


def _train(path, mode, trace_dir=None):
    rng = np.random.default_rng(7)
    X = rng.standard_normal((600, 6)).astype(np.float32)
    y = ((X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
          + 0.3 * rng.standard_normal(600)) > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "min_data_in_leaf": 20, "verbosity": -1, "metric": "none",
              **PATHS[path], **MODES[mode]}
    if params.get("tpu_trace"):
        params["tpu_trace_dir"] = str(trace_dir)
    ds = lgb.Dataset(X, label=y, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(ITERS):
        bst.update()
    return bst


@functools.lru_cache(maxsize=None)
def _off_model(path):
    """The unobserved run's model text, trained once a path."""
    return _train(path, "off").model_to_string()


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("mode", list(MODES))
def test_observed_round_contract(mode, path, tmp_path, monkeypatch):
    want = _off_model(path)
    fences = []
    monkeypatch.setattr(
        obs_trace, "_block",
        lambda x: fences.append(1) or jax.block_until_ready(x))
    bst = _train(path, mode, tmp_path)
    g = bst._gbdt
    traced, metered = "tpu_trace" in MODES[mode], "tpu_metrics" in MODES[mode]

    assert bst.model_to_string() == want, \
        f"observation mode {mode!r} changed the {path} model"
    assert g._iter_path.startswith(path)

    assert (g.telemetry is not None) is traced
    if traced:
        rr = g.telemetry.round_records()
        assert [r["round"] for r in rr] == list(range(ITERS))
        for r in rr:
            extra = set(r) - ROUND_KEYS
            assert set(r) >= ROUND_KEYS and extra in (set(), NOTE_KEYS), r
            assert 0 <= r["device_ms"] <= r["wall_ms"], r
            assert r["aligned"] is (path == "aligned")
            assert r["path"] == g._iter_path and r["fallbacks"] == 0
        assert [r["trees"] for r in rr] == [1, 2, 3]
        assert len(fences) >= ITERS
        g.telemetry.close()
    else:
        assert fences == [], f"mode {mode!r} fenced without the tracer"
        assert obs_trace.fence_count == 0

    assert (g._metrics is not None) is metered
    snap = obs_metrics.snapshot()
    if metered:
        assert snap["counters"]["train_rounds_total"] == float(ITERS)
        assert snap["counters"]["train_trees_total"] == float(ITERS)
        assert snap["histograms"]["train_round_ms"]["count"] == ITERS
    else:
        assert snap["counters"] == {}
