"""Which builder a config trains on: the gate's decision table, pinned.

Four tree builders sit behind stacked gates (`DeviceTreeLearner
.aligned_mode_gate`, then `GBDT._aligned_eligible` /
`_aligned_variant_gate` / `_aligned_mc_eligible` / `_mega_fused_eligible`).
Each row is one config on at most 2,000 rows and one `update()`; it
asserts the `train_path` event's path and, where the aligned engine is
refused, the reason the event names. No row changes the gate: the table
says what the code decides today, so a PR that merges builders or gates
has something to be held to.
"""
import json

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.utils import log

# off the chip the engine's kernels only run interpreted
INTERPRET = {"tpu_aligned_interpret": True, "tpu_chunk": 256}
SEQUENTIAL = "sequential-only features (forced splits/CEGB)"


def _data(kind="binary", n=400, f=6):
    rng = np.random.default_rng(3)
    X = rng.standard_normal((n, f)).astype(np.float32)
    m = X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.3 * rng.standard_normal(n)
    if kind == "binary":
        return X, (m > 0).astype(np.float32), None
    if kind == "regression":
        return X, m.astype(np.float32), None
    if kind == "classes3":
        return X, np.digitize(m, [-0.5, 0.5]).astype(np.float32), None
    if kind == "classes128":
        return X, (np.arange(n) % 128).astype(np.float32), None
    if kind == "category":       # column 0 holds five categories
        X[:, 0] = rng.integers(0, 5, n)
        return X, (m + X[:, 0] > 2).astype(np.float32), None
    assert kind == "queries"
    return (X, np.digitize(m, [-1, 0, 1, 2]).astype(np.float32),
            np.full(n // 20, 20, np.int32))


# (id, params over the base, data, path, reason the engine was refused)
TABLE = [
    # --- taken
    ("binary", dict(objective="binary", **INTERPRET), {}, "aligned", None),
    ("goss", dict(objective="binary", boosting="goss", **INTERPRET), {},
     "aligned", None),
    ("dart", dict(objective="binary", boosting="dart", **INTERPRET), {},
     "aligned", None),
    ("bagging", dict(objective="binary", bagging_freq=1,
                     bagging_fraction=0.5, **INTERPRET), {},
     "aligned", None),
    ("data-parallel", dict(objective="binary", tree_learner="data",
                           num_machines=2, **INTERPRET), {},
     "aligned", None),
    ("multiclass", dict(objective="multiclass", num_class=3, **INTERPRET),
     dict(kind="classes3"), "aligned-mc", None),
    ("multiclassova", dict(objective="multiclassova", num_class=3,
                           **INTERPRET),
     dict(kind="classes3"), "aligned-mc", None),
    ("lambdarank-forced", dict(objective="lambdarank",
                               tpu_grow_mode="aligned", **INTERPRET),
     dict(kind="queries", n=500), "aligned", None),
    # --- refused by the learner's gate, in the gate's own order
    ("leafwise", dict(objective="binary", tpu_grow_mode="leafwise",
                      **INTERPRET), {}, "fused", "tpu_grow_mode=leafwise"),
    ("level", dict(objective="binary", tpu_grow_mode="level", **INTERPRET),
     {}, "fused", "tpu_grow_mode=level"),
    ("forced-splits", dict(objective="binary", forcedsplits_filename="?",
                           **INTERPRET), {}, "fused", SEQUENTIAL),
    ("cegb", dict(objective="binary", cegb_penalty_split=0.1, **INTERPRET),
     {}, "fused", SEQUENTIAL),
    ("quant-hist-on", dict(objective="binary", tpu_quant_hist="on",
                           **INTERPRET), {}, "fused",
     "tpu_quant_hist=on (quantized hist rides the fused path)"),
    ("no-kernels", dict(objective="binary"), {}, "fused",
     "pallas kernels unavailable (no TPU, interpret off)"),
    ("no-kernels-fused-iteration",
     dict(objective="binary", tpu_fuse_iteration=True), {}, "mega-fused",
     "pallas kernels unavailable (no TPU, interpret off)"),
    ("feature-parallel", dict(objective="binary", tree_learner="feature",
                              num_machines=2, **INTERPRET), {},
     "fused", "parallel_mode=feature"),
    ("voting-parallel", dict(objective="binary", tree_learner="voting",
                             num_machines=2, **INTERPRET), {},
     "fused", "parallel_mode=voting"),
    ("multiclass-data-parallel",
     dict(objective="multiclass", num_class=3, tree_learner="data",
          num_machines=2, **INTERPRET), dict(kind="classes3"),
     "fused", "multiclass under data-parallel"),
    ("1021-features", dict(objective="binary", **INTERPRET),
     dict(n=300, f=1021), "fused", "num_features 1021 > 1020"),
    # 300 bins need 16-bit bin ids, which the gate meets before it looks
    # at max_bin: "max_bin > 256" is a reason no config reaches
    ("max_bin-300", dict(objective="binary", max_bin=300,
                         min_data_in_bin=1, **INTERPRET), dict(n=2000),
     "fused", "bins not uint8"),
    ("num_class-128", dict(objective="multiclass", num_class=128,
                           **INTERPRET), dict(kind="classes128", n=1280),
     "fused", "num_class > 127"),
    ("multiclass-weighted", dict(objective="multiclass", num_class=3,
                                 **INTERPRET),
     dict(kind="classes3", weighted=True),
     "fused", "objective lacks a multiclass lane mode"),
    ("lambdarank-row-floor", dict(objective="lambdarank", **INTERPRET),
     dict(kind="queries", n=500),
     "fused", "non-pointwise objective below the row floor"),
    ("custom-fobj", dict(objective="none", **INTERPRET), dict(fobj=True),
     "fused", "no objective"),
    # --- refused by the boosting variant
    ("dart-multiclass", dict(objective="multiclass", num_class=3,
                             boosting="dart", **INTERPRET),
     dict(kind="classes3"), "fused",
     "boosting=dart with multiclass: the record walk follows one score "
     "lane"),
    ("dart-data-parallel", dict(objective="binary", boosting="dart",
                                tree_learner="data", num_machines=2,
                                **INTERPRET), {}, "fused",
     "boosting=dart under tree_learner=data: the record walk is not "
     "sharded"),
    # ("bundled features" is a reason no config reaches: the dataset
    # bundles under boosting=gbdt and goss only)
    ("dart-categorical", dict(objective="binary", boosting="dart",
                              categorical_feature="0", **INTERPRET),
     dict(kind="category"), "fused",
     "boosting=dart with categorical features: the record walk takes "
     "numerical splits"),
    ("dart-lambdarank", dict(objective="lambdarank", boosting="dart",
                             tpu_grow_mode="aligned", **INTERPRET),
     dict(kind="queries", n=500), "fused",
     "boosting=dart with a non-pointwise objective: its row-order "
     "gradients are made before the drop"),
    ("dart-1025-leaves", dict(objective="binary", boosting="dart",
                              num_leaves=1025, **INTERPRET), {}, "fused",
     "boosting=dart above 1024 leaves: the record walk's tables are "
     "sized for VMEM"),
    ("rf", dict(objective="binary", boosting="rf", bagging_freq=1,
                bagging_fraction=0.7, **INTERPRET), {},
     "fused", "boosting=rf: one-time gradients and a running-average "
              "score, its own iteration"),
    ("goss-multiclass", dict(objective="multiclass", num_class=3,
                             boosting="goss", **INTERPRET),
     dict(kind="classes3"), "fused",
     "boosting=goss with multiclass: the compact record's bag bit holds "
     "no multiplier"),
    ("goss-data-parallel", dict(objective="binary", boosting="goss",
                                tree_learner="data", num_machines=2,
                                **INTERPRET), {}, "fused",
     "boosting=goss under tree_learner=data: the device selects do not "
     "sum their counts over the mesh"),
    # --- off the device learner altogether: the host learner has no
    # gate to ask, so the event names no reason
    ("regression_l1", dict(objective="regression_l1", **INTERPRET),
     dict(kind="regression"), "per-tree", None),
    ("cegb-lazy", dict(objective="binary",
                       cegb_penalty_feature_lazy=[0.1] * 6, **INTERPRET),
     {}, "per-tree", None),
]


@pytest.mark.parametrize("params,data,path,rejected",
                         [row[1:] for row in TABLE],
                         ids=[row[0] for row in TABLE])
def test_path_gate(params, data, path, rejected, tmp_path):
    data = dict(data)
    fobj, weighted = data.pop("fobj", False), data.pop("weighted", False)
    X, y, group = _data(**data)
    assert len(X) <= 2000
    params = dict({"num_leaves": 4, "max_bin": 63, "min_data_in_leaf": 5,
                   "verbosity": 1, "metric": "none"}, **params)
    if params.get("forcedsplits_filename"):
        forced = tmp_path / "forced.json"
        forced.write_text(json.dumps({"feature": 0, "threshold": 0.0}))
        params["forcedsplits_filename"] = str(forced)
    weight = np.linspace(0.5, 1.5, len(y)) if weighted else None
    lines = []
    log.register_callback(lines.append)
    try:
        ds = lgb.Dataset(X, label=y, group=group, weight=weight,
                         params=params).construct()
        bst = lgb.Booster(params=params, train_set=ds)
        if fobj:
            bst.update(fobj=lambda s, _: (s - y, np.ones_like(s)))
        else:
            bst.update()
    finally:
        log.register_callback(None)
        log.set_verbosity(1)
    events = [r for r in map(log.parse_event, lines)
              if r and r["event"] == "train_path"]
    assert len(events) == 1, events
    assert (events[0]["path"], events[0]["rejected"]) == (path, rejected)
    assert bst._gbdt._iter_path == path
    assert bst.num_trees() == params.get("num_class", 1)
