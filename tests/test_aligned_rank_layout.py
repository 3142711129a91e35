"""The engine's external index space under a ranking objective.

Where `LambdarankNDCG` states a layout (`grad_layout`: every query rides
the fused kernel), the EXT record's index lane holds each row's slot in
the kernel's tile pack: scores leave the records by one scatter into the
pack, gradients come back by two gathers out of it, and row order is
only composed in for the cold row-order surface. Withholding the layout
(the accessor patched to return None; there is no option) is the path
every other objective takes, and must train the same model to the bit.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import trace as obs_trace
from lightgbm_tpu.ops import pallas_rank
from lightgbm_tpu.ops.objectives import LambdarankNDCG

PARAMS = {"objective": "lambdarank", "num_leaves": 7, "max_bin": 31,
          "min_data_in_leaf": 5, "min_sum_hessian_in_leaf": 1e-3,
          "verbosity": -1, "metric": "none", "tpu_grow_mode": "aligned",
          "tpu_aligned_interpret": True, "tpu_chunk": 256,
          "tpu_rank_fused": "on", "tpu_rank_tile": 128}


def _data(seed=0, queries=40, longest=60):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(3, longest, queries)
    n = int(sizes.sum())
    X = rng.standard_normal((n, 6)).astype(np.float32)
    y = np.clip(np.round(X[:, 0] + 0.5 * X[:, 1] + 1
                         + 0.5 * rng.standard_normal(n)), 0, 4)
    w = rng.uniform(0.5, 2.0, n).astype(np.float32)
    return X, y, sizes, w


def _booster(extra=None, weight=None, data=None, iters=5, withheld=False):
    """A booster after `iters` updates; `withheld`: the objective states
    no layout, so the engine keeps row ids in the lane."""
    X, y, sizes, _ = data or _data()
    params = dict(PARAMS, **(extra or {}))
    with pytest.MonkeyPatch.context() as mp:
        if withheld:
            mp.setattr(LambdarankNDCG, "grad_layout", lambda self: None)
        ds = lgb.Dataset(X, label=y, group=sizes, weight=weight,
                         params=params).construct()
        bst = lgb.Booster(params=params, train_set=ds)
        for _ in range(iters):
            bst.update()
        bst._gbdt.materialized_models()
    return bst


def _eng(bst):
    return bst._gbdt._aligned_eng_ref


def _same_model(a, b):
    assert a.dump_model()["tree_info"] == b.dump_model()["tree_info"]
    np.testing.assert_array_equal(_eng(a).row_scores(), _eng(b).row_scores())


# ---------------------------------------------------------------------------
# (a) the tiles layout trains the withheld layout's model
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", ["plain", "weights", "bag", "goss"])
def test_tiles_layout_trains_the_row_layouts_model(case):
    data = _data()
    extra = {"bag": {"bagging_fraction": 0.5, "bagging_freq": 1,
                     "bagging_seed": 3},
             "goss": {"boosting": "goss", "top_rate": 0.3,
                      "other_rate": 0.2, "learning_rate": 0.5}}.get(case)
    weight = data[3] if case == "weights" else None
    a = _booster(extra, weight, data)
    b = _booster(extra, weight, data, withheld=True)
    ea, eb = _eng(a), _eng(b)
    assert ea.ext and ea.ext_of_row is not None and eb.ext_of_row is None
    layout = a._gbdt.objective.grad_layout()
    assert ea.ext_shape == layout.shape and ea.ext_n == layout.slots
    assert eb.ext_shape == (ea.n,) and ea.w_used == eb.w_used
    if case == "goss":
        assert ea.bag_sampled and eb.bag_sampled
    assert a.trees[-1].num_leaves > 1
    _same_model(a, b)


# ---------------------------------------------------------------------------
# (b) the row-order surface keeps its meaning on the tiles layout
# ---------------------------------------------------------------------------
def test_row_scores_are_the_models_and_round_trip():
    X, *_ = data = _data()
    bst = _booster(data=data)
    eng = _eng(bst)
    np.testing.assert_allclose(eng.row_scores(),
                               bst.predict(X, raw_score=True), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(eng.row_scores_dev()),
                                  eng.row_scores())
    fresh = np.random.default_rng(1).standard_normal(eng.n) \
        .astype(np.float32)
    eng.set_row_scores(fresh)
    np.testing.assert_array_equal(eng.row_scores(), fresh)
    # and in external order: each row's score in its slot, 0 in the pads
    ext = np.asarray(eng.ext_scores_dev()).reshape(-1)
    slot = np.asarray(eng.ext_of_row)
    np.testing.assert_array_equal(ext[slot], fresh)
    assert np.count_nonzero(ext) == np.count_nonzero(fresh)


def test_gradient_lanes_hold_get_gradients_in_row_order():
    bst = _booster(iters=3)
    eng = _eng(bst)
    before = eng.row_scores().copy()
    bst.update()
    g, h = bst._gbdt.objective.get_gradients(jnp.asarray(before)[None, :])
    np.testing.assert_array_equal(eng.row_lane("grad"), np.asarray(g[0]))
    np.testing.assert_array_equal(eng.row_lane("hess"), np.asarray(h[0]))


def test_a_fallback_replays_to_the_same_model():
    """A starved speculation budget makes rounds inexact: the fallback
    syncs scores out in row order, grows the tree on the host and puts
    its scores back, all through `ext_of_row`."""
    data = _data(queries=80)
    extra = {"tpu_level_spec": 0.6, "num_leaves": 31}
    a = _booster(extra, data=data, iters=6)
    assert getattr(_eng(a), "fallbacks", 0) > 0, "needs a fallback"
    b = _booster(extra, data=data, iters=6, withheld=True)
    assert _eng(a).ext_of_row is not None and _eng(b).ext_of_row is None
    _same_model(a, b)


# ---------------------------------------------------------------------------
# (c) identity where the objective cannot state a layout, or a mesh
# sums shards by row
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case,extra,longest", [
    ("long-query", {}, 200),
    ("fused-off", {"tpu_rank_fused": "off"}, 60),
    ("data-parallel", {"tree_learner": "data", "num_machines": 2}, 60),
])
def test_identity_layout_is_chosen(case, extra, longest):
    data = _data(longest=longest)
    obs_trace.reset()
    a = _booster(extra, data=data, iters=3)
    (pack,) = obs_trace.seams("aligned.pack")
    eng = _eng(a)
    assert eng.ext and eng.ext_of_row is None and eng.ext_shape == (eng.n,)
    assert (pack["grad_layout"], pack["grad_slots"]) == ("rows", eng.n)
    obj = a._gbdt.objective
    if case == "long-query":
        assert obj.rank_fused_active and obj.rank_fused_fallback_queries > 0
    assert (obj.grad_layout() is None) == (case != "data-parallel")
    assert (eng.axis is not None) == (case == "data-parallel")
    assert a.trees[-1].num_leaves > 1
    _same_model(a, _booster(extra, data=data, iters=3, withheld=True))


# ---------------------------------------------------------------------------
# (d) one kernel, two orders
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("weighted", [False, True], ids=["plain", "weights"])
def test_row_order_gradients_are_the_slot_orders(weighted):
    X, y, sizes, w = data = _data(seed=4)
    bst = _booster(weight=w if weighted else None, data=data, iters=1)
    obj = bst._gbdt.objective
    layout = obj.grad_layout()
    slot = layout.slot_of_row
    pack = obj._fused_pack
    # the inverse of doc_idx over the real slots, onto them
    real = (pack.qid >= 0).reshape(-1)
    np.testing.assert_array_equal(
        pack.doc_idx.reshape(-1)[slot], np.arange(len(y)))
    assert sorted(slot) == list(np.flatnonzero(real))
    score = np.random.default_rng(2).standard_normal(len(y)) \
        .astype(np.float32)
    g, h = obj.get_gradients(jnp.asarray(score)[None, :])
    # pad slots poisoned: none of it may reach a row, or a real slot
    score_t = np.full(layout.slots, np.nan, np.float32)
    score_t[slot] = score
    g_t, h_t = obj.slot_gradients(jnp.asarray(score_t.reshape(layout.shape)))
    g_t, h_t = np.asarray(g_t).reshape(-1), np.asarray(h_t).reshape(-1)
    assert g_t.shape == (layout.slots,) and not real.all()
    np.testing.assert_array_equal(g_t[slot], np.asarray(g[0]))
    np.testing.assert_array_equal(h_t[slot], np.asarray(h[0]))
    assert not g_t[~real].any() and not h_t[~real].any()
    assert np.abs(g_t[real]).max() > 0 and np.isfinite(g_t).all()


# ---------------------------------------------------------------------------
# the mechanism, pinned without a chip: what runs between two trees
# ---------------------------------------------------------------------------
_OP = re.compile(r'"?stablehlo\.(gather|scatter)"?\(.*?\) -> ', re.S)
_TENSOR = re.compile(r"tensor<([0-9x]+)x[a-z]+[0-9]+>")


def _permutations(text, least):
    """Names of the gather / scatter operations of a StableHLO module
    that move at least `least` elements (their index operand's leading
    extent: one index an element moved)."""
    found = []
    for m in _OP.finditer(text):
        types = m.group(0).rsplit(" : ", 1)[1]
        shapes = [[int(d) for d in s.split("x")]
                  for s in _TENSOR.findall(types)]
        indices = shapes[1]                 # (operand, indices[, updates])
        if int(np.prod(indices)) >= least:
            found.append(m.group(1))
    return sorted(found)


def _between_trees(bst):
    """StableHLO of the three programs `_dispatch_aligned` runs between
    two trees, chosen as it chooses them."""
    eng, obj = _eng(bst), bst._gbdt.objective
    tiled = eng.ext_of_row is not None
    mat = jax.jit(eng._materialize_program("score", rows=not tiled))
    scores = mat(eng.rec, eng.cnts)
    pack = obj._fused_pack
    rank = pallas_rank.make_fused_grad_fn(
        pack.num_tiles, pack.tile, int(pack.band), float(obj.cfg.sigmoid),
        interpret=True, rows=not tiled)
    tabs = obj._fused_dev_tables()
    rank_args = ((scores, *tabs["slots"], tabs["weight"]) if tiled
                 else (scores, *tabs["rows"], *tabs["slots"]))
    g, h = rank(*rank_args)
    build = jax.jit(eng._build_program(external_grads=True))
    build_args = (eng.rec, eng.cnts, eng.learner._fmask_arr(None),
                  jnp.float32(0.1), jnp.asarray(True), g, h)
    return {"materialise": mat.lower(eng.rec, eng.cnts).as_text(),
            "rank": rank.lower(*rank_args).as_text(),
            "build_ext": build.lower(*build_args).as_text()}


@pytest.mark.parametrize("withheld,want", [
    (False, {"materialise": ["scatter"], "rank": [],
             "build_ext": ["gather", "gather"]}),
    (True, {"materialise": ["scatter"],
            "rank": ["gather", "gather", "gather"],
            "build_ext": ["gather", "gather"]}),
], ids=["tiles-three", "rows-six"])
def test_permutations_between_two_trees(withheld, want):
    bst = _booster(iters=1, withheld=withheld)
    eng = _eng(bst)
    texts = _between_trees(bst)
    got = {k: _permutations(t, least=eng.n) for k, t in texts.items()}
    assert got == want
    assert sum(len(v) for v in got.values()) == (6 if withheld else 3)


# ---------------------------------------------------------------------------
# the counter that says it engaged
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("withheld", [False, True], ids=["tiles", "rows"])
def test_pack_seam_names_the_gradient_layout(withheld):
    obs_trace.reset()
    bst = _booster(iters=1, withheld=withheld)
    (pack,) = obs_trace.seams("aligned.pack")
    eng = _eng(bst)
    pk = bst._gbdt.objective._fused_pack
    if withheld:
        assert (pack["grad_layout"], pack["grad_slots"]) == ("rows", eng.n)
    else:
        assert pack["grad_layout"] == "tiles"
        assert pack["grad_slots"] == pk.num_tiles * pk.tile > eng.n
    assert pack["w_used"] == eng.w_used == eng.wcnt + 4
