"""Seam spans and per-round counters (ISSUE 25): a handful of host
boundaries that ALWAYS record (`obs.trace.seam`), never fence and change
no program, and one `aligned.iter` record per resolved iteration with the
build program's own per-round counters (`aligned_builder.ROUND_STATS`),
pulled with the exactness flags.

Everything here runs at toy size on the CPU with interpreted kernels; one
16-iteration run is shared by the cases that only read what it left.
"""
import dataclasses
import glob
import json
import multiprocessing
import os
import re
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import compile_cache, native
from lightgbm_tpu.config import Config
from lightgbm_tpu.models import aligned_builder
from lightgbm_tpu.models.aligned_builder import ROUND_STATS
from lightgbm_tpu.models.level_builder import SI_LC, SI_RC
from lightgbm_tpu.obs import trace as obs_trace
from lightgbm_tpu.ops.aligned import (ROUTE_SELECTORS, ROUTE_STAGE,
                                      route_tile, route_unroll)

ALIGNED = {"tpu_grow_mode": "aligned", "tpu_aligned_interpret": True,
           "tpu_chunk": 256}
ITERS = 16


def _data(seed=3, n=900, f=8):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] * X[:, 2]
          + 0.3 * rng.standard_normal(n)) > 0).astype(np.float32)
    return X, y


def _booster(extra=None, data=None):
    X, y = data or _data()
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 20,
              "verbosity": -1, "metric": "none", **ALIGNED,
              **(extra or {})}
    ds = lgb.Dataset(X, label=y, params=params).construct()
    return lgb.Booster(params=params, train_set=ds)


def _col(rec, name):
    return [row[rec["columns"].index(name)] for row in rec["table"]]


# ---------------------------------------------------------------------------
# the seam itself
# ---------------------------------------------------------------------------

def test_seam_records_with_tracing_off():
    assert not obs_trace.enabled()
    obs_trace.reset()
    with obs_trace.seam("demo.outer", iter=7, rows=3) as outer:
        with obs_trace.seam("demo.inner", why="nested"):
            pass
        outer.attrs["late"] = True      # attributes may arrive late
    inner, out = obs_trace.seams()
    assert (inner["name"], out["name"]) == ("demo.inner", "demo.outer")
    assert inner["parent"] == out["id"] and out["parent"] is None
    # one iteration's seams share its number: given once, inherited below
    assert inner["iter"] == out["iter"] == 7
    assert out["t0"] <= inner["t0"] <= inner["t1"] <= out["t1"]
    assert (out["rows"], out["late"], inner["why"]) == (3, True, "nested")
    # the fenced tracer's own list stays empty while it is off
    assert obs_trace.spans() == []
    assert obs_trace.seams("demo.inner") == [inner]


def test_point_record_sits_under_the_open_seam():
    obs_trace.reset()
    with obs_trace.seam("demo.pull", iter=4):
        obs_trace.seam_record("demo.fact", iter=2, rounds=5)
    fact, pull = obs_trace.seams()
    assert fact["t0"] == fact["t1"] and fact["parent"] == pull["id"]
    assert (fact["iter"], fact["rounds"], pull["iter"]) == (2, 5, 4)


def test_ring_is_bounded():
    obs_trace.reset()
    for i in range(obs_trace.SEAM_RING + 50):
        obs_trace.seam_record("demo.many", iter=i)
    kept = obs_trace.seams()
    assert len(kept) == obs_trace.SEAM_RING == 8192
    assert kept[0]["iter"] == 50 and kept[-1]["iter"] == len(kept) + 49


def test_a_check_takes_its_own_records_out_again():
    import time
    obs_trace.reset()
    with obs_trace.seam("train.drain", iter=3):
        pass
    t = time.perf_counter()
    with obs_trace.seam("aligned.dispatch", iter=4):
        obs_trace.seam_record("aligned.iter", iter=4, rounds=1)
    assert obs_trace.forget_seams_since(t) == 2
    assert [r["name"] for r in obs_trace.seams()] == ["train.drain"]


def test_a_raising_body_still_closes_its_seam():
    obs_trace.reset()
    with pytest.raises(ValueError):
        with obs_trace.seam("demo.raises"):
            raise ValueError("inside")
    with obs_trace.seam("demo.after"):
        pass
    raised, after = obs_trace.seams()
    assert raised["name"] == "demo.raises" and after["parent"] is None


def test_fenced_tracer_keeps_the_boundaries_that_became_seams(tmp_path):
    """With `tpu_trace` on, a seam also lands in `spans()` and in
    `spans-<pid>.jsonl`, in a span's shape, for the summary and the
    timeline that read those."""
    obs_trace.reset()
    obs_trace.enable(str(tmp_path))
    try:
        with obs_trace.seam("demo.traced", iter=1, bytes=8):
            pass
        spans = obs_trace.spans()
    finally:
        obs_trace.disable()
    assert [s["name"] for s in spans] == ["demo.traced"]
    assert spans[0]["kind"] == "span" and spans[0]["dur_ms"] >= 0
    (path,) = glob.glob(os.path.join(str(tmp_path), "spans-*.jsonl"))
    (line,) = [json.loads(ln) for ln in open(path)]
    assert (line["name"], line["iter"], line["bytes"]) == ("demo.traced",
                                                           1, 8)
    assert obs_trace.seams()[0]["kind"] == "seam"
    obs_trace.reset()


def test_config_signature_has_nothing_of_the_seams():
    """Seams are no parameter: the signature's fields that speak of
    tracing are the fenced modes' own, and using seams moves nothing."""
    cfg = Config()
    before = compile_cache.config_signature(cfg)
    with obs_trace.seam("demo.sig"):
        assert compile_cache.config_signature(cfg) == before
    names = {f.name for f in dataclasses.fields(Config)}
    assert not [n for n in names if "seam" in n]
    assert {n for n in names if n.startswith("tpu_trace")} == {
        "tpu_trace", "tpu_trace_dir"}


# ---------------------------------------------------------------------------
# one 16-iteration aligned run, and what it left behind
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def run16():
    """ITERS x update() + eval_train() with every blocking device pull
    counted by the function that made it, and `_block` (the tracer's
    only fence) counted too."""
    pulls, fences = [], []
    real_get = jax.device_get

    def counting_get(x):
        pulls.append(sys._getframe(1).f_code.co_name)
        return real_get(x)

    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "device_get", counting_get)
    mp.setattr(obs_trace, "_block", lambda x: fences.append(1) or x)
    obs_trace.reset()
    try:
        bst = _booster()
        for _ in range(ITERS):
            bst.update()
        loop_pulls = list(pulls)
        bst.eval_train()
        drain_pulls = pulls[len(loop_pulls):]
        seams = obs_trace.seams()
        gbdt = bst._gbdt
        specs = real_get([m.record for m in gbdt.models])
        eng = gbdt._aligned_eng_ref
    finally:
        mp.undo()
    model = bst.dump_model()
    return dict(bst=bst, eng=eng, seams=seams, specs=specs, model=model,
                loop_pulls=loop_pulls, drain_pulls=drain_pulls,
                fences=fences, fence_count=obs_trace.fence_count)


def test_no_seam_fences(run16):
    """The zero-fence probe of test_obs, with seams live: a run that
    recorded seams at every boundary never reached the tracer's fence."""
    names = {r["name"] for r in run16["seams"]}
    assert {"aligned.pack", "aligned.upload", "aligned.program",
            "aligned.dispatch", "train.flag_pull", "train.drain",
            "aligned.iter"} <= names
    assert run16["fences"] == [] and run16["fence_count"] == 0
    assert obs_trace.spans() == []


def test_the_loop_blocks_only_where_it_pulled_flags_before(run16):
    """16 iterations at depth 8: the parent commit blocks twice in
    `_resolve_aligned_pending` (iteration 9's pull of 8 flags, and the
    final resolve at the 16-tree trim) and once in the trim itself. The
    counters ride those same pulls."""
    assert run16["loop_pulls"] == ["_resolve_aligned_pending"] * 2 \
        + ["_trim_trailing_empty"]
    assert run16["drain_pulls"] == []       # the queue was already empty
    pulls = [r for r in run16["seams"] if r["name"] == "train.flag_pull"]
    assert [(p["queued"], p["final"]) for p in pulls] == [(8, False),
                                                          (8, True)]
    assert [p["iter"] for p in pulls] == [8, 16]


def test_one_iter_record_per_iteration_with_the_specs_rounds(run16):
    recs = [r for r in run16["seams"] if r["name"] == "aligned.iter"]
    assert [r["iter"] for r in recs] == list(range(ITERS))
    assert all(r["columns"] == list(ROUND_STATS) for r in recs)
    for rec, spec in zip(recs, run16["specs"]):
        assert rec["rounds"] == int(spec.rounds) == len(rec["table"]) > 0
        # rows past `rounds` were never written
        assert not np.asarray(spec.round_stats)[rec["rounds"]:].any()


def test_rounds_of_either_parity_leave_the_rows_in_the_first_buffer(run16):
    """The round loop reads one record buffer and writes the other, so
    after an odd number of rounds the build program copies the rows back
    once, and after an even number not at all: the parity of `rounds`
    says which (the record's `norm_passes` said the same and is gone; the
    copy's device time is phase `build.copy_back`). The engine's one
    record matrix is what every other program reads: its scores are the
    model's after 16 trees of either kind."""
    recs = [r for r in run16["seams"] if r["name"] == "aligned.iter"]
    for rec, spec in zip(recs, run16["specs"]):
        assert "norm_passes" not in rec
        assert not hasattr(spec, "norm_passes")
    assert {r["rounds"] % 2 for r in recs} == {0, 1}
    X, _ = _data()
    np.testing.assert_allclose(
        run16["eng"].row_scores(),
        run16["bst"].predict(X, raw_score=True), rtol=1e-5, atol=1e-6)


def test_counters_add_up_to_the_trees(run16):
    recs = [r for r in run16["seams"] if r["name"] == "aligned.iter"]
    trees = run16["model"]["tree_info"]

    def internal_counts(node):
        if "split_index" not in node:
            return 0
        return node["internal_count"] + internal_counts(
            node["left_child"]) + internal_counts(node["right_child"])

    for rec, spec, tree in zip(recs, run16["specs"], trees):
        n_exec = int(spec.n_exec)
        assert sum(_col(rec, "leaves_split")) == n_exec
        parents = np.asarray(spec.execI)[:n_exec]
        assert sum(_col(rec, "rows_split")) == int(
            parents[:, SI_LC].sum() + parents[:, SI_RC].sum())
        # speculative splits may not commit: the tree is at most that
        assert sum(_col(rec, "rows_split")) >= internal_counts(
            tree["tree_structure"])
        # the first round splits the root: every row, one leaf
        assert rec["table"][0][2:4] == [900, 1]


def test_chunk_counts_stay_inside_the_grid(run16):
    nc = run16["eng"].NC
    for rec in (r for r in run16["seams"] if r["name"] == "aligned.iter"):
        live = [s + c for s, c in zip(_col(rec, "chunks_split"),
                                      _col(rec, "chunks_copied"))]
        # the root round spans the inherited layout: the whole grid
        assert live[0] == nc and _col(rec, "chunks_copied")[0] == 0
        assert all(0 < v <= nc for v in live)
        assert all(v + d <= nc for v, d in zip(live,
                                               _col(rec, "chunks_dead")))
        # no spill ring at this size
        assert not any(_col(rec, "spill_slots"))


def test_pack_seam_carries_the_layout(run16):
    eng = run16["eng"]
    (pack,) = [r for r in run16["seams"] if r["name"] == "aligned.pack"]
    (up,) = [r for r in run16["seams"] if r["name"] == "aligned.upload"]
    assert {k: pack[k] for k in ("rows", "W", "w_used", "C", "NC", "bits")} \
        == dict(rows=900, W=eng.W, w_used=eng.w_used, C=eng.C, NC=eng.NC,
                bits=eng.bits)
    assert pack["bytes"] == up["bytes"] == eng.rec.nbytes + eng.cnts.nbytes
    # 900 rows, no bagging, one shard: no round needs the count pass
    assert pack["count_pass"] is False
    # move_pass's split path: sub-tiles of route_tile rows, whole chunks
    assert pack["route_tile"] * pack["route_tiles"] == eng.C
    assert pack["route_tile"] == route_tile(eng.C)
    # and what its route matmul selects: one block a tile
    assert pack["route_selectors"] == ROUTE_SELECTORS == 1
    # how it stages a tile's rows: each side's open window in the tile
    # loop's carry, every width alike, and the tiles a trip of that loop
    assert pack["route_stage"] == ROUTE_STAGE == "carried"
    assert pack["route_unroll"] == route_unroll(eng.C) \
        == min(8, pack["route_tiles"])
    assert pack["t1"] <= up["t0"]


def test_valid_pack_seam_carries_the_block():
    """A validation set on the engine leaves one `valid.pack` seam, where
    the engine is built: its rows, the packed block's bytes and chunks,
    the walk that takes it and why (None: the record walk)."""
    X, y = _data()
    Xv, yv = _data(seed=5, n=700)
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 20,
              "verbosity": -1, "metric": "auc", **ALIGNED}
    ds = lgb.Dataset(X, label=y, params=params).construct()
    vs = lgb.Dataset(Xv, label=yv, reference=ds, params=params).construct()
    obs_trace.reset()
    bst = lgb.Booster(params=params, train_set=ds)
    bst.add_valid(vs, "v")
    for _ in range(2):
        bst.update()
        bst.eval_valid()
    eng = bst._gbdt._aligned_eng_ref
    su = bst._gbdt.valid_scores[0]
    (pack,) = obs_trace.seams("valid.pack")
    assert {k: pack[k] for k in ("rows", "bytes", "chunks", "walk", "why")} \
        == dict(rows=700, bytes=su.rec.nbytes + su.cnts.nbytes,
                chunks=-(-700 // eng.C), walk="records", why=None)
    assert su.rec.shape[1:] == (eng.W, eng.C)
    (train_pack,) = obs_trace.seams("aligned.pack")
    assert train_pack["t1"] <= pack["t0"]
    # the iterations' records count what the walk read
    recs = obs_trace.seams("aligned.iter")
    assert [(r["valid_rows_walked"], r["valid_walk_passes"])
            for r in recs] == [(700, 1)] * 2


def test_seams_of_one_iteration_share_its_number(run16):
    seams = run16["seams"]
    by_id = {r["id"]: r for r in seams}
    dispatch = [r for r in seams if r["name"] == "aligned.dispatch"]
    assert [r["iter"] for r in dispatch] == list(range(ITERS))
    programs = [r for r in seams if r["name"] == "aligned.program"]
    assert {p["key"] for p in programs} == {"build", "mat"}
    build = next(p for p in programs if p["key"] == "build")
    assert by_id[build["parent"]] is dispatch[0] and build["iter"] == 0
    assert build["cache"] in ("miss", "hit", "memory")
    (drain,) = [r for r in seams if r["name"] == "train.drain"]
    mat = next(p for p in programs if p["key"] == "mat")
    assert mat["parent"] == drain["id"] and drain["iter"] == ITERS


def test_second_booster_at_the_same_shapes_traces_nothing(run16):
    before = compile_cache.trace_count()
    obs_trace.reset()
    bst = _booster()
    for _ in range(3):
        bst.update()
    bst.eval_train()
    assert compile_cache.trace_count() == before
    programs = obs_trace.seams("aligned.program")
    assert {p["key"] for p in programs} == {"build", "mat"}
    assert {p["cache"] for p in programs} == {"memory"}
    assert len(obs_trace.seams("aligned.iter")) == 3


# ---------------------------------------------------------------------------
# the branches a toy run does not take by itself
# ---------------------------------------------------------------------------

def _unparked_bag():
    """(params, data) of an engine with a bag it does not park: feature
    0 is categorical, and the record walk takes numerical splits only.
    Its out-of-bag rows ride the rounds, so the rounds count the rows."""
    X, y = _data()
    X = X.copy()
    X[:, 0] = np.floor(np.abs(X[:, 0]) * 3)
    return ({"categorical_feature": "0", "bagging_fraction": 0.7,
             "bagging_freq": 1, "bagging_seed": 3}, (X, y))


@pytest.mark.parametrize("extra,spills,why", [
    ({"tpu_hist_spill_vmem_mb": 0.001}, True, None),
    ("unparked_bag", False, "bag, not parked"),
    # the standard record, and no count pass: the histograms' counts
    # are exact at any row count
    ({"tpu_force_big_n": True}, False, None),
    ({}, False, None),
])
def test_spill_column_and_count_pass_follow_the_program(extra, spills,
                                                        why):
    obs_trace.reset()
    data = None
    if extra == "unparked_bag":
        extra, data = _unparked_bag()
    bst = _booster(extra, data)
    for _ in range(2):
        bst.update()
    bst.eval_train()
    # (an engine that found its build program registered never ran the
    # factory that sets the attribute)
    assert getattr(bst._gbdt._aligned_eng_ref, "hist_spill", False) == spills
    # fixed per engine, so a fact of the pack seam and no counter
    (pack,) = obs_trace.seams("aligned.pack")
    assert pack["count_pass"] is (why is not None)
    assert pack["count_why"] == why
    recs = obs_trace.seams("aligned.iter")
    assert len(recs) == 2
    for rec in recs:
        # every split block flushes its histogram slot once
        assert _col(rec, "spill_slots") == (
            _col(rec, "leaves_split") if spills else [0] * rec["rounds"])


def test_ingest_seams_say_who_binned():
    from lightgbm_tpu.io.dataset import Dataset as CoreDataset
    X, y = _data(n=600)
    obs_trace.reset()
    core = CoreDataset.create_from_sample(X[:200], 600, config=Config())
    core.push_rows(X[:256], label=y[:256])
    core.push_rows(X[256:], label=y[256:])
    core.finish_load()
    seams = obs_trace.seams()
    assert [r["name"] for r in seams] == [
        "ingest.find_bins", "ingest.push_rows", "ingest.push_rows",
        "ingest.finish_load"]
    assert [r["rows"] for r in seams] == [600, 256, 344, 600]
    # a silent fall-back to the Python binner would read False here; the
    # other two do no binning of rows, and say nothing of it
    assert [r["native"] for r in seams[1:3]] == [native.native_available()] * 2
    assert "native" not in seams[0] and "native" not in seams[3]
    assert all(r["parent"] is None and r["iter"] is None for r in seams)


def test_profiler_session_holds_the_seams_on_its_host_plane(tmp_path):
    """Whenever a profiler session is live the seams are events of the
    xplane's host plane, on the device operations' clock."""
    from jax.profiler import ProfileData
    bst = _booster()
    bst.update()
    bst.eval_train()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            bst.update()
        bst.eval_train()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    assert {"aligned.dispatch", "train.flag_pull", "train.drain"} <= names


def test_lowered_build_program_names_its_three_kernels():
    """Each `pallas_call` carries its name itself, so wrapping or inlining
    the jitted function around it cannot rename the kernel in a trace.
    Lowered for the TPU from here; nothing is compiled or run."""
    bst = _booster(*_unparked_bag())
    bst.update()
    cpu_eng = bst._gbdt._aligned_eng_ref
    assert cpu_eng.count_why == "bag, not parked"
    eng = aligned_builder.AlignedEngine(cpu_eng.learner, cpu_eng.objective,
                                        interpret=False, bagged=True,
                                        bag_device=cpu_eng.bag_device)
    assert eng.count_pass
    fmask = cpu_eng.learner._fmask_arr(None)
    lowered = jax.jit(eng._build_program()).trace(
        eng.rec, eng.cnts, fmask, jnp.float32(0.1), jnp.asarray(True)
    ).lower(lowering_platforms=("tpu",))
    names = re.findall(r'kernel_name = "([^"]+)"', lowered.as_text())
    assert sorted(set(names)) == ["count_pass", "move_pass",
                                  "slot_hist_pass"]


# ---------------------------------------------------------------------------
# the native library's build, raced
# ---------------------------------------------------------------------------

def _build_and_load(src_dir, out):
    import ctypes
    native._SRC_DIR = src_dir
    path, reason = native._build()
    ok = path is not None and ctypes.CDLL(path).lgbt_num_threads() >= 1
    out.put((os.path.basename(path) if path else None, reason, ok))


def test_native_build_survives_a_race(tmp_path):
    """Six processes (the tier-1 run's xdist workers on a fresh checkout)
    build the same digest at once: each compiles into a temporary of its
    own and renames it into place, and whoever loses looks again, so
    every one of them loads the library."""
    if shutil.which("make") is None or shutil.which("g++") is None:
        pytest.skip("no toolchain here")
    src = str(tmp_path / "native")
    shutil.copytree(native._SRC_DIR, src,
                    ignore=shutil.ignore_patterns("*.so", "*.tmp"))
    assert not glob.glob(os.path.join(src, "*.so"))
    ctx = multiprocessing.get_context("spawn")
    out = ctx.Queue()
    procs = [ctx.Process(target=_build_and_load, args=(src, out))
             for _ in range(6)]
    for p in procs:
        p.start()
    got = [out.get(timeout=600) for _ in procs]
    for p in procs:
        p.join(timeout=60)
    assert all(ok for _, _, ok in got), got
    assert len({name for name, _, _ in got}) == 1
    # no temporary is left behind, only the digest-named library
    assert len(os.listdir(src)) == len(native._SOURCES) + 1
