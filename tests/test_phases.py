"""Phases (`lightgbm_tpu/obs/phases.py`): the names the device programs
give their own XLA operations, and the table that joins them to a trace.

A phase is a `jax.named_scope`: HLO metadata, so it is held here by the
compiled text of toy aligned runs of every kind (CPU: Pallas interpret
mode, where a kernel is inlined HLO under its call site's phase). Held
too: the registry is closed, no training run builds a table or keeps an
argument buffer alive for one, and the drain's two new seams nest where
the reader of the drain's idle gap looks for them.
"""
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import compile_cache
from lightgbm_tpu.obs import hlo, phases
from lightgbm_tpu.obs import trace as obs_trace

BASE = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
        "learning_rate": 0.5, "min_data_in_leaf": 20, "verbosity": -1,
        "metric": "none", "tpu_chunk": 256, "tpu_grow_mode": "aligned",
        "tpu_aligned_interpret": True}
BUILD = ["build.head", "build.root", "build.layout", "build.eval",
         "build.replay", "build.copy_back", "build.tail"]
PARKED = ["build.park", "walk.tables"]
# kind -> (params over BASE, the phases its programs must reach beyond
# the build program's own and the drain's)
KINDS = {
    "plain": ({}, []),
    "goss": ({"boosting": "goss", "top_rate": 0.2, "other_rate": 0.1,
              "bagging_seed": 5}, ["sample.goss"] + PARKED),
    "bagging": ({"bagging_fraction": 0.8, "bagging_freq": 2,
                 "feature_fraction": 0.8}, ["sample.bag"] + PARKED),
    "dart": ({"boosting": "dart", "drop_rate": 0.5, "skip_drop": 0.3,
              "learning_rate": 0.3}, ["walk.tables", "walk.apply"]),
    # a validation set watched every iteration: its walk and metrics,
    # and the training metrics at the drain
    "valid": ({"metric": "auc,binary_logloss"},
              ["valid.walk", "valid.metric", "train.metric"]),
    "lambdarank": ({"objective": "lambdarank", "num_leaves": 7,
                    "max_bin": 31, "min_data_in_leaf": 5,
                    "min_sum_hessian_in_leaf": 1e-3,
                    "tpu_rank_fused": "on", "tpu_rank_tile": 128},
                   ["rank.scatter", "rank.glue", "rank.gather"]),
}
HEAVY = ("fusion", "sort", "scatter", "gather", "copy", "custom-call")


def _data(kind, seed=0):
    rng = np.random.default_rng(seed)
    if kind != "lambdarank":
        X = rng.standard_normal((1800, 6)).astype(np.float32)
        y = ((X[:, 0] + X[:, 1] * X[:, 2]
              + 0.3 * rng.standard_normal(1800)) > 0).astype(np.float32)
        return X, y, None
    sizes = rng.integers(3, 60, 40)
    X = rng.standard_normal((int(sizes.sum()), 6)).astype(np.float32)
    y = np.clip(np.round(X[:, 0] + 0.5 * X[:, 1] + 1
                         + 0.5 * rng.standard_normal(len(X))), 0, 4)
    return X, y, sizes


def _run(kind, iters=5):
    X, y, group = _data(kind)
    params = dict(BASE, **KINDS[kind][0])
    ds = lgb.Dataset(X[:1500], label=y[:1500], group=group,
                     params=params).construct() if kind == "valid" else \
        lgb.Dataset(X, label=y, group=group, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    if kind == "valid":
        bst.add_valid(lgb.Dataset(X[1500:], label=y[1500:], reference=ds,
                                  params=params).construct(), "v")
    for _ in range(iters):
        bst.update()
        if kind == "valid":
            bst.eval_valid()
    bst.eval_train()
    return bst


_tables = {}


@pytest.fixture
def table_of():
    """kind -> the phase table of a toy run of that kind (one run a kind
    a process; the registry is emptied first, so the table is that run's)."""
    def get(kind):
        if kind not in _tables:
            phases.forget()
            obs_trace.reset()
            bst = _run(kind)
            _tables[kind] = (phases.table(), bst, obs_trace.seams())
        return _tables[kind]
    return get


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["build.nothing", "", "gbdt.build.eval"])
def test_the_registry_refuses_an_unregistered_name(name):
    with pytest.raises(KeyError):
        phases.scope(name)
    with pytest.raises(KeyError):
        phases.scoped(name)


def test_every_phase_says_what_it_is():
    assert all(isinstance(v, str) and v for v in phases.PHASES.values())
    assert all(k == k.strip() and "/" not in k for k in phases.PHASES)


@pytest.mark.parametrize("op_name,want", [
    ("jit(traced)/while/body/gbdt.build.eval/gather", "build.eval"),
    ("jit(f)/gbdt.build.head/gbdt.rank.gather/jit(clip)/max", "rank.gather"),
    ("jit(f)/gbdt.build.tail/gbdt.not_a_phase/add", "build.tail"),
    ("jit(f)/while/body/add", None), (None, None), ("", None)])
def test_the_innermost_registered_scope_is_the_phase(op_name, want):
    assert phases.phase_of(op_name) == want


def test_a_scope_is_metadata_and_changes_no_computation():
    def plain(x):
        return jnp.cumsum(x * 2.0)[::-1]

    def scoped(x):
        with phases.scope("build.layout"):
            return jnp.cumsum(x * 2.0)[::-1]
    x = jnp.arange(64, dtype=jnp.float32)
    a = jax.jit(plain).lower(x).compile()
    b = jax.jit(scoped).lower(x).compile()
    strip = hlo.instructions
    assert [(i.opcode, hlo.plain_shape(i.shape)) for i in strip(a.as_text())] \
        == [(i.opcode, hlo.plain_shape(i.shape)) for i in strip(b.as_text())]
    assert "gbdt.build.layout" in b.as_text()
    assert "gbdt." not in a.as_text()


# ---------------------------------------------------------------------------
# the table of a run of each kind
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", list(KINDS))
def test_every_phase_the_kind_can_reach_is_in_its_table(table_of, kind):
    table = table_of(kind)[0]
    found = {r["phase"] for r in table}
    want = set(BUILD + ["drain.materialise"] + KINDS[kind][1])
    assert want <= found, sorted(want - found)
    assert {"program", "instruction", "opcode", "shape", "phase",
            "source_file", "source_line"} == set(table[0])
    # a phase's rows say where in the source it was entered
    named = [r for r in table if r["phase"] == "build.eval"
             and r["source_file"]]
    assert named and all(r["source_file"].endswith(".py") for r in named)


@pytest.mark.parametrize("kind", list(KINDS))
def test_the_build_programs_heavy_instructions_carry_a_phase(table_of, kind):
    table = table_of(kind)[0]
    build = [r for r in table if r["program"].startswith("build")
             and r["opcode"] in HEAVY]
    assert len(build) > 100
    named = sum(r["phase"] is not None for r in build)
    assert named >= 0.9 * len(build), (named, len(build))


@pytest.mark.parametrize("kind,program,phase", [
    ("goss", "goss_select", "sample.goss"),
    ("bagging", "bag_select", "sample.bag"),
    ("dart", "walk_rec", "walk.apply"),
    ("dart", "walk_tree", "walk.tables"),
    ("lambdarank", "mat_ext", "rank.scatter"),
    ("lambdarank", "rank_fused", "rank.glue"),
    ("lambdarank", "build_ext", "rank.gather"),
    ("plain", "mat", "drain.materialise"),
    ("valid", "walk_rec", "valid.walk"),
    ("valid", "valid_view", "valid.metric"),
    ("valid", "valid.metric.auc", "valid.metric"),
    ("valid", "train.metric.binary_logloss", "train.metric")])
def test_a_program_outside_the_build_names_its_own_work(table_of, kind,
                                                        program, phase):
    rows = [r for r in table_of(kind)[0] if program in r["program"]
            and r["opcode"] in HEAVY]
    assert rows, sorted({r["program"] for r in table_of(kind)[0]})
    assert any(r["phase"] == phase for r in rows)
    if program not in ("build_ext", "walk_rec"):
        assert {r["phase"] for r in rows} <= {phase, None}


def test_the_rank_kernel_has_a_name_of_its_own():
    """`pallas_call(name=...)`: the trace called it after the jitted
    function around it (`grad_fn`). Lowered for the TPU from here;
    nothing is compiled or run."""
    import re

    from lightgbm_tpu.ops import pallas_rank
    fn = pallas_rank.make_fused_grad_fn(2, 128, 1, 1.0)
    i32, f32 = jnp.int32, jnp.float32
    tile = jax.ShapeDtypeStruct((2, 128), f32)
    lowered = fn.trace(tile, jax.ShapeDtypeStruct((2, 128), i32), tile,
                       jax.ShapeDtypeStruct((2, 128), i32), tile,
                       jax.ShapeDtypeStruct((1, 128), f32)).lower(
                           lowering_platforms=("tpu",))
    assert re.findall(r'kernel_name = "([^"]+)"', lowered.as_text()) \
        == ["rank_grad_pass"]


# ---------------------------------------------------------------------------
# what a run pays for it
# ---------------------------------------------------------------------------

def test_a_training_run_never_builds_a_table():
    before = phases.table_calls
    bst = _run("goss", iters=4)
    bst.predict(_data("goss")[0][:16])
    bst.dump_model()
    assert phases.table_calls == before
    phases.table(only=[])
    assert phases.table_calls == before + 1     # the probe counts


def test_the_registry_keeps_shapes_and_no_buffer(table_of):
    table_of("bagging")
    assert phases.programs()
    for (name, *_), (fn, args, kwargs) in phases._programs.items():
        assert hasattr(fn, "lower")
        for leaf in jax.tree_util.tree_leaves((args, kwargs)):
            assert isinstance(leaf, (jax.ShapeDtypeStruct, bool, int,
                                     float, str)), (name, type(leaf))


def test_remember_lets_go_of_the_array_it_was_shown():
    fn = jax.jit(lambda x, k=None: x + 1)
    x = jnp.arange(8.0)
    ref = weakref.ref(x)
    phases.remember("toy_program", fn, (x,), {"k": (jnp.int32(1), True)})
    del x
    gc.collect()
    assert ref() is None
    rows = phases.table(only=["toy_program"])
    assert rows and {r["program"] for r in rows} == {"toy_program"}
    # one entry per (name, shapes): the same call again replaces it
    n = len(phases.programs())
    phases.remember("toy_program", fn, (jnp.arange(8.0),),
                    {"k": (jnp.int32(2), True)})
    assert len(phases.programs()) == n
    phases.remember("toy_program", fn, (jnp.arange(9.0),))
    assert len(phases.programs()) == n + 1
    compile_cache.clear_programs()      # empties this registry too
    assert phases.programs() == []


# ---------------------------------------------------------------------------
# the drain's seams
# ---------------------------------------------------------------------------

def test_the_drains_parts_are_on_its_record_and_nothing_fences(monkeypatch):
    fences = []
    monkeypatch.setattr(obs_trace, "_block",
                        lambda x: fences.append(1) or x)
    obs_trace.reset()
    _run("plain", iters=3)      # under the pipeline's depth: the drain pulls
    seams = obs_trace.seams()
    (drain,) = [r for r in seams if r["name"] == "train.drain"]
    # a part adds no name to the ring: a window's set of names is what
    # the host did in it, and the drain does what it did
    assert {r["name"] for r in seams} == {
        "aligned.pack", "aligned.upload", "aligned.program",
        "aligned.dispatch", "aligned.iter", "train.flag_pull",
        "train.drain"}
    assert set(drain["parts"]) == {"train.resolve", "train.materialise"}
    assert all(v > 0 for v in drain["parts"].values())
    (pull,) = [r for r in seams if r["name"] == "train.flag_pull"]
    assert pull["parent"] == drain["id"]
    assert (pull["t1"] - pull["t0"]) + sum(drain["parts"].values()) \
        <= drain["t1"] - drain["t0"]
    # the resolved iterations' records are the drain's, as before
    iters = [r for r in seams if r["name"] == "aligned.iter"]
    assert len(iters) == 3
    assert all(r["parent"] == drain["id"] for r in iters)
    assert fences == [] and obs_trace.fence_count == 0


def test_a_part_outside_any_seam_records_nothing():
    obs_trace.reset()
    with obs_trace.part("train.resolve"):
        pass
    assert obs_trace.seams() == []
    with obs_trace.seam("demo.outer"):
        for _ in range(2):
            with obs_trace.part("demo.part"):
                pass
    (outer,) = obs_trace.seams()
    assert set(outer["parts"]) == {"demo.part"}
    assert 0 <= outer["parts"]["demo.part"] <= outer["t1"] - outer["t0"]


def test_a_profiler_session_holds_the_parts_inside_the_drains_event(
        tmp_path):
    """What the reader of the drain's idle gap stands on: under a live
    profiler session the parts are events of the xplane's host plane,
    nested in `train.drain`'s, on the device operations' clock."""
    import glob
    import os

    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        _run("plain", iters=3)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("train."):
                        spans.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    (drain,) = spans["train.drain"]
    for name in ("train.flag_pull", "train.resolve", "train.materialise"):
        (inner,) = spans[name]
        assert drain[0] <= inner[0] <= inner[1] <= drain[1], name
    assert spans["train.flag_pull"][0][1] <= spans["train.resolve"][0][0]
    assert spans["train.resolve"][0][1] <= spans["train.materialise"][0][0]


# ---------------------------------------------------------------------------
# the parser, on text written by hand
# ---------------------------------------------------------------------------

HLO = """HloModule jit_toy, is_scheduled=true

FileNames
1 "/src/builder.py"

FunctionNames
1 "build"

FileLocations
1 {file_name_id=1 function_name_id=1 line=40 end_line=40 column=4 end_column=9}
2 {file_name_id=1 function_name_id=1 line=77 end_line=78 column=4 end_column=9}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=2}

%fused_inner (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(toy)/gbdt.build.eval/mul" stack_frame_id=1}
}

%fused_mixed (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %add.1 = f32[8]{0} add(%p.1, %p.1), metadata={op_name="jit(toy)/gbdt.build.eval/add"}
  ROOT %add.2 = f32[8]{0} add(%add.1, %p.1), metadata={op_name="jit(toy)/gbdt.build.tail/add"}
}

%back_body (st: (f32[8], s32[])) -> (f32[8], s32[]) {
  %st = (f32[8]{0}, s32[]) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%st), index=0
  %copy.9 = f32[8]{0:T(256)} copy(%gte.1)
  %zero = s32[] constant(0)
  ROOT %tuple.1 = (f32[8]{0}, s32[]) tuple(%copy.9, %zero)
}

%back_cond (st.1: (f32[8], s32[])) -> pred[] {
  %st.1 = (f32[8]{0}, s32[]) parameter(0)
  %gte.2 = s32[] get-tuple-element(%st.1), index=1
  %one = s32[] constant(1)
  ROOT %eq = pred[] compare(%gte.2, %one), direction=EQ, metadata={op_name="jit(toy)/gbdt.build.copy_back/while/cond/eq"}
}

ENTRY %main (x: f32[8], n: s32[]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %n = s32[] parameter(1)
  %fusion.1 = f32[8]{0:T(256)} fusion(%x), kind=kLoop, calls=%fused_inner
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_mixed
  %copy.3 = f32[8]{0} copy(%x)
  %sort.4 = f32[8]{0} sort(%copy.3), dimensions={0}, to_apply=%fused_inner, metadata={op_name="jit(toy)/gbdt.build.layout/sort" stack_frame_id=2}
  %add.9 = f32[8]{0} add(%fusion.2, %sort.4)
  %tuple.2 = (f32[8]{0}, s32[]) tuple(%sort.4, %n)
  %while.1 = (f32[8]{0}, /*index=1*/s32[]) while(%tuple.2), condition=%back_cond, body=%back_body, metadata={op_name="jit(toy)/gbdt.build.copy_back/while" stack_frame_id=2}
  %move_pass.7 = (s32[4,8]{1,0}, f32[2]{0}) custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(toy)/gbdt.build.park/move_pass"}
  ROOT %gte.3 = f32[8]{0} get-tuple-element(%while.1), index=0
}
"""


def test_the_parser_takes_a_line_apart():
    name, shape, opcode, rest = hlo.instruction(
        "  %while.1 = (f32[8]{0}, /*index=1*/s32[]) while(%tuple.2), "
        "condition=%c, body=%b")
    assert (name, opcode) == ("while.1", "while")
    assert hlo.plain_shape(shape) == "(f32[8],s32[])"
    assert hlo.plain_shape("f32[11043840]{0:T(1024)}") == "f32[11043840]"
    assert hlo.nbytes("s32[24588,24,2048]{2,1,0}") == 24588 * 24 * 2048 * 4
    assert hlo.instruction("ENTRY %main (x: f32[8]) -> f32[8] {") is None
    assert hlo.frames(HLO) == {1: ("/src/builder.py", 40),
                               2: ("/src/builder.py", 77)}
    instrs = hlo.instructions(HLO)
    assert hlo.inner_computations(instrs) == {"fused_inner", "fused_mixed"}
    by_name = {i.name: i for i in instrs}
    assert hlo.is_kernel(by_name["move_pass.7"])
    assert hlo.operands(by_name["tuple.2"]) == ["sort.4", "n"]
    assert hlo.callees(by_name["while.1"]) == [("body", "back_body"),
                                               ("condition", "back_cond")]


def test_rows_of_gives_each_instruction_the_phase_it_can_prove():
    rows = {r["instruction"]: r for r in phases.rows_of("toy", HLO)}
    # the inside of a fusion and parameters are no rows of the table
    assert "mul.1" not in rows and "x" not in rows
    got = {k: rows[k]["phase"] for k in rows}
    # its own op_name, with the source line of its frame
    assert got["sort.4"] == "build.layout"
    assert (rows["sort.4"]["source_file"], rows["sort.4"]["source_line"]) \
        == ("/src/builder.py", 77)
    # a fusion without one: what its computation's instructions agree on
    assert got["fusion.1"] == "build.eval"
    # a compiler-made piece: its users' phase, else its operands' (the
    # instructions inside `fusion.2` disagree, its one operand decides)
    assert got["copy.3"] == "build.layout"
    assert got["fusion.2"] == "build.eval"
    # and nothing where neither agrees
    assert got["add.9"] is None
    # the copy inside a scoped loop's body: the loop's
    assert got["copy.9"] == "build.copy_back"
    assert got["while.1"] == "build.copy_back"
    # a kernel: its call site's
    assert got["move_pass.7"] == "build.park"
    assert rows["fusion.1"]["shape"] == "f32[8]"
    assert rows["move_pass.7"]["shape"] == "(s32[4,8],f32[2])"
