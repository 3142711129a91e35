"""The phase readers (`benchmark/layer_metrics/_phases.py`) on made-up
events, tables and seams: the join by instruction name and result shape,
the ambiguous name, milliseconds by phase, the named share, the idle gaps
cut at nested seams, and None from every reader where there is nothing to
read (no `obs.phases` in the program, no device plane, no xplane).
"""
import importlib
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.layer_metrics import _phases  # noqa: E402

NEW = ["xla_phase_named_pct", "build_eval_ms_per_iter",
       "build_layout_ms_per_iter", "build_replay_ms_per_iter",
       "build_ends_ms_per_iter", "drain_materialise_ms_per_iter",
       "sample_draw_ms_per_iter", "park_partition_ms_per_iter",
       "rank_permute_ms_per_iter", "walk_tables_ms_per_iter",
       "drain_gap_ms", "drain_gap_unnamed_pct"]
MS = 1_000_000      # ns


def row(program, instruction, shape, phase, opcode="fusion"):
    return {"program": program, "instruction": instruction,
            "opcode": opcode, "shape": shape, "phase": phase,
            "source_file": None, "source_line": None}


TABLE = [
    row("build", "fusion.7", "f32[8]", "build.eval"),
    row("build", "fusion.8", "f32[8]", "build.layout"),
    row("build", "copy.3", "s32[4,8]", "build.park", "copy"),
    row("build", "copy.4", "s32[4,8]", "build.copy_back", "copy"),
    row("build", "move_pass.1", "(s32[4,8],f32[2])", "build.park",
        "custom-call"),
    row("build", "move_pass.2", "(s32[4,8],f32[2])", "build.layout",
        "custom-call"),
    row("build", "fusion.9", "f32[8]", None),
    # one name and shape in two programs: under one phase it is that
    # phase, under two it is nobody's
    row("build", "fusion.1", "f32[16]", "build.tail"),
    row("mat", "fusion.1", "f32[16]", "drain.materialise"),
    row("build", "sort.2", "f32[16]", "drain.materialise", "sort"),
    row("mat", "sort.2", "f32[16]", "drain.materialise", "sort"),
    # the same name with another shape is another instruction
    row("goss_select", "fusion.7", "f32[4,8]", "sample.goss"),
]
# (instruction, shape, kind, start, end) in ms from the window's start
EVENTS = [
    ("fusion.7", "f32[8]", "op", 0, 10),            # build.eval 10
    ("move_pass.1", "(s32[4,8],f32[2])", "kernel", 10, 30),   # park kernel 20
    ("copy.3", "s32[4,8]", "op", 30, 34),           # build.park 4
    ("fusion.8", "f32[8]", "op", 34, 40),           # build.layout 6
    ("move_pass.2", "(s32[4,8],f32[2])", "kernel", 40, 70),
    ("fusion.7", "f32[4,8]", "op", 70, 75),         # sample.goss 5
    ("fusion.9", "f32[8]", "op", 75, 78),           # in the table, no phase: 3
    ("fusion.1", "f32[16]", "op", 78, 80),          # ambiguous: 2
    ("unknown.5", "f32[3]", "op", 80, 81),          # in no table: 1
    # idle 81 .. 90, then the drain's materialise
    ("sort.2", "f32[16]", "op", 90, 96),            # drain.materialise 6
    ("copy.4", "s32[4,8]", "op", 100, 103),         # build.copy_back 3
]
SEAMS = [   # (name, start, end) in ms
    ("aligned.dispatch", 0, 1),
    ("train.drain", 80, 110),
    ("train.flag_pull", 80, 82),
    ("train.resolve", 82, 88),
    ("train.materialise", 89, 108),
    ("aligned.program", 89, 90),
]


def fake(monkeypatch, table=TABLE, events=EVENTS, seams=SEAMS, devices=1):
    """`_phases.window` over made-up parts: a program whose `table()`
    returns `table`, an xplane whose load returns `events` and `seams`."""
    _phases._cache.clear()
    from lightgbm_tpu.obs import hlo
    phases = types.SimpleNamespace(table=lambda: list(table))
    monkeypatch.setattr(_phases, "program", lambda: (phases, hlo))
    monkeypatch.setattr(_phases, "newest_xplane", lambda root=None: "made-up")
    raw = {"devices": {f"/device:TPU:{d}": [
        ((n, s, k), a * MS, b * MS) for n, s, k, a, b in events]
        for d in range(devices)},
        "seams": [(n, a * MS, b * MS) for n, a, b in seams]}
    monkeypatch.setattr(_phases, "load", lambda path, hlo: raw)
    ops = {d: [("x", 0, 1)] for d in raw["devices"]}
    return {"iterations": 2, "trace": {
        "spans": [("bench.update", 0, 80 * MS), ("bench.drain", 80 * MS,
                                                 110 * MS)],
        "ops": ops, "kernels": {"move_pass"},
        "window": {"busy_s": 0.1, "window_s": 0.11}}}


def read(name, ctx):
    return importlib.import_module(
        "benchmark.layer_metrics." + name).read(ctx)


def test_the_join_is_by_name_and_shape_and_two_phases_are_nobodys():
    index = _phases.phase_index(TABLE)
    assert index[("fusion.7", "f32[8]")] == "build.eval"
    assert index[("fusion.7", "f32[4,8]")] == "sample.goss"
    assert index[("fusion.1", "f32[16]")] is None       # two phases
    assert index[("sort.2", "f32[16]")] == "drain.materialise"   # one
    assert index[("fusion.9", "f32[8]")] is None
    assert ("unknown.5", "f32[3]") not in index


def test_an_events_name_is_taken_apart_as_the_tables_rows_are():
    from lightgbm_tpu.obs import hlo
    assert _phases.parse_event(
        "%fusion.96 = f32[11043840]{0:T(1024)} fusion(%p.1, %p.2), "
        "kind=kCustom, calls=%fused_computation.96", hlo) \
        == ("fusion.96", "f32[11043840]", "op")
    assert _phases.parse_event(
        '%move_pass.15 = (s32[24588,24,2048]{2,1,0:T(8,128)}, '
        'f32[2,1,6,128]{3,2,1,0}) custom-call(%a), '
        'custom_call_target="tpu_custom_call"', hlo) \
        == ("move_pass.15", "(s32[24588,24,2048],f32[2,1,6,128])", "kernel")
    assert _phases.parse_event(
        "%while.3 = (s32[], f32[8]{0}) while(%t), condition=%c, body=%b",
        hlo)[2] == "wrapper"


@pytest.mark.parametrize("name,want", [
    ("build_eval_ms_per_iter", 10 / 2),
    ("build_layout_ms_per_iter", 6 / 2),
    ("build_ends_ms_per_iter", 3 / 2),          # the copy back alone
    ("drain_materialise_ms_per_iter", 6 / 2),
    ("sample_draw_ms_per_iter", 5 / 2),
    ("park_partition_ms_per_iter", (20 + 4) / 2),   # kernel and copy
    ("build_replay_ms_per_iter", None),         # no program has the phase
    ("rank_permute_ms_per_iter", None),
    ("walk_tables_ms_per_iter", None),
    # XLA time 40 ms, of it 3 + 2 + 1 under no phase
    ("xla_phase_named_pct", 100 * 34 / 40),
    # idle inside the drain: 81-90 and 96-100 and 103-110 = 20 ms, of it
    # 88-89 and 108-110 under the drain alone
    ("drain_gap_ms", 20.0),
    ("drain_gap_unnamed_pct", 100 * 3 / 20)])
def test_readers_on_a_made_up_window(monkeypatch, name, want):
    got = read(name, fake(monkeypatch))
    assert got == pytest.approx(want) if want is not None else got is None


def test_a_phase_some_program_has_reads_zero_where_it_did_not_run(
        monkeypatch):
    ctx = fake(monkeypatch, events=[e for e in EVENTS if e[0] != "copy.4"])
    assert read("build_ends_ms_per_iter", ctx) == 0.0


def test_phases_and_the_unnamed_rest_add_up_to_the_xla_time(monkeypatch):
    ctx = fake(monkeypatch)
    win = _phases.window(ctx)
    ops = _phases.by_phase(win)
    assert sum(ops.values()) == 40 * MS
    assert ops[None] == 6 * MS
    assert _phases.by_phase(win, kernels=True) == {
        "build.park": 20 * MS, "build.layout": 30 * MS}
    # two chips: the average, not the sum
    ctx = fake(monkeypatch, devices=2)
    assert sum(_phases.by_phase(_phases.window(ctx)).values()) == 40 * MS
    assert read("drain_gap_ms", ctx) == pytest.approx(20.0)


def test_idle_gaps_go_to_the_innermost_seam(monkeypatch):
    idle = _phases.idle_by_seam(fake(monkeypatch))
    assert {k: v / MS for k, v in idle.items()} == {
        "train.drain/train.resolve": 6.0,           # 82-88
        "train.drain/train.flag_pull": 1.0,         # 81-82
        "train.drain": 3.0,                         # 88-89, 108-110
        "train.drain/train.materialise/aligned.program": 1.0,    # 89-90
        "train.drain/train.materialise": 9.0}       # 96-100, 103-108


def test_seam_paths_cut_at_every_edge_and_idle_outside_is_outside():
    pieces = _phases.seam_paths([("a", 0, 10), ("b", 2, 4), ("c", 3, 4),
                                 ("d", 20, 30)])
    assert pieces == [(0, 2, "a"), (2, 3, "a/b"), (3, 4, "a/b/c"),
                      (4, 10, "a"), (20, 30, "d")]
    events = [("op", 5, 8), ("op", 25, 26)]
    assert _phases.idle_in(events, pieces, 0, 40) == {
        "a": 2 + 1 + 2, "a/b": 1, "a/b/c": 1, "outside": 10 + 10,
        "d": 5 + 4}
    assert _phases.idle_in([], [], 0, 7) == {"outside": 7}


def test_the_table_is_built_once_a_run(monkeypatch):
    calls = []
    ctx = fake(monkeypatch)
    phases, hlo = _phases.program()
    phases.table = lambda: calls.append(1) or list(TABLE)
    for name in NEW:
        read(name, ctx)
    assert calls == [1]


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_without_phases_or_a_device_plane(
        monkeypatch, name):
    ctx = fake(monkeypatch)
    # a program without `obs.phases` (the parent, under these files)
    monkeypatch.setattr(_phases, "program", lambda: None)
    assert read(name, ctx) is None
    # a trace without a device plane (a CPU): `run.py`'s own loader finds
    # no operations
    ctx = fake(monkeypatch)
    ctx["trace"]["ops"] = {}
    assert read(name, ctx) is None
    # no xplane where `run.py` writes them
    ctx = fake(monkeypatch)
    monkeypatch.setattr(_phases, "newest_xplane", lambda root=None: None)
    assert read(name, ctx) is None
    # a table that cannot be built fails no run
    ctx = fake(monkeypatch)

    def broken():
        raise RuntimeError("no compiler")
    _phases.program()[0].table = broken
    assert read(name, ctx) is None


def test_program_is_none_where_the_import_fails(monkeypatch):
    import lightgbm_tpu.obs
    assert _phases.program() is not None
    monkeypatch.delattr(lightgbm_tpu.obs, "phases")
    monkeypatch.setitem(sys.modules, "lightgbm_tpu.obs.phases", None)
    assert _phases.program() is None


def test_newest_xplane_looks_below_a_directory(tmp_path):
    assert _phases.newest_xplane(str(tmp_path)) is None
    old = tmp_path / "cell" / "plugins" / "profile" / "t1"
    new = tmp_path / "cell" / "plugins" / "profile" / "t2"
    for d, age in ((old, 100), (new, 0)):
        d.mkdir(parents=True)
        f = d / "host.xplane.pb"
        f.write_bytes(b"")
        stamp = f.stat().st_mtime - age
        os.utime(f, (stamp, stamp))
    assert _phases.newest_xplane(str(tmp_path)) == str(new / "host.xplane.pb")


def test_every_new_metric_is_in_the_benchmark_with_its_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = entries[name]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] == "train_ms_per_iter"
        assert m["workloads"] and set(m["workloads"]) <= cells
    assert entries["xla_phase_named_pct"]["better"] == "higher"
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == NEW
