"""Checks of the seam readers (`benchmark/layer_metrics/_seams.py` and the
metrics on it), on made-up seams and intervals and on one toy run:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/selftest -q

Since these readers exist, a traced toy run on the CPU reports the
host-side seam metrics too, so `test_benchmark.py`'s exact set in
`test_traced_toy_run_leaves_out_what_it_cannot_read` is out of date and
that ONE case FAILS on its `set(res["metrics"])` line. That file is not
this PR's to edit (ROADMAP.md Queue 1 names the `benchmark` issue that
does); the last case below takes its place meanwhile.
"""
import importlib
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from benchmark import run  # noqa: E402
from benchmark.layer_metrics import _seams  # noqa: E402

BENCH = run.load_json("BENCHMARK.json")
COLUMNS = ["chunks_split", "chunks_copied", "rows_split", "leaves_split",
           "spill_slots", "chunks_dead"]
NEW = ["ingest_program_s", "pack_records_s", "upload_s", "program_load_s",
       "first_drain_s", "driver_host_ms_per_iter", "move_rounds_per_iter",
       "move_split_chunk_pct", "move_us_per_split_chunk",
       "move_us_per_copied_chunk", "move_pass_hbm_roofline_pct",
       "move_leaves_split_per_iter", "hist_spill_flushes_per_iter"]
ON_CPU = NEW[:8] + NEW[11:]     # the rest needs the device's events
SPLIT_US, COPIED_US = 40.0, 1.5     # what the made-up kernel costs a chunk
PEAK = 819e9


def read(name, ctx):
    return importlib.import_module(
        "benchmark.layer_metrics." + name).read(ctx)


def made_up_ring():
    """Two warm-up iterations and a window of two, as the program would
    leave them: ingest, pack, upload, a program's first call inside the
    first dispatch, a drain per phase with its flag pull inside."""
    ids = iter(range(1, 1000))
    ring = []

    def seam(name, t0, t1, it=None, parent=None, **attrs):
        rec = dict(kind="seam", name=name, id=next(ids), parent=parent,
                   iter=it, t0=float(t0), t1=float(t1), **attrs)
        ring.append(rec)
        return rec["id"]

    def iteration(it, tables):
        seam("aligned.iter", 0, 0, it=it, rounds=len(tables),
             columns=COLUMNS, table=tables)

    seam("ingest.find_bins", 0, 2, native=True)
    seam("ingest.push_rows", 2, 5, rows=10, native=True)
    seam("ingest.push_rows", 6, 8, rows=10, native=True)
    seam("ingest.finish_load", 8, 8.5)
    seam("aligned.pack", 10, 50, rows=20, bytes=1920, W=24, w_used=23,
         C=4, NC=9, bits=8)
    seam("aligned.upload", 50, 53, bytes=1920)
    ring.append(dict(kind="seam", name="aligned.dispatch", id=100,
                     parent=None, iter=0, t0=53.0, t1=60.0))
    seam("aligned.program", 54, 59.5, it=0, parent=100, key="build",
         cache="hit")
    drain = 200
    seam("train.flag_pull", 60.5, 70, it=1, parent=drain, queued=1,
         final=True)
    iteration(0, [[9, 0, 20, 1, 0, 0]])
    seam("aligned.program", 70, 71, it=1, parent=drain, key="mat",
         cache="miss")
    ring.append(dict(kind="seam", name="train.drain", id=drain, parent=None,
                     iter=1, t0=60.0, t1=72.0))
    seam("aligned.dispatch", 72, 72.1, it=1)
    # ---- the window: iterations 2 and 3
    seam("aligned.dispatch", 80, 80.1, it=2)
    seam("aligned.dispatch", 80.2, 80.3, it=3)
    seam("train.flag_pull", 80.4, 81.4, it=3, queued=2, final=False)
    iteration(1, [[9, 0, 20, 1, 0, 0], [2, 5, 8, 1, 1, 1]])
    drain = 300
    seam("train.flag_pull", 82, 90, it=4, parent=drain, queued=2,
         final=True)
    iteration(2, [[9, 0, 20, 1, 0, 0], [4, 3, 12, 2, 2, 1]])
    iteration(3, [[9, 0, 20, 1, 0, 0], [1, 6, 5, 1, 1, 2],
                  [3, 4, 9, 2, 2, 1]])
    ring.append(dict(kind="seam", name="train.drain", id=drain, parent=None,
                     iter=4, t0=81.5, t1=93.5))
    # ---- after the window: the checks drain an idle device
    seam("train.drain", 95, 99, it=4)
    return ring


def made_up_trace(ring, drop_last: bool = False):
    """`move_pass` events that cost exactly SPLIT_US a compute-path chunk
    and COPIED_US a copied one, in the window's round order, between
    other operations."""
    win = _seams.window(ring, 2)
    split = [s + d for s, d in zip(_seams.column(win["iters"], "chunks_split"),
                                   _seams.column(win["iters"], "chunks_dead"))]
    copied = _seams.column(win["iters"], "chunks_copied")
    events, at = [], 1000
    for s, c in zip(split, copied):
        ns = int(1e3 * (SPLIT_US * s + COPIED_US * c))
        events += [("fusion.7", at, at + 50), ("move_pass", at + 60,
                                               at + 60 + ns)]
        at += 100 + ns
    if drop_last:
        events = events[:-1]
    return {"ops": {"/device:TPU:0": events}, "kernels": {"move_pass"},
            "window": {"busy_s": 1.0, "window_s": 1.0}}


@pytest.fixture
def ring(monkeypatch):
    made = made_up_ring()
    monkeypatch.setattr(_seams, "ring", lambda: list(made))
    monkeypatch.setattr(_seams, "hbm_bytes_per_s", lambda: PEAK)
    return made


@pytest.mark.parametrize("name,expected", [
    ("ingest_program_s", 2 + 3 + 2 + 0.5),
    ("pack_records_s", 40.0),
    ("upload_s", 3.0),
    ("program_load_s", 5.5),    # the one in the drain is the drain's
    ("first_drain_s", 12.0),
    # the window less the pull in the loop and the drain, whose own pull
    # is counted once: what is left is the host's
    ("driver_host_ms_per_iter", 1e3 * (13.5 - (1.0 + 12.0)) / 2),
    ("move_rounds_per_iter", (2 + 3) / 2),
    ("move_split_chunk_pct", 100.0 * 26 / (26 + 13)),
    ("move_us_per_split_chunk", SPLIT_US),
    ("move_us_per_copied_chunk", COPIED_US),
    ("move_leaves_split_per_iter", (1 + 2 + 1 + 1 + 2) / 2),
    ("hist_spill_flushes_per_iter", (0 + 2 + 0 + 1 + 2) / 2),
])
def test_reader_on_made_up_seams(ring, name, expected):
    ctx = {"iterations": 2, "trace": made_up_trace(ring), "walls": {},
           "compiles": {}}
    assert read(name, ctx) == pytest.approx(expected, rel=1e-6)


def test_roofline_counts_only_the_rows_that_had_to_move(ring):
    trace = made_up_trace(ring)
    ctx = {"iterations": 2, "trace": trace}
    rows = 20 + 12 + 20 + 5 + 9
    seconds = sum(e - s for n, s, e in trace["ops"]["/device:TPU:0"]
                  if n == "move_pass") / 1e9
    assert read("move_pass_hbm_roofline_pct", ctx) == pytest.approx(
        100.0 * (2 * rows * 4 * 23 / PEAK) / seconds)


def test_a_count_mismatch_gives_none(ring):
    """The fit pairs events with rounds by order: one event too few (a
    window cut inside an iteration) and there is no pairing to trust."""
    ctx = {"iterations": 2, "trace": made_up_trace(ring, drop_last=True)}
    assert read("move_us_per_split_chunk", ctx) is None
    assert read("move_us_per_copied_chunk", ctx) is None
    # what needs no pairing still reads
    assert read("move_rounds_per_iter", ctx) == 2.5
    assert read("move_pass_hbm_roofline_pct", ctx) > 0


def test_fit_recovers_known_costs_and_refuses_a_singular_system():
    x1, x2 = [9, 2, 9, 4, 9, 3, 4], [0, 5, 0, 3, 0, 6, 4]
    y = [7.0 * a + 0.25 * b for a, b in zip(x1, x2)]
    assert _seams.fit_two(x1, x2, y) == pytest.approx((7.0, 0.25))
    assert _seams.fit_two([1, 2, 3], [2, 4, 6], [1, 2, 3]) is None
    assert _seams.fit_two([3, 3], [0, 0], [1, 1]) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_seams(monkeypatch, name):
    """The parent of the PR that added the seams: an empty ring, a trace
    that still has its `move_pass` events; no reader raises."""
    trace = made_up_trace(made_up_ring())
    monkeypatch.setattr(_seams, "ring", lambda: [])
    ctx = {"iterations": 2, "trace": trace, "walls": {}, "compiles": {}}
    assert read(name, ctx) is None
    assert read(name, dict(ctx, trace={"ops": {}, "kernels": set()})) is None


def test_too_few_iterations_in_the_ring_is_nothing(ring):
    ctx = {"iterations": 9, "trace": made_up_trace(ring)}
    for name in ("move_rounds_per_iter", "move_split_chunk_pct",
                 "program_load_s", "driver_host_ms_per_iter",
                 "move_us_per_split_chunk", "move_pass_hbm_roofline_pct",
                 "move_leaves_split_per_iter"):
        assert read(name, ctx) is None


def test_every_new_metric_has_its_entry_and_its_reader():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    for name in NEW:
        assert entries[name]["workloads"] == cells
        assert callable(importlib.import_module(
            "benchmark.layer_metrics." + name).read)
    assert [m["name"] for m in BENCH["per_layer"]][-len(NEW):] == NEW


def test_traced_toy_run_reads_the_programs_own_seams(tmp_path):
    """On the CPU there is no device plane: the host seams and the
    counters are on the line and agree with the clocks held from outside;
    what needs device events is left out."""
    from benchmark.selftest.test_benchmark import TOY
    from lightgbm_tpu.obs import trace
    trace.reset()       # a benchmark process holds one run; pytest's may not
    res = run.run_cell(BENCH["workloads"][0]["name"], 2**31 + 9, 0.0, True,
                       overrides=TOY, trace_dir=str(tmp_path))
    assert res["correct"] is True and res["failed"] == 0
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(got) == {"ingest_bin_s", "first_update_s", "cache_misses",
                        *ON_CPU}
    walls = res["detail"]["walls"]
    assert 0 < got["ingest_program_s"] <= walls["ingest_bin_s"]
    assert got["ingest_program_s"] >= 0.9 * walls["ingest_bin_s"]
    parts = (got["pack_records_s"] + got["upload_s"] + got["program_load_s"]
             + got["first_drain_s"])
    assert 0.8 * walls["first_update_s"] <= parts <= walls["first_update_s"]
    assert got["move_rounds_per_iter"] >= 2
    assert 0 < got["move_split_chunk_pct"] <= 100
    # the host's own share of the window is inside it
    assert 0 < got["driver_host_ms_per_iter"] \
        < 1e3 * res["detail"]["window_s"] / res["attempted"]
    # a tree commits its leaves less one; speculation may execute more
    grown = res["detail"]["leaves"][-res["attempted"]:]
    assert got["move_leaves_split_per_iter"] >= sum(
        n - 1 for n in grown) / len(grown)
    assert got["hist_spill_flushes_per_iter"] == 0
    assert res["detail"]["in_window"] == {
        "traces": 0, "cache_hits": 0, "cache_misses": 0}
