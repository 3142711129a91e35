"""The reader of `move_parked_row_pct` at toy size on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/selftest/test_parked.py -q

A toy run of each of the two cells whose engine parks the rows its bag
leaves out, through `run_cell`: the reader gives 100 less the bag's share
(20.0 under `bagging_fraction=0.8`; 70.0 under GOSS's 0.2 / 0.1 but for
the rows that tie with the threshold, which are kept), and the cell's
other readers go on reading. On made-up seams: the arithmetic, and None,
with no error, on a ring whose records lack the counter (the parent of
the PR that added it), on one that holds no window, and on one whose
pack seam has no row count.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from benchmark import run  # noqa: E402
from benchmark.layer_metrics import _seams, move_parked_row_pct  # noqa: E402
from benchmark.selftest import test_bagging, test_goss  # noqa: E402
from benchmark.tasks import binary_goss  # noqa: E402


@pytest.mark.parametrize("toy, seed", [(test_goss, 2**31 + 36),
                                       (test_bagging, 2**31 + 37)])
def test_toy_run_reads_what_the_bag_leaves_out(toy, seed, tmp_path,
                                               monkeypatch):
    monkeypatch.setattr(binary_goss, "AUC_15_FLOOR", 0.55)
    monkeypatch.setattr(binary_goss, "HOLDOUT_ROWS", 600)
    res = run.run_cell(toy.CELL, seed, 0.0, True, overrides=toy.TOY,
                       trace_dir=str(tmp_path))
    assert res["correct"] is True, res["compared"]
    got = res["metrics"]["move_parked_row_pct"]["value"]
    if toy is test_bagging:
        assert got == 20.0
        assert res["metrics"]["bag_kept_row_pct"]["value"] == 80.0
    else:
        assert got == pytest.approx(
            100.0 - res["metrics"]["goss_kept_row_pct"]["value"])
        assert 55.0 < got <= 70.0
    # the partition is a row of the round table: the counters' readers
    # of the layer still read, one round and one leaf more a partition
    win = _seams.window(_seams.ring(), 3)
    parts = sum(r["park_rounds"] for r in win["iters"])
    assert parts == (3 if toy is test_goss else 1)
    assert all(len(r["table"]) == r["rounds"] for r in win["iters"])
    assert res["metrics"]["move_rounds_per_iter"]["value"] * 3 \
        == sum(r["rounds"] for r in win["iters"])


def made_up(monkeypatch, parked, rows=1000):
    ring = [{"name": "aligned.pack", "id": 1, "parent": 0, "t0": 0.0,
             "t1": 1.0, "rows": rows}]
    for it, n in enumerate(parked):
        ring.append({"name": "aligned.dispatch", "id": 10 + it, "parent": 0,
                     "t0": 2.0 + it, "t1": 2.1 + it, "iter": it})
    for it, n in enumerate(parked):
        rec = {"name": "aligned.iter", "id": 20 + it, "parent": 0,
               "t0": 9.0, "t1": 9.0, "iter": it, "rounds": 0,
               "columns": [], "table": []}
        if n is not None:
            rec["rows_parked"] = n
        ring.append(rec)
    ring.append({"name": "train.drain", "id": 99, "parent": 0, "t0": 9.5,
                 "t1": 9.9})
    monkeypatch.setattr(_seams, "ring", lambda: ring)
    return {"iterations": len(parked), "trace": {}}


def test_reader_on_made_up_seams(monkeypatch):
    ctx = made_up(monkeypatch, [700, 700, 710])
    assert move_parked_row_pct.read(ctx) == pytest.approx(70.0 + 1 / 3)
    assert move_parked_row_pct.read(made_up(monkeypatch, [0, 0])) == 0.0
    # the parent's ring: records without the counter, on all or on some
    assert move_parked_row_pct.read(
        made_up(monkeypatch, [None, None, None])) is None
    assert move_parked_row_pct.read(
        made_up(monkeypatch, [200, None, 200])) is None
    # no window, no pack, no row count: nothing to read, nothing raised
    assert move_parked_row_pct.read(
        dict(made_up(monkeypatch, [200]), iterations=3)) is None
    made_up(monkeypatch, [200], rows=0)
    assert move_parked_row_pct.read({"iterations": 1, "trace": {}}) is None
    monkeypatch.setattr(_seams, "ring", lambda: [])
    assert move_parked_row_pct.read({"iterations": 3, "trace": {}}) is None
