#!/usr/bin/env python3
"""The control of `correct`, at a cell's own size on the chip:

    python3 benchmark/selftest/control_on_chip.py <cell> <seed> [seconds]

One run of the cell through `run.run_cell` with the guarantee "trees on
every row, no sampling" broken by the program's own bagging (half of the
rows a tree, drawn anew every iteration). It has to come out NOT correct,
by `root_left_count_err`; the exit code is 0 when it does, 1 when the
broken run passed. The benchmark's own runs never call this; the same
control at toy size is `test_benchmark.py`'s
`test_control_sampled_rows_break_the_guarantee_and_the_root_check`.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run  # noqa: E402

BROKEN = {"params": {"bagging_fraction": 0.5, "bagging_freq": 1}}


def main(argv) -> int:
    cell, seed = argv[1], int(argv[2])
    seconds = float(argv[3]) if len(argv) > 3 else 1.0
    res = run.run_cell(cell, seed, seconds, False, overrides=BROKEN)
    failing = {k: c for k, c in res["compared"].items()
               if not (c["value"] <= c["limit"] if c["holds"] == "<="
                       else c["value"] >= c["limit"])}
    print(json.dumps({"cell": cell, "seed": seed, "correct": res["correct"],
                      "failing": failing, "compared": res["compared"]}))
    return 0 if res["correct"] is False else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
