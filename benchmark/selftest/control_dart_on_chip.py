#!/usr/bin/env python3
"""The upper readings of task `binary_dart`, at the cell's own size on
the chip:

    python3 benchmark/selftest/control_dart_on_chip.py <fault> <seed> [seconds]

One run of `criteo67-255-dart.train-dropping` through `run.run_cell` with
one fault planted in what the program does to its score lane around a
dropping iteration (`FAULTS`); the drop sets, the tree weights and the
dumped model stay the schedule's. It has to come out NOT correct, by the
number the fault names; the exit code is 0 when it does, 1 when the
broken run passed. The benchmark's own runs never call this;
`test_dart.py` plants the same faults at toy size.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run  # noqa: E402

CELL = "criteo67-255-dart.train-dropping"
WALK = "score_walk_err"


def _walks(patch, change):
    """Every walk of dropped trees goes through `change(f_first)`: the
    factor to walk at in its place, or None for no walk at all. The walk
    out has f_first -1, the walk back `keep` > 0."""
    from lightgbm_tpu.models.aligned_builder import AlignedEngine
    from lightgbm_tpu.ops.aligned import WALK_TREES
    real = AlignedEngine.walk_trees

    def walk_trees(self, trees, first, f_first, *rest, **kw):
        if not trees:                   # the warm-up's pass of no tree
            return real(self, trees, first, f_first, *rest, **kw)
        f = change(f_first)
        if f is None:           # the passes it would have made
            return -(-len(trees) // WALK_TREES)
        return real(self, trees, first, f, *rest, **kw)
    patch(AlignedEngine, "walk_trees", walk_trees)
    return WALK


def nothing_dropped(patch):
    """No tree leaves the score lane or comes back: gradients at the
    whole score, and the lane keeps the dropped trees at full weight."""
    return _walks(patch, lambda f: None)


def not_put_back(patch):
    """The dropped trees leave the lane and never come back."""
    return _walks(patch, lambda f: None if f > 0 else f)


def put_back_whole(patch):
    """The dropped trees come back at their whole weight, not at k / (k +
    1) of it."""
    return _walks(patch, lambda f: 1.0 if f > 0 else f)


def new_tree_at_lr(patch):
    """The new tree enters the lane at learning_rate, not at
    learning_rate / (1 + k)."""
    from lightgbm_tpu.models.aligned_builder import AlignedEngine
    real = AlignedEngine.train_iter
    patch(AlignedEngine, "train_iter", lambda self, scale, *a, **kw: real(
        self, float(self.cfg.learning_rate), *a, **kw))
    return WALK


FAULTS = {f.__name__: f for f in (nothing_dropped, not_put_back,
                                  put_back_whole, new_tree_at_lr)}


def failing(compared: dict) -> dict:
    return {k: c for k, c in compared.items()
            if not (c["value"] <= c["limit"] if c["holds"] == "<="
                    else c["value"] >= c["limit"])}


def main(argv) -> int:
    fault, seed = argv[1], int(argv[2])
    seconds = float(argv[3]) if len(argv) > 3 else 1.0
    named = FAULTS[fault](setattr)
    res = run.run_cell(CELL, seed, seconds, False)
    bad = failing(res["compared"])
    print(json.dumps({"cell": CELL, "fault": fault, "seed": seed,
                      "correct": res["correct"], "named": named,
                      "failing": bad, "compared": res["compared"],
                      "dropping_tree": res["detail"]["first_tree"]
                      ["dropping_tree"]}, default=lambda o: o.item()))
    return 0 if res["correct"] is False and named in bad else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
