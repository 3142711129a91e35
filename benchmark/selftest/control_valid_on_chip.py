#!/usr/bin/env python3
"""The upper readings of the validation checks (`valid_metric_err`,
run.py; `valid_logloss_err`, task `binary_valid`), at the cell's own size
on the chip:

    python3 benchmark/selftest/control_valid_on_chip.py <fault> <seed> [seconds]

One run of `criteo67-255.train-valid` through `run.run_cell` with one
fault planted in what the program does to its validation scores
(`FAULTS`); the training and the dumped model stay the program's. It has
to come out NOT correct, by the number the fault names; the exit code is
0 when it does, 1 when the broken run passed. `scores_in_bf16` is the
precision control: the scores the metrics read rounded to bf16, the
precision under the configuration's f32. The benchmark's own runs never
call this; `test_valid.py` plants the faults at toy size.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run  # noqa: E402

CELL = "criteo67-255.train-valid"
AUC, LOGLOSS = "valid_metric_err", "valid_logloss_err"


def _walks(patch, change):
    """Every walk of committed trees over a packed validation set goes
    through `change(trees, applied, held)`: the (trees, applied) to walk
    in its place, or None for no walk; `held` keeps state between
    walks."""
    from lightgbm_tpu.models.gbdt import _RecordScores
    real = _RecordScores.walk
    held = {}

    def walk(self, trees, applied):
        instead = change(trees, applied, held)
        return 1 if instead is None else real(self, *instead)
    patch(_RecordScores, "walk", walk)


def newest_tree_left_out(patch):
    """Each walk takes the previous round's tree: the scores the metrics
    read lack the newest tree."""
    def change(trees, applied, held):
        prev = held.get("prev")
        held["prev"] = (trees, applied)
        return prev
    _walks(patch, change)
    return AUC


def shrinkage_one(patch):
    """Every tree walked at shrinkage 1, not at learning_rate: the order
    of the scores is kept, their size is not."""
    _walks(patch, lambda trees, applied, held: (
        [(t, 1.0, bias) for t, _, bias in trees], applied))
    return LOGLOSS


def _view(patch, change):
    """The row-order view the metrics read passes through `change`."""
    from lightgbm_tpu.models.aligned_builder import AlignedEngine
    real = AlignedEngine.block_scores
    patch(AlignedEngine, "block_scores",
          lambda self, rec, n: change(real(self, rec, n)))


def out_of_row_order(patch):
    """The score lane read one row off its labels."""
    import jax.numpy as jnp
    _view(patch, lambda s: jnp.roll(s, 1, axis=1))
    return AUC


def scores_in_bf16(patch):
    """The precision control: the scores the metrics read rounded to
    bf16 after the walk."""
    import jax.numpy as jnp
    _view(patch, lambda s: s.astype(jnp.bfloat16).astype(jnp.float32))
    return AUC


FAULTS = {f.__name__: f for f in (newest_tree_left_out, shrinkage_one,
                                  out_of_row_order, scores_in_bf16)}


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
                      "valid": res["detail"]["first_tree"]},
                     default=lambda o: o.item()))
    return 0 if res["correct"] is False and named in bad else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
