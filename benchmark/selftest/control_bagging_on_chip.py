#!/usr/bin/env python3
"""The upper readings of task `binary_bagged`, at the cell's own size on
the chip:

    python3 benchmark/selftest/control_bagging_on_chip.py <fault> <seed> [seconds]

One run of `criteo67-255-bagged.train-rebagging` through `run.run_cell`
with one fault planted in the program's sampling (`FAULTS`). It has to
come out NOT correct, by the number the fault names; the exit code is 0
when it does, 1 when the broken run passed. The benchmark's own runs
never call this; `test_bagging.py` plants the same faults at toy size.

`host_drawn` is no fault but the path this cell's task refuses: the bag
drawn on the host and uploaded at every re-bag, one round in flight. On a
program whose engine can draw a bag it takes that away (`_host_bag_why`);
on one that cannot (the parent of the PR that added the draw) it stands
in the attribute the task looks for, so that the run reaches its result
line. Either way it reads what a re-bag on the host costs at this size,
and comes out not correct by the schedule: no `bag.draw` is recorded.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run  # noqa: E402
from benchmark.selftest.control_goss_on_chip import failing  # noqa: E402

CELL = "criteo67-255-bagged.train-rebagging"


def never_redrawn(patch):
    """Bag 0 is held for ever: no bag is drawn after iteration 0."""
    from lightgbm_tpu.models.gbdt import GBDT
    real = GBDT._draw_bag_seed
    patch(GBDT, "_draw_bag_seed",
          lambda self: (self._aligned_sample or [real(self)])[0])
    return "bag_schedule_mismatch_iters"


def fraction_0_7(patch):
    """Bags of int(0.7 n) rows where the configuration says 0.8."""
    from lightgbm_tpu.models.gbdt import GBDT
    patch(GBDT, "_bag_cnt_plain", lambda self: int(0.7 * self.num_data))
    return "bag_kept_count_err"


def feature_mask_ignored(patch):
    """The mask is drawn and queued, and the build program is handed all
    ones: every tree may split on every feature."""
    import jax.numpy as jnp
    from lightgbm_tpu.models.device_learner import DeviceTreeLearner
    patch(DeviceTreeLearner, "_fmask_arr",
          lambda self, mask: jnp.ones(self.num_features, jnp.float32))
    return "feature_mask_violations"


def out_of_bag_scores_left(patch):
    """A tree's leaf values reach only the rows it trained on: behind
    every build the out-of-bag rows get their old scores back."""
    import jax.numpy as jnp
    from lightgbm_tpu.models.aligned_builder import AlignedEngine
    real = AlignedEngine.train_iter

    def train_iter(self, *args, **kwargs):
        old = self.row_scores_dev()
        out = real(self, *args, **kwargs)
        in_bag = self._materialized("bag") > 0.5
        self.set_row_scores(jnp.where(in_bag, self.row_scores_dev(), old))
        return out
    patch(AlignedEngine, "train_iter", train_iter)
    return "score_walk_err"


def another_key(patch):
    """The device's draw under a key with another multiplier: a bag of
    the right size that is not the reference's."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops import goss
    real = goss.goss_key
    patch(goss, "goss_key",
          lambda rid, seed: real(rid, jnp.asarray(seed).astype(jnp.uint32)
                                 ^ jnp.uint32(0x5BD1E995)))
    return "bag_mismatch_rows"


def host_drawn(patch):
    """See the module's docstring."""
    from lightgbm_tpu.models.aligned_builder import AlignedEngine
    from lightgbm_tpu.models.gbdt import GBDT
    if hasattr(AlignedEngine, "bag_select"):
        patch(GBDT, "_host_bag_why", lambda self: "the control asks for it")
    else:
        patch(AlignedEngine, "bag_select", None)
    return "bag_schedule_mismatch_iters"


FAULTS = {f.__name__: f for f in (
    never_redrawn, fraction_0_7, feature_mask_ignored,
    out_of_bag_scores_left, another_key)}


def main(argv) -> int:
    fault, seed = argv[1], int(argv[2])
    seconds = float(argv[3]) if len(argv) > 3 else 1.0
    named = dict(FAULTS, host_drawn=host_drawn)[fault](setattr)
    res = run.run_cell(CELL, seed, seconds, False)
    bad = failing(res["compared"])
    detail = res["detail"]
    print(json.dumps({"cell": CELL, "fault": fault, "seed": seed,
                      "correct": res["correct"], "named": named,
                      "failing": bad, "compared": res["compared"],
                      "metrics": res["metrics"],
                      "attempted": res["attempted"],
                      "window_host_s": detail["walls"]["window_host_s"],
                      "first_tree": detail["first_tree"]},
                     default=lambda o: o.item()))
    return 0 if res["correct"] is False and named in bad else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
