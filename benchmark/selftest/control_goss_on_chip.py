#!/usr/bin/env python3
"""The upper readings of task `binary_goss`, at the cell's own size on
the chip:

    python3 benchmark/selftest/control_goss_on_chip.py <fault> <seed> [seconds]

One run of `criteo67-255-goss.train-sampled` through `run.run_cell` with
one fault planted in the program's selection (`FAULTS`). It has to come
out NOT correct, by the number the fault names; the exit code is 0 when
it does, 1 when the broken run passed. The benchmark's own runs never
call this; `test_goss.py` plants the same faults at toy size.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run  # noqa: E402

CELL = "criteo67-255-goss.train-sampled"
MISMATCH = "goss_multiplier_mismatch_rows"


def rest_left_at_1(patch):
    """The sampled rest keeps multiplier 1: its rows are chosen as the
    reference chooses them and weigh an eighth of what they should."""
    from lightgbm_tpu.models import boosting_variants as bv
    real = bv.goss_sizes
    patch(bv, "goss_sizes", lambda cfg, n: real(cfg, n)[:2] + (1.0,))
    return MISMATCH


def _with_a(patch, change):
    """The selection run on `change(a, rid, seed)` in place of a."""
    from lightgbm_tpu.ops import goss
    real = goss.goss_multipliers

    def multipliers(a, rid, live, seed, *rest):
        return real(change(a, rid, seed), rid, live, seed, *rest)
    patch(goss, "goss_multipliers", multipliers)
    return MISMATCH


def uniform_sample(patch):
    """The top set drawn uniformly (20% by a second key) in place of the
    rows with the largest |g x h|: plain sampling of 30%."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops import goss
    return _with_a(patch, lambda a, rid, seed: goss.goss_key(
        rid, seed ^ jnp.uint32(0x5BD1E995)).astype(jnp.float32))


def a_in_bf16(patch):
    """|g x h| cut to bf16's 8 bits of precision before the selects. By
    its bit pattern: the chip's compiler keeps the excess precision of a
    convert to bf16 and back, and that fault then plants nothing (my chip
    run, PR 29: 12 mismatched rows, `correct: true`)."""
    import jax.numpy as jnp
    from jax import lax

    def cut(a, rid, seed):
        bits = lax.bitcast_convert_type(a, jnp.uint32)
        return lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)
    return _with_a(patch, cut)


def no_sampling(patch):
    """Sampling never starts: every iteration trains on every row."""
    from lightgbm_tpu.models.boosting_variants import GOSS
    patch(GOSS, "_goss_seed", lambda self, iter_idx: None)
    return MISMATCH


FAULTS = {f.__name__: f for f in (rest_left_at_1, uniform_sample,
                                  no_sampling, a_in_bf16)}


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
                      "sampled_tree": res["detail"]["first_tree"]
                      ["sampled_tree"]}))
    return 0 if res["correct"] is False and named in bad else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
