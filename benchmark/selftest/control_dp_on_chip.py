#!/usr/bin/env python3
"""The upper readings of the data-parallel checks (task `binary_dp`), at
the cell's own size on the chips:

    python3 benchmark/selftest/control_dp_on_chip.py <fault> <seed> [seconds]

One run of `criteo67-255-dp4.train` through `run.run_cell` with one fault
planted in what the shards do apart (`FAULTS`). It has to come out NOT
correct, by the number the fault names; the exit code is 0 when it does,
1 when the broken run passed. The benchmark's own runs never call this;
`test_dp.py` plants the same faults at toy size.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run  # noqa: E402

CELL = "criteo67-255-dp4.train"
AXIS = "data"       # the mesh axis of the data-parallel learner


def no_all_reduce(patch):
    """The histograms are not summed over the chips: each shard chooses
    its splits from its own rows' histograms."""
    from lightgbm_tpu.models import aligned_builder
    patch(aligned_builder, "hist_psum", lambda x, axis: x)
    return "root_left_count_err"


def shard3_scores_still(patch):
    """Shard 3's score lane is left unchanged by every tree."""
    from jax import lax

    from lightgbm_tpu.models import aligned_builder
    real = aligned_builder._add_to_lane

    def add(rec, lane, addend):
        keep = lax.axis_index(AXIS) != 3
        return real(rec, lane, addend * keep.astype(addend.dtype))
    patch(aligned_builder, "_add_to_lane", add)
    return "shard_score_walk_err"


def shard1_block_short(patch):
    """Shard 1 packs one block short: its first block is not written."""
    import jax.numpy as jnp
    from jax import lax

    from lightgbm_tpu.ops import aligned
    real = aligned._pack_block

    def pack(rec, bins, first, facts, rows, **static):
        at = (first // static["chunk"], 0, 0)
        shape = (static["blk"], static["w_pad"], static["chunk"])
        before = lax.dynamic_slice(rec, at, shape)
        out, token = real(rec, bins, first, facts, rows, **static)
        skip = (lax.axis_index(AXIS) == 1) & (first == 0)
        out = lax.dynamic_update_slice(
            out, jnp.where(skip, before, lax.dynamic_slice(out, at, shape)),
            at)
        return out, jnp.where(skip, 0, token)
    patch(aligned, "_pack_block", pack)
    return "shard_rows_err"


FAULTS = {f.__name__: f for f in (no_all_reduce, shard3_scores_still,
                                  shard1_block_short)}


def failing(compared) -> list:
    return [k for k, c in compared.items()
            if not (c["value"] <= c["limit"] if c["holds"] == "<="
                    else c["value"] >= c["limit"])]


def main(argv) -> int:
    fault, seed = argv[1], int(argv[2])
    seconds = float(argv[3]) if len(argv) > 3 else 1.0
    named = FAULTS[fault](setattr)
    res = run.run_cell(CELL, seed, seconds, False)
    bad = failing(res["compared"])
    print(json.dumps({"fault": fault, "seed": seed, "named": named,
                      "correct": res["correct"], "failing": bad,
                      "compared": res["compared"]}))
    return 0 if res["correct"] is False and named in bad else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
