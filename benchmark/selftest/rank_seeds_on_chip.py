#!/usr/bin/env python3
"""The lower readings of a ranking cell's limits over many seeds, from
one run on the chip:

    python3 benchmark/selftest/rank_seeds_on_chip.py <cell> <seed> <n> [seconds]

A ranking cell's generator makes the same rows under every seed
(`generators/istella.py`), and the seed draws only the queries and rows
that `tasks/lambdarank.py` compares after the window. So one run of the
cell through `run.run_cell` can read the task's numbers for seeds
`seed .. seed + n - 1`: the first-tree check is called once a seed on the
same booster. Prints one JSON line a seed and the run's own result line
last; exit code 0 when every seed is within every limit. For each seed it
also leaves `chiprun_out/rank_samples/<cell>.<seed>.npz` (the sampled
queries' sizes, labels and trained scores), from which
`control_rank_bf16.py` reads the control on the host. The benchmark's own
runs never call this.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from benchmark import run  # noqa: E402
from benchmark.tasks import lambdarank as task  # noqa: E402


def main(argv) -> int:
    cell, seed, n = argv[1], int(argv[2]), int(argv[3])
    seconds = float(argv[4]) if len(argv) > 4 else 1.0
    out = os.path.join(run.ROOT, "chiprun_out", "rank_samples")
    os.makedirs(out, exist_ok=True)
    real, lines = task.first_tree, []

    def every_seed(r):
        first = None
        groups = np.asarray(r.groups, np.int64)
        bounds = np.concatenate([[0], np.cumsum(groups)])
        score = np.asarray(r.booster._gbdt.train_score.score[0])
        for s in range(seed, seed + n):
            r.gen.seed = s
            compared, detail = real(r)
            first = first or (compared, detail)
            lines.append({"seed": s, "compared": {
                k: {"value": v, "limit": lim} for k, (v, lim)
                in compared.items()}})
            picked = task.sampled_queries(s, len(groups), task.GRAD_QUERIES)
            at = np.concatenate([np.arange(bounds[q], bounds[q + 1])
                                 for q in picked])
            np.savez_compressed(
                os.path.join(out, f"{cell}.{s}.npz"), sizes=groups[picked],
                labels=r.labels[at].astype(np.int8), score=score[at])
        r.gen.seed = seed
        return first
    task.first_tree = every_seed
    res = run.run_cell(cell, seed, seconds, False)
    res.pop("detail")
    for line in lines:
        print(json.dumps(line), flush=True)
    print(json.dumps(res), flush=True)
    within = all(c["value"] <= c["limit"] for line in lines
                 for c in line["compared"].values())
    return 0 if res["correct"] and within else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
