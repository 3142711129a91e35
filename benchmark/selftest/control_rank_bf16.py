#!/usr/bin/env python3
"""The control of the ranking gradients' limit, at a cell's own size, on
the host:

    python3 benchmark/selftest/control_rank_bf16.py <samples.npz> ...

The rank kernel's stated arithmetic is pair factors in bf16 and their
sums in f32. The control is the plain reference put in the program's
place one step below, pair factors AND sums in bf16, on the queries that a
run on the chip sampled (`rank_seeds_on_chip.py` leaves them: sizes,
labels, the scores the window left), compared as `tasks/lambdarank.py`
compares: at the start scores and at the trained ones. Every reading has
to be over `GRAD_TOL`; the exit code is 0 when all are. It needs no chip:
the control is the reference. The same control at toy size, through
`run_cell`, is `test_benchmark.py`'s
`test_control_gradients_summed_in_bf16_fail_the_gradient_limit`.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

from benchmark import reference  # noqa: E402
from benchmark.tasks import lambdarank as task  # noqa: E402


def main(argv) -> int:
    failed_all = True
    for path in argv[1:]:
        with np.load(path) as f:
            sizes, labels, score = f["sizes"], f["labels"], f["score"]
        line = {"samples": os.path.basename(path), "limit": task.GRAD_TOL}
        for name, s in (("", np.zeros(len(labels))),
                        ("_trained", score.astype(np.float64))):
            g, h = reference.lambdarank_gradients(s, labels, sizes)
            g16, h16 = reference.lambdarank_gradients(
                s, labels, sizes, dtype=ml_dtypes.bfloat16)
            line["grad_err" + name] = task.worst(g16, g)
            line["hess_err" + name] = task.worst(h16, h)
        print(json.dumps(line), flush=True)
        failed_all &= all(v > task.GRAD_TOL for k, v in line.items()
                          if k.endswith(("err", "trained")))
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
