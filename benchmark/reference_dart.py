"""Plain reference for `boosting=dart`: which trees every iteration drops,
the shrinkage of the tree it builds, and every tree's weight afterwards.

Independent of the program: it imports nothing of it. It follows LightGBM
2.2.4 `src/boosting/dart.hpp:97-196` (`DroppingTrees`, `Normalize`;
Rashmi and Gilad-Bachrach, AISTATS 2015) in float64. Nothing here
depends on the data: the schedule is a constant of (`drop_seed`, the
rates, the learning rate), so a run's whole drop history can be written
down before it starts.

One departure from the header, on purpose: it draws with LightGBM's own
`Random`; here, as in the program, the draws are
`numpy.random.RandomState(drop_seed).rand()` in the header's order: one
for the skip, then one a tree in iteration order, stopping once
`max_drop` trees are dropped.

An iteration that drops k trees, each of weight w before it:
- builds its tree at learning_rate / (1 + k) (`xgboost_dart_mode`:
  learning_rate / (learning_rate + k), and learning_rate where k = 0);
- leaves every dropped tree at w x k / (k + 1) (`xgboost_dart_mode`:
  w x k / (k + learning_rate)).
Without `uniform_drop` a tree is dropped with probability drop_rate x w /
(mean weight), the rate first cut to max_drop / (sum of weights) x (mean
weight)... as the header writes it: min(drop_rate, max_drop x inv_avg /
sum_weight); with it, with probability min(drop_rate, max_drop /
iterations so far).
"""
import numpy as np


def drop_schedule(drop_seed: int, iterations: int, learning_rate: float,
                  drop_rate: float = 0.1, max_drop: int = 50,
                  skip_drop: float = 0.5, uniform_drop: bool = False,
                  xgboost_dart_mode: bool = False) -> list:
    """One dict an iteration (0-based `iter`): `skipped`, `dropped` (the
    iterations whose trees are dropped, ascending), `shrinkage` of the
    new tree, and `weights`, every tree's weight after the iteration
    (the new tree's last)."""
    rng = np.random.RandomState(drop_seed)
    lr = float(learning_rate)
    weight = []         # every tree's weight: what its leaf values carry
    drawn = []          # the header's tree_weight_: kept without uniform_drop
    sum_weight = 0.0
    out = []
    for it in range(iterations):
        dropped = []
        skipped = bool(rng.rand() < skip_drop)
        if not skipped:
            rate = float(drop_rate)
            if not uniform_drop:
                inv_avg = len(drawn) / sum_weight if drawn else 1.0
                if max_drop > 0 and sum_weight > 0:
                    rate = min(rate, max_drop * inv_avg / sum_weight)
                for i in range(it):
                    if rng.rand() < rate * drawn[i] * inv_avg:
                        dropped.append(i)
                        if len(dropped) >= max_drop > 0:
                            break
            else:
                if max_drop > 0 and it > 0:
                    rate = min(rate, max_drop / it)
                for i in range(it):
                    if rng.rand() < rate:
                        dropped.append(i)
                        if len(dropped) >= max_drop > 0:
                            break
        k = float(len(dropped))
        if not xgboost_dart_mode:
            shrinkage, keep, gone = lr / (1.0 + k), k / (k + 1.0), \
                1.0 / (k + 1.0)
        else:
            shrinkage = lr / (lr + k) if dropped else lr
            keep, gone = k / (k + lr), 1.0 / (k + lr)
        for i in dropped:
            weight[i] *= keep
            if not uniform_drop:
                sum_weight -= drawn[i] * gone
                drawn[i] *= keep
        weight.append(shrinkage)
        if not uniform_drop:
            drawn.append(shrinkage)
            sum_weight += shrinkage
        out.append({"iter": it, "skipped": skipped, "dropped": dropped,
                    "shrinkage": shrinkage, "weights": list(weight)})
    return out


def dropped_per_iteration(schedule, first: int, count: int) -> float:
    """Mean number of dropped trees over iterations [first, first +
    count), 0-based."""
    return sum(len(s["dropped"]) for s in schedule[first:first + count]) \
        / count
