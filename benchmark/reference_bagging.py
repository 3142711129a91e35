"""Plain numpy reference for row and column sampling: which rows a bag
holds, at which iterations a bag is drawn and under what seed, and which
features every tree may split on.

Independent of the program: it imports nothing of it. It follows LightGBM
2.2.4 `src/boosting/gbdt.cpp:209-275` (`GBDT::Bagging`: a bag every
`bagging_freq` iterations, held in between, out-of-bag rows left out of
the tree and scored by it all the same) and
`src/treelearner/serial_tree_learner.cpp:270-300` (`BeforeTrain`: a fresh
feature sample a tree) as `SURVEY.md` and the program cite them
(`/root/reference` is not mounted here), in whole numbers.
Departures from the sources, each on purpose and each listed under the
configuration's `assumed`:

- `gbdt.cpp` walks the rows per thread block with `Random::NextFloat` and
  keeps about `bagging_fraction` of each block, which only a sequential
  walk makes again; here a row's key is a whole number made from its row
  id and the re-bag's seed alone (`benchmark/reference_goss.py:key`, a
  bijection of the row ids for every seed), and the bag is the `cnt = int(
  bagging_fraction x n)` rows with the smallest key: exactly `cnt` rows,
  over all the rows of the chip;
- the re-bag's seed is the next `randint(0, 2^31 - 1)` of
  `numpy.random.RandomState(bagging_seed)`, one draw a re-bag;
- a tree's features are `RandomState(feature_fraction_seed).choice(
  features, used, replace=False)`, one draw a tree, with `used =
  max(1, round(features x feature_fraction))` where
  `serial_tree_learner.cpp` truncates (67 x 0.8 = 53.6: 54 here, 53
  there).

Nothing here depends on the data: bags, schedule and masks are constants
of (`bagging_seed`, `bagging_freq`, `bagging_fraction`,
`feature_fraction_seed`, `feature_fraction`, the rows, the features).
"""
import numpy as np

from benchmark.reference_goss import key  # noqa: F401  (row id, seed) -> key


def bag_count(n: int, fraction: float) -> int:
    return int(fraction * n)


def bag_mask(n: int, seed: int, cnt: int) -> np.ndarray:
    """bool[n]: True for the `cnt` rows of 0..n-1 with the smallest key
    under `seed`."""
    if cnt <= 0:
        return np.zeros(n, bool)
    keys = key(np.arange(n), seed)
    # no two rows share a key, so "up to the cnt-th smallest" is cnt rows
    return keys <= np.partition(keys, min(cnt, n) - 1)[min(cnt, n) - 1]


def bag_schedule(bagging_seed: int, freq: int, iterations: int) -> list:
    """[(iteration, seed)] of every re-bag among the 0-based iterations
    0 .. iterations - 1: one at every multiple of `freq`."""
    rng = np.random.RandomState(bagging_seed)
    return [(it, int(rng.randint(0, 2**31 - 1)))
            for it in range(0, iterations, freq)]


def feature_masks(feature_fraction_seed: int, features: int,
                  fraction: float, iterations: int) -> np.ndarray:
    """bool[iterations, features]: the features tree i may split on. All
    of them where `fraction` is 1 or more (the program draws nothing
    then)."""
    masks = np.zeros((iterations, features), bool)
    if fraction >= 1.0:
        masks[:] = True
        return masks
    rng = np.random.RandomState(feature_fraction_seed)
    used = max(1, int(round(features * fraction)))
    for it in range(iterations):
        masks[it, rng.choice(features, used, replace=False)] = True
    return masks


def split_features(tree: dict) -> list:
    """The `split_feature` of every inner node of one dumped tree
    (`dump_model()["tree_info"][i]`)."""
    out, todo = [], [tree["tree_structure"]]
    while todo:
        node = todo.pop()
        if "split_feature" in node:
            out.append(int(node["split_feature"]))
            todo += [node["left_child"], node["right_child"]]
    return out
