"""Plain numpy reference of upstream's data-parallel tree learner
(`tree_learner=data`, LightGBM 2.2.4 `src/treelearner/
data_parallel_tree_learner.cpp`): the rows split into contiguous shards,
one a machine; each shard builds the histograms of its own rows, the
histograms are summed over the machines (`Network::ReduceScatter` with a
`HistogramSumReducer`), and every machine chooses the same split from
the sum (`SyncUpGlobalBestSplit`). So the tree is the one a serial
learner grows over all the rows, and what can go wrong is what the
shards do apart: a shard whose histogram is left out of the sum, a
shard that holds fewer rows than its share.

Independent of the program: it imports nothing of it. `root_split` takes
the bins as the program's binning gave them (the bin boundaries are not
what this reference checks) and the first iteration's gradients.
"""
import numpy as np


def shard_bounds(rows: int, shards: int) -> list:
    """[lo, hi) of each shard: ceil(rows / shards) rows a shard, the
    last one short where they do not divide."""
    per = -(-rows // shards)
    return [(min(rows, s * per), min(rows, (s + 1) * per))
            for s in range(shards)]


def shard_stretches(seed: int, rows: int, shards: int, take: int) -> list:
    """[lo, hi) row ranges, one inside each shard, `take` rows in all:
    shard s's from an offset drawn from the seed, the last shard's ending
    at the last row."""
    rng = np.random.default_rng([int(seed), 42])
    out = []
    bounds = shard_bounds(rows, shards)
    for s, (lo, hi) in enumerate(bounds):
        n = min(max(1, take // shards), hi - lo)
        start = hi - n if s == shards - 1 \
            else lo + int(rng.integers(hi - lo - n + 1))
        out.append((start, start + n))
    return out


def shard_histograms(bins: np.ndarray, grad: np.ndarray, hess: np.ndarray,
                     num_bins: int, shards: int) -> np.ndarray:
    """float64 [shards, F, num_bins, 3]: each shard's histograms (sum of
    gradients, of hessians, row count) of its own rows."""
    bins = np.asarray(bins)
    out = np.zeros((shards, bins.shape[1], num_bins, 3))
    for s, (lo, hi) in enumerate(shard_bounds(len(bins), shards)):
        for f in range(bins.shape[1]):
            col = bins[lo:hi, f].astype(np.int64)
            out[s, f, :, 0] = np.bincount(col, grad[lo:hi], num_bins)
            out[s, f, :, 1] = np.bincount(col, hess[lo:hi], num_bins)
            out[s, f, :, 2] = np.bincount(col, minlength=num_bins)
    return out


def best_split(hist: np.ndarray, lambda_l2: float = 0.0,
               min_data_in_leaf: int = 20,
               min_sum_hessian_in_leaf: float = 1e-3) -> dict:
    """The split of largest gain over summed histograms [F, B, 3], every
    threshold `bin <= t` of every feature (numerical, no missing values):
    {"feature", "bin", "left_count", "gain"}, the gain as the dump's
    `split_gain` has it (the children's terms less the parent's)."""
    def term(g, h):
        return g * g / (h + lambda_l2)
    total = hist[0].sum(axis=0)                  # every feature's rows
    left = np.cumsum(hist, axis=1)[:, :-1]       # [F, B - 1, 3]
    right = total[None, None, :] - left
    ok = ((left[..., 2] >= min_data_in_leaf)
          & (right[..., 2] >= min_data_in_leaf)
          & (left[..., 1] >= min_sum_hessian_in_leaf)
          & (right[..., 1] >= min_sum_hessian_in_leaf))
    gain = (term(left[..., 0], left[..., 1]) + term(right[..., 0],
                                                     right[..., 1])
            - term(total[0], total[1]))
    gain = np.where(ok, gain, -np.inf)
    f, b = np.unravel_index(int(np.argmax(gain)), gain.shape)
    return {"feature": int(f), "bin": int(b),
            "left_count": int(round(left[f, b, 2])),
            "gain": float(gain[f, b])}


def root_split(bins: np.ndarray, labels: np.ndarray, shards: int,
               num_bins: int, **split_args) -> dict:
    """Tree 0's root of `objective=binary` boosted from the label average
    (g = p0 - y, h = p0 (1 - p0)) as the data-parallel learner finds it:
    per-shard histograms summed, then `best_split`."""
    y = np.asarray(labels, np.float64)
    p0 = y.mean()
    grad = p0 - y
    hess = np.full(len(y), p0 * (1.0 - p0))
    hist = shard_histograms(bins, grad, hess, num_bins, shards).sum(axis=0)
    return best_split(hist, **split_args)
