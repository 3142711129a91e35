"""Plain numpy reference for gradient-based one-side sampling: which rows
an iteration of `boosting=goss` trains on, and at what weight.

Independent of the program: it imports nothing of it. It follows
LightGBM 2.2.4 `src/boosting/goss.hpp:96-134` (`BaggingHelper`; Ke et
al., NeurIPS 2017, Algorithm 2) as `SURVEY.md` and
`lightgbm_tpu/models/boosting_variants.py` cite it (`/root/reference` is
not mounted here), sort-based, in float64 and whole numbers. Departures
from the header, each on purpose:

- the header works per thread block of rows with `top_k` and `other_k`
  scaled to the block; here they are exact over all rows, `max(1, int(n x
  rate))` each, as the program states in its guarantees;
- the header finds its threshold with `ArgMaxAtK` and keeps a row when
  `a >= threshold`, so rows that tie with the `top_k`-th are all kept:
  the same here, and the kept set can then exceed `top_k`;
- the header draws the rest with `Random::NextFloat` in row order, which
  only a sequential walk can make again; here a row's key is a whole
  number made from its row id and the iteration's seed alone (`key`), and
  the `other_k` rows of the rest with the smallest key are kept. Every
  such row weighs `(n - top_k) / other_k`, as in the header.
"""
import numpy as np

M32 = np.uint64(0xFFFFFFFF)


def key(row_ids: np.ndarray, seed: int) -> np.ndarray:
    """A row's sampling key, a whole number under 2^32: the row id times
    0x9E3779B1 plus the seed, then murmur3's 32-bit finaliser, all modulo
    2^32. For one seed no two row ids share a key."""
    x = (np.asarray(row_ids, np.uint64) * np.uint64(0x9E3779B1)
         + np.uint64(seed)) & M32
    x ^= x >> np.uint64(16)
    x = (x * np.uint64(0x85EBCA6B)) & M32
    x ^= x >> np.uint64(13)
    x = (x * np.uint64(0xC2B2AE35)) & M32
    return x ^ (x >> np.uint64(16))


def goss_multipliers(grad, hess, row_ids, seed: int, top_rate: float,
                     other_rate: float) -> dict:
    """{"multiplier": float64 per row (0 = left out), "threshold",
    "top_k", "other_k", "kept_top", "a"} for one iteration's gradients
    and hessians before any multiplier."""
    a = np.abs(np.asarray(grad, np.float64) * np.asarray(hess, np.float64))
    n = len(a)
    top_k = max(1, int(n * top_rate))
    other_k = max(1, int(n * other_rate))
    threshold = np.partition(a, n - top_k)[n - top_k]   # top_k-th largest
    big = a >= threshold
    rest = np.flatnonzero(~big)
    keep = rest[np.argsort(key(np.asarray(row_ids)[rest], seed),
                           kind="stable")[:other_k]]
    mult = np.where(big, 1.0, 0.0)
    mult[keep] = (n - top_k) / other_k
    return {"multiplier": mult, "threshold": float(threshold), "a": a,
            "top_k": top_k, "other_k": other_k, "kept_top": int(big.sum())}
