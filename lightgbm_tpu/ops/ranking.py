"""Ranking utilities shared by the lambdarank objective and NDCG/MAP metrics.

Re-creates the reference `DCGCalculator` (`src/metric/dcg_calculator.cpp`):
discount 1/log2(2+i), label gains 2^label-1 (configurable), max-DCG from
label counts. Adds the TPU-side query bucketing: queries padded to
power-of-two document counts so per-query pairwise work is batched into a few
fixed-shape device programs instead of a ragged host loop.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import numpy as np

from ..utils import log


def dcg_discounts(n: int) -> np.ndarray:
    """discount[i] = 1/log2(2+i) (reference dcg_calculator.cpp:Init)."""
    return 1.0 / np.log2(2.0 + np.arange(n, dtype=np.float64))


def max_dcg_at_k(k: int, labels: np.ndarray, label_gain: np.ndarray) -> float:
    """reference DCGCalculator::CalMaxDCGAtK (dcg_calculator.cpp:53-77):
    accumulate discounts over labels sorted descending."""
    n = len(labels)
    k = min(k, n)
    if k <= 0:
        return 0.0
    sorted_gains = np.sort(label_gain[labels])[::-1]
    disc = dcg_discounts(k)
    return float(np.sum(sorted_gains[:k] * disc))


def dcg_at_k(k: int, labels: np.ndarray, scores: np.ndarray,
             label_gain: np.ndarray) -> float:
    """reference DCGCalculator::CalDCGAtK: DCG of score-sorted order."""
    n = len(labels)
    k = min(k, n)
    if k <= 0:
        return 0.0
    order = np.argsort(-scores, kind="stable")
    disc = dcg_discounts(k)
    return float(np.sum(label_gain[labels[order[:k]]] * disc))


def bucket_queries(query_boundaries: np.ndarray, min_size: int = 8,
                   include: Optional[np.ndarray] = None
                   ) -> Dict[int, Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Group queries by padded (power-of-two) document count.

    Returns {padded_size: (query_ids [Q], doc_idx [Q, S] int32,
    mask [Q, S] bool)} where doc_idx are global row ids (pads point at the
    query's first doc and are masked out). `include` (bool per query)
    restricts bucketing to a subset — the fused-kernel path uses it to
    route only its oversize leftovers here.

    Emits a `rank_buckets` log event (docs-per-bucket histogram and
    padded-pair waste %) at dataset construct time so ladder re-tuning
    is data-driven instead of hand-derived.
    """
    qb = np.asarray(query_boundaries, np.int64)
    counts = np.diff(qb)
    # pairwise work is O(S^2), so ladder spacing is pure padding waste
    # vs compiled-program count. From 32 to 256 docs — where real
    # ranking sets concentrate (MSLR queries are ~40..200 docs) — the
    # ladder runs QUARTER steps (pow2 + 1.25x/1.5x/1.75x): a 161-doc
    # query pads to 192 not 256 (1.78x fewer pairs), a 130-doc one to
    # 160 not 192, for at most ~9 extra compiled programs. BELOW 32 the
    # steps are pow2 only: the quarter rungs at 10/12/14/20/24/28 held
    # <2% of MSLR's pair work yet 6 of the ladder's ~15 compiled
    # programs — cold-start XLA compiles for nothing (the 255-bin
    # warm-up cliff: round 0 compiled 15 bucket programs). Above 256 the
    # ladder falls back to ~sqrt(2) spacing (pow2 + 1.5x midpoints) —
    # giant queries are rare enough that halved pair tensors no longer
    # pay for the extra compiles.
    ladder = []
    s = max(8, min_size)
    while s <= (1 << 20):
        ladder.append(s)
        if 32 <= s <= 256:
            ladder.extend([s + s // 4, s + s // 2, s + 3 * s // 4])
        elif s > 256:
            ladder.append(s + s // 2)
        s <<= 1
    ladder = sorted(set(ladder))
    sizes = {}
    for q, c in enumerate(counts):
        if include is not None and not include[q]:
            continue
        c = max(int(c), 1)
        need = max(c, min_size)
        s = next((x for x in ladder if x >= need), None)
        if s is None:       # beyond the ladder: plain pow2 rounding
            s = 1 << int(math.ceil(math.log2(need)))
        sizes.setdefault(s, []).append(q)
    if sizes:
        real_pairs = sum(int(counts[q]) ** 2
                         for qs in sizes.values() for q in qs)
        padded_pairs = sum(s * s * len(qs) for s, qs in sizes.items())
        log.event(
            "rank_buckets",
            queries=sum(len(qs) for qs in sizes.values()),
            docs=int(sum(counts[q] for qs in sizes.values() for q in qs)),
            buckets={str(s): [len(qs),
                              int(sum(counts[q] for q in qs))]
                     for s, qs in sorted(sizes.items())},
            pair_waste_pct=round(
                100.0 * (padded_pairs - real_pairs) / max(real_pairs, 1),
                1),
            subset=include is not None)
    out = {}
    for s, qids in sizes.items():
        qids = np.asarray(qids, np.int64)
        doc_idx = np.zeros((len(qids), s), np.int32)
        mask = np.zeros((len(qids), s), bool)
        for row, q in enumerate(qids):
            lo, hi = int(qb[q]), int(qb[q + 1])
            c = hi - lo
            doc_idx[row, :c] = np.arange(lo, hi, dtype=np.int32)
            doc_idx[row, c:] = lo
            mask[row, :c] = True
        out[s] = (qids, doc_idx, mask)
    return out
