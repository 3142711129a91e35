"""Istella-LETOR-like query-document rows: ONE data set in ONE order,
whatever the seed.

Istella LETOR (Dato et al., TOIS 2016) is 10,454,629 documents of 33,018
queries, 220 dense numeric features and a relevance grade 0-4 a document.
There is no network and no such file here, so rows are drawn with the
columns, the margin and the block-wise streams of `generators/criteo.py`
(count-like and continuous columns, a fixed sparse non-linear margin over
a few of them), and two things of a ranking set added:

- the label is a grade: the margin plus logistic noise, cut at the fixed
  `grade_thresholds`, so that most documents are grade 0 and the trees
  have an order to learn inside every query;
- consecutive rows form queries. `query_segments` lists [queries, rows]
  pairs laid end to end (the training set, then the holdout): each
  segment's query lengths are drawn once from `structure_seed`, between 1
  and `longest_query` with the segment's mean, and sum to its rows exactly.

**The seed changes no row and no order.** Every stream is keyed by
`structure_seed`, the block and the column, so a run trains on the same
rows in the same places as every other run, as every user of the
published file does. Measured (PERF.md, PR 28): fresh draws for every seed
grew other trees, and `train_ms_per_iter` spread 1.4% and NDCG@10 2.3%
from seed to seed while two runs of one seed agreed to 0.003%; the same
rows in another order of queries still spread 1.35%, because another
order of f32 histogram sums grows other trees from about the tenth on. A
tree learner's time follows its trees, so the only window that two runs
share is the one on the same rows in the same order. The seed is kept
(`self.seed`) for what is drawn after the window: the task's sample of
queries and rows that `correct` compares.

Query lengths have to be the structure's in any case: the program's rank
kernel packs queries into tiles in their order and compiles the tile
count in.
"""
import numpy as np

from benchmark.generators import criteo


def query_lengths(st: np.random.Generator, queries: int, rows: int,
                  longest: int) -> np.ndarray:
    """`queries` lengths in [1, longest] that sum to `rows`: a Beta(a, 1)
    share of `longest` with the mean rows / queries (for Istella: skewed
    towards the cap, as a crawl's candidate lists are), then moved onto
    the exact sum one document at a time."""
    if not queries <= rows <= queries * longest:
        raise ValueError(f"{queries} queries of 1..{longest} documents "
                         f"cannot hold {rows} rows")
    mean = rows / queries
    a = mean / max(longest - mean, 1e-9)
    n = np.clip(np.rint(longest * st.beta(a, 1.0, queries)), 1,
                longest).astype(np.int64)
    while (diff := rows - int(n.sum())) != 0:
        step = 1 if diff > 0 else -1
        room = np.flatnonzero((n + step >= 1) & (n + step <= longest))
        n[st.choice(room, size=min(abs(diff), len(room)),
                    replace=False)] += step
    return n


class Generator(criteo.Generator):
    """The columns' distributions, the margin, the block-wise arithmetic
    and `rows` / `column` / `block` are `criteo.Generator`'s; where a
    block's draws come from, the label and the queries are this class's."""

    DATA, SAMPLE = (1,), ()     # what a stream's key holds after the seed

    def __init__(self, spec: dict, seed: int, stream: tuple = DATA):
        super().__init__(spec, seed)
        self.structure_seed = int(spec["structure_seed"])
        self.stream = tuple(stream)
        self.thresholds = np.asarray(spec["grade_thresholds"], np.float32)
        st = np.random.default_rng([self.structure_seed, 1])
        self.sizes = np.concatenate([
            query_lengths(st, int(q), int(r), int(spec["longest_query"]))
            for q, r in spec["query_segments"]])
        self.bounds = np.concatenate([[0], np.cumsum(self.sizes)])

    def _rng(self, block: int, stream: int) -> np.random.Generator:
        return np.random.Generator(np.random.SFC64(np.random.SeedSequence(
            (self.structure_seed, *self.stream, block, stream))))

    def sample(self, rows: int) -> np.ndarray:
        """`rows` rows of the same distributions from a stream of their
        own: what bin boundaries are found from."""
        return type(self)(self.spec, self.seed, self.SAMPLE).rows(0, rows)[0]

    def _label(self, m: np.ndarray, u: np.ndarray) -> np.ndarray:
        """Grade = how many thresholds margin + logistic noise passes."""
        with np.errstate(divide="ignore"):
            latent = m + np.log(u / (1.0 - u))
        return np.searchsorted(self.thresholds, latent).astype(np.float32)

    def groups(self, first_row: int, rows: int) -> np.ndarray:
        """Lengths of the queries that make up rows [first_row, first_row
        + rows): whole queries only, anything else is an error."""
        lo, hi = np.searchsorted(self.bounds, [first_row, first_row + rows])
        if hi >= len(self.bounds) or self.bounds[lo] != first_row \
                or self.bounds[hi] != first_row + rows:
            raise ValueError(f"rows {first_row}..{first_row + rows} do not "
                             "begin and end on query boundaries")
        return self.sizes[lo:hi]
