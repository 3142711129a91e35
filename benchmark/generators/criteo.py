"""Criteo-like dense numeric click rows, made from a seed.

The reference's data-parallel experiment trains on the Criteo 1.7B-row
click log as 67 dense numeric features (13 integer counts, the rest
count/CTR statistics of the categorical fields). There is no network and
no such file here, so rows are drawn: `count_columns` heavy-tailed integer
counts (many zeros and ties, as I1..I13 have), `continuous_columns`
normal or log-normal columns, and a 0/1 label drawn from a sparse
non-linear margin over a dozen of them, so that trees find real splits
and a holdout AUC means something.

Two rules keep every run on the same compiled programs and let one column
be made again without the matrix:

- what depends on the seed is only the draws. The columns' distributions
  and the margin's terms come from `structure_seed` in the config, and
  every shape from the config's row counts;
- rows come in blocks of `block_rows`, and each (block, column) has its
  own stream, so `column(j, lo, hi)` costs one column's draws.
"""
import numpy as np


class Generator:
    def __init__(self, spec: dict, seed: int):
        self.spec = spec
        self.n_count = int(spec["count_columns"])
        self.n_cont = int(spec["continuous_columns"])
        self.features = self.n_count + self.n_cont
        self.block_rows = int(spec["block_rows"])
        self.seed = int(seed)
        st = np.random.default_rng(int(spec["structure_seed"]))
        f = self.features
        # count columns: floor(exp(mu + sigma z)) - heavy tail, mass at 0;
        # continuous: mu + sigma z, every third one exponentiated
        self.mu = st.uniform(-0.5, 2.0, f).astype(np.float32)
        self.sigma = st.uniform(0.6, 1.6, f).astype(np.float32)
        self.lognormal = np.zeros(f, bool)
        self.lognormal[self.n_count::3] = True
        terms = int(spec["margin_terms"])
        self.term_cols = st.choice(f, size=(terms, 2), replace=True)
        self.term_w = st.uniform(0.4, 1.1, terms) * st.choice([-1, 1], terms)
        self.term_kind = st.integers(0, 3, terms)   # tanh, step, product
        self.term_q = st.uniform(-0.8, 0.8, (terms, 2)).astype(np.float32)
        self.bias = float(spec["margin_bias"])

    # ------------------------------------------------------------------
    def _rng(self, block: int, stream: int) -> np.random.Generator:
        return np.random.Generator(np.random.SFC64(
            np.random.SeedSequence((self.seed, block, stream))))

    def _z(self, block: int, j: int, out=None) -> np.ndarray:
        return self._rng(block, j).standard_normal(
            self.block_rows, dtype=np.float32, out=out)

    def _value(self, j: int, z: np.ndarray) -> np.ndarray:
        """Column j's values from its standard-normal draws, in place."""
        z *= self.sigma[j]
        z += self.mu[j]
        if j < self.n_count:
            np.floor(np.exp(z, out=z), out=z)
        elif self.lognormal[j]:
            np.exp(z, out=z)
        return z

    def _block_t(self, block: int, z=None):
        """One block, column-major, into `z`: (z [F, B], y [B])."""
        b = self.block_rows
        if z is None:
            z = np.empty((self.features, b), np.float32)
        for j in range(self.features):
            self._z(block, j, out=z[j])
        # the margin reads the standard-normal draws, so each term is a
        # threshold on the column's own quantile scale
        m = np.full(b, self.bias, np.float32)
        for (ja, jb), w, kind, (qa, qb) in zip(self.term_cols, self.term_w,
                                               self.term_kind, self.term_q):
            if kind == 0:
                m += np.float32(w) * np.tanh(z[ja] - qa)
            elif kind == 1:
                m += np.float32(w) * (z[ja] > qa)
            else:
                m += np.float32(w) * np.sign((z[ja] - qa) * (z[jb] - qb))
        y = self._label(m, self._rng(block, self.features).random(
            b, dtype=np.float32))
        for j in range(self.features):
            self._value(j, z[j])
        return z, y

    def _label(self, m: np.ndarray, u: np.ndarray) -> np.ndarray:
        """0/1 with probability sigmoid(margin), from one uniform draw a
        row (a generator of another label overrides this alone)."""
        return (u * (1.0 + np.exp(-m)) < 1.0).astype(np.float32)

    def block(self, block: int, z=None, x=None):
        """(X [block_rows, F] float32 row-major, y [block_rows] float32).
        `z` [F, block_rows] and `x` [block_rows, F] are buffers to reuse:
        fresh ones cost more in page faults than the draws do."""
        z, y = self._block_t(block, z)
        if x is None:
            x = np.empty((self.block_rows, self.features), np.float32)
        step = 4096     # transpose in tiles that stay in cache
        for r in range(0, self.block_rows, step):
            x[r:r + step] = z[:, r:r + step].T
        return x, y

    def sample(self, rows: int) -> np.ndarray:
        """`rows` rows of the same distributions from a stream that no
        seed changes (the config's `structure_seed`): what bin boundaries
        are found from, so that they are the same in every run."""
        fixed = Generator(self.spec, self.spec["structure_seed"])
        return fixed.rows(0, rows)[0]

    def _parts(self, lo: int, hi: int):
        """(block, slice of it) for each block that rows lo..hi touch."""
        b = self.block_rows
        for blk in range(lo // b, (hi - 1) // b + 1):
            yield blk, slice(max(lo - blk * b, 0), min(hi - blk * b, b))

    def rows(self, lo: int, hi: int):
        """Rows lo..hi of the endless table: (X [hi-lo, F], y [hi-lo])."""
        xs, ys = [], []
        for blk, part in self._parts(lo, hi):
            x, y = self.block(blk)
            xs.append(x[part])
            ys.append(y[part])
        return np.concatenate(xs), np.concatenate(ys)

    def column(self, j: int, lo: int, hi: int) -> np.ndarray:
        """Column j of rows lo..hi, bit-equal to `rows(lo, hi)[0][:, j]`."""
        return np.concatenate([self._value(j, self._z(blk, j))[part]
                               for blk, part in self._parts(lo, hi)])
