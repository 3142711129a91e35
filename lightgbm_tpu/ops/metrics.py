"""Evaluation metrics.

Re-creates the reference metric zoo (`src/metric/*.hpp`, factory
`src/metric/metric.cpp:16-60`) with the same interface: `eval(raw_scores,
objective)` applying the objective's `ConvertOutput` when present, returning
named values plus `bigger_is_better` for early stopping
(`include/LightGBM/metric.h`).

Host NumPy (f64) implementations: metrics run once per iteration over the
label vector — bandwidth-trivial next to histogram work — and exact f64
averages match the reference's double accumulators.
"""
from __future__ import annotations

import math
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..config import Config
from .ranking import dcg_at_k, dcg_discounts, max_dcg_at_k

K_EPSILON = 1e-15


def _safe_log(x):
    return np.log(np.maximum(x, 1e-308))


class Metric:
    name: str = ""
    bigger_is_better: bool = False

    def __init__(self, cfg: Config) -> None:
        self.cfg = cfg

    def init(self, metadata, num_data: int) -> None:
        self.label = np.asarray(metadata.label, np.float64) \
            if metadata.label is not None else np.zeros(num_data)
        self.weight = (np.asarray(metadata.weight, np.float64)
                       if metadata.weight is not None else None)
        self.num_data = num_data
        self.sum_weights = (float(self.weight.sum()) if self.weight is not None
                            else float(num_data))

    def eval(self, scores: np.ndarray, objective) -> List[Tuple[str, float]]:
        raise NotImplementedError

    def eval_dev(self, scores_dev, objective, phase: str = "valid.metric"):
        """Device-side eval over a DEVICE score matrix, returning
        [(name, device_scalar)] — or None when this metric has no device
        implementation (the caller falls back to the host path). Lets
        per-iteration valid evals avoid pulling full score arrays over
        the host link. `phase` (`obs.phases`): whose scores these are,
        `valid.metric` or `train.metric`."""
        return None

    def _device(self, phase: str, make):
        """The jitted device program of this metric under `phase`, made
        once by `make()`; its first call at a shape is remembered for
        the phase table (`obs.phases.remember`)."""
        import jax

        from ..obs import phases
        progs = self.__dict__.setdefault("_dev_fns", {})
        if phase not in progs:
            body = make()

            def scoped(*args):
                with phases.scope(phase):
                    return body(*args)
            progs[phase] = (jax.jit(scoped), set())
        fn, seen = progs[phase]

        def run(*args):
            shape = tuple(getattr(a, "shape", ()) for a in args)
            if shape not in seen:
                seen.add(shape)
                phases.remember(f"{phase}.{self.name}", fn, args)
            return fn(*args)
        return run


class _PointwiseMetric(Metric):
    """Weighted mean of a pointwise loss with ConvertOutput applied
    (reference RegressionMetric::Eval, regression_metric.hpp:50-95)."""
    use_convert = True

    def loss(self, label: np.ndarray, pred: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def average(self, sum_loss: float) -> float:
        return sum_loss / self.sum_weights

    def eval(self, scores, objective):
        pred = scores[0].astype(np.float64)
        if self.use_convert and objective is not None:
            pred = objective.convert_output(pred)
        pt = self.loss(self.label, pred)
        if self.weight is not None:
            s = float(np.sum(pt * self.weight))
        else:
            s = float(np.sum(pt))
        return [(self.name, self.average(s))]


class L2Metric(_PointwiseMetric):
    name = "l2"

    def loss(self, y, p):
        return (p - y) ** 2


class RMSEMetric(L2Metric):
    name = "rmse"

    def average(self, s):
        return math.sqrt(s / self.sum_weights)


class L1Metric(_PointwiseMetric):
    name = "l1"

    def loss(self, y, p):
        return np.abs(p - y)


class QuantileMetric(_PointwiseMetric):
    name = "quantile"

    def loss(self, y, p):
        delta = y - p
        return np.where(delta < 0, (self.cfg.alpha - 1.0) * delta,
                        self.cfg.alpha * delta)


class HuberMetric(_PointwiseMetric):
    name = "huber"

    def loss(self, y, p):
        d = p - y
        a = self.cfg.alpha
        return np.where(np.abs(d) <= a, 0.5 * d * d,
                        a * (np.abs(d) - 0.5 * a))


class FairMetric(_PointwiseMetric):
    name = "fair"

    def loss(self, y, p):
        x = np.abs(p - y)
        c = self.cfg.fair_c
        return c * x - c * c * np.log(1.0 + x / c)


class PoissonMetric(_PointwiseMetric):
    name = "poisson"

    def loss(self, y, p):
        p = np.maximum(p, 1e-10)
        return p - y * np.log(p)


class MAPEMetric(_PointwiseMetric):
    name = "mape"

    def loss(self, y, p):
        return np.abs(y - p) / np.maximum(1.0, np.abs(y))


class GammaMetric(_PointwiseMetric):
    name = "gamma"

    def loss(self, y, p):
        # (regression_metric.hpp:261-268)
        theta = -1.0 / p
        b = -_safe_log(-theta)
        c = _safe_log(y) - _safe_log(y)  # psi=1: log(y/1) - log(y) = 0
        return -((y * theta - b) + c)


class GammaDevianceMetric(_PointwiseMetric):
    name = "gamma_deviance"

    def loss(self, y, p):
        tmp = y / (p + 1e-9)
        return tmp - _safe_log(tmp) - 1.0

    def average(self, s):
        return s * 2.0


class TweedieMetric(_PointwiseMetric):
    name = "tweedie"

    def loss(self, y, p):
        rho = self.cfg.tweedie_variance_power
        eps = 1e-10
        p = np.maximum(p, eps)
        a = y * np.exp((1 - rho) * np.log(p)) / (1 - rho)
        b = np.exp((2 - rho) * np.log(p)) / (2 - rho)
        return -a + b


class BinaryLoglossMetric(_PointwiseMetric):
    name = "binary_logloss"

    def loss(self, y, p):
        # (binary_metric.hpp:119-131)
        pos = y > 0
        out = np.zeros_like(p)
        neg_ok = (1.0 - p) > K_EPSILON
        pos_ok = p > K_EPSILON
        out = np.where(pos, np.where(pos_ok, -np.log(np.maximum(p, 1e-300)),
                                     -np.log(K_EPSILON)),
                       np.where(neg_ok, -np.log(np.maximum(1 - p, 1e-300)),
                                -np.log(K_EPSILON)))
        return out

    def eval_dev(self, scores_dev, objective, phase: str = "valid.metric"):
        """The mean log loss on the device in f32 from the raw score z =
        sigmoid x score: softplus(-z) for a positive row, softplus(z) for
        a negative one (the host's -log p and -log(1 - p) without the
        cancellation of 1 - p), capped at -log(K_EPSILON) as the host
        clips p; summed pairwise. Only under the binary objective, whose
        ConvertOutput this is."""
        if objective is None or objective.name != "binary":
            return None
        import jax.numpy as jnp
        if not hasattr(self, "_y_dev"):
            self._y_dev = jnp.asarray(self.label > 0)
            self._w_dev = jnp.asarray(
                self.weight if self.weight is not None
                else np.ones(1), jnp.float32)
        cap = float(-np.log(K_EPSILON))
        sig = float(objective.cfg.sigmoid)
        total = float(self.sum_weights)

        def make():
            def fn(score, y, w):
                z = sig * score
                loss = jnp.minimum(jnp.logaddexp(0.0, jnp.where(y, -z, z)),
                                   cap)
                return pairwise_sum(loss * w) / total
            return fn
        return [(self.name, self._device(phase, make)(
            scores_dev[0], self._y_dev, self._w_dev))]


def pairwise_sum(x):
    """Sum of a 1-D f32 array by halves (log2(n) adds, each over half of
    what is left): its rounding error grows with log n, not n."""
    import jax.numpy as jnp
    n = 1 << max(0, (int(x.shape[0]) - 1).bit_length())
    x = jnp.pad(x, (0, n - x.shape[0]))
    while x.shape[0] > 1:
        half = x.shape[0] // 2
        x = x[:half] + x[half:]
    return x[0]


class BinaryErrorMetric(_PointwiseMetric):
    name = "binary_error"

    def loss(self, y, p):
        return np.where(p <= 0.5, (y > 0).astype(float),
                        (y <= 0).astype(float))


class AUCMetric(Metric):
    """Weighted rank-sum AUC on raw scores (binary_metric.hpp:159-240)."""
    name = "auc"
    bigger_is_better = True

    def eval(self, scores, objective):
        score = scores[0].astype(np.float64)
        y = self.label > 0
        w = (self.weight if self.weight is not None
             else np.ones_like(score))
        order = np.argsort(score, kind="mergesort")
        s, ys, ws = score[order], y[order], w[order]
        # tie groups share the average rank: accumulate per distinct score
        pos_w = ws * ys
        neg_w = ws * (~ys)
        # cumulative negative weight strictly below each element + half ties
        boundaries = np.nonzero(np.diff(s))[0]
        group_id = np.zeros(len(s), np.int64)
        group_id[1:] = np.cumsum(np.diff(s) != 0)
        n_groups = group_id[-1] + 1 if len(s) else 0
        gsum_neg = np.bincount(group_id, weights=neg_w, minlength=n_groups)
        gsum_pos = np.bincount(group_id, weights=pos_w, minlength=n_groups)
        cum_neg_before = np.concatenate([[0], np.cumsum(gsum_neg)[:-1]])
        acc = float(np.sum(gsum_pos * (cum_neg_before + 0.5 * gsum_neg)))
        total_pos = float(pos_w.sum())
        total_neg = float(neg_w.sum())
        if total_pos <= 0 or total_neg <= 0:
            return [(self.name, 1.0)]
        return [(self.name, acc / (total_pos * total_neg))]

    def eval_dev(self, scores_dev, objective, phase: str = "valid.metric"):
        import jax.numpy as jnp
        from jax import lax
        weighted = self.weight is not None
        if not hasattr(self, "_y_dev"):
            self._y_dev = jnp.asarray((self.label > 0).astype(np.int32))
            self._w_dev = (jnp.asarray(self.weight, jnp.float32)
                           if weighted else jnp.zeros(1, jnp.float32))

        def make():
            def fn(score, y, w):
                # one sort carries the labels (and weights) with the
                # scores: no gather by an argsort's order
                if weighted:
                    s, yo, wo = lax.sort((score, y, w), num_keys=1)
                else:
                    s, yo = lax.sort((score, y), num_keys=1)
                n = s.shape[0]
                step = s[1:] != s[:-1]      # a new run of equal scores
                if weighted:
                    # f32 scatter/scan path: log-depth reductions keep
                    # relative error ~1e-6 — consistent across
                    # iterations, so early-stopping comparisons are
                    # stable even where the absolute value drifts from
                    # the host f64 metric in the 6th decimal
                    gid = jnp.cumsum(jnp.concatenate(
                        [jnp.zeros(1, jnp.int32), step.astype(jnp.int32)]))
                    yf = yo.astype(jnp.float32)
                    gneg = jnp.zeros(n, jnp.float32).at[gid].add(
                        wo * (1.0 - yf))
                    gpos = jnp.zeros(n, jnp.float32).at[gid].add(wo * yf)
                    before = jnp.cumsum(gneg) - gneg
                    acc = jnp.sum(gpos * (before + 0.5 * gneg))
                    tp, tn = jnp.sum(gpos), jnp.sum(gneg)
                else:
                    # unweighted: integer counts by scans over the sorted
                    # runs of equal scores, EXACT (counts < 2^31), and no
                    # scatter; only the final sum drops to f32. A row's
                    # negatives below its run are the count at the run's
                    # first row, its run's the count at the run's last
                    # row less that: both carried along the run by a
                    # running max / min of a count that never decreases
                    neg = jnp.cumsum(1 - yo)
                    first = jnp.concatenate([jnp.ones(1, bool), step])
                    last = jnp.concatenate([step, jnp.ones(1, bool)])
                    below = lax.cummax(jnp.where(first, neg - (1 - yo), 0))
                    upto = lax.cummin(jnp.where(last, neg, n), reverse=True)
                    run = (upto - below).astype(jnp.float32)
                    acc = pairwise_sum(jnp.where(
                        yo > 0, below.astype(jnp.float32) + 0.5 * run, 0.0))
                    tp = jnp.sum(yo).astype(jnp.float32)
                    tn = jnp.float32(n) - tp
                bad = (tp <= 0) | (tn <= 0)
                return jnp.where(bad, 1.0,
                                 acc / jnp.maximum(tp * tn, 1e-30))
            return fn
        return [(self.name, self._device(phase, make)(
            scores_dev[0], self._y_dev, self._w_dev))]


class MultiLoglossMetric(Metric):
    name = "multi_logloss"

    def eval(self, scores, objective):
        # scores [K, N] raw
        k, n = scores.shape
        raw = scores.astype(np.float64).T  # [N, K]
        if objective is not None:
            p = objective.convert_output(raw)
        else:
            p = raw
        li = self.label.astype(np.int64)
        pl = np.maximum(p[np.arange(n), li], K_EPSILON)
        pt = -np.log(pl)
        if self.weight is not None:
            s = float(np.sum(pt * self.weight))
        else:
            s = float(np.sum(pt))
        return [(self.name, s / self.sum_weights)]


class MultiErrorMetric(Metric):
    name = "multi_error"

    def eval(self, scores, objective):
        k, n = scores.shape
        raw = scores.astype(np.float64).T
        li = self.label.astype(np.int64)
        topk = self.cfg.multi_error_top_k
        # error when the true class is not within top-k scores
        # (multiclass_metric.hpp:158+)
        true_score = raw[np.arange(n), li]
        rank = np.sum(raw > true_score[:, None], axis=1)
        pt = (rank >= topk).astype(np.float64)
        if self.weight is not None:
            s = float(np.sum(pt * self.weight))
        else:
            s = float(np.sum(pt))
        return [(self.name, s / self.sum_weights)]


class _RankMetric(Metric):
    bigger_is_better = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            raise ValueError(f"{self.name} metric requires query information")
        self.qb = np.asarray(metadata.query_boundaries, np.int64)
        self.num_queries = len(self.qb) - 1
        # per-query weights (sum to num_queries by default)
        self.query_weights = metadata.query_weights


class NDCGMetric(_RankMetric):
    name = "ndcg"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        self.label_gain = np.asarray(self.cfg.label_gain, np.float64)
        self.eval_at = list(self.cfg.eval_at)
        li = self.label.astype(np.int64)
        self.max_dcgs = {
            k: np.asarray([
                max_dcg_at_k(k, li[self.qb[q]:self.qb[q + 1]],
                             self.label_gain)
                for q in range(self.num_queries)])
            for k in self.eval_at
        }

    def eval(self, scores, objective):
        score = scores[0].astype(np.float64)
        li = self.label.astype(np.int64)
        out = []
        for k in self.eval_at:
            accum = 0.0
            for q in range(self.num_queries):
                lo, hi = self.qb[q], self.qb[q + 1]
                m = self.max_dcgs[k][q]
                if m <= 0:
                    accum += 1.0
                else:
                    accum += dcg_at_k(k, li[lo:hi], score[lo:hi],
                                      self.label_gain) / m
            out.append((f"{self.name}@{k}", accum / self.num_queries))
        return out


class MAPMetric(_RankMetric):
    name = "map"

    def eval(self, scores, objective):
        score = scores[0].astype(np.float64)
        y = (self.label > 0).astype(np.float64)
        out = []
        for k in self.cfg.eval_at:
            accum = 0.0
            for q in range(self.num_queries):
                lo, hi = self.qb[q], self.qb[q + 1]
                order = np.argsort(-score[lo:hi], kind="stable")
                rel = y[lo:hi][order][:k]
                hits = np.cumsum(rel)
                denom = np.arange(1, len(rel) + 1)
                npos = y[lo:hi].sum()
                if npos > 0:
                    accum += float(np.sum(rel * hits / denom)
                                   / min(npos, k))
                else:
                    accum += 1.0
            out.append((f"{self.name}@{k}", accum / self.num_queries))
        return out


class CrossEntropyMetric(_PointwiseMetric):
    name = "xentropy"

    def loss(self, y, p):
        p = np.clip(p, K_EPSILON, 1 - K_EPSILON)
        return -y * np.log(p) - (1 - y) * np.log(1 - p)


class CrossEntropyLambdaMetric(Metric):
    name = "xentlambda"

    def eval(self, scores, objective):
        # (xentropy_metric.hpp:166+): scores converted via lambda link
        raw = scores[0].astype(np.float64)
        if objective is not None and objective.name == "xentlambda":
            lam = objective.convert_output(raw)
        else:
            lam = np.log1p(np.exp(raw))
        w = self.weight if self.weight is not None else np.ones_like(raw)
        y = self.label
        hhat = lam * w
        p = 1.0 - np.exp(-hhat)
        p = np.clip(p, K_EPSILON, 1 - K_EPSILON)
        pt = -y * np.log(p) - (1 - y) * np.log(1 - p)
        return [(self.name, float(np.sum(pt)) / self.num_data)]


class KLDivMetric(_PointwiseMetric):
    name = "kldiv"

    def loss(self, y, p):
        p = np.clip(p, K_EPSILON, 1 - K_EPSILON)
        yy = np.clip(y, K_EPSILON, 1 - K_EPSILON)
        # KL(y||p) = xent(y,p) - entropy(y)
        return (yy * np.log(yy) + (1 - yy) * np.log(1 - yy)
                - y * np.log(p) - (1 - y) * np.log(1 - p))


_METRICS = {
    "l1": L1Metric, "l2": L2Metric, "rmse": RMSEMetric,
    "quantile": QuantileMetric, "huber": HuberMetric, "fair": FairMetric,
    "poisson": PoissonMetric, "mape": MAPEMetric, "gamma": GammaMetric,
    "gamma_deviance": GammaDevianceMetric, "tweedie": TweedieMetric,
    "binary_logloss": BinaryLoglossMetric, "binary_error": BinaryErrorMetric,
    "auc": AUCMetric, "multi_logloss": MultiLoglossMetric,
    "multi_error": MultiErrorMetric, "ndcg": NDCGMetric, "map": MAPMetric,
    "xentropy": CrossEntropyMetric, "xentlambda": CrossEntropyLambdaMetric,
    "kldiv": KLDivMetric,
}

_DEFAULT_METRIC_FOR_OBJECTIVE = {
    "regression": "l2", "regression_l1": "l1", "huber": "huber",
    "fair": "fair", "poisson": "poisson", "quantile": "quantile",
    "mape": "mape", "gamma": "gamma", "tweedie": "tweedie",
    "binary": "binary_logloss", "multiclass": "multi_logloss",
    "multiclassova": "multi_logloss", "xentropy": "xentropy",
    "xentlambda": "xentlambda", "lambdarank": "ndcg",
}


def metric_names(cfg: Config) -> List[str]:
    """Resolve configured metric list with the objective default
    (reference Config::CheckParamConflict + metric.cpp:16)."""
    names = [m for m in cfg.metric if m]
    if not names:
        default = _DEFAULT_METRIC_FOR_OBJECTIVE.get(cfg.objective)
        if default:
            names = [default]
    return [n for n in names if n != "none"]


def create_metrics(cfg: Config, names: Optional[Sequence[str]] = None
                   ) -> List[Metric]:
    out = []
    for n in (names if names is not None else metric_names(cfg)):
        cls = _METRICS.get(n)
        if cls is None:
            raise ValueError(f"Unknown metric: {n}")
        out.append(cls(cfg))
    return out
