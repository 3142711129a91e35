"""Objective functions (gradients/hessians as jitted device programs).

Re-creates the reference objective zoo (`src/objective/*.hpp`, factory
`src/objective/objective_function.cpp:15`): regression L2/L1/huber/fair/
poisson/quantile/mape/gamma/tweedie, binary logloss, multiclass softmax/OVA,
cross-entropy (xentropy/xentlambda), and lambdarank. Interface mirrors
`include/LightGBM/objective_function.h:19-91`: `get_gradients`,
`boost_from_score`, `convert_output`, `is_constant_hessian`,
`num_model_per_iteration`, and the percentile-based `renew_tree_output` used
by L1/quantile/MAPE.

Scores are laid out `[num_tree_per_iteration, num_data]` (the reference's
flat `num_data * k + i` indexing, e.g. `multiclass_objective.hpp:80`).
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import compile_cache
from ..config import Config
from ..io.dataset import Metadata
from ..obs import phases


def _sign(x):
    return jnp.sign(x)


class GradLayout(NamedTuple):
    """An objective's own gradient layout: `shape` of the score and
    gradient arrays `slot_gradients` takes and gives, and each row's flat
    slot in them. Slots that hold no row take any score and give 0."""
    shape: Tuple[int, ...]
    slot_of_row: np.ndarray         # [num_data] int32, a bijection onto
    #                                 the slots that hold a row

    @property
    def slots(self) -> int:
        return int(np.prod(self.shape))


class ObjectiveFunction:
    """Base class (reference objective_function.h:19)."""

    name = "none"
    is_constant_hessian = False
    is_renew_tree_output = False
    need_query = False

    def __init__(self, cfg: Config) -> None:
        self.cfg = cfg
        self.num_class = 1
        self.label: Optional[jax.Array] = None
        self.weight: Optional[jax.Array] = None
        self._label_np: Optional[np.ndarray] = None
        self._weight_np: Optional[np.ndarray] = None

    @property
    def num_model_per_iteration(self) -> int:
        return 1

    def init(self, metadata: Metadata, num_data: int) -> None:
        self._label_np = np.asarray(metadata.label, np.float32) \
            if metadata.label is not None else np.zeros(num_data, np.float32)
        self.label = jnp.asarray(self._label_np)
        if metadata.weight is not None:
            self._weight_np = np.asarray(metadata.weight, np.float32)
            self.weight = jnp.asarray(self._weight_np)

    def trace_signature(self) -> Tuple:
        """Hashable key covering everything this objective's gradient
        closures bake into a jax trace: the concrete class, its scalar
        parameters, and fingerprints of the label/weight/query data the
        closures capture as device constants. Two objectives with equal
        signatures may share one compiled gradient program."""
        sig = self.__dict__.get("_trace_sig")
        if sig is None:
            scalars = tuple(
                (k, v) for k, v in sorted(self.__dict__.items())
                if isinstance(v, (int, float, bool, str)))
            sig = ("obj", type(self).__name__, self.num_class,
                   self.weight is not None, scalars,
                   compile_cache.array_fingerprint(
                       self._label_np, self._weight_np,
                       getattr(self, "query_boundaries", None)))
            self.__dict__["_trace_sig"] = sig
        return sig

    # grad/hess: [K, N] given scores [K, N]. The public entry jits the
    # per-class `gradients_impl` once so the whole gradient computation
    # is ONE device program, not a chain of eager ops (each eager
    # dispatch costs a host round-trip). The jitted
    # program lives in the process-wide registry keyed by the
    # objective's trace signature, so a second model over the same data
    # reuses it instead of retracing.
    def get_gradients(self, scores: jax.Array) -> Tuple[jax.Array, jax.Array]:
        fn = self.__dict__.get("_jit_gradients")
        if fn is None:
            impl = self.gradients_impl

            def traced(scores):
                compile_cache.note_trace()
                return impl(scores)

            fn = compile_cache.program(
                ("gradients", self.trace_signature()),
                lambda: jax.jit(traced))
            self.__dict__["_jit_gradients"] = fn
        return fn(scores)

    def gradients_impl(self, scores: jax.Array) -> Tuple[jax.Array, jax.Array]:
        g, h = self._point_grad(scores[0], self.label)
        if self.weight is not None:
            g = g * self.weight
            h = h * self.weight
        return g[None, :], h[None, :]

    def _point_grad(self, score, label):
        raise NotImplementedError

    def grad_layout(self) -> Optional[GradLayout]:
        """The layout this objective computes gradients in where that is
        not row order and it can take scores and give gradients there
        directly (`slot_gradients`); None: row order, `get_gradients`."""
        return None

    def point_grad_fn(self):
        """Pure elementwise (score, label, weight|None) -> (g, h), or
        None when gradients are not pointwise (ranking, multiclass).
        The aligned builder (models/aligned_builder.py) evaluates
        gradients in PERMUTED row order, so the function must depend only
        on the per-row values, not on stored row-order arrays."""
        if type(self)._point_grad is ObjectiveFunction._point_grad:
            return None

        def fn(score, label, weight):
            g, h = self._point_grad(score, label)
            if weight is not None:
                g = g * weight
                h = h * weight
            return g, h
        return fn

    def mc_lane_mode(self):
        """How a K-class objective's per-class gradients read the
        aligned record (the engine's in-kernel multiclass hook):
        "prob" — from a per-class PROBABILITY lane written once per
        iteration from pre-iteration scores (softmax: cross-class
        coupling lives in the prob computation); "score" — from the
        class's own score lane (OVA: no cross-class coupling); None —
        not lane-wise (single-class, weighted)."""
        return None

    def prob_point_grad(self):
        """mc_lane_mode()=="prob": elementwise (p_k, is_label_k) ->
        (g, h), Pallas-traceable."""
        return None

    def score_point_grad(self, k: int):
        """mc_lane_mode()=="score": elementwise (s_k, is_label_k) ->
        (g, h) for class k, Pallas-traceable."""
        return None

    def boost_from_score(self, class_id: int) -> float:
        return 0.0

    def convert_output(self, raw: np.ndarray) -> np.ndarray:
        return raw

    def renew_tree_output(self, leaf_pred_values, row_leaf, scores) -> None:
        """Optional per-leaf output renewal (reference RenewTreeOutput)."""
        raise NotImplementedError

    def __str__(self) -> str:
        return self.name


# ---------------------------------------------------------------------------
# regression family (src/objective/regression_objective.hpp)
# ---------------------------------------------------------------------------
class RegressionL2(ObjectiveFunction):
    name = "regression"
    is_constant_hessian = True  # false when weighted; handled below

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if self.cfg.reg_sqrt:
            # sqrt transform of label (regression_objective.hpp:88-100)
            self._sqrt_sign = np.sign(self._label_np)
            self._label_np = (np.sign(self._label_np)
                              * np.sqrt(np.abs(self._label_np))).astype(
                                  np.float32)
            self.label = jnp.asarray(self._label_np)
        if self.weight is not None:
            self.is_constant_hessian = False

    def _point_grad(self, score, label):
        return score - label, jnp.ones_like(score)

    def boost_from_score(self, class_id):
        # weighted mean (regression_objective.hpp:156-177)
        if self._weight_np is not None:
            return float(np.sum(self._label_np * self._weight_np)
                         / np.sum(self._weight_np))
        return float(np.mean(self._label_np))

    def convert_output(self, raw):
        if self.cfg.reg_sqrt:
            return np.sign(raw) * raw * raw
        return raw


def _percentile(data: np.ndarray, alpha: float) -> float:
    """reference PercentileFun (regression_objective.hpp:18-44)."""
    n = len(data)
    if n <= 1:
        return float(data[0]) if n else 0.0
    s = np.sort(data)
    float_pos = (1.0 - alpha) * n
    pos = int(float_pos)
    if pos < 1:
        return float(s[-1])
    if pos >= n:
        return float(s[0])
    bias = float_pos - pos
    v1 = s[n - pos]
    v2 = s[n - pos - 1]
    # reference scans from the top for alpha-percentile of residuals
    return float(v1 - (v1 - v2) * bias)


def _weighted_percentile(data: np.ndarray, w: np.ndarray,
                         alpha: float) -> float:
    """reference WeightedPercentileFun (regression_objective.hpp:46-76)."""
    n = len(data)
    if n <= 1:
        return float(data[0]) if n else 0.0
    order = np.argsort(data, kind="stable")
    cdf = np.cumsum(w[order])
    threshold = cdf[-1] * alpha
    pos = int(np.searchsorted(cdf, threshold, side="right"))
    pos = min(pos, n - 1)
    if pos == 0 or pos == n - 1:
        return float(data[order[pos]])
    v1 = data[order[pos - 1]]
    v2 = data[order[pos]]
    if cdf[pos] <= cdf[pos - 1]:
        return float(v2)
    return float(v1 + (v2 - v1) * (threshold - cdf[pos - 1])
                 / (cdf[pos] - cdf[pos - 1]))


class _PercentileRenewMixin:
    """Leaf-output renewal by residual percentile (reference
    RegressionL1loss::RenewTreeOutput, regression_objective.hpp:233-268)."""
    is_renew_tree_output = True
    renew_alpha = 0.5

    def renew_leaf_output(self, residuals: np.ndarray,
                          weights: Optional[np.ndarray]) -> float:
        if len(residuals) == 0:
            return 0.0
        if weights is None:
            return _percentile(residuals, self.renew_alpha)
        return _weighted_percentile(residuals, weights, self.renew_alpha)

    def residual(self, label: np.ndarray, score: np.ndarray) -> np.ndarray:
        return label - score


class RegressionL1(_PercentileRenewMixin, RegressionL2):
    name = "regression_l1"
    is_constant_hessian = True

    def _point_grad(self, score, label):
        return _sign(score - label), jnp.ones_like(score)

    def boost_from_score(self, class_id):
        if self._weight_np is not None:
            return _weighted_percentile(self._label_np, self._weight_np, 0.5)
        return _percentile(self._label_np, 0.5)


class RegressionHuber(RegressionL2):
    name = "huber"
    is_constant_hessian = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if self.weight is not None:
            self.is_constant_hessian = False

    def _point_grad(self, score, label):
        a = self.cfg.alpha
        diff = score - label
        g = jnp.where(jnp.abs(diff) <= a, diff, _sign(diff) * a)
        return g, jnp.ones_like(score)


class RegressionFair(ObjectiveFunction):
    name = "fair"

    def _point_grad(self, score, label):
        c = self.cfg.fair_c
        x = score - label
        g = c * x / (jnp.abs(x) + c)
        h = c * c / ((jnp.abs(x) + c) ** 2)
        return g, h

    def boost_from_score(self, class_id):
        # fair: mean like L2? reference uses 0 (no BoostFromScore override ->
        # percentile? RegressionFairLoss overrides with 0 via base) — the
        # reference RegressionFairLoss inherits L2's mean boost.
        if self._weight_np is not None:
            return float(np.sum(self._label_np * self._weight_np)
                         / np.sum(self._weight_np))
        return float(np.mean(self._label_np))


class RegressionPoisson(ObjectiveFunction):
    name = "poisson"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if np.any(self._label_np < 0):
            raise ValueError("[poisson]: at least one target label is "
                             "negative")

    def _point_grad(self, score, label):
        g = jnp.exp(score) - label
        h = jnp.exp(score + self.cfg.poisson_max_delta_step)
        return g, h

    def boost_from_score(self, class_id):
        if self._weight_np is not None:
            mean = float(np.sum(self._label_np * self._weight_np)
                         / np.sum(self._weight_np))
        else:
            mean = float(np.mean(self._label_np))
        return math.log(max(mean, 1e-20))

    def convert_output(self, raw):
        return np.exp(raw)


class RegressionQuantile(_PercentileRenewMixin, ObjectiveFunction):
    name = "quantile"
    is_constant_hessian = True

    @property
    def renew_alpha(self):
        return self.cfg.alpha

    def _point_grad(self, score, label):
        a = self.cfg.alpha
        delta = score - label
        g = jnp.where(delta >= 0, 1.0 - a, -a)
        return g, jnp.ones_like(score)

    def boost_from_score(self, class_id):
        if self._weight_np is not None:
            return _weighted_percentile(self._label_np, self._weight_np,
                                        self.cfg.alpha)
        return _percentile(self._label_np, self.cfg.alpha)


class RegressionMAPE(_PercentileRenewMixin, ObjectiveFunction):
    name = "mape"
    is_constant_hessian = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        # label_weight = w / max(1, |label|) (regression_objective.hpp:575-589)
        w = (self._weight_np if self._weight_np is not None
             else np.ones(num_data, np.float32))
        self._label_weight_np = (w / np.maximum(1.0, np.abs(self._label_np))
                                 ).astype(np.float32)
        self._label_weight = jnp.asarray(self._label_weight_np)

    def gradients_impl(self, scores):
        diff = scores[0] - self.label
        g = _sign(diff) * self._label_weight
        h = self._label_weight
        return g[None, :], h[None, :]

    def boost_from_score(self, class_id):
        return _weighted_percentile(self._label_np, self._label_weight_np, 0.5)

    def renew_leaf_output(self, residuals, weights):
        # weights here are the label weights (hpp:640-658)
        return _weighted_percentile(residuals, weights, 0.5)


class RegressionGamma(ObjectiveFunction):
    name = "gamma"

    def _point_grad(self, score, label):
        g = 1.0 - label * jnp.exp(-score)
        h = label * jnp.exp(-score)
        return g, h

    def boost_from_score(self, class_id):
        if self._weight_np is not None:
            mean = float(np.sum(self._label_np * self._weight_np)
                         / np.sum(self._weight_np))
        else:
            mean = float(np.mean(self._label_np))
        return math.log(max(mean, 1e-20))

    def convert_output(self, raw):
        return np.exp(raw)


class RegressionTweedie(ObjectiveFunction):
    name = "tweedie"

    def _point_grad(self, score, label):
        rho = self.cfg.tweedie_variance_power
        e1 = jnp.exp((1 - rho) * score)
        e2 = jnp.exp((2 - rho) * score)
        g = -label * e1 + e2
        h = -label * (1 - rho) * e1 + (2 - rho) * e2
        return g, h

    def boost_from_score(self, class_id):
        if self._weight_np is not None:
            mean = float(np.sum(self._label_np * self._weight_np)
                         / np.sum(self._weight_np))
        else:
            mean = float(np.mean(self._label_np))
        return math.log(max(mean, 1e-20))

    def convert_output(self, raw):
        return np.exp(raw)


# ---------------------------------------------------------------------------
# binary (src/objective/binary_objective.hpp)
# ---------------------------------------------------------------------------
class BinaryLogloss(ObjectiveFunction):
    name = "binary"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        pos = self._label_np > 0
        cnt_pos = int(pos.sum())
        cnt_neg = num_data - cnt_pos
        self._cnt_pos, self._cnt_neg = cnt_pos, cnt_neg
        # label weights (binary_objective.hpp:79-100)
        w_pos, w_neg = 1.0, 1.0
        if self.cfg.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                w_pos = 1.0
                w_neg = cnt_pos / cnt_neg
            else:
                w_pos = cnt_neg / cnt_pos
                w_neg = 1.0
        w_pos *= self.cfg.scale_pos_weight
        self._w_pos, self._w_neg = float(w_pos), float(w_neg)
        self._sign_label = jnp.where(jnp.asarray(pos), 1.0, -1.0)
        self._label_weight = jnp.where(jnp.asarray(pos), w_pos, w_neg)
        self.need_train = cnt_pos > 0 and cnt_neg > 0

    def point_grad_fn(self):
        sig = float(self.cfg.sigmoid)
        wp, wn = self._w_pos, self._w_neg

        def fn(score, label, weight):
            sl = jnp.where(label > 0, 1.0, -1.0)
            lw = jnp.where(label > 0, wp, wn)
            response = -sl * sig / (1.0 + jnp.exp(sl * sig * score))
            absr = jnp.abs(response)
            g = response * lw
            h = absr * (sig - absr) * lw
            if weight is not None:
                g = g * weight
                h = h * weight
            return g, h
        return fn

    def gradients_impl(self, scores):
        sig = self.cfg.sigmoid
        score = scores[0]
        label = self._sign_label
        response = -label * sig / (1.0 + jnp.exp(label * sig * score))
        absr = jnp.abs(response)
        g = response * self._label_weight
        h = absr * (sig - absr) * self._label_weight
        if self.weight is not None:
            g = g * self.weight
            h = h * self.weight
        return g[None, :], h[None, :]

    def boost_from_score(self, class_id):
        # weighted average prob -> log odds / sigmoid
        # (binary_objective.hpp:136-153)
        if self._weight_np is not None:
            suml = float(np.sum((self._label_np > 0) * self._weight_np))
            sumw = float(np.sum(self._weight_np))
        else:
            suml = float(self._cnt_pos)
            sumw = float(self._cnt_pos + self._cnt_neg)
        pavg = min(max(suml / max(sumw, 1e-20), 1e-15), 1 - 1e-15)
        return math.log(pavg / (1.0 - pavg)) / self.cfg.sigmoid

    def convert_output(self, raw):
        return 1.0 / (1.0 + np.exp(-self.cfg.sigmoid * raw))


# ---------------------------------------------------------------------------
# multiclass (src/objective/multiclass_objective.hpp)
# ---------------------------------------------------------------------------
class MulticlassSoftmax(ObjectiveFunction):
    name = "multiclass"

    def __init__(self, cfg):
        super().__init__(cfg)
        self.num_class = cfg.num_class

    @property
    def num_model_per_iteration(self):
        return self.num_class

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        li = self._label_np.astype(np.int32)
        if li.min() < 0 or li.max() >= self.num_class:
            raise ValueError(f"Label must be in [0, {self.num_class})")
        self._label_int = jnp.asarray(li)
        probs = np.zeros(self.num_class)
        w = (self._weight_np if self._weight_np is not None
             else np.ones(num_data, np.float32))
        np.add.at(probs, li, w)
        self._class_init_probs = probs / probs.sum()

    def gradients_impl(self, scores):
        # scores [K, N]
        p = jax.nn.softmax(scores, axis=0)
        onehot = (jnp.arange(self.num_class)[:, None]
                  == self._label_int[None, :])
        g = p - onehot.astype(p.dtype)
        h = 2.0 * p * (1.0 - p)
        if self.weight is not None:
            g = g * self.weight[None, :]
            h = h * self.weight[None, :]
        return g, h

    def mc_lane_mode(self):
        """Softmax couples classes through p = softmax(s)
        (multiclass_objective.hpp:77-97): the engine writes per-class
        PROB lanes once per iteration from pre-iteration scores, so
        per-class gradients stay lane-local. Unweighted only (weights
        would need a weight lane the compact record does not carry)."""
        return None if self.weight is not None else "prob"

    def prob_point_grad(self):
        def fn(pk, is_label):
            g = pk - is_label.astype(pk.dtype)
            h = 2.0 * pk * (1.0 - pk)
            return g, h
        return fn

    def boost_from_score(self, class_id):
        # avg_output = log(class prob) (multiclass_objective.hpp:118-126)
        return math.log(max(self._class_init_probs[class_id], 1e-300))

    def convert_output(self, raw):
        # raw: [..., K] -> softmax over classes
        e = np.exp(raw - raw.max(axis=-1, keepdims=True))
        return e / e.sum(axis=-1, keepdims=True)


class MulticlassOVA(ObjectiveFunction):
    name = "multiclassova"

    def __init__(self, cfg):
        super().__init__(cfg)
        self.num_class = cfg.num_class
        self._binary = [BinaryLogloss(cfg) for _ in range(cfg.num_class)]

    @property
    def num_model_per_iteration(self):
        return self.num_class

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        li = self._label_np.astype(np.int32)
        for k, b in enumerate(self._binary):
            md = Metadata(num_data)
            md.set_label((li == k).astype(np.float32))
            md.weight = metadata.weight
            b.init(md, num_data)

    def get_gradients(self, scores):
        gs, hs = [], []
        for k, b in enumerate(self._binary):
            g, h = b.get_gradients(scores[k:k + 1])
            gs.append(g[0])
            hs.append(h[0])
        return jnp.stack(gs), jnp.stack(hs)

    def mc_lane_mode(self):
        """One-vs-all: class k's binary logloss reads ONLY its own
        score lane (multiclass_objective.hpp:160-199) — no cross-class
        coupling, so gradients come straight from the score lane."""
        return None if self.weight is not None else "score"

    def score_point_grad(self, k):
        b = self._binary[k]
        sig = float(b.cfg.sigmoid)
        wp, wn = b._w_pos, b._w_neg

        def fn(sk, is_label):
            sl = jnp.where(is_label, 1.0, -1.0)
            lw = jnp.where(is_label, wp, wn)
            response = -sl * sig / (1.0 + jnp.exp(sl * sig * sk))
            absr = jnp.abs(response)
            return response * lw, absr * (sig - absr) * lw
        return fn

    def boost_from_score(self, class_id):
        return self._binary[class_id].boost_from_score(0)

    def convert_output(self, raw):
        return 1.0 / (1.0 + np.exp(-self.cfg.sigmoid * raw))


# ---------------------------------------------------------------------------
# cross-entropy (src/objective/xentropy_objective.hpp)
# ---------------------------------------------------------------------------
class CrossEntropy(ObjectiveFunction):
    name = "xentropy"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if np.any(self._label_np < 0) or np.any(self._label_np > 1):
            raise ValueError("[xentropy]: labels must be in [0, 1]")

    def _point_grad(self, score, label):
        z = 1.0 / (1.0 + jnp.exp(-score))
        return z - label, z * (1.0 - z)

    def boost_from_score(self, class_id):
        # (xentropy_objective.hpp:116-133): log-odds of weighted mean label
        if self._weight_np is not None:
            suml = float(np.sum(self._label_np * self._weight_np))
            sumw = float(np.sum(self._weight_np))
        else:
            suml = float(np.sum(self._label_np))
            sumw = float(len(self._label_np))
        pavg = min(max(suml / max(sumw, 1e-20), 1e-15), 1 - 1e-15)
        return math.log(pavg / (1.0 - pavg))

    def convert_output(self, raw):
        return 1.0 / (1.0 + np.exp(-raw))


class CrossEntropyLambda(ObjectiveFunction):
    name = "xentlambda"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if np.any(self._label_np < 0) or np.any(self._label_np > 1):
            raise ValueError("[xentlambda]: labels must be in [0, 1]")

    def gradients_impl(self, scores):
        """(xentropy_objective.hpp:185-224): weights act as exposure/trials
        under the log(1+exp(score)) link."""
        score = scores[0]
        label = self.label
        if self.weight is None:
            z = 1.0 / (1.0 + jnp.exp(-score))
            g = z - label
            h = z * (1.0 - z)
        else:
            # exact reference formulas (xentropy_objective.hpp:196-211)
            w = self.weight
            y = label
            epf = jnp.exp(score)
            hhat = jnp.log1p(epf)
            z = 1.0 - jnp.exp(-w * hhat)
            enf = 1.0 / epf
            g = (1.0 - y / z) * w / (1.0 + enf)
            c = 1.0 / (1.0 - z)
            d = 1.0 + epf
            a = w * epf / (d * d)
            d = c - 1.0
            b = (c / (d * d)) * (1.0 + w * epf - c)
            h = a * (1.0 + y * b)
        return g[None, :], h[None, :]

    def boost_from_score(self, class_id):
        if self._weight_np is not None:
            suml = float(np.sum(self._label_np * self._weight_np))
            sumw = float(np.sum(self._weight_np))
        else:
            suml = float(np.sum(self._label_np))
            sumw = float(len(self._label_np))
        pavg = min(max(suml / max(sumw, 1e-20), 1e-15), 1 - 1e-15)
        return math.log(math.log1p(pavg / (1.0 - pavg)))

    def convert_output(self, raw):
        return np.log1p(np.exp(raw))


# ---------------------------------------------------------------------------
# lambdarank (src/objective/rank_objective.hpp)
# ---------------------------------------------------------------------------
from . import pallas_rank
from .pallas_hist import pallas_available
from .ranking import (bucket_queries, dcg_discounts, max_dcg_at_k)


class LambdarankNDCG(ObjectiveFunction):
    name = "lambdarank"
    need_query = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            raise ValueError("Lambdarank tasks require query information")
        self.query_boundaries = np.asarray(metadata.query_boundaries,
                                           np.int64)
        self.num_queries = len(self.query_boundaries) - 1
        label_gain = np.asarray(self.cfg.label_gain, np.float64)
        max_label = int(self._label_np.max())
        if max_label >= len(label_gain):
            raise ValueError("label_gain too short for labels")
        self.label_gain = label_gain
        # cached inverse max DCG at optimize position (rank_objective.hpp:60-69)
        k = self.cfg.max_position
        inv = np.zeros(self.num_queries, np.float64)
        for q in range(self.num_queries):
            lo, hi = self.query_boundaries[q], self.query_boundaries[q + 1]
            m = max_dcg_at_k(k, self._label_np[lo:hi].astype(np.int64),
                             label_gain)
            inv[q] = 1.0 / m if m > 0 else 0.0
        self._inv_max_dcg = inv
        self._grad_fns: Dict[int, Callable] = {}
        self.num_data = num_data
        # --- segment-fused Pallas gradient path (ops/pallas_rank.py).
        # Mode resolution: "off" -> bucketed; "auto" -> fused iff a real
        # TPU is attached; "on" -> fused everywhere (interpret-mode
        # kernel on CPU, for tests/CI). Queries longer than
        # tpu_rank_tile stay on the bucketed path; a kernel failure
        # propagates.
        self._fused_pack = None
        self._fused_dev = None
        self._fused_fn = {}
        self._fused_interpret = False
        self.rank_fused_active = False
        self.rank_fused_fallback_queries = 0
        include = None
        mode = str(getattr(self.cfg, "tpu_rank_fused", "auto")).lower()
        on_tpu = pallas_available()
        if mode == "on" or (mode == "auto" and on_tpu):
            tile = max(pallas_rank.SUBTILE,
                       int(getattr(self.cfg, "tpu_rank_tile", 512)))
            tile = -(-tile // pallas_rank.SUBTILE) * pallas_rank.SUBTILE
            pack = pallas_rank.pack_query_tiles(self.query_boundaries,
                                                tile)
            if pack.num_tiles > 0:
                self._fused_pack = pack
                self._fused_interpret = not on_tpu
                self.rank_fused_active = True
                self.rank_fused_fallback_queries = int(
                    pack.leftover.sum())
                from ..utils import log
                log.event("rank_fused", tiles=pack.num_tiles,
                          tile=pack.tile, band=int(pack.band),
                          fill_pct=round(100.0 * pack.fill, 1),
                          fallback_queries=self.rank_fused_fallback_queries,
                          interpret=self._fused_interpret)
                # only oversize leftovers keep a bucket ladder
                include = pack.leftover
        self._buckets = bucket_queries(self.query_boundaries,
                                       include=include)

    def _make_grad_fn(self, size: int):
        sig = float(self.cfg.sigmoid)
        gains = jnp.asarray(self.label_gain, jnp.float32)
        disc = jnp.asarray(dcg_discounts(size), jnp.float32)

        @jax.jit
        def per_bucket(scores_q, labels_q, mask_q, inv_q):
            # scores_q [Q, S]; labels_q int32; mask_q bool; inv_q [Q]
            compile_cache.note_trace()
            neg_inf = jnp.float32(-np.inf)
            s = jnp.where(mask_q, scores_q, neg_inf)
            order = jnp.argsort(-s, axis=1, stable=True)   # desc, pads last
            ss = jnp.take_along_axis(s, order, 1)          # sorted scores
            sl = jnp.take_along_axis(
                jnp.where(mask_q, labels_q, -1), order, 1)  # sorted labels
            cnt = mask_q.sum(axis=1).astype(jnp.int32)
            valid_s = jnp.arange(size)[None, :] < cnt[:, None]
            best = ss[:, 0]
            worst_pos = jnp.maximum(cnt - 1, 0)
            worst = jnp.take_along_axis(ss, worst_pos[:, None], 1)[:, 0]
            norm_on = best != worst
            gain_s = gains[jnp.clip(sl, 0, gains.shape[0] - 1)]
            # pair tensors [Q, S(high), S(low)] in BF16: the O(S^2) exp +
            # divide chain is the per-iteration hot spot at MSLR scale
            # (measured ~270 ms/iter in f32); the reference itself
            # quantizes the sigmoid through a lookup table
            # (rank_objective.hpp:71), so ~8-bit pair factors are within
            # its own tolerance. Reductions accumulate in f32. Score
            # DIFFERENCES are formed in f32 first (bf16 subtraction of
            # near-equal scores would cancel catastrophically), only the
            # results are narrowed.
            bf = jnp.bfloat16
            ds = (ss[:, :, None] - ss[:, None, :]).astype(bf)
            gain_b = gain_s.astype(bf)
            dgap = gain_b[:, :, None] - gain_b[:, None, :]
            pd = jnp.abs(disc[None, :, None]
                         - disc[None, None, :]).astype(bf)
            delta_ndcg = dgap * pd * inv_q[:, None, None].astype(bf)
            delta_ndcg = jnp.where(norm_on[:, None, None],
                                   delta_ndcg / (0.01 + jnp.abs(ds)),
                                   delta_ndcg)
            p_lambda = (2.0 / (1.0 + jnp.exp(
                (2.0 * sig) * ds.astype(jnp.float32)))).astype(bf)
            p_hess = p_lambda * (2.0 - p_lambda)
            pair_valid = ((sl[:, :, None] > sl[:, None, :])
                          & valid_s[:, :, None] & valid_s[:, None, :])
            lam = jnp.where(pair_valid, -p_lambda * delta_ndcg,
                            jnp.asarray(0.0, bf))
            hes = jnp.where(pair_valid, p_hess * 2.0 * delta_ndcg,
                            jnp.asarray(0.0, bf))
            # high gets +lam, low gets -lam; both get +hes
            g_sorted = (lam.sum(axis=2, dtype=jnp.float32)
                        - lam.sum(axis=1, dtype=jnp.float32))
            h_sorted = (hes.sum(axis=2, dtype=jnp.float32)
                        + hes.sum(axis=1, dtype=jnp.float32))
            # unsort back to doc positions
            inv_order = jnp.argsort(order, axis=1)
            g = jnp.take_along_axis(g_sorted, inv_order, 1)
            hh = jnp.take_along_axis(h_sorted, inv_order, 1)
            return (jnp.where(mask_q, g, 0.0), jnp.where(mask_q, hh, 0.0))

        return per_bucket

    def _bucket_dev_tables(self):
        """Device-resident per-bucket constants (doc ids, labels, masks,
        inv max DCG) — uploaded ONCE; re-uploading them per iteration put
        ~30 MB/iter on the host link and dominated ranking training."""
        tabs = getattr(self, "_bucket_dev", None)
        if tabs is None:
            tabs = {}
            # the first get_gradients call may run under an outer jit
            # trace (the device-time harness chains it in a fori_loop);
            # without the eval guard these "constants" would be cached
            # as that trace's tracers and leak into the next one
            with jax.ensure_compile_time_eval():
                for size, (qids, doc_idx, mask) in self._buckets.items():
                    tabs[size] = (
                        jnp.asarray(doc_idx),
                        jnp.asarray(
                            self._label_np[doc_idx].astype(np.int32)),
                        jnp.asarray(mask),
                        jnp.asarray(self._inv_max_dcg[qids],
                                    jnp.float32))
            self._bucket_dev = tabs
        return tabs

    def _fused_dev_tables(self):
        """Device-resident constants of the fused kernel: the row tables
        of the row-order wrapper (`doc_idx`, `slot_of_row`), the per-slot
        tables (query ids, label gains, labels, inv max DCG, discount
        table) and the per-slot weights or None; uploaded once, like
        `_bucket_dev_tables`."""
        tabs = self._fused_dev
        if tabs is None:
            pack = self._fused_pack
            real = pack.qid >= 0
            lab = np.where(
                real, self._label_np[pack.doc_idx].astype(np.int32), -1)
            gain = np.where(
                real,
                self.label_gain[np.clip(lab, 0, None)].astype(np.float32),
                0.0).astype(np.float32)
            inv = np.where(
                real,
                self._inv_max_dcg[np.clip(pack.qid, 0, None)],
                0.0).astype(np.float32)
            w_t = None if self._weight_np is None else np.where(
                real, self._weight_np[pack.doc_idx], 0.0).astype(np.float32)
            # see _bucket_dev_tables: cached constants must be concrete
            # even when the first call runs under an outer trace
            with jax.ensure_compile_time_eval():
                tabs = dict(
                    rows=(jnp.asarray(pack.doc_idx),
                          jnp.asarray(pack.slot_of_row(self.num_data))),
                    slots=(jnp.asarray(pack.qid),
                           jnp.asarray(gain), jnp.asarray(lab),
                           jnp.asarray(inv),
                           jnp.asarray(
                               pallas_rank.discount_table(pack.tile))),
                    weight=None if w_t is None else jnp.asarray(w_t))
            self._fused_dev = tabs
        return tabs

    def _fused_program(self, rows: bool):
        """The slot-order program or its row-order wrapper: ONE kernel,
        whichever order the caller keeps its scores in."""
        fn = self._fused_fn.get(rows)
        if fn is None:
            pack = self._fused_pack
            lut = int(getattr(self.cfg, "tpu_rank_sigmoid_bins", 0))
            fn = compile_cache.program(
                pallas_rank.fused_program_key(
                    pack, float(self.cfg.sigmoid), lut,
                    self._fused_interpret, rows),
                lambda: pallas_rank.make_fused_grad_fn(
                    pack.num_tiles, pack.tile, int(pack.band),
                    float(self.cfg.sigmoid), lut,
                    interpret=self._fused_interpret, rows=rows))
            self._fused_fn[rows] = fn
            # jitted outside the engine: its first call says what lowers
            # it again for a phase table (`obs/phases.py`)
            return phases.remember_first(
                "rank_fused_rows" if rows else "rank_fused", fn)
        return fn

    def grad_layout(self):
        """The kernel's tile pack, where every query rides the kernel: a
        leftover query's rows have no slot, and its bucket works in row
        order."""
        pack = self._fused_pack
        if not self.rank_fused_active or pack.leftover.any():
            return None
        return GradLayout((pack.num_tiles, pack.tile),
                          pack.slot_of_row(self.num_data))

    def slot_gradients(self, score_t):
        """(g, h) [NT, T] at scores [NT, T], weights folded in: what
        `get_gradients` computes, without the way in from row order and
        back out (only under a `grad_layout`)."""
        tabs = self._fused_dev_tables()
        return self._fused_program(rows=False)(
            score_t, *tabs["slots"], tabs["weight"])

    def _fused_grads(self, score):
        tabs = self._fused_dev_tables()
        return self._fused_program(rows=True)(
            score, *tabs["rows"], *tabs["slots"])

    def get_gradients(self, scores):
        score = scores[0]
        if self.rank_fused_active:
            g, h = self._fused_grads(score)
        else:
            g = jnp.zeros_like(score)
            h = jnp.zeros_like(score)
        for size, (didx, labels_q, mask, inv) in \
                self._bucket_dev_tables().items():
            fn = self._grad_fns.get(size)
            if fn is None:
                # per-bucket programs capture only cfg-derived constants
                # (sigmoid, label_gain, discounts) — bucket data arrives
                # as runtime args — so they dedup across models by size.
                fn = compile_cache.program(
                    ("rank_bucket", size, float(self.cfg.sigmoid),
                     tuple(float(g) for g in self.label_gain)),
                    lambda: self._make_grad_fn(size))
                self._grad_fns[size] = fn
            sc = score[didx] * mask  # [Q, S]
            gq, hq = fn(sc, labels_q, mask, inv)
            flat_idx = didx.reshape(-1)
            g = g.at[flat_idx].add(gq.reshape(-1))
            h = h.at[flat_idx].add(hq.reshape(-1))
        if self.weight is not None:
            g = g * self.weight
            h = h * self.weight
        return g[None, :], h[None, :]


# ---------------------------------------------------------------------------
_OBJECTIVES = {
    "regression": RegressionL2,
    "regression_l1": RegressionL1,
    "huber": RegressionHuber,
    "fair": RegressionFair,
    "poisson": RegressionPoisson,
    "quantile": RegressionQuantile,
    "mape": RegressionMAPE,
    "gamma": RegressionGamma,
    "tweedie": RegressionTweedie,
    "binary": BinaryLogloss,
    "multiclass": MulticlassSoftmax,
    "multiclassova": MulticlassOVA,
    "xentropy": CrossEntropy,
    "xentlambda": CrossEntropyLambda,
    "lambdarank": LambdarankNDCG,
}


def create_objective(cfg: Config) -> Optional[ObjectiveFunction]:
    """reference ObjectiveFunction::CreateObjectiveFunction
    (objective_function.cpp:15)."""
    if cfg.objective in ("none", ""):
        return None
    cls = _OBJECTIVES.get(cfg.objective)
    if cls is None:
        raise ValueError(f"Unknown objective: {cfg.objective}")
    return cls(cfg)
