"""Boosting variants: GOSS, DART, RF + the boosting factory.

Re-creates `src/boosting/goss.hpp`, `src/boosting/dart.hpp`,
`src/boosting/rf.hpp` and the name factory `Boosting::CreateBoosting`
(`src/boosting/boosting.cpp:35-69`).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..io.dataset import Dataset
from ..ops.goss import goss_multipliers
from .gbdt import GBDT, K_EPSILON, _ScoreUpdater
from .tree import Tree


def goss_sizes(cfg: Config, n: int) -> Tuple[int, int, float]:
    """(top_k, other_k, the sampled rest's multiplier) over `n` rows:
    exact counts, not the reference's per-thread-block shares."""
    top_k = max(1, int(n * cfg.top_rate))
    other_k = max(1, int(n * cfg.other_rate))
    return top_k, other_k, (n - top_k) / other_k


def goss_select_body(g, h, seed, n: int, top_k: int, other_k: int):
    """The device GOSS selection (goss.hpp:96-134) in ROW order — the
    sequential per-model program AND the sweep trainer's vmapped fleet
    select (sweep/batched.py) both call it, so their bitwise parity is
    by construction; the aligned engine runs the same
    `ops.goss.goss_multipliers` over its permuted records. |g*h| summed
    over classes, every row at or above the top_k'th value kept, of the
    rest the other_k smallest integer keys of (row id, seed). Returns
    the [N] keep-mask and the [N] multiplier (0 = left out)."""
    a = jnp.abs(g * h).sum(axis=0)
    mult, _ = goss_multipliers(
        a, jnp.arange(n, dtype=jnp.int32), jnp.ones(n, bool), seed,
        top_k, other_k, (n - top_k) / other_k)
    return mult > 0, mult


class GOSS(GBDT):
    """Gradient-based one-side sampling (goss.hpp:25-160): keep the
    top_rate fraction by |g*h|, sample other_rate of the rest and up-weight
    their gradients by (1-top_rate)/other_rate.

    On the aligned engine the sample never exists on the host: each
    iteration's seed is drawn here, the engine's `goss_select` program
    writes the multipliers into the bag lane ahead of the build, and a
    fallback makes the same sample again from (scores, seed)."""

    _bag_on_device = True

    def __init__(self, cfg: Config, train_data: Dataset, objective=None):
        super().__init__(cfg, train_data, objective)
        if not (cfg.top_rate + cfg.other_rate <= 1.0):
            raise ValueError("top_rate + other_rate must be <= 1.0")
        if cfg.top_rate <= 0.0 or cfg.other_rate <= 0.0:
            raise ValueError("top_rate and other_rate must be positive")
        self._goss_multiplier = None     # device [N] or None
        self._goss_mask = None           # device [N] keep-mask, not pulled
        self._goss_select_fn = None

    # the sample's row indices are pulled from the device mask only when
    # a host partition asks for them (the fused path's root partition, an
    # aligned fallback, a checkpoint): never on the aligned hot path
    @property
    def bag_data_indices(self):
        if self._bag_idx is None and self._goss_mask is not None:
            self._bag_idx = np.nonzero(np.asarray(self._goss_mask))[0] \
                .astype(np.int32)
            self._bag_cnt = len(self._bag_idx)
        return self._bag_idx

    @bag_data_indices.setter
    def bag_data_indices(self, value):
        self._bag_idx = value
        self._goss_mask = None

    @property
    def bag_data_cnt(self):
        if self._goss_mask is not None:
            self.bag_data_indices       # pulls, and counts
        return self._bag_cnt

    @bag_data_cnt.setter
    def bag_data_cnt(self, value):
        self._bag_cnt = value

    def _will_bag(self) -> bool:
        return True

    def _goss_seed(self, iter_idx: int) -> Optional[int]:
        """This iteration's sampling seed, or None inside the first
        1/learning_rate iterations (goss.hpp:141-160). Drawn from the
        bagging RNG stream so runs stay reproducible under bagging_seed
        and a checkpoint carries the stream's state."""
        if iter_idx < int(1.0 / self.cfg.learning_rate):
            return None
        return int(self._bag_rng.randint(0, 2**31 - 1))

    def _bagging(self, iter_idx: int) -> None:
        self._goss_sample(self._goss_seed(iter_idx))

    def _goss_sample(self, seed: Optional[int]) -> None:
        """Select in row order from `_cur_grad` / `_cur_hess` ON DEVICE;
        the mask stays there until a host partition asks for indices."""
        self._goss_multiplier = None
        self.bag_data_indices = None
        self.bag_data_cnt = self.num_data
        if seed is None:
            return
        n = self.num_data
        fn = self._goss_select_fn
        if fn is None:
            top_k, other_k, _ = goss_sizes(self.cfg, n)

            def select(g, h, seed_arr):
                return goss_select_body(g, h, seed_arr[0], n, top_k,
                                        other_k)
            fn = jax.jit(select)
            self._goss_select_fn = fn
        self._goss_mask, self._goss_multiplier = fn(
            self._cur_grad, self._cur_hess, jnp.asarray([seed], jnp.uint32))

    def _post_bagging_gradients(self, gdev, hdev):
        if self._goss_multiplier is None:
            return gdev, hdev
        m = jnp.asarray(self._goss_multiplier)[None, :]
        return gdev * m, hdev * m

    # ---- the aligned engine's side (gbdt._train_one_iter_aligned)
    def _aligned_variant_gate(self) -> Optional[str]:
        if self.num_tree_per_iteration > 1:
            return ("boosting=goss with multiclass: the compact record's "
                    "bag bit holds no multiplier")
        if getattr(self.learner, "mode", "") == "data":
            return ("boosting=goss under tree_learner=data: the device "
                    "selects do not sum their counts over the mesh")
        return None

    def _maybe_rebag(self, eng) -> None:
        self._aligned_sample = self._goss_seed(self.iter)

    def _aligned_apply_sample(self, eng, seed, grads):
        """Queue the selection of the iteration about to be built, ahead
        of its build program; returns its device counters. An unsampled
        iteration after a sampled dispatch (a replay across the warm-up's
        end, cold) puts the lane back to ones."""
        if seed is None:
            if eng.bag_sampled:
                eng.set_bag(np.ones(self.num_data, np.float32))
            return None
        return eng.goss_select(seed, *goss_sizes(self.cfg, self.num_data),
                               grads=grads, boost_iter=self.iter)

    def _aligned_fallback_sample(self, seed, bag_idx, bag_cnt, gdev, hdev):
        """The sample the failed device build trained on, made again in
        row order from the synced scores and the same seed. No mask is
        left behind: the aligned loop stashes `bag_data_indices` with
        every round, and must find nothing there to pull."""
        self._cur_grad, self._cur_hess = gdev, hdev
        self._goss_sample(seed)
        out = (self.bag_data_indices, self.bag_data_cnt,
               *self._post_bagging_gradients(gdev, hdev))
        self._goss_sample(None)
        return out


class DART(GBDT):
    """Dropouts meet Multiple Additive Regression Trees (dart.hpp:25-209).

    Round 4: trains on the FUSED device learner (whole-tree jitted
    programs) like plain GBDT — the drop/renormalize machinery already
    runs on device score arrays via binned traversal
    (apply_tree_to_score); only the per-iteration tree materialization
    (one small batched pull in _dropping_trees) touches the host. The
    aligned engine stays out (its score lane cannot follow dropped
    scores — get_training_score override gates it), so DART uses the
    leaf-wise fused path (dart.hpp:58 shares the full-speed core the
    same way)."""

    def __init__(self, cfg: Config, train_data: Dataset, objective=None):
        super().__init__(cfg, train_data, objective)
        self.tree_weight: List[float] = []
        self.sum_weight = 0.0
        self.drop_index: List[int] = []
        self._drop_rng = np.random.RandomState(cfg.drop_seed)
        self._dropped_this_iter = False
        self.num_init_iteration = 0

    def _aligned_variant_gate(self) -> Optional[str]:
        return ("boosting=dart: the engine's score lane cannot follow "
                "dropped trees")

    def get_training_score(self) -> jax.Array:
        if not self._dropped_this_iter:
            self._dropping_trees()
            self._dropped_this_iter = True
        return self.train_score.score

    def train_one_iter(self, grad=None, hess=None) -> bool:
        self._dropped_this_iter = False
        ret = super().train_one_iter(grad, hess)
        if ret:
            return ret
        # the fused path defers its empty-tree check (batched trim), but
        # DART's tree_weight/sum_weight bookkeeping must stay aligned
        # with self.models — resolve the just-trained tree NOW (DART
        # pulls each iteration anyway for drop materialization) and stop
        # at the first no-split iteration like the reference
        if self._pending_numsplits \
                and len(self.models) > self.num_tree_per_iteration:
            ns = int(np.max(jax.device_get(
                self._pending_numsplits[-self.num_tree_per_iteration:])))
            if ns == 0:
                del self.models[-self.num_tree_per_iteration:]
                del self._pending_numsplits[-self.num_tree_per_iteration:]
                self.iter -= 1
                return True
        self._normalize()
        if not self.cfg.uniform_drop:
            self.tree_weight.append(self.shrinkage_rate)
            self.sum_weight += self.shrinkage_rate
        return False

    # ------------------------------------------------------------------
    def _dropping_trees(self) -> None:
        """dart.hpp:97-146."""
        # the fused path appends LazyTree records; dropping needs host
        # trees (leaf-value mutation + re-application)
        self.materialized_models()
        cfg = self.cfg
        self.drop_index = []
        is_skip = self._drop_rng.rand() < cfg.skip_drop
        if not is_skip:
            drop_rate = cfg.drop_rate
            if not cfg.uniform_drop:
                if self.tree_weight:
                    inv_avg = len(self.tree_weight) / self.sum_weight
                else:
                    inv_avg = 1.0
                if cfg.max_drop > 0 and self.sum_weight > 0:
                    drop_rate = min(drop_rate,
                                    cfg.max_drop * inv_avg / self.sum_weight)
                for i in range(self.iter):
                    if self._drop_rng.rand() < drop_rate \
                            * self.tree_weight[i] * inv_avg:
                        self.drop_index.append(self.num_init_iteration + i)
                        if len(self.drop_index) >= cfg.max_drop > 0:
                            break
            else:
                if cfg.max_drop > 0 and self.iter > 0:
                    drop_rate = min(drop_rate, cfg.max_drop / self.iter)
                for i in range(self.iter):
                    if self._drop_rng.rand() < drop_rate:
                        self.drop_index.append(self.num_init_iteration + i)
                        if len(self.drop_index) >= cfg.max_drop > 0:
                            break
        # drop: NEGATE the stored tree (reference Shrinkage(-1),
        # dart.hpp:137-143) then add — the stored sign matters because
        # Normalize's two shrinkage steps continue FROM -1 and must end
        # at +k/(k+1) (see the reference's step 1-3 note); applying the
        # subtraction as a score-side scale instead left dropped trees'
        # stored values negated after normalization (wrong exported
        # model AND wrong renormalized scores)
        for i in self.drop_index:
            for k in range(self.num_tree_per_iteration):
                t = self.models[i * self.num_tree_per_iteration + k]
                if t.num_leaves > 1:
                    t.apply_shrinkage(-1.0)
                    self.apply_tree_to_score(self.train_score,
                                             self.train_data.bins, t, k, 1.0)
        if not self.cfg.xgboost_dart_mode:
            self.shrinkage_rate = self.cfg.learning_rate \
                / (1.0 + len(self.drop_index))
        else:
            if not self.drop_index:
                self.shrinkage_rate = self.cfg.learning_rate
            else:
                self.shrinkage_rate = self.cfg.learning_rate \
                    / (self.cfg.learning_rate + len(self.drop_index))

    def _normalize(self) -> None:
        """dart.hpp:148-196: renormalize dropped trees and patch scores."""
        cfg = self.cfg
        k = float(len(self.drop_index))
        for i in self.drop_index:
            for cid in range(self.num_tree_per_iteration):
                t = self.models[i * self.num_tree_per_iteration + cid]
                if t.num_leaves <= 1:
                    continue
                if not cfg.xgboost_dart_mode:
                    t.apply_shrinkage(1.0 / (k + 1.0))
                    for ds, su in zip(self.valid_sets, self.valid_scores):
                        self.apply_tree_to_score(su, ds.bins, t, cid, 1.0)
                    t.apply_shrinkage(-k)
                    self.apply_tree_to_score(self.train_score,
                                             self.train_data.bins, t, cid,
                                             1.0)
                else:
                    t.apply_shrinkage(self.shrinkage_rate)
                    for ds, su in zip(self.valid_sets, self.valid_scores):
                        self.apply_tree_to_score(su, ds.bins, t, cid, 1.0)
                    t.apply_shrinkage(-k / cfg.learning_rate)
                    self.apply_tree_to_score(self.train_score,
                                             self.train_data.bins, t, cid,
                                             1.0)
            if not cfg.uniform_drop:
                if not cfg.xgboost_dart_mode:
                    self.sum_weight -= self.tree_weight[i] * (1.0 / (k + 1.0))
                    self.tree_weight[i] *= k / (k + 1.0)
                else:
                    self.sum_weight -= self.tree_weight[i] \
                        * (1.0 / (k + cfg.learning_rate))
                    self.tree_weight[i] *= k / (k + cfg.learning_rate)


class RF(GBDT):
    """Random forest mode (rf.hpp:25-194): mandatory bagging, no shrinkage,
    one-time gradients from constant init scores, running-average output.

    Round 4: trains on the FUSED device learner when eligible (renewal
    objectives still use the host learner), mirroring rf.hpp:103 sharing
    the full-speed core; the running-average score reshaping stays in
    device score arrays (MultiplyScore + traversal)."""

    def __init__(self, cfg: Config, train_data: Dataset, objective=None):
        super().__init__(cfg, train_data, objective)
        if not (cfg.bagging_freq > 0 and 0.0 < cfg.bagging_fraction < 1.0):
            raise ValueError("RF needs bagging (bagging_freq > 0 and "
                             "0 < bagging_fraction < 1)")
        self.shrinkage_rate = 1.0
        self.average_output = True
        self.init_scores = [0.0] * self.num_tree_per_iteration
        self._rf_boosting()

    def _rf_boosting(self) -> None:
        """rf.hpp:82-101: gradients from constant init scores, once."""
        for k in range(self.num_tree_per_iteration):
            init = 0.0
            if self.cfg.boost_from_average and self.objective is not None:
                init = self.objective.boost_from_score(k)
            self.init_scores[k] = init
        tmp = jnp.asarray(
            np.tile(np.asarray(self.init_scores, np.float32)[:, None],
                    (1, self.num_data)))
        g, h = self.objective.get_gradients(tmp)
        self._rf_grad, self._rf_hess = g, h

    def _build_rf_tree(self, gdev, hdev, k):
        """One RF tree: fused device learner (whole-tree jitted program,
        one small pull) when eligible, host learner otherwise."""
        if self.use_fused:
            fmask = self.learner.feature_mask()
            idxs, count = self.learner.init_root_partition(
                self.bag_data_indices, self.bag_data_cnt)
            idxs, rec = self._dispatch_device(
                "learner.train", self.learner.train,
                gdev[k], hdev[k], idxs, count, fmask)
            return self.learner.record_to_tree(jax.device_get(rec), 1.0)
        new_tree, leaf_map = self._dispatch_device(
            "learner.train", self.learner.train,
            gdev[k], hdev[k], self.bag_data_indices, self.bag_data_cnt)
        if (new_tree.num_leaves > 1 and self.objective is not None
                and getattr(self.objective, "is_renew_tree_output",
                            False)):
            pred = np.full(self.num_data, self.init_scores[k])
            self.learner.renew_tree_output(
                new_tree, leaf_map, self.objective, pred,
                self._label_np, self._weight_np)
        return new_tree

    def _aligned_variant_gate(self) -> Optional[str]:
        return ("boosting=rf: one-time gradients and a running-average "
                "score, its own iteration")

    def train_one_iter(self, grad=None, hess=None) -> bool:
        """rf.hpp:103-166."""
        self._log_train_path("fused" if self.use_fused else "per-tree")
        self._bagging(self.iter)
        gdev, hdev = self._rf_grad, self._rf_hess
        for k in range(self.num_tree_per_iteration):
            new_tree = Tree(2)
            if self._class_need_train[k] \
                    and self.train_data.num_features > 0:
                new_tree = self._build_rf_tree(gdev, hdev, k)
            if new_tree.num_leaves > 1:
                if abs(self.init_scores[k]) > K_EPSILON:
                    new_tree.add_bias(self.init_scores[k])
                # running average of tree outputs (rf.hpp:141-144)
                self.train_score.multiply_score(self.iter, k)
                for su in self.valid_scores:
                    su.multiply_score(self.iter, k)
                self._update_score(new_tree, k)
                self.train_score.multiply_score(1.0 / (self.iter + 1), k)
                for su in self.valid_scores:
                    su.multiply_score(1.0 / (self.iter + 1), k)
            else:
                if len(self.models) < self.num_tree_per_iteration:
                    output = 0.0
                    if not self._class_need_train[k] \
                            and self.objective is not None:
                        output = self.objective.boost_from_score(k)
                    new_tree.as_constant_tree(output)
            self.models.append(new_tree)
        self.iter += 1
        return False


def create_boosting(cfg: Config, train_data: Dataset,
                    objective=None) -> GBDT:
    """reference Boosting::CreateBoosting (boosting.cpp:35-69)."""
    name = cfg.boosting
    if name == "gbdt":
        return GBDT(cfg, train_data, objective)
    if name == "goss":
        return GOSS(cfg, train_data, objective)
    if name == "dart":
        return DART(cfg, train_data, objective)
    if name == "rf":
        return RF(cfg, train_data, objective)
    raise ValueError(f"Unknown boosting type: {name}")
