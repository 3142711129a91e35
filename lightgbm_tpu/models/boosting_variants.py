"""Boosting variants: GOSS, DART, RF + the boosting factory.

Re-creates `src/boosting/goss.hpp`, `src/boosting/dart.hpp`,
`src/boosting/rf.hpp` and the name factory `Boosting::CreateBoosting`
(`src/boosting/boosting.cpp:35-69`).
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..io.dataset import Dataset
from ..ops.goss import goss_multipliers
from ..obs import trace as obs_trace
from .gbdt import (GBDT, K_EPSILON, LazyAlignedTree, LazyTree,
                   _ScoreUpdater)
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

    _bag_on_device = True       # by its own rule, whatever bagging_* say
    _bag_multiplier = True

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
        stats = eng.goss_select(seed, *goss_sizes(self.cfg, self.num_data),
                                grads=grads, boost_iter=self.iter)
        return dict(zip(("goss_kept_top", "goss_kept_other",
                         "goss_threshold"), stats))

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


class DropSample(NamedTuple):
    """What one DART iteration does to the trees before it, drawn on the
    host from `drop_seed`'s stream and from nothing the device computes:
    it is known at dispatch and rides the queued round."""
    iter: int
    skipped: bool           # the skip_drop draw said: drop nothing
    dropped: Tuple[int, ...]    # iterations whose trees are dropped
    shrinkage: float        # of the tree this iteration builds
    keep: float             # what of its weight a dropped tree keeps


class DART(GBDT):
    """Dropouts meet Multiple Additive Regression Trees (dart.hpp:25-209).

    Every iteration draws its drop set on the host (`_draw_drop`), takes
    the dropped trees out of the training score before the gradients are
    made, builds at lr / (1 + k) and puts the k trees back at k / (k + 1)
    of their weight.

    On the aligned engine the score is a lane of records that lie in
    another order after every tree, so "out" and "back" are walks of the
    committed trees over the records as they lie (`AlignedEngine
    .walk_trees`, the `walk_pass` kernel), queued around the build and
    gated by its flags: an inexact round leaves the lane as it found it.
    Trees stay device specs and take their accumulated weight when they
    are materialised; the drop set rides each queued round, so the
    pipeline runs as deep as plain boosting's and a fallback replays it.
    Off the engine (`_aligned_variant_gate` names why) the fused
    leaf-wise loop runs as before: trees pulled each iteration and
    re-applied over row-order bins."""

    def __init__(self, cfg: Config, train_data: Dataset, objective=None):
        super().__init__(cfg, train_data, objective)
        self.tree_weight: List[float] = []
        self.sum_weight = 0.0
        self.drop_index: List[int] = []
        self._drop_rng = np.random.RandomState(cfg.drop_seed)
        self._dropped_this_iter = False
        self.num_init_iteration = 0
        # the engine's side: per dispatched round, what puts the host's
        # bookkeeping back if the round is discarded
        self._dart_undo: List[tuple] = []
        self._dart_rng_before = None
        self._dart_out: list = []       # the walks of the round in dispatch

    def _aligned_variant_gate(self) -> Optional[str]:
        lr = self.learner
        if self.num_tree_per_iteration > 1:
            return ("boosting=dart with multiclass: the record walk "
                    "follows one score lane")
        if getattr(lr, "mode", "") == "data":
            return ("boosting=dart under tree_learner=data: the record "
                    "walk is not sharded")
        if getattr(lr, "bundled", False):
            return ("boosting=dart with bundled features: the record "
                    "walk reads unbundled bins")
        if np.any(np.asarray(lr.meta["bin_type"]) != 0):
            return ("boosting=dart with categorical features: the record "
                    "walk takes numerical splits")
        if self.objective is not None \
                and self.objective.point_grad_fn() is None:
            return ("boosting=dart with a non-pointwise objective: its "
                    "row-order gradients are made before the drop")
        if self.cfg.num_leaves > 1024:
            return ("boosting=dart above 1024 leaves: the record walk's "
                    "tables are sized for VMEM")
        return None

    def get_training_score(self) -> jax.Array:
        if not self._dropped_this_iter:
            self._dropping_trees()
            self._dropped_this_iter = True
        return self.train_score.score

    def train_one_iter(self, grad=None, hess=None) -> bool:
        if grad is None and hess is None and self._aligned_eligible():
            # the engine's loop keeps the bookkeeping itself, a round
            # behind the dispatch (`_aligned_after_build`)
            return super().train_one_iter()
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
        self._weigh_new_tree()
        return False

    def _weigh_new_tree(self) -> None:
        if not self.cfg.uniform_drop:
            self.tree_weight.append(self.shrinkage_rate)
            self.sum_weight += self.shrinkage_rate

    # ------------------------------------------------------------------
    def _draw_drop(self) -> DropSample:
        """dart.hpp:97-146's draws, in its order: one for the skip, then
        one a tree until max_drop are dropped."""
        cfg = self.cfg
        drop: List[int] = []
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
                        drop.append(self.num_init_iteration + i)
                        if len(drop) >= cfg.max_drop > 0:
                            break
            else:
                if cfg.max_drop > 0 and self.iter > 0:
                    drop_rate = min(drop_rate, cfg.max_drop / self.iter)
                for i in range(self.iter):
                    if self._drop_rng.rand() < drop_rate:
                        drop.append(self.num_init_iteration + i)
                        if len(drop) >= cfg.max_drop > 0:
                            break
        k, lr = float(len(drop)), cfg.learning_rate
        den = self._drop_denominator(k)
        # (xgboost_dart_mode builds at learning_rate where nothing drops)
        shrinkage = lr / den if drop or not cfg.xgboost_dart_mode else lr
        sample = DropSample(self.iter, bool(is_skip), tuple(drop),
                            shrinkage, k / den)
        obs_trace.seam_record("dart.drop", iter=sample.iter,
                              skipped=sample.skipped, k=len(drop),
                              dropped=list(drop), shrinkage=shrinkage)
        return sample

    def _dropping_trees(self) -> None:
        """dart.hpp:97-146."""
        # the fused path appends LazyTree records; dropping needs host
        # trees (leaf-value mutation + re-application)
        self.materialized_models()
        sample = self._draw_drop()
        self.drop_index = list(sample.dropped)
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
        self.shrinkage_rate = sample.shrinkage

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
            self._reweigh_dropped(i, k)

    def _drop_denominator(self, k: float) -> float:
        """k + 1, or k + learning_rate under xgboost_dart_mode: the new
        tree is built at learning_rate over it, a dropped tree keeps k
        over it and loses 1 over it."""
        cfg = self.cfg
        return k + (cfg.learning_rate if cfg.xgboost_dart_mode else 1.0)

    def _reweigh_dropped(self, i: int, k: float) -> None:
        if not self.cfg.uniform_drop:
            den = self._drop_denominator(k)
            self.sum_weight -= self.tree_weight[i] * (1.0 / den)
            self.tree_weight[i] *= k / den

    # ---- the aligned engine's side (gbdt._train_one_iter_aligned)
    def _host_bag_why(self) -> Optional[str]:
        return super()._host_bag_why() or (
            "bagging under boosting=dart: a queued round's sample is "
            "its drop set")

    def _maybe_rebag(self, eng) -> None:
        super()._maybe_rebag(eng)
        self._dart_rng_before = self._drop_rng.get_state()
        self._aligned_sample = self._draw_drop()

    def _walked(self, eng, i: int):
        """(WalkTree, shrinkage, bias) of iteration i's tree as it stands:
        a device spec keeps its weight beside it, a host tree holds it in
        its leaf values. None for a tree that never split."""
        t = self.models[i]
        if isinstance(t, LazyTree) and not isinstance(t, LazyAlignedTree):
            t = self.models[i] = t.materialize()    # a fallback's record
        if isinstance(t, LazyAlignedTree):
            if t.walk is None:      # made on the device: nothing is pulled
                t.walk = eng.walk_tree_of_spec(t.record)
            return t.walk, t.shrinkage, t.bias
        if t.num_leaves <= 1:
            return None
        return eng.walk_tree_of_host(t), 1.0, 0.0

    def _dropped_walks(self, eng, sample) -> list:
        return [w for w in (self._walked(eng, i) for i in sample.dropped)
                if w is not None]

    def _aligned_apply_sample(self, eng, sample, grads):
        """The dropped trees out of the score lane, ahead of the build
        that makes its gradients there; the counters of the iteration."""
        if "walk_rec" not in eng._programs:
            # the walk's program, made before any tree is dropped (a pass
            # of no tree): a caller that times iterations has warmed up
            # by the time one is
            eng.walk_trees([], eng._last_exact, 0.0)
        if self.models:
            # the newest tree in the walk's form while nothing waits for
            # it: a few KB a tree, and the program that makes it is
            # compiled in the second iteration
            self._walked(eng, len(self.models) - 1)
        self.shrinkage_rate = sample.shrinkage
        trees = self._dart_out = self._dropped_walks(eng, sample)
        passes = eng.walk_trees(trees, eng._last_exact, -1.0) \
            if trees else 0
        return {"dart_dropped": len(sample.dropped),
                "walk_passes": 2 * passes,
                "rows_walked": 2 * len(trees) * self.num_data}

    def _scale_tree(self, i: int, factor: float):
        """Iteration i's tree at `factor` of its weight; returns what
        puts it back."""
        t = self.models[i]
        if isinstance(t, LazyTree):
            old = (t.shrinkage, t.bias)
            t.shrinkage, t.bias = old[0] * factor, old[1] * factor
            return old
        old = (t.leaf_value.copy(), t.internal_value.copy(), t.shrinkage)
        t.apply_shrinkage(factor)
        return old

    def _unscale_tree(self, i: int, old) -> None:
        t = self.models[i]
        if isinstance(t, LazyTree):
            t.shrinkage, t.bias = old
        else:
            t.leaf_value, t.internal_value, t.shrinkage = old

    def _commit_sample(self, sample) -> None:
        """The host's half of dart.hpp:148-196, once the round's device
        work is queued: dropped trees at `keep` of their weight, the
        weights the next draw reads, and what undoes both."""
        k = float(len(sample.dropped))
        weighed = not self.cfg.uniform_drop
        undo = (sample.iter, len(self.tree_weight), self.sum_weight,
                [(i, self.tree_weight[i] if weighed else None,
                  self._scale_tree(i, sample.keep))
                 for i in sample.dropped])
        for i in sample.dropped:
            self._reweigh_dropped(i, k)
        self._weigh_new_tree()
        self._dart_undo.append(undo)
        # a round leaves the ring once the host has resolved it
        depth = 2 * self._aligned_pipeline_depth() + 2
        del self._dart_undo[:-depth]

    def _aligned_after_build(self, eng, sample, out, prev_ok) -> None:
        """The dropped trees back at `keep` of their weight where the
        build applied; at all of it where the round ran but its build was
        inexact (the host rebuilds that round from the lane as it was);
        not at all in a round behind an inexact one, which took nothing
        out."""
        if self._dart_out:      # as they went out: nothing re-weighed yet
            eng.walk_trees(self._dart_out, out[3], sample.keep, prev_ok, 1.0)
        self._commit_sample(sample)

    def _aligned_valid_walks(self, eng, sample) -> list:
        # the valid set held the dropped trees whole, at the weight they
        # had before `_commit_sample` cut it to `keep` of that: each goes
        # in again at 1 - 1 / keep of the weight it has now
        walks = [w for w in (self._walked(eng, i) for i in sample.dropped)
                 if w is not None]
        if not walks:
            return []
        f = 1.0 - 1.0 / sample.keep
        return [(t, shrinkage * f, bias * f) for t, shrinkage, bias in walks]

    def _aligned_forget_from(self, first_iter: int) -> None:
        while self._dart_undo and self._dart_undo[-1][0] >= first_iter:
            _, weights, self.sum_weight, scaled = self._dart_undo.pop()
            del self.tree_weight[weights:]
            for i, weight, old in reversed(scaled):
                if weight is not None:
                    self.tree_weight[i] = weight
                if i < len(self.models):
                    self._unscale_tree(i, old)

    def _keeps_ahead(self, eng) -> bool:
        # the round ahead also took its dropped trees out of the lane
        return False

    def _discard_eager(self) -> None:
        stash = getattr(self, "_aligned_next", None)
        sample = self._aligned_sample       # the eager round's
        super()._discard_eager()
        if stash is None:
            return
        # the eager round took 1 - keep of its dropped trees out of the
        # lane where it applied: back in, at the weights they had
        applied = stash[0][3]
        self._aligned_forget_from(sample.iter)
        eng = self._aligned_eng_ref
        trees = self._dropped_walks(eng, sample)
        if trees:
            eng.walk_trees(trees, applied, 1.0 - sample.keep)
        self._drop_rng.set_state(self._dart_rng_before)

    def _trim_trailing_empty(self) -> bool:
        stop = super()._trim_trailing_empty()
        if stop and len(self.tree_weight) > self.iter:
            # trees that never split are gone from the model; what their
            # iterations dropped stays re-weighted, in lane and trees alike
            del self.tree_weight[self.iter:]
            self.sum_weight = float(sum(self.tree_weight))
        return stop

    def materialized_models(self):
        # a round dispatched ahead of its turn has already left its
        # dropped trees lighter: the model is read without it
        self._discard_eager()
        return super().materialized_models()

    def _aligned_fallback_iter(self, init_scores, eng, fmask, bag_idx=None,
                               bag_cnt=0, sample=None) -> bool:
        """The exact rebuild of a round the engine could not replay, in
        row order as the fused loop does it: dropped trees out, the tree
        grown on those scores at the round's shrinkage, dropped trees
        back at `keep`, valid sets alike."""
        self._sync_train_score()
        for i in sample.dropped:
            if isinstance(self.models[i], LazyTree):
                self.models[i] = self.models[i].materialize()
        dropped = [self.models[i] for i in sample.dropped
                   if self.models[i].num_leaves > 1]
        for t in dropped:
            self.apply_tree_to_score(self.train_score, self.train_data.bins,
                                     t, 0, -1.0)
        self.shrinkage_rate = sample.shrinkage
        self._dropped_this_iter = True      # `_gradients` drops no more
        stop = super()._aligned_fallback_iter(init_scores, eng, fmask,
                                              bag_idx, bag_cnt, sample)
        for t in dropped:
            self.apply_tree_to_score(self.train_score, self.train_data.bins,
                                     t, 0, sample.keep)
            for ds, su in zip(self.valid_sets, self.valid_scores):
                self.apply_tree_to_score(su, ds.bins, t, 0,
                                         sample.keep - 1.0)
        self._commit_sample(sample)
        eng.set_row_scores(self.train_score.score[0])
        return stop


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
