"""GBDT boosting driver.

Re-creates the reference `GBDT` (`src/boosting/gbdt.cpp`): per-iteration
gradient computation from the objective, bagging (plain + pos/neg balanced,
`gbdt.cpp:159-275`), per-class tree training, boost-from-average with the
bias folded back into the first trees (`gbdt.cpp:343-412`), shrinkage, score
updates for train/valid sets, early stopping, rollback, and model text
serialization (`gbdt_model_text.cpp`).

TPU structure: the host drives iterations (exactly the reference's
one-C-call-per-iteration shape, `basic.py:1846` -> `LGBM_BoosterUpdateOneIter`)
while gradients, histograms, splits, partitions and score updates are jitted
device programs. Scores are kept on device [K, N]; metrics pull them to host
once per eval.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..io.dataset import Dataset
from ..obs import trace as obs_trace
from ..ops.goss import bag_rows
from ..ops.metrics import Metric, create_metrics
from ..ops.objectives import ObjectiveFunction, create_objective
from ..ops.predict import TreePredictor, stack_trees, _predict_binned_stacked
from .device_learner import (DeviceTreeLearner, TreeRecord,
                             add_record_score, traversal_arrays)
from .serial_learner import SerialTreeLearner
from .tree import Tree

K_EPSILON = 1e-15


class LazyTree:
    """A tree still living on device as a TreeRecord; materialized to a host
    `Tree` only when the model surface needs it (export/predict)."""

    __slots__ = ("record", "shrinkage", "bias", "learner", "max_nodes")

    def __init__(self, record: TreeRecord, shrinkage: float, bias: float,
                 learner: DeviceTreeLearner, max_nodes: int) -> None:
        self.record = record
        self.shrinkage = shrinkage
        self.bias = bias
        self.learner = learner
        self.max_nodes = max_nodes

    def materialize(self, rec_host=None) -> Tree:
        rec = rec_host if rec_host is not None else jax.device_get(
            self.record)
        tree = self.learner.record_to_tree(rec, self.shrinkage)
        if abs(self.bias) > K_EPSILON:
            tree.add_bias(self.bias)
        return tree


def _record_aligned_iter(it: int, rounds, table, sampled=None,
                         eng=None) -> None:
    """One `aligned.iter` seam record for a resolved aligned iteration:
    the build program's round count and its per-round counters
    (`aligned_builder.ROUND_STATS` order, rows up to `rounds`), as pulled
    with the exactness flags. Data-parallel (`table` [shards, rows, R]):
    the table holds each counter's mean over the shards, a chip's share,
    and `<counter>_by_shard` each round's counts by shard; `psum_bytes`
    is what the build's histogram all-reduces summed over the mesh (the
    root's and one a round: `eng.psum_bytes`).
    `sampled` = the named counters the boosting variant recorded of the
    iteration, device scalars or the host's own numbers: GOSS's
    selection (`goss_kept_top`, `goss_kept_other`, `goss_threshold`),
    DART's walks (`dart_dropped`, `walk_passes`, `rows_walked`), the
    rows a bag left outside the tree's rounds (`rows_parked`,
    `chunks_parked`, and `park_rounds`: 1 where the table's first row is
    the partition by the bag); absent where there is none."""
    from .aligned_builder import ROUND_STATS
    rounds = int(rounds)
    extra = {k: np.asarray(v).item() for k, v in (sampled or {}).items()}
    table = np.asarray(table)
    if table.ndim == 3:
        by_shard = table[:, :rounds]
        table = by_shard.mean(axis=0)
        extra.update({f"{name}_by_shard": by_shard[:, :, i].T.tolist()
                      for i, name in enumerate(ROUND_STATS)})
        psum = None if eng is None else eng.psum_bytes
        if psum is not None:
            extra["psum_bytes"] = psum[0] + rounds * psum[1]
    obs_trace.seam_record("aligned.iter", iter=int(it), rounds=rounds,
                          columns=list(ROUND_STATS),
                          table=table[:rounds].tolist(), **extra)


class LazyAlignedTree(LazyTree):
    """A tree still living as a device AlignedSpec; the host leaf-wise
    replay runs at materialization (deterministically identical to the
    on-device replay that committed the tree)."""

    walk = None     # the tree as the record walk takes it, once asked for

    def materialize(self, rec_host=None) -> Tree:
        from .aligned_builder import replay_spec
        spec = rec_host if rec_host is not None else jax.device_get(
            self.record)
        record, _ = replay_spec(spec, self.learner.cfg.num_leaves)
        tree = self.learner.record_to_tree(record, self.shrinkage)
        if abs(self.bias) > K_EPSILON:
            tree.add_bias(self.bias)
        return tree


class _DeviceScoreView:
    """Duck-typed stand-in for _ScoreUpdater in _eval: a device [K, N]
    score matrix materialized on demand."""

    def __init__(self, score) -> None:
        self.score = score

    def numpy(self) -> np.ndarray:
        return np.asarray(self.score, np.float64)


class _ScoreUpdater:
    """Per-dataset cached raw scores (reference ScoreUpdater,
    score_updater.hpp:27-85)."""

    def __init__(self, num_data: int, num_class: int,
                 init_score: Optional[np.ndarray]) -> None:
        self.num_data = num_data
        self.num_class = num_class
        self.has_init_score = init_score is not None
        if init_score is not None:
            arr = np.asarray(init_score, np.float64).reshape(
                num_class, num_data)
            self.score = jnp.asarray(arr, jnp.float32)
        else:
            self.score = jnp.zeros((num_class, num_data), jnp.float32)

    def add_constant(self, val: float, class_id: int) -> None:
        self.score = self.score.at[class_id].add(jnp.float32(val))

    def multiply_score(self, factor: float, class_id: int) -> None:
        """reference ScoreUpdater::MultiplyScore (used by RF running
        average)."""
        self.score = self.score.at[class_id].multiply(jnp.float32(factor))

    def add_tree_by_leaves(self, leaves: jax.Array, leaf_values: np.ndarray,
                           class_id: int) -> None:
        """leaves: [N] leaf index per row; leaf_values: host array."""
        lv = jnp.asarray(leaf_values, jnp.float32)
        self.score = self.score.at[class_id].add(lv[leaves])

    def numpy(self) -> np.ndarray:
        return np.asarray(self.score, np.float64)

    @property
    def nbytes(self) -> int:
        return int(self.score.nbytes)


class _RecordScores(_ScoreUpdater):
    """A validation set's scores as the score lane of its rows packed
    into records once (`AlignedEngine.pack_rows`), where `walk_pass` adds
    each committed tree (`walk`). The rows never move, so record order is
    row order and `score` is a view of the lane, `[1, n]`, on the device;
    what sets `score` (the updater's own updates, a resume) writes the
    lane back."""

    def __init__(self, eng, bins, su: _ScoreUpdater) -> None:
        self.eng = eng
        self.num_data = su.num_data
        self.num_class = 1
        self.has_init_score = su.has_init_score
        self.rec, self.cnts, self.pack_info = eng.pack_rows(bins, su.score)

    @property
    def score(self):
        return self.eng.block_scores(self.rec, self.num_data)

    @score.setter
    def score(self, value) -> None:
        self.rec = self.eng.block_set_scores(self.rec, value)

    @property
    def nbytes(self) -> int:
        return int(self.rec.nbytes) + int(self.cnts.nbytes)

    def walk(self, trees, applied) -> int:
        """Scores += the sum over `trees` = [(WalkTree, shrinkage, bias)]
        where the device flag `applied` holds; returns the passes."""
        self.rec, passes = self.eng.walk_block(
            self.rec, self.cnts, trees, applied, 1.0, phase="valid.walk")
        return passes

    def add_tree(self, tree: Tree, factor: float = 1.0) -> None:
        """Scores += factor x a host tree's leaf values (a fallback's)."""
        self.walk([(self.eng.walk_tree_of_host(tree), factor, 0.0)],
                  jnp.asarray(True))

    def unpacked(self) -> _ScoreUpdater:
        """The scores as a row-order `_ScoreUpdater`, for a path off the
        engine."""
        su = _ScoreUpdater(self.num_data, 1, None)
        su.has_init_score = self.has_init_score
        su.score = self.score
        return su


class GBDT:
    """reference `GBDT` (gbdt.h:41+)."""

    def _bundle_arrays(self):
        """(col, boff, bpk) for binned traversal when the training bins
        are EFB-bundled (valid sets share the training bundling)."""
        if getattr(self.learner, "bundled", False):
            lr = self.learner
            return (lr._col_dev, lr._boff_dev, lr._bpk_dev)
        return None

    _fused_ok = True  # subclass hook (no current subclass disables it)

    def __init__(self, cfg: Config, train_data: Dataset,
                 objective: Optional[ObjectiveFunction] = None) -> None:
        from ..utils.log import set_verbosity
        set_verbosity(int(cfg.verbosity))
        self.cfg = cfg
        self.train_data = train_data
        self.num_data = train_data.num_data
        self.objective = (objective if objective is not None
                          else create_objective(cfg))
        if self.objective is not None:
            self.objective.init(train_data.metadata, self.num_data)
        self.num_tree_per_iteration = (
            self.objective.num_model_per_iteration
            if self.objective is not None else max(1, cfg.num_class))
        self.shrinkage_rate = cfg.learning_rate
        self.models: List[Tree] = []
        self.iter = 0
        # fused on-device learner when the objective has no host-side leaf
        # renewal hook; host-driven serial learner otherwise
        # voting-parallel forced splits would read LOCAL histograms
        # against GLOBAL totals, and coupled-CEGB state is serial-only:
        # both route to the host twin (the reference's own learner)
        seq_host = ((bool(cfg.forcedsplits_filename)
                     and cfg.tree_learner == "voting")
                    or (len(cfg.cegb_penalty_feature_coupled) > 0
                        and cfg.tree_learner != "serial"))
        self.use_fused = (
            self._fused_ok
            and not (self.objective is not None
                     and getattr(self.objective, "is_renew_tree_output",
                                 False))
            and not cfg.forces_host_learner
            and not seq_host
            and cfg.tree_learner in ("serial", "data", "feature", "voting"))
        if self.use_fused:
            # the dist runtime owns topology: it resolves the shard
            # count (tpu_dist_devices / num_machines / all devices),
            # builds the mesh, pre-shards the dataset onto it, and
            # routes through parallel.make_parallel_learner. A 1-wide
            # mesh degenerates to the serial device learner.
            from ..dist import runtime as dist_runtime
            if cfg.tree_learner == "serial" or not dist_runtime.active(cfg):
                from ..utils import log
                if (getattr(train_data, "_bins_freed", False)
                        and getattr(train_data, "_bins", None) is None):
                    # stream-to-shard built per-device shards but the run
                    # degenerated to the serial learner (1-wide mesh or
                    # tpu_stream_shard="on" without a parallel learner):
                    # the first host-side bins read below re-gathers the
                    # full matrix from the mesh. Correct, but the O(n)
                    # host copy the sharded ingest avoided comes back.
                    log.warning(
                        "dataset was stream-sharded but the run routes to "
                        "the serial device learner; re-gathering the host "
                        "binned matrix (set tpu_stream_shard=off or widen "
                        "the mesh to avoid the extra copy)")
                self.learner = DeviceTreeLearner(cfg, train_data)
            else:
                self.learner = dist_runtime.make_learner(cfg, train_data)
            self._trav_nb = jnp.asarray(self.learner.meta["num_bin"],
                                        jnp.int32)
            self._trav_db = jnp.asarray(self.learner.meta["default_bin"],
                                        jnp.int32)
            self._trav_mt = jnp.asarray(self.learner.meta["missing_type"],
                                        jnp.int32)
        else:
            self.learner = SerialTreeLearner(cfg, train_data)
        self.train_score = _ScoreUpdater(
            self.num_data, self.num_tree_per_iteration,
            self._reshape_init_score(train_data))
        self.valid_sets: List[Dataset] = []
        self.valid_scores: List[_ScoreUpdater] = []
        self.valid_metrics: List[List[Metric]] = []
        self.train_metrics: List[Metric] = create_metrics(cfg)
        for m in self.train_metrics:
            m.init(train_data.metadata, self.num_data)
        self.best_iter: Dict[str, int] = {}
        self.best_score: Dict[str, float] = {}
        self._bag_rng = np.random.RandomState(cfg.bagging_seed)
        self.bag_data_indices: Optional[np.ndarray] = None
        self.bag_data_cnt = self.num_data
        self._label_np = (np.asarray(train_data.metadata.label, np.float64)
                          if train_data.metadata.label is not None
                          else np.zeros(self.num_data))
        self._weight_np = (np.asarray(train_data.metadata.weight, np.float64)
                           if train_data.metadata.weight is not None else None)
        self._balanced_bagging = (
            cfg.objective == "binary"
            and (cfg.pos_bagging_fraction < 1.0
                 or cfg.neg_bagging_fraction < 1.0))
        self._class_need_train = [True] * self.num_tree_per_iteration
        if self.objective is not None and hasattr(self.objective, "need_train"):
            self._class_need_train = [self.objective.need_train] \
                * self.num_tree_per_iteration
        self._pending_numsplits: List[jax.Array] = []
        self._valid_bins_dev: List[jax.Array] = []
        # telemetry (obs/): None when off — the round loop's ONLY added
        # cost on the default path is this attribute check
        self.telemetry = None
        self._obs_fallbacks_seen = 0
        if cfg.tpu_trace:
            from ..obs import ledger as obs_ledger
            from ..obs import trace as obs_trace
            tdir = cfg.tpu_trace_dir or "lgbt_trace"
            obs_trace.enable(tdir)
            self.telemetry = obs_ledger.RoundLedger.for_training(tdir, cfg)
        # live metrics plane (obs/metrics.py): None when off — the same
        # single-branch discipline as telemetry, and the metered path
        # never fences (host wall + counter deltas only)
        self._metrics = None
        self._obs_trees_seen = 0
        if cfg.tpu_metrics:
            from ..obs import metrics as obs_metrics
            obs_metrics.enable()
            self._metrics = obs_metrics.train_instruments()
        # HBM accountant (obs/memory.py): the training score buffers are
        # a named owner; registration is once-per-booster and read only
        # at snapshot time
        from ..obs import memory as obs_memory
        obs_memory.track(
            "train/scores", self,
            lambda g: g.train_score.nbytes
            + sum(su.nbytes for su in g.valid_scores))
        # resilience (resilience/): deterministic fault plan (param/env)
        # and the retry wrapper around device dispatches. None/False on
        # the default path — _dispatch_device is then a plain call
        self._fault_plan = None
        if cfg.tpu_fault_spec or os.environ.get("LGBT_FAULTS", ""):
            from ..resilience.faults import FaultPlan
            self._fault_plan = FaultPlan.from_config(
                cfg, telemetry=self.telemetry)
        # rolling-median anomaly watch (obs/straggler.py) over the traced
        # rounds' walls: pure host arithmetic over what the round fence
        # already measured; None unless the timeline is live
        self._anomaly = None
        from ..obs.timeline import timeline_on
        if cfg.tpu_anomaly_factor > 0 and timeline_on(cfg):
            from ..obs.straggler import AnomalyWatch
            self._anomaly = AnomalyWatch(factor=cfg.tpu_anomaly_factor,
                                         window=cfg.tpu_anomaly_window)

    @staticmethod
    def _reshape_init_score(ds: Dataset) -> Optional[np.ndarray]:
        if ds.metadata.init_score is None:
            return None
        return ds.metadata.init_score

    # ------------------------------------------------------------------
    def add_valid_dataset(self, ds: Dataset,
                          metrics: Optional[List[Metric]] = None) -> None:
        """reference GBDT::AddValidDataset (gbdt.cpp:119-147)."""
        self._valid_eval_stash = None   # stash indexed by old set count
        self.valid_sets.append(ds)
        su = _ScoreUpdater(ds.num_data, self.num_tree_per_iteration,
                           self._reshape_init_score(ds))
        self._valid_bins_dev.append(None)   # uploaded where walked by rows
        # replay existing model onto the new valid set
        if self.models:
            models = self.materialized_models()
            pred = TreePredictor(models)
            leaves = pred.predict_binned_leaves(ds.bins, self._bundle_arrays())
            for i, tree in enumerate(models):
                su.add_tree_by_leaves(leaves[i],
                                      tree.leaf_value[:tree.num_leaves],
                                      i % self.num_tree_per_iteration)
        self.valid_scores.append(su)
        ms = metrics if metrics is not None else create_metrics(self.cfg)
        for m in ms:
            m.init(ds.metadata, ds.num_data)
        self.valid_metrics.append(ms)
        if getattr(self, "_aligned_eng_ref", None) is not None:
            self._pack_valid(len(self.valid_sets) - 1)

    def _valid_bins(self, i: int) -> jax.Array:
        """Valid set i's row-order bins on the device, for the walks over
        rows (the fused loop, the XLA walkers); uploaded at first use, so
        a set packed into records never holds them."""
        if self._valid_bins_dev[i] is None:
            self._valid_bins_dev[i] = jnp.asarray(self.valid_sets[i].bins)
        return self._valid_bins_dev[i]

    def _pack_valid(self, i: int) -> None:
        """Valid set i onto the aligned engine: where the record walk can
        follow the engine's trees (`AlignedEngine.record_walk_why`, static
        facts), its rows packed into records once and its scores moved
        into their score lane; else its row-order bins for the XLA
        walkers. One `valid.pack` seam says which and why."""
        eng = self._aligned_eng_ref
        ds, su = self.valid_sets[i], self.valid_scores[i]
        why = eng.record_walk_why()
        with obs_trace.seam("valid.pack", rows=int(ds.num_data),
                            walk="rows" if why else "records",
                            why=why) as sm:
            if why is None:
                su = self.valid_scores[i] = _RecordScores(eng, ds.bins, su)
                su.rec.block_until_ready()  # graftlint: disable=LGT002 the validation pack's one wait at load time, not a round-loop fence
                sm.attrs.update(bytes=su.nbytes, chunks=int(su.rec.shape[0]),
                                pack="device",
                                pack_blocks=su.pack_info["blocks"],
                                upload_bytes=su.pack_info["upload_bytes"])
            else:
                nbytes = int(self._valid_bins(i).nbytes)
                sm.attrs.update(bytes=nbytes, chunks=0, pack="none",
                                pack_blocks=0, upload_bytes=nbytes)

    # ------------------------------------------------------------------
    def _bagging(self, iter_idx: int) -> None:
        """reference GBDT::Bagging (gbdt.cpp:209-275) in ROW order — the
        per-block `Random::NextFloat` walk replaced by an exact-count
        select: the `cnt` rows with the smallest integer key of (row id,
        the re-bag's seed), `ops/goss.py:bag_rows`, the numpy twin of
        what the aligned engine draws on the device, so every path
        trains on one bag. Balanced bagging keeps pos/neg fractions
        separately (gbdt.cpp:177-207), drawn here."""
        cfg = self.cfg
        if not self._will_bag():
            return
        redraw = iter_idx % cfg.bagging_freq == 0
        if self._balanced_bagging:
            if not redraw:
                return
            pos = self._label_np > 0
            pos_idx = np.nonzero(pos)[0]
            neg_idx = np.nonzero(~pos)[0]
            take_pos = self._bag_rng.rand(len(pos_idx)) \
                < cfg.pos_bagging_fraction
            take_neg = self._bag_rng.rand(len(neg_idx)) \
                < cfg.neg_bagging_fraction
            sel = np.sort(np.concatenate([pos_idx[take_pos],
                                          neg_idx[take_neg]])
                          ).astype(np.int32)
        else:
            if redraw:
                seed = self._draw_bag_seed()
            elif (self.bag_data_indices is None and self._bag_on_device
                  and self._aligned_sample is not None):
                seed = self._aligned_sample[0]  # the engine drew it: made
            else:                               # here from what it holds
                return
            sel = bag_rows(self.num_data, seed, self._bag_cnt_plain())
        self.bag_data_indices = sel
        self.bag_data_cnt = len(sel)

    def _bag_cnt_plain(self) -> int:
        return int(self.cfg.bagging_fraction * self.num_data)

    def _draw_bag_seed(self) -> int:
        """A re-bag's seed, from `bagging_seed`'s stream as GOSS draws
        its."""
        return int(self._bag_rng.randint(0, 2**31 - 1))

    def _host_bag_why(self) -> Optional[str]:
        """Why this run's bag is drawn on the host and uploaded
        (`AlignedEngine.set_bag`, pipeline depth 1) where the engine could
        draw it, for the `train_path` event's `gate_notes`; None where
        the device draws it."""
        if self._balanced_bagging:
            return ("balanced bagging: pos_/neg_bagging_fraction draw "
                    "per label from the host's stream")
        if self.num_tree_per_iteration > 1:
            return ("bagging on the multiclass engine: it pulls every "
                    "round's flags anyway, one round in flight")
        if getattr(self.learner, "mode", "") == "data":
            return ("bagging under tree_learner=data: the device select "
                    "does not sum its counts over the mesh")
        return None

    @property
    def _bag_on_device(self) -> bool:
        """The bag lane is written by a device program and never
        uploaded: plain bagging here, GOSS by its own rule."""
        return self._will_bag() and self._host_bag_why() is None

    # ------------------------------------------------------------------
    def boost_from_average(self, class_id: int) -> float:
        """reference GBDT::BoostFromAverage (gbdt.cpp:342-365)."""
        if (not self.models and not self.train_score.has_init_score
                and self.objective is not None
                and self.cfg.boost_from_average):
            init_score = self.objective.boost_from_score(class_id)
            if abs(init_score) > K_EPSILON:
                self.train_score.add_constant(init_score, class_id)
                for su in self.valid_scores:
                    su.add_constant(init_score, class_id)
                return init_score
        return 0.0

    def _gradients(self) -> Tuple[jax.Array, jax.Array]:
        g, h = self.objective.get_gradients(self.get_training_score())
        return g, h

    def get_training_score(self) -> jax.Array:
        """Hook: DART drops trees from the returned score (dart.hpp:77-86)."""
        self._sync_train_score()
        return self.train_score.score

    def _post_bagging_gradients(self, gdev, hdev):
        """Hook: GOSS re-weights sampled small-gradient rows
        (goss.hpp:102-108)."""
        return gdev, hdev

    def apply_tree_to_score(self, su: "_ScoreUpdater", bins, tree: Tree,
                            class_id: int, scale: float = 1.0) -> None:
        """Add scale * tree(x) into a score updater via binned traversal
        (a set packed into records: by the record walk)."""
        if isinstance(su, _RecordScores):
            su.add_tree(tree, scale)
            return
        pred = TreePredictor([tree])
        leaves = pred.predict_binned_leaves(bins, self._bundle_arrays())[0]
        su.add_tree_by_leaves(
            leaves, tree.leaf_value[:tree.num_leaves] * scale, class_id)

    # ------------------------------------------------------------------
    def train_one_iter(self, grad: Optional[np.ndarray] = None,
                       hess: Optional[np.ndarray] = None) -> bool:
        """reference GBDT::TrainOneIter (gbdt.cpp:367-448). Returns True when
        training should STOP (no splittable tree), mirroring the C API's
        is_finished flag. With `tpu_trace` or `tpu_metrics` on the round
        runs inside _train_one_iter_observed; off, this is two None
        checks."""
        if self.telemetry is None and self._metrics is None:
            return self._train_one_iter_impl(grad, hess)
        return self._train_one_iter_observed(grad, hess)

    def _dispatch_device(self, what: str, fn, *args):
        """Every learner/engine device dispatch funnels through here so
        the resilience layer can inject deterministic faults and retry
        transient device errors (resilience/retry.py). With no fault
        plan and no retries this is a plain call."""
        plan = self._fault_plan
        if plan is None and self.cfg.tpu_retry_max <= 0:
            return fn(*args)
        from ..resilience.retry import call_with_retry
        return call_with_retry(
            fn, args, what=what, plan=plan,
            max_retries=self.cfg.tpu_retry_max,
            backoff_s=self.cfg.tpu_retry_backoff_s,
            telemetry=self.telemetry)

    def _round_fence_target(self):
        """What to drain to observe this round's device time: the
        aligned engine's newest pending dispatch when the pipelined path
        is active (train_score is synced lazily there and would fence
        stale work), the score buffer otherwise."""
        pend = getattr(self, "_aligned_pending", None) or []
        if pend:
            return pend[-1]
        pend_mc = getattr(self, "_aligned_pending_mc", None)
        if pend_mc is not None:
            return pend_mc[0]
        return self.train_score.score

    def _train_one_iter_observed(self, grad, hess) -> bool:
        """The one observed round around the untouched implementation.
        Always: host wall and the trace / fallback counter deltas, fed to
        the live metrics where `tpu_metrics` is set. Only where
        `tpu_trace` is set: StepTraceAnnotation + span, ONE fence to split
        the wall into the host-visible part and the residual device
        drain, and a ledger commit. Without the tracer nothing fences, so
        wall_ms is then dispatch wall, not device wall."""
        import time as _time

        from ..compile_cache import trace_count
        tel = self.telemetry
        rnd = self.iter
        traces0 = trace_count()
        t0 = _time.perf_counter()
        if tel is None:
            finished = self._train_one_iter_impl(grad, hess)
            t_host = t1 = _time.perf_counter()
        else:
            with obs_trace.step(rnd):
                with obs_trace.span("train.round", round=rnd):
                    finished = self._train_one_iter_impl(grad, hess)
                    t_host = _time.perf_counter()
                    with obs_trace.span("train.round.fence", round=rnd):
                        obs_trace.fence(self._round_fence_target())
            t1 = _time.perf_counter()
        wall_ms = round((t1 - t0) * 1e3, 3)
        traces = trace_count() - traces0
        eng = getattr(self, "_aligned_eng_ref", None)
        fb = int(getattr(eng, "fallbacks", 0) or 0) if eng is not None \
            else 0
        fallbacks = fb - self._obs_fallbacks_seen
        self._obs_fallbacks_seen = fb
        if tel is not None:
            path = getattr(self, "_iter_path", "unknown")
            rec = {
                "kind": "round", "round": rnd,
                "wall_ms": wall_ms,
                "device_ms": round((t1 - t_host) * 1e3, 3),
                "traces": traces,
                "path": path,
                "aligned": path.startswith("aligned"),
                "fallbacks": fallbacks,
                "trees": len(self.models),
                "bag_cnt": int(self.bag_data_cnt),
                "finished": bool(finished),
                # raw perf_counter at round start: the timeline's clock
                # anchor (CLOCK_MONOTONIC — shared across processes on
                # the host, so spans/ledger/reqtrace join without
                # alignment)
                "t0": round(t0, 6),
            }
            notes = list(getattr(self, "_gate_notes", ()) or ())
            if notes:
                rec["gate_notes"] = notes
                rec["hist_spill"] = any("spill" in n.lower() for n in notes)
            tel.commit(rec)
            if self._anomaly is not None:
                self._note_anomaly(rnd, wall_ms)
        if self._metrics is not None:
            self._note_round_metrics(wall_ms, traces, fallbacks)
        return finished

    def _note_anomaly(self, rnd: int, wall_ms: float) -> None:
        """Fold one traced round's wall into the rolling-median anomaly
        watch (obs/straggler.py — pure host arithmetic, zero fences). A
        deviation past tpu_anomaly_factor commits a ``round_anomaly``
        ledger note + event while the run can still react."""
        hit = self._anomaly.update(wall_ms)
        if hit is None:
            return
        import time as _time

        from ..utils import log
        self.telemetry.commit(
            {"kind": "note", "note": "round_anomaly", "round": rnd,
             "wall_ms": wall_ms,
             "t0": round(_time.perf_counter(), 6), **hit})
        log.event("round_anomaly", round=rnd,
                  wall_ms=wall_ms, **hit)

    def _note_round_metrics(self, wall_ms: float, traces: int,
                            fallbacks: int) -> None:
        """Feed one completed round into the live metrics registry."""
        m = self._metrics
        m.rounds.inc()
        m.round_ms.observe(wall_ms)
        if traces > 0:
            m.retraces.inc(traces)
        if fallbacks > 0:
            m.fallbacks.inc(fallbacks)
        trees = len(self.models)
        if trees > self._obs_trees_seen:
            m.trees.inc(trees - self._obs_trees_seen)
        self._obs_trees_seen = trees

    def _train_one_iter_impl(self, grad: Optional[np.ndarray] = None,
                             hess: Optional[np.ndarray] = None) -> bool:
        cfg = self.cfg
        init_scores = [0.0] * self.num_tree_per_iteration
        if grad is None or hess is None:
            for k in range(self.num_tree_per_iteration):
                init_scores[k] = self.boost_from_average(k)
            if self._aligned_eligible():
                self._log_train_path("aligned")
                return self._train_one_iter_aligned(init_scores)
            if self._aligned_mc_eligible():
                self._log_train_path("aligned-mc")
                return self._train_one_iter_aligned_mc(init_scores)
            if self._mega_fused_eligible():
                self._log_train_path("mega-fused")
                return self._train_one_iter_mega(init_scores)
            gdev, hdev = self._gradients()
        else:
            gdev = jnp.asarray(np.asarray(grad, np.float32).reshape(
                self.num_tree_per_iteration, self.num_data))
            hdev = jnp.asarray(np.asarray(hess, np.float32).reshape(
                self.num_tree_per_iteration, self.num_data))
        self._cur_grad, self._cur_hess = gdev, hdev
        self._bagging(self.iter)
        gdev, hdev = self._post_bagging_gradients(gdev, hdev)

        if self.use_fused:
            self._log_train_path("fused")
            return self._train_one_iter_fused(gdev, hdev, init_scores)
        self._log_train_path("per-tree")

        should_continue = False
        for k in range(self.num_tree_per_iteration):
            new_tree = Tree(2)
            leaf_map = {}
            if self._class_need_train[k] and self.train_data.num_features > 0:
                new_tree, leaf_map = self._dispatch_device(
                    "learner.train", self.learner.train,
                    gdev[k], hdev[k], self.bag_data_indices,
                    self.bag_data_cnt)
            if new_tree.num_leaves > 1:
                should_continue = True
                if (self.objective is not None
                        and getattr(self.objective, "is_renew_tree_output",
                                    False)):
                    scores_np = self.train_score.numpy()[k]
                    self.learner.renew_tree_output(
                        new_tree, leaf_map, self.objective, scores_np,
                        self._label_np, self._weight_np)
                new_tree.apply_shrinkage(self.shrinkage_rate)
                self._update_score(new_tree, k)
                if abs(init_scores[k]) > K_EPSILON:
                    new_tree.add_bias(init_scores[k])
                self.models.append(new_tree)
            else:
                self._append_constant_tree(k, init_scores)

        if not should_continue:
            # keep the constant first iteration, drop later no-split ones
            # (gbdt.cpp:436-444)
            if len(self.models) > self.num_tree_per_iteration:
                del self.models[-self.num_tree_per_iteration:]
            return True
        self.iter += 1
        return False

    def _log_train_path(self, path: str) -> None:
        """One-shot INFO naming the chosen per-iteration training path
        (VERDICT r5 #8). When the aligned engine was NOT chosen, name the
        first failing gate so a mis-routed run is diagnosable from the
        log alone."""
        self._iter_path = path          # per-round, telemetry reads it
        if getattr(self, "_path_logged", False):
            return
        self._path_logged = True
        from ..utils import log
        msg = f"training path: {path}"
        notes: List[str] = []
        why = None
        if path.startswith("aligned"):
            # info gate-notes: the path IS aligned, but e.g. the
            # slot-hist store spilled to HBM — a different perf regime
            # the log must name (not a fallback)
            gate_notes = getattr(self.learner, "aligned_gate_notes", None)
            if gate_notes is not None:
                try:
                    for note in gate_notes():
                        notes.append(str(note))
                        msg += f" ({note})"
                except Exception:
                    pass
            if self._will_bag() and not self._bag_on_device:
                # not a fallback either: the engine trains, but on a bag
                # the host draws and uploads at every re-bag, one round
                # in flight
                note = f"bag drawn on the host: {self._host_bag_why()}"
                notes.append(note)
                msg += f" ({note})"
        if not path.startswith("aligned"):
            gate = getattr(self.learner, "aligned_mode_gate", None)
            if gate is not None:
                try:
                    why = gate(self.objective)
                except Exception:
                    why = None
                if why is None:
                    why = self._aligned_variant_gate() \
                        or "gbdt-level eligibility (renew-output " \
                           "objective, or multi-tree class gating)"
            if why is not None:
                msg += f" (aligned engine rejected: {why})"
        self._gate_notes = notes
        log.info(msg)
        log.event("train_path", path=path, gate_notes=notes,
                  rejected=why)
        qb = int(getattr(self.learner, "quant_bits", 0) or 0)
        # quantization lives on the fused leaf-wise builders; the aligned
        # engine's packed records keep f32 gradient lanes, so under "auto"
        # an aligned route means the oracle ran
        active = qb > 0 and not path.startswith("aligned")
        if active:
            log.event("quant_hist", bits=qb,
                      dtype="int8" if qb == 8 else "int16", reason=None)
        elif str(self.cfg.tpu_quant_hist).lower() != "off":
            reason = getattr(self.learner, "_quant_why", None) \
                or f"{path} path keeps f32 payloads"
            log.event("quant_hist", bits=0, dtype="f32", reason=reason)

    def _note_aligned_fallback(self, eng, why: str) -> None:
        """Count an aligned exact-replay fallback on the engine and
        surface it on the structured channel; the ledger folds the
        counter delta into the next round record."""
        from ..utils import log
        eng.fallbacks = getattr(eng, "fallbacks", 0) + 1
        log.event("aligned_fallback", count=int(eng.fallbacks), why=why)

    def _append_constant_tree(self, k: int, init_scores) -> Tree:
        """Constant tree carrying the init score (gbdt.cpp:413-433): only the
        first iteration's constant trees hold an output; later no-split
        iterations append blanks."""
        t = Tree(2)
        if len(self.models) < self.num_tree_per_iteration:
            if not self._class_need_train[k] and self.objective is not None:
                output = self.objective.boost_from_score(k)
            else:
                output = init_scores[k]
            t.as_constant_tree(output)
            if abs(output) > K_EPSILON:
                self.train_score.add_constant(output, k)
                for su in self.valid_scores:
                    su.add_constant(output, k)
        self.models.append(t)
        return t

    # ------------------------------------------------------------------
    def _apply_record_to_valid_scores(self, rec, trav=None,
                                      class_id: int = 0):
        """Add one tree record's predictions to every valid-set score
        (shared by the fused/mega/aligned iteration paths). A set packed
        into records takes the tree as a host tree by the record walk (a
        fallback's: cold)."""
        cfg = self.cfg
        for i, su in enumerate(self.valid_scores):
            if isinstance(su, _RecordScores):
                su.add_tree(self.learner.record_to_tree(
                    jax.device_get(rec), self.shrinkage_rate))
                continue
            if trav is None:
                trav = traversal_arrays(rec, max(cfg.num_leaves - 1, 1))
            vb = self._valid_bins(i)
            bundled = getattr(self.learner, "bundled", False)
            su.score = su.score.at[class_id].set(
                add_record_score(su.score[class_id], vb, trav,
                                 self._trav_nb, self._trav_db,
                                 self._trav_mt,
                                 jnp.float32(self.shrinkage_rate),
                                 self.learner._col_dev if bundled else None,
                                 self.learner._boff_dev if bundled else None,
                                 self.learner._bpk_dev if bundled else None))
        return trav

    def _aligned_eligible(self) -> bool:
        """Chunk-aligned pipeline (models/aligned_builder.py): the fastest
        path — persistent permuted records, Pallas partition + histogram
        kernels, gradients evaluated in permuted order. Restrictions
        mirror _mega_fused_eligible plus the learner's aligned_mode_ok
        (numerical features, pointwise single-class objective)."""
        return (self.use_fused
                and (type(self.learner) is DeviceTreeLearner
                     or getattr(self.learner, "mode", "") == "data")
                and not getattr(self, "_aligned_disabled", False)
                and self.num_tree_per_iteration == 1
                and self._class_need_train[0]
                and self.train_data.num_features > 0
                and self.objective is not None
                and not getattr(self.objective, "is_renew_tree_output",
                                False)
                and self.learner.aligned_mode_ok(self.objective)
                and self._aligned_variant_gate() is None)

    def _aligned_variant_gate(self) -> Optional[str]:
        """Why this boosting variant keeps off the aligned engine, by
        name (the `train_path` event's `rejected`), or None. The engine
        owns the score lane and computes gradients in its records, so a
        subclass that reshapes either is out unless it says how it rides
        (GOSS and DART do: boosting_variants.py)."""
        if (type(self).get_training_score is not GBDT.get_training_score
                or type(self)._post_bagging_gradients
                is not GBDT._post_bagging_gradients):
            return (f"{type(self).__name__}: custom get_training_score / "
                    "_post_bagging_gradients hooks")
        return None

    def _aligned_mc_eligible(self) -> bool:
        """Multiclass on the aligned engine: K score lanes + per-class
        grad lanes written from pre-iteration scores, one build program
        per class with deferred leaf-value application (VERDICT r3 item
        3; reference trains K trees per iteration, gbdt.cpp:415-444)."""
        return (self.use_fused
                and type(self.learner) is DeviceTreeLearner
                and not getattr(self, "_aligned_disabled", False)
                and self.num_tree_per_iteration > 1
                and all(self._class_need_train)
                and self.train_data.num_features > 0
                and self.objective is not None
                and not getattr(self.objective, "is_renew_tree_output",
                                False)
                and self.learner.aligned_mode_ok(self.objective)
                and self._aligned_variant_gate() is None)

    def _train_one_iter_aligned_mc(self, init_scores) -> bool:
        """One multiclass boosting iteration on the aligned engine: K
        chained class-tree dispatches (no sync), exactness resolved one
        iteration behind like the single-class path."""
        cfg = self.cfg
        K = self.num_tree_per_iteration
        eng = getattr(self, "_aligned_eng_ref", None)
        if eng is None:
            eng = self.learner.aligned_engine(
                self.objective,
                init_row_scores=self.train_score.score,
                bagged=self._will_bag(), num_class=K)
            self._aligned_eng_ref = eng
            for i in range(len(self.valid_sets)):
                self._pack_valid(i)
        self._maybe_rebag(eng)
        fmasks = [self.learner.feature_mask() for _ in range(K)]
        outs = [self._dispatch_device(
                    "engine.train_iter_mc",
                    eng.train_iter_mc, k, self.shrinkage_rate, fmasks[k])
                for k in range(K)]
        # resolve the PREVIOUS iteration while this one runs on device
        redo = self._resolve_aligned_pending_mc()
        if redo is not None:
            # an inexact class in the previous iteration: this
            # iteration's dispatches are chain-gated score no-ops —
            # rebuild the failed iteration exactly, then redispatch
            # this one on the SAME masks and bag draw
            stop = self._aligned_mc_fallback(redo)
            if stop:
                return True
            outs = [eng.train_iter_mc(k, self.shrinkage_rate, fmasks[k])
                    for k in range(K)]
        for k, (spec, ncommit, _exact, _applied) in enumerate(outs):
            self.models.append(LazyAlignedTree(
                spec, self.shrinkage_rate, init_scores[k], self.learner,
                max(cfg.num_leaves - 1, 1)))
            self._pending_numsplits.append(ncommit)
        self.iter += 1
        self._train_score_stale = True
        self._aligned_pending_mc = (
            [o[2] for o in outs], [o[0] for o in outs],
            [o[3] for o in outs], list(init_scores), fmasks,
            self.bag_data_indices, self.bag_data_cnt)
        # valid-set scores: committed-tree walks per class, gated by the
        # device-side chain flags (a later-discarded dispatch adds 0)
        for i, su in enumerate(self.valid_scores):
            sc = su.score
            for k, (spec, _nc, _ex, applied) in enumerate(outs):
                sc = eng.apply_spec_to_scores(
                    sc, k, self._valid_bins(i), spec, applied,
                    self.shrinkage_rate)
            su.score = sc
        if self.valid_scores:
            self._valid_eval_stash = [
                self._eval_dev(su.score, ms, "valid.metric")
                for su, ms in zip(self.valid_scores, self.valid_metrics)]
        if len(self._pending_numsplits) >= 16 * K:
            res = self._resolve_aligned_pending_mc()
            if res is not None:
                stop = self._aligned_mc_fallback(res)
                if stop:
                    return True
            return self._trim_trailing_empty()
        return False

    def _resolve_aligned_pending_mc(self):
        """Pull the pending multiclass iteration's exact flags (ONE
        device_get). None when clean; otherwise the pending tuple plus
        the first inexact class index, with the iteration's trees
        already discarded."""
        pending = getattr(self, "_aligned_pending_mc", None)
        if pending is None:
            return None
        self._aligned_pending_mc = None
        exact_flags = [bool(x) for x in
                       jax.device_get(jnp.stack(pending[0]))]
        if all(exact_flags):
            return None
        K = self.num_tree_per_iteration
        del self.models[-K:]
        del self._pending_numsplits[-K:]
        self.iter -= 1
        j = exact_flags.index(False)
        return pending + (j,)

    def _aligned_mc_fallback(self, info) -> bool:
        """Exact rebuild of a multiclass iteration whose class j replay
        was inexact. Classes 0..j-1 already applied (train lanes AND
        valid walks, chain gates were true at their application time):
        undo them with the committed-tree walker at -shrinkage, restore
        row scores, rebuild all K trees through the fused whole-tree
        programs on the same bag draw and feature masks, and reset the
        engine lanes + exactness chain."""
        cfg = self.cfg
        (_flags, specs, applieds, init_scores, fmasks,
         bag_idx, bag_cnt, j) = info
        K = self.num_tree_per_iteration
        eng = self._aligned_eng_ref
        self._note_aligned_fallback(eng, "multiclass inexact replay")
        self._valid_eval_stash = None
        self._train_eval_stash = None
        scores = eng.row_scores_mc_dev()               # [K, N], no pull
        train_bins = self.learner.bins_dev
        for k in range(j):
            scores = eng.apply_spec_to_scores(
                scores, k, train_bins, specs[k], applieds[k],
                -self.shrinkage_rate)
            for i, su in enumerate(self.valid_scores):
                su.score = eng.apply_spec_to_scores(
                    su.score, k, self._valid_bins(i), specs[k],
                    applieds[k], -self.shrinkage_rate)
        self.train_score.score = scores
        self._train_score_stale = False
        # exact rebuild (fused whole-tree programs, reference per-class
        # loop gbdt.cpp:415-444) on the restored pre-iteration scores
        gdev, hdev = self.objective.get_gradients(scores)
        bagged = self._will_bag() and bag_idx is not None
        for k in range(K):
            if bagged:
                idxs, count = self.learner.init_root_partition(
                    bag_idx, bag_cnt)
                idxs, rec = self.learner.train(gdev[k], hdev[k], idxs,
                                               count, fmasks[k])
            else:
                idxs, rec = self.learner.train_fresh(gdev[k], hdev[k],
                                                     fmasks[k])
            lazy = LazyTree(rec, self.shrinkage_rate, init_scores[k],
                            self.learner, max(cfg.num_leaves - 1, 1))
            self.models.append(lazy)
            trav = traversal_arrays(rec, max(cfg.num_leaves - 1, 1))
            self.train_score.score = self.train_score.score.at[k].set(
                self.learner.add_score(self.train_score.score[k], trav,
                                       self.shrinkage_rate))
            self._apply_record_to_valid_scores(rec, trav=trav,
                                               class_id=k)
            self._pending_numsplits.append(rec.num_splits)
        eng.reset_mc(self.train_score.score)
        self.iter += 1
        if len(self._pending_numsplits) >= 16 * K:
            return self._trim_trailing_empty()
        return False

    def _train_one_iter_aligned(self, init_scores) -> bool:
        """One boosting iteration on the aligned engine. The engine owns
        the training scores (a record lane, permuted); train_score is
        synced lazily via _sync_train_score().

        PIPELINED: the exactness flag of iteration i-1 is pulled AFTER
        dispatching iteration i, hiding the host round-trip behind device compute. This is safe
        because an inexact program leaves the score lane untouched, so
        the speculatively-dispatched successor deterministically
        rebuilds the same tree and is discarded along with it."""
        cfg = self.cfg
        eng = getattr(self, "_aligned_eng_ref", None)
        if eng is None:
            eng = self.learner.aligned_engine(
                self.objective,
                init_row_scores=self.train_score.score[0],
                bagged=self._will_bag(),
                bag_multiplier=self._bag_multiplier,
                bag_device=self._bag_on_device)
            self._aligned_eng_ref = eng
            for i in range(len(self.valid_sets)):
                self._pack_valid(i)
        stash = getattr(self, "_aligned_next", None)
        if stash is not None:
            # this iteration was dispatched EAGERLY at the end of the
            # previous call (before its blocking metric eval), keeping
            # the device busy through per-iteration valid evals
            self._aligned_next = None
            out, fmask, _rng_snap = stash
            sample = self._aligned_sample
        else:
            self._maybe_rebag(eng)
            sample = self._aligned_sample
            fmask = self.learner.feature_mask()
            out = self._dispatch_aligned(eng, fmask, sample)
        # resolve PREVIOUS iterations while this one runs on device.
        # With metric rounds / bagging this checks the one pending round
        # (depth 1); on the pure training loop the flags accumulate and
        # are pulled in ONE batched device_get every
        # _aligned_pipeline_depth() rounds — no per-round blocking sync
        redo = self._resolve_aligned_pending(final=False)
        if redo is not None:
            if redo[0] == "caught_up":
                # an older queued round was inexact: it was rebuilt
                # exactly and its successors replayed inside the
                # resolve; only the current dispatch needs a redo
                if redo[1]:
                    return True
                out = self._dispatch_aligned(eng, fmask, sample)
            else:
                # previous tree was inexact: the current dispatch rebuilt
                # the same (failed) tree on unchanged scores — discard
                # it, grow the failed tree exactly, then dispatch this
                # iteration fresh
                self._note_aligned_fallback(
                    eng, "speculative successor discarded")
                stop = self._aligned_fallback_iter(*redo[1:])
                if stop:
                    return True
                out = self._dispatch_aligned(eng, fmask, sample)
        spec, ncommit_dev, exact_dev, applied_dev = out
        self._train_score_stale = True
        lazy = LazyAlignedTree(spec, self.shrinkage_rate, init_scores[0],
                               self.learner, max(cfg.num_leaves - 1, 1))
        self.models.append(lazy)
        self._pending_numsplits.append(ncommit_dev)
        self.iter += 1
        # the bag draw is stashed with the pending iteration: a fallback
        # must rebuild tree i on the SAME bag mask the device build used,
        # not on the next iteration's freshly-resampled one. A sample
        # drawn on the device (GOSS) is stashed as what makes it again,
        # [7], with its device counters, [8]; it has no indices here
        q = getattr(self, "_aligned_pending", None) or []
        q.append((exact_dev, list(init_scores),
                  fmask if fmask is None else fmask.copy(),
                  self.bag_data_indices, self.bag_data_cnt,
                  # [5], [6]: the spec, whose counters ride the flag
                  # pull, and the iteration they are recorded under
                  spec, self.iter - 1, sample, self._aligned_sample_stats))
        self._aligned_pending = q
        # valid-set scores: walk the committed tree ON DEVICE from the
        # spec, still pipelined — the walk is gated by the program's own
        # applied flag, so a dispatch the host later discards (inexact
        # predecessor / fallback) contributed exactly 0 and the exact
        # fallback's host application stays correct. A set packed into
        # records takes the tree, and what `sample` did to earlier trees,
        # in ONE `walk_pass`; one walked by rows takes each by an XLA walk
        walks = self._aligned_valid_walks(eng, sample)
        rows = passes = 0
        for i, su in enumerate(self.valid_scores):
            if isinstance(su, _RecordScores):
                if lazy.walk is None:
                    lazy.walk = eng.walk_tree_of_spec(spec, "valid.walk")
                passes += su.walk([(lazy.walk, self.shrinkage_rate, 0.0)]
                                  + walks, applied_dev)
                rows += su.num_data
                continue
            # the whole [K, Nv] buffer is donated and updated in place
            # at lane 0 — no gather/scatter copy pair per valid set
            su.score = eng.apply_spec_to_scores(
                su.score, 0, self._valid_bins(i), spec,
                applied_dev, self.shrinkage_rate)
            for w in walks:
                su.score = eng.walk_rows(su.score, 0, self._valid_bins(i),
                                         *w, applied_dev, 1.0)
        if passes:
            q[-1][8].update(valid_rows_walked=rows,
                            valid_walk_passes=passes)
        if self.valid_scores:
            # queue the device metric programs for THIS iteration before
            # the eager next build: the device executes in queue order,
            # so eval scalars resolve right after the walks instead of
            # behind the whole next build
            self._valid_eval_stash = [
                self._eval_dev(su.score, ms, "valid.metric")
                for su, ms in zip(self.valid_scores, self.valid_metrics)]
            # train metrics likewise (valid_sets often include the train
            # set): queue device scalars over the materialized score
            # lane so per-iteration train eval doesn't have to discard
            # the eager dispatch. Only where eval_train followed each of
            # the last two updates: a caller that evaluates the training
            # set now and then (at a drain) would pay a full-N
            # materialization and metric programs for nothing
            self._train_eval_stash = None
            if (getattr(self, "_train_eval_at", None)
                    == (self.iter - 2, self.iter - 1)
                    and self.train_metrics and all(
                        type(m).eval_dev is not Metric.eval_dev
                        for m in self.train_metrics)):
                self._train_eval_stash = self._eval_dev(
                    eng.row_scores_dev()[None, :], self.train_metrics,
                    "train.metric")
            # per-iteration eval is about to BLOCK on this iteration's
            # completion; dispatch the next build now so the device never
            # idles (if training stops instead, _discard_eager undoes the
            # speculative tree's score-lane contribution AND restores the
            # column/bag sampling RNG state its preparation consumed)
            rng_snap = (self.learner._feat_rng.get_state()
                        if hasattr(self.learner, "_feat_rng") else None,
                        self._bag_rng.get_state(),
                        self.bag_data_indices, self.bag_data_cnt,
                        self._aligned_sample)
            self._maybe_rebag(eng)
            fmask_n = self.learner.feature_mask()
            self._aligned_next = (
                self._dispatch_aligned(eng, fmask_n, self._aligned_sample),
                fmask_n, rng_snap)
        if len(self._pending_numsplits) >= 16 * self.num_tree_per_iteration:
            res = self._resolve_aligned_pending(final=True)
            if res is not None and res[1]:
                return True
            return self._trim_trailing_empty()
        return False

    def _maybe_rebag(self, eng) -> None:
        """Resample on bagging_freq boundaries (gbdt.cpp:209-275; the
        engine's histograms and gradients honor the bag lane, the
        physical layout keeps ALL rows so out-of-bag rows still get
        scores). A device-drawn bag is only NAMED here: the seed comes
        off the stream at a re-bag, (seed, iteration) rides the round as
        its `sample`, and `_aligned_apply_sample` queues the draw. A
        host-drawn one (`_host_bag_why`) is drawn and its 0/1 mask
        re-ingested into the lane."""
        cfg = self.cfg
        if not self._will_bag():
            return
        redraw = self.iter % cfg.bagging_freq == 0
        if self._bag_on_device:
            if redraw:
                self._aligned_sample = (self._draw_bag_seed(), self.iter)
                self.bag_data_indices = None    # no host copy of it
                self.bag_data_cnt = self._bag_cnt_plain()
            return
        if not redraw:
            return
        self._bagging(self.iter)
        mask = np.zeros(self.num_data, np.float32)
        if self.bag_data_indices is not None:
            mask[self.bag_data_indices] = 1.0
        else:
            mask[:] = 1.0
        eng.set_bag(mask)

    def _keeps_ahead(self, eng) -> bool:
        """Whether a drain may leave the round dispatched ahead
        (`_aligned_next`) for its turn: where its one trace on the records
        is its tree's score-lane update (no parked rows walked; a variant
        whose round does more to the records says False), on one score
        lane on one chip, rows in row order."""
        return (not eng.parks and eng.num_class == 1
                and eng.axis is None and eng.ext_of_row is None)

    _recorded_ahead = None      # the iteration `_record_ahead` recorded

    def _record_ahead(self) -> None:
        """The `aligned.iter` record of the round a drain kept for its
        turn, made at the drain, where its build is over: a window that
        ends in a drain then holds the records of the builds it ran. It
        carries the validation walk its tree is queued for; its turn
        records it no more. An inexact round is left to its turn."""
        nxt = getattr(self, "_aligned_next", None)
        if nxt is None or self._recorded_ahead == self.iter:
            return
        spec, _nc, exact_dev, _applied = nxt[0]
        exact, rounds, table = jax.device_get(
            (exact_dev, spec.rounds, spec.round_stats))
        if not bool(exact):
            return
        packed = [su for su in self.valid_scores
                  if isinstance(su, _RecordScores)]
        walked = {"valid_rows_walked": sum(su.num_data for su in packed),
                  "valid_walk_passes": len(packed)} if packed else {}
        _record_aligned_iter(self.iter, rounds, table,
                             dict(self._aligned_sample_stats or {}, **walked),
                             eng=self._aligned_eng_ref)
        self._recorded_ahead = self.iter

    def _discard_eager(self) -> None:
        """Drop a speculatively-dispatched next iteration: undo its
        (gated) score-lane contribution so the engine lane is
        authoritative again. f32 add-then-subtract restore is exact to
        metric tolerance; nothing else of the dispatch is visible."""
        stash = getattr(self, "_aligned_next", None)
        if stash is None:
            return
        self._aligned_next = None
        self._recorded_ahead = None
        (spec, _nc, _ex, applied_dev), _fmask, rng_snap = stash
        eng = self._aligned_eng_ref
        eng.undo_spec_scores(spec, applied_dev, self.shrinkage_rate)
        # rewind the sampling state the eager preparation consumed so a
        # later re-dispatch draws the same mask/bag as a non-eager run
        feat_state, bag_state, bag_idx, bag_cnt, sample = rng_snap
        if feat_state is not None:
            self.learner._feat_rng.set_state(feat_state)
        self._bag_rng.set_state(bag_state)
        self._aligned_sample = sample
        self.bag_data_indices = bag_idx
        self.bag_data_cnt = bag_cnt

    # ---- a sample drawn ON THE DEVICE (`_bag_on_device`): the host
    # holds only what makes the sample again, `_aligned_sample`. Plain
    # bagging's is (seed, iteration drawn at) of the bag in force, kept
    # from one re-bag to the next and by a checkpoint; GOSS overrides all
    # three with its per-iteration seed
    _bag_multiplier = False         # the sample weights its rows (GOSS)
    _aligned_sample = None          # of the iteration about to be built
    _aligned_sample_stats = None    # its selection's device counters

    def _aligned_apply_sample(self, eng, sample, grads):
        """Queue `sample`'s selection ahead of the build; returns its
        device counters, or None. Plain bagging draws only where the
        lane holds another bag than `sample`'s: at a re-bag, and when a
        discarded round is replayed behind a successor that drew anew.
        Between draws no program touches the lane."""
        if not self._bag_on_device or sample is None:
            return None
        seed, drawn_at = sample
        if eng.bag_drawn != seed:
            cnt = self._bag_cnt_plain()
            with obs_trace.seam("bag.draw", iter=drawn_at, seed=seed,
                                cnt=cnt, freq=int(self.cfg.bagging_freq)):
                eng.bag_select(seed, cnt)
        return {"bag_kept": eng.bag_kept}

    def _aligned_fallback_sample(self, sample, bag_idx, bag_cnt, gdev, hdev):
        """(bag indices, bag count, g, h) an exact fallback trains on: a
        device-drawn bag made again in row order from its seed."""
        if self._bag_on_device and sample is not None:
            bag_idx = bag_rows(self.num_data, sample[0],
                               self._bag_cnt_plain())
            bag_cnt = len(bag_idx)
        return bag_idx, bag_cnt, gdev, hdev

    # ---- a variant that reaches back to earlier trees (DART overrides
    # all three): what it does to them rides the queued round as
    # `sample`, and host state it keeps per round is dropped with a
    # discarded round
    def _aligned_after_build(self, eng, sample, out, prev_ok) -> None:
        """Queued right behind `sample`'s build; `out` is the build's
        (spec, ncommit, exact, applied), `prev_ok` the chain flag it was
        dispatched under."""

    def _aligned_valid_walks(self, eng, sample) -> list:
        """What `sample` did to earlier trees, as a valid set takes it:
        [(WalkTree, shrinkage, bias)] to add to its scores."""
        return []

    def _aligned_forget_from(self, first_iter: int) -> None:
        """Iterations `first_iter` and later were dispatched and are
        discarded (their device work was a gated no-op)."""

    def _dispatch_aligned(self, eng, fmask, sample=None):
        grads = None
        if eng._pgrad is None:
            # non-pointwise objective (ranking): the scores leave the
            # records in the engine's external order, the gradients come
            # back in it and are re-ingested by the index lane. That
            # order is the objective's own layout where it stated one at
            # pack time, else the rows
            scores = eng.ext_scores_dev()
            if eng.ext_of_row is None:
                gd, hd = self.objective.get_gradients(scores[None, :])
                grads = (gd[0], hd[0])
            else:
                grads = self.objective.slot_gradients(scores)
        prev_ok = eng._last_exact
        self._aligned_sample_stats = dict(
            self._aligned_apply_sample(eng, sample, grads) or {},
            features_used=int(self.learner.num_real_features
                              if fmask is None else fmask.sum()))
        out = self._dispatch_device(
            "engine.train_iter",
            lambda: eng.train_iter(self.shrinkage_rate, fmask, grads=grads,
                                   boost_iter=self.iter))
        # what the build left parked, where the engine parks at all
        self._aligned_sample_stats.update(eng.park_counters)
        self._aligned_after_build(eng, sample, out, prev_ok)
        return out

    def _aligned_pipeline_depth(self) -> int:
        """How many dispatched rounds may stay unresolved before the
        host pulls their exactness flags. Per-iteration metric evals,
        host-drawn bagging (`_host_bag_why`: its mask is uploaded at
        every re-bag), and multiclass sync every round anyway, so they
        keep depth 1 (the classic one-behind pipeline). Plain bagging
        and GOSS run at the pure loop's depth: the bag is a device
        program queued ahead of a build from the record's own index (and
        score) lanes, so it needs no host sync, and each queued round
        carries the seed that makes its bag again, so recovery replays
        it as drawn. DART likewise: its
        drop set is the host's draw from `drop_seed`'s stream, known at
        dispatch, and rides the queued round. The pure training
        loop (the bench hot path) batches 8 rounds per pull: one
        device_get per 8 iterations instead of per iteration. Safe
        because an inexact round's successors are chain-gated score
        no-ops — on failure they are discarded and replayed on their
        original column draws, reproducing the depth-1 sequence
        bit-exactly (and fallbacks measure ZERO at the default
        tpu_level_spec=4.5 budget, so the recovery path is cold)."""
        if (self.valid_scores
                or (self._will_bag() and not self._bag_on_device)
                or self.num_tree_per_iteration > 1):
            return 1
        return 8

    def _resolve_aligned_pending(self, final: bool, ride=None):
        """Resolve queued speculative rounds' exactness flags (one
        batched device_get — see _aligned_pipeline_depth). `ride`: a
        one-item list whose item (device values) is pulled in that same
        device_get and put back as host values (`eval_valid`'s metrics).
        Returns:
        - None: queue not full yet, or every queued round was exact;
        - ("redo", init_scores, eng, fmask, bag_idx, bag_cnt, sample):
          `_aligned_fallback_iter`'s arguments, final=False
          and the NEWEST queued round was inexact (popped; the caller
          discards its identical in-flight dispatch, grows the round
          exactly, and re-dispatches);
        - ("caught_up", stop): final=False and an OLDER queued round was
          inexact — it was rebuilt exactly and its discarded successors
          replayed in here; the caller re-dispatches the current round;
        - ("fellback", stop): final=True and a round was inexact: the
          exact fallback (+ successor replays) already ran; `stop` is
          the stop signal."""
        q = getattr(self, "_aligned_pending", None)
        if not q:
            if ride is not None and ride[0] is not None:
                ride[0] = jax.device_get(ride[0])
            return None
        if not final and len(q) < self._aligned_pipeline_depth():
            return None
        self._aligned_pending = None
        # the one place the loop's host blocks. The per-round counters of
        # the queued programs ride the same pull as they are (no stack,
        # no concatenate: nothing here may compile a new program), and
        # so does whatever the caller hands in `ride`. One chip's flags
        # cross as one stack; a mesh's ride as they are, since a stack of
        # flags replicated over the mesh is a program of its own, for
        # every count of them
        with obs_trace.seam("train.flag_pull", iter=self.iter,
                            queued=len(q), final=final):
            flags = q[0][0] if len(q) == 1 else [p[0] for p in q]
            if len(q) > 1 and len(flags[0].sharding.device_set) < 2:
                flags = jnp.stack(flags)
            flags, stats, rode = jax.device_get((
                flags,
                [(p[5].rounds, p[5].round_stats, p[8]) for p in q],
                None if ride is None else ride[0]))
        if ride is not None:
            ride[0] = rode
        # the host's own work on what it pulled: the device idles here
        # where nothing else is queued (the drain, whose part this is)
        with obs_trace.part("train.resolve"):
            flags = [bool(v) for v in np.atleast_1d(flags)]
            for p, ok, counters in zip(q, flags, stats):
                # a discarded dispatch is rebuilt, and recorded then; one
                # a drain kept was recorded there (`_record_ahead`)
                if ok and p[6] == self._recorded_ahead:
                    self._recorded_ahead = None
                elif ok:
                    _record_aligned_iter(p[6], *counters,
                                         eng=self._aligned_eng_ref)
        if all(flags):
            return None
        j = flags.index(False)
        # round j left the score lane untouched, so trees j+1.. were
        # built on stale scores with a false chain gate: discard them
        # all along with tree j
        drop = len(q) - j
        del self.models[-drop:]
        del self._pending_numsplits[-drop:]
        self.iter -= drop
        self._aligned_forget_from(self.iter)
        eng = self._aligned_eng_ref

        def fallback_args(p):
            return (p[1], eng, p[2], p[3], p[4], p[7])
        if not final and j == len(q) - 1:
            return ("redo",) + fallback_args(q[j])
        self._note_aligned_fallback(eng, "inexact replay in pending batch")
        stop = self._aligned_fallback_iter(*fallback_args(q[j]))
        for p in q[j + 1:]:
            if stop:
                break
            stop = self._aligned_replay_round(eng, p[1], p[2], p[7])
        if final:
            return ("fellback", stop)
        return ("caught_up", stop)

    def _aligned_replay_round(self, eng, init_scores, fmask,
                              sample=None) -> bool:
        """Re-dispatch one discarded pipeline round on its ORIGINAL
        column draw and device `sample`, and resolve it synchronously.
        Only runs during batched-pipeline failure recovery (depth > 1
        implies no host-drawn bag and no valid sets, so there is no bag
        mask to restore and no valid walk to replay)."""
        spec, ncommit_dev, exact_dev, _applied = \
            self._dispatch_aligned(eng, fmask, sample)
        with obs_trace.seam("train.flag_pull", iter=self.iter, queued=1,
                            final=True):
            exact, *counters = jax.device_get(
                (exact_dev, spec.rounds, spec.round_stats,
                 self._aligned_sample_stats))
        if not bool(exact):
            self._note_aligned_fallback(eng, "inexact replay")
            self._aligned_forget_from(self.iter)
            return self._aligned_fallback_iter(init_scores, eng, fmask,
                                               sample=sample)
        _record_aligned_iter(self.iter, *counters, eng=eng)
        self._train_score_stale = True
        lazy = LazyAlignedTree(spec, self.shrinkage_rate, init_scores[0],
                               self.learner,
                               max(self.cfg.num_leaves - 1, 1))
        self.models.append(lazy)
        self._pending_numsplits.append(ncommit_dev)
        self.iter += 1
        return False

    def _aligned_fallback_iter(self, init_scores, eng, fmask,
                               bag_idx=None, bag_cnt=0,
                               sample=None) -> bool:
        # (callers guarantee no unresolved pending iteration here)
        """Exact leaf-wise tree for an iteration whose speculative build
        could not be replayed exactly (the aligned analogue of the level
        builder's fallback). `bag_idx`/`bag_cnt` = the bag draw the
        failed device build trained on; `sample` = what makes a
        device-drawn one again (the rows and g, h follow from it)."""
        cfg = self.cfg
        # any stashed metric scalars were computed on pre-fallback scores
        self._valid_eval_stash = None
        self._train_eval_stash = None
        self._sync_train_score()
        gdev, hdev = self._gradients()
        bag_idx, bag_cnt, gdev, hdev = self._aligned_fallback_sample(
            sample, bag_idx, bag_cnt, gdev, hdev)
        bagged = self._will_bag() and bag_idx is not None
        if bagged:
            # mirror the fused bagged branch: partition over the bagged
            # subset, score update via traversal (covers OOB rows too)
            idxs, count = self.learner.init_root_partition(
                bag_idx, bag_cnt)
            idxs, rec = self.learner.train(gdev[0], hdev[0], idxs, count,
                                           fmask)
        else:
            idxs, rec = self.learner.train_fresh(gdev[0], hdev[0], fmask)
        lazy = LazyTree(rec, self.shrinkage_rate, init_scores[0],
                        self.learner, max(cfg.num_leaves - 1, 1))
        self.models.append(lazy)
        if bagged:
            trav = traversal_arrays(rec, max(cfg.num_leaves - 1, 1))
            self.train_score.score = self.train_score.score.at[0].set(
                self.learner.add_score(self.train_score.score[0], trav,
                                       self.shrinkage_rate))
            self._apply_record_to_valid_scores(rec, trav=trav)
        else:
            self.train_score.score = self.learner.add_score_from_partition(
                self.train_score.score, 0, rec, idxs, self.shrinkage_rate)
            self._apply_record_to_valid_scores(rec)
        eng.set_row_scores(self.train_score.score[0])
        self._train_score_stale = False
        self._pending_numsplits.append(rec.num_splits)
        self.iter += 1
        if len(self._pending_numsplits) >= 16 * self.num_tree_per_iteration:
            return self._trim_trailing_empty()
        return False

    def _sync_train_score(self) -> None:
        """Materialize row-order training scores from the aligned engine
        (lazy: only metrics / renewal / rollback need them)."""
        eng = getattr(self, "_aligned_eng_ref", None)
        if eng is None:         # no aligned state: nothing to drain
            self._train_score_stale = False
            return
        # the drain: the last flags, then the materialise program, whose
        # result the host waits for
        with obs_trace.seam("train.drain", iter=self.iter):
            self._discard_eager()
            self._resolve_aligned_pending(final=True)
            res = self._resolve_aligned_pending_mc()
            if res is not None:
                self._aligned_mc_fallback(res)
            if getattr(self, "_train_score_stale", False):
                # the enqueue of the materialise program, the wait for
                # it, and the scores' way to the host and back
                with obs_trace.part("train.materialise"):
                    if getattr(eng, "num_class", 1) > 1:
                        self.train_score.score = jnp.asarray(
                            eng.row_scores_mc())
                    else:
                        self.train_score.score = jnp.asarray(
                            eng.row_scores())[None, :]
                self._train_score_stale = False

    def _drop_aligned(self) -> None:
        """Leave aligned mode permanently (rollback and other mutations
        the permuted engine state cannot follow)."""
        self._discard_eager()
        self._resolve_aligned_pending(final=True)
        self._sync_train_score()
        self.valid_scores = [su.unpacked() if isinstance(su, _RecordScores)
                             else su for su in self.valid_scores]
        self._aligned_disabled = True
        self._aligned_eng_ref = None
        if hasattr(self.learner, "drop_aligned_engine"):
            self.learner.drop_aligned_engine()

    # ------------------------------------------------------------------
    def _mega_fused_eligible(self) -> bool:
        """Whole-iteration single-program path: gradients + tree build +
        score update traced together (saves per-program launch latency). Requires: fused learner on a single device,
        one tree per iteration, no bagging this iteration, a jit-traceable
        objective (no host-side gradient composition like lambdarank), and
        no DART-style score reshaping."""
        return (self.cfg.tpu_fuse_iteration
                and self.use_fused
                and type(self.learner) is DeviceTreeLearner
                and self.num_tree_per_iteration == 1
                and self._class_need_train[0]
                and self.train_data.num_features > 0
                and not self._will_bag()
                ) and (
                type(self.objective).get_gradients
                is ObjectiveFunction.get_gradients
                ) and (
                type(self).get_training_score is GBDT.get_training_score
                ) and (
                type(self)._post_bagging_gradients
                is GBDT._post_bagging_gradients)

    def _will_bag(self) -> bool:
        cfg = self.cfg
        need = (cfg.bagging_freq > 0
                and (cfg.bagging_fraction < 1.0 or self._balanced_bagging))
        return bool(need)

    def _train_one_iter_mega(self, init_scores) -> bool:
        """One fused device program per boosting iteration."""
        cfg = self.cfg
        fmask = self.learner.feature_mask()
        new_score, idxs, rec = self._dispatch_device(
            "learner.train_iter_fused", self.learner.train_iter_fused,
            self.train_score.score, self.objective, self.shrinkage_rate,
            fmask)
        self.train_score.score = new_score
        lazy = LazyTree(rec, self.shrinkage_rate, init_scores[0],
                        self.learner, max(cfg.num_leaves - 1, 1))
        self.models.append(lazy)
        self._apply_record_to_valid_scores(rec)
        self._pending_numsplits.append(rec.num_splits)
        self.iter += 1
        if len(self._pending_numsplits) >= 16 * self.num_tree_per_iteration:
            return self._trim_trailing_empty()
        return False

    def _trim_trailing_empty(self) -> bool:
        """Deferred empty-tree check shared by the fused paths
        (gbdt.cpp:436-444 batched)."""
        ns = [int(x) for x in jax.device_get(self._pending_numsplits)]
        self._pending_numsplits = []
        k = self.num_tree_per_iteration
        empty_trailing = 0
        for it in range(len(ns) // k - 1, -1, -1):
            if max(ns[it * k:(it + 1) * k]) == 0:
                empty_trailing += 1
            else:
                break
        if empty_trailing and len(self.models) > k:
            drop = min(empty_trailing * k, len(self.models) - k)
            del self.models[-drop:]
            self.iter -= drop // k
            return True
        return False

    def _train_one_iter_fused(self, gdev, hdev, init_scores) -> bool:
        """Fused path: whole-tree device programs, no mid-iteration host
        syncs; empty-tree detection is deferred and batched."""
        cfg = self.cfg
        bagged = self.bag_data_indices is not None
        any_trained = False
        for k in range(self.num_tree_per_iteration):
            # fresh column sample per tree, like SerialTreeLearner
            fmask = self.learner.feature_mask()
            if not self._class_need_train[k] \
                    or self.train_data.num_features == 0:
                self._append_constant_tree(k, init_scores)
                # keep exactly k pending entries per iteration so the
                # batched trim and rollback arithmetic stay aligned
                self._pending_numsplits.append(0)
                continue
            any_trained = True
            if not bagged:
                # fresh identity partition created inside the fused program:
                # contiguous root histogram, no init-partition dispatch
                idxs, rec = self._dispatch_device(
                    "learner.train_fresh", self.learner.train_fresh,
                    gdev[k], hdev[k], fmask)
            else:
                idxs, count = self.learner.init_root_partition(
                    self.bag_data_indices, self.bag_data_cnt)
                idxs, rec = self._dispatch_device(
                    "learner.train", self.learner.train,
                    gdev[k], hdev[k], idxs, count, fmask)
            lazy = LazyTree(rec, self.shrinkage_rate, init_scores[k],
                            self.learner, max(cfg.num_leaves - 1, 1))
            self.models.append(lazy)
            if not bagged:
                # partition-based score update: leaf fill + one key-sort back
                # to row order (no per-level tree traversal); one fused
                # program with the score buffer donated
                self.train_score.score = \
                    self.learner.add_score_from_partition(
                        self.train_score.score, k, rec, idxs,
                        self.shrinkage_rate)
                trav = None
            else:
                # bagged: out-of-bag rows also need scores -> traversal
                trav = traversal_arrays(rec, max(cfg.num_leaves - 1, 1))
                self.train_score.score = self.train_score.score.at[k].set(
                    self.learner.add_score(self.train_score.score[k], trav,
                                           self.shrinkage_rate))
            self._apply_record_to_valid_scores(rec, trav=trav, class_id=k)
            self._pending_numsplits.append(rec.num_splits)
        if not any_trained:
            # nothing trainable this iteration: mirror the non-fused
            # immediate stop (gbdt.cpp:436-444) — keep a constant first
            # iteration, drop later no-op ones
            k = self.num_tree_per_iteration
            del self._pending_numsplits[-k:]
            if len(self.models) > k:
                del self.models[-k:]
            return True
        self.iter += 1
        # deferred empty-tree check: one batched pull every N iterations;
        # trailing all-empty iterations are trimmed like the reference's
        # immediate stop (gbdt.cpp:436-444)
        if len(self._pending_numsplits) >= 16 * self.num_tree_per_iteration:
            return self._trim_trailing_empty()
        return False

    def materialized_models(self) -> List[Tree]:
        """Convert any LazyTree records to host Trees in ONE batched
        device->host transfer."""
        pending = getattr(self, "_aligned_pending", None) is not None
        if not pending and not any(isinstance(m, LazyTree)
                                   for m in self.models):
            return self.models
        with obs_trace.seam("train.drain", iter=self.iter):
            if pending:
                self._resolve_aligned_pending(final=True)
            lazies = [(i, m) for i, m in enumerate(self.models)
                      if isinstance(m, LazyTree)]
            if lazies:
                recs = jax.device_get([m.record for _, m in lazies])
                for (i, m), rec in zip(lazies, recs):
                    self.models[i] = m.materialize(rec)
        return self.models

    # ------------------------------------------------------------------
    def _update_score(self, tree: Tree, class_id: int) -> None:
        """reference GBDT::UpdateScore (gbdt.cpp:487-506): train scores via
        one binned traversal (covers in-bag and out-of-bag rows alike), valid
        scores likewise."""
        pred = TreePredictor([tree])
        leaves = pred.predict_binned_leaves(self.train_data.bins, self._bundle_arrays())[0]
        self.train_score.add_tree_by_leaves(
            leaves, tree.leaf_value[:tree.num_leaves], class_id)
        for ds, su in zip(self.valid_sets, self.valid_scores):
            vleaves = pred.predict_binned_leaves(ds.bins, self._bundle_arrays())[0]
            su.add_tree_by_leaves(vleaves,
                                  tree.leaf_value[:tree.num_leaves], class_id)

    def rollback_one_iter(self) -> None:
        """reference GBDT::RollbackOneIter (gbdt.cpp:450-466)."""
        if self.iter <= 0:
            return
        if getattr(self, "_aligned_eng_ref", None) is not None:
            self._drop_aligned()
        # drop the rolled-back iteration's deferred empty-tree records so the
        # batched trim stays aligned with self.models
        if self._pending_numsplits:
            del self._pending_numsplits[-self.num_tree_per_iteration:]
        self.materialized_models()
        start = len(self.models) - self.num_tree_per_iteration
        for k in range(self.num_tree_per_iteration):
            tree = self.models[start + k]
            if tree.num_leaves > 1:
                # subtract the tree's contribution (Shrinkage(-1) + AddScore)
                pred = TreePredictor([tree])
                leaves = pred.predict_binned_leaves(self.train_data.bins, self._bundle_arrays())[0]
                self.train_score.add_tree_by_leaves(
                    leaves, -tree.leaf_value[:tree.num_leaves], k)
                for ds, su in zip(self.valid_sets, self.valid_scores):
                    vleaves = pred.predict_binned_leaves(ds.bins, self._bundle_arrays())[0]
                    su.add_tree_by_leaves(
                        vleaves, -tree.leaf_value[:tree.num_leaves], k)
        del self.models[-self.num_tree_per_iteration:]
        self.iter -= 1

    # ------------------------------------------------------------------
    def eval_train(self) -> List[Tuple[str, str, float, bool]]:
        # the iterations of the last two calls: a caller that evaluates
        # the training set after every update gets its metrics queued
        # with each round (`_train_one_iter_aligned`)
        at = getattr(self, "_train_eval_at", None)
        self._train_eval_at = (None if at is None else at[1], self.iter)
        # aligned engine: evaluate from a DEVICE score view when every
        # metric supports it — the permuted->row materialization stays on
        # device instead of bouncing [N] f32 through the host
        eng = getattr(self, "_aligned_eng_ref", None)
        stash = getattr(self, "_train_eval_stash", None)
        if eng is not None and stash is not None:
            self._resolve_aligned_pending(final=True)
            st = getattr(self, "_train_eval_stash", None)
            if st is not None:      # no fallback invalidated it
                self._train_eval_stash = None
                out = []
                for m, dev in zip(self.train_metrics, st):
                    for mname, val in dev:
                        out.append(("training", mname, float(val),
                                    m.bigger_is_better))
                return out
        if (eng is not None and self.train_metrics
                and all(type(m).eval_dev is not Metric.eval_dev
                        for m in self.train_metrics)):
            # the drain, as `_sync_train_score` has it, with the scores
            # kept on the device for the metrics. A round dispatched
            # ahead of its turn stays for its turn where all it left on
            # the records is its tree's score-lane update: the scores are
            # read without it, and no finished build is thrown away
            with obs_trace.seam("train.drain", iter=self.iter):
                if not self._keeps_ahead(eng):
                    self._discard_eager()
                self._resolve_aligned_pending(final=True)
                if getattr(self, "_train_score_stale", False):
                    nxt = getattr(self, "_aligned_next", None)
                    ahead = None if nxt is None else (
                        nxt[0][0], nxt[0][3], self.shrinkage_rate)
                    with obs_trace.part("train.materialise"):
                        view = _DeviceScoreView(
                            eng.row_scores_dev(ahead)[None, :])
                    out = self._eval(view, self.train_metrics, "training")
                    self._record_ahead()
                    return out
        self._sync_train_score()
        return self._eval(self.train_score, self.train_metrics, "training")

    def eval_valid(self) -> List[Tuple[str, str, float, bool]]:
        # an inexact pending aligned iteration contributed 0 to the valid
        # scores (applied gate): resolve it NOW so the exact fallback tree
        # is applied before its metrics are recorded. The round's flag and
        # every metric scalar queued with it come in ONE pull
        ride = [getattr(self, "_valid_eval_stash", None)]
        self._valid_eval_stash = None
        fell_back = self._resolve_aligned_pending(final=True,
                                                  ride=ride) is not None
        stash = ride[0]
        out = []
        for i, (su, ms) in enumerate(zip(self.valid_scores,
                                         self.valid_metrics)):
            name = f"valid_{i}"
            if stash is not None and not fell_back:
                # metric values queued on the device with the round and
                # pulled with its flag; host-only metrics evaluate here
                scores = None
                if any(d is None for d in stash[i]):
                    scores = su.numpy()
                for m, dev in zip(ms, stash[i]):
                    pairs = (dev if dev is not None
                             else m.eval(scores, self.objective))
                    for mname, val in pairs:
                        out.append((name, mname, float(val),
                                    m.bigger_is_better))
            else:
                # fallback replaced the tree (stashed scalars were
                # computed on pre-fallback scores) — evaluate fresh
                out.extend(self._eval(su, ms, name))
        return out

    def _eval_dev(self, scores, metrics: List[Metric], phase: str) -> list:
        """Each metric's device values over `scores` (a device `[K, N]`),
        or None for one without a device implementation."""
        return [m.eval_dev(scores, self.objective, phase) for m in metrics]

    def _eval(self, su, metrics: List[Metric],
              name: str) -> List[Tuple[str, str, float, bool]]:
        if not metrics:
            return []
        # dispatch all device-capable metrics first (async), then emit in
        # the USER'S metric order — first_metric_only early stopping keys
        # on position 0 of the result list
        dev_vals = self._eval_dev(
            su.score, metrics,
            "train.metric" if name == "training" else "valid.metric")
        scores = su.numpy() if any(d is None for d in dev_vals) else None
        out = []
        for m, dev in zip(metrics, dev_vals):
            pairs = (dev if dev is not None
                     else m.eval(scores, self.objective))
            for mname, val in pairs:
                out.append((name, mname, float(val), m.bigger_is_better))
        return out

    # ------------------------------------------------------------------
    @property
    def num_iterations_trained(self) -> int:
        return self.iter

    def predict_raw(self, X: np.ndarray,
                    num_iteration: Optional[int] = None,
                    device: Optional[bool] = None) -> np.ndarray:
        """Raw scores for a dense matrix [N, F_total] -> [N, K]
        (predictor.hpp:66-115 semantics). `device=True` (or
        tpu_predict_device=on) routes through the serve engine's cached
        depth-synchronized traversal; leaf routing there is bit-exact vs
        the host walk, only the value sum runs in f32."""
        self.materialized_models()
        trees = self._trees_for(num_iteration)
        n = len(X)
        k = self.num_tree_per_iteration
        if device is None:
            device = str(getattr(self.cfg, "tpu_predict_device", "auto")
                         ).lower() in ("on", "device", "true", "1")
        if device and trees:
            from ..serve import ForestEngine
            eng = getattr(self, "_serve_eng", None)
            if eng is None:
                eng = ForestEngine(trees, num_class=k)
                self._serve_eng = eng
            else:
                eng.update(trees)
            return eng.predict(X)[0]
        from ..ops.predict import predict_raw_values
        out = np.zeros((n, k), np.float64)
        for cls in range(k):
            cls_trees = trees[cls::k]
            if cls_trees:
                out[:, cls] = predict_raw_values(cls_trees, X)
        return out

    def _trees_for(self, num_iteration: Optional[int]) -> List[Tree]:
        if num_iteration is None or num_iteration < 0:
            return self.models
        return self.models[:num_iteration * self.num_tree_per_iteration]
