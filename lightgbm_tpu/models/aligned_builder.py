"""Aligned tree builder: speculative level growth over the chunk-aligned
record pipeline (`ops/aligned.py`), with exact leaf-wise replay.

Same speculative-growth + host-replay contract as `level_builder.py` (the
reference's priority-queue leaf-wise order, `serial_tree_learner.cpp:
173-237`, is replayed exactly on the host), but the physical work per
round is three streaming passes instead of a global 11-operand sort:

1. layout (XLA): the finder's left count of every splitting block (exact:
   the histograms count in i32, `ops/aligned.py` COUNT_UNIT; the
   `count_pass` kernel counts the rows instead where that count is not
   theirs, `AlignedEngine.count_why`) -> the new chunk-aligned layout
   (left child at the parent's slot, right child at a fresh slot, every
   block's begin rounded up to a chunk).
2. `move_pass` (Pallas): stable two-way partition of every block straight
   into the new layout — 4.5 ns/row vs 18 for the sort.
3. `slot_hist_pass` (Pallas): histograms of each split's SMALLER child
   accumulated per-chunk into its slot; the larger child comes from
   parent-minus-sibling (`FeatureHistogram::Subtract`,
   feature_histogram.hpp:75).

State lives in ONE persistent [NC, W, C] i32 record matrix (bins words +
score/label/grad/hess/rid/weight lanes, `ops/aligned.py` docstring) that
stays PERMUTED across boosting iterations: gradients are elementwise in
the row dimension, so nothing is ever unpermuted on the hot path. The
score in row order is materialized lazily (metrics, model dump) via the
rid lane.

Restrictions (callers fall back to the level/leaf-wise builders — the
authoritative gate is `DeviceTreeLearner.aligned_mode_ok`): serial
parallelism, n <= 2^24 rows, <= 1020 features, NC <= 65535 chunks,
max_bin <= 256, and an objective that is either pointwise (any
missing-type/categorical feature mix, bagging and multiclass included)
or non-pointwise at >= 1M rows (where the external-gradient round-trip
amortizes; forced tpu_grow_mode=aligned bypasses the floor).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import compile_cache
from ..obs import phases
from ..obs import trace as obs_trace
from ..ops.aligned import (META_BAG, META_LABEL, META_LABEL_MASK,
                           META_RID_MASK, R_CAT,
                           R_COPY, R_DL, R_MT, R_SHIFT, _bpw_for_bits,
                           bin_bits, count_bits, count_pass, hist_counts,
                           hist_sub, lane_layout, move_pass, pack_device,
                           pack_route2, park_pass, slot_hist_pass,
                           with_counts)
from ..utils import log
from ..ops.histogram import NUM_HIST_STATS
from .device_learner import (BF_GAIN, BF_LG, BF_LH, BF_LOUT, BF_RG, BF_RH,
                             BF_ROUT, BF_W, BI_DEFLEFT, BI_FEAT, BI_ISCAT,
                             BI_LC, BI_RC, BI_THR, BI_W, LF_MAXC, LF_MINC,
                             LF_SG, LF_SH, LF_VALUE, LF_W, LI_BEGIN,
                             LI_COUNT, LI_COUNTG, LI_DEPTH, LI_W, NEG_INF,
                             TreeRecord, pack_best_payload)
from .level_builder import (SF_GAIN, SF_IVAL, SF_LOUT, SF_ROUT, SF_W,
                            SI_DEFLEFT, SI_FEAT, SI_ISCAT, SI_LC, SI_RC,
                            SI_SLOT, SI_THR, SI_W, replay_leafwise,
                            spec_slots)


# columns of AlignedSpec.round_stats, one row per while-loop round: what
# the round scheduled, summed over vectors the round's body already has
# (per shard under data-parallel, gathered to [shards, rounds, R] on every
# chip: the host records their mean and each shard's)
ROUND_STATS = (
    "chunks_split",    # live chunks routed through move_pass's compute path
    "chunks_copied",   # live chunks shifted whole by one HBM->HBM DMA
    "rows_split",      # rows of the leaves split this round
    "leaves_split",    # k, the leaves split this round
    "spill_slots",     # histogram slots flushed through the HBM spill ring
    "chunks_dead",     # chunks of no live block that still take move_pass's
                       # compute path: the chunk map hands the free tail to
                       # the last slot, and its route word says "split"
                       # (a parked chunk's says "copy": it is none of them)
)


class AlignedSpec(NamedTuple):
    """Device outputs of one aligned speculative build (small arrays)."""
    rounds: jax.Array      # i32 scalar: while-loop rounds executed
    n_exec: jax.Array      # i32 scalar
    execF: jax.Array       # f32[Sm1, SF_W]
    execI: jax.Array       # i32[Sm1, SI_W]
    execB: jax.Array       # u32[Sm1, 8]
    bestF: jax.Array       # f32[S, BF_W]
    bestI: jax.Array       # i32[S, BI_W]
    bestB: jax.Array       # u32[S, 8]
    leafF: jax.Array       # f32[S, LF_W]
    leafI: jax.Array       # i32[S, LI_W]  (LI_BEGIN in CHUNK units)
    # committed-tree view for the DEVICE valid-set walker (gbdt.cpp:
    # 487-506 without the host replay): first committed exec per slot,
    # next committed exec per exec, committed leaf value per slot
    first_c: jax.Array     # i32[S+1]
    nxt_c: jax.Array       # i32[Sm1+1]
    cover: jax.Array       # f32[S+1]
    round_stats: jax.Array  # i32[Sm1, len(ROUND_STATS)], rows < rounds
    #                         ([shards, Sm1, ...] under data-parallel)


class WalkTree(NamedTuple):
    """A tree in the compact form the record walk takes
    (`ops.aligned.walk_expand`): nodes and leaves numbered densely from
    0, every one naming its parent and the side it hangs on. Made on the
    device from a committed spec (`AlignedEngine.walk_tree_of_spec`) or
    in numpy from a host `Tree` (`walk_tree_of_host`)."""
    nodes: jax.Array       # i32[5, Np]: feature, threshold bin, default
                           # left, parent (-1: root), side (+1 left, -1)
    leaves: jax.Array      # i32[2, Lp]: parent, side
    nn: jax.Array          # i32 scalar: nodes; the leaves are nn + 1
    base: jax.Array        # f32[Lp]: leaf outputs before shrinkage, bias


def slot_in_any_map(begin, count, nc, chunk):
    """(slot_of [nc], in_any [nc]) from monotonic block begins — the
    layout-to-chunk mapping shared by the build program's chunk_maps and
    undo_spec_scores (they must agree bit-for-bit: the undo subtracts
    exactly the valmap the build added). Begins are an exclusive cumsum
    over slot ids, so the containing slot is the LAST slot with
    begin <= c: scatter one count per slot at its begin position and
    prefix-sum over chunks — O(S + nc) where the broadcast count
    (sum over [S, nc] compares) cost ~4 ms/round at S=765, NC=22k."""
    nslot = begin.shape[0]
    chunk_iota = jnp.arange(nc, dtype=jnp.int32)
    marks = jnp.zeros(nc + 1, jnp.int32).at[
        jnp.clip(begin, 0, nc)].add(1)
    slot_of = jnp.cumsum(marks[:nc]) - 1
    slot_of = jnp.clip(slot_of, 0, nslot - 1)
    nch = (count + chunk - 1) // chunk
    in_range = ((chunk_iota >= begin[slot_of])
                & (chunk_iota < begin[slot_of] + nch[slot_of])
                & (count[slot_of] > 0))
    return slot_of, in_range


def _f32(x):
    return lax.bitcast_convert_type(x, jnp.float32)


def _i32(x):
    return lax.bitcast_convert_type(x, jnp.int32)


def _add_to_lane(rec, lane: int, addend):
    """`rec` with f32 lane `lane` increased by `addend` [NC, 1 or C].

    Written over the aligned window of 8 lanes that holds the lane, not
    over the lane's own [NC, 1, C] slice: the records are tiled (8, 128)
    over (lane, row), a one-lane slice pads eightfold in that tiling, and
    XLA, to avoid that, gave the slice another layout and copied the
    WHOLE matrix into it first (4.5 GiB, 14.5 ms a tree: `copy.997`,
    PERF.md section 6, PR 30)."""
    lo = lane - lane % 8
    win = lax.slice_in_dim(rec, lo, lo + 8, axis=1)
    lanes = lax.broadcasted_iota(jnp.int32, win.shape, 1)
    win = jnp.where(lanes == lane - lo,
                    _i32(_f32(win) + addend[:, None, :]), win)
    return lax.dynamic_update_slice_in_dim(rec, win, lo, axis=1)


# AlignedEngine trace signature -> {"root": bytes, "round": bytes}: the
# histogram all-reduces of its build program, filled as it is traced
_PSUM_BYTES = {}


def hist_parts(x):
    """A finished histogram as its all-reduce sends it: the f32 g and h
    channels, and the count channel as the i32 it holds."""
    return x[..., :2], hist_counts(x)


def hist_psum(x, axis):
    """The data-parallel learner's histogram all-reduce: every shard's
    histogram of the same leaves summed over the mesh's `axis`
    (`Network::ReduceScatter` + the global histogram of upstream's
    `DataParallelTreeLearner`). The counts are summed as integers: a sum
    of the f32 that holds their bits would be no count."""
    gh, cnt = lax.psum(hist_parts(x), axis)
    return jnp.concatenate([gh, count_bits(cnt)[..., None]], axis=-1)


def replay_spec(spec_host, num_leaves):
    """Host leaf-wise replay over a pulled AlignedSpec (exec/leaf tables
    are the level builder's format, so `replay_leafwise` applies as-is).
    Deterministically identical to the on-device replay: both resolve
    gain ties to the lowest slot id, so a tree the device committed is
    reproduced exactly at export time."""
    class _V:
        n_exec = spec_host.n_exec
        execF = spec_host.execF
        execI = spec_host.execI
        execB = spec_host.execB
        bestF = spec_host.bestF
        leafI = spec_host.leafI
    return replay_leafwise(_V, num_leaves)


class AlignedEngine:
    """Persistent aligned-record training state for one Dataset.

    Owns the [NC, W, C] record matrix and the jitted per-iteration
    programs. One instance per (learner, objective) pair.
    """

    def __init__(self, learner, objective, interpret: bool = False,
                 init_row_scores=None, bagged: bool = False,
                 num_class: int = 1, bag_multiplier: bool = False,
                 bag_device: bool = False):
        self.learner = learner
        self.objective = objective
        self.cfg = learner.cfg
        self.interpret = interpret
        self.bagged = bagged
        # the bag lane holds a per-row f32 MULTIPLIER (0 = out of the
        # sample, else the weight of the row's gradients) that a device
        # program writes (`goss_select`), not a host-drawn 0/1 mask. The
        # kernels need nothing new: gradients are multiplied by the lane
        # where they are written, and a row is in the bag, and counted
        # once, where its lane is over 0.5 (`_payload_gh`)
        self.bag_multiplier = bag_multiplier
        self.bag_sampled = False     # a selection has written the lane
        # a device program writes the bag and nothing is uploaded: GOSS's
        # multipliers, or plain bagging's 0/1 draw (`bag_select`), which
        # fits the compact record's bag bit as the host's mask does
        self.bag_device = bag_device
        # what `bag_select` left: the seed of the bag the records hold,
        # which no program touches until the next draw, and its in-bag
        # row count as a device scalar
        self.bag_drawn = None
        self.bag_kept = None
        assert bagged or not bag_device
        assert bag_device or not bag_multiplier
        self.num_class = num_class
        # the chunk is the unit of the grid, the DMA, the flush and the
        # route words (destinations pack 16-bit, capping NC at 65k
        # chunks); move_pass partitions it in sub-tiles of route_tile(C)
        # rows, so the permutation matmul no longer grows with it
        from ..ops.aligned import (ROUTE_SELECTORS, ROUTE_STAGE, chunk_for,
                                   route_tile, route_unroll)
        self.C = C = chunk_for(self.cfg, learner.num_features,
                               learner.aligned_shard_rows)
        # the records packed on the device from the bins, until the
        # device holds them: what the readers of this seam need of the
        # layout rides on it
        with obs_trace.seam("aligned.pack", rows=int(learner.n)) as sm:
            cnts_all, ext_of_row, info = self._pack_device(
                learner, objective, init_row_scores, bagged, num_class)
            self.rec.block_until_ready()  # graftlint: disable=LGT002 the pack's one wait at construction, not a round-loop fence: the seam ends when the device holds the records
            nbytes = int(self.rec.nbytes) + int(cnts_all.nbytes)
            sm.attrs.update(
                pack="device", pack_blocks=info["blocks"],
                upload_bytes=info["upload_bytes"],
                bytes=nbytes, W=int(self.W), w_used=int(self.w_used),
                C=int(C), NC=int(self.NC), bits=int(self.bits),
                shards=int(self.nd), count_pass=self.count_pass,
                count_why=self.count_why,
                route_tile=route_tile(C), route_tiles=C // route_tile(C),
                route_selectors=ROUTE_SELECTORS, route_stage=ROUTE_STAGE,
                route_unroll=route_unroll(C),
                grad_layout="rows" if ext_of_row is None else "tiles",
                grad_slots=int(self.ext_n),
                # who writes the bag: a device program from the index
                # lane, or the host's mask through `set_bag`
                bag=("device" if self.bag_device else "host") if bagged
                else "none")
            if self.axis is not None:
                # each shard's own share: the rows its blocks wrote, as
                # the pack program counted them, and the bytes sent to
                # its device
                sm.attrs.update(
                    rows_by_shard=info["rows_by_shard"],
                    upload_bytes_by_shard=info["upload_bytes_by_shard"])
        self.rows_by_shard = info["rows_by_shard"]
        # the records are on the device already: what is left is the
        # ENQUEUE of the chunk counts' transfer (the span's `bytes` are
        # still the record matrix's)
        with obs_trace.seam("aligned.upload", bytes=nbytes):
            if self.axis is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                sh = NamedSharding(self.mesh, P(self.axis))
                self.cnts = jax.device_put(cnts_all, sh)
            else:
                self.cnts = jnp.asarray(cnts_all)
            # device int32[n], or None for the identity (see _pack_device)
            self.ext_of_row = (None if ext_of_row is None
                               else jnp.asarray(ext_of_row))
        from ..obs import memory as obs_memory
        obs_memory.track(
            "train/aligned_records", self,
            lambda e: int(e.rec.nbytes) + int(e.cnts.nbytes))
        self._pgrad = objective.point_grad_fn()
        if self._pgrad is not None:
            # hash/eq by signature: the point-grad closure rides into
            # move_pass/slot_hist_pass as a STATIC jit arg, and a fresh
            # closure per engine would retrace the module-level kernels
            self._pgrad = compile_cache.HashableFn(
                self._pgrad, ("pgrad", objective.trace_signature()))
        self._programs = {}
        # process-wide program identity: everything the engine's program
        # factories bake into their traces (the learner signature covers
        # config + bin metadata + mesh; the objective signature covers
        # gradient closures incl. content-hashed label/weight data)
        import os as _os
        self._trace_sig = (
            "aligned", learner.trace_signature(),
            objective.trace_signature(), self.C, self.NC, self.S,
            self.W, self.wcnt, self.w_used, self.bits,
            tuple(sorted(self.lanes.items())), self.compact, self.ext,
            self.gh_off, self.num_class, self.mc_mode, self.interpret,
            self.bagged, self.axis, self.nd, self.per_shard, self.ext_shape,
            _os.environ.get("LGBT_KCAP", ""),
            str(self.mesh) if self.mesh is not None else None)
        self._score_cache = None     # (iter_tag, np array)
        self._iter_tag = 0
        # exactness of the LAST dispatched program (device scalar): the
        # next dispatch gates its score update on it, so a successor of
        # an inexact tree is a guaranteed score no-op (see build())
        self._last_exact = self._true_flag()
        # multiclass deferred application: (spec, class_k, scale) of the
        # last dispatch, applied at the start of the NEXT dispatch (or by
        # flush_pending_apply), gated by the exactness CHAIN self._gate
        self._mc_pending = None
        self._gate = jnp.asarray(True)
        # the PARKED block (`parks`): the rows the bag leaves out lie, in
        # chunks of their own, from chunk `park_begin` to the buffer's
        # end (NC: nothing is parked), outside every round of a tree and
        # scored by a walk behind it. `cnts` counts every chunk's rows,
        # live or parked, so what reads rows where they lie sees both
        # alike. `_park_kept`: the rows in the bag under the lane as it
        # stands (a device scalar, the count of whoever wrote it), and
        # `_park_stale`: the lane was written since the last partition
        self.park_begin = jnp.int32(self.NC)
        self._park_kept = jnp.int32(self.n)
        self._park_stale = False
        self.park_counters = {}     # of the last build, device scalars

    def record_walk_why(self) -> Optional[str]:
        """Why the record walk (`walk_pass`) cannot follow this engine's
        trees, or None where it can. By what the engine is, no option:
        ONE score lane on ONE chip (`walk_block` asserts both), unbundled
        bins, numerical splits and tables that fit VMEM
        (`DART._aligned_variant_gate` names the same facts)."""
        lr = self.learner
        if self.num_class > 1:
            return "K trees an iteration: the walk follows one score lane"
        if self.axis is not None:
            return "a mesh: the walk is not sharded"
        if lr.bundled:
            return "bundled features: the walk reads unbundled bins"
        if np.any(np.asarray(lr.meta["bin_type"]) != 0):
            return "categorical splits: the walk takes numerical splits"
        if self.cfg.num_leaves > 1024:
            return "over 1,024 leaves: the walk's tables are sized for VMEM"
        return None

    @property
    def parks(self) -> bool:
        """Whether the build program parks the rows a bag leaves out: a
        bag, and trees the record walk can follow. Every other bagged
        engine moves all of its rows through every round."""
        return bool(self.bagged and self.record_walk_why() is None)

    def _true_flag(self):
        """A device True placed as the build program returns its flags:
        replicated over the mesh where there is one, so that the first
        build and every later one take operands of one sharding and
        compile once (an unplaced flag made the second build compile the
        program again)."""
        flag = jnp.asarray(True)
        if self.axis is None:
            return flag
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(flag, NamedSharding(self.mesh, P()))

    @property
    def psum_bytes(self):
        """(bytes all-reduced for the root's histogram, for one round's
        children) by the build program as it was traced, or None: one
        chip, or no build traced yet."""
        sites = _PSUM_BYTES.get(self._trace_sig) if self.axis else None
        if not sites or set(sites) != {"root", "round"}:
            return None
        return sites["root"], sites["round"]

    @property
    def count_why(self) -> Optional[str]:
        """Why every round of the build program runs `count_pass`, or
        None where the finder's left count places the rows: the
        histograms count in exact i32 at any row count, so that count is
        the rows' wherever the histogram counts every row the layout
        places. By what the engine is, no option: on a mesh the finder
        sees the all-reduced count and each shard's layout needs its own
        (data_parallel_tree_learner.cpp:251-257); a bag that is not
        parked leaves its out-of-bag rows in the rounds, and the
        histogram counts in-bag rows only (gbdt.cpp:209-275)."""
        if self.axis is not None:
            return "mesh"
        if self.bagged and not self.parks:
            return "bag, not parked"
        return None

    @property
    def count_pass(self) -> bool:
        """Whether every round of the build program runs `count_pass`.
        Fixed per engine, so it rides the `aligned.pack` seam with its
        `count_why` and is no per-round counter."""
        return self.count_why is not None

    # ------------------------------------------------------------------
    def _pack_device(self, learner, objective, init_row_scores, bagged,
                     num_class):
        """Construction's pack: choose the record layout and pack every
        shard's rows, with `init_row_scores` [K, n] (host or device) in
        the score lanes, into `self.rec` on the device (`pack_device`,
        block by block; each shard [nc_data + S + 2, W, C], the fresh
        chunks zero). Returns (cnts_all numpy, ext_of_row, the pack's
        info)."""
        C = self.C
        bins = np.asarray(learner.ds.bins)
        # feature-parallel zero-padding only; under EFB bundling
        # ds.bins holds the [N, G] bundled storage whose column count
        # LEGITIMATELY differs from the feature count (bundling is
        # serial-gated, so the two conditions never overlap)
        self.ncols = bins.shape[1]
        if (not learner.bundled
                and learner.num_features != learner.num_real_features):
            self.ncols += learner.num_features - learner.num_real_features
        pack_max_bin = (learner.hist_bins if learner.bundled
                        else learner.max_bin_global)
        label = objective._label_np if objective._label_np is not None \
            else np.zeros(learner.n, np.float32)
        weight = objective._weight_np
        # COMPACT record layout (ops/aligned.py lane_layout): pointwise
        # unweighted objectives with 0/1 labels at max_bin <= 64 pack
        # 6-bit bins 5/word, drop the grad/hess/label/weight lanes
        # (gradients recompute in-kernel from score+label), and ride
        # rid/label/bag in ONE meta lane — W 16 -> 8 at HIGGS shape,
        # halving every DMA and the move pass's route matmul
        lab01 = label is not None and np.all((np.asarray(label) == 0)
                                             | (np.asarray(label) == 1))
        if num_class > 1:
            # multiclass REQUIRES the compact layout (K score lanes +
            # int label in the meta lane); callers gate on
            # aligned_mode_ok which mirrors these conditions
            self.mc_mode = objective.mc_lane_mode()
            assert self.mc_mode in ("prob", "score") \
                and weight is None and learner.n <= (1 << 24) \
                and num_class <= 127
            self.compact = True
            label = np.asarray(
                objective._label_np).astype(np.int64)
        else:
            self.mc_mode = None
            # no bin-width condition: at max_bin <= 64 compact packs
            # 6-bit bins; above it keeps 8-bit words but still drops the
            # label/grad/hess/rid/weight lanes (g/h recompute in-kernel
            # from score + meta), shrinking the route matmul and killing
            # the per-iteration grad-lane pass at 255 bins
            self.compact = bool(
                objective.point_grad_fn() is not None
                and weight is None and lab01
                and learner.n <= (1 << 24)   # rid must fit 24 meta bits
                # the compact record's bag is one BIT of the meta lane
                and not self.bag_multiplier
                # tpu_force_big_n exercises the standard record and its
                # route-word repack at small n, which the compact layout
                # would otherwise shadow
                and not bool(getattr(self.cfg, "tpu_force_big_n", False)))
        with_prob = self.mc_mode == "prob"
        # external-gradient objectives (ranking) drop the label/weight
        # lanes: g/h arrive in row order with weights folded in
        self.ext = (not self.compact and num_class == 1
                    and objective.point_grad_fn() is None)
        self.gh_off = 1 if self.ext else 2
        # DATA-PARALLEL (reference DataParallelTreeLearner over a GPU
        # learner, tree_learner.cpp:13-36 + data_parallel_tree_learner
        # .cpp:149-164): rows are sharded in contiguous per-shard blocks
        # over the mesh's chunk axis; every jitted program runs under
        # shard_map with the histogram psums at the _gsum seams already
        # in the build, and split decisions replicate bit-identically
        self.axis = (learner.axis_name
                     if learner.parallel_mode == "data" else None)
        self.nd = learner.mesh_size if self.axis else 1
        self.mesh = getattr(learner, "_mesh", None)
        assert self.axis is None or self.mesh is not None, \
            "data-parallel aligned engine needs learner._mesh"
        self.n = learner.n
        # the EXTERNAL index space: what the EXT record's index lane
        # counts in, and so the order in which scores leave the records
        # for the objective and its gradients come back. Where the
        # objective states a layout of its own (`grad_layout`) it is that
        # layout's slots, and neither scores nor gradients stop over in
        # row order between trees; else it is the rows. Row-order callers
        # compose with `ext_of_row` (None: the identity). A mesh keeps
        # the rows: its materialise sums shards that own row ranges
        layout = (objective.grad_layout()
                  if self.ext and self.axis is None else None)
        self.ext_shape = (self.n,) if layout is None else layout.shape
        self.ext_n = self.n if layout is None else layout.slots
        ext_of_row = None if layout is None else layout.slot_of_row
        L = self.cfg.num_leaves
        # default speculation budget 4.5x num_leaves: late-training
        # iterations speculate far more than early ones (gains converge
        # and tie), and a 500-iteration HIGGS-shape run at 3.0 fell back
        # 106 times after iteration ~100 (each fallback costs seconds);
        # 4.5 measured ZERO fallbacks over full 500-iteration runs at
        # both 63 and 255 bins for ~5% per-iteration cost
        self.S = spec_slots(L, float(getattr(self.cfg, "tpu_level_spec",
                                             1.5)))
        import math as _math
        self.per_shard = int(_math.ceil(self.n / self.nd))
        scores = init_row_scores
        if scores is not None and scores.ndim == 1:
            scores = scores[None, :]
        self.bits = bin_bits(bins, pack_max_bin)
        # every shard's chunk grid has IDENTICAL static shape:
        # ceil(per_shard/C) data chunks + S + 2 fresh
        self.NC = (self.per_shard + C - 1) // C + self.S + 2
        self.rec, self.wcnt, self.W, cnts_all, info = pack_device(
            bins, label, weight, C, self.NC, bits=self.bits,
            cols=self.ncols, with_bag=bagged, compact=self.compact,
            num_class=num_class, with_prob=with_prob, ext=self.ext,
            index=None if ext_of_row is None else (ext_of_row, self.ext_n),
            scores=scores, mesh=self.mesh if self.axis else None,
            axis=self.axis,
            per_shard=self.per_shard)
        self.lanes, _ = lane_layout(self.wcnt, with_bag=bagged,
                                    compact=self.compact,
                                    num_class=num_class,
                                    with_prob=with_prob, ext=self.ext)
        # lanes actually carrying data (w_used <= W): only these ride
        # the move pass's route matmul
        self.w_used = max(self.lanes.values()) + 1
        return cnts_all, ext_of_row, info

    def pack_rows(self, bins, scores):
        """Other rows, a validation set's, packed as the engine packs its
        own (`pack_device`, the same chunk, lanes and bin words) into a
        block of records `[ceil(n / C), W, C]` whose score lane holds
        `scores` (f32 [1, n], row order, host or device), and its
        per-chunk counts: device arrays, with the pack's info. The rows
        never move, so record order is row order. Lanes the walk does not
        read hold what the pack puts there."""
        bins = np.asarray(bins)
        n = bins.shape[0]
        rec, wcnt, w, cnts, info = pack_device(
            bins, np.zeros(n, np.float32), None, self.C, -(-n // self.C),
            bits=self.bits, cols=self.ncols, with_bag=self.bagged,
            compact=self.compact, ext=self.ext,
            scores=jnp.reshape(scores, (1, n)))
        assert (wcnt, w) == (self.wcnt, self.W)
        return rec, jnp.asarray(cnts), info

    def block_scores(self, rec, n: int):
        """The score lane of a packed block in row order, `[1, n]`, as a
        device array: a view the metrics read (phase `valid.metric`)."""
        lane = self.lanes["score"]

        @phases.scoped("valid.metric")
        def view(rec):
            return _f32(rec[:, lane, :]).reshape(1, -1)[:, :n]
        return self._program(("valid_view", rec.shape, n), lambda: view)(rec)

    def block_set_scores(self, rec, scores):
        """A packed block with its score lane set to row-order `scores`
        (what `_ScoreUpdater`'s updates of `score` write back)."""
        lane = self.lanes["score"]
        nc, _, c = rec.shape

        def put(rec, scores):
            flat = jnp.pad(scores.reshape(-1), (0, nc * c - scores.size))
            return rec.at[:, lane, :].set(_i32(flat.reshape(nc, c)))
        return self._program(("valid_set", rec.shape), lambda: put,
                             donate=(0,))(rec, jnp.asarray(scores,
                                                           jnp.float32))

    # ------------------------------------------------------------------
    def _ext_args(self):
        """`ext_of_row` as the trailing operand of a program that maps
        between row order and the records; nothing under the identity."""
        return () if self.ext_of_row is None else (self.ext_of_row,)

    def _materialized(self, lane: str = "score", rows: bool = True):
        """One f32 lane of the records as a DEVICE array, in row order or
        (`rows=False`) in external order, shaped `ext_shape`. Under the
        identity the two are one program."""
        rows = rows or self.ext_of_row is None
        if not rows:
            key = ("mat_ext", lane)
        else:
            key = "mat" if lane == "score" else ("mat", lane)
        fn = self._program(
            key, lambda: self._materialize_program(lane, rows),
            specs=self._specs("mat") if self.axis else None)
        return fn(self.rec, self.cnts, *(self._ext_args() if rows else ()))

    def row_scores_dev(self, ahead=None):
        """Training scores in ROW order as a DEVICE array (metrics, the
        drain, a fallback). `ahead` = (spec, applied, scale) of a round
        dispatched ahead of its turn: the scores without its update,
        which the lane keeps for the round's turn (one score lane, row
        ids in the index lane, one chip)."""
        if ahead is None:
            return self._materialized()
        assert self.ext_of_row is None and self.axis is None
        spec, applied, scale = ahead
        fn = self._program("mat_ahead", self._materialize_ahead_program)
        return fn(self.rec, self.cnts, spec.leafI, spec.cover, spec.n_exec,
                  applied, jnp.float32(scale))

    def ext_scores_dev(self):
        """Training scores in EXTERNAL order as a DEVICE array: what an
        objective whose gradients are not pointwise is handed between
        trees (ranking needs query-grouped documents), and the order its
        gradients come back in (`train_iter`'s `grads`)."""
        return self._materialized(rows=False)

    # ------------------------------------------------------------------
    def _grad_lanes(self, rec):
        """g/h record lanes from the score/label(/weight) lanes —
        evaluated in PERMUTED row order (pointwise objectives only).
        COMPACT records have no grad lanes: the kernels recompute g/h
        from (score, label) at histogram time."""
        if self.compact:
            return rec
        ln = self.lanes
        score = _f32(rec[:, ln["score"], :])
        label = _f32(rec[:, ln["label"], :])
        w = (_f32(rec[:, ln["weight"], :])
             if self.objective.weight is not None else None)
        g, h = self._pgrad(score, label, w)
        if self.bagged:
            # out-of-bag rows contribute nothing to sums/histograms
            bag = _f32(rec[:, ln["bag"], :])
            g = g * bag
            h = h * bag
        rec = rec.at[:, ln["grad"], :].set(_i32(g))
        rec = rec.at[:, ln["hess"], :].set(_i32(h))
        return rec

    # ------------------------------------------------------------------
    def _mc_payload_fn(self, class_k: int):
        """In-kernel (g, h, bagmask) closure for multiclass class_k:
        reads the class's PROB lane (softmax) or SCORE lane (OVA) plus
        the meta label bits — lane indices baked in, Pallas-traceable."""
        ln = self.lanes
        meta_lane = ln["meta"]
        bagged = self.bagged
        if self.mc_mode == "prob":
            lane = ln["prob"] + class_k
            pg = self.objective.prob_point_grad()
        else:
            lane = ln["score"] + class_k
            pg = self.objective.score_point_grad(class_k)

        def fn(rows):
            v = _f32(rows[lane, :])
            meta = rows[meta_lane, :]
            is_lab = ((meta >> META_LABEL) & META_LABEL_MASK) == class_k
            g, h = pg(v, is_lab)
            bag = (((meta >> META_BAG) & 1) != 0) if bagged else None
            return g, h, bag
        return fn

    def _build_program(self, external_grads: bool = False,
                       class_k: int = 0):
        """The jitted per-iteration program: gradients + speculative tree
        build. Returns (rec_final, cnts_final, AlignedSpec). With
        external_grads the g/h lanes come from external-order arrays
        (`ext_scores_dev`) gathered by the index lane instead of the
        pointwise in-lane computation.

        MULTICLASS (self.num_class > 1, one program per class_k):
        per-class g/h lanes are written from the K score lanes FIRST
        (pre-iteration scores, the reference's gradients-once semantics,
        boosting gbdt.cpp:415-444), then the PREVIOUS dispatch's leaf
        values are applied to its class lane (deferred application: the
        valmap is defined on this program's STARTING layout), and no
        score application happens at the end — this class's valmap
        applies at the start of the next dispatch, or via
        flush_pending_apply at a sync point."""
        lr = self.learner
        cfg = self.cfg
        C, NC, S = self.C, self.NC, self.S
        Sm1 = S - 1
        # per-round split cap: K=256 unconditionally — when the move
        # kernel's [K+1, ...] hist store exceeds the VMEM budget it no
        # longer shrinks K (the old K=64 fallback cost rounds AND still
        # blew VMEM at F=137 x 255 bins); the store SPILLS to HBM and
        # streams through the kernel's 2-deep DMA staging ring instead
        from ..ops.aligned import hist_layout, walk_expand, walk_pass
        _bh = lr.hist_bins if lr.bundled else lr.max_bin_global
        import os as _os
        kcap = int(_os.environ.get("LGBT_KCAP", "0") or 0) or 256  # graftlint: disable=LGT006 sound: LGBT_KCAP is mirrored into _trace_sig, so a changed value changes the cache key
        K = min(Sm1, kcap)
        subbin, spill, slot_bytes, spill_budget = hist_layout(
            cfg, self.ncols, _bh, K)
        self.hist_subbin, self.hist_spill = subbin, spill
        if spill:
            # the move kernel's [K+1]-slot hist store lives in HBM while
            # spilling; its size is static per program, so the owner
            # claim is a constant
            from ..obs import memory as obs_memory
            obs_memory.track("train/hist_spill_store", self,
                             lambda e, b=(K + 1) * slot_bytes: b)
        if spill and not getattr(self, "_spill_logged", False):
            self._spill_logged = True
            log.info(
                f"aligned: slot-hist spilled to HBM "
                f"({slot_bytes >> 10} KB/slot x {K + 1} slots > "
                f"{spill_budget >> 20} MB VMEM budget; "
                f"2-deep DMA ring, K stays {K})")
        Lm1_commit = max(self.cfg.num_leaves - 1, 1)
        F = lr.num_features
        B = lr.max_bin_global
        # EFB bundles (io/bundling.py): the records pack the ds.bins
        # STORAGE columns — G bundle columns of <= 256 bins each (the
        # reference GPU path's own constraint, dataset.cpp:78) — so the
        # kernels histogram G x BH and routing unpacks bundle -> feature
        # bin in-kernel; per-feature histograms expand at EVAL time only
        # (expansion and parent-minus-sibling subtraction commute: both
        # are linear, and the FixHistogram term uses the leaf's own
        # totals, dataset.cpp:928-947)
        bundled = lr.bundled
        G = self.ncols
        BH = lr.hist_bins if bundled else B
        if bundled:
            col_dev = lr._col_dev
            boff_dev = lr._boff_dev
            bpk_dev = lr._bpk_dev
            emap = lr._emap_dev          # [F, B] flat indices into G*BH
            edef = lr._edef_dev          # [F, B] default-bin mask (f32)

            def expand_hist(h, sg, sh, cnt):
                """[Ks, G, BH, 3] bundle hists -> [Ks, F, B, 3]; sg/sh/
                cnt are the leaves' totals [Ks]. The counts stay exact
                i32 (`hist_counts`), for the min_data guards and the
                layout."""
                flat = h.reshape(h.shape[0], G * BH, NUM_HIST_STATS)
                safe = jnp.clip(emap, 0, G * BH - 1)
                out = jnp.where((emap >= 0)[None, :, :, None],
                                flat[:, safe], 0.0)
                totals = jnp.stack([sg, sh, jnp.zeros_like(sg)],
                                   axis=-1)                   # [Ks, 3]
                fix = totals[:, None, :] - jnp.sum(out, axis=2)
                c_out = hist_counts(out)                      # [Ks, F, B]
                fix_c = cnt[:, None] - jnp.sum(c_out, axis=2)
                c_out = c_out + jnp.where(edef[None] > 0,
                                          fix_c[:, :, None], 0)
                return with_counts(
                    out + edef[None, :, :, None] * fix[:, :, None, :],
                    c_out)
        wcnt, W = self.wcnt, self.W
        ln = self.lanes
        finder = lr.finder
        depth_limit = lr._depth_limit
        mono_dev = jnp.asarray(lr.meta["monotone"], jnp.int32)
        mono_any = lr._mono_any
        nb_np = np.asarray(lr.meta["num_bin"], np.int32)
        db_np = np.asarray(lr.meta["default_bin"], np.int32)
        mt_np = np.asarray(lr.meta["missing_type"], np.int32)
        nb_dev = jnp.asarray(nb_np)
        db_dev = jnp.asarray(db_np)
        mt_dev = jnp.asarray(mt_np)
        group = 8 if BH <= 64 else 4
        interpret = self.interpret
        bagged = self.bagged
        # bag: f32 lane (standard) or meta bit (-2, compact); -1 = none
        bag_lane = (-2 if self.compact else ln["bag"]) if bagged else -1
        bits = self.bits
        bpw = _bpw_for_bits(bits)
        K_cls = self.num_class
        multiclass = K_cls > 1
        # single-class compact: pointwise gradients inline in the
        # kernels; multiclass: per-class closure over prob/score lanes
        if multiclass:
            # signature-hashed so the static grad_fn arg of the kernel
            # jits compares equal across engine instances
            gfn = compile_cache.HashableFn(
                self._mc_payload_fn(class_k),
                ("mc_payload", self.objective.trace_signature(), class_k,
                 self.mc_mode, self.bagged))
        else:
            gfn = self._pgrad if self.compact else None
        score_lane = ln["score"] + class_k
        prev_lane_off = ln["score"] + ((class_k - 1) % K_cls)
        axis = lr.axis_name
        dp = axis is not None and lr.parallel_mode == "data"
        counted = self.count_pass
        parks = self.parks
        if parks:
            walk_tree = self._walk_tree_program("walk.tables")
            _, _, walk_w8, walk_fp = self._walk_dims()

        # bytes of each all-reduce site as traced: what `psum_bytes` reads
        psum_sites = _PSUM_BYTES.setdefault(self._trace_sig, {}) if dp \
            else None

        def _gsum(x, site):
            """A histogram all-reduced over the mesh (phase `dp.psum`);
            one chip's as it is."""
            if not dp:
                return x
            psum_sites[site] = sum(int(p.size) * p.dtype.itemsize
                                   for p in hist_parts(x))
            with phases.scope("dp.psum"):
                return hist_psum(x, axis)

        chunk_iota = jnp.arange(NC, dtype=jnp.int32)
        E_INF = Sm1 + 1     # "no exec" sentinel for replay pointers

        def device_replay(execF, execI, best_gain, n_exec):
            """The reference's leaf-wise priority queue
            (serial_tree_learner.cpp:173-237) replayed ON DEVICE over the
            speculated splits. Returns (commit [Sm1+1] bool, ncommit,
            need [S+1] bool): `commit` marks executed splits the true
            leaf-wise order takes; `need` marks slots whose NEXT split
            leaf-wise wants but speculation has not executed yet (the
            frontier). An empty `need` means the replay is EXACT."""
            eidx = jnp.arange(Sm1 + 1, dtype=jnp.int32)
            slot_e = execI[:, SI_SLOT]
            valid_e = eidx < n_exec
            first_e = jnp.full(S + 1, E_INF, jnp.int32).at[
                jnp.where(valid_e, slot_e, S)].min(
                jnp.where(valid_e, eidx, E_INF))
            # next exec of the same slot: group by (slot, e)
            key = jnp.where(valid_e, slot_e, S + 2) * (Sm1 + 2) + eidx
            order_e = jnp.argsort(key)
            so = slot_e[order_e]
            same = jnp.concatenate(
                [(so[:-1] == so[1:]) & valid_e[order_e[1:]],
                 jnp.zeros(1, bool)])
            nxt = jnp.full(Sm1 + 1, E_INF, jnp.int32).at[order_e].set(
                jnp.where(same, jnp.concatenate(
                    [order_e[1:], jnp.full(1, E_INF, jnp.int32)]), E_INF))

            active0 = jnp.zeros(S + 1, bool).at[0].set(True)
            ptr0 = jnp.full(S + 1, E_INF, jnp.int32).at[0].set(first_e[0])
            st0 = (active0, ptr0, jnp.zeros(Sm1 + 1, bool),
                   jnp.zeros(S + 1, bool), jnp.int32(0), jnp.int32(0),
                   jnp.bool_(False))

            def rcond(st):
                return (~st[6]) & (st[4] < Lm1_commit)

            def rbody(st):
                active, ptr, commit, need, ncommit, nneed, _ = st
                has_e = ptr < E_INF
                pe = jnp.clip(ptr, 0, Sm1)
                g = jnp.where(has_e, execF[pe, SF_GAIN], best_gain)
                g = jnp.where(active, g, NEG_INF)
                sl = jnp.argmax(g).astype(jnp.int32)
                gm = g[sl]
                stop = gm <= 0.0
                he = has_e[sl]
                e = pe[sl]
                take = (~stop) & he
                # BUDGET-CAPPED need marking: a frontier pop can only be
                # in the true tree if, even with every earlier-marked
                # frontier committing, the L-1 split budget is not yet
                # spent. Marks beyond that bound are provably outside the
                # final tree — suppressing them prunes wasted speculative
                # splits (execs that never commit) without touching
                # exactness: for an exact tree nneed stays 0 and the cap
                # reduces to the rcond bound.
                front = ((~stop) & ~he
                         & (ncommit + nneed < Lm1_commit))
                commit = commit.at[e].set(jnp.where(take, True, commit[e]))
                ncommit = ncommit + take.astype(jnp.int32)
                need = need.at[sl].set(jnp.where(front, True, need[sl]))
                nneed = nneed + front.astype(jnp.int32)
                # left path: slot keeps its chain; frontier pop kills it
                active = active.at[sl].set(
                    jnp.where(stop, active[sl], he))
                ptr = ptr.at[sl].set(jnp.where(take, nxt[e], ptr[sl]))
                r = jnp.clip(e + 1, 0, S)
                active = active.at[r].set(
                    jnp.where(take, True, active[r]))
                ptr = ptr.at[r].set(jnp.where(take, first_e[r], ptr[r]))
                return (active, ptr, commit, need, ncommit, nneed, stop)

            _, _, commit, need, ncommit, _, _ = lax.while_loop(
                rcond, rbody, st0)
            return commit, need, ncommit

        def chunk_maps(leafI, exists, cnts_pc=None, root_span=None,
                       park_begin=NC):
            """(slot_of_chunk [NC], cnt_of_chunk [NC], first, last) from
            the block tables. Chunks from `park_begin` on are the parked
            block's and belong to no slot: the spanning root ends there.

            Freshly-moved layouts are table-exact (full chunks, ceil'd
            last), so per-chunk counts come from the clip formula. The
            INHERITED layout at each tree's root round is sparse (blocks
            of the previous tree left gaps): there the root block must
            span ALL chunks and counts come from the carried `cnts_pc`
            (root_span = traced bool, True on the first round)."""
            begin = leafI[:, LI_BEGIN]
            count = leafI[:, LI_COUNT]
            nch = (count + C - 1) // C
            if root_span is not None:
                is_root = jnp.arange(S + 1) == 0
                nch = jnp.where(root_span & is_root, park_begin, nch)
            # slot/in-range mapping shared with undo_spec_scores (see
            # slot_in_any_map); nch here may carry the root_span
            # override, so the range check stays local
            slot_of, _ = slot_in_any_map(begin, count, NC, C)
            end_of = begin[slot_of] + nch[slot_of]
            in_any = ((chunk_iota >= begin[slot_of])
                      & (chunk_iota < end_of)
                      & exists[slot_of] & (count[slot_of] > 0))
            if parks:
                in_any = in_any & (chunk_iota < park_begin)
            if cnts_pc is None:
                cnt_of = jnp.clip(count[slot_of]
                                  - (chunk_iota - begin[slot_of]) * C, 0, C)
            else:
                cnt_of = cnts_pc
            cnt_of = jnp.where(in_any, cnt_of, 0)
            first = in_any & (chunk_iota == begin[slot_of])
            last = in_any & (chunk_iota == begin[slot_of]
                             + jnp.maximum(nch[slot_of], 1) - 1)
            return slot_of, cnt_of, first, last, in_any

        def eval_one(fmask, hist, sg, sh, cnt, minc, maxc, depth, exists):
            # the finder sums the exact counts as i32
            out = finder(hist, sg, sh, cnt, minc, maxc,
                         counts=hist_counts(hist))
            gain = jnp.where(fmask > 0, out["gain"], NEG_INF)
            gain = jnp.where((depth >= depth_limit) | ~exists,
                             jnp.full_like(gain, NEG_INF), gain)
            return pack_best_payload(out, gain)

        eval_all = jax.vmap(eval_one, in_axes=(None, 0, 0, 0, 0, 0, 0, 0, 0))

        def build(rec, cnts_pc, feature_mask_f32, scale_in, prev_ok,
                  g_rows=None, h_rows=None, pleafI=None, pcover=None,
                  pn_exec=None, pscale=None, park=None):
            with phases.scope("build.head"):
                if multiclass:
                    # deferred application of the PREVIOUS dispatch's
                    # committed leaf values to ITS class lane: the valmap is
                    # defined on THIS program's starting layout (the prev
                    # build's final layout), gated by the exactness chain
                    pbegin = pleafI[:, LI_BEGIN]
                    pcount = pleafI[:, LI_COUNT]
                    slot_p, in_range_p = slot_in_any_map(pbegin, pcount,
                                                         NC, C)
                    exists_p = jnp.arange(S + 1) <= pn_exec
                    in_any_p = in_range_p & exists_p[slot_p]
                    valmap_p = jnp.where(in_any_p & prev_ok,
                                         pcover[slot_p], 0.0)
                    sc = _f32(rec[:, prev_lane_off, :]) \
                        + valmap_p[:, None] * pscale
                    rec = rec.at[:, prev_lane_off, :].set(_i32(sc))
                    if class_k == 0 and self.mc_mode == "prob":
                        # iteration boundary: refresh the PROB lanes from
                        # the now-complete previous iteration's scores —
                        # every class of this iteration derives gradients
                        # from these pre-iteration probabilities
                        # (gbdt.cpp:415-444 computes gradients once),
                        # untouched by the same-iteration deferred score
                        # applications
                        scores = [_f32(rec[:, ln["score"] + j, :])
                                  for j in range(K_cls)]
                        m = scores[0]
                        for j in range(1, K_cls):
                            m = jnp.maximum(m, scores[j])
                        tot = jnp.zeros_like(m)
                        exps = []
                        for j in range(K_cls):
                            e = jnp.exp(scores[j] - m)
                            exps.append(e)
                            tot = tot + e
                        for j in range(K_cls):
                            rec = rec.at[:, ln["prob"] + j, :].set(
                                _i32(exps[j] / tot))
                elif external_grads:
                    assert not self.compact, \
                        "external grads need grad lanes (standard layout)"
                    with phases.scope("rank.gather"):
                        rid = jnp.clip(rec[:, ln["rid"], :], 0,
                                       self.ext_n - 1)
                        ge = g_rows.reshape(-1)[rid]
                        he = h_rows.reshape(-1)[rid]
                    if bagged:
                        bag = _f32(rec[:, ln["bag"], :])
                        ge = ge * bag
                        he = he * bag
                    rec = rec.at[:, ln["grad"], :].set(_i32(ge))
                    rec = rec.at[:, ln["hess"], :].set(_i32(he))
                else:
                    rec = self._grad_lanes(rec)

                rec_b0 = jnp.zeros_like(rec)
                root_slots = jnp.zeros(NC, jnp.int32)
                round_stats0 = jnp.zeros(
                    (Sm1 + int(parks), len(ROUND_STATS)), jnp.int32)
                # nothing parked, no partition
                park_begin, park_rounds = NC, 0
            with phases.scope("build.park"):
                if parks:
                    # ---------- the partition by the bag ----------
                    # `park` = (where the parked block begins, whether the
                    # lane was written since the last partition, the in-bag
                    # rows under it). One round of its own ahead of the tree's
                    # and a row of the round table, run where the lane is
                    # newer and a row is, or was, out of the bag: every chunk
                    # that holds rows, live or parked, is split by the bag
                    # into the live rows from chunk 0 on and the parked block
                    # at the buffer's end. A loop of no or one trip whose
                    # carry takes the rows back into the first buffer (as the
                    # copy behind an odd round count does, below): the tree's
                    # rounds then find the rows where they always do and the
                    # parked block in BOTH buffers, so no round need carry it
                    park_begin, repark, kept = park
                    rows_all = jnp.sum(cnts_pc).astype(jnp.int32)
                    go = repark & ((rows_all > kept) | (park_begin < NC))
                    park_rounds = go.astype(jnp.int32)
                    round_stats0 = round_stats0.at[0].set(jnp.where(
                        go, jnp.stack([
                            jnp.sum((cnts_pc > 0).astype(jnp.int32)),
                            jnp.int32(0), rows_all, jnp.int32(1),
                            jnp.int32(0), jnp.int32(0)]), 0))

                    def park_round(st):
                        _, rec_b, cnts, pb = park_pass(
                            st[0], st[1], jnp.int32(0), st[2], kept, C, W,
                            wcnt, bag_lane, bits=bits, w_used=self.w_used,
                            interpret=interpret)
                        return rec_b, rec_b, cnts, pb, jnp.bool_(False)

                    rec, rec_b0, cnts_pc, park_begin, _ = lax.while_loop(
                        lambda st: st[4], park_round,
                        (rec, rec_b0, cnts_pc, park_begin, go))
                    parked = chunk_iota >= park_begin
                    park_cnts = jnp.where(parked, cnts_pc, 0)
                    cnts_pc = jnp.where(parked, 0, cnts_pc)
                    # a parked chunk costs the root histogram a fetch
                    root_slots = parked.astype(jnp.int32)

            with phases.scope("build.root"):
                # ---------- root ----------
                root_hist_all = slot_hist_pass(rec, root_slots, cnts_pc, 1,
                                               G, BH, C, group, wcnt,
                                               bag_lane=bag_lane, bits=bits,
                                               grad_fn=gfn, num_class=K_cls,
                                               gh_off=self.gh_off,
                                               interpret=interpret,
                                               subbin=subbin)
                root_hist = _gsum(root_hist_all[0], "root")
                root_g = jnp.sum(root_hist[0, :, 0])
                root_h = jnp.sum(root_hist[0, :, 1])
                root_cnt_g = jnp.sum(hist_counts(root_hist[0]))
                local_cnt = jnp.sum(cnts_pc).astype(jnp.int32)

            with phases.scope("build.head"):
                leafF = jnp.zeros((S + 1, LF_W), jnp.float32)
                leafF = leafF.at[:, LF_MINC].set(-jnp.inf)
                leafF = leafF.at[:, LF_MAXC].set(jnp.inf)
                leafF = leafF.at[0, LF_SG].set(root_g)
                leafF = leafF.at[0, LF_SH].set(root_h)
                leafI = jnp.zeros((S + 1, LI_W), jnp.int32)
                leafI = leafI.at[:, LI_BEGIN].set(
                    jnp.full((S + 1,), NC, jnp.int32).at[0].set(0))
                leafI = leafI.at[0, LI_COUNT].set(local_cnt)
                leafI = leafI.at[0, LI_COUNTG].set(root_cnt_g)

                hist_store = jnp.zeros((S + 1, G, BH, NUM_HIST_STATS),
                                       jnp.float32)
                hist_store = hist_store.at[0].set(root_hist)
                execF = jnp.zeros((Sm1 + 1, SF_W), jnp.float32)
                execI = jnp.zeros((Sm1 + 1, SI_W), jnp.int32)
                execB = jnp.zeros((Sm1 + 1, 8), jnp.uint32)

            with phases.scope("build.root"):
                # root eval: slot 0 only (the old all-slots eval was pure
                # waste, and bundle expansion makes it expensive too)
                root_eh = root_hist[None]
                if bundled:
                    root_eh = expand_hist(root_eh, root_g[None], root_h[None],
                                          root_cnt_g[None])
                rF0, rI0, rB0 = eval_all(
                    feature_mask_f32, root_eh, leafF[0:1, LF_SG],
                    leafF[0:1, LF_SH], leafI[0:1, LI_COUNTG],
                    leafF[0:1, LF_MINC], leafF[0:1, LF_MAXC],
                    leafI[0:1, LI_DEPTH], jnp.ones(1, bool))
                bestF = jnp.full((S + 1, BF_W), NEG_INF,
                                 jnp.float32).at[0].set(rF0[0])
                bestI = jnp.zeros((S + 1, BI_W), jnp.int32).at[0].set(rI0[0])
                bestB = jnp.zeros((S + 1, 8), jnp.uint32).at[0].set(rB0[0])

                need0 = jnp.zeros(S + 1, bool).at[0].set(
                    bestF[0, BF_GAIN] > 0.0)
            # the round loop PING-PONGS between two record buffers: round
            # r reads the buffer `rounds % 2` names (0 = rec) and
            # move_pass writes the other, both aliased operand to output.
            # Every carried buffer is then updated in its own place; with
            # one buffer carried and a fresh one out of every round, XLA
            # copied the whole matrix back into the carry after each
            # (4.5 GiB, 14.6 ms, 13 times a tree at Criteo's size:
            # PERF.md section 6, PR 30). The second buffer is a temporary
            # of this program, in the place of that fresh output
            state = (jnp.int32(0), rec, rec_b0, cnts_pc,
                     leafF, leafI, bestF, bestI, bestB, hist_store,
                     execF, execI, execB,
                     need0, jnp.zeros(Sm1 + 1, bool), jnp.int32(0),
                     jnp.int32(0), round_stats0)

            def cond(state):
                with phases.scope("build.layout"):
                    done, need = state[0], state[13]
                    return (done < Sm1) & jnp.any(need)

            def body(state):
                (done, rec_a, rec_b, cnts_pc, leafF, leafI, bestF, bestI,
                 bestB, hist_store, execF, execI, execB, need, _commit,
                 _ncommit, rounds, round_stats) = state
                with phases.scope("build.layout"):
                    src = rounds % 2
                    s_ids = jnp.arange(S + 1, dtype=jnp.int32)
                    gains = bestF[:, BF_GAIN]
                    # K also caps per-round splits: compact hist ids must fit
                    # the VMEM-resident store (dropped needs re-offer next
                    # round via the replay)
                    budget = jnp.minimum(Sm1 - done, K)
                    # NEED-driven speculation: split exactly the slots the
                    # on-device leaf-wise replay flagged as its frontier last
                    # round — early rounds this is every positive leaf, late
                    # rounds just the deep paths still growing. The loop ends
                    # when the replay completes with an empty frontier, which
                    # certifies the replay EXACT by construction.
                    sel = need & (gains > 0.0)
                    order = jnp.argsort(-gains, stable=True)
                    sel_sorted = sel[order]
                    selrank_sorted = jnp.cumsum(
                        sel_sorted.astype(jnp.int32)) - 1
                    selrank = jnp.zeros(S + 1, jnp.int32).at[order].set(
                        selrank_sorted)
                    sel = sel & (selrank < budget)
                    k = jnp.sum(sel.astype(jnp.int32))
                    seq = done + selrank
                    right_slot = seq + 1

                    # ---- record executed splits
                    safe_seq = jnp.where(sel, seq, Sm1)
                    rowF = jnp.stack([bestF[:, BF_GAIN], bestF[:, BF_LOUT],
                                      bestF[:, BF_ROUT], leafF[:, LF_VALUE]],
                                     axis=1)
                    rowI = jnp.zeros((S + 1, SI_W), jnp.int32)
                    rowI = rowI.at[:, SI_SLOT].set(s_ids)
                    rowI = rowI.at[:, SI_FEAT].set(bestI[:, BI_FEAT])
                    rowI = rowI.at[:, SI_THR].set(bestI[:, BI_THR])
                    rowI = rowI.at[:, SI_DEFLEFT].set(bestI[:, BI_DEFLEFT])
                    rowI = rowI.at[:, SI_ISCAT].set(bestI[:, BI_ISCAT])
                    rowI = rowI.at[:, SI_LC].set(bestI[:, BI_LC])
                    rowI = rowI.at[:, SI_RC].set(bestI[:, BI_RC])
                    selF = sel[:, None]
                    execF = execF.at[safe_seq].set(
                        jnp.where(selF, rowF, execF[safe_seq]))
                    execI = execI.at[safe_seq].set(
                        jnp.where(selF, rowI, execI[safe_seq]))
                    execB = execB.at[safe_seq].set(
                        jnp.where(selF, bestB, execB[safe_seq]))

                    exists = s_ids <= done
                    slot_of, cnt_of, first, last, in_any = chunk_maps(
                        leafI, exists, cnts_pc=cnts_pc, root_span=(done == 0),
                        park_begin=park_begin)

                    # ---- left counts: the finder's left count (BI_LC, a
                    # sum of the histograms' exact i32 counts) IS the rows'
                    # left count wherever the histogram counts every row
                    # the layout places; elsewhere (`count_why`) the
                    # count pass counts them
                    feat = bestI[:, BI_FEAT]
                    scol = col_dev[feat] if bundled else feat
                    wsel_s = scol // bpw
                    shift_s = (scol % bpw) * bits
                    # route words + chunk meta (shared by the count pass and
                    # the move pass; both read the OLD layout)
                    r1_s = (jnp.clip(bestI[:, BI_THR], 0, 255)
                            | (shift_s << R_SHIFT)
                            | (bestI[:, BI_DEFLEFT] << R_DL)
                            | (mt_dev[feat] << R_MT)
                            | ((1 - sel.astype(jnp.int32)) << R_COPY)
                            | (bestI[:, BI_ISCAT] << R_CAT))
                    # compact per-round bitset table for categorical splits
                    # (tiny SMEM prefetch; row K is the never-read pad row)
                    cbits = jnp.zeros((K + 1, 8), jnp.int32).at[
                        jnp.where(sel, jnp.clip(selrank, 0, K - 1), K)].set(
                        jnp.where(sel[:, None],
                                  lax.bitcast_convert_type(bestB, jnp.int32),
                                  0)).reshape(-1)
                    r2_s = pack_route2(
                        jnp.clip(db_dev[feat], 0, 255),
                        jnp.clip(nb_dev[feat], 1, 256),
                        boff_dev[feat] if bundled else 0,
                        bpk_dev[feat] if bundled else 0)
                    r1_pc = r1_s[slot_of]
                    r2_pc = r2_s[slot_of]
                    wsel_pc = wsel_s[slot_of]
                    meta_pc = (cnt_of
                               | (first.astype(jnp.int32) << 20)
                               | (last.astype(jnp.int32) << 21))
                    if counted:
                        # the histogram count cannot drive the physical
                        # layout when it is IN-BAG only and out-of-bag rows
                        # ride the rounds (a bag that is not parked,
                        # gbdt.cpp:209-275) or GLOBAL (data-parallel: BI_LC
                        # is the psum-reduced count; the shard's local
                        # layout needs its own rows' left counts,
                        # data_parallel_tree_learner.cpp:251-257): exact i32
                        # per-shard counts come from the dedicated count
                        # pass (streams just the split-word sublane; the
                        # R_COPY bit is never read there — counted chunks
                        # are selected splits, whose copy bit is 0)
                        ks_s = jnp.where(sel, jnp.clip(selrank, 0, K - 1), K)
                        ks_pc = jnp.where(in_any & sel[slot_of],
                                          ks_s[slot_of], K)
                        phys = count_pass(rec_a, rec_b, src, r1_pc, r2_pc,
                                          meta_pc, wsel_pc, ks_pc, cbits, K, C,
                                          bits=bits, bundled=bundled,
                                          interpret=interpret)
                        left_local = jnp.where(
                            sel, phys[jnp.clip(selrank, 0, K - 1)],
                            leafI[:, LI_COUNT])
                    else:
                        left_local = jnp.where(sel, bestI[:, BI_LC],
                                               leafI[:, LI_COUNT])
                    right_local = leafI[:, LI_COUNT] - left_local

                    # ---- new layout
                    newcnt = jnp.where(exists, left_local, 0)
                    safe_right = jnp.where(sel, right_slot, S)
                    rightcnt = jnp.zeros(S + 1, jnp.int32).at[safe_right].set(
                        jnp.where(sel, right_local, 0))
                    # disjoint: right slots are fresh
                    allcnt = newcnt + rightcnt
                    nch_new = (allcnt + C - 1) // C
                    new_begin = jnp.concatenate(
                        [jnp.zeros(1, jnp.int32), jnp.cumsum(nch_new)[:-1]])

                    # ---- move destinations per chunk (NEW layout)
                    copy_pc = ~sel[slot_of] & in_any
                    # unsplit blocks shift as WHOLE chunks: per-chunk direct
                    # destination (kernel bypasses all compute with one DMA)
                    direct_pc = (new_begin[slot_of] + chunk_iota
                                 - leafI[:, LI_BEGIN][slot_of])
                    bl_s = new_begin
                    br_s = jnp.where(sel, new_begin[safe_right], new_begin)
                    bl_pc = jnp.where(copy_pc, direct_pc, bl_s[slot_of])
                    br_pc = br_s[slot_of]
                    # smaller-child hist slots (COMPACT per-round ids =
                    # selection rank, so the move pass's VMEM-resident store
                    # stays small), fused into the move pass
                    smaller_is_left = bestI[:, BI_LC] <= bestI[:, BI_RC]
                    hslot_s = jnp.where(
                        sel, jnp.clip(selrank, 0, K - 1)
                        | ((~smaller_is_left).astype(jnp.int32) << 24),
                        K)
                    hslots_pc = jnp.where(in_any, hslot_s[slot_of], K)
                    copied_pc = copy_pc
                    dead_pc = sel[slot_of] & ~in_any
                    if parks:
                        # a parked chunk is a copy chunk of no row: a grid
                        # step, and nothing of the split path (the chunk map
                        # hands the free tail to the last slot, whose route
                        # word may say "split"). On a tree that began with no
                        # partition the second buffer, a temporary of this
                        # program, lacks the parked block: its chunks ride
                        # the first round as whole-chunk copies to their own
                        # place, and either buffer may end the tree
                        r1_pc = jnp.where(parked, 1 << R_COPY, r1_pc)
                        ride = (parked & (park_cnts > 0) & (rounds == 0)
                                & (park_rounds == 0))
                        meta_pc = jnp.where(ride, park_cnts, meta_pc)
                        bl_pc = jnp.where(ride, chunk_iota, bl_pc)
                        copied_pc = copy_pc | ride
                        dead_pc = dead_pc & ~parked
                    # ---- what this round schedules (ROUND_STATS order): the
                    # kernel's split path runs wherever the chunk's route word
                    # has the copy bit clear, live block or not, and a
                    # spilling store is flushed once per split block, on its
                    # last chunk
                    split_pc = sel[slot_of]

                    def nsum(x):
                        return jnp.sum(x.astype(jnp.int32))
                    # the partition by the bag, where one ran, is row 0
                    round_stats = round_stats.at[rounds + park_rounds].set(
                        jnp.stack([
                            nsum(split_pc & in_any), nsum(copied_pc),
                            nsum(jnp.where(sel, leafI[:, LI_COUNT], 0)), k,
                            nsum(split_pc & last) if spill else jnp.int32(0),
                            nsum(dead_pc)]))
                    rec_a, rec_b, hout = move_pass(
                        rec_a, rec_b, src, r1_pc, r2_pc, bl_pc, br_pc,
                        meta_pc, wsel_pc, hslots_pc, cbits,
                        C, W, wcnt, K, G, BH, group,
                        bag_lane=bag_lane, bits=bits, grad_fn=gfn,
                        num_class=K_cls, w_used=self.w_used,
                        gh_off=self.gh_off, bundled=bundled,
                        interpret=interpret, subbin=subbin, spill=spill)

                    # ---- updated tables (begins relaid for ALL slots)
                    depth_new = leafI[:, LI_DEPTH] + 1
                    if mono_any:
                        mono = mono_dev[bestI[:, BI_FEAT]]
                        mid = (bestF[:, BF_LOUT] + bestF[:, BF_ROUT]) / 2.0
                        minc0 = leafF[:, LF_MINC]
                        maxc0 = leafF[:, LF_MAXC]
                        lmax = jnp.where(mono > 0, jnp.minimum(maxc0, mid),
                                         maxc0)
                        rmin = jnp.where(mono > 0, jnp.maximum(minc0, mid),
                                         minc0)
                        lmin = jnp.where(mono < 0, jnp.maximum(minc0, mid),
                                         minc0)
                        rmax = jnp.where(mono < 0, jnp.minimum(maxc0, mid),
                                         maxc0)
                    else:
                        lmin = rmin = leafF[:, LF_MINC]
                        lmax = rmax = leafF[:, LF_MAXC]

                    rrowF = jnp.zeros((S + 1, LF_W), jnp.float32)
                    rrowF = rrowF.at[:, LF_SG].set(bestF[:, BF_RG])
                    rrowF = rrowF.at[:, LF_SH].set(bestF[:, BF_RH])
                    rrowF = rrowF.at[:, LF_MINC].set(rmin)
                    rrowF = rrowF.at[:, LF_MAXC].set(rmax)
                    rrowF = rrowF.at[:, LF_VALUE].set(bestF[:, BF_ROUT])
                    rrowI = jnp.zeros((S + 1, LI_W), jnp.int32)
                    rrowI = rrowI.at[:, LI_BEGIN].set(new_begin[safe_right])
                    rrowI = rrowI.at[:, LI_COUNT].set(
                        jnp.where(sel, right_local, 0))
                    rrowI = rrowI.at[:, LI_COUNTG].set(bestI[:, BI_RC])
                    rrowI = rrowI.at[:, LI_DEPTH].set(depth_new)
                    leafF = leafF.at[safe_right].set(
                        jnp.where(selF, rrowF, leafF[safe_right]))
                    leafI = leafI.at[safe_right].set(
                        jnp.where(selF, rrowI, leafI[safe_right]))
                    leafF = leafF.at[:, LF_SG].set(
                        jnp.where(sel, bestF[:, BF_LG], leafF[:, LF_SG]))
                    leafF = leafF.at[:, LF_SH].set(
                        jnp.where(sel, bestF[:, BF_LH], leafF[:, LF_SH]))
                    leafF = leafF.at[:, LF_MINC].set(
                        jnp.where(sel, lmin, leafF[:, LF_MINC]))
                    leafF = leafF.at[:, LF_MAXC].set(
                        jnp.where(sel, lmax, leafF[:, LF_MAXC]))
                    leafF = leafF.at[:, LF_VALUE].set(
                        jnp.where(sel, bestF[:, BF_LOUT], leafF[:, LF_VALUE]))
                    leafI = leafI.at[:, LI_COUNT].set(
                        jnp.where(sel, left_local, leafI[:, LI_COUNT]))
                    leafI = leafI.at[:, LI_COUNTG].set(
                        jnp.where(sel, bestI[:, BI_LC], leafI[:, LI_COUNTG]))
                    leafI = leafI.at[:, LI_DEPTH].set(
                        jnp.where(sel, depth_new, leafI[:, LI_DEPTH]))
                    # full relayout: every existing slot gets its new begin
                    exists2 = s_ids <= done + k
                    leafI = leafI.at[:, LI_BEGIN].set(
                        jnp.where(exists2, new_begin, NC))

                    # ---- new per-chunk counts
                    slot_of2, cnt_of2, _, _, _ = chunk_maps(leafI, exists2)
                    cnts_pc = cnt_of2

                with phases.scope("build.eval"):
                    # ---- child histograms + eval on CHANGED slots only,
                    # [K]-compact by selection rank: the [S+1, F, B, 3] store
                    # is touched by one gather + two scatters instead of six
                    # full-store passes, and the split finder runs on the 2k
                    # changed children instead of every slot (unchanged slots'
                    # cached best split cannot change). At F=137/B=256 shapes
                    # the full-store traffic dominated the round.
                    rk = jnp.arange(K, dtype=jnp.int32)
                    valid_rk = rk < jnp.minimum(k, K)
                    # slot_l[r] = tree slot of selection rank r (pad -> S, the
                    # dump slot: right children cap at S-1 so S is never live)
                    idx_sc = jnp.where(sel, jnp.clip(selrank, 0, K - 1), K)
                    slot_l = jnp.full(K + 1, S, jnp.int32).at[idx_sc].set(
                        jnp.where(sel, s_ids, S))[:K]
                    slot_r = jnp.where(valid_rk, done + rk + 1, S)
                    sm_k = _gsum(hout, "round")             # [K, F, B, 3]
                    parent_k = hist_store[slot_l]
                    lg_k = hist_sub(parent_k, sm_k)
                    sil_k = smaller_is_left[slot_l][:, None, None, None]
                    left_k = jnp.where(sil_k, sm_k, lg_k)
                    right_k = jnp.where(sil_k, lg_k, sm_k)
                    v4 = valid_rk[:, None, None, None]
                    hist_store = hist_store.at[slot_l].set(
                        jnp.where(v4, left_k, parent_k))
                    # pad ranks target S with the old store row (parent_k of a
                    # pad IS hist_store[S]) -> consistent duplicate writes
                    hist_store = hist_store.at[slot_r].set(
                        jnp.where(v4, right_k, parent_k))

                    # children stats for the finder ([K] gathers, all tiny)
                    dep_k = depth_new[slot_l]
                    left_e, right_e = left_k, right_k
                    if bundled:
                        left_e = expand_hist(
                            left_k, bestF[slot_l, BF_LG],
                            bestF[slot_l, BF_LH], bestI[slot_l, BI_LC])
                        right_e = expand_hist(
                            right_k, bestF[slot_l, BF_RG],
                            bestF[slot_l, BF_RH], bestI[slot_l, BI_RC])
                    lF, lI, lB = eval_all(
                        feature_mask_f32, left_e, bestF[slot_l, BF_LG],
                        bestF[slot_l, BF_LH], bestI[slot_l, BI_LC],
                        lmin[slot_l], lmax[slot_l], dep_k, valid_rk)
                    rF, rI, rB = eval_all(
                        feature_mask_f32, right_e, bestF[slot_l, BF_RG],
                        bestF[slot_l, BF_RH], bestI[slot_l, BI_RC],
                        rmin[slot_l], rmax[slot_l], dep_k, valid_rk)
                    vK = valid_rk[:, None]
                    bestF = bestF.at[slot_l].set(
                        jnp.where(vK, lF, bestF[slot_l]))
                    bestI = bestI.at[slot_l].set(
                        jnp.where(vK, lI, bestI[slot_l]))
                    bestB = bestB.at[slot_l].set(
                        jnp.where(vK, lB, bestB[slot_l]))
                    bestF = bestF.at[slot_r].set(
                        jnp.where(vK, rF, bestF[slot_r]))
                    bestI = bestI.at[slot_r].set(
                        jnp.where(vK, rI, bestI[slot_r]))
                    bestB = bestB.at[slot_r].set(
                        jnp.where(vK, rB, bestB[slot_r]))

                with phases.scope("build.replay"):
                    # Replay-skip shortcut, at the PROVABLY equivalent
                    # threshold: with e = done + k execs, the capped replay
                    # pops at most e commits + (e + 1) frontier tips, so
                    # while 2e + 1 < L-1 the budget cap cannot bind and
                    # need == every positive slot — no replay required. (The
                    # old done+k < L-1 threshold over-asked by up to ~L/2
                    # execs in the transition rounds; past the new threshold
                    # the real budget-capped replay prunes the frontier to
                    # what the true leaf-wise order can still reach.)
                    def full_replay(_):
                        return device_replay(execF, execI, bestF[:, BF_GAIN],
                                             done + k)

                    def all_needed(_):
                        nd = (bestF[:, BF_GAIN] > 0.0) & exists2
                        return (jnp.zeros(Sm1 + 1, bool), nd, jnp.int32(0))

                    commit, need2, ncommit = lax.cond(
                        2 * (done + k) + 1 < Lm1_commit, all_needed,
                        full_replay, operand=None)

                return (done + k, rec_a, rec_b, cnts_pc, leafF, leafI,
                        bestF, bestI, bestB, hist_store, execF, execI,
                        execB, need2, commit, ncommit, rounds + 1,
                        round_stats)

            (n_exec, rec, rec_b, cnts_pc, leafF, leafI, bestF, bestI,
             bestB, _, execF, execI, execB, need_end, _commit_c,
             _ncommit_c, rounds, round_stats) = lax.while_loop(
                 cond, body, state)
            # a tree of an odd number of rounds leaves its rows in the
            # second buffer: one copy a tree, at most, where the carried
            # single buffer cost one a round. A loop of no or one trip and
            # no `lax.cond`: a loop's carry is overwritten in its place,
            # where a conditional's result is a third buffer that both
            # branches copy into (4.5 GiB more, and a copy on every tree)
            with phases.scope("build.copy_back"):
                rec, _, _ = lax.while_loop(
                    lambda st: st[2] == 1,
                    lambda st: (st[1], st[1], jnp.int32(0)),
                    (rec, rec_b, rounds % 2))
            rounds = rounds + park_rounds   # the partition is a round too
            with phases.scope("build.replay"):
                # authoritative final replay: the in-loop replay may have been
                # skipped on the last round (all_needed shortcut), and a tree
                # that stops growing early must still commit its real splits
                commit, need_fin, ncommit = device_replay(
                    execF, execI, bestF[:, BF_GAIN], n_exec)
                exact = ~jnp.any(need_fin)

            with phases.scope("build.tail"):
                # ---- committed cover value per slot (host _value_map twin,
                # the reference's leaf outputs applied through the finer
                # physical partition) — sequential over execs, tiny
                def cov_step(e, cov):
                    sl = execI[e, SI_SLOT]
                    live = e < n_exec
                    com = commit[e] & live
                    parent = cov[sl]
                    newp = jnp.where(com, execF[e, SF_LOUT], parent)
                    cov = cov.at[sl].set(newp)
                    child = jnp.where(com, execF[e, SF_ROUT], parent)
                    r = jnp.clip(e + 1, 0, S)
                    cov = cov.at[r].set(jnp.where(live, child, cov[r]))
                    return cov

                cover = lax.fori_loop(0, Sm1, cov_step,
                                      jnp.zeros(S + 1, jnp.float32))

                # ---- committed-only chains (valid-set device walker): the
                # committed tree's topology as slot-chain pointers, same
                # grouping trick as device_replay but filtered to commits
                eidx_c = jnp.arange(Sm1 + 1, dtype=jnp.int32)
                slot_ec = execI[:, SI_SLOT]
                valid_c = (eidx_c < n_exec) & commit
                first_c = jnp.full(S + 1, E_INF, jnp.int32).at[
                    jnp.where(valid_c, slot_ec, S)].min(
                    jnp.where(valid_c, eidx_c, E_INF))
                key_c = jnp.where(valid_c, slot_ec, S + 2) * (Sm1 + 2) + eidx_c
                order_c = jnp.argsort(key_c)
                so_c = slot_ec[order_c]
                same_c = jnp.concatenate(
                    [(so_c[:-1] == so_c[1:]) & valid_c[order_c[1:]],
                     jnp.zeros(1, bool)])
                nxt_c = jnp.full(Sm1 + 1, E_INF, jnp.int32).at[order_c].set(
                    jnp.where(same_c, jnp.concatenate(
                        [order_c[1:], jnp.full(1, E_INF, jnp.int32)]), E_INF))

                # ---- score-lane update ON DEVICE (only when the replay is
                # exact AND the previous dispatch committed: a program
                # dispatched speculatively after an inexact predecessor will
                # be discarded by the host, so prev_ok forces it to be a
                # score no-op instead of trusting it to rebuild identically
                # on the shifted physical layout)
                applied = exact & prev_ok
                if not multiclass:
                    exists_f = jnp.arange(S + 1) <= n_exec
                    slot_f, _, _, _, in_any_f = chunk_maps(leafI, exists_f)
                    valmap = jnp.where(in_any_f & applied, cover[slot_f], 0.0)
                    rec = _add_to_lane(rec, score_lane,
                                       valmap[:, None] * scale_in)
                if parks:
                    # the parked rows' share of the same update: no chunk map
                    # names their leaf, so the committed tree is walked over
                    # their chunks where they lie (every other chunk's count
                    # is 0 here, and the kernel skips it), under the flag the
                    # live rows' update is under. GOSS's next selection reads
                    # every row's score: once a tree, behind its build
                    tree = walk_tree(execI[:Sm1], first_c, nxt_c, cover)
                    with phases.scope("walk.tables"):
                        tabs = walk_expand(
                            tree.nodes, tree.leaves, tree.nn, nb_dev,
                            db_dev, mt_dev, w8=walk_w8, bits=bits,
                            fp=walk_fp)
                    rec = walk_pass(
                        rec, park_cnts, applied.astype(jnp.int32),
                        *(t[None] for t in tabs),
                        (scale_in * tree.base)[None, :, None], chunk=C,
                        wcnt=wcnt, bits=bits, lane=score_lane,
                        interpret=interpret)
                    cnts_pc = cnts_pc + park_cnts

            if dp:
                # every shard's counters, not one shard's: [shards, Sm1, R]
                # on each chip
                with phases.scope("build.tail"):
                    round_stats = lax.all_gather(round_stats, axis)
            spec = AlignedSpec(rounds=rounds, n_exec=n_exec,
                               execF=execF[:Sm1],
                               execI=execI[:Sm1], execB=execB[:Sm1],
                               bestF=bestF[:S], bestI=bestI[:S],
                               bestB=bestB[:S], leafF=leafF[:S],
                               leafI=leafI[:S], first_c=first_c,
                               nxt_c=nxt_c, cover=cover,
                               round_stats=round_stats)
            if parks:
                return (rec, cnts_pc, spec, exact, ncommit, applied,
                        (park_begin, jnp.sum(park_cnts),
                         jnp.sum((park_cnts > 0).astype(jnp.int32)),
                         park_rounds))
            return rec, cnts_pc, spec, exact, ncommit, applied

        return build

    # ------------------------------------------------------------------
    def _program(self, key, factory, donate=(), specs=None):
        """jit (and, data-parallel, shard_map) a program factory. specs =
        (in_specs, out_specs) pytrees of PartitionSpec for the DP case;
        programs whose inputs are all replicated pass specs=None and run
        unwrapped (XLA replicates them across the mesh).

        Programs live in the process-wide registry keyed by the engine's
        trace signature, so a second engine at the same shape/config/data
        reuses the jitted callable — zero new traces. Every program body
        bumps compile_cache.note_trace() exactly once per jax trace."""
        fn = self._programs.get(key)
        if fn is None:
            def build_jit():
                inner = factory()

                def traced(*args, **kwargs):
                    compile_cache.note_trace()
                    return inner(*args, **kwargs)

                wrapped = traced
                if self.axis is not None and specs is not None:
                    wrapped = jax.shard_map(wrapped, mesh=self.mesh,
                                           in_specs=specs[0],
                                           out_specs=specs[1],
                                           check_vma=False)
                return jax.jit(wrapped, donate_argnums=donate)

            fn = compile_cache.program(
                self._trace_sig + ("prog", key), build_jit)
            self._programs[key] = fn
            return self._first_call(key, fn)
        return fn

    @staticmethod
    def _first_call(key, fn):
        """`fn` under an `aligned.program` seam: this engine's first call
        of a program is where it is traced, lowered and compiled or
        loaded from the persistent cache, all on the host before the
        execution is enqueued. Later lookups hand out `fn` itself."""
        def run(*args, **kwargs):
            traces = compile_cache.trace_count()
            before = compile_cache.persistent_cache_events()
            # what lowers the program again, should a phase table be
            # asked for: shapes and dtypes, no buffer
            phases.remember(str(key), fn, args, kwargs)
            with obs_trace.seam("aligned.program", key=str(key)) as sm:
                out = fn(*args, **kwargs)
                after = compile_cache.persistent_cache_events()
                if compile_cache.trace_count() == traces:
                    sm.attrs["cache"] = "memory"    # compiled in-process
                elif (after["hits"] > before["hits"]
                      and after["misses"] == before["misses"]):
                    sm.attrs["cache"] = "hit"
                else:       # compiled here, persistent cache wired or not
                    sm.attrs["cache"] = "miss"
            return out
        return run

    def _specs(self, kind):
        """(in_specs, out_specs) for the DP shard_map wrap of each
        program. The chunk axis of rec/cnts (and the per-shard physical
        block tables leafI) shard over the mesh; split decisions and
        exec/best tables replicate (identical global histograms on every
        shard, data_parallel_tree_learner.cpp:167-248's FromMemory
        restore made redundant by the psum)."""
        from jax.sharding import PartitionSpec as P
        ax = self.axis
        spec_out = AlignedSpec(
            rounds=P(), n_exec=P(), execF=P(), execI=P(), execB=P(), bestF=P(), bestI=P(), bestB=P(), leafF=P(),
            leafI=P(ax),
            first_c=P(), nxt_c=P(), cover=P(), round_stats=P())
        if kind == "build":
            return ((P(ax), P(ax), P(), P(), P()),
                    (P(ax), P(ax), spec_out, P(), P(), P()))
        if kind == "build_ext":
            return ((P(ax), P(ax), P(), P(), P(), P(), P()),
                    (P(ax), P(ax), spec_out, P(), P(), P()))
        if kind == "mat":
            return ((P(ax), P(ax)), P())
        if kind == "setsc":
            return ((P(ax), P()), P(ax))
        if kind == "setbag":
            return ((P(ax), P()), P(ax))
        if kind == "undo":
            return ((P(ax), P(ax), P(), P(), P(), P()), P(ax))
        raise KeyError(kind)

    def train_iter(self, scale: float,
                   feature_mask: Optional[np.ndarray] = None,
                   grads=None, boost_iter: Optional[int] = None):
        """One boosting iteration: gradients + tree build + score-lane
        update. Returns (spec, ncommit_dev, exact_dev, applied_dev) —
        ALL device values, no sync. `applied_dev` = exact & prev_ok: True
        iff this program's score-lane update actually happened (a
        dispatch following an inexact predecessor is a guaranteed no-op
        and will be discarded by the host). `grads` = (g, h) device
        arrays in external order (`ext_scores_dev`) for non-pointwise
        objectives. `boost_iter` names
        the boosting iteration on the dispatch seam (the engine's own
        dispatch count where the caller gives none)."""
        fmask = self.learner._fmask_arr(feature_mask)
        park = {"park": (self.park_begin, np.bool_(self._park_stale),
                         self._park_kept)} if self.parks else {}
        # host-side dispatch seam only — this boundary must stay free of
        # device syncs (the round loop pipelines on it): the seam times
        # the enqueue, always on, and never fences
        with obs_trace.seam("aligned.dispatch",
                            iter=self._iter_tag if boost_iter is None
                            else boost_iter):
            if grads is not None:
                fn = self._program(
                    "build_ext",
                    lambda: self._build_program(external_grads=True),
                    donate=(0, 1), specs=self._specs("build_ext")
                    if self.axis else None)
            else:
                fn = self._program("build", self._build_program,
                                   donate=(0, 1), specs=self._specs("build")
                                   if self.axis else None)
            rec, cnts, spec, exact_dev, ncommit_dev, applied_dev, *parked = \
                fn(self.rec, self.cnts, fmask, jnp.float32(scale),
                   self._last_exact, *(grads or ()), **park)
        if parked:
            # the layout advances whatever the flags say: a discarded
            # round leaves the parked rows parked, and unscored as it
            # leaves the live ones
            self.park_begin = parked[0][0]
            self.park_counters = dict(zip(
                ("rows_parked", "chunks_parked", "park_rounds"),
                parked[0][1:]))
            self._park_stale = False
        # the CHAIN, not this program's own flag: after an inexact
        # round every successor is a score no-op until the host has
        # rebuilt it (`set_row_scores`), whatever its own replay says.
        # A successor that rebuilds the same tree is inexact as well;
        # one that samples or drops anew (GOSS, DART) need not be
        self._last_exact = applied_dev
        # records AND per-chunk counts were donated (the round loop
        # ping-pongs between the donated matrix and a temporary of the
        # program, and ends in the donated one): the physical layout
        # advances either way (harmless — the next root re-reads
        # everything); the SCORE
        # lane was updated on device only when the replay was exact.
        # NOTHING is pulled here: the caller checks `exact_dev` one
        # iteration later, hiding the host round-trip behind device
        # compute (an inexact program is a deterministic score-no-op, so
        # a speculatively-dispatched successor is safely discardable).
        self.rec, self.cnts = rec, cnts
        self._iter_tag += 1
        self._score_cache = None
        return spec, ncommit_dev, exact_dev, applied_dev

    def _null_prev(self):
        """A no-op 'previous spec' for the first multiclass dispatch:
        begins at NC so no chunk is in range -> valmap is exactly 0."""
        S = self.S
        leafI = jnp.zeros((S, LI_W), jnp.int32).at[:, LI_BEGIN].set(
            jnp.full((S,), self.NC, jnp.int32))
        return leafI, jnp.zeros(S + 1, jnp.float32), jnp.int32(0), \
            jnp.float32(0.0)

    def train_iter_mc(self, class_k: int, scale: float,
                      feature_mask: Optional[np.ndarray] = None):
        """One multiclass class-tree build (one of K dispatches per
        boosting iteration). Applies the PREVIOUS dispatch's leaf values
        (deferred, exactness-chain gated) and trains class_k's tree from
        pre-iteration scores. Returns (spec, ncommit_dev, exact_dev,
        applied_dev) — all device values, no sync; `applied_dev` is the
        chain gate under which this spec's values will apply."""
        fmask = self.learner._fmask_arr(feature_mask)
        fn = self._program(
            ("build_mc", class_k),
            lambda: self._build_program(class_k=class_k), donate=(0, 1))
        if self._mc_pending is None:
            pleafI, pcover, pn_exec, pscale = self._null_prev()
        else:
            pspec, _pk, psc = self._mc_pending
            pleafI, pcover, pn_exec, pscale = (
                pspec.leafI, pspec.cover, pspec.n_exec, jnp.float32(psc))
        # dispatch-only span (no sync — the mc chain pipelines too)
        with obs_trace.span("aligned.dispatch_mc", class_k=class_k,
                            iter=self._iter_tag):
            rec, cnts, spec, exact_dev, ncommit_dev, applied_dev = fn(
                self.rec, self.cnts, fmask, jnp.float32(scale), self._gate,
                pleafI=pleafI, pcover=pcover, pn_exec=pn_exec, pscale=pscale)
        self.rec, self.cnts = rec, cnts
        self._gate = applied_dev          # chain: g & exact
        self._mc_pending = (spec, class_k, scale)
        self._iter_tag += 1
        self._score_cache = None
        return spec, ncommit_dev, exact_dev, applied_dev

    def flush_pending_apply(self):
        """Apply the last multiclass dispatch's deferred leaf values to
        its class lane (sync points: metrics, fallback, end of
        training). The undo program's valmap math is reused with the
        sign flipped."""
        if self._mc_pending is None:
            return
        spec, class_k, scale = self._mc_pending
        self._mc_pending = None
        fn = self._program(("apply_mc", class_k),
                           lambda: self._undo_program(class_k=class_k,
                                                      sign=+1.0),
                           donate=(0,))
        self.rec = fn(self.rec, spec.leafI, spec.cover, spec.n_exec,
                      self._gate, jnp.float32(scale))
        self._score_cache = None

    def reset_mc(self, row_scores_kn):
        """Fallback reset: drop any deferred application, re-ingest
        authoritative row-order scores into ALL class lanes, reset the
        exactness chain."""
        self._mc_pending = None
        for k in range(self.num_class):
            self.set_row_scores_lane(k, row_scores_kn[k])
        self._gate = jnp.asarray(True)

    def set_row_scores_lane(self, class_k: int, row_scores):
        fn = self._program(("setsc", class_k),
                           lambda: self._set_scores_program(class_k),
                           donate=(0,),
                           specs=self._specs("setsc")
                           if self.axis else None)
        self.rec = fn(self.rec, jnp.asarray(row_scores, jnp.float32),
                      *self._ext_args())
        self._score_cache = None

    def row_scores_mc_dev(self) -> jax.Array:
        """[K, N] row-order scores as a DEVICE array (flush any
        deferred application first so the lanes are authoritative)."""
        self.flush_pending_apply()
        fn = self._program("mat_mc", self._materialize_mc_program)
        return fn(self.rec, self.cnts)

    def row_scores_mc(self) -> np.ndarray:
        return np.asarray(self.row_scores_mc_dev())

    def _materialize_mc_program(self):
        ln = self.lanes
        n, C, K = self.n, self.C, self.num_class

        @phases.scoped("drain.materialise")
        def fn(rec, cnts):
            rid = self._rid_lanes(rec).reshape(-1)
            pos = jnp.arange(C, dtype=jnp.int32)
            valid = (pos[None, :] < cnts[:, None]).reshape(-1)
            rid = jnp.where(valid & (rid < n), rid, n)
            outs = []
            for k in range(K):
                sc = _f32(rec[:, ln["score"] + k, :]).reshape(-1)
                outs.append(
                    jnp.zeros(n + 1, jnp.float32).at[rid].set(sc)[:n])
            return jnp.stack(outs)
        return fn

    def apply_spec_to_scores(self, score, lane, vbins, spec, applied,
                             scale):
        """score [K, Nv] lane `lane` += scale * committed_tree(vbins) ON
        DEVICE — the valid-set analogue of the score-lane update
        (gbdt.cpp:487-506), walking the committed-exec chains of the
        spec. Gated by `applied` (the exact & prev_ok flag): a dispatch
        the host will discard contributes exactly 0, so this can be
        dispatched pipelined with no sync. The FULL [K, Nv] buffer is
        donated and updated in place at a device-side lane index — the
        old per-lane form (`score[k]` gather in, `.at[k].set` scatter
        out) cost two full-buffer copies per valid set per round."""
        fn = self._program(("walk", vbins.shape), self._walk_program,
                           donate=(0,))
        return fn(score, jnp.int32(lane), vbins, spec.execI, spec.execB,
                  spec.first_c, spec.nxt_c, spec.cover,
                  jnp.float32(scale), applied)

    def _walk_program(self):
        lr = self.learner
        S, Sm1 = self.S, self.S - 1
        E_INF = Sm1 + 1
        nb = jnp.asarray(lr.meta["num_bin"], jnp.int32)
        db = jnp.asarray(lr.meta["default_bin"], jnp.int32)
        mt = jnp.asarray(lr.meta["missing_type"], jnp.int32)
        bundled = lr.bundled
        if bundled:
            col = lr._col_dev
            boff = lr._boff_dev
            bpk = lr._bpk_dev

        @phases.scoped("valid.walk")
        def fn(score, lane, vb, execI, execB, first_c, nxt_c, cover,
               scale, applied):
            nv = vb.shape[0]
            node0 = jnp.full(nv, first_c[0], jnp.int32)
            slot0 = jnp.zeros(nv, jnp.int32)

            def cond(st):
                return jnp.any(st[0] < E_INF)

            def body(st):
                node, slot = st
                act = node < E_INF
                e = jnp.clip(node, 0, Sm1)
                f = execI[e, SI_FEAT]
                scol = col[f] if bundled else f
                binv = jnp.take_along_axis(
                    vb, jnp.clip(scol, 0, vb.shape[1] - 1)[:, None],
                    axis=1)[:, 0].astype(jnp.int32)
                if bundled:
                    from ..ops.partition import bundle_unpack
                    binv = bundle_unpack(binv, boff[f], bpk[f], db[f],
                                         nb[f])
                thr = execI[e, SI_THR]
                dl = execI[e, SI_DEFLEFT] != 0
                iscat = execI[e, SI_ISCAT] != 0
                mtf = mt[f]
                is_def = ((mtf == 1) & (binv == db[f])) | \
                         ((mtf == 2) & (binv == nb[f] - 1))
                num_left = jnp.where(is_def, dl, binv <= thr)
                w = jnp.take_along_axis(
                    execB[e].astype(jnp.uint32),
                    jnp.clip(binv >> 5, 0, 7)[:, None], axis=1)[:, 0]
                cat_left = (((w >> (binv & 31).astype(jnp.uint32)) & 1)
                            != 0)
                left = jnp.where(iscat, cat_left, num_left)
                nn = jnp.where(left, nxt_c[e],
                               first_c[jnp.clip(e + 1, 0, S)])
                ns = jnp.where(left, slot, jnp.clip(e + 1, 0, S))
                return (jnp.where(act, nn, node),
                        jnp.where(act, ns, slot))

            node, slot = lax.while_loop(cond, body, (node0, slot0))
            gate = applied.astype(jnp.float32)
            # in-place lane update on the donated [K, Nv] buffer
            return score.at[lane].add(
                cover[jnp.clip(slot, 0, S)] * scale * gate)
        return fn

    def undo_spec_scores(self, spec, applied, scale):
        """Subtract a dispatched-but-discarded iteration's (gated)
        score-lane contribution — the exact valmap the build program
        added, reconstructed from the spec's final leaf tables. Used
        when an eagerly-dispatched next iteration is abandoned (training
        stopped); restores the lane to metric-exactness."""
        fn = self._program("undo", self._undo_program, donate=(0,),
                           specs=self._specs("undo")
                           if self.axis else None)
        self.rec = fn(self.rec, spec.leafI, spec.cover, spec.n_exec,
                      applied, jnp.float32(scale))
        if self.parks:      # the parked rows took the tree by a walk
            self.walk_trees([(self.walk_tree_of_spec(spec), scale, 0.0)],
                            applied, -1.0, parked_only=True)
        self._score_cache = None
        self._last_exact = self._true_flag()

    def _undo_program(self, class_k: int = 0, sign: float = -1.0):
        """Subtract (sign=-1, the undo) or add (sign=+1, the multiclass
        deferred apply) a spec's gated valmap to class_k's score lane."""
        lane = self.lanes["score"] + class_k

        def fn(rec, leafI, cover, n_exec, applied, scale):
            valmap = self._valmap(leafI, cover, n_exec, applied)
            sc = _f32(rec[:, lane, :]) + valmap[:, None] * (sign * scale)
            return rec.at[:, lane, :].set(_i32(sc))
        # the undo is a drain's; the multiclass apply the next build's
        return phases.scoped("drain.undo")(fn) if sign < 0 else fn

    def _valmap(self, leafI, cover, n_exec, applied):
        """[NC]: what a spec's tree added to each row of a chunk where
        `applied` holds (0 in a chunk of no leaf), from its final leaf
        tables: the build program's score-lane update, made again."""
        slot_of, in_range = slot_in_any_map(
            leafI[:, LI_BEGIN], leafI[:, LI_COUNT], self.NC, self.C)
        exists = jnp.arange(leafI.shape[0]) <= n_exec
        return jnp.where(in_range & exists[slot_of] & applied,
                         cover[slot_of], 0.0)

    # ---- the walk of committed trees over the records as they lie
    # (`ops.aligned.walk_pass`): what lets a boosting variant take an
    # earlier tree out of the score lane and put it back (DART)
    def _walk_dims(self):
        from ..ops.aligned import walk_dims
        return walk_dims(self.cfg.num_leaves, self.wcnt, self.bits)

    def walk_tree_of_spec(self, spec, phase: str = "walk.tables") -> WalkTree:
        """The committed tree of a device spec in the walk's compact
        form, made on the device: nothing is pulled. `phase`: whose work
        it is (`valid.walk` where only a validation set's walk needs
        it)."""
        fn = self._program("walk_tree" if phase == "walk.tables"
                           else ("walk_tree", phase),
                           lambda: self._walk_tree_program(phase))
        return fn(spec.execI, spec.first_c, spec.nxt_c, spec.cover)

    def _walk_tree_program(self, phase: str):
        S = self.S
        ne = S                  # exec ids 0 .. S - 1; S is "no exec"
        np_, lp, _, _ = self._walk_dims()

        @phases.scoped(phase)
        def fn(execI, first_c, nxt_c, cover):
            eidx = jnp.arange(ne, dtype=jnp.int32)
            # a committed exec is one a committed chain points at
            is_c = jnp.zeros(ne + 1, bool).at[first_c].set(True) \
                .at[nxt_c].set(True)[:ne]
            rank = jnp.cumsum(is_c).astype(jnp.int32) - 1     # its node id
            ex = jnp.pad(execI, ((0, ne - execI.shape[0]), (0, 0)))
            slot = ex[:, SI_SLOT]
            rfirst = first_c[jnp.clip(eidx + 1, 0, S)]
            l_node = is_c & (nxt_c < ne)
            r_node = is_c & (rfirst < ne)
            l_id = rank[jnp.clip(nxt_c, 0, ne - 1)]
            r_id = rank[jnp.clip(rfirst, 0, ne - 1)]

            def put(size, fill, *pairs):
                out = jnp.full(size, fill, jnp.int32)
                for ok, at, val in pairs:
                    out = out.at[jnp.where(ok, at, size)].set(
                        val, mode="drop")
                return out
            one = jnp.ones(ne, jnp.int32)
            nodes = jnp.stack([
                put(np_, 0, (is_c, rank, ex[:, SI_FEAT])),
                put(np_, 0, (is_c, rank, ex[:, SI_THR])),
                put(np_, 0, (is_c, rank, ex[:, SI_DEFLEFT])),
                put(np_, -1, (l_node, l_id, rank), (r_node, r_id, rank)),
                put(np_, 0, (l_node, l_id, one), (r_node, r_id, -one))])
            # the left child keeps its parent's slot, the right child of
            # exec e takes slot e + 1: slot 0 is leaf 0, slot e + 1 leaf
            # rank[e] + 1
            l_leaf = is_c & ~l_node
            r_leaf = is_c & ~r_node
            l_lid = jnp.where(slot == 0, 0,
                              rank[jnp.clip(slot - 1, 0, ne - 1)] + 1)
            leaves = jnp.stack([
                put(lp, -1, (l_leaf, l_lid, rank), (r_leaf, rank + 1, rank)),
                put(lp, 0, (l_leaf, l_lid, one), (r_leaf, rank + 1, -one))])
            base = jnp.zeros(lp, jnp.float32).at[0].set(cover[0]).at[
                jnp.where(is_c, rank + 1, lp)].set(
                    cover[jnp.clip(eidx + 1, 0, S)], mode="drop")
            return WalkTree(nodes, leaves, jnp.sum(is_c).astype(jnp.int32),
                            base)
        return fn

    def walk_tree_of_host(self, tree) -> WalkTree:
        """A host `Tree` in the walk's compact form; its leaf values
        already hold shrinkage and bias."""
        np_, lp, _, _ = self._walk_dims()
        nn = int(tree.num_leaves) - 1
        nodes = np.zeros((5, np_), np.int32)
        leaves = np.zeros((2, lp), np.int32)
        nodes[3], leaves[0] = -1, -1
        nodes[0, :nn] = tree.split_feature_inner[:nn]
        nodes[1, :nn] = tree.threshold_in_bin[:nn]
        nodes[2, :nn] = (tree.decision_type[:nn] & 2) != 0
        ids = np.arange(nn, dtype=np.int32)
        for kids, side in ((tree.left_child[:nn], 1),
                           (tree.right_child[:nn], -1)):
            inner = kids >= 0
            nodes[3, kids[inner]], nodes[4, kids[inner]] = ids[inner], side
            leaves[0, ~kids[~inner]] = ids[~inner]
            leaves[1, ~kids[~inner]] = side
        base = np.zeros(lp, np.float32)
        base[:nn + 1] = tree.leaf_value[:nn + 1]
        return WalkTree(nodes, leaves, np.int32(nn), base)

    def _null_walk_tree(self) -> WalkTree:
        np_, lp, _, _ = self._walk_dims()
        nodes = np.zeros((5, np_), np.int32)
        leaves = np.zeros((2, lp), np.int32)
        nodes[3], leaves[0] = -1, -1
        return WalkTree(nodes, leaves, np.int32(0),
                        np.zeros(lp, np.float32))

    def walk_trees(self, trees, first, f_first, second=None,
                   f_second: float = 0.0, parked_only: bool = False) -> int:
        """Score lane of every row, live or parked (or with `parked_only`
        of the parked block's rows alone) += the sum over `trees` =
        [(WalkTree, shrinkage, bias)] of f x (shrinkage x leaf output +
        bias), where f is `f_first` if the device flag `first` holds,
        else `f_second` if `second` holds, else 0: so a walk queued
        beside a build can follow that build's own `applied` flag and
        nothing is pulled. WALK_TREES trees a `walk_pass`; returns the
        passes made. An empty list still makes one (of no tree): the
        warm-up's."""
        self.rec, passes = self.walk_block(
            self.rec, self.cnts, trees, first, f_first, second, f_second,
            lo=self.park_begin if parked_only else np.int32(0))
        self._score_cache = None
        return passes

    def walk_block(self, rec, cnts, trees, first, f_first, second=None,
                   f_second: float = 0.0, lo=np.int32(0),
                   phase: str = "walk.apply"):
        """`walk_trees` over any block of records laid out as the
        engine's (its own, or a validation set's from `pack_rows`), from
        chunk `lo` on: returns (the block, passes made). The block is
        donated. `phase` names whose walk it is: the training rows'
        (`walk.apply`, tables under `walk.tables`) or a validation set's
        (`valid.walk`, tables and kernel alike)."""
        from ..ops.aligned import WALK_TREES
        assert self.axis is None and self.num_class == 1
        key = "walk_rec" if phase == "walk.apply" \
            else ("walk_rec", phase, rec.shape)
        fn = self._program(key, lambda: self._walk_rec_program(phase),
                           donate=(0,))
        second = first if second is None else second
        null = None
        passes = 0
        for at in range(0, max(len(trees), 1), WALK_TREES):
            part = list(trees[at:at + WALK_TREES])
            if len(part) < WALK_TREES and null is None:
                null = (self._null_walk_tree(), 0.0, 0.0)
            full = part + [null] * (WALK_TREES - len(part))
            rec = fn(
                rec, cnts, lo, np.int32(len(part)), first,
                np.float32(f_first), second, np.float32(f_second),
                np.asarray([t[1] for t in full], np.float32),
                np.asarray([t[2] for t in full], np.float32),
                tuple(t[0] for t in full))
            passes += 1
        return rec, passes

    def _walk_rec_program(self, phase: str):
        from ..ops.aligned import walk_expand, walk_pass
        lr = self.learner
        nb = jnp.asarray(lr.meta["num_bin"], jnp.int32)
        db = jnp.asarray(lr.meta["default_bin"], jnp.int32)
        mt = jnp.asarray(lr.meta["missing_type"], jnp.int32)
        _, _, w8, fp = self._walk_dims()
        C, wcnt, bits = self.C, self.wcnt, self.bits
        lane, interpret = self.lanes["score"], self.interpret
        tables = "walk.tables" if phase == "walk.apply" else phase

        @phases.scoped(phase)
        def fn(rec, cnts, lo, ntrees, first, f_first, second, f_second,
               shr, bias, trees):
            # chunks below `lo` are none of this walk's
            cnts = jnp.where(jnp.arange(cnts.shape[0]) >= lo, cnts, 0)
            with phases.scope(tables):
                tabs = jax.vmap(lambda n, l, k: walk_expand(
                    n, l, k, nb, db, mt, w8=w8, bits=bits, fp=fp))(
                        jnp.stack([jnp.asarray(t.nodes) for t in trees]),
                        jnp.stack([jnp.asarray(t.leaves) for t in trees]),
                        jnp.stack([jnp.asarray(t.nn) for t in trees]))
            base = jnp.stack([jnp.asarray(t.base) for t in trees])
            f = jnp.where(first, f_first, jnp.where(second, f_second, 0.0))
            vals = f * (shr[:, None] * base + bias[:, None])
            return walk_pass(rec, cnts, ntrees, *tabs, vals[:, :, None],
                             chunk=C, wcnt=wcnt, bits=bits, lane=lane,
                             interpret=interpret)
        return fn

    def walk_rows(self, score, lane, vbins, tree: WalkTree, shrinkage,
                  bias, applied, factor):
        """score [K, Nv] lane `lane` += factor x (shrinkage x tree(vbins)
        + bias) where `applied` holds: `apply_spec_to_scores` for a tree
        in the walk's compact form, so for a host tree as for a spec."""
        fn = self._program(("walk_rows", vbins.shape),
                           self._walk_rows_program, donate=(0,))
        return fn(score, jnp.int32(lane), vbins, tree, jnp.float32(shrinkage),
                  jnp.float32(bias), applied, jnp.float32(factor))

    def _walk_rows_program(self):
        lr = self.learner
        nb = jnp.asarray(lr.meta["num_bin"], jnp.int32)
        db = jnp.asarray(lr.meta["default_bin"], jnp.int32)
        mt = jnp.asarray(lr.meta["missing_type"], jnp.int32)

        @phases.scoped("valid.walk")
        def fn(score, lane, vb, tree, shrinkage, bias, applied, factor):
            feat, thr, dl, parent, side = jnp.asarray(tree.nodes)
            leaves = jnp.asarray(tree.leaves)
            np_, lp = feat.shape[0], leaves.shape[1]
            ids = jnp.arange(np_, dtype=jnp.int32)
            lids = jnp.arange(lp, dtype=jnp.int32)

            def kids(s):
                """Each node's child on side s: a node id, or ~leaf."""
                out = jnp.full(np_, -1, jnp.int32)
                out = out.at[jnp.where((ids < tree.nn) & (side == s),
                                       parent, np_)].set(ids, mode="drop")
                return out.at[jnp.where((lids <= tree.nn)
                                        & (leaves[1] == s), leaves[0],
                                        np_)].set(~lids, mode="drop")
            lch, rch = kids(1), kids(-1)

            def body(node):
                e = jnp.clip(node, 0, np_ - 1)
                f = feat[e]
                binv = jnp.take_along_axis(
                    vb, f[:, None], axis=1)[:, 0].astype(jnp.int32)
                is_def = ((mt[f] == 1) & (binv == db[f])) \
                    | ((mt[f] == 2) & (binv == nb[f] - 1))
                left = jnp.where(is_def, dl[e] != 0, binv <= thr[e])
                return jnp.where(node >= 0,
                                 jnp.where(left, lch[e], rch[e]), node)

            node = lax.while_loop(
                lambda node: jnp.any(node >= 0), body,
                jnp.full(vb.shape[0], jnp.where(tree.nn > 0, 0, -1),
                         jnp.int32))
            val = shrinkage * jnp.asarray(tree.base)[~node] + bias
            return score.at[lane].add(
                jnp.where(applied, factor, 0.0) * val)
        return fn

    def set_bag(self, mask_rows):
        """Re-ingest a per-row 0/1 bagging mask into the bag lane (one
        streaming pass; called on bagging_freq boundaries)."""
        fn = self._program("setbag", self._set_bag_program, donate=(0,),
                           specs=self._specs("setbag")
                           if self.axis else None)
        self.rec = fn(self.rec, jnp.asarray(mask_rows, jnp.float32),
                      *self._ext_args())
        self.bag_sampled = False
        self.bag_drawn = None
        if self.parks:
            self._park_kept = jnp.int32(
                np.count_nonzero(np.asarray(mask_rows) > 0.5))
            self._park_stale = True

    def bag_select(self, seed: int, cnt: int):
        """Queue plain bagging's draw (`ops/goss.py:bag_multipliers`) over
        the records as they lie: the `cnt` rows with the smallest key of
        (row id, `seed`) are in the bag, the others out of it (the bag
        lane's 1.0 / 0.0, or the compact record's bag bit). Nothing is
        uploaded but the seed and nothing is pulled; the bag then moves
        with its rows, untouched, until the next draw. Returns the in-bag
        row count, a device scalar (`bag_kept`)."""
        assert self.bag_device and not self.bag_multiplier \
            and self.axis is None
        fn = self._program(("bag_select", cnt),
                           lambda: self._bag_select_program(cnt),
                           donate=(0,))
        self.rec, self.bag_kept = fn(self.rec, self.cnts, jnp.uint32(seed),
                                     *self._ext_args())
        self.bag_drawn = int(seed)
        self._park_kept, self._park_stale = self.bag_kept, True
        return self.bag_kept

    def _bag_select_program(self, cnt):
        from ..ops.goss import bag_multipliers

        @phases.scoped("sample.bag")
        def bag_select(rec, cnts, seed, ext_of_row=None):
            rid, live = self._row_ids(rec, cnts, ext_of_row)
            mult, kept = bag_multipliers(rid, live, seed, cnt)
            return self._with_bag(rec, mult), kept
        return bag_select

    def _with_bag(self, rec, vals):
        """`rec` with the per-cell 0/1 f32 `vals` as its bag."""
        ln = self.lanes
        if not self.compact:
            return rec.at[:, ln["bag"], :].set(_i32(vals))
        # the bag bit is the SIGN bit (31): int32-safe clear + set
        meta = (rec[:, ln["meta"], :] & jnp.int32(0x7FFFFFFF)) | jnp.where(
            vals > 0.5, jnp.int32(-(1 << 31)), jnp.int32(0))
        return rec.at[:, ln["meta"], :].set(meta)

    def _row_ids(self, rec, cnts, ext_of_row=None):
        """(row id, does the cell hold a row) per record cell. The index
        lane names the row, or under the `tiles` layout its slot in the
        objective's pack, which `ext_of_row` inverted turns back: a
        sampling key is the ROW's, whatever the lane counts in, since a
        fallback draws the sample again in row order."""
        rid = self._rid_lanes(rec)
        live = (jnp.arange(self.C, dtype=jnp.int32)[None, :]
                < cnts[:, None]) & (rid < self.ext_n)
        if ext_of_row is not None:
            rid = self._rows_to_ext(
                jnp.arange(self.n, dtype=jnp.int32),
                ext_of_row)[jnp.clip(rid, 0, self.ext_n - 1)]
        return rid, live

    def goss_select(self, seed: int, top_k: int, other_k: int,
                    multiply: float, grads=None,
                    boost_iter: Optional[int] = None):
        """Queue one iteration's GOSS selection (`ops/goss.py`) over the
        records as they lie: a = |g x h| from the score and label lanes
        (or `grads` = external-order (g, h) gathered by the index lane,
        for an objective that is not pointwise), before any multiplier; the
        per-row multiplier goes into the bag lane, which the build
        program queued next reads. Nothing is pulled and nothing is
        uploaded but the seed. Returns the device counters (kept_top,
        kept_other, threshold)."""
        assert self.bag_multiplier and self.axis is None
        from ..ops.goss import PASSES
        with obs_trace.seam("goss.select", iter=boost_iter, seed=int(seed),
                            top_k=top_k, other_k=other_k,
                            multiplier=multiply, passes=2 * PASSES):
            fn = self._program(
                ("goss_select", top_k, other_k, grads is not None),
                lambda: self._goss_select_program(top_k, other_k, multiply,
                                                  grads is not None),
                donate=(0,))
            self.rec, stats, self._park_kept = fn(
                self.rec, self.cnts, jnp.uint32(seed), *(grads or ()),
                *self._ext_args())
        self.bag_sampled = self._park_stale = True
        return stats

    def _goss_select_program(self, top_k, other_k, multiply, external):
        from ..ops.goss import goss_multipliers
        ln = self.lanes

        @phases.scoped("sample.goss")
        def goss_select(rec, cnts, seed, g_rows=None, h_rows=None,
                        ext_of_row=None):
            rid, live = self._row_ids(rec, cnts, ext_of_row)
            if external:
                at = jnp.clip(rec[:, ln["rid"], :], 0, self.ext_n - 1)
                g, h = g_rows.reshape(-1)[at], h_rows.reshape(-1)[at]
            else:
                g, h = self._pgrad(
                    _f32(rec[:, ln["score"], :]),
                    _f32(rec[:, ln["label"], :]),
                    _f32(rec[:, ln["weight"], :])
                    if self.objective.weight is not None else None)
            mult, stats = goss_multipliers(jnp.abs(g * h), rid, live, seed,
                                           top_k, other_k, multiply)
            return (rec.at[:, ln["bag"], :].set(_i32(mult)), stats,
                    stats[0] + stats[1])
        return goss_select

    def row_lane(self, lane: str) -> np.ndarray:
        """One f32 lane of the records in ROW order (a check's accessor;
        pulls N): "grad" and "hess" hold what the last build trained
        on."""
        return np.asarray(self._materialized(lane))

    def row_bag(self) -> np.ndarray:
        """The bag lane in ROW order."""
        return self.row_lane("bag")

    def _set_bag_program(self):
        @phases.scoped("sample.bag")
        def fn(rec, mask, ext_of_row=None):
            rid = jnp.clip(self._rid_lanes(rec), 0, self.ext_n)
            vals = jnp.concatenate([self._rows_to_ext(mask, ext_of_row),
                                    jnp.zeros(1, jnp.float32)])[rid]
            return self._with_bag(rec, vals)
        return fn

    def set_row_scores(self, row_scores):
        """Re-ingest ROW-order scores into the score lane (leaf-wise
        fallback path: the fallback tree updated scores in row order)."""
        self.set_row_scores_lane(0, row_scores)
        self._last_exact = self._true_flag()   # lane is authoritative again

    def _rid_lanes(self, rec):
        """External ids per record cell: row ids unless the objective
        stated a layout at pack time (compact: low 24 meta bits)."""
        ln = self.lanes
        if self.compact:
            return rec[:, ln["meta"], :] & META_RID_MASK
        return rec[:, ln["rid"], :]

    def _rows_to_ext(self, x, ext_of_row):
        """Row-order `x` in external order; slots of no row hold 0."""
        if ext_of_row is None:
            return x
        return jnp.zeros(self.ext_n, x.dtype).at[ext_of_row].set(x)

    def _set_scores_program(self, class_k: int = 0):
        lane = self.lanes["score"] + class_k

        def fn(rec, scores, ext_of_row=None):
            rid = jnp.clip(self._rid_lanes(rec), 0, self.ext_n - 1)
            vals = self._rows_to_ext(scores, ext_of_row)[rid]
            return rec.at[:, lane, :].set(_i32(vals))
        return fn

    def row_scores(self) -> np.ndarray:
        """Materialize the training scores in ROW order (lazy; only
        metrics / dumps need this)."""
        if self._score_cache is not None:
            return self._score_cache
        out = np.asarray(self.row_scores_dev())
        self._score_cache = out
        return out

    def _materialize_program(self, lane: str = "score", rows: bool = True):
        ln = self.lanes
        ax = self.axis

        # out of the records for the drain, a metric or a check in row
        # order; between two trees for an objective with a layout of its own
        @phases.scoped("drain.materialise" if rows else "rank.scatter")
        def fn(rec, cnts, ext_of_row=None):
            if lane == "bag" and self.compact:      # the meta lane's sign
                sc = (rec[:, ln["meta"], :] < 0).astype(jnp.float32)
            else:
                sc = _f32(rec[:, ln[lane], :])
            out = self._to_ext(rec, cnts, sc)
            if ax is not None:
                # each shard scatters only its own rows; the psum
                # assembles the full row-order vector on every shard
                out = lax.psum(out, ax)
            if not rows:
                return out.reshape(self.ext_shape)
            return out if ext_of_row is None else out[ext_of_row]
        return fn

    def _materialize_ahead_program(self):
        lane = self.lanes["score"]

        @phases.scoped("drain.materialise")
        def fn(rec, cnts, leafI, cover, n_exec, applied, scale):
            valmap = self._valmap(leafI, cover, n_exec, applied)
            return self._to_ext(rec, cnts, _f32(rec[:, lane, :])
                                - valmap[:, None] * scale)
        return fn

    def _to_ext(self, rec, cnts, vals):
        """`vals` [NC, C], one a record cell, in external order [ext_n] by
        the index lane; cells of no row are dropped."""
        n = self.ext_n
        rid = self._rid_lanes(rec).reshape(-1)
        pos = jnp.arange(self.C, dtype=jnp.int32)
        valid = (pos[None, :] < cnts[:, None]).reshape(-1)
        rid = jnp.where(valid & (rid < n), rid, n)
        return jnp.zeros(n + 1, jnp.float32).at[rid].set(
            vals.reshape(-1))[:n]
