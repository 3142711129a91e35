"""Fused on-device tree builder: ONE jitted program grows a whole tree.

Why: the host-driven `SerialTreeLearner` issues ~15 host<->device syncs per
split, and each sync stalls the device queue. This
learner keeps the entire leaf-wise loop (reference
`SerialTreeLearner::Train`, serial_tree_learner.cpp:173-237) inside one
`lax.while_loop`: per-leaf state, the histogram pool
(reference HistogramPool, feature_histogram.hpp:654), the partition, and the
recorded splits all live in device arrays. Dynamic leaf sizes are handled by
a `lax.switch` over power-of-two size buckets — each branch compiles its own
statically-shaped gather + MXU histogram / stable partition.

TPU-profile-driven layout choices (v5e measurements):
- random row gathers are the dominant cost (~10-16 ns/element through XLA's
  gather lowering), so the ROOT histogram reads the binned matrix
  contiguously whenever the partition is the identity (fresh per-tree
  partitions make that the common case), and per-split work is bucketed to
  the smaller child's power-of-two size;
- a TRANSPOSED copy of the bins (`bins_T[F, N]`) makes the split feature's
  column a contiguous `dynamic_slice`, and the stable partition carries row
  ids through the sort network as a payload operand (no argsort+gather);
- the per-leaf best-split/record state lives in a few PACKED [L, 8]-wide
  arrays rather than ~26 scalar arrays — each split updates 6 rows, not 40,
  which keeps the sequential tiny-op chain per split short;
- `lax.while_loop` (not fori_loop+cond) stops the program at the last real
  split, so early-stopped trees don't pay for the remaining leaf budget.

The host pulls nothing during training; a finished tree is a `TreeRecord`
pytree of device arrays, convertible to a host `Tree` (one batched transfer)
only when the model is exported, and convertible to traversal arrays
on-device for score updates.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import compile_cache
from ..config import Config
from ..io.dataset import Dataset
from ..ops.histogram import (NUM_HIST_STATS, histogram_from_gathered_gh,
                             quantize_gh)
from ..ops.partition import (categorical_goes_left, leaf_value_fill,
                             numerical_goes_left, split_partition,
                             unpermute_to_rows)
from ..ops.split import SplitHyper, make_split_finder
from .tree import Tree

NEG_INF = -jnp.inf

# packed per-leaf "best split" float lanes
BF_GAIN, BF_LG, BF_LH, BF_RG, BF_RH, BF_LOUT, BF_ROUT = range(7)
BF_W = 8
# packed per-leaf "best split" int lanes
BI_FEAT, BI_THR, BI_LC, BI_RC, BI_DEFLEFT, BI_ISCAT = range(6)
BI_W = 8
# packed per-leaf float state lanes
LF_SG, LF_SH, LF_MINC, LF_MAXC, LF_VALUE = range(5)
LF_W = 8
# packed per-leaf int state lanes
LI_BEGIN, LI_COUNT, LI_COUNTG, LI_DEPTH = range(4)
LI_W = 8
# packed per-split record float lanes
RF_LOUT, RF_ROUT, RF_GAIN, RF_IVAL = range(4)
RF_W = 4
# packed per-split record int lanes
RI_LEAF, RI_FEAT, RI_THR, RI_DEFLEFT, RI_ISCAT, RI_LC, RI_RC = range(7)
RI_W = 8


class TreeRecord(NamedTuple):
    """Per-split records of one grown tree (device pytree).

    The level builder (level_builder.py) replays speculated splits on the
    host and emits a NumPy TreeRecord whose physical partition is FINER
    than the committed tree; there the block_* fields carry the
    (begin, count, covering committed leaf value) tables that the
    partition score update consumes instead of the leaf_* fields.
    """
    num_splits: jax.Array          # i32 scalar: actual splits made
    leaf: jax.Array                # i32[L-1] leaf id split at step s
    feature: jax.Array             # i32[L-1] inner feature index
    threshold_bin: jax.Array       # i32[L-1]
    default_left: jax.Array        # bool[L-1]
    is_cat: jax.Array              # bool[L-1]
    cat_bitset: jax.Array          # u32[L-1, 8] (bins)
    left_output: jax.Array         # f32[L-1]
    right_output: jax.Array        # f32[L-1]
    left_count: jax.Array          # i32[L-1]
    right_count: jax.Array         # i32[L-1]
    gain: jax.Array                # f32[L-1]
    internal_value: jax.Array      # f32[L-1] (parent output before split)
    leaf_value: jax.Array          # f32[L] final leaf outputs
    leaf_count_arr: jax.Array      # i32[L]
    leaf_begin: jax.Array          # i32[L] partition begins
    leaf_cnt_part: jax.Array       # i32[L]
    block_begin: Optional[jax.Array] = None    # i32[S] physical blocks
    block_cnt: Optional[jax.Array] = None      # i32[S]
    block_value: Optional[jax.Array] = None    # f32[S] covering leaf value


def _pow2ceil(n: int) -> int:
    return 1 << max(0, int(math.ceil(math.log2(max(n, 1)))))


def pack_best_payload(out: Dict, gain: jax.Array):
    """Pack the winning feature's split into (vecF, vecI, bitset) rows —
    shared by the leaf-wise and level builders (BF_*/BI_* lanes)."""
    f = jnp.argmax(gain)
    vecF = jnp.zeros(BF_W, jnp.float32)
    vecF = vecF.at[BF_GAIN].set(gain[f])
    vecF = vecF.at[BF_LG].set(out["left_g"][f])
    vecF = vecF.at[BF_LH].set(out["left_h"][f])
    vecF = vecF.at[BF_RG].set(out["right_g"][f])
    vecF = vecF.at[BF_RH].set(out["right_h"][f])
    vecF = vecF.at[BF_LOUT].set(out["left_output"][f])
    vecF = vecF.at[BF_ROUT].set(out["right_output"][f])
    vecI = jnp.zeros(BI_W, jnp.int32)
    vecI = vecI.at[BI_FEAT].set(f.astype(jnp.int32))
    vecI = vecI.at[BI_THR].set(out["threshold"][f])
    vecI = vecI.at[BI_LC].set(out["left_c"][f])
    vecI = vecI.at[BI_RC].set(out["right_c"][f])
    vecI = vecI.at[BI_DEFLEFT].set(out["default_left"][f].astype(jnp.int32))
    vecI = vecI.at[BI_ISCAT].set(out["is_cat"][f].astype(jnp.int32))
    return vecF, vecI, out["cat_bitset"][f]


def bucket_table(min_pad: int, root_count: int) -> List[int]:
    """~sqrt(2)-spaced leaf-size table (pow2 plus 1.5x midpoints rounded
    up to 512) for the dynamic-leaf switch: the average pad factor on the
    gather/histogram/partition work drops from ~1.5x to ~1.2x for ~2x the
    compiled branches."""
    cands = []
    s = min_pad
    while True:
        cands.append(s)
        mid = (s * 3 // 2 + 511) & ~511
        if mid > s:
            cands.append(mid)
        if s >= root_count:
            break
        s <<= 1
    out = []
    for sz in sorted(set(cands)):
        out.append(sz)
        if sz >= root_count:
            break
    return out


@functools.partial(jax.jit, static_argnames=("max_nodes",))
def record_to_children(leaf_rec: jax.Array, num_splits: jax.Array,
                       max_nodes: int) -> Tuple[jax.Array, jax.Array]:
    """Reconstruct left/right child links from the split sequence.

    Node s split leaf `leaf_rec[s]` into left=same leaf id, right=s+1.
    left_child[s] -> the NEXT step that splits leaf_rec[s] (as a node), else
    ~leaf_rec[s]; right_child[s] -> the next step that splits leaf s+1, else
    ~(s+1).  O(L^2) vectorized — trivial next to histogram work.
    """
    s_idx = jnp.arange(max_nodes)
    later = (s_idx[None, :] > s_idx[:, None]) \
        & (s_idx[None, :] < num_splits)

    def next_split_of(target):  # target: [max_nodes] leaf ids
        hit = later & (leaf_rec[None, :] == target[:, None])
        any_hit = hit.any(axis=1)
        first = jnp.argmax(hit, axis=1)
        return any_hit, first

    l_hit, l_first = next_split_of(leaf_rec)
    left = jnp.where(l_hit, l_first, ~leaf_rec)
    r_leaf = s_idx + 1
    r_hit, r_first = next_split_of(r_leaf)
    right = jnp.where(r_hit, r_first, ~r_leaf)
    return left.astype(jnp.int32), right.astype(jnp.int32)


class DeviceTreeLearner:
    """Drop-in replacement for SerialTreeLearner with zero mid-tree syncs.

    With ``axis_name`` set, the same whole-tree program becomes the
    data-parallel learner (reference `DataParallelTreeLearner`,
    `data_parallel_tree_learner.cpp`): rows are sharded over a mesh axis,
    local histograms are `lax.psum`-reduced (the XLA/ICI analogue of
    `Network::ReduceScatter` + best-split allreduce — since every shard then
    holds the GLOBAL histogram, the best split is computed redundantly and
    identically on all shards, so no separate `SyncUpGlobalBestSplit` is
    needed), and leaf counts split into a LOCAL set driving the per-shard
    partition and a GLOBAL set driving split decisions (the reference's
    `global_data_count_in_leaf_`, data_parallel_tree_learner.cpp:251-257).
    Collectives sit inside the while-loop body, which is safe because every
    shard makes identical split decisions from the identical (global)
    histograms and therefore iterates the loop the same number of times.
    """

    def __init__(self, cfg: Config, dataset: Dataset,
                 axis_name: Optional[str] = None,
                 parallel_mode: Optional[str] = None,
                 feature_pad_to: Optional[int] = None,
                 mesh_size: int = 1) -> None:
        self.cfg = cfg
        self.axis_name = axis_name
        # serial (single program) / data (rows sharded, psum histograms) /
        # feature (rows replicated, feature-block histogram work division) /
        # voting (rows sharded, top-k vote + selected-feature reduce)
        self.parallel_mode = parallel_mode or (
            "data" if axis_name is not None else "serial")
        self.mesh_size = mesh_size
        self.ds = dataset
        self.n = dataset.num_data
        self.num_real_features = dataset.num_features
        meta = dataset.feature_meta_arrays()
        if feature_pad_to and feature_pad_to > len(meta["num_bin"]):
            # pad the feature axis so it divides evenly over the mesh
            # (feature-parallel block slicing); padded features are trivial
            # (num_bin=2, no data) and masked out of every split search
            pad = feature_pad_to - len(meta["num_bin"])
            meta = dict(meta)
            meta["num_bin"] = np.concatenate(
                [meta["num_bin"], np.full(pad, 2, meta["num_bin"].dtype)])
            for key, fill in (("default_bin", 0), ("missing_type", 0),
                              ("bin_type", 0), ("monotone", 0)):
                meta[key] = np.concatenate(
                    [meta[key], np.full(pad, fill, meta[key].dtype)])
            meta["penalty"] = np.concatenate(
                [meta["penalty"], np.ones(pad, meta["penalty"].dtype)])
        self.num_features = len(meta["num_bin"])
        self.meta = meta
        self.max_bin_global = int(meta["num_bin"].max()) \
            if len(meta["num_bin"]) else 2
        self._bins_dev = None  # lazy: the data-parallel wrapper never
        # materializes this second (replicated) device copy of the bins
        self._bins_T_dev = None
        self.hyper = SplitHyper.from_config(cfg)
        self.finder = make_split_finder(self.hyper, meta, self.max_bin_global)
        self.mappers = dataset.used_mappers()
        self._feat_rng = np.random.RandomState(cfg.feature_fraction_seed)
        if cfg.tpu_use_f64_hist:
            # genuine f64 accumulation (ops/histogram.py): exact, hence
            # topology-invariant — required for byte-equal distributed parity
            self.hist_precision = "f64"
        elif cfg.gpu_use_dp:
            self.hist_precision = "f32"
        elif cfg.tpu_use_pallas:
            from ..ops.pallas_hist import pallas_available
            self.hist_precision = ("pallas" if pallas_available()
                                   else "bf16x2")
        else:
            self.hist_precision = "bf16x2"
        self.min_pad = int(cfg.tpu_min_pad)
        self.quant_bits, self._quant_why = self._resolve_quant_bits(cfg)
        self._qseq = 0  # host counter: one fresh quantization key per tree
        # device feature metadata for the partition step
        self._nb_dev = jnp.asarray(meta["num_bin"], jnp.int32)
        self._db_dev = jnp.asarray(meta["default_bin"], jnp.int32)
        self._mt_dev = jnp.asarray(meta["missing_type"], jnp.int32)
        self._mono_any = bool(np.any(meta["monotone"] != 0))
        self._build_cache: Dict[Tuple[int, bool], callable] = {}
        self._depth_limit = cfg.max_depth if cfg.max_depth > 0 else 1 << 30
        # Exclusive Feature Bundling view (io/bundling.py): bins columns
        # are bundles; per-feature histograms are sliced out on device
        bnd = getattr(dataset, "bundles", None)
        self.bundled = bnd is not None
        if self.bundled:
            from ..io.bundling import expansion_map
            self.hist_bins = int(max(self.max_bin_global,
                                     bnd.group_num_bin.max()))
            m_idx, dmask = expansion_map(bnd, meta["num_bin"],
                                         meta["default_bin"],
                                         self.hist_bins)
            self._emap_dev = jnp.asarray(m_idx[:, :self.max_bin_global])
            self._edef_dev = jnp.asarray(
                dmask[:, :self.max_bin_global].astype(np.float32))
            self._col_dev = jnp.asarray(bnd.col, jnp.int32)
            self._boff_dev = jnp.asarray(bnd.off, jnp.int32)
            self._bpk_dev = jnp.asarray(bnd.packed.astype(np.int32))
        else:
            self.hist_bins = self.max_bin_global
            self._col_dev = jnp.arange(self.num_features, dtype=jnp.int32)
            self._boff_dev = jnp.zeros(self.num_features, jnp.int32)
            self._bpk_dev = jnp.zeros(self.num_features, jnp.int32)

    def _resolve_quant_bits(self, cfg: Config) -> Tuple[int, Optional[str]]:
        """Resolve ``tpu_quant_hist`` to active bits (0 = f32 oracle) plus
        the human-readable reason when the oracle runs instead. The f32
        path is bitwise-unchanged when inactive — same discipline as
        ``tpu_rank_fused``; `gbdt._log_train_path` surfaces the outcome as
        a ``quant_hist`` event once the actual train path is known."""
        mode = str(cfg.tpu_quant_hist).lower()
        if mode == "off":
            return 0, "tpu_quant_hist=off"
        bits = 8 if int(cfg.tpu_quant_hist_bits) == 8 else 16
        if self.hist_precision in ("f64", "f32"):
            # exact-f64 distributed parity and the gpu_use_dp double path
            # must keep full-precision payloads
            return 0, f"hist_precision={self.hist_precision} never quantizes"
        if self.parallel_mode != "serial":
            # data_parallel.py wraps build entries in shard_map with
            # fixed-arity in_specs; the quantized entries take an extra
            # qseq operand, so the parallel learners keep the f32 path
            return 0, f"parallel_mode={self.parallel_mode} keeps f32 payloads"
        if cfg.tpu_grow_mode == "level":
            # the level builder's packed-word hist path bypasses
            # _make_build_fn entirely
            return 0, "tpu_grow_mode=level keeps f32 payloads"
        if mode == "on":
            return bits, None
        if jax.default_backend() == "tpu":
            return bits, None
        return 0, "auto: no TPU attached"

    def _next_qseq(self) -> int:
        """Fresh per-tree quantization sequence number (host counter,
        passed as a traced int32 so advancing it never retraces)."""
        self._qseq += 1
        return self._qseq

    def trace_signature(self) -> Tuple:
        """Hashable key covering everything this learner's build-program
        closures bake into a jax trace: the full config, the binning
        metadata (content-hashed — closures capture the device copies as
        constants), bundling tables, data shape, and mesh placement.
        Programs built by learners with equal signatures are shared
        process-wide (see compile_cache.program), so a second Booster on
        the same shapes triggers zero new traces."""
        sig = getattr(self, "_trace_sig_cache", None)
        if sig is None:
            m = self.meta
            bundle_fp = None
            if self.bundled:
                bnd = self.ds.bundles
                bundle_fp = compile_cache.array_fingerprint(
                    bnd.col, bnd.off, bnd.packed, bnd.group_num_bin)
            forced = (tuple(map(tuple, self._forced_nodes()))
                      if self.cfg.forcedsplits_filename else ())
            sig = ("learner", type(self).__name__,
                   compile_cache.config_signature(self.cfg),
                   compile_cache.array_fingerprint(
                       m["num_bin"], m["default_bin"], m["missing_type"],
                       m["bin_type"], m["monotone"], m["penalty"]),
                   bundle_fp, self.n, self.num_features,
                   self.num_real_features, self.max_bin_global,
                   self.hist_bins, self.axis_name, self.parallel_mode,
                   self.mesh_size, self.min_pad, self.hist_precision,
                   self.quant_bits, forced)
            self._trace_sig_cache = sig
        return sig

    def _cached_program(self, key, factory):
        """Two-level program lookup: per-instance memo over the
        process-wide registry (keyed by trace_signature + key)."""
        fn = self._build_cache.get(key)
        if fn is None:
            fn = compile_cache.program(
                self.trace_signature() + ("prog", key), factory)
            self._build_cache[key] = fn
        return fn

    @property
    def bins_dev(self) -> jax.Array:
        if self._bins_dev is None:
            # device_bins() reuses the HBM buffer the streaming ingest
            # left behind (io/stream.py) — no second upload of the full
            # binned matrix at train start
            dev = getattr(self.ds, "device_bins", None)
            self._bins_dev = dev() if dev is not None \
                else jnp.asarray(self.ds.bins)
            from ..obs import memory as obs_memory
            obs_memory.track(
                "train/bins_dev", self,
                lambda lr: 0 if lr._bins_dev is None
                else int(lr._bins_dev.nbytes))
        return self._bins_dev

    # ------------------------------------------------------------------
    def level_mode_ok(self) -> bool:
        """True when the level-batched builder (`level_builder.py`) can grow
        trees for this learner: uint8 bins, serial/data parallelism, and the
        grow mode allows it. Bagged iterations always use the leaf-wise
        path (the level records assume a full fresh root). "auto" now
        selects the aligned pipeline or leafwise — the sort-based level
        builder stays opt-in (measured on par with leafwise on v5e)."""
        return (self.cfg.tpu_grow_mode == "level"
                and not self.cfg.sequential_device_only
                and not self.bundled
                and self.parallel_mode in ("serial", "data")
                and self.ds.bins_dtype() == np.uint8
                and self.num_features > 0
                and self.cfg.num_leaves >= 2)

    @property
    def words_dev(self) -> jax.Array:
        """Packed bin words [ceil(F/4), N] for the level builder (lazy)."""
        if getattr(self, "_words_dev", None) is None:
            from .level_builder import pack_bin_words
            bins = np.asarray(self.ds.bins)
            if self.num_features != self.num_real_features:
                pad = self.num_features - self.num_real_features
                bins = np.pad(bins, ((0, 0), (0, pad)))
            self._words_dev = jnp.asarray(pack_bin_words(bins))
        return self._words_dev

    def _level_fn(self):
        def factory():
            from .level_builder import make_level_build_fn
            return make_level_build_fn(self)
        return self._cached_program("level", factory)

    def _level_train_fresh(self, grad, hess, feature_mask):
        """Speculative level build + host leaf-wise replay; falls back to
        the sequential leaf-wise builder when speculation was too shallow
        for an exact replay."""
        from .level_builder import replay_leafwise
        spec = self._level_fn()(self.words_dev, grad, hess,
                                self._fmask_arr(feature_mask))
        host = jax.device_get(spec._replace(rid=None))
        rec, exact = replay_leafwise(host, self.cfg.num_leaves)
        if not exact:
            self._level_fallbacks = getattr(self, "_level_fallbacks", 0) + 1
            return None
        rec = rec._replace(block_begin=spec.block_begin,
                           block_cnt=spec.block_cnt)
        return spec.rid, rec

    @property
    def bins_T_dev(self) -> jax.Array:
        """Transposed bins [F, N] so a dynamic feature's column is one
        contiguous dynamic_slice (the row-major column read costs a stride-F
        pass over the whole matrix on TPU)."""
        if self._bins_T_dev is None:
            self._bins_T_dev = jnp.asarray(
                np.ascontiguousarray(np.asarray(self.ds.bins).T))
        return self._bins_T_dev

    def add_score(self, score_row: jax.Array, trav: Dict,
                  scale: float) -> jax.Array:
        """score += scale * tree(x) over the training bins."""
        return add_record_score(score_row, self.bins_dev, trav, self._nb_dev,
                                self._db_dev, self._mt_dev,
                                jnp.float32(scale),
                                self._col_dev if self.bundled else None,
                                self._boff_dev if self.bundled else None,
                                self._bpk_dev if self.bundled else None)

    def add_score_from_partition(self, score: jax.Array, class_id: int,
                                 record: "TreeRecord", indices: jax.Array,
                                 scale: float) -> jax.Array:
        """score[class_id] += scale * tree(x) using the final partition:
        each leaf's rows are contiguous in `indices`, so the per-row leaf
        value is a scatter-at-L-boundaries + cumsum fill, and the only
        irregular step is ONE key-sort back to row order — no per-level tree
        traversal. One fused program, score buffer donated. (Replaces the
        reference's Tree::AddPredictionToScore bulk update,
        tree.cpp:112-204.) Valid only for full-data (no bagging) trees.

        Level-built records carry a FINER physical partition than the
        committed tree: score through the block tables instead."""
        if record.block_begin is not None:
            return _partition_score_update(
                score, jnp.int32(class_id), jnp.asarray(record.block_begin),
                jnp.asarray(record.block_cnt),
                jnp.asarray(record.block_value, dtype=jnp.float32), indices,
                jnp.int32(self.n), jnp.float32(scale))
        return _partition_score_update(
            score, jnp.int32(class_id), record.leaf_begin,
            record.leaf_cnt_part, record.leaf_value, indices,
            jnp.int32(self.n), jnp.float32(scale))

    # ------------------------------------------------------------------
    def feature_mask(self) -> Optional[np.ndarray]:
        frac = self.cfg.feature_fraction
        if frac >= 1.0:
            if self.num_features != self.num_real_features:
                mask = np.zeros(self.num_features, bool)
                mask[:self.num_real_features] = True  # padded features off
                return mask
            return None
        used_cnt = max(1, int(round(self.num_real_features * frac)))
        mask = np.zeros(self.num_features, bool)
        mask[self._feat_rng.choice(self.num_real_features, used_cnt,
                                   replace=False)] = True
        return mask

    # ------------------------------------------------------------------
    def _buckets_for(self, root_count: int) -> List[int]:
        return bucket_table(self.min_pad, root_count)

    @staticmethod
    def _bucket_index(count, sizes_list):
        """Smallest bucket size >= count — exact integer comparison against
        the bucket-size table (float log2 would undercount near 2^24 and
        silently drop rows)."""
        sizes = jnp.asarray(sizes_list, jnp.int32)
        b = jnp.sum((count > sizes).astype(jnp.int32))
        return jnp.clip(b, 0, len(sizes_list) - 1)

    # ------------------------------------------------------------------
    def _make_build_fn(self, root_padded: int, root_contiguous: bool):
        """Build the jitted whole-tree program for a given root size.

        root_contiguous: the root partition is the identity permutation
        (fresh per-tree partition, no bagging), so the root histogram and
        root sums read bins/grad/hess contiguously — skipping the single
        biggest random gather of the tree.
        """
        cfg = self.cfg
        L = cfg.num_leaves
        Lm1 = max(L - 1, 1)
        F = self.num_features
        B = self.max_bin_global
        BH = self.hist_bins
        bundled = self.bundled
        if bundled:
            emap, edef = self._emap_dev, self._edef_dev

            def expand_hist(hist_g, sg, sh, cnt):
                """[G, BH, 3] bundle histogram -> [F, B, 3] per-feature
                view; skipped default bins come from leaf totals
                (FixHistogram, dataset.cpp:928-947)."""
                flat = hist_g.reshape(-1, NUM_HIST_STATS)
                safe = jnp.clip(emap, 0, flat.shape[0] - 1)
                out = flat[safe] * (emap >= 0)[:, :, None]
                totals = jnp.stack([sg, sh, cnt.astype(jnp.float32)])
                fix = totals[None, :] - jnp.sum(out, axis=1)
                # the count channel must stay an exact integer or the
                # min_data_in_leaf guards flip on reconstruction noise
                fix = fix.at[:, 2].set(jnp.round(fix[:, 2]))
                return out + edef[:, :, None] * fix[:, None, :]
        buckets = self._buckets_for(root_padded)
        nbk = len(buckets)
        finder = self.finder
        nb_dev, db_dev, mt_dev = self._nb_dev, self._db_dev, self._mt_dev
        chunk = int(cfg.tpu_hist_chunk)
        precision = self.hist_precision
        # ---- quantized histogram payload (tpu_quant_hist): gradients are
        # stochastic-rounded to int8/int16 ONCE per tree, so every per-leaf
        # gather moves quarter/half the f32 bytes; finished histograms and
        # root sums are rescaled back to gradient units by the pack scale.
        # int8 fits a SINGLE bf16 pass exactly (|q| <= 127), so the hi/lo
        # split is dropped too — half the MXU work on top of the bandwidth.
        quant_bits = self.quant_bits
        quant_on = quant_bits > 0
        if quant_on and quant_bits == 8 and precision == "bf16x2":
            precision = "bf16"
        qseed = int(cfg.data_random_seed)
        # mutable closure slot for the per-call pack scale (same pattern as
        # coupled_box below): set when the entry packs the payload, read by
        # the hist/sum rescale sites inside the same trace
        qscale_box = [jnp.ones((2,), jnp.float32)]

        def _gh_payload(grad, hess, opt):
            """Stack (and optionally quantize) the [N, 2] payload; returns
            (gh, remaining_opt) with the qseq operand consumed."""
            gh = jnp.stack([grad, hess], axis=1)
            if not quant_on:
                return gh, opt
            qseq, opt = opt[0], opt[1:]
            key = jax.random.fold_in(jax.random.PRNGKey(qseed), qseq)
            q, scale = quantize_gh(gh, quant_bits, key)
            qscale_box[0] = scale
            return q, opt

        depth_limit = self._depth_limit
        mono_dev = jnp.asarray(self.meta["monotone"], jnp.int32)

        # ---- CEGB on the device path (reference CalculateOndemandCosts,
        # serial_tree_learner.cpp:488-568): split penalty scales with the
        # leaf's (global) row count; coupled penalties charge a feature
        # once per model, tracked by a [F] used-mask carried through the
        # tree loop. Per-(row, feature) LAZY penalties keep the host twin
        # (forces_host_learner).
        cegb_on = (cfg.cegb_penalty_split > 0
                   or len(cfg.cegb_penalty_feature_coupled) > 0)
        cegb_coupled_on = len(cfg.cegb_penalty_feature_coupled) > 0
        cegb_tr = float(cfg.cegb_tradeoff)
        cegb_sp = float(cfg.cegb_penalty_split) * cegb_tr
        # coupled penalties charge a feature once per MODEL: features
        # used by EARLIER trees arrive zeroed in the per-call
        # coupled_eff array (see _cegb_coupled_eff / _cegb_note_record);
        # the in-loop used-mask handles this tree's own first uses
        assert not (cegb_coupled_on and self.parallel_mode != "serial"), \
            "coupled CEGB routes to the host twin off the serial learner"

        # ---- forced splits (reference ForceSplits, serial_tree_learner
        # .cpp:597-755): the JSON prefix flattens to node arrays; a BFS
        # queue rides the tree-loop state, each pop overriding the
        # gain-driven leaf/split choice with the node's (feature,
        # threshold) evaluated AT-threshold from the leaf histogram
        # (GatherInfoForThreshold, feature_histogram.hpp:290+). A node
        # whose forced threshold leaves an empty child is skipped like
        # the host twin does.
        fnodes = self._forced_nodes()
        MF = len(fnodes)
        MFq = max(MF, 1)
        fF_dev = jnp.asarray([x[0] for x in fnodes] or [0], jnp.int32)
        fT_dev = jnp.asarray([x[1] for x in fnodes] or [0], jnp.int32)
        fL_dev = jnp.asarray([x[2] for x in fnodes] or [-1], jnp.int32)
        fR_dev = jnp.asarray([x[3] for x in fnodes] or [-1], jnp.int32)
        l1_hp = float(self.hyper.lambda_l1)
        l2_hp = float(self.hyper.lambda_l2)

        def forced_info(ph, sg, sh, cntg, f, thr):
            """BF/BI payload rows for a forced split AT (f, thr) from the
            parent's [F, B, 3] histogram — mirrors the host twin's
            _forced_split_info bit-for-bit in f32."""
            row = ph[f]                                     # [B, 3]
            nbf = nb_dev[f]
            hi = jnp.minimum(thr + 1, nbf)
            m = (jnp.arange(B, dtype=jnp.int32) < hi)[:, None]
            sums = jnp.sum(jnp.where(m, row, 0.0), axis=0)
            lg, lh, lcf = sums[0], sums[1], sums[2]
            nan_adj = (mt_dev[f] == 2) & (hi > nbf - 1)
            last = row[jnp.clip(nbf - 1, 0, B - 1)]
            lg = lg - jnp.where(nan_adj, last[0], 0.0)
            lh = lh - jnp.where(nan_adj, last[1], 0.0)
            lcf = lcf - jnp.where(nan_adj, last[2], 0.0)
            lc = jnp.round(lcf).astype(jnp.int32)
            rg, rh = sg - lg, sh - lh
            rc = cntg - lc

            def tl1(sv):
                return jnp.sign(sv) * jnp.maximum(jnp.abs(sv) - l1_hp, 0.0)

            def pgain(sv, hv):
                return jnp.where(hv + l2_hp > 0,
                                 tl1(sv) ** 2 / (hv + l2_hp), 0.0)

            def outp(sv, hv):
                return jnp.where(hv + l2_hp > 0,
                                 -tl1(sv) / (hv + l2_hp), 0.0)

            gain = pgain(lg, lh) + pgain(rg, rh) - pgain(sg, sh)
            vF = jnp.zeros(BF_W, jnp.float32)
            vF = vF.at[BF_GAIN].set(gain)
            vF = vF.at[BF_LG].set(lg)
            vF = vF.at[BF_LH].set(lh)
            vF = vF.at[BF_RG].set(rg)
            vF = vF.at[BF_RH].set(rh)
            vF = vF.at[BF_LOUT].set(outp(lg, lh))
            vF = vF.at[BF_ROUT].set(outp(rg, rh))
            vI = jnp.zeros(BI_W, jnp.int32)
            vI = vI.at[BI_FEAT].set(f)
            vI = vI.at[BI_THR].set(thr)
            vI = vI.at[BI_LC].set(lc)
            vI = vI.at[BI_RC].set(rc)
            return vF, vI

        mode = self.parallel_mode
        nd = self.mesh_size if mode == "feature" else 1
        f_block = F // nd if mode == "feature" else F
        if mode == "voting":
            vote_k = max(1, min(int(cfg.top_k), F))
            vote_sel = min(2 * vote_k, F)
            # local searches relax min_data/min_hessian by the machine count
            # (reference voting_parallel_tree_learner.cpp:58-59)
            m = max(1, self.mesh_size)
            hyper_local = self.hyper._replace(
                min_data_in_leaf=max(1, self.hyper.min_data_in_leaf // m),
                min_sum_hessian_in_leaf=(
                    self.hyper.min_sum_hessian_in_leaf / m))
            finder_local = make_split_finder(hyper_local, self.meta, B)


        def _feature_block_hist(rows, gh, valid):
            if mode != "feature":
                h = histogram_from_gathered_gh(rows, gh, valid, BH,
                                               chunk, precision)
                if quant_on:
                    # back to gradient units: grad/hess columns by the pack
                    # scale, count column untouched (exact integers)
                    h = h * jnp.concatenate(
                        [qscale_box[0], jnp.ones((1,), jnp.float32)])
                return h
            # feature-parallel: each shard histograms only its feature block
            # (reference feature_parallel_tree_learner.cpp:33-52 work
            # division); the psum that follows assembles the global
            # histogram, subsuming SyncUpGlobalBestSplit
            start = lax.axis_index(self.axis_name) * f_block
            size = rows.shape[0]
            rows = lax.dynamic_slice(rows, (jnp.int32(0), start),
                                     (size, f_block))
            hb = histogram_from_gathered_gh(rows, gh, valid, BH, chunk,
                                            precision)
            if hb.dtype == jnp.float64:
                with jax.enable_x64(True):
                    full = jnp.zeros((F, B, NUM_HIST_STATS), jnp.float64)
                    return lax.dynamic_update_slice(
                        full, hb, (start, jnp.int32(0), jnp.int32(0)))
            full = jnp.zeros((F, B, NUM_HIST_STATS), jnp.float32)
            return lax.dynamic_update_slice(
                full, hb, (start, jnp.int32(0), jnp.int32(0)))

        def hist_bucket(size):
            def fn(bins, indices, gh, begin, count):
                idx = lax.dynamic_slice(indices, (begin,), (size,))
                pos = jnp.arange(size, dtype=jnp.int32)
                valid = pos < count
                safe = jnp.where(valid, idx, 0)
                return _feature_block_hist(bins[safe], gh[safe], valid)
            return fn

        def part_bucket(size):
            def fn(bins_col, indices, begin, count, threshold, default_left,
                   missing_type, default_bin, num_bin, is_cat, bitset,
                   boff, bpk):
                return split_partition(indices, bins_col, begin, count, size,
                                       threshold, default_left, missing_type,
                                       default_bin, num_bin, is_cat, bitset,
                                       boff, bpk)
            return fn

        hist_fns = [hist_bucket(s) for s in buckets]
        part_fns = [part_bucket(s) for s in buckets]
        col_dev = self._col_dev
        boff_dev = self._boff_dev
        bpk_dev = self._bpk_dev
        axis = self.axis_name

        # Collective placement by mode (all ride ICI as XLA all-reduces;
        # every shard takes identical split decisions so the collective
        # schedules never diverge):
        #   data:    histograms psum'd (ReduceScatter analogue); row-local
        #            scalars psum'd (root-sums allreduce)
        #   feature: block histograms psum'd into the global histogram
        #            (subsumes SyncUpGlobalBestSplit); rows replicated so
        #            scalars are already global
        #   voting:  histograms stay LOCAL (only elected features are
        #            reduced, inside eval_leaf); row-local scalars psum'd
        # Under precision == "f64" the partials entering a collective are
        # exact, so psum(partials) == serial total in f64; the single
        # f64→f32 rounding AFTER the reduce makes every downstream value
        # bit-identical across topologies (the byte-equal parity contract
        # of dist/runtime.py).
        def _gsum_hist(x):
            if axis is not None and mode in ("data", "feature"):
                x = lax.psum(x, axis)
            if x.dtype == jnp.float64:
                x = x.astype(jnp.float32)
            return x

        def _gsum_scalar(x):
            if axis is not None and mode in ("data", "voting"):
                x = lax.psum(x, axis)
            if x.dtype == jnp.float64:
                x = x.astype(jnp.float32)
            return x

        # loop budget: num_leaves-1 splits (0 when num_leaves == 1); Lm1 is
        # only the (>=1) record-array length
        split_budget = max(L - 1, 0)

        # local row count for fresh (identity-partition) builds: static for
        # replicated-row modes, per-shard via axis_index for rows-sharded
        rows_sharded = axis is not None and mode in ("data", "voting")
        per_shard_rows = (int(math.ceil(self.n / max(self.mesh_size, 1)))
                          if rows_sharded else self.n)

        coupled_box = [jnp.zeros((F,), jnp.float32)]

        def build_fresh(bins, bins_T, grad, hess, feature_mask_f32, *opt):
            """Fresh-tree entry: creates the identity partition internally
            (one fused program instead of init-partition + build
            dispatches); only valid without bagging.

            Trailing variadic operands, in order: the per-tree qseq (when
            quant_on) then coupled_eff (when coupled CEGB is on) — both
            consumed positionally so the donation/in_specs plumbing never
            sees optional keywords."""
            n_pad = per_shard_rows + max(_pow2ceil(per_shard_rows),
                                         self.min_pad)
            pos = jnp.arange(n_pad, dtype=jnp.int32)
            if rows_sharded:
                s = lax.axis_index(axis)
                cnt = jnp.clip(self.n - s * per_shard_rows, 0,
                               per_shard_rows).astype(jnp.int32)
            else:
                cnt = jnp.int32(per_shard_rows)
            indices = jnp.where(pos < cnt, pos, 0)
            gh, opt = _gh_payload(grad, hess, opt)
            return _build(bins, bins_T, indices, gh, cnt, feature_mask_f32,
                          *opt)

        def build(bins, bins_T, indices, grad, hess, root_count,
                  feature_mask_f32, *opt):
            gh, opt = _gh_payload(grad, hess, opt)
            return _build(bins, bins_T, indices, gh, root_count,
                          feature_mask_f32, *opt)

        def _build(bins, bins_T, indices, gh, root_count, feature_mask_f32,
                   coupled_eff=None):
            compile_cache.note_trace()
            if cegb_coupled_on:
                coupled_box[0] = coupled_eff

            def _mask_gain(gain, depth):
                gain = jnp.where(feature_mask_f32 > 0, gain, NEG_INF)
                return jnp.where(depth >= depth_limit,
                                 jnp.full_like(gain, NEG_INF), gain)

            _payload = pack_best_payload

            def _cegb_pen(cnt, used, coupled_eff):
                """Per-feature CEGB gain penalty for one leaf."""
                pen = cegb_sp * cnt.astype(jnp.float32)
                if cegb_coupled_on:
                    pen = pen + coupled_eff * (1.0 - used)
                return pen

            if mode == "voting":
                # PV-Tree (reference voting_parallel_tree_learner.cpp:
                # 262-400): local top-k vote -> global vote -> reduce only
                # the elected features' histograms -> global best split.
                # `hist` here is this shard's LOCAL histogram of the leaf.
                def eval_leaf(hist, sg, sh, cnt, minc, maxc, depth,
                              used=None):
                    # local leaf sums: every row lands in exactly one bin of
                    # feature 0, so its histogram column sums to the local
                    # totals (no FixHistogram-style bin skipping here)
                    lsg = jnp.sum(hist[0, :, 0])
                    lsh = jnp.sum(hist[0, :, 1])
                    lcnt = jnp.sum(hist[0, :, 2]).astype(jnp.int32)
                    lout = finder_local(hist, lsg, lsh, lcnt, minc, maxc)
                    lgain = _mask_gain(lout["gain"], depth)
                    _, top_idx = lax.top_k(lgain, vote_k)
                    # votes weighted by local data share (GlobalVoting
                    # weighting, voting_parallel_tree_learner.cpp:170-200)
                    votes = jnp.zeros((F,), jnp.float32).at[top_idx].add(
                        1.0 + lcnt.astype(jnp.float32))
                    votes = lax.psum(votes, axis)
                    _, sel_idx = lax.top_k(votes, vote_sel)  # same on all
                    hist_sel = lax.psum(hist[sel_idx], axis)
                    ghist = jnp.zeros_like(hist).at[sel_idx].set(hist_sel)
                    out = finder(ghist, sg, sh, cnt, minc, maxc)
                    selmask = jnp.zeros((F,), bool).at[sel_idx].set(True)
                    gain = jnp.where(selmask, out["gain"], NEG_INF)
                    if cegb_on:
                        gain = gain - _cegb_pen(cnt, used, coupled_box[0])
                    return _payload(out, _mask_gain(gain, depth))
            else:
                def eval_leaf(hist, sg, sh, cnt, minc, maxc, depth,
                              used=None):
                    if bundled:
                        hist = expand_hist(hist, sg, sh, cnt)
                    out = finder(hist, sg, sh, cnt, minc, maxc)
                    gain = out["gain"]
                    if cegb_on:
                        gain = gain - _cegb_pen(cnt, used, coupled_box[0])
                    return _payload(out, _mask_gain(gain, depth))

            # ---------- root ----------
            if root_contiguous:
                # identity partition: read the head of bins/grad/hess
                # directly (static slice, no gather); pow2 padding can
                # exceed the physical row count, so clamp statically
                rp = min(root_padded, bins.shape[0], gh.shape[0])
                pos = jnp.arange(rp, dtype=jnp.int32)
                valid = pos < root_count
                rows = lax.slice(bins, (0, 0), (rp, bins.shape[1]))
                gh0 = lax.slice(gh, (0, 0), (rp, 2))
                root_hist = _feature_block_hist(rows, gh0, valid)
                masked = jnp.where(valid[:, None],
                                   gh0.astype(jnp.float32), 0.0)
                if precision == "f64":
                    # exact root sums: the partials entering the root-sums
                    # allreduce must be order-independent (see _gsum_scalar)
                    with jax.enable_x64(True):
                        sums = jnp.sum(masked.astype(jnp.float64), axis=0)
                        root_g, root_h = sums[0], sums[1]
                else:
                    sums = jnp.sum(masked, axis=0)
                    root_g, root_h = sums[0], sums[1]
            else:
                bsel = self._bucket_index(root_count, buckets)
                root_hist = lax.switch(
                    bsel, hist_fns, bins, indices, gh, jnp.int32(0),
                    root_count)
                root_g, root_h = _masked_sums(indices, gh, root_count,
                                              root_padded,
                                              f64=precision == "f64")
            if quant_on:
                qs = qscale_box[0]
                root_g = root_g * qs[0]
                root_h = root_h * qs[1]
            root_hist = _gsum_hist(root_hist)
            # root grad/hess sums (data-parallel: the root-sums allreduce,
            # data_parallel_tree_learner.cpp:120-145)
            root_g, root_h = _gsum_scalar(root_g), _gsum_scalar(root_h)
            root_count_g = _gsum_scalar(root_count)

            # ---------- packed state ----------
            ncols = F if not bundled else len(
                np.asarray(self.ds.bundles.group_num_bin))
            # histogram_pool_size (reference HistogramPool,
            # feature_histogram.hpp:654-829): the reference bounds the
            # per-leaf histogram cache in MB with LRU + recompute. The
            # TPU store is one [L, F, B, 3] array; the budget ladder is
            # f32 store -> bf16 store (subtract upcasts to f32) ->
            # RECOMPUTE mode (no per-leaf store at all: both children
            # are histogrammed directly at each split, the analogue of
            # an always-missing pool — up to ~2x histogram work, O(1)
            # histogram memory).
            store_dtype = jnp.float32
            pool_recompute = False
            pool_mb = float(cfg.histogram_pool_size)
            if pool_mb > 0:
                f32_mb = L * ncols * BH * NUM_HIST_STATS * 4 / 2**20
                if f32_mb > pool_mb:
                    store_dtype = jnp.bfloat16
                    if f32_mb / 2 > pool_mb:
                        pool_recompute = True
            store_L = 1 if pool_recompute else L
            hist_store = jnp.zeros((store_L, ncols, BH, NUM_HIST_STATS),
                                   store_dtype)
            if not pool_recompute:
                hist_store = hist_store.at[0].set(
                    root_hist.astype(store_dtype))
            leafF = jnp.zeros((L, LF_W), jnp.float32)
            leafF = leafF.at[:, LF_MINC].set(-jnp.inf)
            leafF = leafF.at[:, LF_MAXC].set(jnp.inf)
            leafF = leafF.at[0, LF_SG].set(root_g)
            leafF = leafF.at[0, LF_SH].set(root_h)
            leafI = jnp.zeros((L, LI_W), jnp.int32)
            leafI = leafI.at[0, LI_COUNT].set(root_count)
            leafI = leafI.at[0, LI_COUNTG].set(root_count_g)
            bestF = jnp.full((L, BF_W), NEG_INF, jnp.float32)
            bestI = jnp.zeros((L, BI_W), jnp.int32)
            bestB = jnp.zeros((L, 8), jnp.uint32)
            recF = jnp.zeros((Lm1, RF_W), jnp.float32)
            recI = jnp.zeros((Lm1, RI_W), jnp.int32)
            recB = jnp.zeros((Lm1, 8), jnp.uint32)

            used0 = jnp.zeros((F,), jnp.float32)
            rvF, rvI, rvB = eval_leaf(
                root_hist, root_g, root_h, root_count_g,
                jnp.float32(-jnp.inf), jnp.float32(jnp.inf), jnp.int32(0),
                used0)
            bestF = bestF.at[0].set(rvF)
            bestI = bestI.at[0].set(rvI)
            bestB = bestB.at[0].set(rvB)

            # forced-split BFS queue (node 0 seeded at the root leaf) +
            # CEGB used-feature mask ride the loop state; both are tiny
            # and inert when the features are off
            fq_leaf0 = jnp.zeros((MFq + 1,), jnp.int32)
            fq_node0 = jnp.zeros((MFq + 1,), jnp.int32)
            state = (jnp.int32(0), indices, leafF, leafI, hist_store,
                     bestF, bestI, bestB, recF, recI, recB, used0,
                     jnp.int32(0), jnp.int32(1 if MF else 0),
                     fq_leaf0, fq_node0)

            def cond(state):
                s = state[0]
                bestF = state[5]
                forced_pending = state[12] < state[13]
                return (s < split_budget) \
                    & ((jnp.max(bestF[:, BF_GAIN]) > 0.0) | forced_pending)

            def body(state):
                (s, indices, leafF, leafI, hist_store, bestF, bestI, bestB,
                 recF, recI, recB, used, fh, ft, fq_leaf, fq_node) = state
                bl = jnp.argmax(bestF[:, BF_GAIN]).astype(jnp.int32)
                new_leaf = s + 1
                act = jnp.bool_(True)
                forced_mode = jnp.bool_(False)
                nid = jnp.int32(0)
                if MF:
                    # pop the BFS queue ahead of gain-driven selection
                    # (ForceSplits runs before normal growth)
                    forced_mode = fh < ft
                    qp = jnp.clip(fh, 0, MFq)
                    nid = jnp.clip(fq_node[qp], 0, MF - 1)
                    bl = jnp.where(forced_mode, fq_leaf[qp], bl)
                bF = bestF[bl]
                bI = bestI[bl]
                bB = bestB[bl]
                if MF:
                    # AT-threshold split info from the parent histogram,
                    # under lax.cond so split iterations after the queue
                    # drains skip the (possibly recomputed) histogram

                    def _forced_payload(_):
                        sgp = leafF[bl, LF_SG]
                        shp = leafF[bl, LF_SH]
                        cntp = leafI[bl, LI_COUNTG]
                        if pool_recompute:
                            bkp = self._bucket_index(leafI[bl, LI_COUNT],
                                                     buckets)
                            ph = lax.switch(bkp, hist_fns, bins, indices,
                                            gh, leafI[bl, LI_BEGIN],
                                            leafI[bl, LI_COUNT])
                            ph = _gsum_hist(ph)
                        else:
                            ph = hist_store[bl].astype(jnp.float32)
                        if bundled:
                            ph = expand_hist(ph, sgp, shp, cntp)
                        return forced_info(ph, sgp, shp, cntp,
                                           fF_dev[nid], fT_dev[nid])

                    def _no_payload(_):
                        return (jnp.zeros(BF_W, jnp.float32),
                                jnp.zeros(BI_W, jnp.int32))

                    fvF, fvI = lax.cond(forced_mode, _forced_payload,
                                        _no_payload, operand=None)
                    bF = jnp.where(forced_mode, fvF, bF)
                    bI = jnp.where(forced_mode, fvI, bI)
                    bB = jnp.where(forced_mode, jnp.zeros_like(bB), bB)
                    # a forced threshold that empties a child is skipped
                    # (host twin: min(left_c, right_c) < 1 -> continue)
                    act = jnp.where(
                        forced_mode,
                        jnp.minimum(fvI[BI_LC], fvI[BI_RC]) >= 1, True)
                f = bI[BI_FEAT]
                thr = bI[BI_THR]
                dleft = bI[BI_DEFLEFT] != 0
                iscat = bI[BI_ISCAT] != 0
                begin = leafI[bl, LI_BEGIN]
                count = leafI[bl, LI_COUNT]
                # GLOBAL child counts come from the (already psum-reduced)
                # histogram's count channel — exact integers in f32.
                # "Smaller" is decided on GLOBAL counts so every shard
                # histograms the same child (the reference uses
                # GetGlobalDataCountInLeaf the same way,
                # data_parallel_tree_learner.cpp:198-220).
                left_cnt_g = bI[BI_LC]
                right_cnt_g = bI[BI_RC]
                smaller_is_left = left_cnt_g <= right_cnt_g
                # contiguous column read from the transposed bins (the
                # feature's STORAGE column under bundling)
                bins_col = lax.dynamic_slice(
                    bins_T, (col_dev[f], jnp.int32(0)),
                    (1, bins_T.shape[1]))[0]
                bk = self._bucket_index(count, buckets)
                new_indices, left_cnt = lax.switch(
                    bk, part_fns, bins_col, indices, begin, count, thr,
                    dleft, mt_dev[f], db_dev[f], nb_dev[f], iscat, bB,
                    boff_dev[f], bpk_dev[f])
                right_cnt = count - left_cnt

                # ---- packed record row
                rowF = jnp.stack([bF[BF_LOUT], bF[BF_ROUT], bF[BF_GAIN],
                                  leafF[bl, LF_VALUE]])
                rowI = jnp.zeros(RI_W, jnp.int32)
                rowI = rowI.at[RI_LEAF].set(bl)
                rowI = rowI.at[RI_FEAT].set(f)
                rowI = rowI.at[RI_THR].set(thr)
                rowI = rowI.at[RI_DEFLEFT].set(bI[BI_DEFLEFT])
                rowI = rowI.at[RI_ISCAT].set(bI[BI_ISCAT])
                rowI = rowI.at[RI_LC].set(left_cnt_g)
                rowI = rowI.at[RI_RC].set(right_cnt_g)
                recF = recF.at[s].set(jnp.where(act, rowF, recF[s]))
                recI = recI.at[s].set(jnp.where(act, rowI, recI[s]))
                recB = recB.at[s].set(jnp.where(act, bB, recB[s]))

                # ---- children bookkeeping (two packed-row writes)
                depth = leafI[bl, LI_DEPTH] + 1
                # monotone constraint propagation
                if self._mono_any:
                    mono = mono_dev[f]
                    mid = (bF[BF_LOUT] + bF[BF_ROUT]) / 2.0
                    minc0 = leafF[bl, LF_MINC]
                    maxc0 = leafF[bl, LF_MAXC]
                    lmax = jnp.where(mono > 0, jnp.minimum(maxc0, mid), maxc0)
                    rmin = jnp.where(mono > 0, jnp.maximum(minc0, mid), minc0)
                    lmin = jnp.where(mono < 0, jnp.maximum(minc0, mid), minc0)
                    rmax = jnp.where(mono < 0, jnp.minimum(maxc0, mid), maxc0)
                else:
                    lmin = rmin = leafF[bl, LF_MINC]
                    lmax = rmax = leafF[bl, LF_MAXC]
                lrowF = jnp.zeros(LF_W, jnp.float32)
                lrowF = lrowF.at[LF_SG].set(bF[BF_LG])
                lrowF = lrowF.at[LF_SH].set(bF[BF_LH])
                lrowF = lrowF.at[LF_MINC].set(lmin)
                lrowF = lrowF.at[LF_MAXC].set(lmax)
                lrowF = lrowF.at[LF_VALUE].set(bF[BF_LOUT])
                rrowF = jnp.zeros(LF_W, jnp.float32)
                rrowF = rrowF.at[LF_SG].set(bF[BF_RG])
                rrowF = rrowF.at[LF_SH].set(bF[BF_RH])
                rrowF = rrowF.at[LF_MINC].set(rmin)
                rrowF = rrowF.at[LF_MAXC].set(rmax)
                rrowF = rrowF.at[LF_VALUE].set(bF[BF_ROUT])
                leafF = leafF.at[bl].set(jnp.where(act, lrowF, leafF[bl]))
                leafF = leafF.at[new_leaf].set(
                    jnp.where(act, rrowF, leafF[new_leaf]))
                lrowI = jnp.stack([begin, left_cnt, left_cnt_g, depth,
                                   jnp.int32(0), jnp.int32(0), jnp.int32(0),
                                   jnp.int32(0)])
                rrowI = jnp.stack([begin + left_cnt, right_cnt, right_cnt_g,
                                   depth, jnp.int32(0), jnp.int32(0),
                                   jnp.int32(0), jnp.int32(0)])
                leafI = leafI.at[bl].set(jnp.where(act, lrowI, leafI[bl]))
                leafI = leafI.at[new_leaf].set(
                    jnp.where(act, rrowI, leafI[new_leaf]))

                # histogram the smaller child (by GLOBAL counts, so every
                # shard histograms the same child); larger = parent - smaller
                # (FeatureHistogram::Subtract)
                sm_begin = jnp.where(smaller_is_left, begin,
                                     begin + left_cnt)
                sm_count = jnp.where(smaller_is_left, left_cnt, right_cnt)
                bk2 = self._bucket_index(sm_count, buckets)
                sm_hist = lax.switch(bk2, hist_fns, bins, new_indices,
                                     gh, sm_begin, sm_count)
                sm_hist = _gsum_hist(sm_hist)
                if pool_recompute:
                    # pool budget below the bf16 store: no per-leaf
                    # cache — histogram the larger child directly too
                    # (the reference's pool-miss recompute path)
                    lg_begin = jnp.where(smaller_is_left,
                                         begin + left_cnt, begin)
                    lg_count = jnp.where(smaller_is_left, right_cnt,
                                         left_cnt)
                    bk3 = self._bucket_index(lg_count, buckets)
                    lg_hist = lax.switch(bk3, hist_fns, bins,
                                         new_indices, gh, lg_begin,
                                         lg_count)
                    lg_hist = _gsum_hist(lg_hist)
                else:
                    lg_hist = hist_store[bl].astype(jnp.float32) - sm_hist
                left_hist = jnp.where(smaller_is_left, sm_hist, lg_hist)
                right_hist = jnp.where(smaller_is_left, lg_hist, sm_hist)
                if not pool_recompute:
                    hist_store = hist_store.at[bl].set(jnp.where(
                        act, left_hist.astype(hist_store.dtype),
                        hist_store[bl]))
                    hist_store = hist_store.at[new_leaf].set(jnp.where(
                        act, right_hist.astype(hist_store.dtype),
                        hist_store[new_leaf]))

                # CEGB: the committed split's feature becomes "used"
                # (coupled penalty drops to zero from here on)
                if cegb_on:
                    used = used.at[f].set(jnp.where(act, 1.0, used[f]))

                # evaluate both children (global counts)
                lF, lI, lB = eval_leaf(left_hist, bF[BF_LG], bF[BF_LH],
                                       left_cnt_g, lmin, lmax, depth,
                                       used)
                rF, rI, rB = eval_leaf(right_hist, bF[BF_RG], bF[BF_RH],
                                       right_cnt_g, rmin, rmax, depth,
                                       used)
                bestF = bestF.at[bl].set(jnp.where(act, lF, bestF[bl]))
                bestF = bestF.at[new_leaf].set(
                    jnp.where(act, rF, bestF[new_leaf]))
                bestI = bestI.at[bl].set(jnp.where(act, lI, bestI[bl]))
                bestI = bestI.at[new_leaf].set(
                    jnp.where(act, rI, bestI[new_leaf]))
                bestB = bestB.at[bl].set(jnp.where(act, lB, bestB[bl]))
                bestB = bestB.at[new_leaf].set(
                    jnp.where(act, rB, bestB[new_leaf]))

                if MF:
                    # advance the queue: pop, and push surviving children
                    # left-then-right (host BFS order); the left child
                    # keeps leaf bl, the right child is new_leaf
                    acti = act.astype(jnp.int32)
                    nl = fL_dev[nid]
                    nr = fR_dev[nid]
                    p1 = forced_mode & act & (nl >= 0)
                    t1 = jnp.clip(ft, 0, MFq)
                    fq_leaf = fq_leaf.at[t1].set(
                        jnp.where(p1, bl, fq_leaf[t1]))
                    fq_node = fq_node.at[t1].set(
                        jnp.where(p1, nl, fq_node[t1]))
                    ft = ft + p1.astype(jnp.int32)
                    p2 = forced_mode & act & (nr >= 0)
                    t2 = jnp.clip(ft, 0, MFq)
                    fq_leaf = fq_leaf.at[t2].set(
                        jnp.where(p2, new_leaf, fq_leaf[t2]))
                    fq_node = fq_node.at[t2].set(
                        jnp.where(p2, nr, fq_node[t2]))
                    ft = ft + p2.astype(jnp.int32)
                    fh = fh + forced_mode.astype(jnp.int32)
                else:
                    acti = 1

                return (s + acti, new_indices, leafF, leafI, hist_store,
                        bestF, bestI, bestB, recF, recI, recB, used,
                        fh, ft, fq_leaf, fq_node)

            (n_splits, indices, leafF, leafI, hist_store, bestF, bestI,
             bestB, recF, recI, recB, _used, _fh, _ft, _fql, _fqn) = \
                lax.while_loop(cond, body, state)

            record = TreeRecord(
                num_splits=n_splits,
                leaf=recI[:, RI_LEAF], feature=recI[:, RI_FEAT],
                threshold_bin=recI[:, RI_THR],
                default_left=recI[:, RI_DEFLEFT] != 0,
                is_cat=recI[:, RI_ISCAT] != 0,
                cat_bitset=recB,
                left_output=recF[:, RF_LOUT],
                right_output=recF[:, RF_ROUT],
                left_count=recI[:, RI_LC], right_count=recI[:, RI_RC],
                gain=recF[:, RF_GAIN], internal_value=recF[:, RF_IVAL],
                leaf_value=leafF[:, LF_VALUE],
                leaf_count_arr=leafI[:, LI_COUNTG],
                leaf_begin=leafI[:, LI_BEGIN],
                leaf_cnt_part=leafI[:, LI_COUNT])
            return indices, record

        fn = build_fresh if root_contiguous else build
        if self.axis_name is not None:
            return fn  # caller wraps in shard_map + jit
        if root_contiguous:
            return jax.jit(fn)
        return jax.jit(fn, donate_argnums=(2,))

    # ------------------------------------------------------------------
    def aligned_mode_ok(self, objective) -> bool:
        """True when the chunk-aligned pipeline (`aligned_builder.py`) can
        run: TPU pallas (or interpret mode for tests), a pointwise
        single-class objective, serial parallelism; numerical AND
        categorical features, with or without bagging (round 4)."""
        return self.aligned_mode_gate(objective) is None

    @property
    def aligned_shard_rows(self) -> int:
        """Rows of the aligned engine's largest shard: ceil(n / shards)
        under data-parallel, else n. The move kernel's SMEM budget and
        its 16-bit chunk ids hold per device, so the chunk and the chunk
        count follow this and not the rows of the whole mesh."""
        if self.parallel_mode == "data":
            return -(-self.n // max(self.mesh_size, 1))
        return self.n

    def aligned_mode_gate(self, objective):
        """First failing aligned-pipeline gate as a short name, or None
        when every gate passes. The gate rationale (VERDICT r5 #8: path
        observability) lives with each check; `aligned_mode_ok` is the
        boolean view."""
        mode = self.cfg.tpu_grow_mode
        if mode not in ("auto", "aligned"):
            return f"tpu_grow_mode={mode}"
        if self.cfg.sequential_device_only:
            # forced splits / CEGB need the sequential fused loop
            return "sequential-only features (forced splits/CEGB)"
        if (str(self.cfg.tpu_quant_hist).lower() == "on"
                and getattr(self, "quant_bits", 0) > 0):
            # explicit "on" means the user wants the quantized MXU hist
            # path, which lives on the fused leaf-wise builder; under
            # "auto" the aligned engine keeps priority and quantization
            # simply stays inactive there
            return "tpu_quant_hist=on (quantized hist rides the fused path)"
        from ..ops.aligned import aligned_available
        if not (bool(self.cfg.tpu_aligned_interpret) or aligned_available()):
            return "pallas kernels unavailable (no TPU, interpret off)"
        from ..ops.aligned import aligned_num_chunks
        from .level_builder import spec_slots
        S = spec_slots(self.cfg.num_leaves,
                       float(getattr(self.cfg, "tpu_level_spec", 1.5)))
        nc = aligned_num_chunks(self.aligned_shard_rows, self.cfg, S,
                                self.num_features)
        if self.parallel_mode not in ("serial", "data"):
            return f"parallel_mode={self.parallel_mode}"
        # multiclass deferred-application machinery (and its fallback)
        # stays serial-only for now
        if not (self.parallel_mode == "serial"
                or (objective is not None
                    and objective.num_model_per_iteration == 1)):
            return "multiclass under data-parallel"
        # EFB bundles ride natively (round 5): records pack the <= 256-bin
        # bundle columns, routing unpacks in-kernel, per-feature
        # histograms expand at eval only. packed-prefetch limits: 16-bit
        # destination chunk ids (NC <= 65535 at the EFFECTIVE chunk size,
        # ~67M rows at C=1024) and 8-bit word selectors (features <=
        # 1020). Above 2^24 rows the histograms' counts stay exact i32
        # (ops/aligned.py COUNT_UNIT), and so does the layout they place
        if nc > 65535:
            return f"chunk count {nc} > 65535"
        if self.num_features > 1020:
            return f"num_features {self.num_features} > 1020"
        if self.ds.bins_dtype() != np.uint8:
            return "bins not uint8"
        if self.num_features <= 0:
            return "no features"
        if self.cfg.num_leaves < 2:
            return "num_leaves < 2"
        if self.max_bin_global > 256 or self.hist_bins > 256:
            return "max_bin > 256"
        if objective is None:
            return "no objective"
        if objective.num_model_per_iteration != 1:
            # multiclass rides K score lanes + lane-wise in-program
            # gradients (compact layout only: the meta-lane rid keeps the
            # 2^24-row cap there)
            if objective.num_model_per_iteration > 127:
                return "num_class > 127"
            if objective.mc_lane_mode() is None:
                return "objective lacks a multiclass lane mode"
            if self.n > (1 << 24):
                return "multiclass above 2^24 rows"
        # non-pointwise objectives pay a row-order gradient round-trip
        # (materialize + gather); the ext record layout (round 5) plus the
        # [K]-compact hist/eval path made this a win at the MSLR shape
        # (2.27M x 137 at 63 bins: 562 vs the fused 1264 ms/iter).
        # The old slot-block VMEM budget clause is GONE: oversized
        # stores (wide-F x 255-bin) now spill to HBM behind the move
        # pass's DMA staging ring instead of faulting (see
        # aligned_gate_notes), so only the row floor remains; forced
        # tpu_grow_mode=aligned bypasses it.
        if not (objective.point_grad_fn() is not None
                or objective.num_model_per_iteration > 1
                or self.n >= 1_000_000
                or mode == "aligned"):
            return "non-pointwise objective below the row floor"
        return None

    def aligned_gate_notes(self):
        """INFO notes about HOW the aligned path will run — distinct
        from aligned_mode_gate, whose non-None return means the path is
        NOT taken. Today: the slot-hist HBM spill. Spilling is not a
        fallback (the kernels still run aligned, the store just streams
        through the 2-deep VMEM DMA ring), so it must not surface as a
        gate failure — but a run whose histograms moved to HBM is a
        different performance regime, and path observability (VERDICT
        r5 #8) requires the log to say so."""
        from ..ops.aligned import hist_layout
        from .level_builder import spec_slots
        notes = []
        try:
            bh = self.hist_bins if self.bundled else self.max_bin_global
            ncols = (len(np.asarray(self.ds.bundles.group_num_bin))
                     if self.bundled else self.num_features)
            import os
            kcap = int(os.environ.get("LGBT_KCAP", "0") or 0) or 256
            S = spec_slots(self.cfg.num_leaves,
                           float(getattr(self.cfg, "tpu_level_spec", 1.5)))
            K = min(max(S - 1, 1), kcap)
            subbin, spill, slot_bytes, budget = hist_layout(
                self.cfg, ncols, bh, K)
            if spill:
                notes.append(
                    f"slot-hist spilled to HBM ({slot_bytes >> 10} KB/"
                    f"slot x {K + 1} slots > {budget >> 20} MB)")
        except Exception:       # notes are best-effort observability
            pass
        return notes

    def aligned_engine(self, objective, init_row_scores=None,
                       bagged=False, num_class=1, bag_multiplier=False,
                       bag_device=False):
        """The persistent AlignedEngine for (this learner, objective)."""
        eng = getattr(self, "_aligned_eng", None)
        if eng is None or eng.objective is not objective \
                or getattr(eng, "bagged", False) != bagged \
                or getattr(eng, "num_class", 1) != num_class \
                or eng.bag_multiplier != bag_multiplier \
                or eng.bag_device != bag_device:
            from .aligned_builder import AlignedEngine
            eng = AlignedEngine(
                self, objective,
                interpret=bool(self.cfg.tpu_aligned_interpret),
                init_row_scores=init_row_scores, bagged=bagged,
                num_class=num_class, bag_multiplier=bag_multiplier,
                bag_device=bag_device)
            self._aligned_eng = eng
        return eng

    def drop_aligned_engine(self):
        self._aligned_eng = None

    # ------------------------------------------------------------------
    def _forced_nodes(self):
        """Forced-splits JSON flattened to (used_feature, threshold_bin,
        left_node, right_node) tuples (indices into the list; -1 = no
        child). Nodes on unused features drop with their subtrees, like
        the host twin (serial_learner._apply_forced_splits)."""
        if not self.cfg.forcedsplits_filename:
            return []
        import json as _json
        with open(self.cfg.forcedsplits_filename) as fh:
            root = _json.load(fh)
        out = []

        def flat(node):
            if not isinstance(node, dict) or "feature" not in node:
                return -1
            real_f = int(node["feature"])
            fmap = self.ds.used_feature_map
            f = int(fmap[real_f]) if real_f < len(fmap) else -1
            if f < 0:
                return -1
            idx = len(out)
            out.append(None)
            thr = int(self.mappers[f].values_to_bins(
                np.asarray([float(node["threshold"])]))[0])
            lft = flat(node.get("left"))
            rgt = flat(node.get("right"))
            out[idx] = (f, thr, lft, rgt)
            return idx

        flat(root)
        return out

    # ------------------------------------------------------------------
    def init_root_partition(self, bag_indices, bag_cnt: int):
        """Fresh root partition for one boosting iteration (the analogue of
        `DataPartition::Init`, data_partition.hpp:59)."""
        from ..ops.partition import init_partition, init_partition_from
        n_pad = self.n + max(_pow2ceil(self.n), self.min_pad)
        if bag_indices is not None:
            return (init_partition_from(jnp.asarray(bag_indices), n_pad),
                    bag_cnt)
        return init_partition(self.n, n_pad), self.n

    def _fmask_arr(self, feature_mask: Optional[np.ndarray]) -> jax.Array:
        if feature_mask is None:
            return jnp.ones(self.num_features, jnp.float32)
        return jnp.asarray(feature_mask.astype(np.float32))

    # -- coupled-CEGB per-model state -----------------------------------
    @property
    def _cegb_coupled_on(self) -> bool:
        return len(self.cfg.cegb_penalty_feature_coupled) > 0

    def _cegb_coupled_eff(self) -> jax.Array:
        """Per-call coupled penalties with already-used features zeroed
        (the host mirror of the reference's once-per-model charge)."""
        if getattr(self, "_cegb_used_np", None) is None:
            self._cegb_used_np = np.zeros(self.num_features, bool)
        arr = np.asarray(self.cfg.cegb_penalty_feature_coupled, np.float64)
        real = np.asarray(self.ds.real_feature_idx)
        cp = np.zeros(self.num_features, np.float32)
        cp[:len(real)] = arr[real] * float(self.cfg.cegb_tradeoff)
        cp[self._cegb_used_np] = 0.0
        return jnp.asarray(cp)

    def _cegb_note_record(self, rec: TreeRecord) -> None:
        """Mark the tree's committed split features used (one small
        device pull; only coupled-CEGB configs pay it)."""
        if not self._cegb_coupled_on:
            return
        k = int(rec.num_splits)
        feats = np.asarray(rec.feature)[:k]
        if getattr(self, "_cegb_used_np", None) is None:
            self._cegb_used_np = np.zeros(self.num_features, bool)
        self._cegb_used_np[feats] = True

    def train(self, grad: jax.Array, hess: jax.Array,
              indices: jax.Array, root_count: int,
              feature_mask: Optional[np.ndarray] = None
              ) -> Tuple[jax.Array, TreeRecord]:
        """Grow one tree on an explicit (e.g. bagged) partition; returns
        (new partition indices, TreeRecord). `indices` must be padded so
        begin+bucket_size never overflows (length n + pow2ceil(n))."""
        from ..obs import trace as obs_trace
        root_padded = max(_pow2ceil(root_count), self.min_pad)
        fn = self._cached_program(
            (root_padded, False),
            lambda: self._make_build_fn(root_padded, False))
        args = [self.bins_dev, self.bins_T_dev, indices, grad, hess,
                jnp.int32(root_count), self._fmask_arr(feature_mask)]
        if self.quant_bits:
            args.append(jnp.int32(self._next_qseq()))
        if self._cegb_coupled_on:
            args.append(self._cegb_coupled_eff())
        with obs_trace.span("learner.train", root=root_padded):
            idxs, rec = fn(*args)
        self._cegb_note_record(rec) if self._cegb_coupled_on else None
        return idxs, rec

    def train_fresh(self, grad: jax.Array, hess: jax.Array,
                    feature_mask: Optional[np.ndarray] = None
                    ) -> Tuple[jax.Array, TreeRecord]:
        """Grow one tree on the full data with a fresh identity partition
        (created inside the program — fewer dispatches, contiguous root
        histogram)."""
        if self.level_mode_ok():
            out = self._level_train_fresh(grad, hess, feature_mask)
            if out is not None:
                return out
        from ..obs import trace as obs_trace
        root_padded = max(_pow2ceil(self.n), self.min_pad)
        fn = self._cached_program(
            (root_padded, True),
            lambda: self._make_build_fn(root_padded, True))
        args = [self.bins_dev, self.bins_T_dev, grad, hess,
                self._fmask_arr(feature_mask)]
        if self.quant_bits:
            args.append(jnp.int32(self._next_qseq()))
        if self._cegb_coupled_on:
            args.append(self._cegb_coupled_eff())
        with obs_trace.span("learner.train_fresh", root=root_padded):
            idxs, rec = fn(*args)
        if self._cegb_coupled_on:
            self._cegb_note_record(rec)
        return idxs, rec

    def sweep_build_fn(self, root_padded: int, root_contiguous: bool,
                       l1, l2, l2c):
        """Raw (un-jitted) whole-tree build with the split lambdas threaded
        as traced scalars — the sweep trainer's per-model build lane.

        Must be called INSIDE an active trace (the sweep round program)
        with `l1`/`l2`/`l2c` tracers: the split finder is rebuilt around a
        hyper whose lambda fields are those tracers, `_make_build_fn`
        captures it, and `self.finder` is restored before returning. The
        raw python body is returned (not the jitted wrapper) so the
        enable_x64 blocks inside `_build` execute live during the caller's
        vmap trace — vmapping the cached jitted program re-canonicalizes
        the f64 reduce inits to f32, which XLA rejects as mixed precision.
        """
        hyper_t = self.hyper._replace(lambda_l1=l1, lambda_l2=l2,
                                      lambda_l2_cat=l2c)
        old_finder = self.finder
        self.finder = make_split_finder(hyper_t, self.meta,
                                        self.max_bin_global)
        try:
            # _make_build_fn captures self.finder into a local; restoring
            # the static finder afterwards does not disturb the closure
            return self._make_build_fn(root_padded, root_contiguous
                                       ).__wrapped__
        finally:
            self.finder = old_finder

    def train_iter_fused(self, score: jax.Array, objective, scale: float,
                         feature_mask: Optional[np.ndarray] = None
                         ) -> Tuple[jax.Array, jax.Array, TreeRecord]:
        """ONE device program for a whole boosting iteration (single-class,
        no bagging): objective gradients -> fused tree build -> partition
        score update. The three stages are traced together to save per-program
        launch latency; the score buffer
        is donated through.

        Returns (new_score [K,N], indices, record).
        """
        if self.level_mode_ok():
            out = self._level_iter_fused(score, objective, scale,
                                         feature_mask)
            if out is not None:
                return out
        root_padded = max(_pow2ceil(self.n), self.min_pad)
        # the fused step closes over the objective's gradient program,
        # which captures label/weight device data — the objective's
        # trace signature (content-hashed data) keys the shared program
        key = (root_padded, "iter_fused", objective.trace_signature())

        def factory():
            build = self._make_build_fn(root_padded, True)
            n_rows = self.n

            def step(score, bins, bins_T, scale, fmask, *opt):
                # bins ride as runtime args (not closure constants) so
                # the program is data-independent and registry-shareable;
                # *opt forwards the (qseq?, coupled_eff?) tail untouched
                compile_cache.note_trace()
                gdev, hdev = objective.gradients_impl(score)
                # nested jitted calls inline into this trace
                indices, rec = build(bins, bins_T, gdev[0], hdev[0],
                                     fmask, *opt)
                new_score = _partition_score_update(
                    score, jnp.int32(0), rec.leaf_begin,
                    rec.leaf_cnt_part, rec.leaf_value, indices,
                    jnp.int32(n_rows), scale)
                return new_score, indices, rec

            return jax.jit(step, donate_argnums=(0,))

        fn = self._cached_program(key, factory)
        args = [score, self.bins_dev, self.bins_T_dev, jnp.float32(scale),
                self._fmask_arr(feature_mask)]
        if self.quant_bits:
            args.append(jnp.int32(self._next_qseq()))
        if self._cegb_coupled_on:
            args.append(self._cegb_coupled_eff())
        out = fn(*args)
        if self._cegb_coupled_on:
            self._cegb_note_record(out[2])
        return out

    def _level_iter_fused(self, score, objective, scale, feature_mask):
        """Level-mode iteration: program A traces gradients + speculative
        build; the leaf-wise replay runs on host; program B applies the
        block score update. Returns None when the replay was inexact (the
        caller then runs the sequential leaf-wise fused path)."""
        from .level_builder import replay_leafwise
        key = ("level_iterA", objective.trace_signature())

        def factory():
            level = self._level_fn()

            def stepA(score, words, fmask):
                compile_cache.note_trace()
                gdev, hdev = objective.gradients_impl(score)
                return level(words, gdev[0], hdev[0], fmask)

            return jax.jit(stepA)

        fnA = self._cached_program(key, factory)
        spec = fnA(score, self.words_dev, self._fmask_arr(feature_mask))
        host = jax.device_get(spec._replace(rid=None))
        rec, exact = replay_leafwise(host, self.cfg.num_leaves)
        if not exact:
            self._level_fallbacks = getattr(self, "_level_fallbacks", 0) + 1
            return None
        rec = rec._replace(block_begin=spec.block_begin,
                           block_cnt=spec.block_cnt)
        new_score = _partition_score_update(
            score, jnp.int32(0), spec.block_begin, spec.block_cnt,
            jnp.asarray(rec.block_value, jnp.float32), spec.rid,
            jnp.int32(self.n), jnp.float32(scale))
        return new_score, spec.rid, rec

    # ------------------------------------------------------------------
    def record_to_tree(self, rec_host, shrinkage: float = 1.0) -> Tree:
        """Host-side conversion of a pulled TreeRecord into a full Tree
        (bin thresholds -> real values via the BinMappers)."""
        n_splits = int(rec_host.num_splits)
        tree = Tree(self.cfg.num_leaves)
        mt_code = {"none": 0, "zero": 1, "nan": 2}
        for s in range(n_splits):
            leaf = int(rec_host.leaf[s])
            f = int(rec_host.feature[s])
            mapper = self.mappers[f]
            real_feature = int(self.ds.real_feature_idx[f])
            mt = mt_code[mapper.missing_type]
            if bool(rec_host.is_cat[s]):
                words = rec_host.cat_bitset[s]
                bins_list = [b for b in range(min(mapper.num_bin, 256))
                             if (int(words[b // 32]) >> (b % 32)) & 1]
                cats = [mapper.bin_2_categorical[b] for b in bins_list
                        if b < len(mapper.bin_2_categorical)]
                tree.split_categorical(
                    leaf, f, real_feature, bins_list, cats,
                    float(rec_host.left_output[s]),
                    float(rec_host.right_output[s]),
                    int(rec_host.left_count[s]),
                    int(rec_host.right_count[s]),
                    float(rec_host.gain[s]), mt,
                    default_bin=mapper.default_bin, num_bin=mapper.num_bin)
            else:
                thr_bin = int(rec_host.threshold_bin[s])
                tree.split(
                    leaf, f, real_feature, thr_bin,
                    mapper.bin_to_value(thr_bin),
                    float(rec_host.left_output[s]),
                    float(rec_host.right_output[s]),
                    int(rec_host.left_count[s]),
                    int(rec_host.right_count[s]),
                    float(rec_host.gain[s]), mt,
                    bool(rec_host.default_left[s]),
                    default_bin=mapper.default_bin, num_bin=mapper.num_bin)
        if shrinkage != 1.0:
            tree.apply_shrinkage(shrinkage)
        return tree


@functools.partial(jax.jit, donate_argnums=(0,))
def _partition_score_update(score, class_id, leaf_begin, leaf_cnt,
                            leaf_value, indices, count, scale):
    """One fused program: leaf fill over the partition + key-sort back to
    row order + score[class_id] += scale * delta."""
    compile_cache.note_trace()
    n = score.shape[1]
    # leaf slices all live inside [0, n): fill and sort only that prefix
    fill = leaf_value_fill(leaf_begin, leaf_cnt, leaf_value, n)
    delta = unpermute_to_rows(lax.slice(indices, (0,), (n,)), fill, count, n)
    return score.at[class_id].add(scale * delta)


def _masked_sums(indices, gh, count, padded: int, f64: bool = False):
    # Deliberately NOT @jax.jit: the only call site is inside `_build`'s
    # trace, and a nested pjit re-canonicalizes the f64 reduce init to f32
    # when the enclosing program is vmapped (sweep mode), which XLA rejects
    # as mixed precision. Inline tracing keeps the enable_x64 block live.
    idx = lax.dynamic_slice(indices, (jnp.int32(0),), (padded,))
    pos = jnp.arange(padded, dtype=jnp.int32)
    valid = pos < count
    safe = jnp.where(valid, idx, 0)
    # explicit f32: the quantized path passes int8/int16 gh rows (the
    # caller rescales the sums by the pack scale afterwards)
    masked = jnp.where(valid[:, None], gh[safe].astype(jnp.float32), 0.0)
    if f64:
        with jax.enable_x64(True):
            s = jnp.sum(masked.astype(jnp.float64), axis=0)
            return s[0], s[1]
    s = jnp.sum(masked, axis=0)
    return s[0], s[1]


# ---------------------------------------------------------------------------
# device score update from a TreeRecord
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("max_nodes",))
def traversal_arrays(rec: TreeRecord, max_nodes: int):
    """Build device traversal arrays (feature/threshold/children) from a
    TreeRecord — the on-device analogue of `stack_trees`."""
    compile_cache.note_trace()
    left, right = record_to_children(rec.leaf, rec.num_splits, max_nodes)
    return {
        "feature": rec.feature, "threshold_bin": rec.threshold_bin,
        "default_left": rec.default_left, "is_cat": rec.is_cat,
        "cat_bitset": rec.cat_bitset, "left": left, "right": right,
        "num_splits": rec.num_splits, "leaf_value": rec.leaf_value,
    }


@jax.jit
def traverse_record(bins: jax.Array, trav: Dict, nb, db, mt,
                    col=None, boff=None, bpk=None) -> jax.Array:
    """[N] leaf index per row for one TreeRecord's tree over binned data.
    nb/db/mt: per-feature num_bin/default_bin/missing arrays; col/boff/bpk
    map features to bundled storage columns (EFB, io/bundling.py)."""
    compile_cache.note_trace()
    n = bins.shape[0]

    def cond(node):
        return jnp.any(node >= 0)

    def body(node):
        safe = jnp.maximum(node, 0)
        feat = trav["feature"][safe]
        scol = feat if col is None else col[feat]
        fval = bins[jnp.arange(n), scol].astype(jnp.int32)
        if boff is not None:
            from ..ops.partition import bundle_unpack
            fval = bundle_unpack(fval, boff[feat], bpk[feat], db[feat],
                                 nb[feat])
        gl_num = numerical_goes_left(fval, trav["threshold_bin"][safe],
                                     trav["default_left"][safe], mt[feat],
                                     db[feat], nb[feat])
        bitsets = trav["cat_bitset"][safe]  # [N, 8]
        in_words = (fval >> 5) < 8
        word = jnp.clip(fval >> 5, 0, 7)
        w = jnp.take_along_axis(bitsets, word[:, None], axis=1)[:, 0]
        gl_cat = (((w >> (fval & 31).astype(jnp.uint32)) & 1) != 0) & in_words
        goes_left = jnp.where(trav["is_cat"][safe], gl_cat, gl_num)
        nxt = jnp.where(goes_left, trav["left"][safe], trav["right"][safe])
        return jnp.where(node >= 0, nxt, node)

    node0 = jnp.where(trav["num_splits"] > 0, jnp.zeros(n, jnp.int32),
                      jnp.full(n, -1, jnp.int32))
    node = lax.while_loop(cond, body, node0)
    return ~node


@jax.jit
def add_record_score(score_row: jax.Array, bins: jax.Array, trav: Dict,
                     nb, db, mt, scale, col=None, boff=None,
                     bpk=None) -> jax.Array:
    """score += scale * tree(x) for all rows via record traversal."""
    compile_cache.note_trace()
    leaves = traverse_record(bins, trav, nb, db, mt, col, boff, bpk)
    return score_row + scale * trav["leaf_value"][leaves]
