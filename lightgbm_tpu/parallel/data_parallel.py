"""Data-parallel tree learning over a `jax.sharding.Mesh`.

TPU-native re-design of the reference `DataParallelTreeLearner`
(`src/treelearner/data_parallel_tree_learner.cpp`): rows are sharded in
contiguous blocks over a 1-D ``("data",)`` mesh axis; each shard keeps a
LOCAL leaf partition (its slice of every leaf's rows) and builds local
histograms, which are summed across shards with `lax.psum` inside
`shard_map` — the XLA-collective replacement for
`Network::ReduceScatter(SumReducer)` + `SyncUpGlobalBestSplit`
(data_parallel_tree_learner.cpp:149-164, parallel_tree_learner.h:190-213).
Because every shard then holds the full GLOBAL histogram, split selection is
computed redundantly and bit-identically on all shards, so no second
collective is needed; only global leaf counts (the reference's
`global_data_count_in_leaf_`) ride along in the tree-build state.

The whole tree still grows in ONE jitted SPMD program (zero mid-tree host
syncs); `jit` + `shard_map` partitions it over the mesh, and XLA lowers the
psums to ICI all-reduces on real hardware.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import Config
from ..io.dataset import Dataset
from ..models.device_learner import DeviceTreeLearner, TreeRecord, _pow2ceil


def default_mesh(num_shards: Optional[int] = None,
                 axis_name: str = "data") -> Mesh:
    devs = jax.devices()
    if num_shards is not None:
        devs = devs[:num_shards]
    return Mesh(np.asarray(devs), (axis_name,))


class DataParallelTreeLearner:
    """Rows-sharded fused tree learner; same train() surface as
    `DeviceTreeLearner` so the GBDT driver is parallelism-agnostic
    (the reference crosses {serial,data,...}x{cpu,gpu} the same way,
    tree_learner.cpp:13-36)."""

    mode = "data"

    def __init__(self, cfg: Config, dataset: Dataset,
                 mesh: Optional[Mesh] = None) -> None:
        self.axis_name = "data"
        self.mesh = mesh if mesh is not None else default_mesh(
            cfg.num_machines if cfg.num_machines > 1 else None,
            self.axis_name)
        self.nd = int(self.mesh.devices.size)
        self.inner = DeviceTreeLearner(cfg, dataset, axis_name=self.axis_name,
                                       parallel_mode=self.mode,
                                       mesh_size=self.nd)
        # the aligned engine shard_maps its programs over this mesh
        self.inner._mesh = self.mesh
        self.cfg = cfg
        self.ds = dataset
        n = dataset.num_data
        self.n = n
        self.per_shard = int(math.ceil(n / self.nd))
        self.local_pad = max(_pow2ceil(self.per_shard), self.inner.min_pad)
        self.local_idx_len = self.per_shard + self.local_pad
        self.pad_rows = self.nd * self.per_shard - n

        # sharded placement comes from the Dataset-level cache so an
        # early loader/CLI shard() and the learner share device buffers
        placed = dataset.shard(self.mesh, self.axis_name)
        self.bins_sharded = placed["bins"]
        self.bins_T_sharded = placed["bins_T"]
        self._row_shard = NamedSharding(self.mesh, P(self.axis_name))
        self._fn_cache = {}

    # --- delegation: GBDT uses these off the learner ------------------
    def __getattr__(self, name):
        if name == "inner":
            raise AttributeError(name)
        return getattr(self.inner, name)

    # ------------------------------------------------------------------
    def init_root_partition(self, bag_indices: Optional[np.ndarray],
                            bag_cnt: int) -> Tuple[jax.Array, jax.Array]:
        """Per-shard local partitions: shard s owns global rows
        [s*per, (s+1)*per); local indices are block-relative. The no-bagging
        identity partition is built ON DEVICE (a fresh iota per call — the
        train step donates/consumes the buffer), avoiding a per-tree
        host build + transfer."""
        if bag_indices is None:
            fn = self._fn_cache.get("identity_part")
            if fn is None:
                nd, per, llen, n = (self.nd, self.per_shard,
                                    self.local_idx_len, self.n)
                shard = self._row_shard

                def make():
                    pos = jnp.arange(nd * llen, dtype=jnp.int32)
                    local = pos % llen
                    s = pos // llen
                    cnt = jnp.minimum(
                        jnp.maximum(n - jnp.arange(nd, dtype=jnp.int32) * per,
                                    0), per)
                    idxs = jnp.where(local < cnt[s], local, 0)
                    return idxs, cnt

                fn = jax.jit(make, out_shardings=(shard, shard))
                self._fn_cache["identity_part"] = fn
            return fn()
        idxs = np.zeros((self.nd, self.local_idx_len), np.int32)
        counts = np.zeros(self.nd, np.int32)
        for s in range(self.nd):
            lo, hi = s * self.per_shard, (s + 1) * self.per_shard
            sel = bag_indices[(bag_indices >= lo) & (bag_indices < hi)]
            c = len(sel)
            idxs[s, :c] = (sel - lo).astype(np.int32)
            counts[s] = c
        shard = self._row_shard
        return (jax.device_put(idxs.reshape(-1), shard),
                jax.device_put(counts, shard))

    # ------------------------------------------------------------------
    def _sharded_train_fn(self, root_contiguous: bool):
        key = (self.local_pad, root_contiguous)
        fn = self._fn_cache.get(key)
        if fn is not None:
            return fn
        build = self.inner._make_build_fn(self.local_pad, root_contiguous)
        ax = self.axis_name
        # per-shard partition state (leaf_begin/leaf_cnt_part) stays sharded;
        # everything else is replicated (identical on every shard)
        rec_specs = TreeRecord(
            num_splits=P(), leaf=P(), feature=P(), threshold_bin=P(),
            default_left=P(), is_cat=P(), cat_bitset=P(), left_output=P(),
            right_output=P(), left_count=P(), right_count=P(), gain=P(),
            internal_value=P(), leaf_value=P(), leaf_count_arr=P(),
            leaf_begin=P(ax), leaf_cnt_part=P(ax))

        if root_contiguous:
            mapped = jax.shard_map(
                build, mesh=self.mesh,
                in_specs=(P(ax), P(None, ax), P(ax), P(ax), P()),
                out_specs=(P(ax), rec_specs),
                check_vma=False)

            def run_fresh(bins, bins_T, grad, hess, fmask):
                pad = self.nd * self.per_shard - grad.shape[0]
                if pad:
                    grad = jnp.pad(grad, (0, pad))
                    hess = jnp.pad(hess, (0, pad))
                return mapped(bins, bins_T, grad, hess, fmask)

            fn = jax.jit(run_fresh)
            self._fn_cache[key] = fn
            return fn

        def per_shard(bins, bins_T, indices, grad, hess, counts, fmask):
            return build(bins, bins_T, indices, grad, hess, counts[0], fmask)

        mapped = jax.shard_map(
            per_shard, mesh=self.mesh,
            in_specs=(P(ax), P(None, ax), P(ax), P(ax), P(ax), P(ax), P()),
            out_specs=(P(ax), rec_specs),
            check_vma=False)

        def run(bins, bins_T, indices, grad, hess, counts, fmask):
            pad = self.nd * self.per_shard - grad.shape[0]
            if pad:
                grad = jnp.pad(grad, (0, pad))
                hess = jnp.pad(hess, (0, pad))
            return mapped(bins, bins_T, indices, grad, hess, counts, fmask)

        fn = jax.jit(run, donate_argnums=(2,))
        self._fn_cache[key] = fn
        return fn

    # ------------------------------------------------------------------
    def _score_fn(self):
        fn = self._fn_cache.get("score")
        if fn is not None:
            return fn
        ax = self.axis_name
        from ..models.device_learner import traverse_record

        def per_shard(score, bins, trav, nb, db, mt, scale):
            leaves = traverse_record(bins, trav, nb, db, mt)
            return score + scale * trav["leaf_value"][leaves]

        mapped = jax.shard_map(
            per_shard, mesh=self.mesh,
            in_specs=(P(ax), P(ax), P(), P(), P(), P(), P()),
            out_specs=P(ax), check_vma=False)

        def run(score_row, trav, scale):
            pad = self.nd * self.per_shard - score_row.shape[0]
            padded = jnp.pad(score_row, (0, pad)) if pad else score_row
            out = mapped(padded, self.bins_sharded, trav,
                         self.inner._nb_dev, self.inner._db_dev,
                         self.inner._mt_dev, scale)
            return out[:score_row.shape[0]] if pad else out

        fn = jax.jit(run)
        self._fn_cache["score"] = fn
        return fn

    def add_score(self, score_row: jax.Array, trav, scale: float) -> jax.Array:
        """Sharded score update: each shard traverses only its row block."""
        return self._score_fn()(score_row, trav, jnp.float32(scale))

    def _partition_score_fn(self):
        fn = self._fn_cache.get("pscore")
        if fn is not None:
            return fn
        ax = self.axis_name
        from jax import lax

        from ..ops.partition import leaf_value_fill, unpermute_to_rows
        local_len = self.local_idx_len
        per = self.per_shard
        n = self.n

        def per_shard(score, leaf_begin, leaf_cnt, leaf_value, indices,
                      scale):
            s = lax.axis_index(ax)
            cnt = jnp.clip(n - s * per, 0, per).astype(jnp.int32)
            fill = leaf_value_fill(leaf_begin, leaf_cnt, leaf_value, per)
            delta = unpermute_to_rows(indices[:per], fill, cnt, per)
            return score + scale * delta

        mapped = jax.shard_map(
            per_shard, mesh=self.mesh,
            in_specs=(P(ax), P(ax), P(ax), P(), P(ax), P()),
            out_specs=P(ax), check_vma=False)

        def run(score_row, leaf_begin, leaf_cnt, leaf_value, indices, scale):
            pad = self.nd * per - score_row.shape[0]
            padded = jnp.pad(score_row, (0, pad)) if pad else score_row
            out = mapped(padded, leaf_begin, leaf_cnt, leaf_value, indices,
                         scale)
            return out[:score_row.shape[0]] if pad else out

        fn = jax.jit(run)
        self._fn_cache["pscore"] = fn
        return fn

    def add_score_from_partition(self, score: jax.Array, class_id: int,
                                 record: TreeRecord, indices: jax.Array,
                                 scale: float) -> jax.Array:
        """Partition-based score update, per shard: leaf fill over the local
        partition + one key-sort back to the shard's row-block order.
        Level-built records score through their (finer) block tables."""
        if record.block_begin is not None:
            row = self._partition_score_fn()(
                score[class_id], record.block_begin, record.block_cnt,
                jnp.asarray(record.block_value, jnp.float32), indices,
                jnp.float32(scale))
        else:
            row = self._partition_score_fn()(
                score[class_id], record.leaf_begin, record.leaf_cnt_part,
                record.leaf_value, indices, jnp.float32(scale))
        return score.at[class_id].set(row)

    # ------------------------------------------------------------------
    def train(self, grad: jax.Array, hess: jax.Array, indices: jax.Array,
              counts: jax.Array, feature_mask: Optional[np.ndarray] = None
              ) -> Tuple[jax.Array, TreeRecord]:
        fn = self._sharded_train_fn(False)
        return fn(self.bins_sharded, self.bins_T_sharded, indices, grad,
                  hess, counts, self.inner._fmask_arr(feature_mask))

    def train_fresh(self, grad: jax.Array, hess: jax.Array,
                    feature_mask: Optional[np.ndarray] = None
                    ) -> Tuple[jax.Array, TreeRecord]:
        if self.inner.level_mode_ok():
            from ..models.level_builder import replay_leafwise
            fn = self._sharded_level_fn()
            spec = fn(self._words_sharded(), grad, hess,
                      self.inner._fmask_arr(feature_mask))
            host = jax.device_get(spec._replace(rid=None))
            # leafI is per-shard [nd*S, w]; global lanes are identical, so
            # shard 0's slice serves the replay
            S = host.bestF.shape[0]
            host = host._replace(leafI=host.leafI[:S],
                                 block_begin=host.block_begin[:S],
                                 block_cnt=host.block_cnt[:S])
            rec, exact = replay_leafwise(host, self.cfg.num_leaves)
            if exact:
                rec = rec._replace(block_begin=spec.block_begin,
                                   block_cnt=spec.block_cnt)
                return spec.rid, rec
            self.inner._level_fallbacks = getattr(
                self.inner, "_level_fallbacks", 0) + 1
        fn = self._sharded_train_fn(True)
        return fn(self.bins_sharded, self.bins_T_sharded, grad, hess,
                  self.inner._fmask_arr(feature_mask))

    # ------------------------------------------------------------------
    def _words_sharded(self) -> jax.Array:
        w = self._fn_cache.get("words")
        if w is None:
            from ..models.level_builder import pack_bin_words
            bins_np = np.asarray(self.ds.bins)
            if self.inner.num_features != self.inner.num_real_features:
                pad_f = self.inner.num_features - self.inner.num_real_features
                bins_np = np.pad(bins_np, ((0, 0), (0, pad_f)))
            if self.pad_rows:
                bins_np = np.pad(bins_np, ((0, self.pad_rows), (0, 0)))
            w = jax.device_put(
                pack_bin_words(bins_np),
                NamedSharding(self.mesh, P(None, self.axis_name)))
            self._fn_cache["words"] = w
        return w

    def _sharded_level_fn(self):
        fn = self._fn_cache.get("level")
        if fn is not None:
            return fn
        from ..models.level_builder import SpecResult, make_level_build_fn
        build = make_level_build_fn(self.inner)
        ax = self.axis_name
        # split decisions are identical on every shard (global histograms);
        # only the physical partition state is shard-local
        spec_specs = SpecResult(
            rid=P(ax), n_exec=P(), execF=P(), execI=P(), execB=P(),
            bestF=P(), bestI=P(), bestB=P(), leafF=P(), leafI=P(ax),
            block_begin=P(ax), block_cnt=P(ax))
        mapped = jax.shard_map(
            build, mesh=self.mesh,
            in_specs=(P(None, ax), P(ax), P(ax), P()),
            out_specs=spec_specs,
            check_vma=False)

        def run(words, grad, hess, fmask):
            pad = self.nd * self.per_shard - grad.shape[0]
            if pad:
                grad = jnp.pad(grad, (0, pad))
                hess = jnp.pad(hess, (0, pad))
            return mapped(words, grad, hess, fmask)

        fn = jax.jit(run)
        self._fn_cache["level"] = fn
        return fn
