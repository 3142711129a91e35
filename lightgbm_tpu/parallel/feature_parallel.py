"""Feature-parallel tree learning over a `jax.sharding.Mesh`.

TPU-native re-design of the reference `FeatureParallelTreeLearner`
(`src/treelearner/feature_parallel_tree_learner.cpp`): every shard holds ALL
rows (the reference's "every worker holds all data" premise, `:33-52`), but
histogram construction — the dominant cost — is divided by contiguous
feature blocks: shard i builds the histograms of features
``[i*F/nd, (i+1)*F/nd)`` and one `lax.psum` assembles the full global
histogram on every shard. Because each shard then holds identical global
state, the best split is found redundantly and bit-identically everywhere —
the histogram reduce subsumes the reference's `SyncUpGlobalBestSplit`
allreduce (`:55-71`, `parallel_tree_learner.h:190-213`) — and the partition
update is computed locally with no further communication, exactly like the
reference workers each applying the synced split.

The feature axis is zero-padded to a multiple of the mesh size; padded
features are trivial (masked out of every search).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import Config
from ..io.dataset import Dataset
from ..models.device_learner import DeviceTreeLearner, TreeRecord, _pow2ceil
from .data_parallel import default_mesh


class FeatureParallelTreeLearner:
    """Feature-blocks-sharded fused tree learner; same train() surface as
    `DeviceTreeLearner` (the factory axis of tree_learner.cpp:13-36)."""

    def __init__(self, cfg: Config, dataset: Dataset,
                 mesh: Optional[Mesh] = None) -> None:
        self.axis_name = "feature"
        self.mesh = mesh if mesh is not None else default_mesh(
            cfg.num_machines if cfg.num_machines > 1 else None,
            self.axis_name)
        self.nd = int(self.mesh.devices.size)
        f = dataset.num_features
        f_pad = int(math.ceil(max(f, 1) / self.nd)) * self.nd
        self.inner = DeviceTreeLearner(cfg, dataset,
                                       axis_name=self.axis_name,
                                       parallel_mode="feature",
                                       feature_pad_to=f_pad,
                                       mesh_size=self.nd)
        self.cfg = cfg
        self.ds = dataset
        self.n = dataset.num_data
        bins_np = np.asarray(dataset.bins)
        if f_pad > f:
            bins_np = np.pad(bins_np, ((0, 0), (0, f_pad - f)))
        # rows replicated on every shard (reference: full data per worker)
        self.bins_repl = jax.device_put(
            bins_np, NamedSharding(self.mesh, P()))
        self._fn_cache = {}

    # --- delegation: GBDT uses these off the learner ------------------
    def __getattr__(self, name):
        if name == "inner":
            raise AttributeError(name)
        return getattr(self.inner, name)

    # ------------------------------------------------------------------
    def init_root_partition(self, bag_indices: Optional[np.ndarray],
                            bag_cnt: int):
        """Replicated full-row partition (identical on every shard)."""
        return self.inner.init_root_partition(bag_indices, bag_cnt)

    # ------------------------------------------------------------------
    def _sharded_train_fn(self, root_padded: int, root_contiguous: bool):
        key = (root_padded, root_contiguous)
        fn = self._fn_cache.get(key)
        if fn is not None:
            return fn
        build = self.inner._make_build_fn(root_padded, root_contiguous)
        rec_specs = TreeRecord(*([P()] * len(TreeRecord._fields)))
        n_in = 5 if root_contiguous else 7
        mapped = jax.shard_map(
            build, mesh=self.mesh,
            in_specs=tuple([P()] * n_in),
            out_specs=(P(), rec_specs),
            check_vma=False)
        fn = jax.jit(mapped)
        self._fn_cache[key] = fn
        return fn

    def add_score(self, score_row: jax.Array, trav, scale: float) -> jax.Array:
        return self.inner.add_score(score_row, trav, scale)

    # ------------------------------------------------------------------
    def train(self, grad: jax.Array, hess: jax.Array, indices: jax.Array,
              root_count: int, feature_mask: Optional[np.ndarray] = None
              ) -> Tuple[jax.Array, TreeRecord]:
        root_padded = max(_pow2ceil(int(root_count)), self.inner.min_pad)
        if feature_mask is None:
            feature_mask = self.inner.feature_mask()
        fn = self._sharded_train_fn(root_padded, False)
        return fn(self.bins_repl, self.inner.bins_T_dev, indices, grad, hess,
                  jnp.int32(root_count), self.inner._fmask_arr(feature_mask))

    def train_fresh(self, grad: jax.Array, hess: jax.Array,
                    feature_mask: Optional[np.ndarray] = None
                    ) -> Tuple[jax.Array, TreeRecord]:
        root_padded = max(_pow2ceil(self.n), self.inner.min_pad)
        if feature_mask is None:
            feature_mask = self.inner.feature_mask()
        fn = self._sharded_train_fn(root_padded, True)
        return fn(self.bins_repl, self.inner.bins_T_dev, grad, hess,
                  self.inner._fmask_arr(feature_mask))
