"""Binned dataset container.

Re-creates the reference `Dataset` / `Metadata` / `DatasetLoader` roles
(`src/io/dataset.cpp`, `src/io/metadata.cpp`, `src/io/dataset_loader.cpp`) in a
TPU-first layout: instead of per-feature-group `Bin` objects with scatter-add
hot loops, the binned matrix is one dense `uint8[num_data, num_features]`
array destined for HBM, and histogramming is a batched one-hot contraction
(see `ops/histogram.py`).

Host-side responsibilities kept here: sampling for bin finding
(`DatasetLoader::SampleTextDataFromMemory`), per-feature BinMapper
construction (distributed bin-finding allgather seam included), metadata
(label/weight/query/init_score, `src/io/metadata.cpp`), and binary
save/load (`Dataset::SaveBinaryFile`, `dataset_loader.cpp:268`).
"""
from __future__ import annotations

import io
import json
import struct
import warnings
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from ..config import Config
from ..obs import trace as obs_trace
from .binning import (BIN_CATEGORICAL, BIN_NUMERICAL, MISSING_NAN,
                      MISSING_NONE, MISSING_ZERO, BinMapper)

_BINARY_MAGIC = b"tpu_gbdt_dataset_v1\n"

_MISSING_CODE = {MISSING_NONE: 0, MISSING_ZERO: 1, MISSING_NAN: 2}
_BINTYPE_CODE = {BIN_NUMERICAL: 0, BIN_CATEGORICAL: 1}


class Metadata:
    """Labels, weights, query boundaries, init scores
    (reference `src/io/metadata.cpp`, `dataset.h:40-249`)."""

    def __init__(self, num_data: int) -> None:
        self.num_data = num_data
        self.label: Optional[np.ndarray] = None
        self.weight: Optional[np.ndarray] = None
        self.query_boundaries: Optional[np.ndarray] = None
        self.query_weights: Optional[np.ndarray] = None
        self.init_score: Optional[np.ndarray] = None

    def set_label(self, label: Sequence[float]) -> None:
        arr = np.asarray(label, dtype=np.float32).reshape(-1)
        if len(arr) != self.num_data:
            raise ValueError(
                f"label length {len(arr)} != num_data {self.num_data}")
        self.label = arr

    def set_weight(self, weight: Optional[Sequence[float]]) -> None:
        if weight is None:
            self.weight = None
            return
        arr = np.asarray(weight, dtype=np.float32).reshape(-1)
        if len(arr) != self.num_data:
            raise ValueError(
                f"weight length {len(arr)} != num_data {self.num_data}")
        self.weight = arr

    def set_group(self, group: Optional[Sequence[int]]) -> None:
        """Accepts group sizes (LightGBM convention) or query boundaries."""
        if group is None:
            self.query_boundaries = None
            return
        arr = np.asarray(group, dtype=np.int64).reshape(-1)
        if arr.sum() == self.num_data:
            self.query_boundaries = np.concatenate(
                [[0], np.cumsum(arr)]).astype(np.int64)
        elif len(arr) > 0 and arr[0] == 0 and arr[-1] == self.num_data:
            self.query_boundaries = arr
        else:
            raise ValueError("group sizes do not sum to num_data")

    def set_init_score(self, init_score: Optional[Sequence[float]]) -> None:
        if init_score is None:
            self.init_score = None
            return
        arr = np.asarray(init_score, dtype=np.float64).reshape(-1)
        if len(arr) % self.num_data != 0:
            raise ValueError("init_score length must be a multiple of num_data")
        self.init_score = arr

    @property
    def num_queries(self) -> int:
        if self.query_boundaries is None:
            return 0
        return len(self.query_boundaries) - 1


def _cat_set_from(cfg, categorical_feature):
    """Union of the categorical_feature argument and the config string
    (reference config categorical_feature handling)."""
    cat_set = set(int(c) for c in (categorical_feature or []))
    if cfg.categorical_feature:
        for tok in str(cfg.categorical_feature).split(","):
            tok = tok.strip()
            if tok.startswith("name:"):
                continue
            if tok:
                cat_set.add(int(tok))
    return cat_set


def _finalize_used_features(self, cfg, f):
    """used-feature map + per-used monotone/penalty arrays (shared by the
    dense and sparse constructors)."""
    self.used_feature_map = np.full(f, -1, dtype=np.int32)
    used = [j for j in range(f) if not self.mappers[j].is_trivial]
    for col_idx, j in enumerate(used):
        self.used_feature_map[j] = col_idx
    self.real_feature_idx = np.asarray(used, dtype=np.int32)
    mono = np.zeros(f, dtype=np.int8)
    for i, v in enumerate(cfg.monotone_constraints[:f]):
        mono[i] = np.int8(v)
    self.monotone_constraints = mono[self.real_feature_idx] \
        if len(used) else np.zeros(0, dtype=np.int8)
    pen = np.ones(f, dtype=np.float64)
    for i, v in enumerate(cfg.feature_contri[:f]):
        pen[i] = float(v)
    self.feature_penalty = pen[self.real_feature_idx] \
        if len(used) else np.zeros(0, dtype=np.float64)
    for j in self.real_feature_idx:
        m = self.mappers[j]
        if m.bin_type == BIN_CATEGORICAL and m.num_bin > 256:
            warnings.warn(
                f"categorical feature {j} has {m.num_bin} bins; only the "
                "256 most frequent categories are split candidates "
                "(device bitset limit)")


class Dataset:
    """Host-side binned dataset (reference `Dataset`, `dataset.h:250+`).

    Attributes
    ----------
    bins : np.ndarray uint8/uint16 [num_data, num_used_features]
        Binned matrix, feature-minor. Uploaded once to HBM by the learner.
    mappers : list[BinMapper]
        One per ORIGINAL feature column (trivial features have
        ``is_trivial=True`` and no column in ``bins``).
    used_feature_map : np.ndarray int32 [num_total_features]
        original feature -> column in bins, or -1 if unused
        (reference ``used_feature_map_``).
    """

    def __init__(self) -> None:
        self.bundles = None
        self._dev_bins = None  # HBM copy left behind by streaming ingest
        self.num_data: int = 0
        self.num_total_features: int = 0
        self._bins: Optional[np.ndarray] = None
        # True when the host matrix was dropped after sharding (the
        # device shards are authoritative); reading `.bins` re-gathers
        self._bins_freed: bool = False
        self.mappers: List[BinMapper] = []
        self.used_feature_map: np.ndarray = np.zeros(0, dtype=np.int32)
        self.real_feature_idx: np.ndarray = np.zeros(0, dtype=np.int32)
        self.feature_names: List[str] = []
        self.metadata: Metadata = Metadata(0)
        self.max_bin: int = 255
        self.min_data_in_bin: int = 3
        self.use_missing: bool = True
        self.zero_as_missing: bool = False
        self.monotone_constraints: np.ndarray = np.zeros(0, dtype=np.int8)
        self.feature_penalty: np.ndarray = np.zeros(0, dtype=np.float64)

    # ------------------------------------------------------------------
    @property
    def bins(self) -> Optional[np.ndarray]:
        """Host binned matrix. After `shard()` / stream-to-shard ingest
        the host copy is freed (the per-device shards are authoritative);
        the first host-side read re-gathers it from the mesh — a
        correctness fallback, not a hot path."""
        if self._bins is None and self._bins_freed:
            self._bins = self._regather_bins()
            self._bins_freed = False
        return self._bins

    @bins.setter
    def bins(self, value) -> None:
        self._bins = value
        self._bins_freed = False

    def _regather_bins(self) -> np.ndarray:
        cache = getattr(self, "_shard_cache", None)
        if cache is None:
            raise RuntimeError(
                "binned matrix was freed but no shard cache exists to "
                "re-gather it from")
        full = np.asarray(cache["bins"])      # [nd*per_shard, U] gather
        return np.ascontiguousarray(full[:self.num_data])

    @property
    def num_features(self) -> int:
        """Number of used (non-trivial) features."""
        if self._bins is not None:
            return self._bins.shape[1]
        cache = getattr(self, "_shard_cache", None)
        if cache is not None:
            return int(cache["bins"].shape[1])
        return 0

    def feature_num_bin(self, sub_feature: int) -> int:
        return self.mappers[self.real_feature_idx[sub_feature]].num_bin

    def used_mappers(self) -> List[BinMapper]:
        return [self.mappers[i] for i in self.real_feature_idx]

    # ------------------------------------------------------------------
    @classmethod
    def from_matrix(cls, data: np.ndarray, label: Optional[Sequence] = None,
                    config: Optional[Config] = None,
                    weight: Optional[Sequence] = None,
                    group: Optional[Sequence] = None,
                    init_score: Optional[Sequence] = None,
                    feature_names: Optional[List[str]] = None,
                    categorical_feature: Optional[Sequence[int]] = None,
                    reference: Optional["Dataset"] = None) -> "Dataset":
        """Build a binned dataset from a dense float matrix (the analogue of
        `LGBM_DatasetCreateFromMat` -> `CostructFromSampleData`,
        `src/c_api.cpp` / `dataset_loader.cpp:535`).

        When `reference` is given, reuse its bin mappers so validation data
        aligns with the training set (reference
        `LoadFromFileAlignWithOtherDataset`, `dataset_loader.cpp:224`).
        """
        cfg = config or Config()
        data = np.asarray(data)
        if data.dtype not in (np.float32, np.float64):
            data = data.astype(np.float64)
        if data.ndim != 2:
            raise ValueError("data must be 2-D")
        n, f = data.shape
        self = cls()
        self.num_data = n
        self.num_total_features = f
        self.metadata = Metadata(n)
        self.max_bin = cfg.max_bin
        self.min_data_in_bin = cfg.min_data_in_bin
        self.use_missing = cfg.use_missing
        self.zero_as_missing = cfg.zero_as_missing
        self.feature_names = (list(feature_names) if feature_names
                              else [f"Column_{i}" for i in range(f)])

        cat_set = set(int(c) for c in (categorical_feature or []))
        if cfg.categorical_feature:
            for tok in str(cfg.categorical_feature).split(","):
                tok = tok.strip()
                if tok.startswith("name:"):
                    continue
                if tok:
                    cat_set.add(int(tok))

        if reference is not None:
            self.mappers = reference.mappers
            self.used_feature_map = reference.used_feature_map
            self.real_feature_idx = reference.real_feature_idx
            self.max_bin = reference.max_bin
            self.monotone_constraints = reference.monotone_constraints
            self.feature_penalty = reference.feature_penalty
            self.feature_names = reference.feature_names
        elif getattr(cfg, "is_parallel_find_bin", False):
            # --- distributed global-sync bin finding: per-shard sample
            #     contributions merged in block order (dist/binning.py);
            #     boundaries are bitwise-equal to the single-host path
            from ..dist import runtime as dist_runtime
            from ..dist.binning import find_bin_mappers_distributed
            self.mappers, sync_stats = find_bin_mappers_distributed(
                data, cfg, cat_set, dist_runtime.num_shards(cfg))
            self._bin_sync_ms = float(sync_stats["bin_sync_ms"])
            _finalize_used_features(self, cfg, f)
        else:
            # --- sample rows for bin finding (reference
            #     bin_construct_sample_cnt, dataset_loader.cpp:162+)
            rng = np.random.RandomState(cfg.data_random_seed)
            sample_cnt = min(n, max(cfg.bin_construct_sample_cnt, 1))
            if sample_cnt < n:
                sample_idx = np.sort(rng.choice(n, sample_cnt, replace=False))
                sample = data[sample_idx]
            else:
                sample = data
            self.mappers = []
            for j in range(f):
                col = np.asarray(sample[:, j], dtype=np.float64)
                # keep only non-zero entries; zeros are implied by count
                nonzero = col[~((col >= -1e-35) & (col <= 1e-35))]
                m = BinMapper()
                bt = BIN_CATEGORICAL if j in cat_set else BIN_NUMERICAL
                m.find_bin(nonzero, total_sample_cnt=len(col),
                           max_bin=cfg.max_bin,
                           min_data_in_bin=cfg.min_data_in_bin,
                           min_split_data=cfg.min_data_in_leaf,
                           bin_type=bt, use_missing=cfg.use_missing,
                           zero_as_missing=cfg.zero_as_missing)
                self.mappers.append(m)
            self.used_feature_map = np.full(f, -1, dtype=np.int32)
            used = [j for j in range(f) if not self.mappers[j].is_trivial]
            for col_idx, j in enumerate(used):
                self.used_feature_map[j] = col_idx
            self.real_feature_idx = np.asarray(used, dtype=np.int32)
            # monotone constraints / feature penalties follow original index
            mono = np.zeros(f, dtype=np.int8)
            for i, v in enumerate(cfg.monotone_constraints[:f]):
                mono[i] = np.int8(v)
            self.monotone_constraints = mono[self.real_feature_idx] \
                if len(used) else np.zeros(0, dtype=np.int8)
            pen = np.ones(f, dtype=np.float64)
            for i, v in enumerate(cfg.feature_contri[:f]):
                pen[i] = float(v)
            self.feature_penalty = pen[self.real_feature_idx] \
                if len(used) else np.zeros(0, dtype=np.float64)

        # --- full binned ingest
        used = self.real_feature_idx
        for j in used:
            m = self.mappers[j]
            if m.bin_type == BIN_CATEGORICAL and m.num_bin > 256:
                warnings.warn(
                    f"categorical feature {j} has {m.num_bin} bins; only the "
                    "256 most frequent categories are split candidates "
                    "(device bitset limit)")
        max_nb = max((self.mappers[j].num_bin for j in used), default=2)
        dtype = np.uint8 if max_nb <= 256 else np.uint16
        bins = self._native_bin_matrix(data, used, dtype)
        if bins is None:
            bins = np.empty((n, len(used)), dtype=dtype)
            for col_idx, j in enumerate(used):
                bins[:, col_idx] = self.mappers[j].values_to_bins(
                    np.asarray(data[:, j], dtype=np.float64)).astype(dtype)
        self.bins = bins
        self._maybe_bundle(cfg, reference)

        if label is not None:
            self.metadata.set_label(label)
        self.metadata.set_weight(weight)
        self.metadata.set_group(group)
        self.metadata.set_init_score(init_score)
        return self

    # ------------------------------------------------------------------
    @classmethod
    def from_sparse(cls, data, label: Optional[Sequence] = None,
                    config: Optional[Config] = None,
                    weight: Optional[Sequence] = None,
                    group: Optional[Sequence] = None,
                    init_score: Optional[Sequence] = None,
                    feature_names: Optional[List[str]] = None,
                    categorical_feature: Optional[Sequence[int]] = None,
                    reference: Optional["Dataset"] = None) -> "Dataset":
        """Build a binned dataset from a scipy CSR/CSC matrix WITHOUT a
        dense float intermediate (the reference's CSR/CSC ingest,
        `LGBM_DatasetCreateFromCSR/CSC`, c_api.h:52-256; our analogue of
        `PushOneRow` keeps only per-column nonzeros + the uint8 output).

        Bin finding runs on each column's nonzeros (zeros are implied by
        count, exactly like the dense path's zero filter); the full
        ingest scatters per-column nonzero bins over a zero-bin
        background, so peak memory is nnz + the uint8 binned matrix.
        """
        cfg = config or Config()
        csc = data.tocsc()
        n, f = csc.shape
        self = cls()
        self.num_data = n
        self.num_total_features = f
        self.metadata = Metadata(n)
        self.max_bin = cfg.max_bin
        self.min_data_in_bin = cfg.min_data_in_bin
        self.use_missing = cfg.use_missing
        self.zero_as_missing = cfg.zero_as_missing
        self.feature_names = (list(feature_names) if feature_names
                              else [f"Column_{i}" for i in range(f)])
        cat_set = _cat_set_from(cfg, categorical_feature)

        if reference is not None:
            self.mappers = reference.mappers
            self.used_feature_map = reference.used_feature_map
            self.real_feature_idx = reference.real_feature_idx
            self.max_bin = reference.max_bin
            self.monotone_constraints = reference.monotone_constraints
            self.feature_penalty = reference.feature_penalty
            self.feature_names = reference.feature_names
        else:
            rng = np.random.RandomState(cfg.data_random_seed)
            sample_cnt = min(n, max(cfg.bin_construct_sample_cnt, 1))
            srows = (np.sort(rng.choice(n, sample_cnt, replace=False))
                     if sample_cnt < n else None)
            self.mappers = []
            for j in range(f):
                lo, hi = csc.indptr[j], csc.indptr[j + 1]
                vals = np.asarray(csc.data[lo:hi], np.float64)
                if srows is not None:
                    rows_j = csc.indices[lo:hi]
                    sel = np.isin(rows_j, srows, assume_unique=False)
                    vals = vals[sel]
                vals = vals[~((vals >= -1e-35) & (vals <= 1e-35))]
                m = BinMapper()
                bt = BIN_CATEGORICAL if j in cat_set else BIN_NUMERICAL
                m.find_bin(vals, total_sample_cnt=sample_cnt,
                           max_bin=cfg.max_bin,
                           min_data_in_bin=cfg.min_data_in_bin,
                           min_split_data=cfg.min_data_in_leaf,
                           bin_type=bt, use_missing=cfg.use_missing,
                           zero_as_missing=cfg.zero_as_missing)
                self.mappers.append(m)
            _finalize_used_features(self, cfg, f)

        used = self.real_feature_idx
        max_nb = max((self.mappers[j].num_bin for j in used), default=2)
        dtype = np.uint8 if max_nb <= 256 else np.uint16
        bins = np.zeros((n, len(used)), dtype=dtype)
        for col_idx, j in enumerate(used):
            m = self.mappers[j]
            zero_bin = int(m.values_to_bins(np.zeros(1))[0])
            if zero_bin:
                bins[:, col_idx] = zero_bin
            lo, hi = csc.indptr[j], csc.indptr[j + 1]
            if hi > lo:
                nz_bins = m.values_to_bins(
                    np.asarray(csc.data[lo:hi], np.float64))
                bins[csc.indices[lo:hi], col_idx] = nz_bins.astype(dtype)
        self.bins = bins
        self._maybe_bundle(cfg, reference)
        if label is not None:
            self.metadata.set_label(label)
        self.metadata.set_weight(weight)
        self.metadata.set_group(group)
        self.metadata.set_init_score(init_score)
        return self

    # ------------------------------------------------------------------
    @classmethod
    def create_from_sample(cls, sample: np.ndarray, n_total: int,
                           config: Optional[Config] = None,
                           feature_names: Optional[List[str]] = None,
                           categorical_feature: Optional[Sequence[int]]
                           = None,
                           reference: Optional["Dataset"] = None,
                           alloc_bins: bool = True) -> "Dataset":
        """Streaming creation, step 1 of 3 (the reference's push-rows
        flow: `LGBM_DatasetCreateFromSampledColumn` + `PushRows`,
        c_api.h:52-256): bin mappers are found from a row SAMPLE, the
        binned matrix is preallocated for ``n_total`` rows, and callers
        fill it incrementally with :meth:`push_rows` before sealing the
        dataset with :meth:`finish_load`. Peak host memory is the sample
        plus the uint8 binned matrix — the full float matrix never
        exists.

        With ``reference`` the sample may be None: mappers are shared so
        a streamed validation set aligns with the training set.
        """
        with obs_trace.seam("ingest.find_bins", rows=int(n_total)):
            cfg = config or Config()
            self = cls()
            self.num_data = int(n_total)
            self.metadata = Metadata(self.num_data)
            self.max_bin = cfg.max_bin
            self.min_data_in_bin = cfg.min_data_in_bin
            self.use_missing = cfg.use_missing
            self.zero_as_missing = cfg.zero_as_missing

            if reference is not None:
                f = reference.num_total_features
                self.num_total_features = f
                self.mappers = reference.mappers
                self.used_feature_map = reference.used_feature_map
                self.real_feature_idx = reference.real_feature_idx
                self.max_bin = reference.max_bin
                self.monotone_constraints = reference.monotone_constraints
                self.feature_penalty = reference.feature_penalty
                self.feature_names = reference.feature_names
            else:
                sample = np.asarray(sample, np.float64)
                f = sample.shape[1]
                self.num_total_features = f
                self.feature_names = (list(feature_names) if feature_names
                                      else [f"Column_{i}" for i in range(f)])
                cat_set = _cat_set_from(cfg, categorical_feature)
                self.mappers = []
                for j in range(f):
                    col = sample[:, j]
                    nonzero = col[~((col >= -1e-35) & (col <= 1e-35))]
                    m = BinMapper()
                    bt = BIN_CATEGORICAL if j in cat_set else BIN_NUMERICAL
                    m.find_bin(nonzero, total_sample_cnt=len(col),
                               max_bin=cfg.max_bin,
                               min_data_in_bin=cfg.min_data_in_bin,
                               min_split_data=cfg.min_data_in_leaf,
                               bin_type=bt, use_missing=cfg.use_missing,
                               zero_as_missing=cfg.zero_as_missing)
                    self.mappers.append(m)
                _finalize_used_features(self, cfg, f)

            used = self.real_feature_idx
            max_nb = max((self.mappers[j].num_bin for j in used), default=2)
            dtype = np.uint8 if max_nb <= 256 else np.uint16
            self._bins_dtype = dtype
            if alloc_bins:
                self.bins = np.zeros((self.num_data, len(used)), dtype=dtype)
            # else: stream-to-shard ingest — rows go straight to their owner
            # device's shard slice and the [n, U] host matrix never exists
            self._push_cfg = cfg
            self._push_ref = reference
            self._push_pos = 0
            self._push_label = None
            self._push_weight = None
            self._push_init = None
            return self

    def push_rows(self, data: np.ndarray, label=None, weight=None,
                  init_score=None) -> None:
        """Streaming creation, step 2: bin one chunk of raw rows into the
        preallocated matrix (reference `Dataset::PushOneRow` via
        `LGBM_DatasetPushRows`, c_api.h:199-226). Chunks arrive in row
        order; per-chunk label/weight/init_score slices ride along."""
        if getattr(self, "_push_pos", None) is None:
            raise RuntimeError(
                "push_rows requires a dataset made by create_from_sample")
        with obs_trace.seam("ingest.push_rows") as sm:
            data = np.asarray(data)
            if data.dtype not in (np.float32, np.float64):
                data = data.astype(np.float64)
            k = data.shape[0]
            pos = self._push_pos
            if pos + k > self.num_data:
                raise ValueError(f"push_rows overflow: {pos + k} > "
                                 f"n_total={self.num_data}")
            used = self.real_feature_idx
            dtype = self.bins.dtype
            chunk = self._native_bin_matrix(data, used, dtype)
            # a silent fall-back to the Python binner shows here, not only
            # as a slower ingest
            sm.attrs.update(rows=int(k), native=chunk is not None)
            if chunk is None:
                chunk = np.empty((k, len(used)), dtype=dtype)
                for col_idx, j in enumerate(used):
                    chunk[:, col_idx] = self.mappers[j].values_to_bins(
                        np.asarray(data[:, j], np.float64)).astype(dtype)
            self.bins[pos:pos + k] = chunk
            if label is not None:
                if self._push_label is None:
                    self._push_label = np.zeros(self.num_data, np.float64)
                self._push_label[pos:pos + k] = np.asarray(label,
                                                           np.float64)
            if weight is not None:
                if self._push_weight is None:
                    self._push_weight = np.ones(self.num_data, np.float64)
                self._push_weight[pos:pos + k] = np.asarray(weight,
                                                            np.float64)
            if init_score is not None:
                if self._push_init is None:
                    self._push_init = np.zeros(self.num_data, np.float64)
                self._push_init[pos:pos + k] = np.asarray(init_score,
                                                          np.float64)
            self._push_pos = pos + k

    def push_binned_rows(self, binned: np.ndarray, label=None, weight=None,
                         init_score=None) -> None:
        """Streaming creation, step 2 (pre-binned variant): append a chunk
        that was ALREADY binned — the streaming ingest path
        (`io/stream.py`) bins each chunk on device and pulls back uint8
        rows, so the host never holds the raw float chunk AND its binned
        copy twice. Same ordering/sidecar contract as :meth:`push_rows`."""
        if getattr(self, "_push_pos", None) is None:
            raise RuntimeError(
                "push_binned_rows requires a dataset made by "
                "create_from_sample")
        binned = np.asarray(binned)
        k = binned.shape[0]
        pos = self._push_pos
        if pos + k > self.num_data:
            raise ValueError(
                f"push_binned_rows overflow: {pos + k} > "
                f"n_total={self.num_data}")
        if binned.shape[1] != self.bins.shape[1]:
            raise ValueError(
                f"push_binned_rows width {binned.shape[1]} != "
                f"{self.bins.shape[1]} used features")
        self.bins[pos:pos + k] = binned.astype(self.bins.dtype, copy=False)
        if label is not None:
            if self._push_label is None:
                self._push_label = np.zeros(self.num_data, np.float64)
            self._push_label[pos:pos + k] = np.asarray(label, np.float64)
        if weight is not None:
            if self._push_weight is None:
                self._push_weight = np.ones(self.num_data, np.float64)
            self._push_weight[pos:pos + k] = np.asarray(weight, np.float64)
        if init_score is not None:
            if self._push_init is None:
                self._push_init = np.zeros(self.num_data, np.float64)
            self._push_init[pos:pos + k] = np.asarray(init_score,
                                                      np.float64)
        self._push_pos = pos + k

    def push_meta_rows(self, k: int, label=None, weight=None,
                       init_score=None) -> None:
        """Streaming creation, step 2 (stream-to-shard variant): advance
        the push cursor and record the chunk's metadata WITHOUT a host
        bins write — the binned rows were appended directly into their
        owner device's shard slice (io/stream.ShardedAppender), so there
        is no host matrix to fill. Same ordering contract as
        :meth:`push_binned_rows`."""
        if getattr(self, "_push_pos", None) is None:
            raise RuntimeError(
                "push_meta_rows requires a dataset made by "
                "create_from_sample")
        k = int(k)
        pos = self._push_pos
        if pos + k > self.num_data:
            raise ValueError(
                f"push_meta_rows overflow: {pos + k} > "
                f"n_total={self.num_data}")
        if label is not None:
            if self._push_label is None:
                self._push_label = np.zeros(self.num_data, np.float64)
            self._push_label[pos:pos + k] = np.asarray(label, np.float64)
        if weight is not None:
            if self._push_weight is None:
                self._push_weight = np.ones(self.num_data, np.float64)
            self._push_weight[pos:pos + k] = np.asarray(weight, np.float64)
        if init_score is not None:
            if self._push_init is None:
                self._push_init = np.zeros(self.num_data, np.float64)
            self._push_init[pos:pos + k] = np.asarray(init_score,
                                                      np.float64)
        self._push_pos = pos + k

    def bins_dtype(self) -> Optional[np.dtype]:
        """dtype of the binned matrix WITHOUT materializing a freed host
        copy (gate checks on the distributed path must stay O(1))."""
        if self._bins is not None:
            return self._bins.dtype
        cache = getattr(self, "_shard_cache", None)
        if cache is not None:
            return np.dtype(cache["bins"].dtype)
        dt = getattr(self, "_bins_dtype", None)
        return np.dtype(dt) if dt is not None else None

    def attach_device_bins(self, dev_bins) -> None:
        """Adopt an HBM-resident copy of ``bins`` built during streaming
        ingest (io/stream.py) so the serial learner's first upload is a
        no-op. Invalidated whenever the host matrix is rewritten (EFB
        bundling, column merges)."""
        self._dev_bins = dev_bins

    def device_bins(self):
        """The HBM copy of ``bins``: the streamed buffer when one is
        attached and still valid, else a lazy upload of the host matrix."""
        if getattr(self, "_dev_bins", None) is None:
            import jax.numpy as jnp
            self._dev_bins = jnp.asarray(self.bins)
        return self._dev_bins

    def finish_load(self, group=None) -> "Dataset":
        """Streaming creation, step 3: seal the dataset (reference
        `Dataset::FinishLoad`, dataset.cpp:330): check the declared row
        count, attach metadata, and apply feature bundling."""
        pos = self._push_pos
        if pos != self.num_data:
            raise ValueError(
                f"finish_load: {pos} rows pushed, {self.num_data} declared")
        with obs_trace.seam("ingest.finish_load", rows=int(pos)):
            if self._push_label is not None:
                self.metadata.set_label(self._push_label)
            self.metadata.set_weight(self._push_weight)
            self.metadata.set_group(group)
            self.metadata.set_init_score(self._push_init)
            self._maybe_bundle(self._push_cfg, self._push_ref)
        self._push_cfg = self._push_ref = None
        self._push_pos = None
        self._push_label = self._push_weight = self._push_init = None
        return self

    # ------------------------------------------------------------------
    def _maybe_bundle(self, cfg, reference) -> None:
        """Exclusive Feature Bundling (reference dataset.cpp:68-213): the
        binned matrix shrinks to one storage column per bundle; the
        per-feature view is reconstructed on device (io/bundling.py)."""
        # every construct path funnels through here once bins are final:
        # register the binned matrix with the HBM accountant (the
        # closure reads live state, so the post-bundle shrink is what a
        # snapshot reports)
        from ..obs import memory as obs_memory
        # the closure reads RAW storage (`_bins`), never the property: a
        # freed-after-shard matrix must report 0 bytes, not silently
        # re-gather the full host copy on every accountant snapshot
        obs_memory.track(
            "dataset/bins", self,
            lambda d: 0 if d._bins is None else int(d._bins.nbytes))
        from .bundling import apply_bundles, plan_bundles
        if reference is not None:
            # valid sets reuse the training set's bundling so binned
            # matrices stay aligned
            self.bundles = getattr(reference, "bundles", None)
            if self.bundles is not None:
                used = self.real_feature_idx
                db = np.asarray([self.mappers[j].default_bin for j in used],
                                np.int32)
                self.bins = apply_bundles(self.bins, self.bundles, db)
                self._dev_bins = None  # streamed HBM copy is pre-bundle
            return
        self.bundles = None
        # Supported surface (v1): fused serial device learner with
        # pointwise non-renewal objectives — the paths whose histogram /
        # partition / traversal kernels understand the bundled layout.
        renew = {"regression_l1", "l1", "mae", "huber", "fair", "quantile",
                 "mape", "poisson", "gamma", "tweedie"}
        if (not getattr(cfg, "enable_bundle", True) or self._bins is None
                or self._bins.dtype != np.uint8 or self.num_features < 3
                or cfg.tree_learner != "serial"
                or str(cfg.boosting) not in ("gbdt", "goss")
                or str(cfg.objective) in renew
                # the host SerialTreeLearner reads per-FEATURE bins — its
                # split/histogram code has no bundled view
                or cfg.forces_host_learner):
            return
        used = self.real_feature_idx
        nb = np.asarray([self.mappers[j].num_bin for j in used], np.int32)
        db = np.asarray([self.mappers[j].default_bin for j in used],
                        np.int32)
        cats = any(self.mappers[j].bin_type == BIN_CATEGORICAL
                   for j in used)
        if cats:
            return    # categorical routing through bundles not supported
        info = plan_bundles(self.bins, nb, db,
                            float(getattr(cfg, "max_conflict_rate", 0.0)),
                            seed=cfg.data_random_seed)
        if info is None or info.num_groups > 0.75 * self.num_features:
            return    # not worth the indirection
        self.bundles = info
        self.bins = apply_bundles(self.bins, info, db)
        self._dev_bins = None  # streamed HBM copy is pre-bundle

    # ------------------------------------------------------------------
    def shard(self, mesh, axis_name: str = "data") -> Dict[str, Any]:
        """Mesh-sharded HBM placement of the binned matrix: contiguous row
        blocks per device via `NamedSharding` (the layout the data-parallel
        learner assumes, parallel/data_parallel.py). The placement is
        cached per mesh so the loader/CLI can shard EARLY and the learner
        reuses the same device buffers instead of re-uploading.

        Returns the cache dict: ``mesh``, ``axis_name``, ``nd``,
        ``per_shard``, ``pad_rows``, row-sharded ``bins`` and its
        column-sharded transpose ``bins_T``, and ``owners``, the HBM
        ledger's names of its per-device rows.
        """
        import math as _math

        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        key = (tuple(int(d.id) for d in mesh.devices.flat), axis_name)
        cached = getattr(self, "_shard_cache", None)
        if cached is not None and cached["key"] == key:
            return cached
        if self.bins is None:
            raise ValueError("shard() needs a constructed dataset "
                             "(bins is None)")
        nd = int(mesh.devices.size)
        n = self.num_data
        per_shard = int(_math.ceil(n / nd))
        pad_rows = nd * per_shard - n
        bins_np = np.asarray(self.bins)
        if pad_rows:
            bins_np = np.pad(bins_np, ((0, pad_rows), (0, 0)))
        bins_sharded = jax.device_put(
            bins_np, NamedSharding(mesh, P(axis_name)))
        # transposed copy, row-sharded along its second axis, for the
        # contiguous split-column reads inside the tree build: made on
        # the devices, each from its own rows (no exchange), where a host
        # transpose of the matrix took a minute at 96M x 67 and crossed
        # to the devices a second time
        bins_t = jax.jit(
            jnp.transpose,
            out_shardings=NamedSharding(mesh, P(None, axis_name)))(
                bins_sharded)
        cache = {"key": key, "mesh": mesh, "axis_name": axis_name,
                 "nd": nd, "per_shard": per_shard, "pad_rows": pad_rows,
                 "bins": bins_sharded, "bins_T": bins_t}
        self._shard_cache = cache
        self._register_shard_owners(cache)
        # the placement is complete and authoritative: drop the host
        # copy (it was doubling peak memory next to the device shards).
        # A later host-side read re-gathers through the `bins` property.
        self._bins = None
        self._bins_freed = True
        self._dev_bins = None
        return cache

    def _register_shard_owners(self, cache: Dict[str, Any]) -> None:
        """Per-device HBM owners for a freshly placed shard cache (each
        device holds per_shard rows of the binned matrix plus its slice
        of the transpose), and the `dist_shard` announcement."""
        nd = cache["nd"]
        per_shard = cache["per_shard"]
        dt = np.dtype(cache["bins"].dtype)
        per_dev = 2 * per_shard * int(cache["bins"].shape[1]) * dt.itemsize
        from ..obs import memory as obs_memory
        # the ledger names this dataset's rows got: `#k`-suffixed where
        # another live dataset holds the plain name
        cache["owners"] = [obs_memory.track(
            f"dist/shard_bytes/d{i}", self,
            lambda d, nb=per_dev, k=cache["key"]: (
                nb if (getattr(d, "_shard_cache", None) is not None
                       and d._shard_cache["key"] == k) else 0))
            for i in range(nd)]
        from ..utils import log
        log.event("dist_shard", shards=nd, rows_per_shard=per_shard,
                  pad_rows=cache["pad_rows"], bytes_per_device=per_dev,
                  bin_sync_ms=getattr(self, "_bin_sync_ms", None))

    def attach_shard_cache(self, cache: Dict[str, Any]) -> None:
        """Adopt a shard placement assembled by stream-to-shard ingest
        (io/stream.ShardedAppender.finish): the cache dict has exactly
        the shape `shard()` builds, so a later `shard(mesh)` call with
        the same mesh is a cache hit and the learner reuses the buffers
        the loader already filled. The host matrix never existed; the
        `bins` property re-gathers on demand if a host-side consumer
        asks."""
        self._shard_cache = cache
        self._register_shard_owners(cache)
        self._bins = None
        self._bins_freed = True
        self._dev_bins = None

    def _native_bin_matrix(self, data: np.ndarray, used: np.ndarray,
                           dtype) -> Optional[np.ndarray]:
        """Full-matrix ingest through the native OpenMP binner
        (src/native/binning.cpp lgbt_bin_matrix); None -> Python loop."""
        from ..native import bin_matrix, native_available
        if not native_available() or len(used) == 0:
            return None
        ms = [self.mappers[j] for j in used]
        bin_type = np.asarray([_BINTYPE_CODE[m.bin_type] for m in ms],
                              np.int32)
        missing = np.asarray([_MISSING_CODE[m.missing_type] for m in ms],
                             np.int32)
        num_bin = np.asarray([m.num_bin for m in ms], np.int32)
        bounds_list = [m.bin_upper_bound if m.bin_type == BIN_NUMERICAL
                       else np.zeros(0) for m in ms]
        bounds_off = np.concatenate(
            [[0], np.cumsum([len(b) for b in bounds_list])]).astype(np.int64)
        bounds = (np.concatenate(bounds_list) if bounds_list
                  else np.zeros(0))
        cats_list, cat_bins_list = [], []
        for m in ms:
            if m.bin_type == BIN_CATEGORICAL and m.categorical_2_bin:
                ck = np.fromiter(m.categorical_2_bin.keys(), np.int64)
                cv = np.fromiter(m.categorical_2_bin.values(), np.int64)
                order = np.argsort(ck)
                cats_list.append(ck[order])
                cat_bins_list.append(cv[order].astype(np.int32))
            else:
                cats_list.append(np.zeros(0, np.int64))
                cat_bins_list.append(np.zeros(0, np.int32))
        cats_off = np.concatenate(
            [[0], np.cumsum([len(c) for c in cats_list])]).astype(np.int64)
        cats = (np.concatenate(cats_list) if cats_list
                else np.zeros(0, np.int64))
        cat_bins = (np.concatenate(cat_bins_list) if cat_bins_list
                    else np.zeros(0, np.int32))
        return bin_matrix(data, np.asarray(used, np.int32), bin_type,
                          missing, num_bin, bounds, bounds_off,
                          cats.astype(np.int64), cat_bins, cats_off, dtype)

    # ------------------------------------------------------------------
    def subset(self, row_indices: np.ndarray) -> "Dataset":
        """Row subset sharing bin mappers (reference `Dataset::CopySubset`,
        used by `lgb.cv` fold construction)."""
        idx = np.asarray(row_indices, dtype=np.int64)
        out = Dataset()
        out.num_data = len(idx)
        out.num_total_features = self.num_total_features
        out.bins = None if self.bins is None else self.bins[idx]
        out.bundles = self.bundles
        out.mappers = self.mappers
        out.used_feature_map = self.used_feature_map
        out.real_feature_idx = self.real_feature_idx
        out.feature_names = self.feature_names
        out.max_bin = self.max_bin
        out.min_data_in_bin = self.min_data_in_bin
        out.use_missing = self.use_missing
        out.zero_as_missing = self.zero_as_missing
        out.monotone_constraints = self.monotone_constraints
        out.feature_penalty = self.feature_penalty
        out.metadata = Metadata(len(idx))
        if self.metadata.label is not None:
            out.metadata.label = self.metadata.label[idx]
        if self.metadata.weight is not None:
            out.metadata.weight = self.metadata.weight[idx]
        if self.metadata.init_score is not None:
            ns = len(self.metadata.init_score) // self.num_data
            out.metadata.init_score = self.metadata.init_score.reshape(
                ns, self.num_data)[:, idx].reshape(-1)
        # query boundaries cannot survive arbitrary subsetting; only keep if
        # the subset respects query blocks
        return out

    # ------------------------------------------------------------------
    def add_features_from(self, other: "Dataset") -> None:
        """Column-wise merge of another constructed dataset into this one
        (reference `Dataset::AddFeaturesFrom`, dataset.cpp:349-437 /
        python basic.py add_features_from, covered by the reference
        test_basic.py:96-219). Both datasets must hold the same rows;
        `other`'s metadata is discarded, its features are appended."""
        if self.num_data != other.num_data:
            raise ValueError(
                f"Cannot add features from a dataset with {other.num_data} "
                f"rows to one with {self.num_data} rows")
        self._dev_bins = None  # column merge rewrites the binned matrix
        off = self.num_total_features
        self.mappers = self.mappers + other.mappers
        self.feature_names = self.feature_names + other.feature_names
        self.num_total_features += other.num_total_features
        other_map = other.used_feature_map.copy()
        shift = self.num_features
        other_map[other_map >= 0] += shift
        self.used_feature_map = np.concatenate(
            [self.used_feature_map, other_map])
        self.real_feature_idx = np.concatenate(
            [self.real_feature_idx, other.real_feature_idx + off])
        if self.bins is None:
            self.bins = other.bins
        elif other.bins is not None:
            dtype = (np.uint16 if np.uint16 in (self.bins.dtype,
                                                other.bins.dtype)
                     else np.uint8)
            self.bins = np.concatenate(
                [self.bins.astype(dtype), other.bins.astype(dtype)], axis=1)
        self.monotone_constraints = np.concatenate(
            [self.monotone_constraints,
             other.monotone_constraints]).astype(np.int8)
        self.feature_penalty = np.concatenate(
            [self.feature_penalty, other.feature_penalty])

    # ------------------------------------------------------------------
    # binary serialization (reference Dataset::SaveBinaryFile /
    # DatasetLoader::LoadFromBinFile)
    def save_binary(self, path: str) -> None:
        header = {
            "num_data": self.num_data,
            "num_total_features": self.num_total_features,
            "max_bin": self.max_bin,
            "min_data_in_bin": self.min_data_in_bin,
            "use_missing": self.use_missing,
            "zero_as_missing": self.zero_as_missing,
            "feature_names": self.feature_names,
            "used_feature_map": self.used_feature_map.tolist(),
            "real_feature_idx": self.real_feature_idx.tolist(),
            "monotone": self.monotone_constraints.tolist(),
            "penalty": self.feature_penalty.tolist(),
            "mappers": [m.to_dict() for m in self.mappers],
            "bins_dtype": str(self.bins.dtype) if self.bins is not None else "",
            "bundles": (None if self.bundles is None else {
                "num_groups": int(self.bundles.num_groups),
                "col": self.bundles.col.tolist(),
                "off": self.bundles.off.tolist(),
                "packed": self.bundles.packed.tolist(),
                "group_num_bin": self.bundles.group_num_bin.tolist(),
            }),
            "has_label": self.metadata.label is not None,
            "has_weight": self.metadata.weight is not None,
            "has_query": self.metadata.query_boundaries is not None,
            "has_init_score": self.metadata.init_score is not None,
        }
        from .file_io import open_file
        with open_file(path, "wb") as fh:
            fh.write(_BINARY_MAGIC)
            hb = json.dumps(header).encode()
            fh.write(struct.pack("<q", len(hb)))
            fh.write(hb)
            if self.bins is not None:
                np.save(fh, self.bins, allow_pickle=False)
            for arr in (self.metadata.label, self.metadata.weight,
                        self.metadata.query_boundaries,
                        self.metadata.init_score):
                if arr is not None:
                    np.save(fh, arr, allow_pickle=False)

    @classmethod
    def load_binary(cls, path: str) -> "Dataset":
        from .file_io import open_file
        with open_file(path, "rb") as fh:
            magic = fh.read(len(_BINARY_MAGIC))
            if magic != _BINARY_MAGIC:
                raise ValueError(f"{path} is not a tpu_gbdt binary dataset")
            (hlen,) = struct.unpack("<q", fh.read(8))
            header = json.loads(fh.read(hlen).decode())
            self = cls()
            self.num_data = header["num_data"]
            self.num_total_features = header["num_total_features"]
            self.max_bin = header["max_bin"]
            self.min_data_in_bin = header["min_data_in_bin"]
            self.use_missing = header["use_missing"]
            self.zero_as_missing = header["zero_as_missing"]
            self.feature_names = header["feature_names"]
            self.used_feature_map = np.asarray(header["used_feature_map"],
                                               dtype=np.int32)
            self.real_feature_idx = np.asarray(header["real_feature_idx"],
                                               dtype=np.int32)
            self.monotone_constraints = np.asarray(header["monotone"],
                                                   dtype=np.int8)
            self.feature_penalty = np.asarray(header["penalty"])
            self.mappers = [BinMapper.from_dict(d) for d in header["mappers"]]
            bd = header.get("bundles")
            if bd is not None:
                from .bundling import BundleInfo
                self.bundles = BundleInfo(
                    num_groups=int(bd["num_groups"]),
                    col=np.asarray(bd["col"], np.int32),
                    off=np.asarray(bd["off"], np.int32),
                    packed=np.asarray(bd["packed"], bool),
                    group_num_bin=np.asarray(bd["group_num_bin"], np.int32))
            self.metadata = Metadata(self.num_data)
            if header["bins_dtype"]:
                self.bins = np.load(fh, allow_pickle=False)
            if header["has_label"]:
                self.metadata.label = np.load(fh, allow_pickle=False)
            if header["has_weight"]:
                self.metadata.weight = np.load(fh, allow_pickle=False)
            if header["has_query"]:
                self.metadata.query_boundaries = np.load(fh, allow_pickle=False)
            if header["has_init_score"]:
                self.metadata.init_score = np.load(fh, allow_pickle=False)
        return self

    # ------------------------------------------------------------------
    def feature_meta_arrays(self) -> Dict[str, np.ndarray]:
        """Per-used-feature metadata arrays consumed by the device split
        finder (`ops/split.py`)."""
        ms = self.used_mappers()
        fcount = len(ms)
        num_bin = np.asarray([m.num_bin for m in ms], dtype=np.int32)
        default_bin = np.asarray([m.default_bin for m in ms], dtype=np.int32)
        missing = np.asarray([_MISSING_CODE[m.missing_type] for m in ms],
                             dtype=np.int32)
        bin_type = np.asarray([_BINTYPE_CODE[m.bin_type] for m in ms],
                              dtype=np.int32)
        mono = (self.monotone_constraints.astype(np.int32)
                if len(self.monotone_constraints) == fcount
                else np.zeros(fcount, dtype=np.int32))
        penalty = (self.feature_penalty.astype(np.float32)
                   if len(self.feature_penalty) == fcount
                   else np.ones(fcount, dtype=np.float32))
        return {
            "num_bin": num_bin,
            "default_bin": default_bin,
            "missing_type": missing,
            "bin_type": bin_type,
            "monotone": mono,
            "penalty": penalty,
        }
