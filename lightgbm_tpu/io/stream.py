"""Streaming out-of-core ingest with device-side binning.

The one-shot construct paths (`Dataset.from_matrix`, the loader's
parse-everything route) materialize the full float matrix on the host —
at Higgs scale that is an 11M x 28 f64 intermediate for a dataset whose
training copy is a 308 MB uint8 matrix. This module replaces that
intermediate with a chunked pipeline:

1. **one bounded sample pass** draws the bin-construction sample with
   the SAME canonical index draw as `Dataset.from_matrix`
   (`dist.binning.sample_indices`), so the resulting bin boundaries are
   bitwise-equal to the in-memory path's — parity by construction, the
   same argument the distributed bin sync makes;
2. **each chunk is binned on device**: a jitted f64 `searchsorted` over
   per-feature upper-bound tables (the device twin of
   `BinMapper.values_to_bins`; categorical columns are dictionary
   lookups and stay host-binned, riding through the kernel untouched);
3. the binned uint8 rows are appended into an HBM-resident buffer
   (donated `dynamic_update_slice`, O(1) reallocation) AND pulled back
   chunk-by-chunk into the host matrix the rest of the stack reads
   (model text, bundling, binary save). The HBM buffer is attached to
   the dataset so the learner's first upload is free.

Peak host memory is O(sample + chunk + uint8 matrix) — the raw float
matrix never exists, so datasets whose FLOAT form exceeds host RAM
load fine as long as their binned form fits.

Arrow/Parquet front door: `iter_parquet_batches` reads record batches
of ~chunk rows through pyarrow when it is installed (gated import — the
toolchain does not bake it in; callers get a clear error otherwise).
"""
from __future__ import annotations

import functools
import queue
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..config import Config
from .binning import BIN_CATEGORICAL, MISSING_NAN
from .dataset import Dataset

__all__ = [
    "ChunkPrefetcher",
    "DeviceBinner",
    "DeviceAppender",
    "ShardedAppender",
    "finish_sharded_ingest",
    "iter_parquet_batches",
    "pyarrow_available",
    "run_sharded_pipeline",
    "stream_matrix",
]


# ---------------------------------------------------------------------------
# device-side value->bin kernel
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("out_bits",))
def _bin_chunk_kernel(vals_T, bounds, is_cat, nan_override, use_override,
                      out_bits: int):
    """Device twin of `BinMapper.values_to_bins` for one padded chunk.

    vals_T:       f64 [U, C] — chunk values, feature-major (categorical
                  columns already hold HOST bin ids)
    bounds:       f64 [U, Bmax] — truncated `bin_upper_bound[:r]` padded
                  with +inf (past-the-end searches land exactly on r,
                  the first pad index, so padding is bitwise-equivalent
                  to the host's per-column truncation)
    nan_override: int32 [U] — `num_bin - 1` for MISSING_NAN columns
    use_override: bool [U] — whether NaN routes to nan_override (else
                  NaN is binned as 0.0, matching the host)

    The comparisons run in f64 — the exactness of the host parity
    argument lives or dies on the compare precision, so the CALLER must
    trace/lower/run this under `enable_x64` (a ctx inside the traced
    body is not enough: weak constants re-canonicalize to f32 at
    lowering time, which happens after the body ctx has exited).
    """
    nan_mask = jnp.isnan(vals_T)
    v = jnp.where(nan_mask, jnp.zeros((), vals_T.dtype), vals_T)
    idx = jax.vmap(
        lambda b, c: jnp.searchsorted(b, c, side="left"))(bounds, v)
    idx = idx.astype(jnp.int32)
    idx = jnp.where(nan_mask & use_override[:, None],
                    nan_override[:, None], idx)
    # categorical columns arrived host-binned: pass the ids through
    idx = jnp.where(is_cat[:, None], v.astype(jnp.int32), idx)
    out_dtype = jnp.uint8 if out_bits == 8 else jnp.uint16
    return idx.T.astype(out_dtype)


class DeviceBinner:
    """Per-dataset binning tables + the jitted chunk kernel.

    Chunks are padded to a fixed ``chunk_rows`` so ONE trace serves the
    whole ingest; the garbage pad rows are sliced off on the host side
    and overwritten by the next append on the device side.

    With ``device`` the tables are COMMITTED to that device and every
    ``bin_chunk`` runs there — the stream-to-shard path builds one
    binner per mesh device so each row block is binned on the device
    that owns its shard slice (no cross-device hop of binned data).
    """

    def __init__(self, ds: Dataset, chunk_rows: int, device=None) -> None:
        self.chunk_rows = int(chunk_rows)
        self.device = device
        self.used = np.asarray(ds.real_feature_idx)
        mappers = [ds.mappers[j] for j in self.used]
        self.out_bits = 8 if ds.bins_dtype() == np.uint8 else 16
        u = len(mappers)
        self.num_used = u
        self._cat_cols = [i for i, m in enumerate(mappers)
                          if m.bin_type == BIN_CATEGORICAL]
        self._mappers = mappers
        if u == 0:
            return
        rs = []
        for m in mappers:
            if m.bin_type == BIN_CATEGORICAL:
                rs.append(0)
            else:
                r = m.num_bin - 1
                if m.missing_type == MISSING_NAN:
                    r -= 1
                rs.append(max(r, 0))
        bmax = max(max(rs), 1)
        bounds = np.full((u, bmax), np.inf, dtype=np.float64)
        for i, (m, r) in enumerate(zip(mappers, rs)):
            if r > 0:
                bounds[i, :r] = np.asarray(m.bin_upper_bound[:r], np.float64)
        def _place(arr):
            return (jnp.asarray(arr) if device is None
                    else jax.device_put(arr, device))

        with jax.enable_x64(True):
            # f64 on device: created inside enable_x64 so the dtype
            # survives canonicalization (a plain asarray would silently
            # downcast to f32 and break bitwise parity with the host)
            self._bounds = _place(np.asarray(bounds, np.float64))
        self._is_cat = _place(
            np.asarray([m.bin_type == BIN_CATEGORICAL for m in mappers]))
        self._nan_override = _place(
            np.asarray([m.num_bin - 1 for m in mappers], np.int32))
        self._use_override = _place(
            np.asarray([m.bin_type != BIN_CATEGORICAL
                        and m.missing_type == MISSING_NAN
                        for m in mappers]))

    def host_prep(self, feats: np.ndarray) -> np.ndarray:
        """Host half of the chunk bin: select used columns, transpose to
        feature-major f64, dictionary-bin categorical columns, pad to
        the fixed ``chunk_rows``. Pure numpy — safe to run on the
        prefetch thread while the previous chunk occupies the device."""
        k = feats.shape[0]
        vals = np.ascontiguousarray(
            np.asarray(feats, np.float64)[:, self.used].T)  # [U, k]
        for i in self._cat_cols:
            # categorical: host dictionary lookup, ids ride through
            vals[i] = self._mappers[i].values_to_bins(vals[i])
        if k < self.chunk_rows:
            vals = np.pad(vals, ((0, 0), (0, self.chunk_rows - k)))
        return vals

    def bin_prepped(self, vals: np.ndarray):
        """Device half: transfer one prepped [U, chunk_rows] block and
        run the searchsorted kernel on this binner's device. Trace,
        lower AND run inside the x64 ctx: the jit cache keys on the x64
        flag, so every call staying inside the ctx reuses one
        genuinely-f64 program."""
        with jax.enable_x64(True):
            if self.device is None:
                vals_dev = jnp.asarray(vals, dtype=jnp.float64)
            else:
                vals_dev = jax.device_put(
                    np.asarray(vals, np.float64), self.device)
            return _bin_chunk_kernel(vals_dev, self._bounds, self._is_cat,
                                     self._nan_override,
                                     self._use_override, self.out_bits)

    def bin_chunk(self, feats: np.ndarray):
        """Bin one [k, F_total] float chunk -> device [chunk_rows, U]
        (rows past k are pad garbage). Returns the DEVICE array; callers
        slice/pull as needed."""
        return self.bin_prepped(self.host_prep(feats))


@functools.partial(jax.jit, donate_argnums=(0,))
def _append_kernel(buf, chunk, pos):
    """Donated in-place append: the buffer is over-allocated by one full
    chunk, so `pos + chunk_rows <= buf_rows` always holds and the update
    never clamps; garbage pad rows are overwritten by the next append
    and sliced off at finish."""
    return lax.dynamic_update_slice(buf, chunk, (pos, jnp.int32(0)))


class DeviceAppender:
    """HBM-resident growing copy of the binned matrix ([n + chunk, U]
    buffer, donated fixed-size appends, final [:n] slice)."""

    def __init__(self, n: int, num_used: int, chunk_rows: int,
                 dtype) -> None:
        self.n = int(n)
        self._buf = jnp.zeros((self.n + int(chunk_rows), num_used),
                              dtype=jnp.uint8 if dtype == np.uint8
                              else jnp.uint16)
        self._pos = 0

    def append(self, chunk_dev, k: int) -> None:
        self._buf = _append_kernel(self._buf, chunk_dev,
                                   jnp.int32(self._pos))
        self._pos += int(k)

    def finish(self):
        if self._pos != self.n:
            raise ValueError(
                f"DeviceAppender: {self._pos} rows appended, "
                f"{self.n} declared")
        return self._buf[:self.n]


# ---------------------------------------------------------------------------
# stream-to-shard: per-device shard destinations + pipelined prefetch
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("per_shard",))
def _shard_finish_kernel(buf, cnt, per_shard: int):
    """Seal one device's shard: slice the over-allocated append buffer
    to its [per_shard, U] slot, zero the pad rows past this device's
    real row count (the legacy `shard()` zero-pads, and byte-equality
    across ingest paths extends to the pad bytes the histogram kernels
    read), and emit the transposed copy the split-column reads use.
    No donation: the outputs are smaller than the buffer, so XLA could
    not alias them anyway; the buffer is dropped right after."""
    out = buf[:per_shard]
    rows = lax.iota(jnp.int32, per_shard)[:, None]
    out = jnp.where(rows < cnt, out, jnp.zeros((), out.dtype))
    return out, out.T


class ShardedAppender:
    """Stream-to-shard destination: one over-allocated append buffer
    per mesh device, filled by donated `dynamic_update_slice` on the
    device that OWNS the row block — the `[n, U]` host matrix never
    exists, peak host memory stays O(chunk) regardless of n.

    Row ownership is the contiguous-block layout `Dataset.shard()`
    produces (device d owns global rows [d*per_shard, (d+1)*per_shard));
    `finish()` seals each buffer and assembles the global row-sharded
    matrix + its column-sharded transpose into exactly the cache dict
    `shard()` would have built, so the data-parallel learner's later
    `shard(mesh)` call is a cache hit on buffers the loader already
    filled.

    Appends are paced two-buffers-deep per device: the previous append
    must complete before the next one is enqueued (the donated chain
    would stay correct without the wait — XLA orders the donations —
    but the wait bounds in-flight work and is where the pipeline's
    device time becomes observable as ``bin_s``).
    """

    def __init__(self, mesh, axis_name: str, n: int, ds: Dataset,
                 chunk_rows: int) -> None:
        import math as _math

        self.mesh = mesh
        self.axis_name = axis_name
        self.devices = list(mesh.devices.flat)
        self.nd = len(self.devices)
        self.n = int(n)
        self.per_shard = int(_math.ceil(self.n / self.nd))
        self.pad_rows = self.nd * self.per_shard - self.n
        self.chunk_rows = int(chunk_rows)
        # one binner per device: tables replicated, chunks binned on
        # their owner
        self.binners = [DeviceBinner(ds, chunk_rows, device=d)
                        for d in self.devices]
        self.num_used = self.binners[0].num_used
        self._dtype = np.dtype(ds.bins_dtype())
        # one host zero template, placed once per device ([per_shard +
        # chunk, U] over-allocation: the fixed-size donated append never
        # clamps; garbage pad rows are overwritten by the next append
        # and zeroed at finish)
        host0 = np.zeros((self.per_shard + self.chunk_rows, self.num_used),
                         self._dtype)
        self._bufs = [jax.device_put(host0, d) for d in self.devices]
        del host0
        self._pending: List[Optional[Any]] = [None] * self.nd
        self.rows_done = 0
        self.wait_s = 0.0

    def host_prep(self, feats: np.ndarray) -> np.ndarray:
        """Device-independent host half of the chunk bin (the tables'
        host metadata is identical across the per-device replicas)."""
        return self.binners[0].host_prep(feats)

    def plan(self, pos: int, k: int) -> List[Tuple[int, int, int, int]]:
        """Split chunk rows [pos, pos+k) by owner device: a list of
        ``(device_idx, local_offset, a, b)`` where chunk rows [a, b)
        land at the owner's shard-local ``local_offset``."""
        segs = []
        a = 0
        while a < k:
            di = (pos + a) // self.per_shard
            b = min(k, (di + 1) * self.per_shard - pos)
            segs.append((di, (pos + a) - di * self.per_shard, a, b))
            a = b
        return segs

    def append_prepped(self,
                       segs: List[Tuple[int, int, int, np.ndarray]]) -> None:
        """Dispatch one chunk's owner segments: ``(device_idx,
        local_offset, rows, prepped_vals)`` each → transfer + bin on the
        owner + donated append into its shard buffer. Waits (timed) for
        the owner's PREVIOUS append before enqueueing the next — the
        double-buffer pacing."""
        for di, off, rows, vals in segs:
            prev = self._pending[di]
            if prev is not None:
                t0 = time.perf_counter()
                prev.block_until_ready()  # graftlint: disable=LGT002 ingest pacing wait at load time, not a round-loop fence; obs fences would trip the tier-1 zero-fence assertion
                self.wait_s += time.perf_counter() - t0
            out = self.binners[di].bin_prepped(vals)
            self._bufs[di] = _append_kernel(self._bufs[di], out,
                                            jnp.int32(off))
            self._pending[di] = self._bufs[di]
            self.rows_done += int(rows)

    def drain(self) -> None:
        """Block until every in-flight append has landed."""
        for arr in self._pending:
            if arr is not None:
                arr.block_until_ready()  # graftlint: disable=LGT002 load-time drain before sealing shards, not a round-loop fence

    def finish(self) -> Dict[str, Any]:
        """Seal every shard (pad rows zeroed) and assemble the global
        arrays — returns the `Dataset.shard()`-shaped cache dict for
        `Dataset.attach_shard_cache`."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self.rows_done != self.n:
            raise ValueError(
                f"ShardedAppender: {self.rows_done} rows appended, "
                f"{self.n} declared")
        self.drain()
        shards, shards_t = [], []
        for di in range(self.nd):
            cnt = min(self.per_shard,
                      max(self.n - di * self.per_shard, 0))
            out, out_t = _shard_finish_kernel(
                self._bufs[di], jnp.int32(cnt), self.per_shard)
            shards.append(out)
            shards_t.append(out_t)
        self._bufs = []
        self._pending = []
        u = self.num_used
        rows_total = self.nd * self.per_shard
        bins = jax.make_array_from_single_device_arrays(
            (rows_total, u),
            NamedSharding(self.mesh, P(self.axis_name)), shards)
        bins_t = jax.make_array_from_single_device_arrays(
            (u, rows_total),
            NamedSharding(self.mesh, P(None, self.axis_name)), shards_t)
        key = (tuple(int(d.id) for d in self.mesh.devices.flat),
               self.axis_name)
        return {"key": key, "mesh": self.mesh,
                "axis_name": self.axis_name, "nd": self.nd,
                "per_shard": self.per_shard, "pad_rows": self.pad_rows,
                "bins": bins, "bins_T": bins_t}


class ChunkPrefetcher:
    """Bounded producer thread over a chunk generator — the pipeline's
    two host staging buffers: the thread parses chunk k+1 while the
    consumer transfers/bins chunk k (numpy parsing holds the GIL, but
    the consumer's device waits release it, so the two genuinely
    overlap). ``parse_s`` accumulates the producer-side wall."""

    _DONE = object()

    def __init__(self, gen: Iterator, depth: int = 2) -> None:
        self.parse_s = 0.0
        self._gen = gen
        self._exc: Optional[BaseException] = None
        # depth counts staging buffers: the consumer holds one, the
        # queue holds the rest
        self._q: "queue.Queue" = queue.Queue(maxsize=max(int(depth) - 1, 1))
        self._t = threading.Thread(target=self._produce, daemon=True,
                                   name="lgbt-ingest-parse")
        self._t.start()

    def _produce(self) -> None:
        try:
            while True:
                t0 = time.perf_counter()
                try:
                    item = next(self._gen)
                except StopIteration:
                    break
                finally:
                    self.parse_s += time.perf_counter() - t0
                self._q.put(item)
        except BaseException as e:   # surfaces on the consumer side
            self._exc = e
        finally:
            self._q.put(self._DONE)

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is self._DONE:
                self._t.join()
                if self._exc is not None:
                    raise self._exc
                return
            yield item


class _InlineChunks:
    """Sequential twin of ChunkPrefetcher (pipeline depth <= 1): same
    interface, no thread — the honest parse-then-bin baseline."""

    def __init__(self, gen: Iterator) -> None:
        self._gen = gen
        self.parse_s = 0.0

    def __iter__(self):
        while True:
            t0 = time.perf_counter()
            try:
                item = next(self._gen)
            except StopIteration:
                self.parse_s += time.perf_counter() - t0
                return
            self.parse_s += time.perf_counter() - t0
            yield item


def run_sharded_pipeline(ds: Dataset, appender: ShardedAppender,
                         gen: Iterator, depth: int
                         ) -> Tuple[float, float, float]:
    """Drive the stream-to-shard pipeline: items are ``(k, label,
    weight, prepped_segs)``; metadata rides through
    `Dataset.push_meta_rows` (no host bins write). Returns
    ``(parse_s, bin_s, wall_s)`` — producer wall, consumer
    transfer/bin/wait wall, and end-to-end wall; with the prefetch
    thread on, wall approaches max(parse, bin) instead of their sum."""
    t_start = time.perf_counter()
    src = (ChunkPrefetcher(gen, depth) if depth >= 2
           else _InlineChunks(gen))
    bin_s = 0.0
    for k, labs, w, segs in src:
        t0 = time.perf_counter()
        appender.append_prepped(segs)
        bin_s += time.perf_counter() - t0
        ds.push_meta_rows(k, label=labs, weight=w)
    t0 = time.perf_counter()
    appender.drain()
    bin_s += time.perf_counter() - t0
    return src.parse_s, bin_s, time.perf_counter() - t_start


def finish_sharded_ingest(ds: Dataset, appender: ShardedAppender,
                          chunk_rows: int, parse_s: float, bin_s: float,
                          wall_s: float, depth: int, source: str) -> None:
    """Common tail of both stream-to-shard front doors: adopt the shard
    cache, record the pipeline breakdown on the dataset, and announce
    `stream_ingest` + `dist_stream` on the event channel."""
    from ..utils import log

    ds.attach_shard_cache(appender.finish())
    seq_s = parse_s + bin_s
    overlap_eff = round(seq_s / wall_s, 3) if wall_s > 0 else 1.0
    dt = np.dtype(ds.bins_dtype())
    shard_bytes = 2 * appender.per_shard * appender.num_used * dt.itemsize
    b0 = appender.binners[0]
    ms = wall_s * 1e3
    ds._ingest_ms = ms
    ds._ingest_stats = {
        "rows": int(appender.n), "chunk_rows": int(chunk_rows),
        "device_cols": int(b0.num_used - len(b0._cat_cols)),
        "host_cols": int(len(b0._cat_cols)),
        "sharded": True, "shards": int(appender.nd),
        "per_shard": int(appender.per_shard),
        "shard_bytes": int(shard_bytes),
        "parse_ms": round(parse_s * 1e3, 1),
        "bin_ms": round(bin_s * 1e3, 1),
        "seq_ms": round(seq_s * 1e3, 1),
        "overlap_eff": overlap_eff,
        "pipeline_depth": int(depth),
    }
    # ingest started wall_s before now — the timeline merger places the
    # ingest lane span at t_start on the shared perf_counter clock
    t_ingest = round(time.perf_counter() - wall_s, 6)
    log.event("stream_ingest", rows=int(appender.n),
              chunk_rows=int(chunk_rows),
              device_cols=ds._ingest_stats["device_cols"],
              host_cols=ds._ingest_stats["host_cols"],
              ingest_ms=ms, wall_ms=round(wall_s * 1e3, 1),
              t_start=t_ingest, source=source)
    log.event("dist_stream", rows=int(appender.n),
              shards=int(appender.nd),
              per_shard=int(appender.per_shard),
              chunk_rows=int(chunk_rows),
              parse_ms=ds._ingest_stats["parse_ms"],
              bin_ms=ds._ingest_stats["bin_ms"],
              wall_ms=round(wall_s * 1e3, 1), t_start=t_ingest,
              ingest_ms=round(ms, 1), overlap_eff=overlap_eff,
              pipeline_depth=int(depth),
              bytes_per_device=int(shard_bytes),
              owners=",".join(f"dist/shard_bytes/d{i}"
                              for i in range(appender.nd)),
              source=source)


# ---------------------------------------------------------------------------
# in-memory matrix front door
# ---------------------------------------------------------------------------
def stream_matrix(data, label=None, config: Optional[Config] = None,
                  weight=None, group=None, init_score=None,
                  feature_names: Optional[List[str]] = None,
                  categorical_feature: Optional[Sequence[int]] = None,
                  reference: Optional[Dataset] = None) -> Dataset:
    """Chunked twin of `Dataset.from_matrix`: same sample draw, same bin
    boundaries, same binned matrix — but built chunk-by-chunk through the
    device binning kernel, leaving the HBM copy attached. `data` may be
    any object supporting 2-D shape + row slicing (an `np.memmap` of a
    larger-than-RAM matrix is the intended caller)."""
    from ..dist.binning import sample_indices
    from ..utils import log

    cfg = config or Config()
    chunk_rows = max(int(cfg.tpu_stream_chunk_rows), 1)
    t0 = time.perf_counter()
    n, f = data.shape[0], data.shape[1]

    shard_mesh = None
    if reference is None:
        from ..dist import runtime as dist_runtime
        shard_mesh = dist_runtime.stream_shard_mesh(cfg)

    if reference is not None:
        ds = Dataset.create_from_sample(None, n, config=cfg,
                                        reference=reference)
    else:
        sample_cnt = min(n, max(cfg.bin_construct_sample_cnt, 1))
        sample_idx = sample_indices(n, sample_cnt, cfg.data_random_seed)
        sample = np.asarray(data[sample_idx], np.float64)
        ds = Dataset.create_from_sample(
            sample, n, config=cfg, feature_names=feature_names,
            categorical_feature=categorical_feature,
            alloc_bins=shard_mesh is None)
        del sample
    if shard_mesh is not None and len(ds.real_feature_idx) == 0:
        # nothing to bin on device; the trivial [n, 0] host matrix is
        # the simpler path
        ds.bins = np.zeros((n, 0), ds.bins_dtype())
        shard_mesh = None

    label = None if label is None else np.asarray(label).reshape(-1)
    weight = None if weight is None else np.asarray(weight).reshape(-1)

    if shard_mesh is not None:
        # ---- stream-to-shard: rows go straight to their owner device
        depth = int(getattr(cfg, "tpu_stream_pipeline_depth", 2))
        appender = ShardedAppender(shard_mesh, "data", n, ds, chunk_rows)

        def _chunks():
            pos = 0
            for lo in range(0, n, chunk_rows):
                hi = min(lo + chunk_rows, n)
                k = hi - lo
                feats = np.asarray(data[lo:hi])
                segs = [(di, off, b - a,
                         appender.host_prep(feats[a:b]))
                        for di, off, a, b in appender.plan(pos, k)]
                pos += k
                yield (k,
                       None if label is None else label[lo:hi],
                       None if weight is None else weight[lo:hi],
                       segs)

        parse_s, bin_s, wall_s = run_sharded_pipeline(
            ds, appender, _chunks(), depth)
        finish_sharded_ingest(ds, appender, chunk_rows, parse_s, bin_s,
                              wall_s, depth, source="matrix")
        ds.finish_load(group=group)
        if init_score is not None:
            ds.metadata.set_init_score(init_score)
        return ds

    binner = DeviceBinner(ds, chunk_rows)
    appender = (DeviceAppender(n, binner.num_used, chunk_rows,
                               ds.bins.dtype)
                if binner.num_used else None)
    for lo in range(0, n, chunk_rows):
        hi = min(lo + chunk_rows, n)
        k = hi - lo
        if binner.num_used:
            dev = binner.bin_chunk(np.asarray(data[lo:hi]))
            appender.append(dev, k)
            host = np.asarray(dev)[:k]
        else:
            host = np.zeros((k, 0), ds.bins.dtype)
        ds.push_binned_rows(
            host,
            label=None if label is None else label[lo:hi],
            weight=None if weight is None else weight[lo:hi])
    if appender is not None:
        ds.attach_device_bins(appender.finish())
    ds.finish_load(group=group)
    if init_score is not None:
        ds.metadata.set_init_score(init_score)
    ms = (time.perf_counter() - t0) * 1e3
    ds._ingest_ms = ms
    ds._ingest_stats = {
        "rows": int(n), "chunk_rows": int(chunk_rows),
        "device_cols": int(binner.num_used - len(binner._cat_cols)),
        "host_cols": int(len(binner._cat_cols)),
    }
    log.event("stream_ingest", rows=int(n), chunk_rows=int(chunk_rows),
              device_cols=ds._ingest_stats["device_cols"],
              host_cols=ds._ingest_stats["host_cols"],
              ingest_ms=ms, wall_ms=round(ms, 1),
              t_start=round(t0, 6), source="matrix")
    return ds


# ---------------------------------------------------------------------------
# Arrow / Parquet front door (gated: pyarrow is not baked into the image)
# ---------------------------------------------------------------------------
def pyarrow_available() -> bool:
    try:
        import pyarrow  # noqa: F401
        import pyarrow.parquet  # noqa: F401
        return True
    except Exception:
        return False


def iter_parquet_batches(path: str, chunk_rows: int
                         ) -> Iterator[Tuple[List[str], np.ndarray]]:
    """Yield ``(column_names, float64 [<=chunk_rows, C] block)`` from a
    Parquet or Arrow IPC file without materializing the whole table."""
    try:
        import pyarrow as pa
        import pyarrow.parquet as pq
    except Exception as e:  # pragma: no cover - exercised via skipif
        raise ImportError(
            "Parquet/Arrow ingest needs pyarrow, which is not installed "
            "in this environment; convert the file to CSV/TSV or install "
            "pyarrow") from e
    if str(path).endswith((".arrow", ".feather", ".ipc")):
        with pa.memory_map(str(path)) as src:
            table = pa.ipc.open_file(src).read_all()
        batches = table.to_batches(max_chunksize=chunk_rows)
    else:
        pf = pq.ParquetFile(str(path))
        batches = pf.iter_batches(batch_size=chunk_rows)
    for batch in batches:
        names = list(batch.schema.names)
        cols = [np.asarray(batch.column(i).to_numpy(zero_copy_only=False),
                           np.float64) for i in range(batch.num_columns)]
        yield names, (np.stack(cols, axis=1) if cols
                      else np.zeros((batch.num_rows, 0)))
