"""Chunk-aligned record pipeline — the Pallas kernels behind the aligned
tree builder (`models/aligned_builder.py`).

Replaces the reference's two hot loops with streaming TPU kernels over ONE
persistent record matrix:

- `DataPartition::Split` + `DenseBin::Split` (data_partition.hpp,
  dense_bin.hpp:195-283) -> `move_pass`: a stable two-way partition of
  EVERY tree block in one pass over the rows.
- `DenseBin::ConstructHistogram` / the OpenCL kernels
  (dense_bin.hpp:71-137, ocl/histogram256.cl:350) -> `slot_hist_pass`: one
  streaming pass accumulating per-leaf histograms into data-dependent
  output blocks.

Record layout: `[NC, W, C] int32` — chunk-blocked and TRANSPOSED so rows
sit in the 128-lane dimension (Mosaic only allows dynamic slicing at
128-aligned lane offsets; with rows on lanes, whole chunks move as
`ref.at[chunk]` DMAs and in-chunk permutations become matmuls). Lanes of
one row live at the same lane index across the W sublanes; the first
wcnt sublanes are packed bin words (4/5/8 bins per word at 8/6/4-bit
widths — under EFB the columns are BUNDLE storage), the rest are the
layout's value lanes (see `lane_layout`: STANDARD score/label/grad/
hess/rid/weight, COMPACT score(+prob)/meta, EXT score/grad/hess/rid).

Tree blocks own disjoint CHUNK-ALIGNED ranges of the record matrix, so
every chunk belongs to exactly one block and per-chunk routing parameters
arrive as scalar-prefetched 1-D arrays (SMEM is 1 MB; 2-D prefetch arrays
lane-pad to 128 and blow it).

The in-chunk permutation is exact: the byte-plane one-hot matmul
(bf16 0/1 one-hot x byte planes, f32 accumulate) produces outputs that are
each a SINGLE term < 256, so record bits survive the MXU untouched.

Measured v5e floors at n=10.5M, F=28 (round-4 prototype, earlier
runtime, not re-measured): move
4.5 ns/row, hist 3.5 ns/row at B=64 / 6.4 at B=256 — vs 18 ns/row for the
11-op lax.sort partition and ~19 ns/row for the einsum histogram.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import compile_cache

NUM_STATS = 3          # grad, hess, count
MISSING_NONE_C, MISSING_ZERO_C, MISSING_NAN_C = 0, 1, 2

# route word 1 bit layout (per chunk)
R_THR = 0          # bits 0..7   threshold bin
R_SHIFT = 8        # bits 8..12  shift within word (0/8/16/24)
R_DL = 13          # bit 13      default_left
R_MT = 14          # bits 14..15 missing type
R_COPY = 16        # bit 16      copy-through (unsplit block)
R_WSEL = 17        # bits 17..24 split word lane of the block
R_CAT = 25         # bit 25      categorical split (bitset routing)
# route word 2: default_bin | (num_bin - 1) << 8 | boff << 16 | bpk << 24
# (8-bit bin fields — num_bin <= 256 stores as num_bin - 1, so the whole
# word fits 25 bits; boff/bpk are the EFB bundle unpack params — one
# packed word keeps the scalar-prefetch SMEM budget at 6 x NC words,
# bounding NC ~40K chunks = ~40M rows at C=1024). pack_route2 is the
# single encode point; _unpack_bundle/_goes_left decode.
# meta word: cnt | first << 20 | last << 21


def effective_chunk(cfg, num_features: int = 0) -> int:
    """The chunk size the aligned engine will actually run at: the unit
    of the grid, the DMA, the flush and the route words. move_pass's
    split path works in sub-tiles of route_tile(C) rows, so its cost a
    row does not grow with C any more (PERF.md section 6, PR 27).

    The rule below is older than that and is NOT retuned: 1024 measured
    best on v5e at the HIGGS shape (10.5M x 28), where per-chunk fixed
    costs dominate the split path, and WIDE records regressed hard at
    1024 (F=137: 2.0 s/iter vs 0.66 at 512; per-chunk VMEM temps scale
    with W*C), so records wider than ~40 features stay at 512. "2048
    regresses everywhere" (HIGGS shape, 2.2 s/iter against 0.53) was
    measured on the untiled kernel with eight inlined copies of the
    histogram code in it; at the Criteo-67 record that code alone cost
    every split chunk 39 us of 45, and without it a 2,048-row chunk
    costs 10.1 us tiled, 17.2 untiled. tpu_chunk overrides."""
    C = int(getattr(cfg, "tpu_chunk", 0) or 0)
    if C > 0:
        return C
    return 1024 if num_features <= 40 else 512


def chunk_for(cfg, num_features: int, n: int) -> int:
    """effective_chunk, scaled up so the move pass's 6 per-chunk route
    words fit the 1 MB scalar-prefetch SMEM budget (NC <= ~40K): very
    large n doubles the chunk until NC fits, the only way a 50M+-row
    dataset trains aligned on one chip at all. The larger chunk costs
    fewer grid steps and no wider one-hots: the split path's tile is
    route_tile(C) whatever C is. An explicit tpu_chunk is escalated the
    same way (the pinned size would fail SMEM allocation outright), with
    a warning so a user who benchmarked at the pinned size knows why
    timing moved."""
    C0 = C = effective_chunk(cfg, num_features)
    while n // C > 40_000:
        C *= 2
    if C != C0 and int(getattr(cfg, "tpu_chunk", 0) or 0):
        from ..utils import log
        log.warning(
            f"tpu_chunk={C0} cannot hold {n} rows within the kernel's "
            f"scalar-prefetch budget; using tpu_chunk={C} instead")
    return C


# Rows move_pass's split path partitions at a time. Its rank mask, its
# one-hot and its route matmul are S x S, so the work a row is
# proportional to S and not to the chunk size, which the SMEM budget and
# the row count set (chunk_for). Chosen by tools/route_tile_sweep.py on
# the v5e (PERF.md section 6, PR 27): us a chunk at 128 / 256 / 512 /
# 1024 / untiled, Criteo-67 record at C=2048: - / 10.1 / 10.4 / 12.6 /
# 17.2 (63 bins: 8.3 / 8.6 / - / 14.5); HIGGS record at C=1024:
# 2.47 / 2.50 / 2.95; 128 ran 2 us behind 256 in an earlier sweep.
ROUTE_TILE = 256
# Blocks the split path's route matmul selects for a tile: ONE, holding
# the rows of both sides and of both ring windows a side's rows can
# reach (route_tile_rows says why that is exact). Static, so it rides the
# `aligned.pack` seam as `route_selectors`: it dates a trace.
ROUTE_SELECTORS = 1


# How the split path stages a tile's rows into the block's rings:
# "carried", each side's OPEN window (the one its cursor is in) held in
# the tile loop's carry, loaded once a grid step and plain-stored once a
# tile (route_tile_rows); PR 32's form read, merged and wrote back two
# windows a side a tile. And ROUTE_UNROLL tiles share one trip of the
# loop, so that one tile's chain of matmuls and stores overlaps the
# next's (tools/route_tile_sweep.py on the v5e, PERF.md section 6,
# PR 38: us a full Criteo-67 chunk at 255 bins, unrolled 1 / 2 / 4 / 8:
# 9.34 / 8.35 / 7.81 / 7.63; PR 32's form 9.64). Both static: they ride
# the `aligned.pack` seam as `route_stage` and `route_unroll`.
ROUTE_STAGE = "carried"
ROUTE_UNROLL = 8


def route_unroll(chunk: int) -> int:
    """Tiles of a `chunk`-row chunk that share one trip of move_pass's
    route loop: ROUTE_UNROLL, or all of the chunk's where it has fewer."""
    return min(ROUTE_UNROLL, chunk // route_tile(chunk))


def route_tile(chunk: int) -> int:
    """S: the sub-tile of a `chunk`-row chunk that move_pass's split
    path partitions at a time. ROUTE_TILE wherever it divides the chunk,
    else the whole chunk (one tile)."""
    return ROUTE_TILE if chunk % ROUTE_TILE == 0 else chunk


def aligned_num_chunks(n: int, cfg, spec_slots: int,
                       num_features: int = 0) -> int:
    """NC of the engine's record matrix: data chunks + one fresh chunk
    per speculative slot + 2 (must mirror AlignedEngine.__init__)."""
    C = chunk_for(cfg, num_features, n)
    return (n + C - 1) // C + spec_slots + 2


# compact meta-lane bit layout: rid | label << 24 (7 bits: 0/1 binary
# label, or the integer class id for multiclass, K <= 127) | bag << 31
META_RID_MASK = (1 << 24) - 1
META_LABEL = 24
META_LABEL_MASK = 127
META_BAG = 31


def _bpw_for_bits(bits: int) -> int:
    """Bins per 32-bit word at a given bin bit-width: COMPACT records
    pack 8 four-bit bins (max_bin <= 16, the reference's
    dense_nbits_bin.hpp:42 2-bins/byte analogue) or 5 six-bit bins
    (max_bin <= 64); standard records pack 4 eight-bit bins."""
    return {4: 8, 6: 5, 8: 4}[bits]


def lane_layout(wcnt: int, with_bag: bool = False, compact: bool = False,
                num_class: int = 1, with_prob: bool = False,
                ext: bool = False):
    """(lane indices, padded W) for a record with `wcnt` bin words.

    COMPACT layout (lane-wise objectives with small-integer labels,
    unweighted, n <= 2^24): bin words + num_class score lanes + meta,
    where meta packs rid | label << 24 | bag << 31 — gradients are
    recomputed in-kernel from (scores, label) instead of riding as
    lanes, halving the record (W 16 -> 8 at HIGGS shape) and with it
    every DMA and the route matmul of the move pass. `score` is the
    FIRST of the num_class score lanes (class k at score + k).

    EXT layout (external-gradient objectives — ranking): the label and
    weight lanes are dropped (the objective computes g/h in row order
    with weights folded in; nothing in the kernels reads them), so the
    record is bins + score + grad + hess + rid (+bag)."""
    ls = wcnt
    if ext:
        lanes = dict(score=ls, grad=ls + 1, hess=ls + 2, rid=ls + 3)
        w = wcnt + 4
        if with_bag:
            lanes["bag"] = w
            w += 1
    elif compact:
        lanes = dict(score=ls)
        w = wcnt + num_class
        if with_prob:
            # softmax multiclass: per-class PROBABILITY lanes, written
            # once per iteration from the pre-iteration score lanes (the
            # reference computes gradients once then trains K trees,
            # gbdt.cpp:415-444); class gradients derive lane-locally
            # from p_k, immune to the same-iteration deferred score
            # applications
            lanes["prob"] = w
            w += num_class
        lanes["meta"] = w
        w += 1
    else:
        lanes = dict(score=ls, label=ls + 1, grad=ls + 2, hess=ls + 3,
                     rid=ls + 4, weight=ls + 5)
        w = wcnt + 6
        if with_bag:
            lanes["bag"] = w
            w += 1
    w_pad = ((w + 7) // 8) * 8
    return lanes, w_pad


def bin_bits(bins, max_bin: int = 0) -> int:
    """The width the records pack their bin fields at: the narrowest the
    MAPPERS' bin range allows (max_bin = max num_bin over used mappers;
    the observed data max where the caller has no mappers): 4-bit
    (8/word, the reference's dense_nbits_bin.hpp:42 two-bins-per-byte at
    twice the density) under 16 bins, 6-bit (5/word) under 64, 8-bit
    (4/word) otherwise — for EVERY lane layout; the kernels parameterize
    on `bits` throughout. Deriving from num_bin rather than bins.max()
    means a split threshold in the (possibly data-empty) upper bin range
    is always representable in-width. The bins are read only where
    max_bin leaves the width open."""
    bmax = max_bin - 1
    if bmax < 64:
        bmax = max(bmax, int(np.max(bins, initial=0)))
    if bmax < 16:
        return 4
    return 6 if bmax < 64 else 8


# Bytes of uint8 bins one call of the device pack takes: the block of
# chunks is as many whole chunks as fit (at least one, at most the data),
# so the pack's program has one shape an engine and at most a few blocks
# of bins are on the device at once, beside the records. Compiled for a
# v5e at Criteo's shape (67 columns, C = 2048) the program holds no
# temporaries at 8 MiB a block and 122 MiB at 16; the pack's peak adds
# to what the device already holds, so the block stays small (the
# upload, not the block count, sets the pack's time).
PACK_BLOCK_BYTES = 8 << 20


def pack_block_chunks(chunk: int, cols: int, data_chunks: int) -> int:
    """Chunks of one device-pack block (see PACK_BLOCK_BYTES)."""
    per_chunk = chunk * max(cols, 1)
    return max(1, min(data_chunks, PACK_BLOCK_BYTES // per_chunk))


def _pack_block(rec, bins, first, facts, rows, *, chunk, blk, cols, bits,
                wcnt, lanes, w_pad, kind, with_bag, num_class):
    """One block of `blk` chunks packed into `rec` [NC, W, C] in place,
    on the device: the rows `first` .. `first + blk * chunk` of one shard.

    bins: uint8 [blk * chunk * cols], the block's bins row-major (rows
    past the shard's end are zero); facts: int32 [1, 3], the shard's row
    count, its first row id and the EXT index lane's pad value; rows: the
    shard's row lanes ("label", "weight", "index" [L], "scores" [K, L]),
    L at least the block's rows, of which the block's are sliced here.
    Chunks past the shard's own rows are written zero. Returns (rec, a
    token that is ready when the block is written)."""
    compile_cache.note_trace()
    from ..obs import phases
    with phases.scope("setup.pack"):
        r_blk = blk * chunk
        bpw = _bpw_for_bits(bits)
        n, rid_base, pad_id = facts[0, 0], facts[0, 1], facts[0, 2]
        row = first + lax.iota(jnp.int32, r_blk)
        valid = row < n
        live = row < (n + chunk - 1) // chunk * chunk

        def lane_of(name):
            # a block that runs past the lane's end (the last, by less
            # than a chunk) reads the lane's last r_blk rows and rolls
            # them into place; what wraps around lies past the rows
            x = rows[name]
            start = jnp.minimum(first, x.shape[-1] - r_blk)
            return jnp.roll(lax.dynamic_slice_in_dim(x, start, r_blk, axis=-1),
                            start - first, axis=-1)

        def on_valid(x):
            return jnp.where(valid, x, 0)

        # rows to the minor dimension, at 32 bits; a word ORs its columns,
        # each shifted to its field. No reshape splits the column axis:
        # on a v5e, a split of 70 uint8 columns into 14 words of 5 made
        # this program pack wrong words (that reshape alone packed right)
        b = bins.reshape(r_blk, cols).T.astype(jnp.uint32)
        out = []
        for w in range(wcnt):
            word = jnp.zeros(r_blk, jnp.uint32)
            for i in range(min(bpw, cols - w * bpw)):
                word = word | (b[w * bpw + i] << (bits * i))
            out.append(lax.bitcast_convert_type(word, jnp.int32))
        lane = {}
        one = jnp.int32(np.float32(1.0).view(np.int32))
        rid = rid_base + row
        if "scores" in rows:
            sc = lax.bitcast_convert_type(lane_of("scores"), jnp.int32)
            for k in range(sc.shape[0]):
                lane[lanes["score"] + k] = on_valid(sc[k])
        if kind == "ext":
            lane[lanes["rid"]] = (jnp.where(valid, lane_of("index"), pad_id)
                                  if "index" in rows else rid)
        elif kind == "compact":
            label = lane_of("label")
            lab = ((label.astype(jnp.int32) & META_LABEL_MASK)
                   if num_class > 1 else (label > 0).astype(jnp.int32))
            meta = (rid & META_RID_MASK).astype(jnp.uint32) | on_valid(
                (lab.astype(jnp.uint32) << META_LABEL)
                | jnp.uint32(1 << META_BAG))     # all rows in-bag
            lane[lanes["meta"]] = lax.bitcast_convert_type(meta, jnp.int32)
        else:
            lane[lanes["label"]] = on_valid(lax.bitcast_convert_type(
                lane_of("label"), jnp.int32))
            lane[lanes["rid"]] = rid
            lane[lanes["weight"]] = on_valid(
                lax.bitcast_convert_type(lane_of("weight"), jnp.int32)
                if "weight" in rows else one)
        if with_bag and kind != "compact":
            lane[lanes["bag"]] = on_valid(one)
        zero = jnp.zeros(r_blk, jnp.int32)
        out += [lane.get(w, zero) for w in range(wcnt, w_pad)]
        block = jnp.where(live, jnp.stack(out), 0)
        block = block.reshape(w_pad, blk, chunk).transpose(1, 0, 2)
        rec = lax.dynamic_update_slice(rec, block, (first // chunk, 0, 0))
        return rec, jnp.sum(valid, keepdims=True, dtype=jnp.int32)


def _pack_program(mesh, axis, row_names, **static):
    """The jitted device pack of one block for a layout, `rec` donated;
    under a mesh, shard_mapped so every shard packs its own block."""
    key = ("pack_block", str(mesh), axis, row_names,
           tuple(sorted((k, v if not isinstance(v, dict)
                         else tuple(sorted(v.items())))
                        for k, v in static.items())))

    def factory():
        body = functools.partial(_pack_block, **static)
        if mesh is not None:
            from jax.sharding import PartitionSpec as P
            rows = {k: P(None, axis) if k == "scores" else P(axis)
                    for k in row_names}
            body = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(axis), P(axis), P(), P(axis), rows),
                out_specs=(P(axis), P(axis)), check_vma=False)
        return jax.jit(body, donate_argnums=(0,))
    return compile_cache.program(key, factory)


def pack_device(bins, label, weight, chunk: int, nc: int, *, bits: int,
                cols: int = 0, with_bag: bool = False, compact: bool = False,
                num_class: int = 1, with_prob: bool = False,
                ext: bool = False, rid_base: int = 0, index=None,
                scores=None, mesh=None, axis=None, per_shard: int = 0):
    """[N, F] uint8 host bins -> int32 records [shards * nc, W, C] on the
    device, packed there block by block (`_pack_block`): only the bins
    and the row lanes cross, each block's bins a row-major slice of
    `bins` uploaded flat while the block before it packs.

    The rows split into `shards` (the size of `mesh`'s `axis`, 1 without
    a mesh) contiguous ranges of `per_shard` rows (ceil(N / shards) where
    0; a layout may be sized for more rows than the bins hold);
    shard s's records are chunks s * nc .. of the result (its device's
    under `mesh`), row ids from rid_base + its first row, and every chunk
    past its rows is zero (`nc` counts the fresh chunks a caller keeps
    behind the data). `cols`: the columns the bin words cover, at least
    F (the rest are zero bins). `index` = (int32[N] ids, their count)
    puts each row's id in another index space into the EXT record's
    index lane, pads the count; `scores` [K, N] (host or device) fill the
    score lanes. Returns (rec, wcnt, W, cnts numpy [shards * nc], info:
    blocks, upload_bytes, and by shard `upload_bytes_by_shard` and
    `rows_by_shard`, the rows each shard's blocks wrote as the pack
    program counted them, each row once)."""
    n, fin = bins.shape
    cols = max(cols, fin)
    bpw = _bpw_for_bits(bits)
    wcnt = (cols + bpw - 1) // bpw
    lanes, w_pad = lane_layout(wcnt, with_bag, compact, num_class,
                               with_prob, ext=ext)
    shards = 1 if mesh is None else mesh.shape[axis]
    per = per_shard or -(-n // shards)
    nc_data = -(-per // chunk)
    assert nc >= nc_data
    span = nc_data * chunk
    bounds = [(min(n, s * per), min(n, s * per + per)) for s in range(shards)]
    cnts = np.zeros((shards, nc), np.int32)
    for s, (lo, hi) in enumerate(bounds):
        cnts[s, :(hi - lo) // chunk] = chunk
        if (hi - lo) % chunk:
            cnts[s, (hi - lo) // chunk] = (hi - lo) % chunk
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        def place(x, two_d=False):
            return jax.device_put(x, NamedSharding(
                mesh, P(None, axis) if two_d else P(axis)))
        rec = jax.jit(lambda: jnp.zeros((shards * nc, w_pad, chunk),
                                        jnp.int32),
                      out_shardings=NamedSharding(mesh, P(axis)))()
    else:
        def place(x, two_d=False):
            return jax.device_put(x)
        rec = jnp.zeros((nc, w_pad, chunk), jnp.int32)
    crossed = np.zeros(shards, np.int64)     # bytes sent to each shard
    blk = pack_block_chunks(chunk, fin, nc_data)

    def by_shard(x, dtype):
        """Row-order `x` [..., N] on the device as `_pack_block` reads
        it: each shard's rows at the start of its `span`, zero behind
        them, under a mesh; else as it is (no copy of a device array),
        padded only where it is shorter than a block."""
        nonlocal crossed
        if mesh is None:
            if not isinstance(x, jax.Array):
                x = np.asarray(x, dtype)
                crossed[0] += x.nbytes
            x = jnp.asarray(x, dtype)
            short = blk * chunk - x.shape[-1]
            if short > 0:
                x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, short)])
            return x
        x = np.asarray(x, dtype)
        out = np.zeros(x.shape[:-1] + (shards * span,), dtype)
        for s, (lo, hi) in enumerate(bounds):
            out[..., s * span:s * span + hi - lo] = x[..., lo:hi]
        crossed += out.nbytes // shards
        return place(out, x.ndim == 2)

    rows = {}
    kind = "ext" if ext else "compact" if compact else "standard"
    if kind != "ext":
        rows["label"] = by_shard(np.zeros(n, np.float32) if label is None
                                 else label, np.float32)
    if kind == "standard" and weight is not None:
        rows["weight"] = by_shard(weight, np.float32)
    if ext and index is not None:
        rows["index"] = by_shard(index[0], np.int32)
    if scores is not None:
        rows["scores"] = by_shard(scores, np.float32)
    pad_id = 0 if index is None else int(index[1])
    facts = np.asarray([[hi - lo, rid_base + lo, pad_id]
                        for lo, hi in bounds], np.int32)
    facts = place(facts)
    blocks = -(-nc_data // blk)

    def info(written=()):
        """The pack's counters; `written`: each block's rows by shard, as
        its program counted them, read under a mesh (where a shard can
        pack apart from the others) once the records are written. On one
        chip the rows are the bounds'."""
        if mesh is None:
            return dict(blocks=len(written), upload_bytes=int(crossed.sum()),
                        upload_bytes_by_shard=crossed.tolist(),
                        rows_by_shard=[hi - lo for lo, hi in bounds])
        rows = np.zeros(shards, np.int64)
        for b, got in enumerate(jax.device_get(list(written))):
            # the last block starts early: the rows it writes once more
            # were counted by the block before it
            first = min(b * blk, nc_data - blk) * chunk
            again = [max(0, min(b * blk * chunk, hi - lo) - first)
                     for lo, hi in bounds]
            rows += np.asarray(got).reshape(-1) - np.asarray(again)
        return dict(blocks=len(written), upload_bytes=int(crossed.sum()),
                    upload_bytes_by_shard=crossed.tolist(),
                    rows_by_shard=rows.tolist())
    if blocks == 0:
        return rec, wcnt, w_pad, cnts.reshape(-1), info()
    program = _pack_program(
        mesh, axis, tuple(sorted(rows)), chunk=chunk, blk=blk, cols=fin,
        bits=bits, wcnt=wcnt, lanes=lanes, w_pad=w_pad, kind=kind,
        with_bag=with_bag, num_class=num_class)
    width = blk * chunk * fin
    if mesh is not None:
        sharding = NamedSharding(mesh, P(axis))
        shard_of = {d: i.start // width for d, (i,) in
                    sharding.addressable_devices_indices_map(
                        (shards * width,)).items()}
    tokens, written = [], []
    for b in range(blocks):
        # the last block ends with the data: it starts early instead,
        # writing chunks of the block before it once more, alike
        first = min(b * blk, nc_data - blk) * chunk
        parts = []
        for s, (lo, hi) in enumerate(bounds):
            part = np.ascontiguousarray(
                bins[min(hi, lo + first):min(hi, lo + first + blk * chunk)])
            part = part.reshape(-1)
            if part.size < width:
                part = np.concatenate([part, np.zeros(width - part.size,
                                                      np.uint8)])
            parts.append(part)
            crossed[s] += part.nbytes
        if mesh is None:
            block = jax.device_put(parts[0])
        else:
            block = jax.make_array_from_single_device_arrays(
                (shards * width,), sharding,
                [jax.device_put(parts[s], d) for d, s in shard_of.items()])
        rec, token = program(rec, block, np.int32(first), facts, rows)
        tokens.append(token)
        written.append(token)
        if len(tokens) > 2:     # at most two blocks in flight
            tokens.pop(0).block_until_ready()  # graftlint: disable=LGT002 load-time pacing of the pack's uploads, not a round-loop fence
    return rec, wcnt, w_pad, cnts.reshape(-1), info(written)


def pack_records(bins: np.ndarray, label: np.ndarray,
                 weight, chunk: int, with_bag: bool = False,
                 compact: bool = False, num_class: int = 1,
                 with_prob: bool = False, max_bin: int = 0,
                 ext: bool = False, rid_base: int = 0, index=None):
    """[N, F] uint8 bins -> [NC, W, C] int32 records, as numpy: the
    device pack (`pack_device`) of every row, read back.

    Returns (records, wcnt, W, cnts, bits) where cnts[i] is the number of
    valid rows in chunk i (C except the last). rid_base offsets the
    stored row ids (data-parallel shards pack their local rows with
    GLOBAL ids). `index` = (int32[N] ids, their count) puts each row's id
    in another index space into the EXT record's index lane in place of
    its row id; pad cells get the count, one past every id, as they do in
    row ids.
    """
    bits = bin_bits(bins, max_bin)
    nc = -(-bins.shape[0] // chunk)
    rec, wcnt, w_pad, cnts, _ = pack_device(
        bins, label, weight, chunk, nc, bits=bits, with_bag=with_bag,
        compact=compact, num_class=num_class, with_prob=with_prob, ext=ext,
        rid_base=rid_base, index=index)
    return np.asarray(rec), wcnt, w_pad, cnts, bits


# ---------------------------------------------------------------------------
# move pass
# ---------------------------------------------------------------------------
def pack_route2(db, nb, boff=0, bpk=0):
    """Encode route word 2: db | (nb - 1) << 8 | boff << 16 | bpk << 24.

    num_bin stores BIASED (nb - 1 <= 255) so every field is 8 bits and
    the word stays within 25 bits — the narrow fields are what lets the
    split threshold/bin arithmetic stay 8-bit end to end at
    max_bin = 255. Single encode point: the aligned builder and the
    kernel-parity tests both construct r2 through this helper, so the
    layout can never drift between encoder and the in-kernel decoders
    (_unpack_bundle/_goes_left). Works on python ints, numpy and jax
    arrays alike."""
    return ((db & 255) | (((nb - 1) & 255) << 8) | ((boff & 255) << 16)
            | ((bpk & 1) << 24))


def _unpack_bundle(binv, r2):
    """EFB: BUNDLE column value -> the split feature's own bin — MUST
    stay bit-identical to ops/partition.bundle_unpack (the valid-set
    walker and fused partition path route through that helper;
    tests/test_efb.py::test_kernel_unpack_matches_bundle_unpack pins the
    equivalence over the full domain). This arithmetic-select form
    exists because Mosaic cannot broadcast the scalar bpk bool into a
    vector select (arith.trunci to i1 fails in-kernel). r2 packs the
    feature-space default_bin/num_bin plus boff/bpk (see pack_route2).
    Must run BEFORE _cat_word/_goes_left — both consume feature-space
    bins."""
    db = r2 & 255
    nb = ((r2 >> 8) & 255) + 1
    boff = (r2 >> 16) & 255
    bpk = (r2 >> 24) & 1
    p = binv - boff
    in_range = ((p >= 0) & (p < nb - 1)).astype(jnp.int32)
    b = jnp.where(p >= db, p + 1, p)
    unpacked = in_range * b + (1 - in_range) * db
    return bpk * unpacked + (1 - bpk) * binv


def _goes_left(binv, r1, r2, valid, catw=None):
    """Reference DenseBin::Split routing (dense_bin.hpp:195-283):
    numerical with missing None/Zero/NaN, categorical by bitset
    membership (Common::FindInBitset); copy-through routes all left.

    Pure i32 arithmetic — Mosaic can't broadcast scalar bools into vector
    selects (arith.trunci to i1 fails), so the scalar route bits enter as
    0/1 integers and the final bool comes from one vector comparison.
    `catw` = per-row selected bitset word (vector, from _cat_word)."""
    thr = r1 & 255
    dl = (r1 >> R_DL) & 1                      # scalar 0/1
    mt = (r1 >> R_MT) & 3
    copy = (r1 >> R_COPY) & 1
    db = r2 & 255
    nb = ((r2 >> 8) & 255) + 1
    base = (binv <= thr).astype(jnp.int32)     # vector 0/1
    mtz = jnp.int32(0) + ((mt == MISSING_ZERO_C).astype(jnp.int32))
    mtn = (mt == MISSING_NAN_C).astype(jnp.int32)
    is_def = (mtz * (binv == db).astype(jnp.int32)
              + mtn * (binv == nb - 1).astype(jnp.int32))
    left_i = is_def * dl + (1 - is_def) * base
    if catw is not None:
        iscat = (r1 >> R_CAT) & 1              # scalar 0/1
        cat_i = (catw >> (binv & 31)) & 1      # vector bit test
        left_i = iscat * cat_i + (1 - iscat) * left_i
    vi = valid.astype(jnp.int32)
    out = copy * vi + (1 - copy) * left_i * vi
    return out != 0


def _cat_word(cbits_ref, ks, binv):
    """Per-row bitset word for a categorical split: cbits_ref is the
    round's compact [K*8] flat bitset table (SMEM prefetch), ks the
    block's compact split id."""
    bw = binv >> 5
    w = jnp.zeros_like(binv)
    for j in range(8):
        w = jnp.where(bw == j, cbits_ref[ks * 8 + j], w)
    return w



def _in_bag(rows, bag_lane, wcnt):
    """Which rows of a [W, C] block of single-class records are in the
    bag: the f32 lane `bag_lane` over 0.5 (a 0/1 mask, or a multiplier
    that is 0 out of the sample), or with `bag_lane` -2 the compact
    record's bag bit, the sign of its meta lane."""
    if bag_lane == -2:
        return ((rows[wcnt + 1, :] >> META_BAG) & 1) != 0
    return lax.bitcast_convert_type(rows[bag_lane, :], jnp.float32) > 0.5


def _payload_gh(rows, nvalid, chunk, wcnt, grad_fn, bag_lane,
                num_class=1, gh_off=2):
    """(g, h, take) for a [W, C] row block: lane-resident gradients
    (standard layout, or multiclass compact where per-class g/h were
    written from pre-iteration scores) or recomputed in-kernel
    (single-class compact, grad_fn not None — the objective's pointwise
    gradient inlined into the Pallas kernel). bag_lane: >= 0 an f32 0/1
    lane, -2 the meta-lane bag BIT, -1 none. gh_off: grad lane offset
    from wcnt (2 in the standard layout, 1 in the ext layout)."""
    posh = lax.broadcasted_iota(jnp.int32, (1, chunk), 1)[0]
    take = posh < nvalid
    if grad_fn is not None and num_class > 1:
        # multiclass: engine-built closure with lane indices baked in,
        # reading the class's prob/score lane + the meta label bits
        g, h, bagmask = grad_fn(rows)
        if bag_lane == -2 and bagmask is not None:
            take = take & bagmask
    elif grad_fn is not None:
        meta = rows[wcnt + 1, :]
        score = lax.bitcast_convert_type(rows[wcnt, :], jnp.float32)
        label = ((meta >> META_LABEL) & META_LABEL_MASK) \
            .astype(jnp.float32)
        g, h = grad_fn(score, label, None)
        if bag_lane == -2:     # compact bagging: bag bit masks stats
            take = take & _in_bag(rows, bag_lane, wcnt)
    else:
        g = lax.bitcast_convert_type(rows[wcnt + gh_off, :], jnp.float32)
        h = lax.bitcast_convert_type(rows[wcnt + gh_off + 1, :],
                                     jnp.float32)
        if bag_lane >= 0:
            take = take & _in_bag(rows, bag_lane, wcnt)
    return g, h, take


def _nibble_hist(b_pad: int) -> bool:
    """True when the histogram accumulates via the hi/lo NIBBLE
    factorization instead of a full-width one-hot: at B=256 the one-hot
    build is 256 compares per (row, feature) on the VPU; factoring the
    bin into two 4-bit halves needs 32 compares + 96 bf16 products and
    the same MAC count (measured 7.37 -> 5.99 ns/row full-data pass).
    The store keeps the kernel-friendly [F, 6, lo, hi] layout; callers
    remap to [F, bin, 3] outside the kernel."""
    return b_pad > 128


def _hist_mode(b_pad: int, subbin: bool = False) -> str:
    """Histogram accumulation mode for a bin width.

    "group": full-width one-hot, features batched per MXU issue
    (b_pad <= 128). Above 128 bins the one-hot build cost forces a
    factored form: "nibble" (legacy bit-3 payload split x 128-wide
    one-hot — 130 compares per row/feature) or "subbin" (hi/lo 4-bit
    halves: TWO 16-wide one-hots, 32 compares, one [16,C]x[128,C] MXU
    issue into a [16, 128] = [lo, pay*16+hi] tile — exactly two f32
    VMEM tiles). subbin is the tpu_hist_subbin knob resolved by the
    caller; it only applies where the factored form is needed."""
    if b_pad > 128:
        return "subbin" if subbin else "nibble"
    return "group"


def _hist_accum(pay6, bin_of, accum, num_features, b_pad, group, C,
                subbin=False):
    """Accumulate one chunk's histogram contributions.

    pay6: [6, C] hi/lo payload; bin_of(f) -> [C] i32 bin values;
    accum(idx, contrib) adds into the store — grouped one-hot indexes by
    group id with [6, group*b_pad] blocks, nibble mode by feature with
    [96, 16] = [6*lo, hi] blocks, subbin mode by feature with [16, 128]
    = [lo, pay*16 + hi] blocks (cols >= 96 stay zero)."""
    mode = _hist_mode(b_pad, subbin)
    if mode == "subbin":
        # sub-binned accumulation: bin = hi*16 + lo. The payload rides
        # the HI one-hot (Z = pay6 x oh_hi -> [96, C], zero-padded to a
        # full [128, C] tile) and ONE MXU contraction against the 16-wide
        # LO one-hot lands the whole [16, 128] sub-bin tile — 32 VPU
        # compares per (row, feature) vs the nibble form's 130, and the
        # tile folds to [256, 3] once per store finalize instead of
        # per-chunk repacking.
        iota16 = lax.broadcasted_iota(jnp.int32, (16, C), 0)
        for f in range(num_features):
            bv = bin_of(f)
            oh_hi = ((bv >> 4)[None, :] == iota16).astype(jnp.bfloat16)
            oh_lo = ((bv & 15)[None, :] == iota16).astype(jnp.bfloat16)
            Z = (pay6[:, None, :] * oh_hi[None, :, :]).reshape(96, C)
            Zp = jnp.concatenate(
                [Z, jnp.zeros((32, C), jnp.bfloat16)], axis=0)
            contrib = lax.dot_general(oh_lo, Zp, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            accum(f, contrib)
        return
    if mode == "nibble":
        # factor bin = hi*16 + b3*8 + lo3 into a 2-row payload split
        # (bit 3) and a 128-wide one-hot (lo3*16 + hi): the [12, 128]
        # contrib tiles VMEM exactly (no 16-lane padding, no in-kernel
        # repack) and Z is only 12 rows of products
        iota2 = lax.broadcasted_iota(jnp.int32, (2, C), 0)
        iota128 = lax.broadcasted_iota(jnp.int32, (128, C), 0)
        for f in range(num_features):
            bv = bin_of(f)
            oh2 = (((bv >> 3) & 1)[None, :] == iota2).astype(jnp.bfloat16)
            col = (bv & 7) * 16 + (bv >> 4)
            ohc = (col[None, :] == iota128).astype(jnp.bfloat16)
            Z = (pay6[:, None, :] * oh2[None, :, :]).reshape(12, C)
            contrib = lax.dot_general(Z, ohc, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            accum(f, contrib)
        return
    iota_b = lax.broadcasted_iota(jnp.int32, (b_pad, C), 0)
    ngroups = (num_features + group - 1) // group
    for gi in range(ngroups):
        ohs = []
        for j in range(group):
            f = min(gi * group + j, num_features - 1)
            ohs.append((bin_of(f)[None, :] == iota_b)
                       .astype(jnp.bfloat16))
        onehot = jnp.concatenate(ohs, axis=0)
        contrib = lax.dot_general(pay6, onehot, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        accum(gi, contrib)


def slot_hist_bytes(ncols: int, b_pad: int, subbin: bool = False) -> int:
    """Bytes of ONE slot's histogram block in the engine's histogram
    stores — the single source of truth for the per-round VMEM budget
    check that decides between the VMEM-resident store and the HBM
    spill ring (aligned_builder / device_learner)."""
    group = 8 if b_pad <= 64 else 4
    return 4 * int(np.prod(
        _hist_store_shape(0, ncols, b_pad, group, subbin)[1:]))


def hist_layout(cfg, ncols: int, bh: int, K: int):
    """Resolve the aligned histogram store layout for a K-split round:
    (subbin, spill, slot_bytes, budget_bytes).

    subbin: the tpu_hist_subbin knob ("auto"/"on" enable the sub-binned
    accumulation wherever the factored form applies, i.e. bh > 128;
    "off" keeps the legacy nibble form). spill: True when the
    [K+1]-slot store exceeds the tpu_hist_spill_vmem_mb VMEM budget —
    the move pass then keeps the store in HBM behind the 2-deep DMA
    staging ring instead of shrinking K. Shared between
    AlignedEngine._build_program and the device learner's gate notes so
    the logged path always matches the compiled kernel."""
    knob = str(getattr(cfg, "tpu_hist_subbin", "auto") or "auto").lower()
    subbin = knob != "off"
    slot_bytes = slot_hist_bytes(ncols, bh, subbin)
    budget = int(float(getattr(cfg, "tpu_hist_spill_vmem_mb", 48) or 48)
                 * (1 << 20))
    spill = slot_bytes * (K + 1) > budget
    return subbin, spill, slot_bytes, budget


def _hist_store_shape(num_slots, num_features, b_pad, group,
                      subbin=False):
    """Per-pass histogram store shape (see _hist_accum layouts). The
    nibble layout's [12, 128] and the subbin layout's [16, 128] blocks
    fill 128-lane tiles exactly — a narrow minor dim would pad 8x in
    VMEM (353 MB at 257 slots)."""
    mode = _hist_mode(b_pad, subbin)
    if mode == "subbin":
        return (num_slots + 1, num_features, 16, 128)
    if mode == "nibble":
        return (num_slots + 1, num_features, 12, 128)
    ngroups = (num_features + group - 1) // group
    return (num_slots + 1, ngroups, 6, group * b_pad)


# ---------------------------------------------------------------------------
# exact counts
# ---------------------------------------------------------------------------
# The histograms' count stat is an exact integer from the kernels to the
# split finder. Each chunk's MXU count is an integer of at most C, and f32
# holds every integer up to 2^24: a sum over chunks is what rounds. So a
# store keeps a count as two f32 halves that are both exact integers: the
# count payload's hi row (`_hi_lo6`'s row 2) takes the chunks' counts, and
# its lo row (row 5), which that payload leaves 0, the multiples of
# COUNT_UNIT folded out of the hi row (`_fold_counts`) before it can pass
# 2^24. `_hist_store_finalize` joins the halves into an i32, and the
# finished histogram's count channel holds its bits plus COUNT_BIAS
# (`count_bits`, `hist_counts`): the bits of 1.0 and up, a normal f32 for
# any count under 2^30, which the device moves as it is wherever it moves
# f32. The plain bits of a count under 2^23 are a denormal, which the TPU
# flushes to zero wherever it computes with one: a build program that
# carried them placed rows out of bounds on a v5e.
COUNT_UNIT = 1 << 12
F32_EXACT = 1 << 24
COUNT_BIAS = 0x3F800000


def count_fold(chunk: int, nc: int) -> int:
    """Chunks a store slot may take between two folds of its counts, for
    a pass over `nc` chunks of `chunk` rows: after a fold the hi half is
    under COUNT_UNIT, and each chunk adds at most `chunk`. 0 where the
    whole pass cannot carry a bin past 2^24, and nothing folds."""
    every = (F32_EXACT - COUNT_UNIT) // chunk
    return every if nc > every else 0


def _count_halves(mode: str):
    """Where a store tile of `mode` keeps the count's hi and lo halves:
    (axis, hi (start, stop), lo (start, stop)), the axis counted from the
    end (see `_hist_accum`'s layouts)."""
    if mode == "subbin":        # [F, lo, pay * 16 + hi]
        return -1, (32, 48), (80, 96)
    if mode == "nibble":        # [F, pay * 2 + b3, lo3 * 16 + hi]
        return -2, (4, 6), (10, 12)
    return -2, (2, 3), (5, 6)   # [groups, pay, group * b_pad]


def _fold_counts(ref, mode: str):
    """Fold the multiples of COUNT_UNIT out of the counts' hi half of the
    store `ref` (one tile or a [slots, ...] stack of them) into their lo
    half. Every step is exact f32 arithmetic on integers under 2^24."""
    axis, (h0, h1), (l0, l1) = _count_halves(mode)
    if axis == -2:
        lead = (slice(None),) * (len(ref.shape) - 2)
        hi_i = lead + (slice(h0, h1), slice(None))
        lo_i = lead + (slice(l0, l1), slice(None))
        hi = ref[hi_i]
        q = jnp.floor(hi * (1.0 / COUNT_UNIT))
        ref[hi_i] = hi - q * COUNT_UNIT
        ref[lo_i] = ref[lo_i] + q
        return
    # lanes: the quotient moves from the hi columns to the lo columns by
    # one rotation along the lanes
    x = ref[...]
    col = lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    q = jnp.where((col >= h0) & (col < h1),
                  jnp.floor(x * (1.0 / COUNT_UNIT)), 0.0)
    ref[...] = x - q * COUNT_UNIT + pltpu.roll(q, l0 - h0, x.ndim - 1)


def count_bits(counts):
    """The f32 a count channel holds for i32 `counts` (under 2^30)."""
    return lax.bitcast_convert_type(counts + COUNT_BIAS, jnp.float32)


def hist_counts(hist):
    """The exact i32 counts a finished histogram's count channel holds
    (`count_bits`): [..., F, B]. A zeroed histogram's bits read 0."""
    return jnp.maximum(
        lax.bitcast_convert_type(hist[..., 2], jnp.int32) - COUNT_BIAS, 0)


def _join_halves(h, axis: int):
    """The payload's hi and lo halves (`axis`, six long: hi g, h, count,
    lo g, h, count) joined: g and h summed in f32, the count made the
    exact i32 hi + lo * COUNT_UNIT, held as `count_bits` in channel 2."""
    hi = lax.slice_in_dim(h, 0, 3, axis=axis)
    lo = lax.slice_in_dim(h, 3, 6, axis=axis)
    cnt = (lax.slice_in_dim(hi, 2, 3, axis=axis).astype(jnp.int32)
           + lax.slice_in_dim(lo, 2, 3, axis=axis).astype(jnp.int32)
           * COUNT_UNIT)
    ch = lax.broadcasted_iota(jnp.int32, hi.shape, axis)
    return jnp.where(ch == 2, count_bits(cnt), hi + lo)


def with_counts(hist, counts):
    """`hist` [..., 3] with its count channel holding the i32 `counts`
    [...] (`count_bits`)."""
    ch = lax.broadcasted_iota(jnp.int32, hist.shape, hist.ndim - 1)
    return jnp.where(ch == 2, count_bits(counts)[..., None], hist)


def hist_sub(a, b):
    """a - b of two finished histograms, the counts as integers (parent
    minus sibling, `FeatureHistogram::Subtract`)."""
    return with_counts(a - b, hist_counts(a) - hist_counts(b))


def _hist_store_finalize(out, num_slots, num_features, b_pad, group,
                         subbin=False):
    """Store -> hist[num_slots, F, b_pad, 3] (hi+lo payload halves
    combined, `_join_halves`: the count channel holds exact i32 counts;
    nibble/subbin modes also remap bin = hi*16 + lo)."""
    mode = _hist_mode(b_pad, subbin)
    if mode == "subbin":
        # [ns+1, F, lo, pay*16 + hi] -> drop the 32 zero pad cols, fold
        # the hi/lo payload halves, land bin = hi*16 + lo
        h = out[..., :96].reshape(num_slots + 1, num_features, 16, 6, 16)
        h = _join_halves(h, 3)                     # [ns,F,lo,3,hi]
        h = jnp.transpose(h, (0, 1, 4, 2, 3))      # [ns,F,hi,lo,3]
        h = h.reshape(num_slots + 1, num_features, 256, 3)
        return h[:num_slots, :, :b_pad]
    if mode == "nibble":
        h = out.reshape(num_slots + 1, num_features, 6, 2, 8, 16)
        h = _join_halves(h, 2)                     # [ns,F,3,b3,lo3,hi]
        h = jnp.transpose(h, (0, 1, 5, 3, 4, 2))   # [ns,F,hi,b3,lo3,3]
        h = h.reshape(num_slots + 1, num_features, 256, 3)
        return h[:num_slots, :, :b_pad]
    ngroups = (num_features + group - 1) // group
    h = out.reshape(num_slots + 1, ngroups, 6, group, b_pad)
    h = _join_halves(h, 2)
    h = jnp.moveaxis(h, 2, 4)
    h = h.reshape(num_slots + 1, ngroups * group, b_pad, 3)
    return h[:num_slots, :num_features]


def _hi_lo6(pay):
    """Split [3, C] f32 payload rows into an exact [6, C] bf16 (hi, lo)
    pair via mantissa TRUNCATION: hi = pay with the low 16 mantissa bits
    zeroed (exactly bf16-representable), lo = bf16(pay - hi). The naive
    round-to-nearest form `bf16(pay - f32(bf16(pay)))` is silently
    simplified to 0 by XLA's convert-folding pass, dropping the
    compensation term and leaving raw bf16 rounding error in the
    histogram sums (~1e-3 absolute on value-concentrated data); the bit
    mask is opaque to that pass, and hi + lo reconstructs ~23 bits."""
    pi = lax.bitcast_convert_type(pay, jnp.int32)
    hi_f = lax.bitcast_convert_type(pi & jnp.int32(-65536), jnp.float32)
    lo = (pay - hi_f).astype(jnp.bfloat16)
    hi = hi_f.astype(jnp.bfloat16)     # exact: low bits already zero
    return jnp.concatenate([hi, lo], axis=0)


def _move_kernel(r1_ref, r2_ref, blbr_ref, meta_ref,
                 hslot_ref, cbits_ref, fetch_ref, src_ref, reca_ref,
                 recb_ref, bufa_ref, bufb_ref, hist_ref, stag,
                 fbuf, hacc, hstage, tri, cur_ref, sems, *, chunk, w_pad,
                 w_used, wcnt, num_features, b_pad, group, dummy,
                 bag_lane, bits, grad_fn, num_class, gh_off, bundled,
                 subbin, spill, route_bag, fold_every):
    """One grid step of the fused move+hist pass.

    TWO record buffers, A and B, each an operand aliased to an output
    (bufa_ref / bufb_ref are the buffers in HBM, reca_ref / recb_ref the
    blocked fetch of each): the pass reads the one `src_ref[0]` names
    (0 = A) and writes the other, so a caller that carries both through
    a loop never has the fresh output of one round copied back over the
    input of the next. The buffer that is not the source keeps its
    blocked index frozen, so only the source is fetched.

    SPLIT chunks: partition rows into the block's left/right staging
    rings (exact byte-plane one-hot matmul, in sub-tiles of
    route_tile(chunk) rows), flush full chunks to dynamic
    destination chunks, and accumulate the smaller child's histogram
    DIRECTLY from the chunk's smaller-side rows into a VMEM-resident
    store indexed by COMPACT per-round slot ids (constant out-spec: the
    whole [K+1, ...] store lives in VMEM across the grid and flushes
    once). COPY chunks (unsplit blocks): one direct HBM->HBM DMA to the
    prefetched destination — no VMEM staging, and the blocked input
    pipeline SKIPS the fetch (fetch_ref holds the last split chunk's
    index, so the block index doesn't change on copy runs).

    Flushes are ASYNC: each staging half is copied to one of two per-side
    flush buffers and DMA'd without waiting; a buffer/semaphore is reused
    only after its previous DMA is waited on (pending flags in SMEM),
    and the final grid step drains all outstanding DMAs.

    SPILL mode (static `spill`): the [K+1, ...] store is HBM-resident
    instead of VMEM-resident — only the per-block hacc accumulator and
    a 2-deep staging ring (hstage) live in VMEM. A slotted block's
    finished hacc is copied to hstage[p] (p ping-pongs per slotted
    block) and DMA'd to its HBM slot without waiting, overlapping the
    next block's accumulation with the previous block's writeback. Each
    slot is written by exactly ONE block per pass, so the DMA is a plain
    overwrite; unvisited slots stay uninitialized and the wrapper masks
    them to zero from hslots.

    EXACT COUNTS (static `fold_every`, `count_fold`): a block of more
    than `fold_every` smaller-side chunks folds hacc's counts
    (`_fold_counts`) after every `fold_every` of them, so that no count
    half passes 2^24; each slot takes one block, whole.

    ROUTE BY BAG (static `route_bag`, the partition that parks the rows a
    bag leaves out): a split chunk's rows go left where the row is in the
    bag (`_in_bag`) and right where it is not, whatever their bins, and
    no histogram is compiled. A kernel of its own, so one that splits by
    features carries none of it.

    cur_ref: [cur_l, cur_r, fl_l, fl_r, pend 4..15, dst 16..27,
    src 28..39, spill_blk 40, spill_pend 41..42, spill_dst 43..44,
    chunks in hacc since its counts last folded 45];
    sems: slots 0-3 = VMEM flush, 4-11 = HBM->HBM copy,
    12-13 = hist spill."""
    i = pl.program_id(0)
    C = chunk
    r1 = r1_ref[i]
    meta = meta_ref[i]
    is_last = (meta >> 21) & 1
    a_is_src = src_ref[0] == 0

    def start_copy(make):
        """Start the DMA `make(source buffer, destination buffer)` gives,
        for the direction this pass runs in."""
        @pl.when(a_is_src)
        def _():
            make(bufa_ref, bufb_ref).start()

        @pl.when(~a_is_src)
        def _():
            make(bufb_ref, bufa_ref).start()

    @pl.when(i == 0)
    def _():
        # SMEM scratch is NOT zero-initialized: clear the DMA pending
        # flags and saved src/dst indices before any use
        for j in range(48):
            cur_ref[j] = 0
        # the rank mask does not depend on the data: built once a pass
        tri[...] = (lax.broadcasted_iota(jnp.int32, tri.shape, 0)
                    < lax.broadcasted_iota(jnp.int32, tri.shape, 1)
                    ).astype(jnp.bfloat16)
        if not spill:
            hist_ref[...] = jnp.zeros_like(hist_ref)

    @pl.when(((meta >> 20) & 1) != 0)     # first chunk of block
    def _():
        cur_ref[0] = 0
        cur_ref[1] = 0
        cur_ref[2] = 0
        cur_ref[3] = 0
        cur_ref[45] = 0
        # per-block hist accumulator: STATIC address per chunk (a
        # dynamic-index RMW per chunk measured 3x slower); flushed to
        # the compact store once per block on its last chunk
        hacc[...] = jnp.zeros_like(hacc)

    cntv = meta & ((1 << 20) - 1)
    is_copy = (r1 >> R_COPY) & 1
    hs = hslot_ref[i]
    hslot = hs & 0xFFFFFF        # compact slot of the smaller child
    hside = (hs >> 24) & 1       # its side: 0 = the chunk's left rows

    def wait_slot(slot):
        # a wait reads its semaphore and the size of its destination,
        # one chunk in either buffer: it names A whatever the direction
        if slot < 4:            # static: flush slots DMA from VMEM
            pltpu.make_async_copy(fbuf.at[slot],
                                  bufa_ref.at[cur_ref[16 + slot]],
                                  sems.at[slot]).wait()
        else:                   # copy slots DMA HBM->HBM
            pltpu.make_async_copy(bufa_ref.at[cur_ref[28 + slot]],
                                  bufa_ref.at[cur_ref[16 + slot]],
                                  sems.at[slot]).wait()
        cur_ref[4 + slot] = 0

    def wait_spill(p):
        pltpu.make_async_copy(hstage.at[p],
                              hist_ref.at[cur_ref[43 + p]],
                              sems.at[12 + p]).wait()
        cur_ref[41 + p] = 0

    bpw = _bpw_for_bits(bits)
    bmask = (1 << bits) - 1

    def hist_flushed(rows, nvalid):
        """Accumulate a flushed [W, C] chunk of the smaller child (first
        nvalid rows valid) into the per-block accumulator: flushed
        buffers hold the side's rows COMPACTED, so the one-hot work runs
        at full density on exactly the smaller child's rows. Bagged
        stats cover IN-BAG rows only (gbdt.cpp:209-275)."""
        g, h, take = _payload_gh(rows, nvalid, C, wcnt, grad_fn,
                                 bag_lane, num_class, gh_off)
        gm = jnp.where(take, g, 0.0)
        hm = jnp.where(take, h, 0.0)
        cntp = take.astype(jnp.float32)
        pay = jnp.stack([gm, hm, cntp], axis=0)
        pay6 = _hi_lo6(pay)

        def bin_of(f):
            return (rows[f // bpw, :] >> ((f % bpw) * bits)) & bmask

        def accum(idx, contrib):
            hacc[idx] += contrib

        _hist_accum(pay6, bin_of, accum, num_features, b_pad, group, C,
                    subbin)

    # ---- copy fast-path: unsplit blocks shift as whole chunks — one
    # direct HBM->HBM DMA to the prefetched destination (bl): no fetch,
    # no VMEM staging, 8 DMAs in flight
    bl_i = blbr_ref[i] & 0xFFFF
    br_i = (blbr_ref[i] >> 16) & 0xFFFF

    @pl.when((is_copy != 0) & (cntv > 0))
    def _():
        for cp in range(8):
            @pl.when((i % 8) == cp)
            def _():
                slot = 4 + cp

                @pl.when(cur_ref[4 + slot] != 0)
                def _():
                    wait_slot(slot)
                start_copy(lambda src, dst: pltpu.make_async_copy(
                    src.at[i], dst.at[bl_i], sems.at[slot]))
                cur_ref[4 + slot] = 1
                cur_ref[16 + slot] = bl_i
                cur_ref[28 + slot] = i

    # ---- split path
    S = route_tile(C)
    T = C // S

    @pl.when(is_copy == 0)
    def _():
        wsel = (r1 >> R_WSEL) & 255
        r2 = r2_ref[i]
        U = w_used
        posS = lax.broadcasted_iota(jnp.int32, (1, S), 1)[0]

        def window(side, cur_s):
            """The S-aligned window of the side's 2C staging ring that
            holds its cursor: staging chunk and lane offset (a dynamic
            multiple of S >= 128, which Mosaic can slice)."""
            win = (cur_s % (2 * C)) // S
            return (side * 2 + win // T, slice(None),
                    pl.ds(pl.multiple_of((win % T) * S, S), S))

        def route_tile_rows(t, carry):
            """Partition rows [t*S, (t+1)*S) of the chunk into the
            block's staging rings; cur_l, cur_r = rows of the block
            staged so far on each side, open_l, open_r = each side's
            OPEN window (the one its cursor is in) as it stands, carried
            in registers so that no tile loads a window. The work is
            S x S whatever C is."""
            cur_l, cur_r, open_l, open_r = carry
            # both buffers' tiles are loaded and one is selected: a branch
            # here, eight times a chunk, cost 0.05 us a chunk
            rows_t = pl.ds(pl.multiple_of(t * S, S), S)
            rec = jnp.where(a_is_src, reca_ref[0, :, rows_t],
                            recb_ref[0, :, rows_t])
            valid = t * S + posS < cntv
            if route_bag:
                left = _in_bag(rec, bag_lane, wcnt) & valid
            else:
                word = rec[0, :]
                for wj in range(1, wcnt):
                    word = jnp.where(wsel == wj, rec[wj, :], word)
                binv = (word >> ((r1 >> R_SHIFT) & 31)) & bmask
                if bundled:
                    binv = _unpack_bundle(binv, r2)
                catw = _cat_word(cbits_ref, hslot, binv)
                left = _goes_left(binv, r1, r2, valid, catw)

            # ranks via one triangular matmul (measured FASTER on the
            # MXU than log2(C) pltpu.roll prefix sums: 3.33 vs 3.82
            # ns/row)
            li = left.astype(jnp.bfloat16)[None, :]
            vi = valid.astype(jnp.bfloat16)[None, :]
            both = jnp.concatenate([li, vi], axis=0)          # [2, S]
            ranks = lax.dot_general(both, tri[...],
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            rank_l = ranks[0].astype(jnp.int32)
            rank_r = ranks[1].astype(jnp.int32) - rank_l
            k_l = jnp.sum(left.astype(jnp.int32))
            k_r = jnp.sum(valid.astype(jnp.int32)) - k_l

            # a side's rows of one tile land in ONE contiguous interval
            # [a, a + k) of its 2C ring, a < S and k <= S, so in at most
            # two of the ring's S-aligned windows: the one the cursor is
            # in (0) and the next (1).
            a_l = cur_l % S
            a_r = cur_r % S
            # ONE selector for the whole tile. An interval no longer
            # than S is injective modulo S, so a side's rows never share
            # an offset modulo S whichever window they fall in, and one
            # [U, S] block holds both windows' rows of the side at their
            # final offsets: the merge below takes window 0's part out of
            # it, and window 1's part opens the next window (disjoint:
            # a + k - S <= a). And
            # k_l + k_r <= S, so both sides fit ONE block: left rows at
            # their own offsets, right rows on the cyclic interval that
            # starts where the left one ends (e_l), rotated to their own
            # offsets after the matmul. Invalid rows select no offset.
            e_l = (a_l + k_l) % S
            q = jnp.where(left, a_l + rank_l, e_l + rank_r)   # < 2S
            lo_of = jnp.where(valid, jnp.where(q >= S, q - S, q), -1)

            # only the USED lanes ride the route matmul (w_used <=
            # w_pad: 8-sublane padding and, under the compact layout,
            # the unused tail lanes carry no data — pad lanes of the
            # output stay stale, which is fine because no kernel reads
            # past w_used).
            # int8 byte planes: the MXU takes s8 x s8 -> s32 at twice
            # the bf16 rate and the f32 -> i32 output converts
            # disappear; byte values wrap to signed but `& 255` after
            # the single-term selection recovers them exactly
            planes = jnp.concatenate(
                [((rec[:U] >> (8 * b)) & 255).astype(jnp.int8)
                 for b in range(4)], axis=0)                  # [4U, S]
            # the one-hot is built TRANSPOSED, [offset, row]: the row's
            # offset broadcasts along sublanes as it lies, where
            # [row, offset] needs a lane -> sublane relayout first
            # (measured 0.6 us a chunk), and the MXU contracts either.
            # Exact: each output offset receives a single term < 256.
            iota_o = lax.broadcasted_iota(jnp.int32, (S, S), 0)
            oh_lo = (lo_of[None, :] == iota_o).astype(jnp.int8)
            moved = lax.dot_general(planes, oh_lo,
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.int32)
            blk = moved & 255
            mrows_l = (blk[:U] | (blk[U:2 * U] << 8)
                       | (blk[2 * U:3 * U] << 16) | (blk[3 * U:] << 24))
            if U < w_pad:
                mrows_l = jnp.concatenate(
                    [mrows_l, jnp.zeros((w_pad - U, S), jnp.int32)],
                    axis=0)
            # the right rows, from e_l + rank to a_r + rank (modulo S)
            mrows_r = pltpu.roll(mrows_l, (a_r - e_l + S) % S, 1)

            # the side's rows of the open window merge into the carry,
            # and the result is stored with no load in front of it and no
            # branch (a branch in this loop cost more than the merge it
            # skipped: PERF.md section 6, PR 32). Where the tile filled
            # the window (a + k >= S) the next one opens with the
            # window-1 rows already at their offsets in mrows: what
            # stands past them is rewritten before any flush reads it,
            # since a flush reads only rows under the cursor
            opened = []
            for side, (mrows_side, a, k, cur_s, open_s) in enumerate((
                    (mrows_l, a_l, k_l, cur_l, open_l),
                    (mrows_r, a_r, k_r, cur_r, open_r))):
                m = (posS >= a) & (posS < a + k)
                merged = jnp.where(m[None, :], mrows_side, open_s)
                stag[window(side, cur_s)] = merged
                opened.append(jnp.where(a + k >= S, mrows_side, merged))
            return (cur_l + k_l, cur_r + k_r) + tuple(opened)

        # each side's open window is loaded once a grid step; tiles past
        # the chunk's last row hold nothing to route. route_unroll(C)
        # tiles share one loop body, where tile t + 1's decode and rank
        # matmul can overlap tile t's one-hot, route matmul and stores;
        # the tiles left over run one a trip, so no tile past the chunk's
        # rows is routed. The route alone is unrolled: the histogram
        # below keeps its one call site
        n_tiles = (cntv + S - 1) // S
        unroll = route_unroll(C)

        def route_group(g, carry):
            for j in range(unroll):
                carry = route_tile_rows(g * unroll + j, carry)
            return carry

        carry = (cur_ref[0], cur_ref[1], stag[window(0, cur_ref[0])],
                 stag[window(1, cur_ref[1])])
        groups = n_tiles // unroll
        carry = lax.fori_loop(0, groups, route_group, carry)
        if unroll > 1:
            carry = lax.fori_loop(groups * unroll, n_tiles, route_tile_rows,
                                  carry)
        new_l, new_r, open_l, open_r = carry
        # and stored once, for the flush below and the block's next step
        stag[window(0, new_l)] = open_l
        stag[window(1, new_r)] = open_r
        cur_ref[0] = jnp.where(is_last != 0, 0, new_l)
        cur_ref[1] = jnp.where(is_last != 0, 0, new_r)

        fl_h0 = cur_ref[2 + hside]

        def flush_side(side, fl_slot, base, cur_val):
            for _ in range(2):    # at most 2 flushes per side per step
                fl = cur_ref[fl_slot]
                full = cur_val - fl * C >= C
                fin = (is_last != 0) & (cur_val - fl * C > 0) & ~full

                @pl.when(full | fin)
                def _():
                    for p in range(2):
                        @pl.when((fl % 2) == p)
                        def _():
                            slot = side * 2 + p

                            @pl.when(cur_ref[4 + slot] != 0)
                            def _():
                                wait_slot(slot)
                            fbuf[slot] = stag[side * 2 + p]
                            start_copy(
                                lambda src, dst: pltpu.make_async_copy(
                                    fbuf.at[slot], dst.at[base + fl],
                                    sems.at[slot]))
                            cur_ref[4 + slot] = 1
                            cur_ref[16 + slot] = base + fl
                    cur_ref[fl_slot] = fl + 1

        flush_side(0, 2, bl_i, new_l)
        flush_side(1, 3, br_i, new_r)

        # the smaller child's histogram, from the chunks its side flushed
        # in this step (at most two, each still whole in its flush
        # buffer). ONE call site for the four (side, buffer) cases: the
        # accumulation unrolls over the features, and eight inlined
        # copies of it made the kernel so large that EVERY split-path
        # grid step paid for it, histogram or not (45 us a chunk at 67
        # features against 6 at 8; PERF.md section 6, PR 27)
        def hist_flushed_chunks():
            cur_h = jnp.where(hside == 0, new_l, new_r)

            def hist_one(fl, carry):
                hist_flushed(fbuf[hside * 2 + fl % 2],
                             jnp.minimum(cur_h - fl * C, C))
                if fold_every:
                    cur_ref[45] = cur_ref[45] + 1

                    @pl.when(cur_ref[45] >= fold_every)
                    def _():
                        _fold_counts(hacc, _hist_mode(b_pad, subbin))
                        cur_ref[45] = 0
                return carry

            lax.fori_loop(fl_h0, cur_ref[2 + hside], hist_one, 0)

        def hist_to_store():
            if not spill:
                hist_ref[hslot] += hacc[...]
            else:
                # 2-deep spill ring: stage the finished block histogram
                # and DMA it to its HBM slot WITHOUT waiting — the next
                # block accumulates into hacc while this one drains.
                # The staging buffer/semaphore is reused only after its
                # previous DMA completed.
                for p in range(2):
                    @pl.when((cur_ref[40] & 1) == p)
                    def _(p=p):
                        @pl.when(cur_ref[41 + p] != 0)
                        def _():
                            wait_spill(p)
                        hstage[p] = hacc[...]
                        cur_ref[43 + p] = hslot
                        pltpu.make_async_copy(
                            hstage.at[p],
                            hist_ref.at[hslot],
                            sems.at[12 + p]).start()
                        cur_ref[41 + p] = 1
                cur_ref[40] = cur_ref[40] + 1

        if not route_bag:
            pl.when(hslot != dummy)(hist_flushed_chunks)
            pl.when((is_last != 0) & (hslot != dummy))(hist_to_store)

        @pl.when(is_last != 0)
        def _():
            cur_ref[2] = 0
            cur_ref[3] = 0

    @pl.when(i == pl.num_programs(0) - 1)   # drain outstanding DMAs
    def _():
        for slot in range(12):
            @pl.when(cur_ref[4 + slot] != 0)
            def _():
                wait_slot(slot)
        if spill:
            for p in range(2):
                @pl.when(cur_ref[41 + p] != 0)
                def _(p=p):
                    wait_spill(p)


@functools.partial(jax.jit, static_argnames=(
    "chunk", "w_pad", "wcnt", "num_slots", "num_features", "b_pad",
    "group", "bag_lane", "bits", "grad_fn", "num_class", "w_used",
    "gh_off", "bundled", "interpret", "subbin", "spill", "route_bag",
    "fold_every"))
def move_pass(records, other, src, r1, r2, basel, baser, meta, wsel,
              hslots, cbits, chunk, w_pad, wcnt, num_slots, num_features,
              b_pad, group,
              bag_lane=-1, bits=8, grad_fn=None, num_class=1,
              w_used=0, gh_off=2, bundled=False,
              interpret=False, subbin=False, spill=False,
              route_bag=False, fold_every=None):
    """Stable two-way partition of every block in one streaming pass,
    with the smaller-child histograms FUSED into the same pass.

    SMEM packing (the prefetch budget is 1 MB): wsel rides in r1 bits
    R_WSEL..R_WSEL+7 (so features <= 1020) and basel/baser pack into one
    16+16-bit word (so <= 65535 chunks) — callers must respect both
    bounds (aligned_mode_ok does).

    records, other: the two [NC, W, C] i32 record buffers, A and B;
    src: i32 scalar, 0 = the rows are read from A and land in B, 1 = the
    other way. Both buffers are operands ALIASED to outputs
    (`input_output_aliases`), so a `lax.while_loop` that carries both
    and flips `src` each round updates every carried buffer in its own
    place: with one buffer in and a fresh one out, XLA copied the whole
    matrix back into the loop's carry after every round (PERF.md section
    6, PR 30). r1/r2/basel/baser/meta/wsel: [NC] i32
    per-chunk routing (see module docstring bit layouts; wsel = split
    word lane index of the chunk's block). hslots[i] packs the smaller
    child's accumulation slot | side << 24 (side 0 = left rows of the
    chunk are the smaller child); slot == num_slots skips. Slots are
    COMPACT per-round ids (0..k-1): the whole [num_slots+1, ...] store
    stays VMEM-resident across the grid (num_slots <= ~256 so it fits
    at B=256), so callers must remap tree slots to the round's selected
    split ranks.

    Returns (A, B, hist[num_slots, F, b_pad, 3]): the source buffer as
    it was, the destination with the new layout. Destination chunks not
    covered by the new layout keep what they held (rows of an earlier
    round); hist slots never present in hslots are zero. The count
    channel holds exact i32 counts (`hist_counts`); `fold_every` (None:
    `count_fold` of the pass) is how many chunks a block's counts take
    between two folds, 0 none.

    `spill` keeps the [num_slots+1, ...] store in HBM (streamed through
    the kernel's 2-deep VMEM staging ring) instead of VMEM-resident —
    the shape that lets wide-F x 255-bin rounds run with K well past
    the VMEM budget. `subbin` selects the sub-binned accumulation at
    b_pad > 128 (see _hist_mode).

    `route_bag` (single-class records with a bag: `bag_lane` >= 0 or -2)
    is the route mode of `park_pass`: every chunk whose copy bit is clear
    sends its in-bag rows left and the others right, reads nothing of
    r2 / wsel / hslots / cbits, and the returned histogram is zeros.
    """
    compile_cache.note_trace()
    assert not route_bag or (bag_lane != -1 and num_class == 1)
    nc = records.shape[0]
    dummy = num_slots
    if fold_every is None:
        fold_every = count_fold(chunk, nc)
    store_shape = _hist_store_shape(num_slots, num_features, b_pad,
                                    group, subbin)
    hacc_shape = store_shape[1:]
    # spill stages through a 2-deep ring; non-spill keeps a tiny dummy
    # so the kernel signature is mode-independent
    hstage_shape = (2,) + hacc_shape if spill else (2, 8, 128)
    kernel = functools.partial(_move_kernel, chunk=chunk, w_pad=w_pad,
                               w_used=w_used or w_pad,
                               wcnt=wcnt, num_features=num_features,
                               b_pad=b_pad, group=group, dummy=dummy,
                               bag_lane=bag_lane, bits=bits,
                               grad_fn=grad_fn, num_class=num_class,
                               gh_off=gh_off, bundled=bundled,
                               subbin=subbin, spill=spill,
                               route_bag=route_bag,
                               fold_every=0 if route_bag else fold_every)
    r1p = r1 | (wsel << R_WSEL)
    blbr = basel | (baser << 16)
    # copy chunks SKIP the blocked fetch: the block index carries the
    # last split chunk's index forward, so the pipeline only fetches
    # when the index changes (i.e. at split chunks)
    iota_nc = jnp.arange(nc, dtype=jnp.int32)
    is_split = ((r1 >> R_COPY) & 1) == 0
    fetch_idx = lax.cummax(jnp.where(is_split, iota_nc, 0))
    # only the source buffer's blocked fetch moves; the other's index
    # stays at chunk 0, fetched once
    src1 = jnp.reshape(src, (1,)).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(nc,),
        in_specs=[
            pl.BlockSpec((1, w_pad, chunk),
                         lambda i, a, b, c, d, e, f, g, s:
                         (jnp.where(s[0] == 0, g[i], 0), 0, 0)),
            pl.BlockSpec((1, w_pad, chunk),
                         lambda i, a, b, c, d, e, f, g, s:
                         (jnp.where(s[0] == 0, 0, g[i]), 0, 0)),
        ],
        out_specs=[
            # the two buffers in HBM, each aliased to its operand: the
            # kernel's DMAs read the source and write the destination
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
            # spill: the store stays in HBM, written slot-by-slot by the
            # kernel's DMA ring. Otherwise a constant index map keeps
            # the compact store resident in VMEM for the whole pass,
            # written back once at the end.
            pl.BlockSpec(memory_space=pltpu.HBM) if spill else
            pl.BlockSpec(store_shape,
                         lambda i, a, b, c, d, e, f, g, s:
                         tuple(0 for _ in store_shape)),
        ],
        scratch_shapes=[
            pltpu.VMEM((4, w_pad, chunk), jnp.int32),
            pltpu.VMEM((4, w_pad, chunk), jnp.int32),   # flush bufs
            pltpu.VMEM(hacc_shape, jnp.float32),
            pltpu.VMEM(hstage_shape, jnp.float32),      # spill ring
            pltpu.VMEM((route_tile(chunk),) * 2, jnp.bfloat16),   # tri
            pltpu.SMEM((48,), jnp.int32),
            pltpu.SemaphoreType.DMA((14,)),
        ],
    )
    buf_a, buf_b, hist = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(records.shape, jnp.int32),
            jax.ShapeDtypeStruct(records.shape, jnp.int32),
            jax.ShapeDtypeStruct(store_shape, jnp.float32),
        ],
        input_output_aliases={8: 0, 9: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 << 20, has_side_effects=True),
        interpret=interpret,
        name="move_pass",
    )(r1p, r2, blbr, meta, hslots, cbits, fetch_idx, src1, records, other)
    hist = _hist_store_finalize(hist, num_slots, num_features,
                                b_pad, group, subbin)
    if spill:
        # HBM store slots are only written by visited blocks; mask the
        # rest to zero (non-spill zeroes the whole store in-kernel)
        visited = jnp.zeros((num_slots + 1,), jnp.int32) \
            .at[hslots & 0xFFFFFF].max(1)
        hist = jnp.where((visited[:num_slots] > 0)[:, None, None, None],
                         hist, 0.0)
    return buf_a, buf_b, hist


def park_pass(records, other, src, cnts, kept, chunk, w_pad, wcnt,
              bag_lane, bits=8, w_used=0, interpret=False):
    """One stable partition of EVERY row by the bag, through `move_pass`
    (its `route_bag` mode; a `move_pass` event in a trace): the `kept`
    rows in the bag (`_in_bag`), in the order they lie, from chunk 0 on,
    the others as ONE block that ends at the buffer's last chunk, the
    PARKED block. cnts: i32 [NC] rows of every chunk of the source
    buffer, all of them live; kept: i32 scalar, how many of them are in
    the bag, exactly (the caller's: the selection counted it).

    Returns (records, other, cnts', park_begin): the two buffers as
    `move_pass` leaves them (the rows in the one `src` does not name),
    the new layout's per-chunk counts and the chunk the parked block
    begins at, NC where no row is out of the bag. The layout is exact by
    the table: full chunks and a last partial one on either side."""
    nc = records.shape[0]
    iota = jnp.arange(nc, dtype=jnp.int32)
    n_out = jnp.sum(cnts).astype(jnp.int32) - kept
    park_begin = nc - (n_out + chunk - 1) // chunk
    # a chunk of no row is skipped (copy bit, count 0); the buffer's last
    # chunk closes the block, rows or none
    split = (cnts > 0) | (iota == nc - 1)
    r1 = jnp.where(split, 0, 1 << R_COPY)
    meta = (cnts | ((iota == 0).astype(jnp.int32) << 20)
            | ((iota == nc - 1).astype(jnp.int32) << 21))
    zeros = jnp.zeros(nc, jnp.int32)
    buf_a, buf_b, _ = move_pass(
        records, other, src, r1, zeros, zeros,
        jnp.full(nc, park_begin, jnp.int32), meta, zeros,
        jnp.ones(nc, jnp.int32), jnp.zeros(16, jnp.int32),
        chunk, w_pad, wcnt, 1, 1, 16, 8, bag_lane=bag_lane, bits=bits,
        w_used=w_used, interpret=interpret, route_bag=True)
    new = jnp.where(iota < park_begin,
                    jnp.clip(kept - iota * chunk, 0, chunk),
                    jnp.clip(n_out - (iota - park_begin) * chunk, 0, chunk))
    return buf_a, buf_b, new, park_begin


# ---------------------------------------------------------------------------
# physical left-count pass
# ---------------------------------------------------------------------------
def _count_kernel(r1_ref, r2_ref, meta_ref, wsel_ref, ks_ref,
                  cbits_ref, src_ref, reca_ref, recb_ref, out_ref, win,
                  cacc, sems, *, chunk, dummy, bits, bundled):
    """Exact i32 count of PHYSICAL rows routed left per selected split,
    over the record buffer `src_ref[0]` names (0 = A), as `move_pass`
    reads it.

    Streams only each block's split-word sublane (4 B/row). Needed only
    where the histogram's count, exact at any row count, is not the
    count of the rows the layout places (`AlignedEngine.count_why`): on
    a mesh, where the finder sees the all-reduced count, and under a bag
    that is not parked, whose out-of-bag rows ride the rounds uncounted
    (gbdt.cpp:209-275).

    The kernel fetches for itself, one chunk ahead into a 2-deep ring
    (`win`), the 8-sublane window that holds the split word (DMAs move
    whole (8, 128) tiles) of the chunks it counts, and of no other: a
    blocked operand for each of the two buffers cost every grid step the
    pipeline's bookkeeping twice (0.39 us a chunk against 0.32: PERF.md
    section 6, PR 30), and fetched the chunks of unsplit blocks too."""
    i = pl.program_id(0)
    last = pl.num_programs(0) - 1
    meta = meta_ref[i]

    def window(j, buf_ref):
        """The DMA of chunk j's window from `buf_ref` into its slot."""
        lanes = pl.ds(pl.multiple_of((wsel_ref[j] >> 3) * 8, 8), 8)
        return pltpu.make_async_copy(buf_ref.at[j, lanes], win.at[j % 2],
                                     sems.at[j % 2])

    def fetch(j):
        @pl.when(src_ref[0] == 0)
        def _():
            window(j, reca_ref).start()

        @pl.when(src_ref[0] != 0)
        def _():
            window(j, recb_ref).start()

    @pl.when(i == 0)
    def _():
        for k in range(out_ref.shape[0]):     # SMEM table: scalar clears
            out_ref[k] = 0

        @pl.when(ks_ref[0] != dummy)
        def _():
            fetch(0)

    nxt = jnp.minimum(i + 1, last)

    @pl.when((i < last) & (ks_ref[nxt] != dummy))
    def _():
        fetch(nxt)

    @pl.when(((meta >> 20) & 1) != 0)
    def _():
        cacc[0] = 0

    @pl.when(ks_ref[i] != dummy)
    def _():
        # a wait reads its semaphore and its destination's size: it names
        # buffer A whatever the source was
        window(i, reca_ref).wait()
        # pick the split word out of its window with a static select
        # chain on wsel & 7
        wsub = wsel_ref[i] & 7
        blk = win[i % 2]
        word = blk[0]
        for wj in range(1, 8):
            word = jnp.where(wsub == wj, blk[wj], word)
        r1 = r1_ref[i]
        binv = (word >> ((r1 >> R_SHIFT) & 31)) & ((1 << bits) - 1)
        if bundled:
            binv = _unpack_bundle(binv, r2_ref[i])
        pos = lax.broadcasted_iota(jnp.int32, (1, chunk), 1)[0]
        valid = pos < (meta & ((1 << 20) - 1))
        catw = _cat_word(cbits_ref, ks_ref[i], binv)
        left = _goes_left(binv, r1, r2_ref[i], valid, catw)
        cacc[0] = cacc[0] + jnp.sum(left.astype(jnp.int32))

        @pl.when(((meta >> 21) & 1) != 0)          # block's last chunk
        def _():
            out_ref[ks_ref[i]] += cacc[0]


@functools.partial(jax.jit, static_argnames=("num_slots", "chunk",
                                             "bits", "bundled",
                                             "interpret"))
def count_pass(records, other, src, r1, r2, meta, wsel, kslots, cbits,
               num_slots, chunk, bits=8, bundled=False, interpret=False):
    """[num_slots] i32 physical left counts per compact slot id.

    records, other, src: the round loop's two record buffers and which of
    them holds the rows, as for move_pass; only that one is read.
    kslots[i] = compact id of chunk i's selected split (num_slots =
    skip); r1/r2/meta/wsel as for move_pass (copy bit must be CLEAR for
    counted chunks)."""
    compile_cache.note_trace()
    nc = records.shape[0]
    kernel = functools.partial(_count_kernel, chunk=chunk,
                               dummy=num_slots, bits=bits,
                               bundled=bundled)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(nc,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.HBM),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        scratch_shapes=[pltpu.VMEM((2, 8, chunk), jnp.int32),
                        pltpu.SMEM((8,), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_slots + 1,), jnp.int32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=100 << 20),
        interpret=interpret,
        name="count_pass",
    )(r1, r2, meta, wsel, kslots, cbits,
      jnp.reshape(src, (1,)).astype(jnp.int32), records, other)
    return out[:num_slots]


# ---------------------------------------------------------------------------
# slot-mapped histogram pass
# ---------------------------------------------------------------------------
def _slot_hist_kernel(slots_ref, meta_ref, rec_ref, out_ref, *,
                      num_features, b_pad, group, chunk, wcnt, dummy,
                      bag_lane, bits, grad_fn, num_class, gh_off,
                      subbin, fold_every):
    i = pl.program_id(0)
    bpw = _bpw_for_bits(bits)
    bmask = (1 << bits) - 1

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(slots_ref[i] != dummy)
    def _():
        rec = rec_ref[0]                              # [W, C]
        ks = slots_ref[i]
        g, h, valid = _payload_gh(rec, meta_ref[i] & ((1 << 20) - 1),
                                  chunk, wcnt, grad_fn, bag_lane,
                                  num_class, gh_off)
        gm = jnp.where(valid, g, 0.0)
        hm = jnp.where(valid, h, 0.0)
        cnt = valid.astype(jnp.float32)
        pay = jnp.stack([gm, hm, cnt], axis=0)
        pay6 = _hi_lo6(pay)                           # [6, C]

        def bin_of(f):
            return (rec[f // bpw, :] >> ((f % bpw) * bits)) & bmask

        def accum(idx, contrib):
            out_ref[ks, idx] += contrib

        _hist_accum(pay6, bin_of, accum, num_features, b_pad, group,
                    chunk, subbin)

    if fold_every:
        # a grid step adds one chunk at most: every slot's count halves
        # stay exact between folds (`count_fold`)
        @pl.when(i % fold_every == fold_every - 1)
        def _():
            _fold_counts(out_ref, _hist_mode(b_pad, subbin))


@functools.partial(jax.jit, static_argnames=(
    "num_slots", "num_features", "b_pad", "chunk", "group", "wcnt",
    "bag_lane", "bits", "grad_fn", "num_class", "gh_off", "interpret",
    "subbin", "fold_every"))
def slot_hist_pass(records, slots, meta, num_slots, num_features, b_pad,
                   chunk, group, wcnt, bag_lane=-1, bits=8, grad_fn=None,
                   num_class=1, gh_off=2, interpret=False, subbin=False,
                   fold_every=None):
    """hist[num_slots, F, b_pad, 3] over the record matrix.

    slots[i] maps chunk i to its accumulation slot (a COMPACT id —
    num_slots must be small enough that the whole store fits VMEM, which
    holds for the root pass and per-round selections); chunks mapped to
    the DUMMY slot (== num_slots) are skipped. The store is VMEM-resident
    across the grid (constant out-spec) and zeroed once, so unvisited
    slots read as zero and chunk order is unconstrained. The count
    channel holds exact i32 counts (`hist_counts`): the store folds its
    counts every `fold_every` grid steps (None: `count_fold`, 0 never).
    """
    compile_cache.note_trace()
    nc = records.shape[0]
    dummy = num_slots
    if fold_every is None:
        fold_every = count_fold(chunk, nc)
    store_shape = _hist_store_shape(num_slots, num_features, b_pad,
                                    group, subbin)
    kernel = functools.partial(_slot_hist_kernel, num_features=num_features,
                               b_pad=b_pad, group=group, chunk=chunk,
                               wcnt=wcnt, dummy=dummy, bag_lane=bag_lane,
                               bits=bits, grad_fn=grad_fn,
                               num_class=num_class, gh_off=gh_off,
                               subbin=subbin, fold_every=fold_every)
    w_pad = records.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nc,),
        in_specs=[pl.BlockSpec((1, w_pad, chunk),
                               lambda i, s, m: (i, 0, 0))],
        out_specs=pl.BlockSpec(store_shape,
                               lambda i, s, m:
                               tuple(0 for _ in store_shape)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(store_shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=100 << 20),
        interpret=interpret,
        name="slot_hist_pass",
    )(slots, meta, records)
    return _hist_store_finalize(out, num_slots, num_features, b_pad,
                                group, subbin)


# ---------------------------------------------------------------------------
# tree walk over the records as they lie
# ---------------------------------------------------------------------------
# Trees one `walk_pass` call holds tables for. The call walks the first
# `ntrees` of them, a run-time number, so one compiled program serves
# every drop count and a caller with more trees calls again.
WALK_TREES = 8
WALK_NEVER = 1.0e6      # the depth of a leaf no row reaches (padding)


def walk_dims(num_leaves: int, wcnt: int, bits: int):
    """(Np, Lp, W8, Fp) of the walk tables: nodes and leaves padded to
    whole MXU tiles, the bin words to whole sublane tiles, and the decoded
    bins (one plane a position in the word, each `W8` rows) to a whole
    bf16 tile."""
    lp = max(128, -(-int(num_leaves) // 128) * 128)
    w8 = -(-wcnt // 8) * 8
    fp = -(-(_bpw_for_bits(bits) * w8) // 16) * 16
    return lp, lp, w8, fp


def walk_expand(nodes, leaves, nn, nb, db, mt, *, w8, bits, fp):
    """The kernel's tables of ONE tree from its compact form (vmap it over
    trees). `nodes` i32 [5, Np]: inner feature, threshold bin, default
    left, parent node (-1 at the root) and side under it (+1 left, -1
    right); `leaves` i32 [2, Lp]: parent node and side; `nn` the number
    of nodes (leaves are nn + 1, both numbered densely from 0). nb / db /
    mt: the features' num_bin, default_bin and missing type.

    Returns (sel bf16 [Np, Fp]: a node's feature as a one-hot over the
    decoded bin planes; thr, dbin, dlv f32 [Np, 1]: the threshold, the
    bin that takes the default side (-1: none) and that side as +-1;
    path bf16 [Lp, Np]: +1 / -1 where the leaf lies left / right under
    the node, 0 elsewhere; depth f32 [Lp, 1]: the leaf's ancestors, or
    WALK_NEVER for a leaf the tree does not have). A row reaches a leaf
    exactly when its +-1 decisions dotted with the leaf's path row give
    the leaf's depth."""
    feat, thr, dl, parent, side = nodes
    np_, lp = feat.shape[0], leaves.shape[1]
    bpw = _bpw_for_bits(bits)
    used = jnp.arange(np_) < nn
    col = (feat % bpw) * w8 + feat // bpw
    sel = ((col[:, None] == jnp.arange(fp)[None, :])
           & used[:, None]).astype(jnp.bfloat16)
    mtf = mt[feat]
    dbin = jnp.where(mtf == MISSING_ZERO_C, db[feat],
                     jnp.where(mtf == MISSING_NAN_C, nb[feat] - 1, -1))
    dbin = jnp.where(used, dbin, -1)
    dlv = jnp.where(dl != 0, 1.0, -1.0)
    lrow = jnp.arange(lp)

    def up(st):
        path, at, s, steps = st
        live = at >= 0
        a = jnp.clip(at, 0, np_ - 1)
        path = path.at[lrow, a].add(jnp.where(live, s, 0))
        return (path, jnp.where(live, parent[a], -1),
                jnp.where(live, side[a], 0), steps + 1)

    has = lrow <= nn
    # no leaf lies deeper than there are nodes: tables that are no tree
    # cannot hold the loop
    path, _, _, _ = lax.while_loop(
        lambda st: jnp.any(st[1] >= 0) & (st[3] < np_), up,
        (jnp.zeros((lp, np_), jnp.int32),
         jnp.where(has, leaves[0], -1), jnp.where(has, leaves[1], 0),
         jnp.int32(0)))
    depth = jnp.where(has, jnp.sum(jnp.abs(path), axis=1), WALK_NEVER)

    def colf(x):
        return x.astype(jnp.float32)[:, None]
    return (sel, colf(thr), colf(dbin), colf(dlv),
            path.astype(jnp.bfloat16), colf(depth))


def _walk_kernel(cnt_ref, nt_ref, rec_ref, sel_ref, thr_ref, dbin_ref,
                 dlv_ref, path_ref, depth_ref, val_ref, out_ref, *, chunk,
                 w8, bits, lane, fp):
    """One chunk: score lane += the sum over the call's trees of the value
    of the leaf each live row reaches. No gather: a node's bin is a
    one-hot product over the chunk's decoded bins, the leaf a product of
    the +-1 decisions with the tree's path matrix, both exact in bf16 with
    f32 sums (one term, and whole numbers under 256); the leaf's f32
    value is picked by a select and a sum over leaves of which one is not
    0. Only the 8-lane window of the score lane is written back."""
    i = pl.program_id(0)
    lo = lane - lane % 8
    out_ref[0] = rec_ref[0, lo:lo + 8, :]
    cnt = cnt_ref[i]

    @pl.when((cnt > 0) & (nt_ref[0] > 0))
    def _():
        bpw = _bpw_for_bits(bits)
        words = rec_ref[0, 0:w8, :]
        planes = [((words >> (bits * k)) & ((1 << bits) - 1))
                  .astype(jnp.float32) for k in range(bpw)]
        if fp > bpw * w8:
            planes.append(jnp.zeros((fp - bpw * w8, chunk), jnp.float32))
        binsb = jnp.concatenate(planes, axis=0).astype(jnp.bfloat16)
        live = lax.broadcasted_iota(jnp.int32, (1, chunk), 1) < cnt
        score = lax.bitcast_convert_type(rec_ref[0, lane:lane + 1, :],
                                         jnp.float32)

        def tree(t, score):
            nbin = lax.dot_general(sel_ref[t], binsb,
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
            dec = jnp.where(nbin == dbin_ref[t], dlv_ref[t],
                            jnp.where(nbin <= thr_ref[t], 1.0, -1.0))
            reach = lax.dot_general(path_ref[t], dec.astype(jnp.bfloat16),
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            val = jnp.sum(jnp.where(reach == depth_ref[t], val_ref[t], 0.0),
                          axis=0, keepdims=True)
            return score + jnp.where(live, val, 0.0)

        score = lax.fori_loop(0, nt_ref[0], tree, score)
        out_ref[0, lane - lo:lane - lo + 1, :] = \
            lax.bitcast_convert_type(score, jnp.int32)


@functools.partial(jax.jit, static_argnames=("chunk", "wcnt", "bits",
                                             "lane", "interpret"))
def walk_pass(records, cnts, ntrees, sel, thr, dbin, dlv, path, depth,
              vals, chunk, wcnt, bits, lane, interpret=False):
    """records with f32 lane `lane` of every live row (position under its
    chunk's `cnts`) increased, tree after tree in f32, by `vals[t,
    leaf]` of the leaf the row reaches in tree t < `ntrees`; the tables
    are `walk_expand`'s, stacked over WALK_TREES trees, `vals` f32 [T, Lp,
    1]. In place (the records are aliased to the result: donate them), one
    read of every chunk and one write of the score lane's window."""
    compile_cache.note_trace()
    nc, w_pad, _ = records.shape
    t, np_, fp = sel.shape
    lp = path.shape[1]
    w8 = -(-wcnt // 8) * 8
    kernel = functools.partial(_walk_kernel, chunk=chunk, w8=w8, bits=bits,
                               lane=lane, fp=fp)

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i, c, n: (0,) * len(shape))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nc,),
        in_specs=[pl.BlockSpec((1, w_pad, chunk), lambda i, c, n: (i, 0, 0)),
                  whole(t, np_, fp), whole(t, np_, 1), whole(t, np_, 1),
                  whole(t, np_, 1), whole(t, lp, np_), whole(t, lp, 1),
                  whole(t, lp, 1)],
        out_specs=pl.BlockSpec((1, 8, chunk),
                               lambda i, c, n: (i, lane // 8, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(records.shape, records.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=100 << 20),
        interpret=interpret,
        name="walk_pass",
    )(cnts, jnp.reshape(ntrees, (1,)).astype(jnp.int32), records, sel, thr,
      dbin, dlv, path, depth, vals)


def aligned_available() -> bool:
    """True when the aligned pipeline's kernels compile natively."""
    return jax.default_backend() == "tpu"
