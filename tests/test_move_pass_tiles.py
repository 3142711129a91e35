"""`move_pass` against an independent partition (CPU: Pallas interpret mode).

The split path routes a chunk in sub-tiles of `route_tile(C)` rows, so a
chunk larger than `ROUTE_TILE` runs the tile loop more than once. Every
training-level test pins `tpu_chunk = 256`, where the loop has one trip;
here hand-built records and route words go through the kernel at
C = 1, 2 and 4 tiles and are compared, bit for bit, with a numpy stable
partition, and the fused histograms with a numpy histogram.

The route matmul selects ONE block a tile, which holds the rows of both
sides and of both ring windows a side's rows can reach, the right rows
rotated to their offsets afterwards; `_edge_scenario` puts the cursors
where the window masks meet and where the rotation wraps, and the `ext`
layout routes a record whose byte planes (4 x 59 lanes) are no multiple
of 8 sublanes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops import aligned
from lightgbm_tpu.ops.aligned import (META_LABEL, META_LABEL_MASK, R_COPY,
                                      R_SHIFT, ROUTE_TILE, _bpw_for_bits,
                                      lane_layout, move_pass, pack_records,
                                      pack_route2, park_pass, route_tile)

F = 6            # features the fused histogram covers
NC = 20          # one grid for every case of a shape: one compile
K = 4            # histogram slots of the round (the dummy is K)
LAYOUTS = {      # name -> (max_bin, compact, ext, packed features)
    "std8": (255, False, False, F),
    "std6": (63, False, False, F),
    "compact": (63, True, False, F),
    # the ranking record: 55 bin words + score, gradient, hessian, row
    # id = 59 used lanes of W = 64. A split may test any of the 220
    # packed features; the histogram is asked for the first F only
    "ext": (255, False, True, 220),
}


def _point_grad(score, label, weight):
    p = 1.0 / (1.0 + jnp.exp(-score))
    return p - label, p * (1.0 - p)


def _scenario(C, heavy):
    """Blocks of the old layout, in chunk order. A split block is
    (rows per chunk, share of rows going to the `heavy` side, which side
    is histogrammed or None); "copy" blocks shift whole; "dead" chunks
    take the split path with no row."""
    def share(x):
        return x if heavy == "left" else 1.0 - x
    return [
        # >= 3 chunks, 90% to one side: its ring wraps and flushes twice
        ("split", [C, C, C, C // 2 + 7], share(0.9), 0),
        ("copy", [C, C // 3]),
        # sparse chunks inside one block, as the root round inherits
        # them: tiles past a chunk's last row are skipped, and the
        # cursors stop off every tile boundary, so the next tile's rows
        # straddle two windows of both rings
        ("split", [C - 5, 1, C // 4 + 3, C], share(0.35), 1),
        ("dead", 1),
        ("copy", [77]),
        # a block smaller than one tile, nearly all to one side
        ("split", [ROUTE_TILE // 2 + 1], share(0.01), None),
        ("split", [C, 2 * C // 3], 0.5, 0),
        ("dead", 2),
    ]


def _exact(n, k):
    """[n] bools, exactly k of them set, the same on every call."""
    return np.random.default_rng(1000 * n + k).permutation(np.arange(n) < k)


def _edge_scenario(C, heavy):
    """One block whose tiles, given as (rows, rows to the `heavy` side),
    stop the cursors where a side's two window masks meet. With a = the
    side's cursor modulo the tile S and k = its rows of the tile:
    tile 1 sends all S rows to one side from a = 5 (k == S: both windows
    filled from one selected block); tile 2 ends both sides on a + k == S
    (window 1 empty); tile 5 wraps BOTH sides (a = S - 10 and S - 20,
    k = S / 2 each), which takes the short tile 4 before it, since whole
    tiles keep the two cursors' sum at a multiple of S; tile 6 sends
    every row to the other side, whose cursor lies behind the heavy
    side's (under `heavy` = left: right rows only, a_r < a_l + k_l, so
    the rotation that brings the right rows home turns backwards)."""
    S = route_tile(C)
    return _block_of_tiles(C, heavy, [
        (S, 5, False), (S, S, False), (S, S - 5, False), (S, S - 10, False),
        (S - 30, 0, False), (S, S // 2, False), (S, 0, False)])


def _carry_scenario(C, heavy):
    """One block whose CHUNK ends fall where a side's open window, which
    the tile loop carries in registers and stores once a tile, has to
    cross a grid step. With h and l the heavy and the light side's
    cursors modulo S: chunk 1 ends mid-window on both sides (h = 37,
    l = S - 87) and chunk 2 continues those windows; chunk 2's last tile
    fills the heavy window exactly (a + k == S); the last tile of chunk
    3 sends S rows to the heavy side from a = 20 (k == S, a > 0), so 20
    rows of the next window cross the step in the carry alone; and the
    block's last chunk ends with window-1 rows of the heavy side held
    only in the carry (a = S - 8, k = S - 60). At four tiles a chunk
    one chunk holds four tiles, which take the unrolled loop's body."""
    S = route_tile(C)
    return _block_of_tiles(C, heavy, [
        (S - 50, 37, True), (S, S - 37, True), (S, 20, False),
        (S, S, True), (S, S - 10, False), (S, S // 2 + 3, False),
        (S, 7, False), (S - 30, 100, True), (S, S - 60, True)])


def _block_of_tiles(C, heavy, tiles):
    """A split block of `tiles`, each (rows, rows to the `heavy` side,
    whether its chunk ends behind it), and a dead chunk after it. A
    chunk also ends where it is full or its last tile is short."""
    S = route_tile(C)
    cnts, masks, closed = [], [], True
    for n, k, end in tiles:
        if closed:
            cnts.append(0)
            masks.append([])
        cnts[-1] += n
        masks[-1].append(_exact(n, k) == (heavy == "left"))
        closed = end or cnts[-1] == C or cnts[-1] % S
    return [("split", cnts, [np.concatenate(m) for m in masks], 0),
            ("dead", 1)]


def _build(C, layout, heavy, seed, scenario=_scenario):
    max_bin, compact, ext, nfeat = LAYOUTS[layout]
    rng = np.random.default_rng(seed)
    bits = 8 if max_bin > 64 else 6
    bpw = _bpw_for_bits(bits)
    chunks, cnts = [], []          # old layout
    blocks = []                    # (kind, chunk ids, route)
    rid = 0
    for bi, blk in enumerate(scenario(C, heavy)):
        kind = blk[0]
        if kind == "dead":
            ids = list(range(len(cnts), len(cnts) + blk[1]))
            # rows of an earlier tree: nothing of them may leak
            garbage = rng.integers(-2**31, 2**31, (blk[1], 1, C))
            chunks.append(np.broadcast_to(garbage, (blk[1], W, C))
                          .astype(np.int32))
            cnts += [0] * blk[1]
            blocks.append((kind, ids, None))
            continue
        feat = int(rng.integers(nfeat))
        thr = int(rng.integers(4, max_bin - 4))
        ids = []
        for j, n in enumerate(blk[1]):
            bins = rng.integers(0, max_bin, (n, nfeat)).astype(np.uint8)
            if kind == "split":
                # a share draws the rows that go left; a list names them
                goes = blk[2][j] if isinstance(blk[2], list) \
                    else rng.random(n) < blk[2]
                bins[:, feat] = np.where(
                    goes, rng.integers(0, thr + 1, n),
                    rng.integers(thr + 1, max_bin, n))
            label = rng.integers(0, 2, n).astype(np.float32)
            rec, wcnt, W, c, pbits = pack_records(
                bins, label, None, C, compact=compact, max_bin=max_bin,
                ext=ext, rid_base=rid)
            assert (rec.shape[0], pbits) == (1, bits)
            rid += n
            ids.append(len(cnts))
            chunks.append(rec)
            cnts.append(n)
        route = (feat, thr, blk[3]) if kind == "split" else None
        blocks.append((kind, ids, route))
    rec = np.concatenate(chunks)
    lanes, _ = lane_layout(wcnt, compact=compact, ext=ext)
    w_used = max(lanes.values()) + 1
    # every byte plane of every value lane carries random bits
    live = np.arange(C)[None, :] < np.asarray(cnts)[:, None]
    f32 = rng.standard_normal((len(cnts), C)).astype(np.float32)
    rec[:, lanes["score"], :] = np.where(live, f32.view(np.int32), 0)
    if not compact:
        for name in ("grad", "hess"):
            v = rng.standard_normal((len(cnts), C)).astype(np.float32)
            rec[:, lanes[name], :] = np.where(live, v.view(np.int32), 0)
    return dict(rec=rec, cnts=np.asarray(cnts), blocks=blocks, bits=bits,
                bpw=bpw, wcnt=wcnt, W=W, w_used=w_used, lanes=lanes,
                compact=compact, max_bin=max_bin, gh_off=1 if ext else 2)


def _bin_of(rows, f, bits, bpw):
    """[n] bins of feature f from [n, W] record rows."""
    return (rows[:, f // bpw] >> ((f % bpw) * bits)) & ((1 << bits) - 1)


def _reference(sc, C):
    """The new layout, the route arrays that ask for it, and what the
    kernel must give: {dest chunk: [cnt, w_used] rows} and hist."""
    rec, cnts = sc["rec"], sc["cnts"]
    nc_old = len(cnts)
    bits, bpw, lanes = sc["bits"], sc["bpw"], sc["lanes"]
    b_pad = 256 if sc["max_bin"] > 64 else 64
    r1 = np.full(nc_old, 1 << R_COPY, np.int32)
    wsel = np.zeros(nc_old, np.int32)
    meta = cnts.astype(np.int32).copy()
    basel = np.zeros(nc_old, np.int32)
    baser = np.zeros(nc_old, np.int32)
    hslots = np.full(nc_old, K, np.int32)
    hist = np.zeros((K, F, b_pad, 3), np.float64)
    expect = {}
    sides = []                     # (rows, is_right, block index)
    for bi, (kind, ids, route) in enumerate(sc["blocks"]):
        if kind == "dead":
            r1[ids] = 7            # copy bit clear: the split path
            continue
        meta[ids[0]] |= 1 << 20
        meta[ids[-1]] |= 1 << 21
        rows = np.concatenate(
            [rec[c, :, :cnts[c]].T for c in ids])       # [n, W], in order
        if kind == "copy":
            sides.append((rows, False, bi))
            continue
        feat, thr, hside = route
        r1[ids] = thr | ((feat % bpw) * bits) << R_SHIFT
        wsel[ids] = feat // bpw
        left = _bin_of(rows, feat, bits, bpw) <= thr
        sides.append((rows[left], False, bi))           # stable
        sides.append((rows[~left], True, bi))
        if hside is not None:
            slot = len(np.unique(hslots)) - 1    # one block a slot
            hslots[ids] = slot | (hside << 24)
            side = rows[~left] if hside else rows[left]
            score = side[:, lanes["score"]].view(np.float32)
            if sc["compact"]:
                label = ((side[:, lanes["meta"]] >> META_LABEL)
                         & META_LABEL_MASK).astype(np.float32)
                p = 1.0 / (1.0 + np.exp(-score.astype(np.float64)))
                g, h = p - label, p * (1.0 - p)
            else:
                g = side[:, lanes["grad"]].view(np.float32)
                h = side[:, lanes["hess"]].view(np.float32)
            for f in range(F):
                b = _bin_of(side, f, bits, bpw)
                np.add.at(hist[slot, f, :, 0], b, g)
                np.add.at(hist[slot, f, :, 1], b, h)
                np.add.at(hist[slot, f, :, 2], b, 1.0)
    # left children and copied blocks keep the blocks' order, the right
    # children follow them all (fresh slots), as the builder lays out
    at = 0
    for rows, is_right, bi in sorted(sides, key=lambda s: s[1]):
        kind, ids, _ = sc["blocks"][bi]
        if kind == "copy":
            basel[ids] = at + np.arange(len(ids))
        elif is_right:
            baser[ids] = at
        else:
            basel[ids] = at
        for j in range(0, len(rows), C):
            expect[at] = rows[j:j + C, :sc["w_used"]]
            at += 1
    assert max(at, nc_old) <= NC
    pad = NC - nc_old              # the grid's free tail: dead chunks
    route = dict(r1=np.pad(r1, (0, pad), constant_values=7),
                 r2=np.full(NC, pack_route2(0, sc["max_bin"]), np.int32),
                 basel=np.pad(basel, (0, pad)),
                 baser=np.pad(baser, (0, pad)),
                 meta=np.pad(meta, (0, pad)), wsel=np.pad(wsel, (0, pad)),
                 hslots=np.pad(hslots, (0, pad), constant_values=K))
    rec_in = np.pad(rec, ((0, pad), (0, 0), (0, 0)), constant_values=-1)
    return rec_in, route, expect, hist, b_pad


# spill changes the histogram's flush and nothing of the tiles, and four
# tiles under the interpreter compile longest: one layout each is enough.
# The wide ext record runs at the two tiles of its cell's chunk
CASES = [(C, layout, spill, heavy)
         for C in (ROUTE_TILE, 2 * ROUTE_TILE, 4 * ROUTE_TILE)
         for layout in LAYOUTS
         for spill in (False, True)
         for heavy in ("left", "right")
         if layout == "std8" and (not spill or C != 2 * ROUTE_TILE)
         or layout == "ext" and not spill and C == 2 * ROUTE_TILE
         or layout in ("std6", "compact") and not spill
         and C < 4 * ROUTE_TILE]
# 384: a chunk that ROUTE_TILE does not divide is one tile of its own size
EDGE_CASES = [(C, "std8") for C in (ROUTE_TILE, 2 * ROUTE_TILE,
                                    4 * ROUTE_TILE, 384)] \
    + [(2 * ROUTE_TILE, "ext")]


def _call(sc, bufs, src, rt, b_pad, spill=False, subbin=True,
          fold_every=None, interpret=True):
    """One pass from buffer `src` of `bufs` into the other."""
    C = sc["rec"].shape[2]
    a, b, hist = move_pass(
        jnp.asarray(bufs[0]), jnp.asarray(bufs[1]), jnp.int32(src),
        *(jnp.asarray(rt[k]) for k in (
            "r1", "r2", "basel", "baser", "meta", "wsel", "hslots")),
        jnp.zeros((K + 1) * 8, jnp.int32), C, sc["W"], sc["wcnt"], K, F,
        b_pad, 4 if b_pad > 64 else 8, bits=sc["bits"],
        grad_fn=_point_grad if sc["compact"] else None,
        w_used=sc["w_used"], gh_off=sc["gh_off"], interpret=interpret,
        subbin=subbin, spill=spill, fold_every=fold_every)
    # the count channel holds the bits of exact i32 counts: read them as
    # the numbers they are, for the comparison with numpy's
    counts = np.asarray(aligned.hist_counts(hist))
    hist = np.asarray(hist).copy()
    hist[..., 2] = counts
    return [np.asarray(a), np.asarray(b)], hist


def _check_rows(sc, got, expect):
    for chunk, rows in expect.items():
        np.testing.assert_array_equal(
            got[chunk, :sc["w_used"], :len(rows)].T, rows,
            err_msg=f"chunk {chunk}")


@pytest.mark.parametrize("src", (0, 1))
@pytest.mark.parametrize("C,layout,spill,heavy", CASES)
def test_move_pass_matches_numpy_partition(C, layout, spill, heavy, src):
    """The rows are read from buffer `src` and land in the other one,
    whichever of the two aliased operands that is."""
    assert C // route_tile(C) == C // ROUTE_TILE
    sc = _build(C, layout, heavy, seed=C + len(layout) + len(heavy))
    rec_in, rt, expect, hist_ref, b_pad = _reference(sc, C)
    held = np.full_like(rec_in, 0x5A5A5A5A)    # what the destination held
    bufs = [held, held]
    bufs[src] = rec_in
    bufs, hist = _call(sc, bufs, src, rt, b_pad, spill)
    assert len(expect) >= 14       # every block of the scenario landed
    _check_rows(sc, bufs[1 - src], expect)
    # the source is read only, and a destination chunk that the new
    # layout does not cover keeps what it held
    np.testing.assert_array_equal(bufs[src], rec_in)
    free = sorted(set(range(NC)) - set(expect))
    np.testing.assert_array_equal(bufs[1 - src][free], held[free])
    np.testing.assert_allclose(hist, hist_ref, rtol=2e-5, atol=2e-4)
    np.testing.assert_array_equal(hist[..., 2], hist_ref[..., 2])
    assert hist_ref[..., 2].sum() > C       # histograms were asked for


# (layout, subbin): the three accumulation modes, `_hist_mode`
FOLD_MODES = {"subbin": ("std8", True), "nibble": ("std8", False),
              "group": ("std6", True)}


@pytest.fixture
def small_unit():
    """COUNT_UNIT = 8, traced afresh (it is read at trace time): a fold
    then moves counts at toy size, where COUNT_UNIT's 4,096 rows never
    gather in one bin between two folds."""
    keep = aligned.COUNT_UNIT
    aligned.COUNT_UNIT = 8
    jax.clear_caches()
    yield
    aligned.COUNT_UNIT = keep
    jax.clear_caches()


@pytest.mark.parametrize("fold_every", (1, 2))
@pytest.mark.parametrize("spill", (False, True))
@pytest.mark.parametrize("mode", sorted(FOLD_MODES))
def test_move_pass_counts_fold_inside_a_block(mode, spill, fold_every,
                                              small_unit):
    """A block's counts fold out of the f32 accumulator every
    `fold_every` chunks (`count_fold`: 8,190 at C = 2,048, so that no
    count half passes 2^24); folded after every chunk or every second
    one, in multiples of 8, the counts are numpy's exactly and g and h
    are the unfolded pass's bit for bit. The scenario's first block
    flushes four chunks of its heavy side."""
    layout, subbin = FOLD_MODES[mode]
    C = ROUTE_TILE
    sc = _build(C, layout, "left", seed=43)
    rec_in, rt, expect, hist_ref, b_pad = _reference(sc, C)
    held = np.full_like(rec_in, 0x5A5A5A5A)
    _, plain = _call(sc, [rec_in, held], 0, rt, b_pad, spill, subbin,
                     fold_every=0)
    bufs, hist = _call(sc, [rec_in, held], 0, rt, b_pad, spill, subbin,
                       fold_every=fold_every)
    _check_rows(sc, bufs[1], expect)
    np.testing.assert_array_equal(hist[..., 2], hist_ref[..., 2])
    np.testing.assert_array_equal(hist, plain)
    np.testing.assert_allclose(hist, hist_ref, rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("heavy", ("left", "right"))
@pytest.mark.parametrize("C,layout", EDGE_CASES)
def test_move_pass_where_the_window_masks_meet(C, layout, heavy):
    """Both sides' rows of a tile come out of ONE selected block, from
    which two masks a side take the ring's two windows: the cursors of
    `_edge_scenario` put every boundary of those masks on a row."""
    S = route_tile(C)
    sc = _build(C, layout, heavy, seed=32, scenario=_edge_scenario)
    rec_in, rt, expect, hist_ref, b_pad = _reference(sc, C)
    held = np.full_like(rec_in, 0x5A5A5A5A)
    bufs, hist = _call(sc, [rec_in, held], 0, rt, b_pad)
    # 7 S - 30 rows: 3.5 S - 10 to the heavy side, the others 10 fewer
    sides = (7 * S // 2 - 10, 7 * S // 2 - 20)
    assert sum(len(r) for r in expect.values()) == sum(sides)
    assert len(expect) == sum(-(-n // C) for n in sides)
    _check_rows(sc, bufs[1], expect)
    free = sorted(set(range(NC)) - set(expect))
    np.testing.assert_array_equal(bufs[1][free], held[free])
    np.testing.assert_allclose(hist, hist_ref, rtol=2e-5, atol=2e-4)


def _check_block(sc, C, rec_in, rt, expect, hist_ref, b_pad):
    """One pass out of buffer 0 against the numpy partition: every row
    where it belongs, bit for bit; what the layout does not cover as it
    was; the histogram."""
    held = np.full_like(rec_in, 0x5A5A5A5A)
    bufs, hist = _call(sc, [rec_in, held], 0, rt, b_pad)
    _check_rows(sc, bufs[1], expect)
    free = sorted(set(range(NC)) - set(expect))
    np.testing.assert_array_equal(bufs[1][free], held[free])
    np.testing.assert_allclose(hist, hist_ref, rtol=2e-5, atol=2e-4)


CARRY_CASES = [(C, layout) for C in (ROUTE_TILE, 2 * ROUTE_TILE,
                                     4 * ROUTE_TILE) for layout in LAYOUTS]


@pytest.mark.parametrize("heavy", ("left", "right"))
@pytest.mark.parametrize("C,layout", CARRY_CASES)
def test_the_open_window_crosses_grid_steps_in_the_carry(C, layout, heavy):
    """Each side's open staging window rides the tile loop's carry and
    is stored once a tile and once behind the loop (the kernel's
    `route_stage`, "carried"): `_carry_scenario` ends chunks mid-window,
    on a window filled exactly, with window-1 rows in the carry alone,
    and the block with them."""
    assert aligned.ROUTE_STAGE == "carried"
    sc = _build(C, layout, heavy, seed=38, scenario=_carry_scenario)
    rec_in, rt, expect, hist_ref, b_pad = _reference(sc, C)
    S = route_tile(C)
    # the block's 9 tiles: 4 S + 50 rows to the heavy side
    assert sum(len(r) for r in expect.values()) == 9 * S - 80
    _check_block(sc, C, rec_in, rt, expect, hist_ref, b_pad)


@pytest.fixture
def unrolled(request):
    """ROUTE_UNROLL set to the test's parameter, traced afresh (it is
    read at trace time, and a jit cache keeps what was traced)."""
    keep = aligned.ROUTE_UNROLL
    aligned.ROUTE_UNROLL = request.param
    jax.clear_caches()
    yield request.param
    aligned.ROUTE_UNROLL = keep
    jax.clear_caches()


@pytest.mark.parametrize("unrolled", (2, 3), indirect=True)
@pytest.mark.parametrize("scenario", (_scenario, _carry_scenario))
def test_tiles_left_over_by_the_unrolled_loop(unrolled, scenario):
    """A chunk whose tiles are no multiple of the unroll runs its last
    ones one a trip, behind the groups, with the windows carried across
    from one loop into the other (at four tiles a chunk: 4 = 2 + 2 or
    3 + 1, 3 = 2 + 1 or 3 + 0)."""
    C = 4 * ROUTE_TILE
    sc = _build(C, "std8", "left", seed=unrolled, scenario=scenario)
    _check_block(sc, C, *_reference(sc, C))


@pytest.mark.parametrize("heavy", ("left", "right"))
@pytest.mark.parametrize("layout", ("lane", "bit"))
@pytest.mark.parametrize("C", (ROUTE_TILE, 2 * ROUTE_TILE, 4 * ROUTE_TILE))
def test_park_pass_carries_the_open_window(C, layout, heavy):
    """The same edges through `park_pass` (one block over the whole
    buffer, routed by the bag: in-bag rows from chunk 0 on, the others
    parked at the buffer's end), against numpy's stable partition by
    the bag; the bag is the `heavy` side's mask of `_carry_scenario`."""
    wcnt = 3
    lanes, W = lane_layout(wcnt, with_bag=True, compact=layout == "bit")
    w_used = max(lanes.values()) + 1
    (_, cnts, masks, _), _ = _carry_scenario(C, heavy)
    rng = np.random.default_rng(C + len(layout))
    rec = rng.integers(0, 1 << 31, (NC, W, C), dtype=np.int64) \
        .astype(np.int32)
    cnts = np.pad(np.asarray(cnts, np.int32), (0, NC - len(cnts)))
    bag = np.zeros((NC, C), bool)
    for c, m in enumerate(masks):
        bag[c, :len(m)] = m
    if layout == "bit":
        meta = rec[:, lanes["meta"], :] & 0x7FFFFFFF
        rec[:, lanes["meta"], :] = np.where(bag, meta | -(1 << 31), meta)
        bag_lane = -2
    else:
        rec[:, lanes["bag"], :] = bag.astype(np.float32).view(np.int32)
        bag_lane = lanes["bag"]
    live = np.arange(C)[None, :] < cnts[:, None]
    rows = rec.transpose(0, 2, 1)[live][:, :w_used]       # as they lie
    inb = bag[live]
    kept = int(inb.sum())
    a, b, new, park_begin = jax.jit(
        lambda a, b: park_pass(
            a, b, 0, jnp.asarray(cnts), jnp.int32(kept), C, W, wcnt,
            bag_lane, bits=8, w_used=w_used, interpret=True))(
        rec, np.full_like(rec, 7))
    np.testing.assert_array_equal(np.asarray(a), rec)
    new, pb = np.asarray(new), int(park_begin)
    got = np.asarray(b).transpose(0, 2, 1)[:, :, :w_used]
    lay = np.arange(C)[None, :] < new[:, None]
    assert new[:pb].sum() == kept and new[pb:].sum() == len(rows) - kept
    np.testing.assert_array_equal(got[:pb][lay[:pb]], rows[inb])
    np.testing.assert_array_equal(got[pb:][lay[pb:]], rows[~inb])


@pytest.mark.parametrize("src", (0, 1))
def test_two_passes_end_in_the_buffer_they_started_from(src):
    """A round loop's ping-pong: the first pass moves the rows out of
    buffer `src`, the second (a round that splits nothing: every live
    chunk shifts whole, onto its own index) brings the new layout back
    into it, over the rows the first pass read."""
    C = 2 * ROUTE_TILE
    sc = _build(C, "std8", "left", seed=30)
    rec_in, rt, expect, hist_ref, b_pad = _reference(sc, C)
    bufs = [np.zeros_like(rec_in), np.zeros_like(rec_in)]
    bufs[src] = rec_in
    bufs, hist = _call(sc, bufs, src, rt, b_pad)
    np.testing.assert_allclose(hist, hist_ref, rtol=2e-5, atol=2e-4)
    live = np.zeros(NC, bool)
    live[list(expect)] = True
    cnt = np.zeros(NC, np.int32)
    for chunk, rows in expect.items():
        cnt[chunk] = len(rows)
    back = dict(rt, r1=np.where(live, 1 << R_COPY, 7).astype(np.int32),
                basel=np.arange(NC, dtype=np.int32),
                baser=np.zeros(NC, np.int32), meta=cnt,
                hslots=np.full(NC, K, np.int32))
    moved = bufs[1 - src].copy()
    bufs, hist = _call(sc, bufs, 1 - src, back, b_pad)
    assert not hist.any()
    _check_rows(sc, bufs[src], expect)
    np.testing.assert_array_equal(bufs[src][live], moved[live])
    np.testing.assert_array_equal(bufs[1 - src], moved)


def test_route_tile_divides_every_chunk():
    """One tile wherever ROUTE_TILE does not divide the chunk: a pinned
    `tpu_chunk` of any multiple of 128 still runs."""
    for C in (128, 256, 384, ROUTE_TILE, 640, 3 * ROUTE_TILE, 2048, 4096):
        assert route_tile(C) == (C if C % ROUTE_TILE else ROUTE_TILE)
