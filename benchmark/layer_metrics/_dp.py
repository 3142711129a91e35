"""What the data-parallel readers share: the histogram all-reduce's
bytes, its least time over the chips' interconnect, and how long each
chip spent in it.

The bytes come from the program's counter `psum_bytes` on the window's
`aligned.iter` records (the root's histogram and one a round, all-reduced
over the mesh: `AlignedEngine.psum_bytes`), the chips from the
`aligned.pack` seam's `shards`. A ring all-reduce of P bytes over n chips
sends 2 (n - 1) / n x P from each chip, and no algorithm sends less, so
any implementation is read against that least traffic at the chip's
published interconnect rate (`peaks.json`'s `ici_bits_per_s`).

A program without the counter (the parent of the PR that added it), a
window without records, a trace without the phase: every reader gives
None and none raises.
"""
import json
import os

from benchmark.layer_metrics import _phases, _seams

HERE = os.path.dirname(os.path.abspath(__file__))
PHASE = "dp.psum"


def ici_bytes_per_s():
    """One chip's published interconnect rate in bytes a second, or None
    for a device `benchmark/peaks.json` does not list."""
    import jax
    with open(os.path.join(os.path.dirname(HERE), "peaks.json")) as f:
        devices = json.load(f)["devices"]
    peak = devices.get(jax.devices()[0].device_kind)
    return None if peak is None else peak["ici_bits_per_s"] / 8.0


def psum_bytes(ctx):
    """(bytes the window's histogram all-reduces carried, chips), or None
    where the ring has no window, no counter or no mesh."""
    recs = _seams.ring()
    win = _seams.window(recs, ctx["iterations"])
    pack = _seams.named(recs, "aligned.pack")
    if win is None or not pack or any("psum_bytes" not in r
                                      for r in win["iters"]):
        return None
    shards = int(pack[-1].get("shards", 1))
    if shards < 2:
        return None
    return sum(r["psum_bytes"] for r in win["iters"]), shards


def ring_bytes(payload: float, shards: int) -> float:
    """Bytes each chip sends in a ring all-reduce of `payload` bytes."""
    return 2.0 * (shards - 1) / shards * payload


def _spans(events) -> list:
    """[(start, end)] of one chip's all-reduce events: an asynchronous
    collective from its `-start` event's start to its `-done` event's end
    (paired in order), so that compute the compiler placed between the
    two counts as the collective's time too; any other event as it is."""
    out, open_ = [], []
    for name, s, e in sorted(events, key=lambda ev: ev[1]):
        if "-start" in name:
            open_.append(s)
        elif "-done" in name and open_:
            out.append((open_.pop(0), e))
        else:
            out.append((s, e))
    return out


def psum_span_s(ctx):
    """Seconds a chip spent in the window's histogram all-reduces, from
    the first event of each to its last (`_spans`), averaged over the
    chips; None where no operation of the window has the phase."""
    win = _phases.window(ctx)
    if win is None or PHASE not in win["phases"]:
        return None
    total, chips = 0, 0
    for evs in win["devices"].values():
        mine = [(op[0], s, e) for op, s, e in evs if op[3] == PHASE]
        if not mine:
            continue
        chips += 1
        total += sum(e - s for s, e in _merge(_spans(mine)))
    return total / chips / 1e9 if chips else None


def _merge(spans) -> list:
    out = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def roofline_pct(ctx):
    """100 x (the least time of the window's all-reduces at the chip's
    interconnect rate) / the time a chip spent in them."""
    found = psum_bytes(ctx)
    seconds = psum_span_s(ctx) if found else None
    peak = ici_bytes_per_s() if seconds else None
    if not peak:
        return None
    return 100.0 * ring_bytes(*found) / peak / seconds


def busy_spread_pct(ctx):
    """100 x (most busy chip - least busy chip) / mean busy, over the
    traced window; None on one chip."""
    from benchmark import xplane
    ops = ctx["trace"].get("ops") or {}
    if len(ops) < 2:
        return None
    busy = [xplane.busy_ns(ev) for ev in ops.values()]
    mean = sum(busy) / len(busy)
    return 100.0 * (max(busy) - min(busy)) / mean if mean else None
