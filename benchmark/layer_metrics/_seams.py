"""What the seam readers share: the program's seam ring
(`lightgbm_tpu.obs.trace.seams()`: host boundaries that always record,
on `time.perf_counter`, and one `aligned.iter` record per resolved
iteration with the build program's per-round counters) cut to the
measured window, and the arithmetic on it.

The readers run in the benchmark's process after the window, so the ring
is read directly. The window's iterations are the last `ctx["iterations"]`
`aligned.iter` records; on the host's clock the window runs from the
`aligned.dispatch` seam of the first of them to the end of the first
`train.drain` after it (the checks that follow drain again, on an idle
device). A program without seams (the parent of the PR that added them)
gives an empty ring and every reader `None`.
"""
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
WAITS = ("train.flag_pull", "train.drain")


def ring() -> list:
    from lightgbm_tpu.obs import trace
    # the driver lays these readers over the parent's checkout too, whose
    # tracer has no seams: nothing to read there, and no reader may raise
    seams = getattr(trace, "seams", None)
    return [] if seams is None else seams()


def seconds(records) -> float:
    return sum(r["t1"] - r["t0"] for r in records)


def named(records, *names) -> list:
    return [r for r in records if r["name"] in names]


def outermost(records) -> list:
    """`records` less those enclosed by another of them, so a flag pull
    inside a drain is not counted twice."""
    ids = {r["id"] for r in records}
    return [r for r in records if r["parent"] not in ids]


def total(prefix: str, before_window_of=None, outside: str = None):
    """Seconds in the outermost seams whose name starts with `prefix`;
    given `before_window_of` (a window's iterations), only those that
    ended before that window began; given `outside`, only those that no
    seam of that name encloses (its seconds are another metric's). None
    where there is none."""
    recs = ring()
    found = outermost([r for r in recs if r["name"].startswith(prefix)])
    if outside is not None:
        by_id = {r["id"]: r for r in recs}

        def enclosed(r):
            up = by_id.get(r["parent"])
            return up is not None and (up["name"] == outside
                                       or enclosed(up))
        found = [r for r in found if not enclosed(r)]
    if before_window_of is not None:
        win = window(recs, before_window_of)
        if win is None:
            return None
        found = [r for r in found if r["t1"] <= win["t0"]]
    return seconds(found) if found else None


def window(records, iterations: int):
    """{"iters": the window's `aligned.iter` records, "t0", "t1"} or None
    where the ring does not hold `iterations` of them."""
    iters = named(records, "aligned.iter")[-iterations:]
    if iterations <= 0 or len(iters) < iterations:
        return None
    first = [r for r in named(records, "aligned.dispatch")
             if r["iter"] == iters[0]["iter"]]
    if not first:
        return None
    t0 = first[-1]["t0"]
    drains = [r for r in named(records, "train.drain") if r["t0"] >= t0]
    if not drains:
        return None
    return {"iters": iters, "t0": t0, "t1": drains[0]["t1"]}


def column(iters, name: str) -> list:
    """One counter of every round of `iters`, in execution order."""
    return [row[rec["columns"].index(name)]
            for rec in iters for row in rec["table"]]


def per_iter(ctx, name: str):
    """One counter summed over the window's rounds, per iteration."""
    win = window(ring(), ctx["iterations"])
    if win is None:
        return None
    return sum(column(win["iters"], name)) / ctx["iterations"]


def blocked_s(ctx):
    """(seconds of the window, seconds of them in which the loop is
    blocked on the device: `WAITS`, a pull inside a drain counted once),
    or None where the ring does not hold the window."""
    recs = ring()
    win = window(recs, ctx["iterations"])
    if win is None:
        return None
    inside = [r for r in named(recs, *WAITS)
              if r["t0"] >= win["t0"] and r["t1"] <= win["t1"]]
    return win["t1"] - win["t0"], seconds(outermost(inside))


def driver_host_ms_per_iter(ctx):
    """The window on the host's clock less the blocked part, per
    iteration: what the driver itself costs, whatever the kernels do."""
    found = blocked_s(ctx)
    if found is None:
        return None
    return 1e3 * (found[0] - found[1]) / ctx["iterations"]


def move_events(ctx) -> list:
    """Durations in ns of the traced window's `move_pass` events in
    execution order, on the first chip (the counters are shard 0's)."""
    ops = ctx["trace"].get("ops") or {}
    if not ops:
        return []
    first = ops[sorted(ops)[0]]
    return [e - s for n, s, e in sorted(first, key=lambda ev: ev[1])
            if n == "move_pass"]


def fit_two(x1, x2, y):
    """Least squares with no intercept of y on (x1, x2): the normal
    equations of a 2 x 2 system. None where they are singular."""
    a = sum(v * v for v in x1)
    b = sum(u * v for u, v in zip(x1, x2))
    c = sum(v * v for v in x2)
    p = sum(u * v for u, v in zip(x1, y))
    q = sum(u * v for u, v in zip(x2, y))
    det = a * c - b * b
    if det <= 1e-9 * max(a * c, 1.0):
        return None
    return (p * c - q * b) / det, (q * a - p * b) / det


def chunk_costs_us(ctx):
    """(us per chunk on the compute path, us per copied chunk) from each
    `move_pass` event's duration and its round's chunk counts. The
    compute path's count is `chunks_split + chunks_dead`: a dead chunk
    does the split path's work on no valid row. None unless the events
    number exactly the window's rounds: the pairing is by order."""
    win = window(ring(), ctx["iterations"])
    ns = move_events(ctx)
    if win is None or not ns or len(ns) != sum(r["rounds"]
                                               for r in win["iters"]):
        return None
    dead = column(win["iters"], "chunks_dead") \
        if "chunks_dead" in win["iters"][0]["columns"] else None
    split = column(win["iters"], "chunks_split")
    if dead is not None:
        split = [s + d for s, d in zip(split, dead)]
    fit = fit_two(split, column(win["iters"], "chunks_copied"), ns)
    return None if fit is None else (fit[0] / 1e3, fit[1] / 1e3)


def hbm_bytes_per_s():
    """The chip's published HBM bandwidth (`benchmark/peaks.json`), or
    None for a device the table does not list."""
    import jax
    with open(os.path.join(os.path.dirname(HERE), "peaks.json")) as f:
        devices = json.load(f)["devices"]
    peak = devices.get(jax.devices()[0].device_kind)
    return None if peak is None else peak["hbm_bytes_per_s"]


def move_roofline_pct(ctx):
    """100 x (bytes that had to move / HBM peak) / `move_pass` seconds:
    the records of the rows of every split leaf (4 bytes x `w_used`
    lanes), read once and written once. Whole-chunk copies and the route
    matmul are not in it: any implementation is read against the same
    least traffic."""
    recs = ring()
    win = window(recs, ctx["iterations"])
    pack = named(recs, "aligned.pack")
    ns = sum(move_events(ctx))
    peak = hbm_bytes_per_s() if ns else None
    if win is None or not pack or not peak:
        return None
    moved = 2 * sum(column(win["iters"], "rows_split")) \
        * 4 * pack[-1]["w_used"]
    return 100.0 * (moved / peak) / (ns / 1e9)
