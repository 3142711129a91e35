"""What the phase readers share: the traced window's device operations
under their FULL names, joined to the phases the program gives its own
XLA operations (`lightgbm_tpu.obs.phases`, PR 37), and the device's idle
gaps named by the program's own seams.

A device trace names an `XLA Ops` event by its HLO text without metadata
(`%fusion.96 = f32[11043840]{0:T(1024)} fusion(...)`), so no scope of the
program reaches it. The compiled HLO has both: `phases.table()` lowers
and compiles every program the run remembered again and lists each
instruction with the phase of its `op_name`. Instruction name and result
shape are the join; an event that matches instructions of two programs
whose phases differ counts as unnamed.

`ctx` holds no path to the trace (`run.py` keeps it to itself), so the
window's xplane is the newest `*.xplane.pb` under `build/benchmark_trace/`,
where `run.py` writes. It is read once a run, here, with the names
`benchmark/xplane.py:load` shortens, and with the host plane's events of
the program's seams (`obs/trace.py:_Seam` enters a `TraceAnnotation`, on
the device's clock), which `load` drops for not starting with `bench.`.

Every reader gives None, and none raises, where the program has no
`obs.phases` (the driver lays these files over the parent's checkout
too), where the trace has no device plane (a CPU), and where no xplane
is found.
"""
import bisect
import glob
import os
import sys

from benchmark import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(HERE)), "build",
                          "benchmark_trace")
# the program's seams that have an extent (`obs/trace.py:seam`)
SEAM_PREFIXES = ("train.", "aligned.", "goss.", "bag.", "ingest.")
DRAIN = "train.drain"
_cache = {}     # xplane path -> what `window` made of it


def program():
    """(`obs.phases`, `obs.hlo`) of the program under test, or None on a
    checkout that has neither."""
    try:
        from lightgbm_tpu.obs import hlo, phases
    except ImportError:
        return None
    return phases, hlo


def newest_xplane(root: str = None):
    found = glob.glob(os.path.join(root or TRACE_ROOT, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def parse_event(text: str, hlo):
    """(instruction name, plain result shape, kind) of an `XLA Ops`
    event's name; kind as `xplane.parse_op` has it."""
    found = hlo.instruction(text)
    kind = xplane.parse_op(text)[1]
    if found is None:
        return text.partition(" = ")[0].lstrip("%"), "", kind
    return found[0], hlo.plain_shape(found[1]), kind


def load(path: str, hlo) -> dict:
    """{"devices": {plane: [((instruction, shape, kind), start_ns, end_ns)]},
    "seams": [(name, start_ns, end_ns)]} of one xplane file: every chip's
    operations but the control-flow wrappers, and the host plane's
    events that are the program's seams. Both in the shape
    `benchmark/xplane.py`'s interval arithmetic takes."""
    from jax.profiler import ProfileData
    devices, seams, parsed = {}, [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(xplane.DEVICE_PLANE):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != xplane.OPS_LINE:
                    continue
                for e in line.events:
                    if e.name not in parsed:
                        parsed[e.name] = parse_event(e.name, hlo)
                    if parsed[e.name][2] != "wrapper":
                        ops.append((parsed[e.name], e.start_ns,
                                    e.start_ns + e.duration_ns))
        elif plane.name == xplane.HOST_PLANE:
            for line in plane.lines:
                seams.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SEAM_PREFIXES))
    return {"devices": devices, "seams": sorted(seams, key=lambda s: s[1])}


def phase_index(table) -> dict:
    """{(instruction, shape): phase or None} from a phase table; None
    also where two programs' instructions of that name and shape carry
    different phases."""
    seen = {}
    for row in table:
        seen.setdefault((row["instruction"], row["shape"]),
                        set()).add(row["phase"])
    return {k: (v.pop() if len(v) == 1 else None) for k, v in seen.items()}


def window(ctx):
    """The traced window as the phase readers see it: {"devices": each
    chip's clipped operations as ((instruction, shape, kind, phase),
    start, end), "seams": the clipped seams, "t0", "t1", and of the table
    its "phases", "programs", "table_rows" and the seconds it took,
    "table_s"}; None where there is nothing to read (the module's head)."""
    found = program()
    spans = (ctx.get("trace") or {}).get("spans") or []
    if found is None or not spans or not ctx["trace"].get("ops"):
        return None
    phases, hlo = found
    path = newest_xplane()
    if path is None:
        return None
    if path in _cache:
        return _cache[path]
    import time
    try:
        raw = load(path, hlo)
        t = time.perf_counter()
        table = phases.table()
        table_s = time.perf_counter() - t
    except Exception as err:    # a reader never fails its run
        print(f"_phases: no phase table ({err!r})", file=sys.stderr)
        _cache[path] = None
        return None
    t0 = min(s for _, s, _ in spans)
    t1 = max(e for _, _, e in spans)
    index = phase_index(table)
    devices = {d: [(op + (index.get(op[:2]),), s, e)
                   for op, s, e in xplane.clip(evs, t0, t1)]
               for d, evs in raw["devices"].items()}
    out = None
    if any(devices.values()):
        out = {"devices": devices,
               "seams": xplane.clip(raw["seams"], t0, t1),
               "t0": t0, "t1": t1, "table_s": table_s,
               "table_rows": len(table),
               "phases": {r["phase"] for r in table},
               "programs": sorted({r["program"] for r in table})}
    _cache[path] = out
    return out


def by_phase(win, kernels: bool = False) -> dict:
    """{phase or None: ns} of the window's XLA operations (with
    `kernels`, of its Pallas kernels instead), averaged over the chips."""
    out, n = {}, max(len(win["devices"]), 1)
    for evs in win["devices"].values():
        for (_, _, kind, phase), s, e in evs:
            if (kind == "kernel") == kernels:
                out[phase] = out.get(phase, 0.0) + (e - s) / n
    return out


def phase_ms_per_iter(ctx, *phases, kernels: bool = False):
    """Device milliseconds per iteration in the XLA operations of the
    named phases (with `kernels`, in the kernels called from them too);
    0 where none of them ran in the window, None where no program of the
    run has any of them."""
    win = window(ctx)
    if win is None or not win["phases"].intersection(phases):
        return None
    parts = [by_phase(win)] + ([by_phase(win, True)] if kernels else [])
    ns = sum(part.get(p, 0.0) for part in parts for p in phases)
    return ns / 1e6 / ctx["iterations"]


def named_share(ctx):
    """Per cent of the window's XLA (non-kernel) device time whose event
    joined a registered phase."""
    win = window(ctx)
    if win is None:
        return None
    ns = by_phase(win)
    total = sum(ns.values())
    return 100.0 * (total - ns.get(None, 0.0)) / total if total else None


def seam_paths(seams) -> list:
    """[(start, end, path)]: the time the seams cover, cut at every edge,
    each piece under the names of the seams that hold it from the
    outermost to the innermost (a path, `train.drain/train.resolve`).
    Seams of one thread nest; where two overlap otherwise the later
    start counts as the inner one (of two that start together, the
    shorter)."""
    edges = sorted({t for _, s, e in seams for t in (s, e)})
    out = []
    for a, b in zip(edges, edges[1:]):
        holding = sorted((s, -e, n) for n, s, e in seams
                         if s <= a and e >= b)
        if holding:
            out.append((a, b, "/".join(n for _, _, n in holding)))
    return out


def idle_in(events, pieces, t0: int, t1: int) -> dict:
    """Idle ns of one chip inside [t0, t1) per seam path (`seam_paths`);
    "outside" where the host was in no seam. `xplane.idle_gaps` for
    nested spans."""
    edges = [t0]
    for s, e in xplane.merge(events):
        edges += [s, e]
    edges.append(t1)
    starts = [p[0] for p in pieces]
    out = {}
    for gap_start, gap_end in zip(edges[0::2], edges[1::2]):
        if gap_end <= gap_start:
            continue
        named = 0
        at = max(bisect.bisect_right(starts, gap_start) - 1, 0)
        while at < len(pieces) and pieces[at][0] < gap_end:
            a, b, path = pieces[at]
            part = min(b, gap_end) - max(a, gap_start)
            if part > 0:
                out[path] = out.get(path, 0) + part
                named += part
            at += 1
        if gap_end - gap_start > named:
            out["outside"] = out.get("outside", 0) + (
                gap_end - gap_start - named)
    return out


def idle_by_seam(ctx):
    """{seam path: idle ns} of the window, averaged over the chips."""
    win = window(ctx)
    if win is None:
        return None
    pieces = seam_paths(win["seams"])
    out, n = {}, max(len(win["devices"]), 1)
    for evs in win["devices"].values():
        for path, ns in idle_in(evs, pieces, win["t0"], win["t1"]).items():
            out[path] = out.get(path, 0.0) + ns / n
    return out


def drain_gap_ns(ctx):
    """(idle ns inside the window's `train.drain` seams, the part of it
    under no seam inside one), or None where no drain seam was traced."""
    idle = idle_by_seam(ctx)
    if idle is None:
        return None
    inside = {p: ns for p, ns in idle.items() if DRAIN in p.split("/")}
    if not inside:
        return None
    bare = sum(ns for p, ns in inside.items() if p.split("/")[-1] == DRAIN)
    return sum(inside.values()), bare
