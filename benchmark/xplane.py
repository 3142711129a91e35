"""The reduction from a profiler trace to intervals, sums and gaps.

`jax.profiler` writes `<dir>/plugins/profile/<time>/<host>.xplane.pb`;
`jax.profiler.ProfileData.from_file` reads it with nothing but JAX. A TPU
has one plane per chip, `/device:TPU:<i>`, whose line `XLA Ops` holds one
event per executed operation, named by the operation's whole HLO text
(`%move_pass.15 = (s32[24588,24,2048]{...}, ...) custom-call(...)`: a
Pallas kernel is one `custom-call` event, however many grid steps it
runs, called after the jitted function around it). `while` and
`conditional` events span the operations of their bodies, which are
listed too, so they are left out. The harness's own
`TraceAnnotation`s land on the host plane's thread lines, on the same
clock, and are what the idle gaps are named by.

Everything below the loader works on plain `(name, start_ns, end_ns)`
tuples, so it is checked on made-up intervals.
"""
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
WRAPPERS = ("while", "conditional", "call")
MOSAIC = 'custom_call_target="tpu_custom_call"'


def parse_op(text: str):
    """(short name, kind) of an `XLA Ops` event name; kind is "kernel"
    for a Mosaic custom call, "wrapper" for control flow whose body's
    operations are listed themselves, else "op". A kernel's short name
    drops the instance number: `move_pass.15` -> `move_pass`."""
    name, _, rest = text.partition(" = ")
    name = name.lstrip("%")
    found = _OPCODE.search(" " + rest)
    opcode = found.group(1) if found else ""
    if opcode == "custom-call" and MOSAIC in rest:
        return re.sub(r"\.\d+\Z", "", name), "kernel"
    return name, "wrapper" if opcode in WRAPPERS else "op"


def load(path: str, span_prefix: str) -> dict:
    """{"devices": {plane: [(name, start_ns, end_ns)]}, "kernels": {names},
    "spans": [...]}: every chip's operations under their short names,
    which of those names are Pallas kernels, and the host annotations
    whose name starts with `span_prefix`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, kernels, spans, parsed = {}, set(), [], {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    if e.name not in parsed:
                        parsed[e.name] = parse_op(e.name)
                    name, kind = parsed[e.name]
                    if kind == "wrapper":
                        continue
                    if kind == "kernel":
                        kernels.add(name)
                    ops.append((name, e.start_ns, e.start_ns + e.duration_ns))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(span_prefix))
    return {"devices": devices, "kernels": kernels,
            "spans": sorted(spans, key=lambda s: s[1])}


def describe(path: str, top: int = 12) -> list:
    """Lines of text saying what a trace holds: for looking at one by
    hand before trusting a reduction written against it."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            total = {}
            for e in events:
                total[e.name] = total.get(e.name, 0) + e.duration_ns
            out.append(f"  line {line.name!r}: {len(events)} events, "
                       f"{len(total)} names")
            for name, ns in sorted(total.items(), key=lambda kv: -kv[1])[:top]:
                out.append(f"    {ns / 1e6:12.3f} ms  {name[:140]}")
            if events and plane.name.startswith(DEVICE_PLANE):
                longest = max(events, key=lambda e: e.duration_ns)
                out.append(f"    stats of {longest.name[:60]!r}: "
                           f"{[(k, str(v)[:200]) for k, v in longest.stats]}")
    return out


def clip(events, t0: int, t1: int) -> list:
    """The parts of `events` inside [t0, t1)."""
    return [(n, max(s, t0), min(e, t1)) for n, s, e in events
            if e > t0 and s < t1]


def merge(events) -> list:
    """Union of the events' intervals: sorted disjoint [start, end)."""
    out = []
    for s, e in sorted((s, e) for _, s, e in events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events) -> int:
    return sum(e - s for s, e in merge(events))


def by_name(events) -> dict:
    """Summed duration per event name, in ns."""
    out = {}
    for n, s, e in events:
        out[n] = out.get(n, 0) + (e - s)
    return out


def idle_gaps(events, spans, t0: int, t1: int) -> dict:
    """Idle ns inside [t0, t1) per host span: each gap between the
    merged device intervals is cut at the spans' edges and every piece
    goes to the span the host was in (`spans` do not overlap), or to
    "outside" where it was in none."""
    edges = [t0]
    for s, e in merge(clip(events, t0, t1)):
        edges += [s, e]
    edges.append(t1)
    out = {}
    for gap_start, gap_end in zip(edges[0::2], edges[1::2]):
        at = gap_start
        for name, s, e in spans:
            if e <= at or s >= gap_end:
                continue
            if s > at:
                out["outside"] = out.get("outside", 0) + (s - at)
            upto = min(e, gap_end)
            out[name] = out.get(name, 0) + (upto - max(s, at))
            at = upto
        if gap_end > at:
            out["outside"] = out.get("outside", 0) + (gap_end - at)
    return {k: v for k, v in out.items() if v > 0}


def window(trace: dict, top: int = 10) -> dict:
    """`trace` with the traced window's facts added: the window is from
    the first harness span's start to the last one's end; `ops` are each
    chip's operations inside it, `window` the busy and wall seconds
    (busy averaged over the chips), `breakdown` the operations that took
    most time and the host spans that hold most idle time, `counts` how
    often each kernel ran (averaged over the chips)."""
    spans = trace["spans"]
    t0 = min((s for _, s, _ in spans), default=0)
    t1 = max((e for _, _, e in spans), default=0)
    ops = {d: clip(ev, t0, t1) for d, ev in trace["devices"].items()}
    n = max(len(ops), 1)
    names, gaps = {}, {}
    for ev in ops.values():
        for into, part in ((names, by_name(ev)),
                           (gaps, idle_gaps(ev, spans, t0, t1))):
            for k, v in part.items():
                into[k] = into.get(k, 0.0) + v / n / 1e9

    def largest(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    counts = {}
    for ev in ops.values():
        for name, _, _ in ev:
            if name in trace["kernels"]:
                counts[name] = counts.get(name, 0) + 1.0 / n
    return dict(trace, ops=ops, counts=counts, window={
        "busy_s": sum(busy_ns(ev) for ev in ops.values()) / n / 1e9,
        "window_s": (t1 - t0) / 1e9},
        breakdown={"device_ops": largest(names), "idle_gaps": largest(gaps)})
