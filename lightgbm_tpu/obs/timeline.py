"""Unified run timeline: every JSONL/event stream the framework emits,
joined onto ONE monotonic clock as Chrome-trace / Perfetto JSON.

The observability planes grew up siloed — span trace
(``spans-<pid>.jsonl``), per-round ledger records
(``ledger-*.jsonl``), request traces (``reqtrace-*.jsonl``), the
streaming-ingest pipeline walls, sweep per-sub-fleet round dispatches,
and compile-cache miss events. Each answers its own question; none
answers "where did the WALL-CLOCK of this run go, across subsystems".
This module
answers that: ``build_timeline`` reads whichever streams exist and
emits one ``trace_events``-format document loadable in
``chrome://tracing`` or https://ui.perfetto.dev.

**Clock model.** Every producer stamps ``t0`` with
``time.perf_counter()``. On Linux that is CLOCK_MONOTONIC — a single
system-wide epoch shared by every process on the host — so spans from
the trainer and the prefetch thread join WITHOUT cross-stream
alignment: the timeline anchors
at the earliest ``t0`` seen and emits ``ts`` in microseconds relative
to it. Rows from old producers that lack ``t0`` are placed
end-to-start after their lane's cursor (ordered, not aligned) and
marked ``args.placed: "sequential"``.

**Lane map** (one Chrome-trace ``pid`` per subsystem; ``tid`` splits a
subsystem into parallel actors):

====== ========= ==================================================
pid    lane      tid semantics
====== ========= ==================================================
1      train     0 = round loop
2      spans     host span trace (tid = span depth)
3      serving   request spans (tid 0)
4      ingest    0 = chunk wall, 1 = parse (prefetch thread),
                 2 = bin (device side)
5      sweep     tid = sub-fleet id (per-sub-fleet round dispatches)
7      events    instant events (compile-cache misses, straggler /
                 anomaly raises, ...) (tid 0)
====== ========= ==================================================

Reading is tolerant by construction: torn JSONL tails are dropped
(mirroring ``obs.ledger.read_ledger``) and absent streams contribute
no lane. Building a
timeline never touches jax and never fences — it is pure host-side
file merging, usable on a machine that never ran the job.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["LANES", "build_timeline", "collect_streams", "has_data",
           "lane_counts", "read_jsonl", "timeline_on",
           "write_timeline"]

# lane name -> Chrome-trace pid (stable: Perfetto sorts by pid)
LANES: Dict[str, int] = {
    "train": 1, "spans": 2, "serving": 3, "ingest": 4,
    "sweep": 5, "events": 7,
}

# ingest tids within the ingest lane
_TID_INGEST_WALL, _TID_INGEST_PARSE, _TID_INGEST_BIN = 0, 1, 2


def timeline_on(cfg: Any) -> bool:
    """Resolve the ``tpu_timeline`` knob: ``on`` unconditional, ``off``
    never, ``auto`` (default) piggybacks on ``tpu_trace`` — a traced
    run gets its timeline for free, an untraced run pays nothing."""
    mode = str(getattr(cfg, "tpu_timeline", "auto")).lower()
    if mode == "on":
        return True
    if mode == "off":
        return False
    return bool(getattr(cfg, "tpu_trace", False))


def read_jsonl(path: str) -> List[Dict[str, Any]]:
    """Parse a JSONL stream, dropping a torn final line (SIGKILL
    mid-flush) instead of failing — same contract as
    ``obs.ledger.read_ledger`` but returning [] for a missing file."""
    if not os.path.isfile(path):
        return []
    try:
        with open(path) as fh:
            lines = [ln.strip() for ln in fh]
    except OSError:
        return []
    rows: List[Dict[str, Any]] = []
    nonempty = [ln for ln in lines if ln]
    for pos, line in enumerate(nonempty):
        try:
            rec = json.loads(line)
        except ValueError:
            if pos == len(nonempty) - 1:
                break           # torn tail: keep everything before it
            raise
        if isinstance(rec, dict):
            rows.append(rec)
    return rows


def collect_streams(trace_dir: Optional[str] = None,
                    ledger_path: Optional[str] = None
                    ) -> Dict[str, Any]:
    """Gather every source stream that exists.

    ``trace_dir`` is scanned for ``spans-*.jsonl``, ``ledger-*.jsonl``,
    ``reqtrace-*.jsonl`` and ``events-*.jsonl``; ``ledger_path`` adds
    one explicit ledger (deduplicated against the scan)."""
    streams: Dict[str, Any] = {
        "spans": [], "ledger": [], "reqtrace": [], "events": [],
    }
    ledger_files: List[str] = []
    if trace_dir and os.path.isdir(trace_dir):
        for f in sorted(glob.glob(os.path.join(trace_dir,
                                               "spans-*.jsonl"))):
            streams["spans"].extend(read_jsonl(f))
        ledger_files.extend(sorted(glob.glob(
            os.path.join(trace_dir, "ledger-*.jsonl"))))
        for f in sorted(glob.glob(os.path.join(trace_dir,
                                               "reqtrace-*.jsonl"))):
            streams["reqtrace"].extend(read_jsonl(f))
        for f in sorted(glob.glob(os.path.join(trace_dir,
                                               "events-*.jsonl"))):
            streams["events"].extend(read_jsonl(f))
    if ledger_path and os.path.abspath(ledger_path) not in (
            os.path.abspath(f) for f in ledger_files):
        ledger_files.append(ledger_path)
    for f in ledger_files:
        streams["ledger"].extend(read_jsonl(f))
    return streams


# ---------------------------------------------------------------------------
def _meta(pid: int, name: str,
          tids: Dict[int, str]) -> List[Dict[str, Any]]:
    evs = [{"ph": "M", "pid": pid, "tid": 0, "name": "process_name",
            "args": {"name": name}},
           {"ph": "M", "pid": pid, "tid": 0, "name":
            "process_sort_index", "args": {"sort_index": pid}}]
    for tid, tname in sorted(tids.items()):
        evs.append({"ph": "M", "pid": pid, "tid": tid,
                    "name": "thread_name", "args": {"name": tname}})
    return evs


class _Builder:
    """Accumulates trace events against a shared anchor; rows without a
    ``t0`` are placed sequentially after their lane cursor."""

    def __init__(self, anchor: float) -> None:
        self.anchor = anchor
        self.events: List[Dict[str, Any]] = []
        self.tids: Dict[int, Dict[int, str]] = {}
        self._cursor: Dict[Tuple[int, int], float] = {}

    def name_tid(self, pid: int, tid: int, name: str) -> None:
        self.tids.setdefault(pid, {}).setdefault(tid, name)

    def _ts_us(self, t0: Optional[float], pid: int, tid: int,
               dur_ms: float) -> Tuple[float, bool]:
        """(start µs, placed-sequentially?) for one row."""
        if isinstance(t0, (int, float)):
            return (float(t0) - self.anchor) * 1e6, False
        cur = self._cursor.get((pid, tid), 0.0)
        return cur, True

    def span(self, pid: int, tid: int, name: str,
             t0: Optional[float], dur_ms: float, src: str,
             args: Optional[Dict[str, Any]] = None) -> None:
        dur_ms = max(float(dur_ms or 0.0), 0.0)
        ts, seq = self._ts_us(t0, pid, tid, dur_ms)
        ev: Dict[str, Any] = {
            "ph": "X", "pid": pid, "tid": tid, "name": name,
            "ts": round(ts, 3), "dur": round(dur_ms * 1e3, 3),
            "cat": src, "args": {"src": src}}
        if seq:
            ev["args"]["placed"] = "sequential"
        if args:
            ev["args"].update(args)
        self.events.append(ev)
        self._cursor[(pid, tid)] = max(
            self._cursor.get((pid, tid), 0.0), ts + dur_ms * 1e3)

    def instant(self, pid: int, tid: int, name: str,
                t0: Optional[float], src: str,
                args: Optional[Dict[str, Any]] = None) -> None:
        ts, seq = self._ts_us(t0, pid, tid, 0.0)
        ev: Dict[str, Any] = {
            "ph": "i", "pid": pid, "tid": tid, "name": name,
            "ts": round(ts, 3), "s": "p", "cat": src,
            "args": {"src": src}}
        if seq:
            ev["args"]["placed"] = "sequential"
        if args:
            ev["args"].update(args)
        self.events.append(ev)


def _find_anchor(streams: Dict[str, Any]) -> float:
    """Earliest monotonic timestamp across every stream (0.0 when no
    stream carries one — everything then places sequentially)."""
    t0s: List[float] = []
    for key in ("spans", "ledger", "events"):
        for r in streams.get(key, ()):
            v = r.get("t0")
            if isinstance(v, (int, float)):
                t0s.append(float(v))
    for r in streams.get("reqtrace", ()):
        v = r.get("t_submit")
        if isinstance(v, (int, float)):
            t0s.append(float(v))
    return min(t0s) if t0s else 0.0


# -- per-stream folds -------------------------------------------------------
def _fold_spans(b: _Builder, rows: List[Dict[str, Any]]) -> int:
    pid = LANES["spans"]
    n = 0
    for r in rows:
        if r.get("kind") != "span":
            continue
        tid = int(r.get("depth", 0) or 0)
        b.name_tid(pid, tid, f"depth {tid}")
        b.span(pid, tid, str(r.get("name", "span")), r.get("t0"),
               r.get("dur_ms", 0.0), "spans")
        n += 1
    return n


def _fold_ledger(b: _Builder, rows: List[Dict[str, Any]]
                 ) -> Tuple[int, int]:
    """Round records -> train lane; sweep records -> sweep lane per
    sub-fleet; watch notes -> instants. Returns (train_rows,
    sweep_rows)."""
    pid_t, pid_s = LANES["train"], LANES["sweep"]
    b.name_tid(pid_t, 0, "round loop")
    n_train = n_sweep = 0
    for r in rows:
        kind = r.get("kind")
        if kind == "round":
            args = {"path": r.get("path"),
                    "timing": r.get("timing", "residual")}
            if "terms_ms" in r:
                args["terms_ms"] = r["terms_ms"]
            if r.get("path") == "sweep":
                sid = int(r.get("subfleet", 0) or 0)
                b.name_tid(pid_s, sid, f"sub-fleet {sid}")
                name = f"round {r.get('round')}"
                if "model" in r:
                    name += f" m{r['model']}"
                b.span(pid_s, sid, name, r.get("t0"),
                       r.get("wall_ms", 0.0), "ledger", args)
                n_sweep += 1
            else:
                b.span(pid_t, 0, f"round {r.get('round')}", r.get("t0"),
                       r.get("wall_ms", 0.0), "ledger", args)
                n_train += 1
        elif kind == "note" and r.get("note") == "round_anomaly":
            b.instant(LANES["events"], 0, str(r["note"]), r.get("t0"),
                      "ledger.note",
                      {k: v for k, v in r.items()
                       if k not in ("kind", "note", "t0")})
    return n_train, n_sweep


def _fold_reqtrace(b: _Builder, rows: List[Dict[str, Any]]) -> int:
    pid = LANES["serving"]
    b.name_tid(pid, 0, "requests")
    n = 0
    for r in rows:
        if r.get("kind") != "request":
            continue
        args = {k: r.get(k) for k in
                ("trace_id", "model", "rows", "queue_wait_ms",
                 "flush_reason", "dispatch_ms", "status")
                if r.get(k) is not None}
        b.span(pid, 0, f"req {r.get('model', '?')}", r.get("t_submit"),
               r.get("total_ms", 0.0), "reqtrace", args)
        n += 1
    return n


def _fold_events(b: _Builder, rows: List[Dict[str, Any]]
                 ) -> Tuple[int, int]:
    """Tee'd structured events -> instants, with the ingest events
    additionally expanded into pipeline-wall spans (the parse and bin
    bars OVERLAP — they are thread totals, not exclusive segments).
    Returns (instants, ingest_spans)."""
    pid_e, pid_i = LANES["events"], LANES["ingest"]
    b.name_tid(pid_e, 0, "events")
    n_ev = n_ing = 0
    for r in rows:
        if r.get("kind") != "event":
            continue
        ev = str(r.get("event", "?"))
        t0 = r.get("t0")
        args = {k: v for k, v in r.items()
                if k not in ("kind", "event", "t0")}
        b.instant(pid_e, 0, ev, t0, "events", args)
        n_ev += 1
        if ev in ("stream_ingest", "dist_stream"):
            wall = r.get("wall_ms")
            if not isinstance(wall, (int, float)):
                continue
            # the event fires at ingest END unless the producer gave
            # an explicit start; the sub-bars start with the wall
            start = r.get("t_start")
            if not isinstance(start, (int, float)):
                start = (t0 - wall / 1e3
                         if isinstance(t0, (int, float)) else None)
            b.name_tid(pid_i, _TID_INGEST_WALL, "chunk wall")
            b.span(pid_i, _TID_INGEST_WALL, ev, start, wall,
                   "ingest", {"rows": r.get("rows")})
            n_ing += 1
            for key, tid, nm in (
                    ("parse_ms", _TID_INGEST_PARSE,
                     "parse (prefetch thread)"),
                    ("bin_ms", _TID_INGEST_BIN, "bin (device)")):
                ms = r.get(key)
                if isinstance(ms, (int, float)):
                    b.name_tid(pid_i, tid, nm)
                    b.span(pid_i, tid, key[:-3], start, ms, "ingest",
                           {"overlapped": True})
                    n_ing += 1
    return n_ev, n_ing


# ---------------------------------------------------------------------------
def build_timeline(trace_dir: Optional[str] = None,
                   ledger_path: Optional[str] = None
                   ) -> Dict[str, Any]:
    """The whole merge: collect streams, anchor the clock, fold every
    row into its lane. Returns the Chrome-trace document; inspect
    ``otherData.lanes`` for per-lane row counts (``has_data`` gates
    on them)."""
    streams = collect_streams(trace_dir, ledger_path)
    anchor = _find_anchor(streams)
    b = _Builder(anchor)
    n_spans = _fold_spans(b, streams["spans"])
    n_train, n_sweep = _fold_ledger(b, streams["ledger"])
    n_req = _fold_reqtrace(b, streams["reqtrace"])
    n_ev, n_ing = _fold_events(b, streams["events"])
    meta: List[Dict[str, Any]] = []
    lanes = {"spans": n_spans, "train": n_train, "sweep": n_sweep,
             "serving": n_req, "events": n_ev, "ingest": n_ing}
    for name, pid in LANES.items():
        if lanes.get(name):
            meta.extend(_meta(pid, name, b.tids.get(pid, {})))
    return {
        "traceEvents": meta + sorted(b.events,
                                     key=lambda e: e.get("ts", 0.0)),
        "displayTimeUnit": "ms",
        "otherData": {
            "schema": 1,
            "clock": "time.perf_counter (CLOCK_MONOTONIC)",
            "anchor_t0": anchor,
            "lanes": lanes,
        },
    }


def lane_counts(doc: Dict[str, Any]) -> Dict[str, int]:
    return dict(doc.get("otherData", {}).get("lanes", {}))


def has_data(doc: Dict[str, Any]) -> bool:
    """True iff any lane folded at least one source row."""
    return any(v > 0 for v in lane_counts(doc).values())


def write_timeline(path: str, doc: Dict[str, Any]) -> str:
    """Atomic write (tmp + rename), mirroring ``obs.trace.write``."""
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(doc, fh, sort_keys=True, default=str)
    os.replace(tmp, path)
    return path
