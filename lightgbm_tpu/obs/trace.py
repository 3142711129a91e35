"""Low-overhead span tracer: nested host spans, device-time fences, and
`jax.profiler` annotations, all gated on a single module-level flag.

Design constraints (ISSUE 4):

- Disabled cost is NIL. `span()` hands back one shared null context
  (no allocation), `fence()` returns its argument untouched (jax is not
  even imported), and callers guard everything else behind
  ``trace.enabled()``.
- Device time is only observable at a fence. ``fence(x)`` calls
  ``jax.block_until_ready`` on the pytree ONLY while tracing is on and
  counts every such call in ``fence_count`` — the tier-1 zero-fence test
  monkeypatches ``_block`` with a counting wrapper and asserts it never
  fires on an untraced run.
- Spans also enter XLA profiles: each span wraps a
  ``jax.profiler.TraceAnnotation`` and the round loop wraps each round
  in ``jax.profiler.StepTraceAnnotation`` (via ``step()``), so attaching
  the jax profiler to a traced run yields named regions for free.

Completed spans accumulate in memory and — when a trace directory is
configured — append to ``<dir>/spans-<pid>.jsonl`` one JSON record per
span, flushed per line so a killed process keeps everything closed so
far.

SEAMS (ISSUE 25) are the other half: a handful of host boundaries
(``seam()``) that record ALWAYS, whatever ``tpu_trace`` says, into one
bounded ring (``seams()``), and always enter a
``jax.profiler.TraceAnnotation`` so a live profiler session sees them
on the device operations' clock. ``part()`` names a stretch INSIDE a
seam the same way, without a record of its own. A seam never fences and
never touches ``_block``: turning nothing on, it changes no program and
no pipeline.
``span`` / ``fence`` / ``tpu_trace`` stay the operator's FENCED mode.
"""
from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time
from typing import Any, Deque, Dict, List, Optional

fence_count = 0          # fences issued while tracing (test probe)
_enabled = False
_dir: Optional[str] = None
_fh = None
_spans: List[Dict[str, Any]] = []
_depth = 0
_lock = threading.Lock()
_block = None            # resolved lazily to jax.block_until_ready
_efh = None              # events-<pid>.jsonl tee (timeline join)
SEAM_RING = 8192         # seam records kept (oldest dropped first)
_seams: Deque[Dict[str, Any]] = collections.deque(maxlen=SEAM_RING)
_seam_ids = itertools.count(1)
_seam_open = threading.local()   # .stack: this thread's open seams


def enabled() -> bool:
    return _enabled


def enable(trace_dir: Optional[str] = None) -> None:
    """Turn the tracer on, optionally appending span JSONL under
    `trace_dir` (created if missing). Idempotent; a later call with a
    directory upgrades a memory-only tracer to a file-backed one."""
    global _enabled, _dir, _fh, _efh
    with _lock:
        _enabled = True
        if trace_dir and trace_dir != _dir:
            if _fh is not None:
                _fh.close()
            if _efh is not None:
                _efh.close()
                _efh = None
            os.makedirs(trace_dir, exist_ok=True)
            _dir = trace_dir
            _fh = open(os.path.join(trace_dir,
                                    f"spans-{os.getpid()}.jsonl"), "a")


def disable() -> None:
    global _enabled, _fh, _dir, _efh
    with _lock:
        _enabled = False
        if _fh is not None:
            _fh.close()
            _fh = None
        if _efh is not None:
            _efh.close()
            _efh = None
        _dir = None


def tee_event(kind: str, fields: Dict[str, Any]) -> None:
    """Mirror one structured event (utils/log.event) into
    ``<dir>/events-<pid>.jsonl``, stamped with a monotonic ``t0`` so
    the timeline (obs/timeline.py) can place compile-cache misses,
    straggler raises, ingest completions etc. on the run's shared
    clock. No-op unless a file-backed trace directory is configured —
    the untraced path pays one bool check in utils/log.event and never
    reaches here."""
    global _efh
    if not _enabled or _dir is None:
        return
    rec = {"kind": "event", "event": kind, "t0": time.perf_counter()}
    rec.update(fields)
    with _lock:
        if _dir is None:
            return
        if _efh is None:
            _efh = open(os.path.join(_dir,
                                     f"events-{os.getpid()}.jsonl"),
                        "a")
        _efh.write(json.dumps(rec, sort_keys=True, default=str) + "\n")
        _efh.flush()


def reset() -> None:
    """Clear accumulated spans, seams and the fence counter (tests)."""
    global fence_count
    with _lock:
        _spans.clear()
        _seams.clear()
        fence_count = 0


def forget_seams_since(t: float) -> int:
    """Take the seam records that began at or after `t` (on
    ``time.perf_counter``) out of the ring again; returns how many. For a
    check that drives the training path AFTER a measured window (the
    benchmark's `binary_goss` task makes one more `update()`): a seam
    always records, so the check's own iteration would be the ring's
    newest, where the window's readers look for the window."""
    with _lock:
        keep = [r for r in _seams if r["t0"] < t]
        dropped = len(_seams) - len(keep)
        _seams.clear()
        _seams.extend(keep)
    return dropped


def spans() -> List[Dict[str, Any]]:
    """Completed span records, in completion order."""
    return list(_spans)


def trace_dir() -> Optional[str]:
    return _dir


class _NullSpan:
    """Shared do-nothing context for the disabled path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


def _profiler_annotation(name: str):
    """A jax.profiler.TraceAnnotation when the profiler is importable;
    None otherwise (the tracer must not force a jax import ordering)."""
    try:
        from jax import profiler
        return profiler.TraceAnnotation(name)
    except Exception:
        return None


class _Span:
    __slots__ = ("name", "attrs", "t0", "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.t0 = 0.0
        self._ann = None

    def __enter__(self):
        global _depth
        self._ann = _profiler_annotation(self.name)
        if self._ann is not None:
            self._ann.__enter__()
        with _lock:
            _depth += 1
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        global _depth
        dur = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        rec = {"kind": "span", "name": self.name, "t0": self.t0,
               "dur_ms": round(dur * 1e3, 4)}
        if self.attrs:
            rec.update(self.attrs)
        with _lock:
            _depth -= 1
            rec["depth"] = _depth
            _spans.append(rec)
            if _fh is not None:
                _fh.write(json.dumps(rec, sort_keys=True, default=str)
                          + "\n")
                _fh.flush()
        return False


def _keep_seam(rec: Dict[str, Any]) -> None:
    """Into the ring; and, while the fenced tracer is on, into its span
    list and file in a span's shape (`dur_ms`, `depth`), so its summary
    and the timeline keep the boundaries that became seams."""
    with _lock:
        _seams.append(rec)
        if not _enabled:
            return
        as_span = dict(rec, kind="span", depth=_depth,
                       dur_ms=round((rec["t1"] - rec["t0"]) * 1e3, 4))
        _spans.append(as_span)
        if _fh is not None:
            _fh.write(json.dumps(as_span, sort_keys=True, default=str)
                      + "\n")
            _fh.flush()


def _seam_rec(name: str, it: Optional[int], t: float) -> Dict[str, Any]:
    return {"kind": "seam", "name": name, "id": next(_seam_ids),
            "parent": None, "iter": it, "t0": t, "t1": t}


class _Seam:
    """One open seam. ``attrs`` may be filled while it is open (sizes
    that are only known at the end of the region)."""
    __slots__ = ("rec", "attrs", "_ann")

    def __init__(self, name: str, it: Optional[int],
                 attrs: Dict[str, Any]):
        self.rec = _seam_rec(name, it, 0.0)
        self.attrs = attrs
        self._ann = None

    def __enter__(self):
        stack = getattr(_seam_open, "stack", None)
        if stack is None:
            stack = _seam_open.stack = []
        if stack:
            up = stack[-1].rec
            self.rec["parent"] = up["id"]
            if self.rec["iter"] is None:
                self.rec["iter"] = up["iter"]
        stack.append(self)
        self._ann = _profiler_annotation(self.rec["name"])
        if self._ann is not None:
            self._ann.__enter__()
        self.rec["t0"] = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.rec["t1"] = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _seam_open.stack.pop()
        self.rec.update(self.attrs)
        _keep_seam(self.rec)
        return False


def seam(name: str, iter: Optional[int] = None, **attrs) -> _Seam:
    """Context manager over one host boundary of the training path.
    ALWAYS on: the record (``name``, ``id``, ``parent`` = the enclosing
    seam's id, ``iter`` = the boosting iteration it belongs to, inherited
    from the enclosing seam when not given, ``t0``/``t1`` on
    ``time.perf_counter``, plus ``attrs``) goes to the ring behind
    ``seams()``, and, while the fenced tracer is on, to ``spans()`` and
    ``spans-<pid>.jsonl`` as well. It enters a
    ``jax.profiler.TraceAnnotation`` of the same name, which is an atomic
    load while no profiler session is live. It never fences: placing one
    changes no program and no pipeline."""
    return _Seam(name, iter, attrs)


class _Part:
    """One open part of the seam this thread is in."""
    __slots__ = ("name", "t0", "_ann")

    def __init__(self, name: str):
        self.name = name
        self.t0 = 0.0
        self._ann = None

    def __enter__(self):
        self._ann = _profiler_annotation(self.name)
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        stack = getattr(_seam_open, "stack", None)
        if stack:
            parts = stack[-1].attrs.setdefault("parts", {})
            parts[self.name] = parts.get(self.name, 0.0) + dur
        return False


def part(name: str) -> _Part:
    """Context manager over a named PART of the seam the thread is in:
    like a seam it enters a ``jax.profiler.TraceAnnotation`` of its name
    (so a profiler session sees it nested in the seam's event, on the
    device's clock) and never fences; unlike one it makes no record of
    its own. Its seconds are summed into the enclosing seam's record
    under ``parts[name]``, and outside any seam it records nothing. For
    naming where inside a seam the time goes without adding a name to
    the ring, whose readers take a window's set of names for what the
    host did in it."""
    return _Part(name)


def seam_record(name: str, iter: Optional[int] = None, **attrs) -> None:
    """A seam with no extent: one record at now (``t0 == t1``), for
    facts the host learns at a point, such as an iteration's counters
    arriving with a flag pull."""
    rec = _seam_rec(name, iter, time.perf_counter())
    stack = getattr(_seam_open, "stack", None)
    if stack:
        rec["parent"] = stack[-1].rec["id"]
    rec.update(attrs)
    _keep_seam(rec)


def seams(name: Optional[str] = None) -> List[Dict[str, Any]]:
    """The ring's seam records in completion order (an enclosing seam
    after the seams it encloses), all or those called ``name``."""
    with _lock:
        out = list(_seams)
    return out if name is None else [r for r in out if r["name"] == name]


def span(name: str, **attrs):
    """Context manager timing a named region. Free when tracing is off
    (returns one shared null context, no allocation)."""
    if not _enabled:
        return _NULL
    return _Span(name, attrs)


def step(step_num: int):
    """Round boundary: wraps `jax.profiler.StepTraceAnnotation` so XLA
    profiles group work per boosting round. Null context when off."""
    if not _enabled:
        return _NULL
    try:
        from jax import profiler
        return profiler.StepTraceAnnotation("train_round",
                                            step_num=step_num)
    except Exception:
        return _NULL


def fence(x):
    """Drain device work hanging off pytree `x` — ONLY while tracing.

    Disabled: returns `x` untouched without importing jax (this is the
    round loop's guarantee of zero added fences). Enabled: blocks until
    every jax array leaf is ready and bumps `fence_count`.
    """
    global fence_count, _block
    if not _enabled:
        return x
    if _block is None:
        import jax
        _block = jax.block_until_ready
    fence_count += 1
    return _block(x)


def force_fence(x):
    """Drain device work hanging off pytree `x` REGARDLESS of the
    tracing flag: what a tool that times a kernel by hand fences with
    (tools/route_tile_sweep.py). Shares `_block` and `fence_count` with
    `fence()` so the tier-1 zero-fence assertion (monkeypatching
    `_block`) covers it too: a training run must never reach here."""
    global fence_count, _block
    if _block is None:
        import jax
        _block = jax.block_until_ready
    fence_count += 1
    return _block(x)


def write(path: str, extra: Optional[Dict[str, Any]] = None) -> str:
    """Dump all completed spans (plus a summary header) to `path` as one
    JSON document — the CLI's end-of-training trace dump. `extra` keys
    merge into the top level (the CLI folds compile-cache hit/miss
    totals and per-program miss attribution in here)."""
    by_name: Dict[str, Dict[str, float]] = {}
    for s in _spans:
        agg = by_name.setdefault(s["name"], {"count": 0, "total_ms": 0.0})
        agg["count"] += 1
        agg["total_ms"] = round(agg["total_ms"] + s["dur_ms"], 4)
    doc = {"pid": os.getpid(), "fences": fence_count,
           "summary": by_name, "spans": _spans}
    if extra:
        doc.update(extra)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(doc, fh, sort_keys=True, default=str)
    os.replace(tmp, path)
    return path
