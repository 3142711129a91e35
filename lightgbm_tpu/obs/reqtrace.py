"""Request-scoped tracing for the serving plane: why was THIS request
slow, and which model is burning its SLO budget right now?

The metrics plane (obs/metrics.py) aggregates into histograms and the
round ledger (obs/ledger.py) records training rounds — neither can
answer a per-request question. This module is the missing layer: every
`RequestCoalescer.submit()` mints a trace ID whose span record
accumulates, across the request's whole life,

* queue wait (submit -> flusher pickup),
* the batch it rode in (id, flush reason full/deadline, rows, requests,
  padded-bucket fill ratio),
* the engine dispatch wall and its share of the request's total
  latency, and
* the total submit-to-result latency and outcome (the error path
  delivers a trace row too — a request that died in a failed batch is
  exactly the one worth reading about).

Finished records land in two places:

* a fixed-size in-memory **ring** (every record, oldest overwritten
  first) served live at the exporter's ``/debug/requests`` endpoint,
  interleaved with registry load/swap/evict **markers** so a slow
  request can be eyeballed against the hot swap that stalled it;
* a **tail-sampled JSONL stream** (``reqtrace-<pid>.jsonl``): requests
  breaching ``tpu_serve_slo_ms`` and errored requests are ALWAYS kept;
  a non-breaching request is kept when a deterministic hash of its
  trace ID falls under ``tpu_serve_trace_sample`` — no RNG, so the same
  traffic keeps the same rows on every run, and sample=0.0 is pure tail
  sampling. One row per line, flushed per line: a killed host keeps
  every finished request so far.

On top of the stream sit the aggregate SLO signals ROADMAP item 4's
load-shedder will consume, registered in the PR-8 metrics registry when
the plane is on: per-model ``serve_slo_burn_rate`` gauges (rolling
bad/total ratio over the last `_BURN_WINDOW` outcomes vs the SLO),
``serve_slo_breaches_total`` counters, a rate-limited
``serve_request_slow`` event per breach burst, and an edge-triggered
``serve_slo_burn`` event when a model's burn rate crosses the high
watermark.

Zero-overhead-off discipline (same as obs/trace.py): the coalescer
holds a tracer handle that is ``None`` when ``tpu_serve_trace`` is off,
so the disabled hot path pays one is-None branch and zero device
fences — tier-1 asserted in tests/test_reqtrace.py.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from ..utils import locks, log

__all__ = ["TraceSpan", "RequestTracer",
           "SLO_BURN_HIGH", "SLO_BURN_CLEAR"]

# rolling per-model outcome window feeding the burn gauge
_BURN_WINDOW = 256
# burn-rate hysteresis: serve_slo_burn fires crossing HIGH upward (with
# at least _BURN_MIN_N outcomes observed) and re-arms below CLEAR
SLO_BURN_HIGH = 0.5
SLO_BURN_CLEAR = 0.25
_BURN_MIN_N = 16
# serve_request_slow is a rate-limited POINTER (at most one per model
# per this interval) — the full span is in the ring/JSONL
_SLOW_EVENT_INTERVAL_S = 1.0


class TraceSpan:
    """One request's span record. Minted by `RequestTracer.start` at
    submit time; the coalescer's flusher fills the batch-side fields via
    `RequestTracer.finish` exactly once, success or failure."""

    __slots__ = ("trace_id", "model", "rows", "ts", "t_submit",
                 "queue_wait_ms", "batch_id", "flush_reason",
                 "batch_rows", "batch_requests", "fill_ratio",
                 "dispatch_ms", "dispatch_share", "total_ms", "status",
                 "error", "slo_breach", "kept")

    def __init__(self, trace_id: str, model: str, rows: int,
                 t_submit: float) -> None:
        self.trace_id = trace_id
        self.model = model
        self.rows = rows
        self.ts = time.time()            # epoch at submit (reporting)
        self.t_submit = t_submit         # perf_counter at submit
        self.queue_wait_ms: Optional[float] = None
        self.batch_id: Optional[str] = None
        self.flush_reason: Optional[str] = None
        self.batch_rows: Optional[int] = None
        self.batch_requests: Optional[int] = None
        self.fill_ratio: Optional[float] = None
        self.dispatch_ms: Optional[float] = None
        self.dispatch_share: Optional[float] = None
        self.total_ms: Optional[float] = None
        self.status = "pending"
        self.error: Optional[str] = None
        self.slo_breach = False
        self.kept = False

    def row(self) -> Dict[str, Any]:
        """The span as one JSON-able trace row."""
        r3 = lambda v: None if v is None else round(v, 3)  # noqa: E731
        return {
            "kind": "request", "trace_id": self.trace_id,
            "model": self.model, "rows": self.rows,
            "ts": round(self.ts, 6),
            # monotonic submit time: what the unified timeline
            # (obs/timeline.py) joins on — epoch ts is reporting-only
            "t_submit": round(self.t_submit, 6),
            "queue_wait_ms": r3(self.queue_wait_ms),
            "batch_id": self.batch_id,
            "flush_reason": self.flush_reason,
            "batch_rows": self.batch_rows,
            "batch_requests": self.batch_requests,
            "fill_ratio": None if self.fill_ratio is None
            else round(self.fill_ratio, 4),
            "dispatch_ms": r3(self.dispatch_ms),
            "dispatch_share": None if self.dispatch_share is None
            else round(self.dispatch_share, 4),
            "total_ms": r3(self.total_ms),
            "status": self.status, "error": self.error,
            "slo_breach": self.slo_breach, "kept": self.kept,
        }


def _sample_keep(trace_id: str, sample: float) -> bool:
    """Deterministic head-sampling decision: hash the trace ID into
    [0, 1) and keep when under `sample`. No RNG — replayable, and
    test-assertable without seeding anything."""
    if sample >= 1.0:
        return True
    if sample <= 0.0:
        return False
    h = hashlib.sha1(trace_id.encode()).digest()
    frac = int.from_bytes(h[:8], "big") / float(1 << 64)
    return frac < sample


@locks.guarded
class RequestTracer:
    """Ring + tail-sampled JSONL + SLO burn accounting for one serving
    host. Thread-safe; every method is a leaf with respect to the
    serving locks (the coalescer/registry may call in while holding
    their own locks, never vice versa)."""

    def __init__(self, slo_ms: float = 0.0, sample: float = 0.0,
                 ring_size: int = 512, out_dir: str = "") -> None:
        self.slo_ms = max(float(slo_ms), 0.0)
        self.sample = min(max(float(sample), 0.0), 1.0)
        self.ring_size = max(int(ring_size), 1)
        self._lock = threading.Lock()
        self._ring: List[Optional[Dict[str, Any]]] = \
            [None] * self.ring_size                 # guarded-by: _lock
        self._ring_next = 0                         # guarded-by: _lock
        self._seq = 0                               # guarded-by: _lock
        self._batch_seq = 0                         # guarded-by: _lock
        self.started = 0                            # guarded-by: _lock
        self.finished = 0                           # guarded-by: _lock
        self.breaches = 0                           # guarded-by: _lock
        self.errors = 0                             # guarded-by: _lock
        self.kept_rows = 0                          # guarded-by: _lock
        self.markers = 0                            # guarded-by: _lock
        self._burn: Dict[str, deque] = {}           # guarded-by: _lock
        self._burn_high: Dict[str, bool] = {}       # guarded-by: _lock
        self._last_slow_emit: Dict[str, float] = {}  # guarded-by: _lock
        self._closed = False                        # guarded-by: _lock
        self.path: Optional[str] = None
        fh = None
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            self.path = os.path.join(out_dir,
                                     f"reqtrace-{os.getpid()}.jsonl")
            fh = open(self.path, "a")
            fh.write(json.dumps(
                {"kind": "header", "pid": os.getpid(),
                 "ts": round(time.time(), 6), "slo_ms": self.slo_ms,
                 "sample": self.sample, "ring_size": self.ring_size},
                sort_keys=True) + "\n")
            fh.flush()
        self._fh = fh                               # guarded-by: _lock
        # live SLO instruments: resolved once, None when the metrics
        # plane is off (finish then skips the registry entirely)
        from . import metrics as obs_metrics
        self._metrics = (obs_metrics.serving_instruments()
                         if obs_metrics.enabled() else None)

    # -- span lifecycle ----------------------------------------------------
    def start(self, model: str, rows: int,
              t_submit: Optional[float] = None) -> TraceSpan:
        """Mint a trace ID + span at submit time (called by
        `RequestCoalescer.submit` under its condition lock; this lock is
        a leaf below it)."""
        with self._lock:
            self._seq += 1
            self.started += 1
            trace_id = f"r{os.getpid():05d}-{self._seq:08d}"
        return TraceSpan(trace_id, model, int(rows),
                         time.perf_counter() if t_submit is None
                         else t_submit)

    def next_batch_id(self) -> str:
        with self._lock:
            self._batch_seq += 1
            return f"b{self._batch_seq:06d}"

    def finish(self, span: TraceSpan, *, queue_wait_ms: float,
               batch_id: Optional[str], flush_reason: str,
               batch_rows: Optional[int], batch_requests: Optional[int],
               fill_ratio: Optional[float], dispatch_ms: Optional[float],
               total_ms: float, status: str = "ok",
               error: Optional[str] = None) -> Dict[str, Any]:
        """Complete one span exactly once: ring insert, burn update,
        sampling decision, JSONL append. Returns the trace row."""
        span.queue_wait_ms = queue_wait_ms
        span.batch_id = batch_id
        span.flush_reason = flush_reason
        span.batch_rows = batch_rows
        span.batch_requests = batch_requests
        span.fill_ratio = fill_ratio
        span.dispatch_ms = dispatch_ms
        span.total_ms = total_ms
        if dispatch_ms is not None and total_ms > 0:
            span.dispatch_share = min(dispatch_ms / total_ms, 1.0)
        span.status = status
        span.error = error
        bad = status != "ok"
        breach = self.slo_ms > 0 and total_ms > self.slo_ms
        span.slo_breach = breach
        span.kept = (breach or bad
                     or _sample_keep(span.trace_id, self.sample))
        row = span.row()
        slow_fields = None
        burn_fields = None
        burn_rate = None
        with self._lock:
            self.finished += 1
            if breach:
                self.breaches += 1
            if bad:
                self.errors += 1
            if span.kept:
                self.kept_rows += 1
                if self._fh is not None and not self._closed:
                    self._fh.write(json.dumps(row, sort_keys=True) + "\n")
                    self._fh.flush()
            self._ring[self._ring_next % self.ring_size] = row
            self._ring_next += 1
            if self.slo_ms > 0:
                win = self._burn.setdefault(
                    span.model, deque(maxlen=_BURN_WINDOW))
                win.append(bool(breach or bad))
                burn_rate = sum(win) / len(win)
                if breach:
                    now = time.monotonic()
                    last = self._last_slow_emit.get(span.model, -1e18)
                    if now - last >= _SLOW_EVENT_INTERVAL_S:
                        self._last_slow_emit[span.model] = now
                        slow_fields = {
                            "trace_id": span.trace_id,
                            "model": span.model,
                            "total_ms": row["total_ms"],
                            "queue_wait_ms": row["queue_wait_ms"],
                            "dispatch_ms": row["dispatch_ms"],
                            "flush_reason": flush_reason,
                            "slo_ms": self.slo_ms,
                        }
                high = self._burn_high.get(span.model, False)
                if not high and burn_rate >= SLO_BURN_HIGH \
                        and len(win) >= _BURN_MIN_N:
                    self._burn_high[span.model] = True
                    burn_fields = {"model": span.model,
                                   "burn_rate": round(burn_rate, 4),
                                   "window": len(win),
                                   "slo_ms": self.slo_ms}
                elif high and burn_rate <= SLO_BURN_CLEAR:
                    self._burn_high[span.model] = False
        # events + metrics OUTSIDE the tracer lock (leaf discipline:
        # the metrics instruments take their own locks)
        m = self._metrics
        if m is not None and burn_rate is not None:
            if breach:
                m.slo_breaches.labels(model=span.model).inc()
            m.slo_burn.labels(model=span.model).set(burn_rate)
        if slow_fields is not None:
            log.event("serve_request_slow", **slow_fields)
        if burn_fields is not None:
            log.event("serve_slo_burn", **burn_fields)
        return row

    # -- markers -----------------------------------------------------------
    def note(self, kind: str, **fields: Any) -> None:
        """Interleave a serving-plane event (load/swap/evict/bad-model)
        into the ring + stream so /debug/requests and trace_report can
        correlate request latency with registry churn. The caller has
        already emitted the catalogued log.event — this is the ring's
        copy, not a second event."""
        row = dict({"kind": "marker", "marker": kind,
                    "ts": round(time.time(), 6)}, **fields)
        with self._lock:
            if self._closed:
                return
            self.markers += 1
            self._ring[self._ring_next % self.ring_size] = row
            self._ring_next += 1
            if self._fh is not None:
                self._fh.write(json.dumps(row, sort_keys=True,
                                          default=str) + "\n")
                self._fh.flush()

    # -- views -------------------------------------------------------------
    def recent(self, n: Optional[int] = None) -> List[Dict[str, Any]]:
        """Ring contents oldest -> newest (requests + markers)."""
        with self._lock:
            total = self._ring_next
            size = self.ring_size
            start = max(total - size, 0)
            out = [self._ring[i % size] for i in range(start, total)]
        if n is not None:
            out = out[-n:]
        return [r for r in out if r is not None]

    def slow_requests(self, n: int = 20) -> List[Dict[str, Any]]:
        """Slowest request rows still in the ring, worst first."""
        rows = [r for r in self.recent() if r.get("kind") == "request"]
        rows.sort(key=lambda r: -(r.get("total_ms") or 0.0))
        return rows[:n]

    def burn_rates(self) -> Dict[str, float]:
        with self._lock:
            return {m: round(sum(w) / len(w), 4)
                    for m, w in self._burn.items() if w}

    def totals(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "started": self.started, "finished": self.finished,
                "breaches": self.breaches, "errors": self.errors,
                "kept_rows": self.kept_rows, "markers": self.markers,
                "slo_ms": self.slo_ms, "sample": self.sample,
                "ring_size": self.ring_size, "path": self.path,
            }

    def snapshot(self, slow_n: int = 20) -> Dict[str, Any]:
        """The /debug/requests document."""
        return {"schema": 1, "totals": self.totals(),
                "burn_rates": self.burn_rates(),
                "recent": self.recent(),
                "slow": self.slow_requests(slow_n)}

    # -- lifecycle ---------------------------------------------------------
    def close(self) -> None:
        """Flush + close the stream and emit the `serve_trace_dump`
        summary event. Idempotent; the ring stays readable after."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            if self._fh is not None:
                self._fh.close()
                self._fh = None
            fields = {"requests": self.finished,
                      "kept_rows": self.kept_rows,
                      "breaches": self.breaches, "errors": self.errors,
                      "markers": self.markers, "path": self.path}
        log.event("serve_trace_dump", **fields)

    def __enter__(self) -> "RequestTracer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
