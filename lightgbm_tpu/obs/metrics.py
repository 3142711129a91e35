"""Process-wide metrics plane: counters, gauges, and latency histograms
behind one thread-safe registry, scrapeable while the process runs.

The one-shot artifacts (span JSONL, round ledger) answer "what
happened"; this module answers "what is happening" — the serving
exporter (`serving/exporter.py`) renders the same registry as Prometheus
text on every scrape and `bst.metrics_snapshot()` returns it as a dict.

Design constraints (same discipline as `obs/trace.py`):

- Disabled cost is NIL on the hot paths. Instruments are plain Python
  ints/floats behind a lock — no jax import, no device fences — and the
  GBDT round loop / serving flusher hold a pre-resolved handle that is
  ``None`` when off, so the per-round cost of the default path is one
  attribute check.
- Histograms use fixed log2 bucket bounds in milliseconds
  (2^-6 .. 2^14 ms), so p50/p99 estimates come from bucket
  interpolation with no per-observation allocation.
- ``snapshot()`` emits a versioned schema (``SCHEMA_VERSION``) so the
  CI scrape can validate shape, not just presence.

Labeled families: ``registry().counter(name, help, labelnames=("model",))``
returns a family whose ``labels(model="ctr")`` child is created on first
use and cached — label cardinality is the caller's responsibility.
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..utils import locks

__all__ = ["SCHEMA_VERSION", "Counter", "Gauge", "Histogram",
           "MetricsRegistry", "registry", "enable", "disable", "enabled",
           "reset", "snapshot", "to_prometheus", "train_instruments",
           "serving_instruments", "note_retry_event"]

SCHEMA_VERSION = 1

# log2 latency bucket upper bounds in milliseconds: 0.015625 ms .. 16.4 s,
# plus +Inf. Fixed (not configurable) so histograms from any two
# processes/stages merge bucket-for-bucket.
BUCKET_BOUNDS_MS: Tuple[float, ...] = tuple(2.0 ** e for e in range(-6, 15))

_enabled = False
_lock = threading.Lock()


def enabled() -> bool:
    return _enabled


def enable() -> None:
    """Turn the metrics plane on (idempotent). Instrument handles held
    by hot paths are resolved at construction time (GBDT.__init__,
    ServingService.__init__), so enable BEFORE building the object that
    should feed the registry."""
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def _label_key(labelnames: Sequence[str],
               labels: Dict[str, str]) -> Tuple[str, ...]:
    if sorted(labels) != sorted(labelnames):
        raise ValueError(f"labels {sorted(labels)} != declared "
                         f"{sorted(labelnames)}")
    return tuple(str(labels[n]) for n in labelnames)


def _fmt_labels(labelnames: Sequence[str], key: Tuple[str, ...]) -> str:
    if not labelnames:
        return ""
    inner = ",".join(f'{n}="{v}"' for n, v in zip(labelnames, key))
    return "{" + inner + "}"


@locks.guarded
class Counter:
    """Monotone float counter. `inc` only — a decrement is a bug."""

    __slots__ = ("name", "help", "_value", "_lock")

    def __init__(self, name: str, help: str = "") -> None:
        self.name = name
        self.help = help
        self._value = 0.0                           # guarded-by: _lock
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        if n < 0:
            raise ValueError(f"counter {self.name} cannot decrease")
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value


@locks.guarded
class Gauge:
    """Point-in-time value; optionally backed by a callback (`set_fn`)
    read at snapshot/scrape time — how the HBM accountant exposes live
    occupancy without a sampling thread."""

    __slots__ = ("name", "help", "_value", "_fn", "_lock")

    def __init__(self, name: str, help: str = "") -> None:
        self.name = name
        self.help = help
        self._value = 0.0                           # guarded-by: _lock
        self._fn: Optional[Callable[[], float]] = None  # guarded-by: _lock
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)
            self._fn = None

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    def set_fn(self, fn: Callable[[], float]) -> None:
        with self._lock:
            self._fn = fn

    @property
    def value(self) -> float:
        fn = self._fn
        if fn is not None:
            try:
                return float(fn())
            except Exception:
                return float("nan")
        return self._value


@locks.guarded
class Histogram:
    """Fixed log2-bucket latency histogram (milliseconds).

    `observe(ms)` is one bisect + two adds under a lock; `quantile(q)`
    interpolates linearly inside the covering bucket (the standard
    Prometheus `histogram_quantile` estimate), so p50/p99 are available
    host-side without retaining observations.

    Exemplars: `observe(ms, exemplar="r...-...")` stamps the bucket the
    observation lands in with that trace ID (last write wins per
    bucket), so "what is p99" comes with "here is a request AT p99" —
    the join key into the request-trace ring/JSONL (obs/reqtrace.py).
    Cost without an exemplar is one extra is-None check.
    """

    __slots__ = ("name", "help", "bounds", "_counts", "_sum", "_count",
                 "_exemplars", "_lock")

    def __init__(self, name: str, help: str = "",
                 bounds: Sequence[float] = BUCKET_BOUNDS_MS) -> None:
        self.name = name
        self.help = help
        self.bounds = tuple(float(b) for b in bounds)
        # last slot = +Inf
        self._counts = [0] * (len(self.bounds) + 1)  # guarded-by: _lock
        self._sum = 0.0                               # guarded-by: _lock
        self._count = 0                               # guarded-by: _lock
        # bucket index -> (exemplar_id, value_ms), last write wins
        self._exemplars: Dict[int, Tuple[str, float]] = {}  # guarded-by: _lock
        self._lock = threading.Lock()

    def observe(self, ms: float, exemplar: Optional[str] = None) -> None:
        ms = float(ms)
        import bisect
        i = bisect.bisect_left(self.bounds, ms)
        with self._lock:
            self._counts[i] += 1
            self._sum += ms
            self._count += 1
            if exemplar is not None:
                self._exemplars[i] = (str(exemplar), ms)

    def _le_key(self, i: int) -> str:
        """JSON bucket key for bucket index `i` — same convention as
        snapshot()'s cumulative-bucket keys."""
        return "+Inf" if i >= len(self.bounds) else repr(self.bounds[i])

    def exemplars(self) -> Dict[str, Dict[str, Any]]:
        """{le_key: {trace_id, value_ms}} for buckets with an exemplar."""
        with self._lock:
            items = sorted(self._exemplars.items())
        return {self._le_key(i): {"trace_id": tid, "value_ms": round(v, 4)}
                for i, (tid, v) in items}

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def cumulative(self) -> List[Tuple[float, int]]:
        """[(le_bound, cumulative_count)] including (+Inf, total)."""
        out, acc = [], 0
        with self._lock:
            counts = list(self._counts)
        for b, c in zip(self.bounds, counts):
            acc += c
            out.append((b, acc))
        out.append((float("inf"), acc + counts[-1]))
        return out

    def quantile(self, q: float) -> Optional[float]:
        """Estimated q-quantile in ms; None with no observations. The
        +Inf bucket clamps to the largest finite bound."""
        cum = self.cumulative()
        total = cum[-1][1]
        if total == 0:
            return None
        target = q * total
        lo = 0.0
        prev_cum = 0
        for b, c in cum:
            if c >= target:
                if b == float("inf"):
                    return self.bounds[-1]
                span = c - prev_cum
                frac = (target - prev_cum) / span if span else 1.0
                return lo + (b - lo) * frac
            lo, prev_cum = b, c
        return self.bounds[-1]


@locks.guarded
class _Family:
    """Labeled instrument family: children cached per label-value tuple."""

    __slots__ = ("name", "help", "labelnames", "_cls", "_children", "_lock")

    def __init__(self, cls, name: str, help: str,
                 labelnames: Sequence[str]) -> None:
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._cls = cls
        self._children: Dict[Tuple[str, ...], Any] = {}  # guarded-by: _lock
        self._lock = threading.Lock()

    def labels(self, **labels) -> Any:
        key = _label_key(self.labelnames, labels)
        child = self._children.get(key)
        if child is None:
            with self._lock:
                child = self._children.get(key)
                if child is None:
                    child = self._cls(self.name, self.help)
                    self._children[key] = child
        return child

    def children(self) -> Dict[Tuple[str, ...], Any]:
        with self._lock:
            return dict(self._children)


_KIND = {Counter: "counter", Gauge: "gauge", Histogram: "histogram"}


@locks.guarded
class MetricsRegistry:
    """Ordered name -> instrument/family map with get-or-create semantics
    (re-declaring the same name with the same type returns the existing
    instrument; a type change raises)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._entries: Dict[str, Any] = {}          # guarded-by: _lock

    def _get_or_create(self, cls, name: str, help: str,
                       labelnames: Sequence[str]):
        with self._lock:
            ent = self._entries.get(name)
            if ent is not None:
                want = cls if not labelnames else _Family
                got_cls = ent._cls if isinstance(ent, _Family) else type(ent)
                if got_cls is not cls or isinstance(ent, _Family) != bool(
                        labelnames):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{_KIND.get(got_cls, got_cls)}"
                        f"{' family' if isinstance(ent, _Family) else ''}, "
                        f"not {want}")
                return ent
            ent = (_Family(cls, name, help, labelnames) if labelnames
                   else cls(name, help))
            self._entries[name] = ent
            return ent

    def counter(self, name: str, help: str = "",
                labelnames: Sequence[str] = ()) -> Any:
        return self._get_or_create(Counter, name, help, labelnames)

    def gauge(self, name: str, help: str = "",
              labelnames: Sequence[str] = ()) -> Any:
        return self._get_or_create(Gauge, name, help, labelnames)

    def histogram(self, name: str, help: str = "",
                  labelnames: Sequence[str] = ()) -> Any:
        return self._get_or_create(Histogram, name, help, labelnames)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    # -- export ------------------------------------------------------------
    def _items(self) -> List[Tuple[str, Any]]:
        with self._lock:
            return list(self._entries.items())

    @staticmethod
    def _each(ent) -> List[Tuple[str, Any]]:
        """(label_suffix, instrument) pairs for one entry."""
        if isinstance(ent, _Family):
            return [(_fmt_labels(ent.labelnames, key), child)
                    for key, child in sorted(ent.children().items())]
        return [("", ent)]

    def snapshot(self) -> Dict[str, Any]:
        """Versioned dict of everything: counters/gauges as scalars,
        histograms as {count, sum_ms, p50_ms, p99_ms, buckets} with
        cumulative bucket counts keyed by the le bound."""
        counters: Dict[str, float] = {}
        gauges: Dict[str, float] = {}
        hists: Dict[str, Any] = {}
        for name, ent in self._items():
            for suffix, inst in self._each(ent):
                key = name + suffix
                if isinstance(inst, Counter):
                    counters[key] = inst.value
                elif isinstance(inst, Gauge):
                    gauges[key] = inst.value
                else:
                    hists[key] = {
                        "count": inst.count,
                        "sum_ms": round(inst.sum, 4),
                        "p50_ms": inst.quantile(0.50),
                        "p99_ms": inst.quantile(0.99),
                        "buckets": {("+Inf" if b == float("inf")
                                     else repr(b)): c
                                    for b, c in inst.cumulative()},
                    }
                    ex = inst.exemplars()
                    if ex:
                        hists[key]["exemplars"] = ex
        return {"schema": SCHEMA_VERSION, "counters": counters,
                "gauges": gauges, "histograms": hists}

    def to_prometheus(self) -> str:
        """Prometheus text exposition format v0.0.4. Histograms emit the
        standard _bucket/_sum/_count series plus _p50/_p99 gauges
        (bucket-interpolated) so a plain curl shows tail latency without
        a query engine."""
        lines: List[str] = []
        for name, ent in self._items():
            kind = _KIND[ent._cls if isinstance(ent, _Family)
                         else type(ent)]
            help_ = ent.help
            if help_:
                lines.append(f"# HELP {name} {help_}")
            lines.append(f"# TYPE {name} {kind}")
            for suffix, inst in self._each(ent):
                if isinstance(inst, (Counter, Gauge)):
                    v = inst.value
                    lines.append(f"{name}{suffix} {v:g}")
                    continue
                base = suffix[1:-1] if suffix else ""
                ex = inst.exemplars()
                for i, (b, c) in enumerate(inst.cumulative()):
                    le = "+Inf" if b == float("inf") else f"{b:g}"
                    joined = ",".join(x for x in (base, f'le="{le}"') if x)
                    line = f"{name}_bucket{{{joined}}} {c}"
                    # OpenMetrics exemplar suffix, appended ONLY to
                    # _bucket lines (non-bucket series stay parseable
                    # as `last token is the value`)
                    e = ex.get(inst._le_key(i))
                    if e is not None:
                        line += (f' # {{trace_id="{e["trace_id"]}"}} '
                                 f'{e["value_ms"]:g}')
                    lines.append(line)
                lines.append(f"{name}_sum{suffix} {inst.sum:g}")
                lines.append(f"{name}_count{suffix} {inst.count}")
                for q, tag in ((0.50, "p50"), (0.99, "p99")):
                    v = inst.quantile(q)
                    if v is not None:
                        lines.append(f"{name}_{tag}{suffix} {v:g}")
        return "\n".join(lines) + "\n"


_REGISTRY = MetricsRegistry()


def registry() -> MetricsRegistry:
    return _REGISTRY


def reset() -> None:
    """Drop every instrument and disable (tests)."""
    global _enabled
    _REGISTRY.clear()
    _enabled = False


def snapshot() -> Dict[str, Any]:
    return _REGISTRY.snapshot()


def to_prometheus() -> str:
    return _REGISTRY.to_prometheus()


# -- instrument catalogues --------------------------------------------------
# Hot paths hold one of these namespaces (resolved once at object
# construction) instead of re-looking instruments up per round/request.

class _Namespace:
    pass


def train_instruments() -> Any:
    """The training round loop's instrument bundle (models/gbdt.py holds
    one when `tpu_metrics` is on; resilience/retry.py bumps the retry
    family through `note_retry_event`)."""
    r = _REGISTRY
    ns = _Namespace()
    ns.rounds = r.counter(
        "train_rounds_total", "boosting rounds completed")
    ns.trees = r.counter(
        "train_trees_total", "trees appended to the ensemble")
    ns.retraces = r.counter(
        "train_retraces_total",
        "new XLA traces observed by compile_cache.note_trace")
    ns.fallbacks = r.counter(
        "train_aligned_fallbacks_total",
        "aligned-engine exact-replay fallbacks")
    ns.round_ms = r.histogram(
        "train_round_ms", "host wall time per boosting round (ms)")
    ns.retry_events = r.counter(
        "train_retry_events_total",
        "resilience retry events by outcome",
        labelnames=("event",))
    return ns


def serving_instruments() -> Any:
    """The serving plane's instrument bundle (coalescer + registry hold
    one when the metrics plane is enabled)."""
    r = _REGISTRY
    ns = _Namespace()
    ns.requests = r.counter(
        "serve_requests_total", "predict requests submitted")
    ns.batches = r.counter(
        "serve_batches_total", "coalesced engine dispatches by trigger",
        labelnames=("reason",))
    ns.rows = r.counter(
        "serve_rows_total", "real rows dispatched to engines")
    ns.padded_rows = r.counter(
        "serve_padded_rows_total",
        "padded bucket rows dispatched (>= serve_rows_total)")
    ns.failures = r.counter(
        "serve_failures_total", "requests completed with an exception")
    ns.fill = r.gauge(
        "serve_batch_fill_ratio",
        "lifetime real-rows / padded-rows of engine dispatches")
    ns.latency = r.histogram(
        "serve_request_latency_ms",
        "submit-to-result latency per request (ms)",
        labelnames=("model",))
    ns.completed = r.counter(
        "serve_requests_completed_total",
        "requests completed by outcome — ok + error sums to "
        "serve_requests_total once the queue drains",
        labelnames=("model", "status"))
    ns.slo_breaches = r.counter(
        "serve_slo_breaches_total",
        "requests whose total latency breached tpu_serve_slo_ms",
        labelnames=("model",))
    ns.slo_burn = r.gauge(
        "serve_slo_burn_rate",
        "rolling fraction of SLO-breaching/errored requests over the "
        "last 256 outcomes (obs/reqtrace.py burn window)",
        labelnames=("model",))
    ns.loads = r.counter(
        "serve_model_loads_total", "registry model loads")
    ns.swaps = r.counter(
        "serve_model_swaps_total", "registry hot swaps")
    ns.evictions = r.counter(
        "serve_model_evictions_total", "registry LRU evictions")
    ns.early_stop = r.counter(
        "serve_early_stop_total",
        "prediction chunks that exited before scoring every tree "
        "(pred_early_stop on the batched engine path)")
    # network front door (serving/frontend/): admission, shedding,
    # placement. Same zero-overhead-off discipline — the bundle is
    # resolved once at construction, None when the plane is off.
    ns.http_requests = r.counter(
        "serve_http_requests_total",
        "front-door HTTP requests by response code",
        labelnames=("code",))
    ns.shed = r.counter(
        "serve_shed_total",
        "front-door requests load-shed with a 429 while the model's "
        "SLO burn rate was above the shed watermark",
        labelnames=("model", "qos"))
    ns.deadline_expired = r.counter(
        "serve_deadline_expired_total",
        "front-door requests that expired their X-Deadline-Ms budget "
        "in the admission queue (answered without dispatch)",
        labelnames=("model",))
    ns.admit_depth = r.gauge(
        "serve_admit_queue_depth",
        "front-door admission queue depth (requests waiting) per QoS "
        "class",
        labelnames=("qos",))
    ns.device_queue = r.gauge(
        "serve_device_queue_rows",
        "rows in flight toward each device's replicas (the placer's "
        "shallowest-queue routing signal)",
        labelnames=("device",))
    ns.replicas = r.gauge(
        "serve_model_replicas",
        "device replicas resident per model (placer hot-model "
        "replication)",
        labelnames=("model",))
    return ns


def note_early_stop() -> None:
    """One chunk exited the forest early (`ForestEngine` pred_early_stop).
    No-op when the metrics plane is off — the engine calls this
    unconditionally because exits are bounded by chunk count."""
    if not _enabled:
        return
    _REGISTRY.counter(
        "serve_early_stop_total",
        "prediction chunks that exited before scoring every tree "
        "(pred_early_stop on the batched engine path)").inc()


def note_retry_event(event: str) -> None:
    """One resilience retry event ('retry' / 'recovered' / 'exhausted').
    No-op when the metrics plane is off — retry sites call this
    unconditionally because the events are rare by construction."""
    if not _enabled:
        return
    _REGISTRY.counter("train_retry_events_total",
                      "resilience retry events by outcome",
                      labelnames=("event",)).labels(event=event).inc()
