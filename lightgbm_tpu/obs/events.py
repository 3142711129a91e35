"""Canonical structured-event vocabulary.

One table names every ``log.event(kind, ...)`` record the framework can
emit — the same role ``obs/terms.py`` plays for device-time terms. The
emit side validates against THIS dict when ``__debug__`` (utils/log.py),
graftlint's LGT005 checker validates every literal kind at lint time,
and ``parse_event`` consumers can rely on the catalog being closed: a
kind that is not here is a bug, not a new feature.

Why a catalog and not grep: event kinds are the join key between the
ledger, CI assertions (e.g. the serving smoke counts
``serve_swap`` notes) and offline tooling. A renamed or misspelled kind
silently breaks those joins — drift used to be caught only by whichever
test happened to parse the affected line, or not at all.

Adding an event: add the kind + one-line description here, then emit it.
``tools/lint`` fails the build on an uncatalogued literal kind; dynamic
kinds (f-strings) are rejected outright unless suppressed with a reason.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

# kind -> one-line description (keep alphabetized within each block)
EVENTS: Dict[str, str] = {
    # training path + compile plane
    "aligned_fallback": "aligned engine exact-replay fallback count for "
                        "a finished training run",
    "compile_cache_miss": "persistent-compile-cache miss, with the "
                          "traced program signature (warm-up forensics)",
    "quant_hist": "quantized-histogram path resolution: active bits "
                  "and payload dtype, or why the f32 oracle ran "
                  "instead",
    "round_anomaly": "a traced round's wall time deviated past the "
                     "anomaly factor from the trailing-window median "
                     "(in-run anomaly watch; edge-triggered)",
    "stream_ingest": "streaming out-of-core ingest finished: rows, "
                     "chunk size, device-vs-host binning split, wall "
                     "time",
    "telemetry": "per-round ledger record mirrored onto the event "
                 "channel by the telemetry callback",
    "train_path": "which training path a run took (fused / aligned / "
                  "level / host) plus the gate notes that routed it",
    # ranking
    "rank_buckets": "bucketed lambdarank pad ladder: per-bucket query/"
                    "doc counts and pair-padding waste",
    "rank_fused": "segment-fused lambdarank kernel built: tile stats, "
                  "oversize-query leftovers, interpret flag",
    # prediction / serving
    "predict_route": "Booster.predict routing decision (device engine "
                     "vs native host walk) and why",
    "serve_aot": "AOT artifact export/load outcome (hit / miss / "
                 "signature_mismatch / export / prefill / bad blob)",
    "serve_compact": "compact dtype plan passed the parity gate at model "
                     "load: plan, bytes, bytes saved vs f32",
    "serve_compact_fallback": "compact plan FAILED the parity gate; the "
                              "load fell back to the f32 engine",
    "serve_compile": "ForestEngine compiled a new shape-bucket program",
    "serve_deadline": "front-door request expired its X-Deadline-Ms "
                      "budget in the admission queue and was answered "
                      "without an engine dispatch (rate-limited)",
    "serve_evict": "registry evicted an LRU entry over the HBM budget",
    "serve_frontend": "scoring front door started or stopped: bind "
                      "address, QoS map, shed mode, request totals",
    "serve_load": "registry loaded (or replaced) a named model",
    "serve_place": "placer assigned/replicated/evicted a model replica "
                   "on a device (HBM-headroom placement; per-device "
                   "LRU budget)",
    "serve_over_budget": "a single protected entry alone exceeds the "
                         "HBM budget (load proceeds with a warning)",
    "serve_request_slow": "a coalesced request breached tpu_serve_slo_ms "
                          "(rate-limited pointer; the full span is in "
                          "the request-trace ring/JSONL)",
    "serve_route": "placer first routed a model's traffic to a replica "
                   "on a device (edge-triggered per model/device pair)",
    "serve_shed": "front-door load shedding tripped or cleared for a "
                  "model (burn-rate hysteresis) with the running shed "
                  "count; shed requests get fast 429s",
    "serve_slo_burn": "a model's rolling SLO burn rate crossed the high "
                      "watermark — the load-shedding trip signal",
    "serve_swap": "registry hot-swapped a named model to a new version",
    "serve_trace_dump": "request tracer closed: kept-row / breach / "
                        "error totals and the JSONL path",
    "serve_watch_bad_model": "checkpoint watcher skipped a torn/invalid "
                             "model version (retried next tick)",
    "serve_watch_error": "checkpoint watcher poll raised; the thread "
                         "survives and retries",
    # many-model sweep trainer (sweep/)
    "sweep_init": "train_many chose its execution mode: fleet size, "
                  "batched vs interleaved, and the gate's fallback "
                  "reason when batching was rejected",
    "sweep_refresh": "continual-refresh cycle published the retrained "
                     "fleet's serving checkpoint versions",
    "sweep_refresh_triggered": "a serving model's SLO burn rate crossed "
                               "the trigger threshold; it is enqueued "
                               "for the next refresh fleet",
    "sweep_subfleet": "one shape-bucketed batched sub-fleet started: "
                      "member indices, size, split reason (shape / hbm "
                      "/ cap), score-stack MiB, variant",
    "sweep_subfleet_imbalance": "sustained per-sub-fleet round-wall "
                                "imbalance (max/median) crossed or "
                                "cleared the straggler threshold "
                                "(edge-triggered)",
    "sweep_train": "train_many finished: fleet size, mode, rounds, "
                   "wall time, trace count",
    # distributed runtime (dist/)
    "dist_init": "distributed runtime activated: tree_learner mode, mesh "
                 "shard count, device kinds",
    "dist_resume": "resumed distributed run rescattered the gathered "
                   "score buffers back onto the mesh",
    "dist_shard": "dataset sharded across the mesh: rows per shard, "
                  "per-device HBM bytes, bin-sync wall time",
    "dist_stream": "stream-to-shard ingest finished: rows, mesh width, "
                   "chunk size, parse/bin walls + overlap efficiency of "
                   "the double-buffered pipeline, per-device shard "
                   "bytes and their HBM-accountant owner names",
    # resilience
    "checkpoint": "full-training-state checkpoint written (iter, path, "
                  "reason, write cost)",
    "fault": "deterministic fault injection fired (tests/CI)",
    "preempt": "SIGTERM/SIGINT observed; training will checkpoint and "
               "exit 75 after the in-flight round",
    "resume": "training resumed from a checkpoint (iter, source)",
    "retry": "transient device-dispatch error; retrying with backoff",
    "retry_exhausted": "dispatch retries exhausted; error propagates",
    "retry_recovered": "dispatch succeeded after transient-error "
                       "retries",
}


def validate_kind(kind: Any) -> Optional[str]:
    """None when `kind` is a catalogued event kind; else a reason
    string (utils/log.event asserts on this under ``__debug__``)."""
    if not isinstance(kind, str):
        return f"event kind must be a str, got {type(kind).__name__}"
    if kind not in EVENTS:
        return (f"unknown event kind {kind!r} — add it to "
                f"obs/events.py EVENTS")
    return None
