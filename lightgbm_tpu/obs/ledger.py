"""Per-round metrics ledger: one JSONL record per boosting round,
flushed as it happens so a killed run still leaves rounds 0..k on disk.

Record kinds:

- ``run``   — one header per ledger: schema version, pid, config digest.
- ``round`` — one per boosting round. Required fields: ``round``,
  ``wall_ms`` (fence-to-fence host wall time), ``device_ms`` (the
  residual device drain after host dispatch returned — i.e. the time
  spent blocked in the tracing fence), ``traces`` (new XLA traces this
  round, from ``compile_cache.trace_count`` deltas), ``path`` (the
  training path string from ``_log_train_path``), ``aligned`` bool,
  ``fallbacks`` (aligned exact-replay fallbacks this round), ``trees``.
  Optional: ``gate_notes`` (e.g. "slot-hist spilled to HBM"),
  ``hist_spill`` bool, ``bag_cnt`` (bagging/GOSS sample size),
  ``finished`` (no-split stop flag), ``eval`` (folded in by the
  ``log_telemetry`` callback after metrics run), and — on a sweep's
  trim rounds only (sweep/trainer.py) — ``profiled`` bool, ``terms_ms``
  (canonical per-term device ms, keys from ``obs.terms.TERMS``) and
  ``timing``, which names the round's device-time convention:
  ``"residual"`` (the default: ONE end-of-round fence, ``device_ms``
  is the pipelined residual drain) vs ``"fenced"`` (the round drained
  the device before and after, ``device_ms`` is the whole fenced
  dispatch). The two are NOT comparable — a fenced round serializes
  the pipeline — so readers must split on ``timing`` before
  aggregating; records without the field are ``"residual"``.
  Rounds also carry ``t0`` (raw ``perf_counter`` at round start — the
  timeline's clock anchor, obs/timeline.py).
- ``eval``  — per-round metric values, appended by the callback seam
  (the round record is already flushed by then; the eval record carries
  the same ``round`` index so readers can join them).

Readers: ``read_ledger(path)`` -> list of dicts (a ``LedgerRows`` whose
``torn_tail`` flag marks a dropped torn final line after a mid-flush
kill); ``validate_record`` raises on schema violations (used by tests
and the CI telemetry smoke).
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

SCHEMA_VERSION = 1

ROUND_REQUIRED = ("round", "wall_ms", "device_ms", "traces", "path",
                  "aligned", "fallbacks", "trees")
_KINDS = ("run", "round", "eval", "note")

_seq = 0


def validate_record(rec: Dict[str, Any]) -> None:
    """Raise ValueError unless `rec` is a well-formed ledger record."""
    if not isinstance(rec, dict):
        raise ValueError(f"ledger record must be a dict, got {type(rec)}")
    kind = rec.get("kind")
    if kind not in _KINDS:
        raise ValueError(f"ledger record kind {kind!r} not in {_KINDS}")
    if kind == "round":
        missing = [k for k in ROUND_REQUIRED if k not in rec]
        if missing:
            raise ValueError(f"round record missing fields: {missing}")
        if not isinstance(rec["round"], int) or rec["round"] < 0:
            raise ValueError(f"bad round index: {rec['round']!r}")
        for k in ("wall_ms", "device_ms"):
            if not isinstance(rec[k], (int, float)) or rec[k] < 0:
                raise ValueError(f"bad {k}: {rec[k]!r}")
        if not isinstance(rec["aligned"], bool):
            raise ValueError(f"bad aligned flag: {rec['aligned']!r}")
        if "terms_ms" in rec:
            from .terms import validate_terms_ms
            why = validate_terms_ms(rec["terms_ms"])
            if why is not None:
                raise ValueError(f"bad terms_ms: {why}")
        timing = rec.get("timing")
        if timing is not None and timing not in ("residual", "fenced"):
            raise ValueError(f"bad timing mode: {timing!r} "
                             f"(must be 'residual' or 'fenced')")
        if "profiled" in rec and not isinstance(rec["profiled"], bool):
            raise ValueError(f"bad profiled flag: {rec['profiled']!r}")
    if kind == "eval" and "round" not in rec:
        raise ValueError("eval record missing round index")


class LedgerRows(List[Dict[str, Any]]):
    """`read_ledger` result: a plain list of records plus a `torn_tail`
    flag — True when the file's LAST line was a torn partial record
    (SIGKILL mid-flush) and was dropped rather than parsed."""

    torn_tail: bool = False


def read_ledger(path: str) -> LedgerRows:
    """Parse a ledger JSONL file.

    A process killed mid-`flush` leaves a torn final line; every record
    before it is intact (one record per line, flushed per commit), so
    the torn tail is dropped and reported via `rows.torn_tail` instead
    of making the whole ledger unreadable. A malformed line anywhere
    BUT the tail still raises — that is corruption, not a crash
    artifact."""
    out = LedgerRows()
    with open(path) as fh:
        lines = [ln.strip() for ln in fh]
    nonempty = [(i, ln) for i, ln in enumerate(lines) if ln]
    for pos, (_i, line) in enumerate(nonempty):
        try:
            out.append(json.loads(line))
        except json.JSONDecodeError:
            if pos == len(nonempty) - 1:
                out.torn_tail = True
                break
            raise
    return out


class RoundLedger:
    """Append-only JSONL metrics ledger with an in-memory mirror."""

    def __init__(self, path: Optional[str] = None,
                 meta: Optional[Dict[str, Any]] = None) -> None:
        self.path = path
        self.records: List[Dict[str, Any]] = []
        self._fh = None
        if path is not None:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            self._fh = open(path, "a")
        head = {"kind": "run", "schema": SCHEMA_VERSION, "pid": os.getpid()}
        if meta:
            head.update(meta)
        self.commit(head)

    @classmethod
    def for_training(cls, trace_dir: str,
                     cfg: Any = None) -> "RoundLedger":
        """A training ledger at ``<dir>/ledger-<pid>-<seq>.jsonl`` with
        a config-digest header (so a trace directory holding several
        runs stays attributable)."""
        global _seq
        _seq += 1
        path = os.path.join(trace_dir,
                            f"ledger-{os.getpid()}-{_seq}.jsonl")
        meta: Dict[str, Any] = {}
        if cfg is not None:
            try:
                import hashlib

                from ..compile_cache import config_signature
                sig = json.dumps(config_signature(cfg), sort_keys=True,
                                 default=str)
                meta["config_sig"] = hashlib.sha1(
                    sig.encode()).hexdigest()[:16]
                meta["objective"] = cfg.objective
            except Exception:
                pass
        return cls(path, meta)

    def commit(self, rec: Dict[str, Any]) -> Dict[str, Any]:
        """Validate, mirror in memory, and flush one JSONL line."""
        validate_record(rec)
        self.records.append(rec)
        if self._fh is not None:
            self._fh.write(json.dumps(rec, sort_keys=True, default=str)
                           + "\n")
            self._fh.flush()
        return rec

    def record_eval(self, round_idx: int, results) -> None:
        """Fold per-round metric values in via the callback seam:
        annotate the in-memory round record AND append an `eval` line
        (the round line is already durable by the time metrics run)."""
        vals = {f"{dn}:{mn}": float(v) for dn, mn, v, _ in results}
        for rec in reversed(self.records):
            if rec.get("kind") == "round" and rec["round"] == round_idx:
                rec["eval"] = vals
                break
        self.commit({"kind": "eval", "round": round_idx, "values": vals})

    def last_round(self) -> Optional[Dict[str, Any]]:
        for rec in reversed(self.records):
            if rec.get("kind") == "round":
                return rec
        return None

    def round_records(self) -> List[Dict[str, Any]]:
        return [r for r in self.records if r.get("kind") == "round"]

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None
