"""Canonical device-time term vocabulary.

One table names every term the framework can attribute device time or
set-up wall to: the keys a ledger round record's ``terms_ms`` may hold
(``obs/ledger.py`` validates against THIS dict; today the sweep's
fenced trim rounds write ``sweep``) and the names the ingest and
quantisation events report their walls under. The vocabulary is closed:
a key that is not here is refused at commit.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

# term -> one-line description
TERMS: Dict[str, str] = {
    # fenced (round-level, disjoint)
    "grad": "pointwise objective gradient + hessian pass",
    "rank_grad": "lambdarank pair-gradient + NDCG-delta pass "
                 "(segment-fused Pallas kernel or bucketed fallback)",
    "build": "whole-tree build program (root hist + move/route + "
             "split eval fused into one dispatch on the aligned path)",
    "score_update": "tree score application to train/valid score lanes",
    "eval": "device metric programs queued for per-round evaluation",
    "collective": "cross-device psum/all-reduce time on parallel "
                  "learners",
    "allreduce": "standalone histogram-shaped all-reduce probe on a "
                 "sampled round (per-round collective visibility for "
                 "the distributed runtime)",
    "other": "residual device drain not attributed to a fenced site",
    # calibration (per-pass kernel rates)
    "bin_sync": "host wall time of distributed bin-boundary finding "
                "(per-shard sample pass + global merge) at dataset "
                "construction",
    "hist": "slot histogram accumulation over the full record store",
    "route": "partition/routing move pass (decode + compact store), "
             "no hist slots",
    "flush": "marginal fused sub-binned hist accumulate + slot flush "
             "in the move pass (hist_move minus route)",
    "hist_move": "hist-accumulating move pass (minuend for flush; "
                 "subtracted out)",
    "copy": "record-store copy move pass (no split, no hist)",
    "split_eval": "split finder over a changed-children histogram "
                  "batch",
    "ingest": "streaming out-of-core ingest wall time (sample pass + "
              "on-device chunk binning + HBM append) at dataset "
              "construction",
    "ingest_parse": "host side of the pipelined stream-to-shard ingest: "
                    "text parse + used-column select/transpose/pad on "
                    "the prefetch thread (overlaps ingest_bin; the two "
                    "sum to MORE than the ingest wall when the pipeline "
                    "overlaps)",
    "ingest_bin": "device side of the pipelined stream-to-shard ingest: "
                  "chunk transfer + owner-device searchsorted binning + "
                  "donated shard append, including the double-buffer "
                  "pacing waits",
    "quant_pack": "stochastic-rounded gradient quantization pass of "
                  "the quantized-histogram path (per-tree int8/int16 "
                  "pack + scale)",
    "sweep": "batched fleet round program (all M models' gradients + "
             "builds + score updates in one dispatch); fenced only on "
             "trim rounds, where the sweep loop drains anyway",
}

def validate_terms_ms(terms: Any) -> Optional[str]:
    """None when `terms` is a well-formed ``terms_ms`` dict (canonical
    keys, numeric-or-null values); else a reason string."""
    if not isinstance(terms, dict):
        return f"terms_ms must be a dict, got {type(terms).__name__}"
    for k, v in terms.items():
        if k not in TERMS:
            return f"unknown term {k!r} (not in obs.terms.TERMS)"
        if v is not None and (not isinstance(v, (int, float))
                              or isinstance(v, bool) or v < 0):
            return f"bad value for term {k!r}: {v!r}"
    return None
