"""Atomic full-training-state checkpoints (reference treats snapshots as
a first-class training feature, `gbdt.cpp:289-293`; this module extends
them from model-text-only to the COMPLETE training state so a resumed
run continues bitwise-identically — see resume.py).

Checkpoint layout (one directory per checkpoint under
``tpu_checkpoint_dir``)::

    <dir>/MANIFEST.json          atomic pointer: latest + retained list
    <dir>/ckpt_000010/model.txt  model text at the checkpoint iteration
    <dir>/ckpt_000010/state.json iter, RNG streams, early-stop state,
                                 training signature, ledger offset
    <dir>/ckpt_000010/arrays.npz f32 train/valid score arrays, bagging
                                 indices, pending numsplit flags

Atomicity: the payload directory is staged under a tmp name in the same
filesystem and ``os.replace``-renamed into place; MANIFEST.json is then
rewritten tmp+rename. A reader either sees the previous manifest or the
new one — never a half-written checkpoint. Retention keeps the newest
``tpu_snapshot_keep`` checkpoints.

Why score arrays and not tree replay: model text stores leaf values
through a decimal repr, and re-applying trees uses a different f32
accumulation order than training — both would break bitwise resume.
The checkpointed f32 arrays restore the exact training-time bits.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ..utils import log

SCHEMA_VERSION = 1
MANIFEST_NAME = "MANIFEST.json"
_CKPT_PREFIX = "ckpt_"

# params that describe the run's infrastructure, not the training math:
# excluded from the checkpoint-compatibility signature so a resumed run
# may e.g. drop the fault spec or change retention without the manifest
# being rejected
RUNTIME_ONLY_PARAMS = frozenset({
    "tpu_checkpoint_dir", "tpu_checkpoint_freq", "tpu_snapshot_keep",
    "tpu_fault_spec", "tpu_retry_max", "tpu_retry_backoff_s",
    "tpu_trace", "tpu_trace_dir",
    "snapshot_freq", "output_model", "input_model", "output_result",
    "num_threads", "verbosity",
    "tpu_serve_hbm_budget_mb", "tpu_serve_max_batch_wait_ms",
    "tpu_serve_max_batch_rows", "tpu_serve_watch_interval_s",
    "tpu_serve_warm_rows", "tpu_metrics", "tpu_serve_metrics_port",
    "tpu_serve_hold_s", "tpu_serve_trace", "tpu_serve_trace_dir",
    "tpu_serve_trace_sample", "tpu_serve_trace_ring", "tpu_serve_slo_ms",
    "tpu_serve_aot_dir", "tpu_serve_compact", "tpu_serve_compact_tol",
    # network front door (serving/frontend/): admission, shedding and
    # placement shape traffic, never the trained trees
    "tpu_serve_port", "tpu_serve_qos", "tpu_serve_shed",
    "tpu_serve_shed_high", "tpu_serve_shed_low", "tpu_serve_admit_rows",
    "tpu_serve_devices", "tpu_serve_replicas",
    "tpu_debug_locks",
    # timeline + straggler/anomaly watches (obs/timeline.py,
    # obs/straggler.py): observability of the run, not training math
    "tpu_timeline", "tpu_straggler_threshold", "tpu_straggler_rounds",
    "tpu_anomaly_factor", "tpu_anomaly_window",
    # sweep-trainer infrastructure (sweep/): a fleet checkpoint may be
    # resumed with different sweep plumbing, and a sequential checkpoint
    # is mode-independent anyway
    "tpu_sweep_mode", "tpu_sweep_checkpoint_dir",
    "tpu_sweep_checkpoint_freq", "tpu_sweep_hbm_budget_mb",
    "tpu_sweep_max_fleet",
    # topology: trees are bit-identical across tree_learner / shard-count
    # choices (distributed parity contract), so a checkpoint taken on one
    # topology may resume on another — e.g. a preempted 4-chip run
    # finishing on a single chip
    "tree_learner", "num_machines", "is_parallel", "is_parallel_find_bin",
    "tpu_dist_devices",
    # how the matrix was ingested does not change what it binned to
    "tpu_stream_chunk_rows", "tpu_stream_shard",
    "tpu_stream_pipeline_depth",
})


def training_signature(cfg) -> str:
    """sha1 over every Config field that affects training math (the
    compile-cache signature minus RUNTIME_ONLY_PARAMS). Two runs with
    the same signature produce the same trees, so a checkpoint from one
    may seed the other."""
    from ..compile_cache import config_signature
    items = [(k, v) for k, v in config_signature(cfg)
             if k not in RUNTIME_ONLY_PARAMS]
    blob = json.dumps(items, sort_keys=True, default=str)
    return hashlib.sha1(blob.encode()).hexdigest()[:16]


def atomic_write_text(path: str, text: str) -> None:
    """tmp + rename in the destination directory (same filesystem, so
    the rename is atomic); a reader never sees a torn file."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = os.path.join(d, f".tmp.{os.getpid()}.{os.path.basename(path)}")
    with open(tmp, "w") as fh:
        fh.write(text)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, path)


def prune_snapshots(output_model: str, keep: int) -> List[str]:
    """Rolling retention for the CLI's ``<output_model>.snapshot_iter_K``
    files: keep the newest `keep` by iteration number, delete the rest.
    Returns the removed paths."""
    import glob
    if keep <= 0:
        return []
    snaps = []
    for p in glob.glob(f"{output_model}.snapshot_iter_*"):
        tail = p.rsplit("snapshot_iter_", 1)[-1]
        if tail.isdigit():
            snaps.append((int(tail), p))
    snaps.sort()
    removed = []
    excess = snaps[:-keep] if len(snaps) > keep else []
    for _, p in excess:
        try:
            os.remove(p)
            removed.append(p)
        except OSError:
            pass
    return removed


def _encode_rng(rs: np.random.RandomState) -> Dict[str, Any]:
    name, keys, pos, has_gauss, cached = rs.get_state()
    return {"name": str(name), "keys": [int(k) for k in keys],
            "pos": int(pos), "has_gauss": int(has_gauss),
            "cached_gaussian": float(cached)}


def _install_rng(rs: np.random.RandomState, enc: Dict[str, Any]) -> None:
    rs.set_state((enc["name"], np.asarray(enc["keys"], np.uint32),
                  int(enc["pos"]), int(enc["has_gauss"]),
                  float(enc["cached_gaussian"])))


def capture_rng_states(gbdt) -> Dict[str, Any]:
    """Every host RNG stream training consumes: the bagging/GOSS stream
    (gbdt._bag_rng), the DART drop stream, and the learner's column-
    sampling stream. Captured by full Mersenne state, not by seed —
    resume REINSTALLS the stream instead of replaying it."""
    out: Dict[str, Any] = {"bag": _encode_rng(gbdt._bag_rng)}
    feat = getattr(gbdt.learner, "_feat_rng", None)
    if feat is not None:
        out["feat"] = _encode_rng(feat)
    drop = getattr(gbdt, "_drop_rng", None)
    if drop is not None:
        out["drop"] = _encode_rng(drop)
    return out


def install_rng_states(gbdt, enc: Dict[str, Any]) -> None:
    _install_rng(gbdt._bag_rng, enc["bag"])
    if "feat" in enc and getattr(gbdt.learner, "_feat_rng", None) is not None:
        _install_rng(gbdt.learner._feat_rng, enc["feat"])
    if "drop" in enc and getattr(gbdt, "_drop_rng", None) is not None:
        _install_rng(gbdt._drop_rng, enc["drop"])


def read_manifest(directory: str) -> Optional[Dict[str, Any]]:
    """The manifest dict, or None when absent/corrupt (a torn write
    cannot happen — see atomic_write_text — but a partial scp can)."""
    path = os.path.join(directory, MANIFEST_NAME)
    if not os.path.isfile(path):
        return None
    try:
        with open(path) as fh:
            man = json.load(fh)
    except (OSError, ValueError):
        return None
    if not isinstance(man, dict) or "latest" not in man:
        return None
    return man


class CheckpointManager:
    """Owns one checkpoint directory: periodic + preemption writes,
    manifest maintenance, rolling retention, and write-cost
    accounting."""

    def __init__(self, directory: str, keep: int = 3, freq: int = 10,
                 signature: str = "") -> None:
        self.directory = directory
        self.keep = max(1, int(keep))
        self.freq = max(1, int(freq))
        self.signature = signature
        self.writes = 0
        self.write_s = 0.0

    @classmethod
    def from_config(cls, cfg) -> "CheckpointManager":
        freq = cfg.tpu_checkpoint_freq
        if freq <= 0:
            freq = cfg.snapshot_freq if cfg.snapshot_freq > 0 else 10
        return cls(cfg.tpu_checkpoint_dir, keep=cfg.tpu_snapshot_keep,
                   freq=freq, signature=training_signature(cfg))

    def due(self, completed_rounds: int) -> bool:
        return completed_rounds % self.freq == 0

    # ------------------------------------------------------------------
    def write(self, booster, loop_iter: int, callbacks=(),
              reason: str = "periodic") -> str:
        """Capture and atomically persist the FULL training state after
        `loop_iter` completed rounds. Returns the checkpoint path."""
        t0 = time.perf_counter()
        gbdt = booster._gbdt
        # one consistency point: resolve speculative/pipelined device
        # work so models/scores/RNG agree (reuses the round-loop seam —
        # no tracing fence is issued here)
        gbdt._sync_train_score()
        gbdt.materialized_models()

        state: Dict[str, Any] = {
            "schema": SCHEMA_VERSION,
            "iter": int(gbdt.iter),
            "loop_iter": int(loop_iter),
            "signature": self.signature,
            "reason": reason,
            "time": time.time(),
            "num_data": int(gbdt.num_data),
            "num_class": int(gbdt.num_tree_per_iteration),
            "bag_data_cnt": int(gbdt.bag_data_cnt),
            # a device-drawn sample (plain bagging's bag in force: no
            # indices are held here), as what makes it again
            "aligned_sample": (gbdt._aligned_sample
                               if gbdt._bag_on_device else None),
            "shrinkage_rate": float(gbdt.shrinkage_rate),
            "best_iteration": int(getattr(booster, "best_iteration", -1)),
            "rng": capture_rng_states(gbdt),
        }
        # DART bookkeeping (tree weights ride the drop/normalize math)
        if hasattr(gbdt, "tree_weight"):
            state["dart"] = {
                "tree_weight": [float(w) for w in gbdt.tree_weight],
                "sum_weight": float(gbdt.sum_weight),
            }
        cb_states: Dict[str, Any] = {}
        for cb in callbacks:
            get = getattr(cb, "get_ckpt_state", None)
            key = getattr(cb, "ckpt_key", None)
            if get is not None and key:
                cb_states[key] = get()
        state["callbacks"] = cb_states
        led = gbdt.telemetry
        if led is not None:
            state["ledger_rounds"] = len(led.round_records())
            state["ledger_path"] = led.path

        arrays: Dict[str, np.ndarray] = {
            "train_score": np.asarray(gbdt.train_score.score, np.float32),
        }
        for i, su in enumerate(gbdt.valid_scores):
            arrays[f"valid_score_{i}"] = np.asarray(su.score, np.float32)
        arrays["bag_data_indices"] = (
            np.asarray(gbdt.bag_data_indices, np.int32)
            if gbdt.bag_data_indices is not None
            else np.zeros(0, np.int32))
        if gbdt._pending_numsplits:
            import jax
            arrays["pending_numsplits"] = np.asarray(
                jax.device_get(gbdt._pending_numsplits), np.int32).ravel()
        else:
            arrays["pending_numsplits"] = np.zeros(0, np.int32)

        name = f"{_CKPT_PREFIX}{int(gbdt.iter):06d}"
        final = os.path.join(self.directory, name)
        tmp = os.path.join(self.directory, f".tmp.{os.getpid()}.{name}")
        os.makedirs(self.directory, exist_ok=True)
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        with open(os.path.join(tmp, "model.txt"), "w") as fh:
            fh.write(booster.model_to_string())
        with open(os.path.join(tmp, "state.json"), "w") as fh:
            json.dump(state, fh, sort_keys=True)
        np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
        shutil.rmtree(final, ignore_errors=True)
        os.replace(tmp, final)

        self._update_manifest(name, state)
        dt = time.perf_counter() - t0
        self.writes += 1
        self.write_s += dt
        log.event("checkpoint", iter=state["iter"], path=final,
                  reason=reason, write_s=round(dt, 4))
        if led is not None:
            led.commit({"kind": "note", "note": "checkpoint",
                        "iter": state["iter"], "reason": reason,
                        "write_s": round(dt, 4)})
        return final

    def _update_manifest(self, name: str, state: Dict[str, Any]) -> None:
        man = read_manifest(self.directory) or {
            "schema": SCHEMA_VERSION, "checkpoints": []}
        kept = [c for c in man.get("checkpoints", []) if c != name]
        kept.append(name)
        # retention: newest `keep` by iteration number
        kept.sort(key=lambda c: int(c[len(_CKPT_PREFIX):]))
        drop, kept = kept[:-self.keep], kept[-self.keep:]
        man.update({
            "schema": SCHEMA_VERSION,
            "latest": name,
            "iter": state["iter"],
            "loop_iter": state["loop_iter"],
            "signature": self.signature,
            "checkpoints": kept,
        })
        atomic_write_text(os.path.join(self.directory, MANIFEST_NAME),
                          json.dumps(man, sort_keys=True, indent=1))
        for c in drop:
            shutil.rmtree(os.path.join(self.directory, c),
                          ignore_errors=True)
