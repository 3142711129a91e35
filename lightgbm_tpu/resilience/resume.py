"""Bitwise-identical resume from a checkpoint.py checkpoint.

The restore path REINSTALLS captured state instead of replaying it:

- trees come from the checkpoint's model text (decimal repr round-trips
  the stored float64/float32 values exactly, so a re-serialized resumed
  model is byte-identical to the uninterrupted run's);
- the f32 train/valid score arrays come from arrays.npz — replaying the
  loaded trees would accumulate in a different order AND through the
  text repr, breaking bitwise continuation;
- the bagging/GOSS/DART and feature-sampling RNG streams are reinstated
  by full Mersenne state (never re-seeded: a re-seeded ``_bag_rng``
  restarts at round 0's draws and silently diverges);
- early-stopping callback state (best score/iter per metric) goes back
  into the callback closures via their ``set_ckpt_state`` hooks.

``engine.train`` calls ``load_latest`` + ``restore`` automatically when
``tpu_checkpoint_dir`` holds a valid manifest whose training signature
matches the current config; a signature or dataset-shape mismatch is
WARNED and training starts fresh (the stale checkpoints age out through
retention).
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

import numpy as np

from ..utils import log
from .checkpoint import (MANIFEST_NAME, SCHEMA_VERSION, install_rng_states,
                         read_manifest)


def load_latest(mgr) -> Optional[Dict[str, Any]]:
    """Validate the manifest + latest checkpoint under `mgr.directory`
    and return a restore bundle {dir, state, model_text, arrays}, or
    None when there is nothing (valid) to resume from."""
    man = read_manifest(mgr.directory)
    if man is None:
        return None
    if man.get("schema", 0) > SCHEMA_VERSION:
        log.warning(f"checkpoint manifest schema {man.get('schema')} is "
                    f"newer than this build ({SCHEMA_VERSION}); ignoring "
                    f"{os.path.join(mgr.directory, MANIFEST_NAME)}")
        return None
    cdir = os.path.join(mgr.directory, str(man["latest"]))
    paths = {n: os.path.join(cdir, n)
             for n in ("model.txt", "state.json", "arrays.npz")}
    if not all(os.path.isfile(p) for p in paths.values()):
        log.warning(f"checkpoint {cdir} is incomplete; ignoring it")
        return None
    try:
        with open(paths["state.json"]) as fh:
            state = json.load(fh)
    except (OSError, ValueError) as exc:
        log.warning(f"unreadable checkpoint state at {cdir}: {exc}")
        return None
    if mgr.signature and state.get("signature") != mgr.signature:
        log.warning(
            f"checkpoint at {cdir} was written under a different training "
            f"config (signature {state.get('signature')!r} != "
            f"{mgr.signature!r}); starting fresh")
        return None
    with open(paths["model.txt"]) as fh:
        model_text = fh.read()
    arrays = dict(np.load(paths["arrays.npz"]))
    return {"dir": cdir, "state": state, "model_text": model_text,
            "arrays": arrays}


def restore(booster, bundle: Dict[str, Any], callbacks=()) -> int:
    """Reinstall `bundle` into a freshly-constructed training booster
    (AFTER its valid sets were attached — their score arrays are
    overwritten here). Returns the loop iteration to continue from."""
    from ..models.model_text import load_model_from_string
    gbdt = booster._gbdt
    state = bundle["state"]
    arrays = bundle["arrays"]

    if int(state["num_data"]) != int(gbdt.num_data) \
            or int(state["num_class"]) != int(gbdt.num_tree_per_iteration):
        log.warning(
            f"checkpoint at {bundle['dir']} does not match this dataset "
            f"(rows {state['num_data']} vs {gbdt.num_data}, classes "
            f"{state['num_class']} vs {gbdt.num_tree_per_iteration}); "
            "starting fresh")
        return 0

    import jax.numpy as jnp
    trees = load_model_from_string(bundle["model_text"])["trees"]
    gbdt.models = list(trees)
    gbdt.iter = int(state["iter"])
    gbdt.shrinkage_rate = float(state["shrinkage_rate"])

    ts = arrays["train_score"]
    if tuple(ts.shape) != tuple(gbdt.train_score.score.shape):
        log.warning(f"checkpoint score shape {ts.shape} does not match "
                    f"{tuple(gbdt.train_score.score.shape)}; starting fresh")
        gbdt.models = []
        gbdt.iter = 0
        return 0
    gbdt.train_score.score = jnp.asarray(ts)
    for i, su in enumerate(gbdt.valid_scores):
        key = f"valid_score_{i}"
        if key not in arrays:
            log.warning(f"checkpoint lacks {key} (valid sets changed); "
                        "its scores will rebuild from the loaded trees")
            continue
        su.score = jnp.asarray(arrays[key])
    # distributed runs: push the gathered score buffers back onto the
    # learner's mesh so the resumed loop is SPMD from its first dispatch
    # (values untouched — bitwise parity rides the contents)
    from ..dist.runtime import rescatter_scores
    rescatter_scores(gbdt)

    bag_idx = arrays.get("bag_data_indices")
    if bag_idx is not None and bag_idx.size:
        gbdt.bag_data_indices = np.asarray(bag_idx, np.int32)
    else:
        gbdt.bag_data_indices = None
    gbdt.bag_data_cnt = int(state["bag_data_cnt"])
    sample = state.get("aligned_sample")
    gbdt._aligned_sample = (tuple(sample) if isinstance(sample, list)
                            else sample)

    install_rng_states(gbdt, state["rng"])

    pend = arrays.get("pending_numsplits")
    gbdt._pending_numsplits = (
        [jnp.asarray(int(v), jnp.int32) for v in pend]
        if pend is not None and pend.size else [])

    dart = state.get("dart")
    if dart is not None and hasattr(gbdt, "tree_weight"):
        gbdt.tree_weight = [float(w) for w in dart["tree_weight"]]
        gbdt.sum_weight = float(dart["sum_weight"])

    cb_states = state.get("callbacks") or {}
    for cb in callbacks:
        key = getattr(cb, "ckpt_key", None)
        setter = getattr(cb, "set_ckpt_state", None)
        if key and setter is not None and key in cb_states:
            setter(cb_states[key])

    booster.best_iteration = int(state.get("best_iteration", -1))

    start_iter = int(state["loop_iter"])
    log.info(f"resuming training from checkpoint {bundle['dir']} "
             f"(iteration {start_iter})")
    log.event("resume", iter=gbdt.iter, loop_iter=start_iter,
              checkpoint=bundle["dir"], reason=state.get("reason"))
    led = gbdt.telemetry
    if led is not None:
        led.commit({"kind": "note", "note": "resume",
                    "iter": gbdt.iter, "loop_iter": start_iter,
                    "checkpoint": bundle["dir"],
                    "ledger_round_offset": state.get("ledger_rounds", 0)})
    return start_iter
