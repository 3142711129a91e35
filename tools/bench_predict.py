#!/usr/bin/env python
"""Forest-scoring throughput: seed TreePredictor vs serve.ForestEngine.

Builds a synthetic binned forest (default T=500 trees, 31 leaves, 50
features, max_bin=63) and a binned matrix (default N=100k rows), then times

* the seed path exactly as `TreePredictor.predict_binned_score` shipped it:
  host `stack_trees` per call, per-tree serial traversal
  (`_predict_binned_stacked_serial`), then a SECOND host re-stack for the
  leaf-value gather;
* the serving engine: device-resident forest, depth-synchronized [T, N]
  traversal, fused gather/accumulate, shape-bucketed jit cache.

Importable as `run(...)` or a CLI:

    JAX_PLATFORMS=cpu python tools/bench_predict.py

Env overrides: BENCH_PRED_TREES / BENCH_PRED_ROWS / BENCH_PRED_FEATURES /
BENCH_PRED_LEAVES / BENCH_PRED_REPEATS, BENCH_SMOKE=1 for tiny sizes.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_forest(num_trees: int, num_leaves: int, num_features: int,
                 max_bin: int, seed: int = 0):
    """Synthesize balanced binned trees through the real `Tree.split` API
    (BFS leaf order keeps depth at ceil(log2(num_leaves)), the shape the
    reference grower produces under depth-wise growth)."""
    from lightgbm_tpu.models.tree import Tree

    rng = np.random.default_rng(seed)
    trees = []
    for _ in range(num_trees):
        t = Tree(num_leaves)
        frontier = [0]          # split oldest leaf first -> balanced
        while t.num_leaves < num_leaves:
            leaf = frontier.pop(0)
            feat = int(rng.integers(0, num_features))
            tb = int(rng.integers(0, max_bin))
            new = t.split(leaf, feat, feat, threshold_bin=tb,
                          threshold_double=float(tb) + 0.5,
                          left_value=float(rng.normal(scale=0.1)),
                          right_value=float(rng.normal(scale=0.1)),
                          left_cnt=1, right_cnt=1, gain=1.0,
                          missing_type=int(rng.integers(0, 3)),
                          default_left=bool(rng.integers(0, 2)),
                          default_bin=0, num_bin=max_bin + 1)
            frontier.extend([leaf, new])
        trees.append(t)
    return trees


def _seed_call(trees, bins_dev):
    """One predict call with the seed `predict_binned_score` semantics."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.predict import (_predict_binned_stacked_serial,
                                          stack_trees)

    host = stack_trees(trees, binned=True)
    stk = {k: jnp.asarray(v) for k, v in host.items()
           if isinstance(v, np.ndarray)}
    leaves = _predict_binned_stacked_serial(bins_dev, stk)
    host2 = stack_trees(trees, binned=True)       # the seed's double stack
    lv = jnp.asarray(host2["leaf_value"]).astype(jnp.float32)
    vals = jnp.take_along_axis(lv, leaves, axis=1)
    return vals.sum(axis=0)


def run(num_trees: int = 500, rows: int = 100_000, num_features: int = 50,
        num_leaves: int = 31, max_bin: int = 63, repeats: int = 3,
        seed: int = 0, verbose: bool = False) -> dict:
    import jax.numpy as jnp
    from lightgbm_tpu.serve import ForestEngine

    def say(msg):
        if verbose:
            print(f"[bench_predict] {msg}", file=sys.stderr, flush=True)

    rng = np.random.default_rng(seed + 1)
    trees = build_forest(num_trees, num_leaves, num_features, max_bin, seed)
    bins = rng.integers(0, max_bin + 1, size=(rows, num_features),
                        dtype=np.uint8)
    bins_dev = jnp.asarray(bins)

    say(f"forest T={num_trees} leaves={num_leaves} F={num_features} "
        f"N={rows} max_bin={max_bin}")

    # -- seed path (warm the compile, then time end-to-end calls) ----------
    ref = np.asarray(_seed_call(trees, bins_dev))
    t0 = time.perf_counter()
    for _ in range(max(repeats // 2, 1)):
        np.asarray(_seed_call(trees, bins_dev))
    seed_s = (time.perf_counter() - t0) / max(repeats // 2, 1)
    say(f"seed TreePredictor: {seed_s:.3f}s/call")

    # -- engine path -------------------------------------------------------
    eng = ForestEngine(trees, num_class=1, mode="binned")
    got = eng.predict(bins)[0][:, 0]              # warmup + parity sample
    err = float(np.max(np.abs(got - ref)))
    if err > 1e-4 * max(1.0, float(np.max(np.abs(ref)))):
        raise AssertionError(f"engine/seed mismatch: maxerr={err}")
    t0 = time.perf_counter()
    for _ in range(repeats):
        eng.predict(bins)
    engine_s = (time.perf_counter() - t0) / repeats
    say(f"ForestEngine: {engine_s:.3f}s/call "
        f"(compiles={eng.compile_count}, maxerr={err:.2e})")

    return {
        "predict_trees": num_trees,
        "predict_rows": rows,
        "predict_seed_s": round(seed_s, 4),
        "predict_engine_s": round(engine_s, 4),
        "predict_seed_rows_s": round(rows / seed_s, 1),
        "predict_engine_rows_s": round(rows / engine_s, 1),
        "predict_speedup": round(seed_s / engine_s, 2),
        "predict_maxerr": err,
        "predict_compiles": eng.compile_count,
    }


def main() -> int:
    smoke = os.environ.get("BENCH_SMOKE", "") not in ("", "0")
    env = os.environ.get
    res = run(
        num_trees=int(env("BENCH_PRED_TREES", 50 if smoke else 500)),
        rows=int(env("BENCH_PRED_ROWS", 5_000 if smoke else 100_000)),
        num_features=int(env("BENCH_PRED_FEATURES", 50)),
        num_leaves=int(env("BENCH_PRED_LEAVES", 31)),
        repeats=int(env("BENCH_PRED_REPEATS", 2 if smoke else 3)),
        verbose=True)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
