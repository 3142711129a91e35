#!/usr/bin/env python3
"""The benchmark: one cell, one run, one JSON line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of `BENCHMARK.json`'s `workloads`: a configuration
(`benchmark/configs/<config>.json`: the reference experiment's parameters
and the shape of one chip's share of it) under a traffic mix
(`benchmark/traffic/<traffic>.json`: how the boosting loop is driven). One
process makes the rows from `--seed` (`benchmark/generators/<name>.py`),
pushes them through the program's streaming ingest, trains through
`Booster.update()`, and measures a window of whole iterations that ends in
a drain, because the host only enqueues and the device's work is over
only then. With `--trace 1` the window is a few iterations under the
profiler and the line carries the per-layer metrics, each read by
`benchmark/layer_metrics/<name>.py`.

What belongs to the kind of learning problem (whether rows come in query
groups, what quality means, how tree 0 is held to the first gradients) is
the configuration's task, `benchmark/tasks/<task>.py`. This file keeps the
loop, the window, the clocks and the checks that no objective changes.

A new configuration, traffic mix, generator, task or per-layer metric is a
new file and a new entry in `BENCHMARK.json`; nothing here names one.

There is no CPU mode: without a TPU, or with fewer chips than the cell
asks for, the process exits non-zero and prints no result. The toy-size
check in `benchmark/selftest/` calls `run_cell` directly.
"""
import time

T0 = time.perf_counter()    # set-up is counted from here

import argparse  # noqa: E402
import collections  # noqa: E402
import concurrent.futures  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import reference, sizing, xplane  # noqa: E402

SPAN = "bench."             # prefix of the harness's own trace annotations
WALK_ROWS = 4096


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_cell(name: str):
    """(cell, its metrics by kind, config, traffic) from the data files."""
    bench = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    metrics = {kind: [m for m in bench[kind]
                      if name in m.get("workloads", [name])]
               for kind in ("end_to_end", "per_layer")}
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = load_json(files[cell["config"]])
    traffic = load_json("benchmark", "traffic", cell["traffic"] + ".json")
    return cell, metrics, config, traffic


class Events:
    """The program's structured events (`utils/log.py`), kept in memory;
    of its human lines only warnings pass, to stderr."""

    def __init__(self):
        from lightgbm_tpu.utils import log
        self.records = []

        def sink(line):
            rec = log.parse_event(line)
            if rec is not None:
                self.records.append(rec)
            elif "[Info]" not in line and "[Debug]" not in line:
                print(line, file=sys.stderr, flush=True)
        log.register_callback(sink)
        log.set_verbosity(1)

    def of(self, kind: str) -> list:
        return [r for r in self.records if r["event"] == kind]

    def count(self, kind: str) -> int:
        return len(self.of(kind))


def compile_counts() -> dict:
    """Traces and persistent-cache lookups so far. Their difference over
    the window has to be zero: anything else means a shape was not warmed."""
    from lightgbm_tpu import compile_cache
    ev = compile_cache.persistent_cache_events()
    return {"traces": compile_cache.trace_count(), "cache_hits": ev["hits"],
            "cache_misses": ev["misses"]}


class HostMemory(threading.Thread):
    """The machine's lowest MemAvailable, sampled twice a second: the
    engine's host-side record pack comes within a few GiB of the chip
    machine's memory, and a run that is killed there prints nothing."""

    def __init__(self):
        super().__init__(daemon=True)
        self.lowest_gib = None      # stays None where /proc has no answer
        self._done = threading.Event()

    def run(self):
        while True:
            with contextlib.suppress(OSError, ValueError, IndexError):
                with open("/proc/meminfo") as f:
                    kb = [ln for ln in f if ln.startswith("MemAvailable")]
                gib = int(kb[0].split()[1]) / (1 << 20)
                self.lowest_gib = gib if self.lowest_gib is None \
                    else min(self.lowest_gib, gib)
            if self._done.wait(0.5):
                return

    def stop(self) -> float:
        self._done.set()
        self.join()
        return self.lowest_gib


def ingest(gen, cfg, rows: int, first_row: int = 0, reference_ds=None,
           grouped: bool = False):
    """Rows [first_row, first_row + rows) through the program's push-rows
    ingest (`create_from_sample` / `push_rows` / `finish_load`, the
    reference's `LGBM_DatasetCreateFromSampledColumn` + `PushRows` flow):
    `lgb.Dataset(matrix)` would copy the matrix to float64, 25.7 GB at
    48M x 67, beside the float32 one. Blocks are made on threads into
    recycled buffers while this thread bins them, in row order.

    Where the task's rows are `grouped`, the generator's query sizes for
    these rows go to `finish_load`.

    Returns (core dataset, labels float32, query sizes or None, walls).
    `program_s` is the time inside the program's three calls; the rest is
    waiting for rows."""
    from lightgbm_tpu.io.dataset import Dataset as CoreDataset
    b, f = gen.block_rows, gen.features
    walls = {"program_s": 0.0, "wait_rows_s": 0.0}
    t_phase = time.perf_counter()

    def timed(key, fn, *a, **kw):
        t = time.perf_counter()
        out = fn(*a, **kw)
        walls[key] += time.perf_counter() - t
        return out

    # few threads: more of them take cores from the program's OpenMP binner
    # and the phase gets longer (measured: 83 s with 7, 57 s with 4)
    workers = max(1, min(4, (os.cpu_count() or 2) - 1))
    free = [(np.empty((f, b), np.float32), np.empty((b, f), np.float32))
            for _ in range(workers + 2)]
    todo = collections.deque(
        range(first_row // b, (first_row + rows - 1) // b + 1))
    labels = np.empty(rows, np.float32)
    core, at = None, 0
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        pending = collections.deque()
        while todo or pending:
            while todo and free:
                blk, bufs = todo.popleft(), free.pop()
                pending.append((blk, bufs, pool.submit(gen.block, blk, *bufs)))
            blk, bufs, made = pending.popleft()
            x, y = timed("wait_rows_s", made.result)
            lo = max(first_row - blk * b, 0)
            hi = min(first_row + rows - blk * b, b)
            x, y = x[lo:hi], y[lo:hi]
            if core is None:
                # bin boundaries from a sample that no seed changes: the bin
                # counts are constants of the build program, and a sample
                # of the run's own rows recompiled it (60 s) for every
                # new seed
                sample = None if reference_ds is not None \
                    else gen.sample(cfg.bin_construct_sample_cnt)
                core = timed("program_s", CoreDataset.create_from_sample,
                             sample, rows, config=cfg, reference=reference_ds)
            timed("program_s", core.push_rows, x, label=y)
            labels[at:at + len(y)] = y
            at += len(y)
            free.append(bufs)
    groups = gen.groups(first_row, rows) if grouped else None
    timed("program_s", core.finish_load, group=groups)
    walls["phase_s"] = time.perf_counter() - t_phase
    return core, labels, groups, walls


def walked(gen, model, first_row: int, rows: int):
    """(the numpy walk's raw scores, labels) of rows [first_row, first_row
    + rows), made again from the seed: block by block of the generator, on
    a few threads (one block at a time, 4.8M rows took longer than the
    window)."""
    b, end = gen.block_rows, first_row + rows
    cuts = [first_row, *range((first_row // b + 1) * b, end, b), end]

    def part(lo, hi):
        x, y = gen.rows(lo, hi)
        return reference.raw_scores(model, x), y
    with concurrent.futures.ThreadPoolExecutor(4) as pool:
        parts = list(pool.map(part, cuts, cuts[1:]))
    return (np.concatenate([p[0] for p in parts]),
            np.concatenate([p[1] for p in parts]))


def wrap(core, params):
    """The public Dataset around an ingested core dataset."""
    import lightgbm_tpu as lgb
    ds = lgb.Dataset(None, params=params)
    ds._handle = core
    return ds


def warm_flag_pulls() -> None:
    """`gbdt._resolve_aligned_pending` pulls the queued exactness flags as
    one stacked array, 8 at a time in the loop and whatever is left at a
    drain. How many are left depends on the window's length, so every
    stack size is warmed here and none compiles in a window."""
    import jax
    import jax.numpy as jnp
    flag = jnp.asarray(True)
    for k in range(2, 9):
        jax.device_get(jnp.stack([flag] * k))


def device_facts(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peaks = load_json("benchmark", "peaks.json")
    kind = devs[0].device_kind
    if jax.default_backend() == "tpu" and kind not in peaks["devices"]:
        raise SystemExit(f"device kind {kind!r} is not in benchmark/peaks.json")
    stats = [d.memory_stats() or {} for d in devs]
    return {"platform": devs[0].platform, "kind": kind,
            "count": jax.device_count(),
            "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                     for s in stats)}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             overrides: dict = None, trace_dir: str = None) -> dict:
    """One run of one cell; returns the result line as a dict. `overrides`
    (the toy-size check only) replaces keys of the config and the traffic
    mix: {"config": {...}, "traffic": {...}, "params": {...}}."""
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu import compile_cache
    from lightgbm_tpu.config import Config

    overrides = overrides or {}
    cell, metrics, config, traffic = load_cell(workload)
    config = dict(config, **overrides.get("config", {}))
    traffic = dict(traffic, **overrides.get("traffic", {}))
    params = dict(config["params"], **traffic["params"], verbosity=1,
                  **overrides.get("params", {}))
    rows, holdout = int(config["rows"]), int(config["holdout_rows"])
    valid_rows = int(traffic["valid_rows"])
    on_chip = jax.default_backend() == "tpu"
    task = importlib.import_module(
        "benchmark.tasks." + config.get("task", "binary"))

    compile_cache.init_persistent_cache()
    events = Events()
    host_memory = HostMemory()
    host_memory.start()
    walls = {"start_s": time.perf_counter() - T0}
    gen = importlib.import_module(
        "benchmark.generators." + config["generator"]).Generator(
            config["generator_params"], seed)

    # ---- set-up: rows, binning, first iteration, warm-up
    cfg = Config.from_params(params)
    core, labels, groups, w = ingest(gen, cfg, rows, grouped=task.GROUPED)
    walls.update(ingest_bin_s=w["program_s"], ingest_wait_rows_s=w["wait_rows_s"],
                 ingest_phase_s=w["phase_s"])
    train_set = wrap(core, params)
    bst = lgb.Booster(params=params, train_set=train_set)
    if valid_rows:
        vcore, _, _, _ = ingest(gen, cfg, valid_rows, first_row=rows + holdout,
                                reference_ds=core, grouped=task.GROUPED)
        bst.add_valid(wrap(vcore, params), "valid")
    gc.collect()    # the generator's buffers, before the engine packs
    evals = []      # what the program said of the validation set, last

    def step(n):
        """n iterations, then the drain; host wall at each return."""
        marks = [time.perf_counter()]
        for _ in range(n):
            with jax.profiler.TraceAnnotation(SPAN + "update"):
                bst.update()
                if valid_rows:
                    evals[:] = bst.eval_valid()
            marks.append(time.perf_counter())
        with jax.profiler.TraceAnnotation(SPAN + "drain"):
            bst.eval_train()
        marks.append(time.perf_counter())
        return marks

    marks = step(1)
    walls["first_update_s"] = marks[-1] - marks[0]
    warm = int(traffic["warmup_iterations"]) - 1
    marks = step(warm)
    walls["warmup_s"] = marks[-1] - marks[0]
    walls["warmup_host_s"] = [round(b - a, 3) for a, b in zip(marks, marks[1:])]
    warm_flag_pulls()
    per_iter = walls["warmup_s"] / warm
    if trace:
        n_window = int(traffic["trace_iterations"])
    else:
        n_window = max(int(traffic["min_window_iterations"]),
                       int(seconds / per_iter))
    trees_before = bst.num_trees()
    fallbacks_before = events.count("aligned_fallback")
    before = compile_counts()
    host_lowest_available_gib = host_memory.stop()
    setup_s = time.perf_counter() - T0

    # ---- the window
    if trace:
        trace_dir = trace_dir or os.path.join(ROOT, "build", "benchmark_trace",
                                              workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # spans and device ops only
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        marks = step(n_window)
    finally:
        if trace:
            jax.profiler.stop_trace()
    window_s = marks[-1] - marks[0]
    in_window = {k: v - before[k] for k, v in compile_counts().items()}
    walls["window_host_s"] = [round(b - a, 3) for a, b in zip(marks, marks[1:])]

    # ---- what the run was, and whether it was right
    t_after = time.perf_counter()
    device = device_facts(cell["chips"])
    trees = bst.trees
    window_trees = trees[trees_before:]
    unsplit = sum(int(t.num_leaves) <= 1 for t in window_trees)
    fallbacks = events.count("aligned_fallback") - fallbacks_before
    paths = [r["path"] for r in events.of("train_path")]
    eng = getattr(bst._gbdt, "_aligned_eng_ref", None)
    checks = {
        "one_aligned_path": len(paths) == 1 and paths[0].startswith("aligned"),
        "no_fallback": events.count("aligned_fallback") == 0,
        "kernels_compiled": eng is not None
        and bool(eng.interpret) == (not on_chip),
        "every_tree_split": len(trees) == trees_before + n_window
        and all(int(t.num_leaves) > 1 for t in trees),
        "nothing_compiled_in_window": not any(in_window.values()),
    }

    x_hold, y_hold = gen.rows(rows, rows + holdout)
    quality_trees = int(traffic["auc_trees"])   # the key's name is PR 24's
    t = time.perf_counter()
    quality = task.quality(
        bst.predict(x_hold, num_iteration=quality_trees), y_hold,
        gen.groups(rows, holdout) if task.GROUPED else None)
    walls["predict_holdout_s"] = time.perf_counter() - t
    model = bst.dump_model()
    walk = reference.raw_scores(model, x_hold[:WALK_ROWS])
    checks["predict_equals_walk"] = bool(np.allclose(
        bst.predict(x_hold[:WALK_ROWS], raw_score=True), walk,
        rtol=1e-5, atol=1e-6))
    # each number compared, beside its limit: (number, limit, "<=" or ">=")
    quality_name = f"holdout_{task.QUALITY}_{quality_trees}"
    compared = {quality_name: (quality, float(config.get(
        "quality_floor", config.get("auc_floor"))), ">=")}
    first, first_detail = task.first_tree(types.SimpleNamespace(
        model=model, gen=gen, rows=rows, labels=labels, groups=groups,
        params=params, booster=bst))
    compared.update({k: (v, lim, "<=") for k, (v, lim) in first.items()})
    if valid_rows:
        # the program's last word on the validation set against the task's
        # quality of the numpy walk over the same rows, made again here
        want = task.quality(
            *walked(gen, model, rows + holdout, valid_rows),
            gen.groups(rows + holdout, valid_rows) if task.GROUPED else None)
        said = evals[0][2] if evals else float("nan")
        compared["valid_metric_err"] = (
            abs(said - want), float(traffic["valid_metric_tol"]), "<=")
    checks.update({k: (v <= lim if op == "<=" else v >= lim)
                   for k, (v, lim, op) in compared.items()})
    walls["checks_s"] = time.perf_counter() - t_after

    # ---- the line
    size = sizing.persistent_bytes(
        rows, gen.features, int(params["max_bin"]), params["objective"],
        int(params["num_leaves"]))
    if trace:
        wanted = metrics["per_layer"]
        ctx = {"walls": walls, "compiles": before,
               "iterations": n_window,
               "trace": xplane.window(
                   xplane.load(xplane.newest_xplane(trace_dir), SPAN))}
        values = {m["name"]: importlib.import_module(
            "benchmark.layer_metrics." + m["name"]).read(ctx) for m in wanted}
        device.update(ctx["trace"]["window"])
    else:
        wanted = metrics["end_to_end"]
        values = {"setup_s": setup_s,
                  "train_ms_per_iter": 1e3 * window_s / n_window,
                  quality_name: quality}
    checks = {k: bool(v) for k, v in checks.items()}
    result = {
        "correct": all(checks.values()),
        "attempted": n_window,
        "failed": fallbacks + unsplit,
        "metrics": {m["name"]: {"value": float(values[m["name"]]),
                                "unit": m["unit"]}
                    for m in wanted if values.get(m["name"]) is not None},
        "device": device,
    }
    if trace:
        result["breakdown"] = ctx["trace"]["breakdown"]
    # last on the line: every number compared, beside its limit; the yes
    # or no checks as 1 or 0 against 1
    result["compared"] = dict(
        {k: {"value": float(v), "limit": float(lim), "holds": op}
         for k, (v, lim, op) in compared.items()},
        **{k: {"value": int(v), "limit": 1, "holds": ">="}
           for k, v in checks.items() if k not in compared})
    result["detail"] = {
        "checks": checks, "walls": walls, "in_window": in_window,
        "compiles_in_setup": before, "compiles": compile_counts(),
        "missed": [r.get("module") for r in events.of("compile_cache_miss")],
        "window_s": window_s,
        quality_name: quality, "first_tree": first_detail,
        "valid_said": evals, "sizing": size,
        "sizing_persistent_gib": size["persistent_bytes"] / sizing.GIB,
        "measured_peak_gib": device["memory_peak_bytes"] / sizing.GIB,
        "peak_over_sizing_gib": sizing.held_over_gib(
            device["memory_peak_bytes"], size),
        "engine": None if eng is None else {
            "chunk": int(eng.C), "lanes": int(eng.W), "chunks": int(eng.NC),
            "bits": int(eng.bits), "compact": bool(eng.compact),
            # set as the build program is traced: absent from a second
            # engine of one process that found the program made
            "hist_spill": getattr(eng, "hist_spill", None),
            "hist_subbin": getattr(eng, "hist_subbin", None)},
        "leaves": [int(t.num_leaves) for t in trees],
        "host_peak_rss_gib": resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss * 1024 / sizing.GIB,
        "host_lowest_available_gib": host_lowest_available_gib,
        "kernel_calls_per_iter": {k: v / n_window for k, v in
                                  ctx["trace"]["counts"].items()}
        if trace else None,
        "total_s": time.perf_counter() - T0,
    }
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)[0]
    import jax
    if jax.default_backend() != "tpu" or jax.device_count() < cell["chips"]:
        print(f"benchmark: needs {cell['chips']} TPU chip(s); found backend "
              f"{jax.default_backend()!r} with {jax.device_count()} device(s)",
              file=sys.stderr)
        return 1
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    detail = result.pop("detail")
    say("detail: " + json.dumps(detail, default=lambda o: o.item()))
    say(json.dumps(result))
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} {c['holds']} {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
