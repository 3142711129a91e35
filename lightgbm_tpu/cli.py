"""Command-line application: ``python -m lightgbm_tpu config=train.conf``.

Re-creates the reference CLI (`src/main.cpp`, `src/application/
application.cpp`): ``key=value`` args with a ``config=`` file
(`LoadParameters` `application.cpp:48-81`), task dispatch
train/predict/convert_model/refit (`application.h:78-88`), periodic
snapshots (`gbdt.cpp:289-293`), and prediction-result files compatible with
`Predictor` output (`src/application/predictor.hpp`).

The reference `examples/*/train.conf` files run unchanged. Where the
reference rendezvouses a TCP/MPI network for ``num_machines > 1``
(`application.cpp:166-200`), this build shards rows over the local
`jax.sharding.Mesh` — multi-host execution uses JAX distributed
initialization instead of a machine list file.
"""
from __future__ import annotations

import os
import sys
from typing import Dict, List, Optional

import numpy as np

from .basic import Booster, Dataset, LightGBMError
from .config import Config
from .engine import train as engine_train
from .io.loader import DatasetLoader


def parse_cli_args(argv: List[str]) -> Dict[str, str]:
    """``key=value`` tokens; ``config=file`` pulls in a config file whose
    entries CLI args override (reference `Application::LoadParameters`)."""
    cli: Dict[str, str] = {}
    for tok in argv:
        tok = tok.strip()
        if not tok or tok.startswith("#"):
            continue
        if "=" not in tok:
            raise LightGBMError(f"Unknown CLI argument: {tok!r}")
        k, v = tok.split("=", 1)
        cli[k.strip()] = v.strip()
    params: Dict[str, str] = {}
    conf_file = cli.get("config", cli.get("config_file", ""))
    if conf_file:
        params.update(read_config_file(conf_file))
    params.update(cli)  # CLI wins over config file
    params.pop("config", None)
    params.pop("config_file", None)
    return params


def read_config_file(path: str) -> Dict[str, str]:
    """``key = value`` lines, ``#`` comments (reference `Config::KV2Map`)."""
    if not os.path.isfile(path):
        raise LightGBMError(f"Config file {path} doesn't exist")
    out: Dict[str, str] = {}
    with open(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if not line or "=" not in line:
                continue
            k, v = line.split("=", 1)
            out[k.strip()] = v.strip()
    return out


def _wrap_core(core, params) -> Dataset:
    d = Dataset(None, params=dict(params))
    d._handle = core
    d.free_raw_data = False
    return d


class Application:
    """reference `Application` (`include/LightGBM/application.h:35-92`)."""

    def __init__(self, argv: List[str]) -> None:
        self.raw_params = parse_cli_args(argv)
        self.config = Config.from_params(self.raw_params)
        if self.config.num_threads > 0:
            os.environ.setdefault("OMP_NUM_THREADS",
                                  str(self.config.num_threads))

    # ------------------------------------------------------------------
    def run(self) -> int:
        task = self.config.task
        if task == "train":
            return self.train()
        elif task in ("predict", "prediction", "test"):
            self.predict()
        elif task in ("convert_model",):
            self.convert_model()
        elif task == "refit":
            self.refit()
        elif task == "serve":
            return self.serve()
        else:
            raise LightGBMError(f"Unknown task type {task}")
        return 0

    # ------------------------------------------------------------------
    def _load_train_data(self):
        cfg = self.config
        if not cfg.data:
            raise LightGBMError("No training data: set data=<file>")
        predict_fun = None
        if cfg.input_model and os.path.isfile(cfg.input_model):
            # continued training: prior model's raw predictions become the
            # init score (reference application.cpp:90-93)
            prior = Booster(model_file=cfg.input_model)
            predict_fun = lambda X: prior.predict(X, raw_score=True)  # noqa: E731
        loader = DatasetLoader(cfg, predict_fun=predict_fun)
        core = loader.load_from_file(cfg.data)
        ing = getattr(core, "_ingest_stats", None)
        if ing:
            print(f"Streamed ingest: {ing['rows']} rows in chunks of "
                  f"{ing['chunk_rows']} ({ing['device_cols']} "
                  f"device-binned + {ing['host_cols']} host-binned "
                  f"columns, "
                  f"{getattr(core, '_ingest_ms', 0.0) / 1e3:.1f} s)")
        train_set = _wrap_core(core, self.raw_params)
        valid_sets, valid_names = [], []
        for vf in cfg.valid:
            vcore = loader.load_from_file_align_with_other_dataset(vf, core)
            valid_sets.append(_wrap_core(vcore, self.raw_params))
            valid_names.append(os.path.basename(vf))
        return train_set, valid_sets, valid_names

    def train(self) -> int:
        cfg = self.config
        if cfg.tpu_trace:
            # enable the file-backed tracer BEFORE data load: ingest
            # fires its events (stream_ingest / dist_stream / dist_init)
            # during dataset construction, and the timeline's events tee
            # only captures what happens after the trace dir exists
            # (GBDT.__init__'s own enable() call is an idempotent no-op)
            from .obs import trace as obs_trace
            obs_trace.enable(cfg.tpu_trace_dir or "lgbt_trace")
        train_set, valid_sets, valid_names = self._load_train_data()
        if cfg.is_provide_training_metric:
            valid_sets = [train_set] + valid_sets
            valid_names = ["training"] + valid_names
        callbacks = []
        if cfg.snapshot_freq > 0 and cfg.output_model \
                and not cfg.tpu_checkpoint_dir:
            # legacy model-only snapshots; with tpu_checkpoint_dir the
            # engine writes full-state checkpoints instead
            callbacks.append(_snapshot_callback(cfg.output_model,
                                                cfg.snapshot_freq,
                                                cfg.tpu_snapshot_keep))
        if cfg.tpu_trace:
            # CLI traced runs re-emit each round record on the
            # structured channel at metric frequency (snapshot-style:
            # progress is observable mid-run, not only at the end)
            from .callback import log_telemetry
            callbacks.append(log_telemetry(period=max(1, cfg.metric_freq)))
        booster = engine_train(
            dict(self.raw_params), train_set,
            num_boost_round=cfg.num_iterations,
            valid_sets=valid_sets, valid_names=valid_names,
            init_model=(cfg.input_model or None),
            verbose_eval=max(1, cfg.metric_freq),
            callbacks=callbacks)
        out = cfg.output_model or "LightGBM_model.txt"
        booster.save_model(out)
        if cfg.tpu_trace:
            from . import compile_cache
            from .obs import trace as obs_trace
            tdir = cfg.tpu_trace_dir or "lgbt_trace"
            # fold the compile-cache story in next to the spans: total
            # persistent-cache hits/misses, which attributed program
            # each miss blamed, and the process trace count
            extra = {"compile_cache": {
                **compile_cache.persistent_cache_events(),
                "miss_by_program": compile_cache.miss_attribution(),
                "traces": compile_cache.trace_count(),
                "cache_dir": compile_cache.persistent_cache_dir(),
            }}
            dump = obs_trace.write(
                os.path.join(tdir, "trace_summary.json"), extra=extra)
            print(f"Telemetry: span summary at {dump}")
            from .obs import timeline as obs_timeline
            if obs_timeline.timeline_on(cfg):
                tl = obs_timeline.build_timeline(tdir)
                tpath = obs_timeline.write_timeline(
                    os.path.join(tdir, "timeline.json"), tl)
                print(f"Telemetry: run timeline at {tpath} "
                      f"(open in Perfetto / chrome://tracing)")
        if getattr(booster, "_preempted", False):
            from .resilience import EXIT_PREEMPTED
            print(f"Preempted mid-training; checkpoint flushed. "
                  f"Partial model saved to {out} — rerun the same "
                  f"command to resume.")
            return EXIT_PREEMPTED
        print(f"Finished training. Model saved to {out}")
        return 0

    # ------------------------------------------------------------------
    def predict(self) -> None:
        cfg = self.config
        if not cfg.input_model:
            raise LightGBMError("No model file: set input_model=<file>")
        if not cfg.data:
            raise LightGBMError("No prediction data: set data=<file>")
        booster = Booster(params=dict(self.raw_params),
                          model_file=cfg.input_model)
        num_iteration = cfg.num_iteration_predict
        # hand the PATH to Booster.predict: its file branch carries the
        # reference's label-free detection (a file whose column count
        # equals the model's feature count has no label column to strip,
        # predictor.hpp:185) which a direct DatasetLoader.parse_file
        # call would skip, silently shifting every feature by one
        preds = booster.predict(
            cfg.data,
            num_iteration=(num_iteration if num_iteration > 0 else None),
            raw_score=cfg.predict_raw_score,
            pred_leaf=cfg.predict_leaf_index,
            pred_contrib=cfg.predict_contrib,
            start_iteration=cfg.start_iteration_predict,
            tpu_predict_device=cfg.tpu_predict_device)
        out = cfg.output_result or "LightGBM_predict_result.txt"
        arr = np.atleast_1d(np.asarray(preds))
        from .io.file_io import open_file
        with open_file(out, "w") as f:
            if arr.ndim == 1:
                for v in arr:
                    f.write(f"{v:g}\n")
            else:
                for row in arr:
                    f.write("\t".join(f"{v:g}" for v in row) + "\n")
        print(f"Finished prediction. Results saved to {out}")

    # ------------------------------------------------------------------
    def convert_model(self) -> None:
        cfg = self.config
        if not cfg.input_model:
            raise LightGBMError("No model file: set input_model=<file>")
        from .models.model_text import model_to_if_else
        booster = Booster(model_file=cfg.input_model)
        out = cfg.convert_model or "gbdt_prediction.cpp"
        code = model_to_if_else(booster.trees,
                                booster.num_tree_per_iteration,
                                average_output=booster._is_average_output())
        from .io.file_io import open_file
        with open_file(out, "w") as f:
            f.write(code)
        print(f"Finished converting model. Code saved to {out}")

    # ------------------------------------------------------------------
    def serve(self) -> int:
        """Batch-mode driver for the serving service (serving/): load the
        named models (``input_model=name=file[,name2=file2]``; a bare
        path serves under its basename) and/or watch a checkpoint
        directory (``tpu_checkpoint_dir=`` — hot-swaps while running),
        then score ``data=`` through the request coalescer into
        ``output_result``. Scores are RAW margins (the service
        contract), i.e. what ``task=predict predict_raw_score=true``
        writes. With no data file the models are loaded, stats print,
        and the process exits — a smoke/validation mode."""
        import json
        cfg = self.config
        from .serving import ServingService
        if not cfg.input_model and not cfg.tpu_checkpoint_dir:
            raise LightGBMError(
                "task=serve needs input_model=<[name=]file,...> and/or "
                "tpu_checkpoint_dir=<dir>")
        svc = ServingService(params=dict(self.raw_params))
        try:
            names: List[str] = []
            if cfg.input_model:
                for i, spec in enumerate(
                        s.strip() for s in cfg.input_model.split(",")
                        if s.strip()):
                    if "=" in spec:
                        name, path = (t.strip()
                                      for t in spec.split("=", 1))
                    else:
                        path = spec
                        name = os.path.splitext(
                            os.path.basename(spec))[0] or f"model{i}"
                    svc.load_model(name, model_file=path)
                    names.append(name)
            if cfg.tpu_checkpoint_dir:
                svc.watch("checkpoint", cfg.tpu_checkpoint_dir)
                if svc.registry.get("checkpoint") is None:
                    raise LightGBMError(
                        f"no readable checkpoint manifest under "
                        f"{cfg.tpu_checkpoint_dir}")
                names.append("checkpoint")
            if cfg.data:
                loader = DatasetLoader(cfg)
                _labels, feats, _ex = loader.parse_file(cfg.data)
                target = names[0]
                req_rows = max(min(cfg.tpu_serve_max_batch_rows, 1024), 1)
                futs = [svc.predict_async(target, feats[s:s + req_rows])
                        for s in range(0, len(feats), req_rows)]
                preds = np.concatenate([np.atleast_1d(f.result(timeout=600))
                                        for f in futs], axis=0)
                out = cfg.output_result or "LightGBM_predict_result.txt"
                from .io.file_io import open_file
                with open_file(out, "w") as f:
                    if preds.ndim == 1:
                        for v in preds:
                            f.write(f"{v:g}\n")
                    else:
                        for row in preds:
                            f.write("\t".join(f"{v:g}" for v in row) + "\n")
                print(f"Finished serving {len(preds)} rows on "
                      f"{target!r}. Results saved to {out}")
            print("Serving stats: "
                  + json.dumps(svc.stats(), sort_keys=True, default=str))
            ac = svc.registry.aot_compact_stats()
            if any(m["aot"]["buckets"] or m["compact"]["plan"] != "off"
                   for m in ac.values()):
                print("Serving aot/compact: "
                      + json.dumps(ac, sort_keys=True, default=str))
            if svc.exporter is not None:
                print(f"Metrics: {svc.exporter.url}/metrics "
                      f"(Prometheus) and /metrics.json", flush=True)
                if svc.tracer is not None:
                    print(f"Request traces: {svc.exporter.url}"
                          f"/debug/requests", flush=True)
            if svc.frontend is not None:
                print(f"Scoring: POST {svc.frontend.url}/v1/score/"
                      f"<model> (health: {svc.frontend.url}/healthz)",
                      flush=True)
            if cfg.tpu_serve_hold_s > 0:
                # scrape/hot-swap window: hold the service up, exit
                # early and cleanly on Ctrl-C / SIGTERM
                import time as _time
                print(f"Holding for {cfg.tpu_serve_hold_s:g}s "
                      f"(tpu_serve_hold_s)...", flush=True)
                try:
                    _time.sleep(cfg.tpu_serve_hold_s)
                except KeyboardInterrupt:
                    pass
        finally:
            svc.close()
        return 0

    # ------------------------------------------------------------------
    def refit(self) -> None:
        cfg = self.config
        if not cfg.input_model:
            raise LightGBMError("No model file: set input_model=<file>")
        if not cfg.data:
            raise LightGBMError("No refit data: set data=<file>")
        booster = Booster(model_file=cfg.input_model,
                          params=dict(self.raw_params))
        loader = DatasetLoader(cfg)
        labels, feats, _ex = loader.parse_file(cfg.data)
        leaf_preds = booster.predict(feats, pred_leaf=True)
        booster.refit(feats, labels, decay_rate=cfg.refit_decay_rate,
                      leaf_preds=leaf_preds)
        out = cfg.output_model or "LightGBM_model.txt"
        booster.save_model(out)
        print(f"Finished refitting. Model saved to {out}")


def _snapshot_callback(output_model: str, freq: int, keep: int = 3):
    """Periodic model snapshots (reference gbdt.cpp:289-293), written
    atomically (tmp + rename — a kill mid-write never leaves a torn
    snapshot) with rolling retention of the newest `keep` files."""
    from .resilience import atomic_write_text, prune_snapshots

    def _cb(env):
        it = env.iteration + 1
        if it % freq == 0:
            atomic_write_text(f"{output_model}.snapshot_iter_{it}",
                              env.model.model_to_string())
            prune_snapshots(output_model, keep)
    _cb.order = 100
    return _cb


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv:
        print("Usage: python -m lightgbm_tpu config=train.conf [key=value ...]")
        return 1
    try:
        rc = Application(argv).run()
    except LightGBMError as e:
        print(f"[LightGBM-TPU] [Fatal] {e}", file=sys.stderr)
        return 1
    return int(rc or 0)


if __name__ == "__main__":
    sys.exit(main())
