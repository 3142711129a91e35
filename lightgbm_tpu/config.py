"""Typed configuration for the TPU GBDT framework.

Re-creates the parameter surface of the reference `struct Config`
(`include/LightGBM/config.h:31+`, parsing in `src/io/config.cpp:15-283`,
alias table generated into `src/io/config_auto.cpp`): a single flat config with
key=value parsing, alias expansion, and conflict checks, so that reference
`train.conf` files and `lgb.train(params={...})` dicts work unchanged.

TPU-specific additions are grouped at the bottom (histogram precision,
pallas toggle, mesh axes) — the analogue of the reference's `gpu_*` block
(`config.h:818-826`).
"""
from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

# ---------------------------------------------------------------------------
# Alias table: maps every accepted alias to the canonical parameter name.
# Mirrors the generated table in the reference `src/io/config_auto.cpp`
# (source comments `include/LightGBM/config.h`, e.g. `alias = ...` lines).
# ---------------------------------------------------------------------------
_ALIASES: Dict[str, str] = {
    "config_file": "config",
    "task_type": "task",
    "objective_type": "objective", "app": "objective", "application": "objective",
    "boosting_type": "boosting", "boost": "boosting",
    "train": "data", "train_data": "data", "train_data_file": "data",
    "data_filename": "data",
    "test": "valid", "valid_data": "valid", "valid_data_file": "valid",
    "test_data": "valid", "test_data_file": "valid", "valid_filenames": "valid",
    "num_iteration": "num_iterations", "n_iter": "num_iterations",
    "num_tree": "num_iterations", "num_trees": "num_iterations",
    "num_round": "num_iterations", "num_rounds": "num_iterations",
    "num_boost_round": "num_iterations", "n_estimators": "num_iterations",
    "shrinkage_rate": "learning_rate", "eta": "learning_rate",
    "num_leaf": "num_leaves", "max_leaves": "num_leaves", "max_leaf": "num_leaves",
    "tree": "tree_learner", "tree_type": "tree_learner",
    "tree_learner_type": "tree_learner",
    "num_thread": "num_threads", "nthread": "num_threads",
    "nthreads": "num_threads", "n_jobs": "num_threads",
    "device": "device_type",
    "random_seed": "seed", "random_state": "seed",
    "min_data_per_leaf": "min_data_in_leaf", "min_data": "min_data_in_leaf",
    "min_child_samples": "min_data_in_leaf",
    "min_sum_hessian_per_leaf": "min_sum_hessian_in_leaf",
    "min_sum_hessian": "min_sum_hessian_in_leaf",
    "min_hessian": "min_sum_hessian_in_leaf",
    "min_child_weight": "min_sum_hessian_in_leaf",
    "sub_row": "bagging_fraction", "subsample": "bagging_fraction",
    "bagging": "bagging_fraction",
    "pos_sub_row": "pos_bagging_fraction", "pos_subsample": "pos_bagging_fraction",
    "pos_bagging": "pos_bagging_fraction",
    "neg_sub_row": "neg_bagging_fraction", "neg_subsample": "neg_bagging_fraction",
    "neg_bagging": "neg_bagging_fraction",
    "subsample_freq": "bagging_freq",
    "bagging_fraction_seed": "bagging_seed",
    "sub_feature": "feature_fraction", "colsample_bytree": "feature_fraction",
    "early_stopping_rounds": "early_stopping_round",
    "early_stopping": "early_stopping_round",
    "max_tree_output": "max_delta_step", "max_leaf_output": "max_delta_step",
    "reg_alpha": "lambda_l1",
    "reg_lambda": "lambda_l2", "lambda": "lambda_l2",
    "min_split_gain": "min_gain_to_split",
    "rate_drop": "drop_rate",
    "topk": "top_k",
    "mc": "monotone_constraints", "monotone_constraint": "monotone_constraints",
    "feature_contrib": "feature_contri", "fc": "feature_contri",
    "fp": "feature_contri", "feature_penalty": "feature_contri",
    "fs": "forcedsplits_filename", "forced_splits_filename": "forcedsplits_filename",
    "forced_splits_file": "forcedsplits_filename",
    "forced_splits": "forcedsplits_filename",
    "verbose": "verbosity",
    "subsample_for_bin": "bin_construct_sample_cnt",
    "hist_pool_size": "histogram_pool_size",
    "data_seed": "data_random_seed",
    "model_output": "output_model", "model_out": "output_model",
    "save_period": "snapshot_freq",
    "model_input": "input_model", "model_in": "input_model",
    "predict_result": "output_result", "prediction_result": "output_result",
    "predict_name": "output_result", "prediction_name": "output_result",
    "pred_name": "output_result", "name_pred": "output_result",
    "init_score_filename": "initscore_filename",
    "init_score_file": "initscore_filename", "init_score": "initscore_filename",
    "input_init_score": "initscore_filename",
    "valid_data_init_scores": "valid_initscore_filenames",
    "valid_data_initscores": "valid_initscore_filenames",
    "valid_init_score_file": "valid_initscore_filenames",
    "valid_init_score": "valid_initscore_filenames",
    "is_pre_partition": "pre_partition",
    "is_enable_bundle": "enable_bundle", "bundle": "enable_bundle",
    "is_sparse": "is_enable_sparse", "enable_sparse": "is_enable_sparse",
    "sparse": "is_enable_sparse",
    "two_round_loading": "two_round", "use_two_round_loading": "two_round",
    "is_save_binary": "save_binary", "is_save_binary_file": "save_binary",
    "has_header": "header",
    "label": "label_column",
    "weight": "weight_column",
    "group": "group_column", "group_id": "group_column",
    "query_column": "group_column", "query": "group_column",
    "query_id": "group_column",
    "ignore_feature": "ignore_column", "blacklist": "ignore_column",
    "cat_feature": "categorical_feature",
    "categorical_column": "categorical_feature",
    "cat_column": "categorical_feature",
    "is_predict_raw_score": "predict_raw_score",
    "predict_rawscore": "predict_raw_score", "raw_score": "predict_raw_score",
    "is_predict_leaf_index": "predict_leaf_index",
    "leaf_index": "predict_leaf_index",
    "is_predict_contrib": "predict_contrib", "contrib": "predict_contrib",
    "convert_model_file": "convert_model",
    "num_classes": "num_class",
    "unbalance": "is_unbalance", "unbalanced_sets": "is_unbalance",
    "metrics": "metric", "metric_types": "metric",
    "output_freq": "metric_freq",
    "training_metric": "is_provide_training_metric",
    "is_training_metric": "is_provide_training_metric",
    "train_metric": "is_provide_training_metric",
    "ndcg_eval_at": "eval_at", "ndcg_at": "eval_at", "map_eval_at": "eval_at",
    "map_at": "eval_at",
    "num_machine": "num_machines",
    "local_port": "local_listen_port", "port": "local_listen_port",
    "machine_list_file": "machine_list_filename",
    "machine_list": "machine_list_filename", "mlist": "machine_list_filename",
    "workers": "machines", "nodes": "machines",
}

# objective-name aliases (reference `config.h:106-126` descl2 lines,
# normalization in `src/objective/objective_function.cpp` / ParseObjectiveAlias)
_OBJECTIVE_ALIASES: Dict[str, str] = {
    "regression": "regression", "regression_l2": "regression",
    "mean_squared_error": "regression", "mse": "regression",
    "l2": "regression", "l2_root": "regression",
    "root_mean_squared_error": "regression", "rmse": "regression",
    "regression_l1": "regression_l1", "l1": "regression_l1",
    "mean_absolute_error": "regression_l1", "mae": "regression_l1",
    "huber": "huber", "fair": "fair", "poisson": "poisson",
    "quantile": "quantile",
    "mape": "mape", "mean_absolute_percentage_error": "mape",
    "gamma": "gamma", "tweedie": "tweedie",
    "binary": "binary",
    "multiclass": "multiclass", "softmax": "multiclass",
    "multiclassova": "multiclassova", "multiclass_ova": "multiclassova",
    "ova": "multiclassova", "ovr": "multiclassova",
    "xentropy": "xentropy", "cross_entropy": "xentropy",
    "xentlambda": "xentlambda", "cross_entropy_lambda": "xentlambda",
    "lambdarank": "lambdarank",
    "none": "none", "null": "none", "custom": "none", "na": "none",
}

_METRIC_ALIASES: Dict[str, str] = {
    "l1": "l1", "mean_absolute_error": "l1", "mae": "l1", "regression_l1": "l1",
    "l2": "l2", "mean_squared_error": "l2", "mse": "l2", "regression_l2": "l2",
    "regression": "l2",
    "l2_root": "rmse", "root_mean_squared_error": "rmse", "rmse": "rmse",
    "quantile": "quantile", "mape": "mape",
    "mean_absolute_percentage_error": "mape",
    "huber": "huber", "fair": "fair", "poisson": "poisson",
    "gamma": "gamma", "gamma_deviance": "gamma_deviance", "tweedie": "tweedie",
    "ndcg": "ndcg", "lambdarank": "ndcg",
    "map": "map", "mean_average_precision": "map",
    "auc": "auc",
    "binary_logloss": "binary_logloss", "binary": "binary_logloss",
    "binary_error": "binary_error",
    "multi_logloss": "multi_logloss", "multiclass": "multi_logloss",
    "softmax": "multi_logloss", "multiclassova": "multi_logloss",
    "multiclass_ova": "multi_logloss", "ova": "multi_logloss",
    "ovr": "multi_logloss",
    "multi_error": "multi_error",
    "xentropy": "xentropy", "cross_entropy": "xentropy",
    "xentlambda": "xentlambda", "cross_entropy_lambda": "xentlambda",
    "kldiv": "kldiv", "kullback_leibler": "kldiv",
    "none": "none", "na": "none", "null": "none", "custom": "none",
}

_TREE_LEARNER_ALIASES: Dict[str, str] = {
    "serial": "serial",
    "feature": "feature", "feature_parallel": "feature",
    "data": "data", "data_parallel": "data",
    "voting": "voting", "voting_parallel": "voting",
}

_BOOSTING_ALIASES: Dict[str, str] = {
    "gbdt": "gbdt", "gbrt": "gbdt",
    "dart": "dart",
    "goss": "goss",
    "rf": "rf", "random_forest": "rf",
}

_DEVICE_ALIASES: Dict[str, str] = {
    "cpu": "cpu", "gpu": "tpu", "tpu": "tpu",
}


def _kv_list(value: Any, typ) -> list:
    """Parse 'a,b,c' strings / sequences into a typed list."""
    if value is None or value == "":
        return []
    if isinstance(value, str):
        parts = [p for p in value.replace(" ", "").split(",") if p != ""]
        return [typ(p) for p in parts]
    if isinstance(value, (list, tuple)):
        return [typ(v) for v in value]
    return [typ(value)]


def _to_bool(v: Any) -> bool:
    if isinstance(v, bool):
        return v
    if isinstance(v, str):
        return v.strip().lower() in ("true", "1", "yes", "+")
    return bool(v)


@dataclass
class Config:
    """All training/IO/prediction parameters (reference `config.h:31+`)."""

    # --- core (config.h:84-208)
    task: str = "train"
    objective: str = "regression"
    boosting: str = "gbdt"
    data: str = ""
    valid: List[str] = field(default_factory=list)
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_leaves: int = 31
    tree_learner: str = "serial"
    num_threads: int = 0
    device_type: str = "tpu"
    seed: int = 0

    # --- learning control (config.h:210-435)
    max_depth: int = -1
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    bagging_fraction: float = 1.0
    pos_bagging_fraction: float = 1.0
    neg_bagging_fraction: float = 1.0
    bagging_freq: int = 0
    bagging_seed: int = 3
    feature_fraction: float = 1.0
    feature_fraction_seed: int = 2
    early_stopping_round: int = 0
    first_metric_only: bool = False
    max_delta_step: float = 0.0
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    # boosting=dart (dart.hpp:97-196): an iteration that does not skip
    # (skip_drop) drops every earlier tree with probability drop_rate x
    # its weight / the mean weight (uniform_drop: drop_rate), at most
    # max_drop of them, grows its tree without them at learning_rate /
    # (1 + k) and puts the k back at k / (k + 1) of their weight
    # (xgboost_dart_mode: learning_rate / (learning_rate + k) and k / (k +
    # learning_rate)). On the aligned engine dropped trees leave and
    # re-enter the score lane through a walk of the committed trees over
    # the records as they lie (ops/aligned.py walk_pass), trees stay on
    # the device and the drop set rides each queued round. Multiclass,
    # tree_learner=data, categorical features and non-pointwise
    # objectives keep boosting=dart off the engine, on the fused
    # leaf-wise loop (the train_path event's `rejected` says which)
    drop_rate: float = 0.1
    max_drop: int = 50
    skip_drop: float = 0.5
    xgboost_dart_mode: bool = False
    uniform_drop: bool = False
    drop_seed: int = 4
    # boosting=goss (goss.hpp:96-160): after the first 1 / learning_rate
    # iterations every row whose |gradient x hessian| is at least the
    # int(n x top_rate)-th largest trains at weight 1 (ties at the
    # threshold are all kept). On the aligned engine the selection is a
    # device program over the records (two counting selects, no sort,
    # nothing pulled: models/aligned_builder.py goss_select); the fused
    # leaf-wise path and the sweep trainer run the same ops/goss.py in
    # row order. Multiclass and tree_learner=data keep off the aligned
    # engine under goss (the train_path event's `rejected` says so)
    top_rate: float = 0.2
    # boosting=goss: of the rows under the top_rate threshold, the
    # int(n x other_rate) with the smallest key (an integer function of
    # the row id and the iteration's seed, drawn from bagging_seed's
    # stream) train at weight (n - top_k) / other_k; every other row is
    # left out of the tree's histograms and counts, and still scored
    other_rate: float = 0.1
    min_data_per_group: int = 100
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_to_onehot: int = 4
    top_k: int = 20
    monotone_constraints: List[int] = field(default_factory=list)
    feature_contri: List[float] = field(default_factory=list)
    forcedsplits_filename: str = ""
    refit_decay_rate: float = 0.9
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0
    cegb_penalty_feature_lazy: List[float] = field(default_factory=list)
    cegb_penalty_feature_coupled: List[float] = field(default_factory=list)
    verbosity: int = 1

    # --- IO / dataset (config.h:437-600)
    max_bin: int = 255
    min_data_in_bin: int = 3
    bin_construct_sample_cnt: int = 200000
    histogram_pool_size: float = -1.0
    data_random_seed: int = 1
    output_model: str = "LightGBM_model.txt"
    snapshot_freq: int = -1
    input_model: str = ""
    output_result: str = "LightGBM_predict_result.txt"
    initscore_filename: str = ""
    valid_initscore_filenames: List[str] = field(default_factory=list)
    pre_partition: bool = False
    enable_bundle: bool = True
    max_conflict_rate: float = 0.0
    is_enable_sparse: bool = True
    sparse_threshold: float = 0.8
    use_missing: bool = True
    zero_as_missing: bool = False
    two_round: bool = False
    save_binary: bool = False
    header: bool = False
    label_column: str = ""
    weight_column: str = ""
    group_column: str = ""
    ignore_column: str = ""
    categorical_feature: str = ""

    # --- prediction (config.h:602-648)
    predict_raw_score: bool = False
    predict_leaf_index: bool = False
    predict_contrib: bool = False
    num_iteration_predict: int = -1
    start_iteration_predict: int = 0
    pred_early_stop: bool = False
    pred_early_stop_freq: int = 10
    pred_early_stop_margin: float = 10.0
    convert_model_language: str = ""
    convert_model: str = "gbdt_prediction.cpp"

    # --- objective (config.h:650-722)
    num_class: int = 1
    is_unbalance: bool = False
    scale_pos_weight: float = 1.0
    sigmoid: float = 1.0
    boost_from_average: bool = True
    reg_sqrt: bool = False
    alpha: float = 0.9
    fair_c: float = 1.0
    poisson_max_delta_step: float = 0.7
    tweedie_variance_power: float = 1.5
    max_position: int = 20
    label_gain: List[float] = field(default_factory=list)

    # --- metric (config.h:724-780)
    metric: List[str] = field(default_factory=list)
    metric_freq: int = 1
    is_provide_training_metric: bool = False
    eval_at: List[int] = field(default_factory=lambda: [1, 2, 3, 4, 5])
    multi_error_top_k: int = 1

    # --- network (config.h:782-809)
    num_machines: int = 1
    local_listen_port: int = 12400
    time_out: int = 120
    machine_list_filename: str = ""
    machines: str = ""

    # --- device: TPU block (replaces gpu_platform_id/gpu_device_id/gpu_use_dp,
    #     config.h:811-826)
    gpu_platform_id: int = -1
    gpu_device_id: int = -1
    gpu_use_dp: bool = False
    # accumulate histograms and root grad/hess sums in f64 (2x pass).
    # f64 sums of f32 gradients are exact at any realistic leaf size, so
    # the per-shard partials entering a cross-device all-reduce are
    # order-independent: a distributed run produces byte-identical model
    # text to a single-device run (the parity contract of the dist/
    # runtime — see docs/Distributed.md). Off by default: the bf16x2
    # MXU path is ~f32-accurate and faster on TPU
    tpu_use_f64_hist: bool = False
    # device count for the distributed runtime (dist/runtime.py): 0 =
    # derive from num_machines (>1) or use every visible device when a
    # non-serial tree_learner is selected; N > 0 = shard over exactly
    # the first N devices. Runtime-only topology: does not change the
    # trained model (see tpu_use_f64_hist) and is excluded from model
    # text and checkpoint signatures
    tpu_dist_devices: int = 0
    tpu_hist_chunk: int = 1 << 16        # rows per histogram matmul chunk
    # pallas VMEM-resident histogram kernel (ops/pallas_hist.py, the
    # ocl/histogram256.cl analogue): the one-hot tile never leaves VMEM,
    # vs the XLA einsum path whose chunk one-hots round-trip through HBM
    tpu_use_pallas: bool = True
    # trace gradients + tree build + score update as ONE program per
    # boosting iteration (saves per-program launch latency, but XLA
    # compile time for the merged program is prohibitive
    # at large row counts — measured >15 min at 10.5M rows vs 132 s for
    # the split programs; enable only for small/medium datasets)
    tpu_fuse_iteration: bool = False
    # tree growth strategy. "leafwise" (default): the strictly sequential
    # reference order (serial_tree_learner.cpp:173-237) as one fused
    # whole-tree device program. "level"/"auto": the speculative
    # level-batched builder (models/level_builder.py) — exact leaf-wise
    # via host replay with automatic fallback — kept opt-in: on v5e its
    # per-round full-array passes (fills + record-carrying sort) measure
    # on par with the leaf-wise program, not faster
    # "aligned"/"auto": the chunk-aligned record pipeline
    # (models/aligned_builder.py + ops/aligned.py Pallas kernels) — exact
    # leaf-wise via host replay; measured ~4x faster per round than the
    # sort-based level builder on v5e. Auto picks aligned when its
    # restrictions hold (numerical features, pointwise single-class
    # objective; bagging IS supported) and a TPU is attached, else
    # leafwise.
    tpu_grow_mode: str = "auto"
    # speculation slots as a multiple of num_leaves for the level/aligned
    # builders; larger values let the exact leaf-wise replay absorb more
    # speculation churn before falling back. LATE-training iterations
    # speculate far more than early ones (gains converge and tie): a
    # full 500-iteration HIGGS-shape run at 3.0 fell back 106 times
    # after iteration ~100, while 4.5 measured ZERO fallbacks at both
    # 63 and 255 bins for ~5% per-iteration glue cost. Lowering this
    # trades that margin back for speed on short trainings.
    tpu_level_spec: float = 4.5
    tpu_min_pad: int = 1024              # smallest padded leaf size (compile cache)
    tpu_chunk: int = 0                   # aligned rows/chunk (0 = auto)
    # run the aligned pipeline's Pallas kernels in interpret mode (CPU
    # testing only — orders of magnitude slower than the TPU kernels)
    tpu_aligned_interpret: bool = False
    tpu_mesh_axis: str = "data"          # mesh axis name for row sharding
    # serving-engine policy for Booster.predict (serve/ForestEngine):
    # "on" always scores on device via the depth-synchronized stacked
    # forest; "off" keeps the host/native walk; "auto" prefers the engine
    # on accelerator backends and falls back to it on CPU only when the
    # native predictor is unavailable and the batch is large enough to
    # amortize a compile
    tpu_predict_device: str = "auto"
    # force the aligned builder's big-n record (the standard layout and
    # its route-word repack, normally n > 2^24 only) at any row count so
    # the path is testable on small data (VERDICT r5 #7). It forces no
    # count pass: the histograms count in exact i32 at any row count
    tpu_force_big_n: bool = False
    # sub-binned histogram accumulation for bin widths above 128 (the
    # 255-bin hot path): the bin index splits into hi/lo 4-bit halves and
    # each (row, feature) costs two 16-wide one-hots plus ONE MXU
    # contraction into a [16, 128] sub-bin tile, folded to [bin, 3] once
    # per pass — replacing the 128-wide one-hot of the legacy nibble
    # form. "auto"/"on": use it wherever the factored form applies
    # (> 128 bins); "off": keep the nibble form. Applies to both the
    # aligned-pipeline kernels (ops/aligned.py) and the standalone
    # pallas histogram (ops/pallas_hist.py)
    tpu_hist_subbin: str = "auto"
    # segment-fused lambdarank gradient kernel (ops/pallas_rank.py): one
    # Pallas program streams query segments (CSR doc offsets packed into
    # fixed-size row tiles) through VMEM and computes rank positions,
    # sigmoid pair factors (bf16 compute, f32 accumulation), NDCG deltas
    # and per-doc lambda/hessian in place — the [Q, S, S] pair tensors of
    # the bucketed path never exist in HBM, and ONE compiled program
    # replaces the per-bucket-size program ladder. "auto": fused when a
    # TPU is attached, bucketed otherwise; "on": fused everywhere
    # (interpret-mode kernel on CPU — slow, tests/CI only); "off": the
    # bucketed pair-tensor path. Queries longer than tpu_rank_tile fall
    # back to the bucketed path per query; a kernel build failure falls
    # back wholesale (warned + logged as a rank_fused event)
    tpu_rank_fused: str = "auto"
    # docs per fused lambdarank tile (multiple of 128). Larger tiles
    # amortize grid overhead but pay more masked cross-query pair work
    # inside each subtile band; 512 fits MSLR's 40..200-doc queries with
    # low waste. Queries longer than this are handled by the bucketed
    # fallback path
    tpu_rank_tile: int = 512
    # quantize the fused kernel's sigmoid *input* to this many bins over
    # the reference table range [-50, 50] — the semantics of the
    # reference's quantized sigmoid lookup table (rank_objective.hpp:71,
    # 2/(1+exp(2*sigmoid*x)) tabulated at bin left edges). 0 = exact
    # sigmoid (default: on TPU the exp is cheaper than a gather, so the
    # LUT exists for reference-parity experiments, not speed)
    tpu_rank_sigmoid_bins: int = 0
    # VMEM budget (MB) for the aligned move pass's [K+1]-slot histogram
    # store. When the store fits, it stays VMEM-resident for the whole
    # pass (fastest); when it does not (wide-F x 255-bin shapes, e.g.
    # MSLR F=137), it is kept in HBM and streamed through a 2-deep VMEM
    # staging ring with double-buffered async DMA — the per-round split
    # cap K stays at 256 instead of shrinking, and shapes that formerly
    # faulted off the aligned path run aligned. Lower it to force the
    # spill ring (tests); raise it only on parts with more VMEM
    tpu_hist_spill_vmem_mb: float = 48.0
    # rows per chunk for the streaming out-of-core ingest (io/stream.py).
    # 0 (default) keeps today's paths: one-shot in-memory construction,
    # or the host-side two_round push-rows flow when two_round=true.
    # > 0 routes file loads AND in-memory matrix construction through
    # the chunked streaming pipeline: one bounded sample pass computes
    # bin boundaries (bitwise-equal to the single-host draw), then each
    # chunk is binned ON DEVICE by a jitted searchsorted kernel and
    # appended straight into the HBM-resident binned matrix — peak host
    # memory stays O(chunk_rows), so datasets larger than host RAM
    # train. The trained model is byte-equal to the in-memory path at
    # the same sampled boundaries (runtime-only: not part of the model)
    tpu_stream_chunk_rows: int = 0
    # stream-to-shard ingest (io/stream.py + dist/runtime.py): when a
    # streamed load (tpu_stream_chunk_rows > 0) feeds a data-parallel
    # run, each chunk is binned ON ITS OWNER DEVICE and written straight
    # into that device's shard slice — the [n, U] single-host binned
    # matrix never exists and peak host memory stays O(chunk_rows)
    # regardless of n. "auto" (default): shard the stream whenever the
    # distributed runtime would activate (tree_learner=data|voting and
    # a >1-wide mesh); "on": shard for data/voting even on a 1-wide
    # mesh (the host matrix is re-gathered on demand if a host-side
    # consumer needs it); "off": always assemble the host matrix and
    # shard later, today's two-step path. The sample draw is the same
    # canonical single-host draw either way, so the model stays
    # byte-equal at every mesh width (runtime-only: not part of the
    # model or the resume signature)
    tpu_stream_shard: str = "auto"
    # host->device staging depth of the streamed-ingest pipeline: with
    # the default 2, a producer thread parses chunk k+1 while chunk k
    # is being transferred/binned on device (two staging buffers +
    # async dispatch), so ingest wall-time approaches max(parse, bin)
    # instead of their sum. 0/1 disables the prefetch thread and runs
    # parse-then-bin sequentially (the honest baseline the bench's
    # overlap-efficiency number compares against; runtime-only)
    tpu_stream_pipeline_depth: int = 2
    # quantized gradient/hessian histogram accumulation on the MXU hist
    # path: per-tree stochastic-rounded int8/int16 gradient quantization
    # with per-leaf histogram rescale back to f32 units. Halves (int16)
    # or quarters (int8) the per-leaf grad/hess gather traffic — the
    # dominant HBM bandwidth term of the fused build program. "auto":
    # quantize when a TPU is attached and the fused leaf-wise path with
    # a bf16x2/pallas histogram runs; "on": quantize everywhere the
    # fused path can run (CPU included — tests/CI; the aligned engine is
    # gated off so the quantized fused path is actually exercised);
    # "off": today's f32 payload path, bitwise-unchanged — the parity
    # oracle, same fallback/oracle discipline as tpu_rank_fused. The
    # exact-f64 and gpu_use_dp histogram modes never quantize
    tpu_quant_hist: str = "auto"
    # quantized-histogram integer width: 16 (default) or 8. int16
    # payloads are exact under the bf16 hi/lo split (|q| <= 32767 needs
    # 15 mantissa bits); int8 (|q| <= 127) is exact in a SINGLE bf16
    # pass, so the hi/lo split collapses to one MXU issue — quarter the
    # gather bytes and half the matmul work, at more rounding noise per
    # tree (stochastic rounding keeps it unbiased)
    tpu_quant_hist_bits: int = 16
    # first-class telemetry (obs/): per-round JSONL metrics ledger
    # (wall/device ms, new-trace count, training path, aligned vs
    # fallback rounds, gate notes, bagging sample sizes, eval values)
    # plus a host/device span tracer whose spans also land in
    # jax.profiler profiles. Off by default and FREE when off — the
    # round loop takes one attribute check and issues zero device
    # fences. On, each round is fenced once to observe device time
    # (target <2% overhead on the HIGGS mb=63 per-iter time). Enters
    # config_signature, so toggling retraces rather than reusing a
    # differently-fenced program
    tpu_trace: bool = False
    # directory for telemetry output (span + ledger JSONL, one record
    # per round flushed as it happens — a killed run keeps rounds 0..k).
    # Defaults to ./lgbt_trace when tpu_trace is on and no directory is
    # given
    tpu_trace_dir: str = ""
    # resilient training runtime (resilience/): directory for
    # full-training-state checkpoints — model text + the bagging/GOSS/
    # DART and feature-sampling RNG streams + the f32 score arrays +
    # iteration counter + early-stopping state — written atomically
    # (tmp + rename behind a MANIFEST.json pointer) every
    # tpu_checkpoint_freq rounds and once more on SIGTERM/SIGINT
    # preemption (the in-flight round finishes first). When the
    # directory already holds a valid manifest whose training signature
    # matches, engine.train auto-resumes from it and continues BITWISE-
    # identically to the uninterrupted run (bagging, multiclass and
    # valid-set early stopping included). Empty: checkpointing off —
    # the round loop takes one None check and issues zero device fences
    tpu_checkpoint_dir: str = ""
    # checkpoint cadence in rounds (with tpu_checkpoint_dir). 0 inherits
    # snapshot_freq when that is positive, else 10
    tpu_checkpoint_freq: int = 0
    # rolling retention shared by checkpoints and the CLI's
    # output_model.snapshot_iter_* files: keep the newest K, delete older
    tpu_snapshot_keep: int = 3
    # deterministic fault injection for tests/CI (also settable via the
    # LGBT_FAULTS environment variable): comma-separated "kill@R"
    # (SIGTERM to own pid before round R), "int@R" (SIGINT), and
    # "transient@N" (raise a retriable error at the N-th device
    # dispatch, 1-based). Every injected fault, retry and recovery is
    # recorded as a ledger note and an [Event] log record
    tpu_fault_spec: str = ""
    # bounded retry with exponential backoff around device dispatch
    # sites: how many times a transient dispatch error (injected, or an
    # XlaRuntimeError naming UNAVAILABLE / ABORTED / DEADLINE_EXCEEDED /
    # preemption) is retried before propagating. 0 disables the retry
    # wrapper entirely (dispatches become plain calls)
    tpu_retry_max: int = 2
    # first retry backoff in seconds; doubles on every further attempt
    tpu_retry_backoff_s: float = 0.05
    # serving service (lightgbm_tpu/serving/): HBM budget in MB for the
    # model registry's pool of device-resident forests. When the
    # resident models exceed it, least-recently-USED entries are evicted
    # (the entry just loaded is never the victim; a single model larger
    # than the whole budget loads with a warning). 0 = unbounded
    tpu_serve_hbm_budget_mb: float = 0.0
    # serving latency SLO: how long the request coalescer may hold a
    # request waiting for batch-mates before flushing to the engine.
    # Larger values fill shape buckets better (throughput); smaller
    # values bound tail latency
    tpu_serve_max_batch_wait_ms: float = 2.0
    # serving batch cap in rows: the coalescer flushes early once the
    # queued rows for a model reach this (a bucket is full). Requests
    # are never split across batches; one larger than the cap flushes
    # alone and the engine chunks it internally
    tpu_serve_max_batch_rows: int = 8192
    # train-to-serve hot-swap: poll interval in seconds at which the
    # serving watcher re-reads a checkpoint directory's MANIFEST.json
    # pointer for a new version to warm and atomically swap in
    tpu_serve_watch_interval_s: float = 0.5
    # rows used to pre-warm a newly loaded/swapped serving engine
    # on-device (compiles the pow2-bucket program before the first real
    # request; swap additionally re-warms the buckets live traffic
    # used). 0 disables warming
    tpu_serve_warm_rows: int = 256
    # live metrics plane (obs/metrics.py + obs/memory.py): feed the
    # process-wide registry from the training round loop — rounds,
    # retraces, aligned fallbacks, retry events, per-round latency
    # histogram — and refresh the HBM accountant gauges. Off by default:
    # the round loop then pays one attribute check and adds zero device
    # fences. Read via bst.metrics_snapshot(); serving exposes the same
    # registry over HTTP (tpu_serve_metrics_port)
    tpu_metrics: bool = False
    # serving /metrics exporter: TCP port for the ServingService's HTTP
    # endpoint — Prometheus text at /metrics (request counters,
    # coalescer batch fill, LRU evictions, per-model latency histograms
    # with p50/p99, live + peak HBM gauges) and the same snapshot as
    # JSON at /metrics.json. Binds 127.0.0.1. 0 disables the exporter
    tpu_serve_metrics_port: int = 0
    # keep the task=serve process alive this many seconds after loading
    # and scoring finish (0 = exit immediately): the window in which
    # scrapers hit the /metrics exporter and checkpoint watchers may
    # hot-swap. SIGINT/SIGTERM end the hold early and exit cleanly
    tpu_serve_hold_s: float = 0.0
    # request-scoped serving tracer (obs/reqtrace.py): every
    # Coalescer.submit mints a trace ID whose span records queue-wait,
    # batch id, flush reason (full vs deadline), batch fill ratio,
    # engine dispatch time share and total latency — even when the
    # batched engine call raises. Records land in a fixed in-memory ring
    # (served at the exporter's /debug/requests) and a tail-sampled
    # JSONL stream, and feed per-model SLO burn-rate gauges. Off by
    # default and free when off: the coalescer hot path pays one is-None
    # branch and zero device fences. Runtime-only: excluded from model
    # text and checkpoint signatures
    tpu_serve_trace: bool = False
    # directory for the request-trace JSONL stream
    # (reqtrace-<pid>.jsonl: one header line, then one row per KEPT
    # request, flushed per line so a killed host keeps everything so
    # far). Empty: ring buffer + /debug/requests only, no file
    tpu_serve_trace_dir: str = ""
    # head-sampling rate in [0, 1] for the request-trace JSONL stream: a
    # non-breaching request is kept when a deterministic hash of its
    # trace ID falls under this rate (no RNG — the same traffic keeps
    # the same rows on every run). Requests breaching tpu_serve_slo_ms
    # and errored requests are ALWAYS kept, so 0.0 is pure tail
    # sampling: SLO breachers and failures only
    tpu_serve_trace_sample: float = 0.0
    # request rows retained in the in-memory trace ring behind the
    # exporter's /debug/requests endpoint (oldest overwritten first);
    # registry load/swap/evict markers share the same ring
    tpu_serve_trace_ring: int = 512
    # per-request latency SLO in milliseconds for the serving plane. A
    # request whose submit-to-result latency exceeds it is a breach:
    # always kept in the trace stream, counted in
    # serve_slo_breaches_total, surfaced as a rate-limited
    # serve_request_slow event, and folded into the rolling per-model
    # serve_slo_burn_rate gauge — the admission/load-shedding signal.
    # 0 disables SLO classification (nothing breaches)
    tpu_serve_slo_ms: float = 0.0
    # AOT serving-artifact directory (serve/aot.py): jax.export
    # serialized forest-traversal programs keyed by an artifact
    # signature (jax version, backend, dtype plan, forest shape). At
    # model load the registry attaches matching buckets so a fresh
    # process reaches first score with zero new jax traces; a signature
    # mismatch emits a serve_aot event and falls back to normal jit.
    # Write artifacts with tools/serve_export.py. Empty disables.
    # Runtime-only: excluded from model text and checkpoint signatures
    tpu_serve_aot_dir: str = ""
    # compact residency plan for served forests: "off" (f32 engine,
    # bit-exact f64 routing), "f16" (thresholds + leaf values as
    # float16), or "int8" (per-feature affine int8 thresholds, the
    # ops/histogram.quantize_gh per-column scale discipline, f16
    # leaves). Compact engines route on f32 compares, so every load is
    # parity-gated against the f64 oracle: failing the gate emits
    # serve_compact_fallback and keeps the f32 engine — never silent
    # drift. Roughly 2.2x more models fit the same
    # tpu_serve_hbm_budget_mb. Runtime-only: excluded from model text
    # and checkpoint signatures
    tpu_serve_compact: str = "off"
    # parity-gate tolerance for compact plans: max |compact - oracle|
    # margin error allowed, relative to max(1, max |oracle margin|)
    # over the probe batch. Exceeding it rejects the compact plan for
    # that model (serve_compact_fallback). Runtime-only: excluded from
    # model text and checkpoint signatures
    tpu_serve_compact_tol: float = 0.05
    # serving network front door (serving/frontend/): TCP port for the
    # scoring HTTP endpoint — POST /v1/score/<model> (JSON rows or
    # packed-binary float rows) submitted through QoS admission into
    # the request coalescer, GET /healthz readiness. Binds 127.0.0.1.
    # 0 disables the front door. Runtime-only: excluded from model
    # text and checkpoint signatures, like the other serving knobs
    tpu_serve_port: int = 0
    # per-model QoS classes for front-door admission:
    # "model:class,..." with classes gold (highest, never shed),
    # silver, bronze (or 0/1/2). A "default:class" item sets the class
    # of unlisted models; without one they serve as bronze. Higher
    # classes dispatch first under saturation; lower classes are load-
    # shed (fast 429 + serve_shed event) while a model's SLO burn rate
    # is above the shed watermark
    tpu_serve_qos: str = ""
    # front-door load shedding: "auto" (shed exactly when the request
    # tracer + SLO are live, i.e. tpu_serve_trace with a nonzero
    # tpu_serve_slo_ms), "on", or "off". Shedding trips per model on
    # the rolling serve_slo_burn_rate gauge (obs/reqtrace.py) with
    # hysteresis, sheds only classes below gold, and clears when the
    # burn rate falls back under the clear watermark
    tpu_serve_shed: str = "auto"
    # SLO burn rate at or above which front-door shedding trips for a
    # model (fraction of breaching/errored requests over the rolling
    # burn window)
    tpu_serve_shed_high: float = 0.5
    # burn rate at or below which a tripped model stops shedding (must
    # be < tpu_serve_shed_high; the gap is the hysteresis band)
    tpu_serve_shed_low: float = 0.25
    # admission window in rows: the front-door dispatcher keeps at most
    # this many rows in flight toward the coalescer; excess requests
    # wait in per-class priority queues (highest class dispatches
    # first). 0 = twice tpu_serve_max_batch_rows
    tpu_serve_admit_rows: int = 0
    # devices the serving placer spreads models across: 1 (default)
    # keeps every forest on the default device and the placer off;
    # 0 = all visible devices; N > 1 = the first N. With more than one
    # device the per-model forests are pinned per device by HBM
    # headroom, hot models are replicated (serve_place events), each
    # batch routes to the replica with the shallowest queue, and
    # tpu_serve_hbm_budget_mb becomes a PER-DEVICE budget with
    # per-device LRU eviction of replicas
    tpu_serve_devices: int = 1
    # replica ceiling per model for the placer's hot-model replication
    # (request-rate ranked; replication only fills free per-device
    # headroom, it never evicts for a copy)
    tpu_serve_replicas: int = 2
    # runtime lock-discipline assertions (utils/locks.py): install a
    # checking __setattr__ on the serving/metrics classes whose shared
    # state is declared `# guarded-by:` — a guarded attribute rebound
    # outside its lock is recorded as a violation (read via
    # locks.violations(); the slow serving stress test asserts zero).
    # The dynamic twin of graftlint's static LGT004 rule. Off by
    # default and free when off (no wrapper is installed). Also
    # settable via the LGBT_DEBUG_LOCKS environment variable.
    # Runtime-only: excluded from model text and checkpoint signatures
    tpu_debug_locks: bool = False
    # unified run timeline (obs/timeline.py): "auto" (default — live
    # exactly when tpu_trace is), "on", or "off". Live, the CLI writes
    # a Chrome-trace/Perfetto timeline.json next to trace_summary.json
    # joining every JSONL/event stream on one monotonic clock, the
    # round loop runs the zero-fence rolling-median anomaly watch
    # (round_anomaly ledger notes + events), and a sweep's fenced
    # rounds feed the edge-triggered sweep_subfleet_imbalance watch.
    # Off adds zero fences and zero work. Runtime-only: excluded from
    # model text and checkpoint signatures
    tpu_timeline: str = "auto"
    # imbalance ratio (max/median per-sub-fleet round time of a sweep)
    # at or above which the straggler watch counts a sampled round as
    # imbalanced. Runtime-only, like tpu_timeline
    tpu_straggler_threshold: float = 1.5
    # consecutive imbalanced sampled rounds before the edge-triggered
    # straggler event fires (and consecutive calm rounds below the
    # hysteresis clear level before it clears). Runtime-only
    tpu_straggler_rounds: int = 3
    # anomaly factor N for the in-run round-wall watch: a traced
    # round's wall > N x the trailing-window median commits a
    # round_anomaly ledger note + event (pure host arithmetic, zero
    # fences). 0 disables the watch. Runtime-only
    tpu_anomaly_factor: float = 3.0
    # trailing window length in rounds for the anomaly median;
    # anomalous rounds never enter the window. The watch arms after
    # window/4 (at least 3) normal rounds. Runtime-only
    tpu_anomaly_window: int = 32
    # many-model sweep trainer (sweep/train_many): "auto" partitions
    # the fleet into shape-bucketed sub-fleets (sweep/subfleet.py) and
    # batches each into one vmapped round program — GBDT, GOSS, and
    # DART fleets, quantized histograms included, with the sweep grid
    # (learning_rate, lambda_l1/l2, bagging seed+freq,
    # feature_fraction_seed) as traced operands — falling back to an
    # interleaved round-robin of per-model rounds for anything the gate
    # rejects; "batched" raises instead of falling back; "interleaved"
    # forces the fallback. Runtime-only: excluded from model text and
    # checkpoint signatures — model bytes are identical across modes
    tpu_sweep_mode: str = "auto"
    # fleet checkpoint directory for train_many (MANIFEST.json + per-
    # model texts + score planes + host RNG). Empty disables fleet
    # checkpointing. Runtime-only, like tpu_checkpoint_dir
    tpu_sweep_checkpoint_dir: str = ""
    # write a fleet checkpoint every N sweep rounds (0 = never).
    # Runtime-only, like tpu_checkpoint_freq
    tpu_sweep_checkpoint_freq: int = 0
    # HBM budget in MiB for one batched sub-fleet's score stack (0 =
    # ask the obs/memory accountant for device headroom, unbounded when
    # the runtime has no memory_stats — e.g. CPU emulation). Fleets
    # whose [M, K, N] stack would exceed it split into pow2-sized
    # sub-fleets. Runtime-only, like tpu_sweep_mode
    tpu_sweep_hbm_budget_mb: int = 0
    # hard cap on models per batched sub-fleet (0 = uncapped); applied
    # after the HBM budget. Runtime-only, like tpu_sweep_mode
    tpu_sweep_max_fleet: int = 0

    # internal (set by trainer, reference config.h:832-833)
    is_parallel: bool = False
    is_parallel_find_bin: bool = False

    # ------------------------------------------------------------------
    @staticmethod
    def canonical_name(key: str) -> str:
        k = key.strip().lower()
        return _ALIASES.get(k, k)

    def __post_init__(self) -> None:
        if isinstance(self.task, dict):
            raise TypeError("Config() takes dataclass fields, not a params "
                            "dict — use Config.from_params({...})")

    @classmethod
    def from_params(cls, params: Optional[Dict[str, Any]] = None) -> "Config":
        cfg = cls()
        cfg.update(params or {})
        return cfg

    def update(self, params: Dict[str, Any]) -> "Config":
        """Apply key=value params with alias expansion.

        First-one-wins among aliases of the same canonical key, matching the
        reference `KV2Map` + alias pass (`src/io/config.cpp:15-40`).
        """
        fields = {f.name: f for f in dataclasses.fields(self)}
        seen = set()
        for key, value in params.items():
            name = self.canonical_name(key)
            if name in seen:
                continue
            if name not in fields:
                # unknown keys are tolerated (reference warns); keep for users
                continue
            seen.add(name)
            f = fields[name]
            if f.type in ("int", int):
                setattr(self, name, int(float(value)))
            elif f.type in ("float", float):
                setattr(self, name, float(value))
            elif f.type in ("bool", bool):
                setattr(self, name, _to_bool(value))
            elif name in ("valid", "valid_initscore_filenames", "metric"):
                setattr(self, name, _kv_list(value, str))
            elif name in ("monotone_constraints",):
                setattr(self, name, _kv_list(value, int))
            elif name == "eval_at":
                setattr(self, name, sorted(_kv_list(value, int)))
            elif name in ("feature_contri", "label_gain",
                          "cegb_penalty_feature_lazy",
                          "cegb_penalty_feature_coupled"):
                setattr(self, name, _kv_list(value, float))
            else:
                setattr(self, name, str(value))
        self._normalize()
        self._check_conflicts()
        if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            # Wire jax's persistent compilation cache before any trace
            # happens (Config.update always precedes Dataset/Booster
            # construction). One-shot per process; see compile_cache.py.
            from . import compile_cache
            compile_cache.init_persistent_cache()
        return self

    # ------------------------------------------------------------------
    def _normalize(self) -> None:
        """Normalize enum-ish strings (reference `config.cpp:121-151`)."""
        obj = self.objective.strip().lower()
        self.objective = _OBJECTIVE_ALIASES.get(obj, obj)
        self.boosting = _BOOSTING_ALIASES.get(self.boosting.strip().lower(),
                                              self.boosting.strip().lower())
        self.tree_learner = _TREE_LEARNER_ALIASES.get(
            self.tree_learner.strip().lower(), self.tree_learner.strip().lower())
        self.device_type = _DEVICE_ALIASES.get(self.device_type.strip().lower(),
                                               self.device_type.strip().lower())
        self.metric = [_METRIC_ALIASES.get(m.strip().lower(), m.strip().lower())
                       for m in self.metric]
        if not self.label_gain:
            # default label gain 2^i - 1 (reference config.h:715-722)
            self.label_gain = [float((1 << i) - 1) for i in range(31)]
        self.tpu_serve_compact = self.tpu_serve_compact.strip().lower()
        if self.tpu_serve_compact not in ("off", "f16", "int8"):
            raise ValueError(
                f"tpu_serve_compact must be off/f16/int8, got "
                f"{self.tpu_serve_compact!r}")
        self.tpu_timeline = self.tpu_timeline.strip().lower()
        if self.tpu_timeline not in ("off", "on", "auto"):
            raise ValueError(
                f"tpu_timeline must be off/on/auto, got "
                f"{self.tpu_timeline!r}")
        self.tpu_serve_shed = self.tpu_serve_shed.strip().lower()
        if self.tpu_serve_shed not in ("off", "on", "auto"):
            raise ValueError(
                f"tpu_serve_shed must be off/on/auto, got "
                f"{self.tpu_serve_shed!r}")
        if not 0.0 < self.tpu_serve_shed_low < self.tpu_serve_shed_high \
                <= 1.0:
            raise ValueError(
                "need 0 < tpu_serve_shed_low < tpu_serve_shed_high <= 1, "
                f"got low={self.tpu_serve_shed_low!r} "
                f"high={self.tpu_serve_shed_high!r}")
        if self.tpu_serve_qos:
            # full parsing lives in serving/frontend/qos.py; the config
            # layer rejects syntactically-broken specs at startup
            from .serving.frontend.qos import parse_qos
            parse_qos(self.tpu_serve_qos)

    def _check_conflicts(self) -> None:
        """Parameter-conflict resolution (reference `CheckParamConflict`
        `src/io/config.cpp:204-283`)."""
        if self.is_provide_training_metric or self.valid:
            pass
        if self.tree_learner != "serial":
            self.is_parallel = True
            # distributed construction also finds bins through the
            # global-sync path (dist/binning.py) — per-shard sample
            # passes merged into boundaries identical on every shard
            # (reference CheckParamConflict sets the same flag for
            # parallel learners, config.cpp:232-238)
            self.is_parallel_find_bin = True
            if self.num_machines <= 1:
                # single machine: fall back to serial semantics but keep the
                # learner (it degrades to a 1-shard mesh)
                pass
        if self.boosting == "rf":
            if not (self.bagging_fraction < 1.0 or self.pos_bagging_fraction < 1.0
                    or self.neg_bagging_fraction < 1.0):
                self.bagging_fraction = 0.9
            if self.bagging_freq <= 0:
                self.bagging_freq = 1
        if self.boosting == "goss":
            # GOSS owns its sampling; plain bagging is disabled
            self.bagging_freq = 0
        if (self.pos_bagging_fraction < 1.0 or self.neg_bagging_fraction < 1.0) \
                and self.objective != "binary":
            self.pos_bagging_fraction = 1.0
            self.neg_bagging_fraction = 1.0
        if self.num_class > 1 and self.objective not in (
                "multiclass", "multiclassova", "none"):
            if self.objective in ("regression",) and self.num_class == 1:
                pass
        if self.max_depth > 0:
            full = 1 << min(self.max_depth, 30)
            self.num_leaves = min(self.num_leaves, full)

    # ------------------------------------------------------------------
    @property
    def forces_host_learner(self) -> bool:
        """True when config alone routes training to the host
        SerialTreeLearner. Forced splits and CEGB split/coupled
        penalties run on the fused DEVICE learner (round 5); only the
        per-(row, feature) LAZY penalties keep the host twin (their
        marking state has no bounded device representation).
        GBDT.use_fused and Dataset._maybe_bundle must agree on this, so
        it lives in one place."""
        return len(self.cegb_penalty_feature_lazy) > 0

    @property
    def sequential_device_only(self) -> bool:
        """True when the config needs the strictly SEQUENTIAL device
        tree loop (fused builder): forced splits and CEGB penalties
        depend on commit order, which the speculative aligned/level
        engines replay out of order."""
        return bool(self.forcedsplits_filename) \
            or self.cegb_penalty_split > 0 \
            or len(self.cegb_penalty_feature_coupled) > 0 \
            or len(self.cegb_penalty_feature_lazy) > 0

    @property
    def num_tree_per_iteration(self) -> int:
        if self.objective == "multiclass" or self.objective == "multiclassova":
            return self.num_class
        return 1

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def clone(self) -> "Config":
        return dataclasses.replace(
            self,
            valid=list(self.valid),
            metric=list(self.metric),
            monotone_constraints=list(self.monotone_constraints),
            feature_contri=list(self.feature_contri),
            label_gain=list(self.label_gain),
            eval_at=list(self.eval_at),
        )


def parse_config_file(text: str) -> Dict[str, str]:
    """Parse a reference-style `train.conf` (`key = value` lines, `#` comments;
    reference `Config::LoadFromString`, `src/io/config.cpp`)."""
    out: Dict[str, str] = {}
    for line in text.splitlines():
        line = line.split("#", 1)[0].strip()
        if not line or "=" not in line:
            continue
        k, v = line.split("=", 1)
        out[k.strip()] = v.strip()
    return out
