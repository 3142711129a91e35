"""Public Dataset / Booster API.

Re-creates the reference python package surface
(`python-package/lightgbm/basic.py`): lazily-constructed `Dataset` with
reference alignment for validation sets, field set/get, and a `Booster` with
`update/eval/predict/save_model/model_to_string/feature_importance` — except
the ctypes/C-API indirection is gone: the booster drives the JAX GBDT core
directly (the reference's one-C-call-per-iteration boundary becomes one
host->device step per iteration).
"""
from __future__ import annotations

import copy
import json
from typing import Any, Dict, List, Optional, Sequence, Union

import time

import numpy as np

from .config import Config
from .io.dataset import Dataset as _CoreDataset
from .models.boosting_variants import create_boosting
from .models.gbdt import GBDT
from .models.model_text import (dump_model_json, load_model_from_string,
                                save_model_to_string, _feature_infos)
from .models.tree import Tree
from .ops.metrics import create_metrics, metric_names
from .ops.objectives import create_objective
from .ops.predict import flatten_forest, predict_raw_values


def _native_predict(trees, X, num_class: int, pred_leaf: bool = False,
                    flat=None, es_freq: int = 0, es_margin: float = 0.0):
    """Batch predict through the native OpenMP predictor
    (src/native/predictor.cpp); None -> caller uses the NumPy walk."""
    from . import native
    if not trees or not native.native_available():
        return None
    if flat is None:
        flat = flatten_forest(trees, num_class)
    if X.shape[1] <= int(flat["feat"].max(initial=-1)):
        raise ValueError(
            f"data has {X.shape[1]} features but the model was trained "
            f"with at least {int(flat['feat'].max()) + 1}")
    out = native.predict_forest(np.asarray(X, np.float64), flat,
                                num_class, pred_leaf, es_freq, es_margin)
    if out is None or pred_leaf:
        return out
    return out.reshape(len(X), num_class) if out.ndim == 1 else out


def _early_stop_predict_py(trees, X, num_class: int, es_freq: int,
                           es_margin: float) -> np.ndarray:
    """Pure-Python fallback for prediction early stopping (reference
    prediction_early_stop.cpp): per row, walk trees until the margin test
    passes at a freq boundary. `es_freq` is in TREES (the caller scales
    the per-iteration freq by num_class so checks land on iteration
    boundaries, like the reference)."""
    X = np.asarray(X, np.float64)
    n = len(X)
    out = np.zeros((n, num_class), np.float64)
    for i in range(n):
        acc = out[i]
        for t, tree in enumerate(trees):
            acc[t % num_class] += tree.predict_row(X[i])
            if es_freq > 0 and (t + 1) % es_freq == 0 and t + 1 < len(trees):
                if num_class <= 1:
                    if abs(acc[0]) > es_margin:
                        break
                else:
                    top = np.sort(acc)[-2:]
                    if top[1] - top[0] > es_margin:
                        break
    return out


class LightGBMError(Exception):
    pass


def _to_matrix(data) -> np.ndarray:
    if isinstance(data, np.ndarray):
        return data.astype(np.float64, copy=False)
    if isinstance(data, (list, tuple)):
        return np.asarray(data, np.float64)
    if hasattr(data, "values"):  # pandas
        return _data_from_pandas(data)[0]  # categories re-derived; callers
        # needing train-time alignment pass pandas_categorical explicitly
    if hasattr(data, "toarray"):  # scipy sparse
        return np.asarray(data.toarray(), np.float64)
    raise LightGBMError(f"Cannot convert data of type {type(data)}")


def _data_from_pandas(df, pandas_categorical=None):
    """DataFrame -> (matrix, feature_names, cat columns, cat categories).

    Mirrors the reference's pandas handling (basic.py:255-298): `category`
    dtype columns become their integer codes (NaN -> -1 -> missing), object
    columns are rejected, and column names become feature names. When
    `pandas_categorical` (the TRAINING category lists, in categorical-
    column order) is given, codes are remapped onto those categories so
    predict-time frames with different category sets stay aligned
    (reference stores pandas_categorical in the model for this)."""
    feature_names = [str(c) for c in df.columns]
    cat_cols = []
    cat_categories = []
    arrs = []
    cat_i = 0
    for i, col in enumerate(df.columns):
        s = df[col]
        if str(s.dtype) == "category":
            cat_cols.append(i)
            if pandas_categorical is not None:
                if cat_i >= len(pandas_categorical):
                    raise LightGBMError(
                        "train and predict DataFrames have different "
                        "numbers of categorical columns")
                train_cats = list(pandas_categorical[cat_i])
                s = s.cat.set_categories(train_cats)
            cat_categories.append([c for c in s.cat.categories])
            cat_i += 1
            codes = s.cat.codes.to_numpy().astype(np.float64)
            codes = np.where(codes < 0, np.nan, codes)
            arrs.append(codes)
        elif s.dtype == object:
            raise LightGBMError(
                f"DataFrame.dtypes for column {col} must be int, float or "
                "bool (or category)")
        else:
            arrs.append(s.to_numpy().astype(np.float64))
    return np.column_stack(arrs) if arrs else np.empty((len(df), 0)), \
        feature_names, cat_cols, cat_categories


class Dataset:
    """Lazily-constructed dataset (reference basic.py:600+)."""

    def __init__(self, data, label=None, reference: "Dataset" = None,
                 weight=None, group=None, init_score=None,
                 feature_name: Union[str, List[str]] = "auto",
                 categorical_feature: Union[str, List[int]] = "auto",
                 params: Optional[Dict] = None, free_raw_data: bool = True,
                 silent: bool = False) -> None:
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params or {})
        self.free_raw_data = free_raw_data
        self._handle: Optional[_CoreDataset] = None
        self.used_indices: Optional[np.ndarray] = None
        self.pandas_categorical = None
        self._predictor = None

    # ------------------------------------------------------------------
    def construct(self) -> "Dataset":
        if self._handle is not None:
            return self
        if self.reference is not None:
            ref = self.reference.construct()._handle
        else:
            ref = None
        if self.used_indices is not None:
            # subset of the (constructed) reference (basic.py subset path)
            parent = self.reference.construct()._handle
            self._handle = parent.subset(self.used_indices)
            if self.label is not None:
                self._handle.metadata.set_label(self.label)
            if self.group is not None:
                self._handle.metadata.set_group(self.group)
            return self
        cfg = Config.from_params(self.params)
        if isinstance(self.data, str):
            # file-path construction (reference basic.py: Dataset accepts
            # a path; two_round=True in params streams it in O(chunk)
            # host memory through the loader's push-rows flow). The
            # constructor's categorical_feature argument folds into the
            # config spec the loader reads (the reference folds it into
            # params the same way for file inputs).
            if self.categorical_feature not in ("auto", None):
                cats = list(self.categorical_feature)
                if any(isinstance(c, str) for c in cats):
                    cfg.categorical_feature = "name:" + ",".join(
                        str(c) for c in cats)
                else:
                    cfg.categorical_feature = ",".join(
                        str(int(c)) for c in cats)
            from .io.loader import DatasetLoader
            loader = DatasetLoader(cfg)
            if ref is not None:
                self._handle = loader.\
                    load_from_file_align_with_other_dataset(self.data, ref)
            else:
                self._handle = loader.load_from_file(self.data)
            if self.label is not None:
                self._handle.metadata.set_label(self.label)
            if self.weight is not None:
                self._handle.metadata.set_weight(self.weight)
            if self.group is not None:
                self._handle.metadata.set_group(self.group)
            if self.init_score is not None:
                self._handle.metadata.set_init_score(self.init_score)
            return self
        feature_names = (None if self.feature_name in ("auto", None)
                         else list(self.feature_name))
        raw_cats = (None if self.categorical_feature in ("auto", None)
                    else list(self.categorical_feature))
        sparse_in = (hasattr(self.data, "tocsc")
                     and not isinstance(self.data, np.ndarray))
        if hasattr(self.data, "values") and hasattr(self.data, "columns"):
            mat, pd_names, pd_cats, pd_categories = \
                _data_from_pandas(self.data)
            if feature_names is None:
                feature_names = pd_names
            if raw_cats is None and pd_cats:
                raw_cats = pd_cats
            self.pandas_categorical = pd_categories or None
        elif sparse_in:
            mat = self.data   # CSR/CSC stays sparse (from_sparse ingest)
        else:
            mat = _to_matrix(self.data)
        cats = None
        if raw_cats is not None:
            cats = []
            for c in raw_cats:
                if isinstance(c, str):
                    # column-name form (the standard pandas idiom,
                    # reference basic.py categorical_feature handling)
                    if feature_names is None or c not in feature_names:
                        raise LightGBMError(
                            f"Unknown categorical feature name: {c!r}")
                    cats.append(feature_names.index(c))
                else:
                    cats.append(int(c))
        if not sparse_in and int(getattr(cfg, "tpu_stream_chunk_rows",
                                         0)) > 0:
            # streaming out-of-core ingest: chunked device-side binning
            # (io/stream.py), same sample draw -> same model bytes
            from .io.stream import stream_matrix as maker
        else:
            maker = (_CoreDataset.from_sparse if sparse_in
                     else _CoreDataset.from_matrix)
        self._handle = maker(
            mat, label=self.label, config=cfg, weight=self.weight,
            group=self.group, init_score=self.init_score,
            feature_names=feature_names, categorical_feature=cats,
            reference=ref)
        if self.free_raw_data:
            self.data = None
        return self

    # ------------------------------------------------------------------
    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score,
                       params=params or self.params)

    def subset(self, used_indices, params=None) -> "Dataset":
        ds = Dataset(None, reference=self,
                     params=params or self.params)
        ds.used_indices = np.asarray(used_indices, np.int64)
        return ds

    # ------------------------------------------------------------------
    def set_label(self, label) -> "Dataset":
        self.label = label
        if self._handle is not None and label is not None:
            self._handle.metadata.set_label(label)
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = weight
        if self._handle is not None:
            self._handle.metadata.set_weight(weight)
        return self

    def set_group(self, group) -> "Dataset":
        self.group = group
        if self._handle is not None:
            self._handle.metadata.set_group(group)
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = init_score
        if self._handle is not None:
            self._handle.metadata.set_init_score(init_score)
        return self

    def get_label(self):
        if self._handle is not None and self._handle.metadata.label is not None:
            return np.asarray(self._handle.metadata.label)
        return self.label

    def get_weight(self):
        if self._handle is not None and self._handle.metadata.weight is not None:
            return np.asarray(self._handle.metadata.weight)
        return self.weight

    def get_group(self):
        if self._handle is not None and \
                self._handle.metadata.query_boundaries is not None:
            return np.diff(self._handle.metadata.query_boundaries)
        return self.group

    def get_init_score(self):
        return self.init_score

    def get_field(self, name):
        return {"label": self.get_label, "weight": self.get_weight,
                "group": self.get_group,
                "init_score": self.get_init_score}[name]()

    def set_field(self, name, data):
        return {"label": self.set_label, "weight": self.set_weight,
                "group": self.set_group,
                "init_score": self.set_init_score}[name](data)

    @property
    def num_data(self) -> int:
        self.construct()
        return self._handle.num_data

    @property
    def num_feature(self) -> int:
        self.construct()
        return self._handle.num_total_features

    def save_binary(self, filename: str) -> "Dataset":
        self.construct()
        self._handle.save_binary(filename)
        return self

    def add_features_from(self, other: "Dataset") -> "Dataset":
        """Append `other`'s features column-wise (reference
        basic.py add_features_from -> LGBM_DatasetAddFeaturesFrom;
        both datasets must be constructed over the same rows)."""
        self.construct()
        other.construct()
        self._handle.add_features_from(other._handle)
        return self

    def _update_params(self, params) -> "Dataset":
        self.params.update(params or {})
        return self


class Booster:
    """reference basic.py:1578 Booster."""

    def __init__(self, params: Optional[Dict] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None,
                 silent: bool = False) -> None:
        self.params = dict(params or {})
        self.best_iteration = -1
        self.best_score: Dict = {}
        self._flat_cache: Optional[tuple] = None
        self._engine_cache: Dict[tuple, Any] = {}
        self._predict_engine_calls = 0
        self._predict_fallback_calls = 0
        self._predict_route_last: Optional[bool] = None
        self._model_gen = 0
        self.pandas_categorical = None
        self._train_set = train_set
        self._gbdt: Optional[GBDT] = None
        self._telemetry = None  # engine.train parks the ledger here
        self._loaded: Optional[Dict] = None
        self._name_valid_sets: List[str] = []
        self._valid_sets_public: List["Dataset"] = []
        self.name_train_set = "training"
        if model_file is not None:
            from .io.file_io import open_file
            with open_file(model_file) as fh:
                self._init_from_string(fh.read())
        elif model_str is not None:
            self._init_from_string(model_str)
        elif train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("Training data should be Dataset instance")
            train_set.construct()
            self.pandas_categorical = train_set.pandas_categorical
            cfg = Config.from_params(self.params)
            self._cfg = cfg
            self._gbdt = create_boosting(cfg, train_set._handle)
        else:
            raise LightGBMError(
                "need at least one of train_set/model_file/model_str")

    # ------------------------------------------------------------------
    def _init_from_string(self, text: str) -> None:
        for line in text.splitlines():
            if line.startswith("pandas_categorical:"):
                try:
                    self.pandas_categorical = json.loads(
                        line.split(":", 1)[1])
                except json.JSONDecodeError:
                    pass
        self._loaded = load_model_from_string(text)
        loaded_params = dict(self._loaded.get("params", {}))
        self.params = {**loaded_params, **self.params}
        # keep the model file's training params (regularization etc.) so
        # downstream refit/predict reuse them (reference GBDT::RefitTree
        # runs under the session config)
        self._cfg = Config.from_params(
            {**self.params,
             "objective": self._loaded["objective"].split(" ")[0],
             "num_class": self._loaded["num_class"]})

    @property
    def trees(self) -> List[Tree]:
        if self._gbdt is not None:
            return self._gbdt.materialized_models()
        return self._loaded["trees"] if self._loaded else []

    @property
    def num_tree_per_iteration(self) -> int:
        if self._gbdt is not None:
            return self._gbdt.num_tree_per_iteration
        return self._loaded.get("num_tree_per_iteration", 1)

    @property
    def telemetry(self):
        """The training RoundLedger (obs/ledger.py) when `tpu_trace` is
        on; None otherwise."""
        return getattr(self._gbdt, "telemetry", None) or self._telemetry

    def metrics_snapshot(self):
        """Live metrics + HBM accounting snapshot — the API twin of the
        serving /metrics endpoint, parked like `bst.telemetry`: the
        registry is process-wide, so the snapshot survives the
        engine.train round-trip onto the fresh booster. Keys:
        ``metrics`` (obs/metrics.py versioned snapshot: counters,
        gauges, histograms with p50/p99) and ``memory`` (obs/memory.py
        owner reconciliation). Counters are zero until something enables
        the plane (`tpu_metrics`, a serving exporter, or
        `obs.metrics.enable()`)."""
        from .obs import memory as obs_memory
        from .obs import metrics as obs_metrics
        return {"metrics": obs_metrics.snapshot(),
                "memory": obs_memory.snapshot()}

    # ------------------------------------------------------------------
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        data.construct()
        self._gbdt.add_valid_dataset(data._handle)
        self._name_valid_sets.append(name)
        self._valid_sets_public.append(data)
        return self

    def update(self, train_set: Optional[Dataset] = None,
               fobj=None) -> bool:
        """One boosting iteration (reference basic.py:1846). Returns True if
        training finished (cannot split any more)."""
        _t0 = time.perf_counter()
        if fobj is not None:
            # custom gradients bypass the aligned engine's score lane:
            # sync the lazily-stale train scores and leave aligned mode
            # (the engine could not follow the external tree)
            if hasattr(self._gbdt, "_drop_aligned"):
                self._gbdt._drop_aligned()
            scores = self._gbdt.train_score.numpy()
            k = self.num_tree_per_iteration
            if k == 1:
                grad, hess = fobj(scores[0], self._train_set)
            else:
                grad, hess = fobj(scores.T, self._train_set)
            grad = np.asarray(grad, np.float32).reshape(k, -1)
            hess = np.asarray(hess, np.float32).reshape(k, -1)
            self._model_gen += 1
            out = self._gbdt.train_one_iter(grad, hess)
            self._log_iter_time(_t0)
            return out
        self._model_gen += 1
        out = self._gbdt.train_one_iter()
        self._log_iter_time(_t0)
        return out

    def _log_iter_time(self, t0: float) -> None:
        # reference logs per-iteration wall time (gbdt.cpp:285-288)
        from .utils import log as _log
        if _log._level >= _log.DEBUG:
            _log.debug("%.3fs elapsed, finished iteration %d"
                       % (time.perf_counter() - t0,
                          self._gbdt.num_iterations_trained))

    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        self._model_gen += 1
        return self

    # ------------------------------------------------------------------
    def refit(self, data, label, decay_rate: float = 0.9,
              leaf_preds=None, **kwargs) -> "Booster":
        """Refit existing tree structures to new data (reference
        basic.py:2337 -> `GBDT::RefitTree` gbdt.cpp:297-320 ->
        `FitByExistingTree` serial_tree_learner.cpp:239-269):
        ``leaf_output = decay_rate * old + (1 - decay_rate) * new`` where
        ``new`` is the closed-form leaf output of the new data's grad/hess
        summed per (fixed) leaf assignment."""
        import jax.numpy as jnp

        from .io.dataset import Metadata
        from .ops.split import threshold_l1_host as _thl1

        trees = self.trees
        if not trees:
            raise LightGBMError("No trees to refit")
        X = _to_matrix(data)
        label = np.asarray(label, np.float64).reshape(-1)
        n = len(X)
        k = self.num_tree_per_iteration
        if leaf_preds is None:
            # all trees, regardless of best_iteration (reference refit
            # predicts with num_iteration=-1, basic.py:2362)
            leaf_preds = predict_raw_values(trees, X, leaf_index=True)
        leaf_preds = np.asarray(leaf_preds, np.int64).reshape(n, len(trees))
        cfg = self._cfg
        objective = create_objective(cfg)
        if objective is None:
            raise LightGBMError("Cannot refit due to null objective function.")
        md = Metadata(n)
        md.set_label(label)
        objective.init(md, n)
        scores = np.zeros((k, n), np.float64)
        l1, l2 = cfg.lambda_l1, cfg.lambda_l2
        mds = cfg.max_delta_step
        for it in range(len(trees) // k):
            g, h = objective.get_gradients(jnp.asarray(scores, jnp.float32))
            g = np.asarray(g, np.float64)
            h = np.asarray(h, np.float64)
            for tid in range(k):
                tree = trees[it * k + tid]
                lp = leaf_preds[:, it * k + tid]
                nl = tree.num_leaves
                sg = np.bincount(lp, weights=g[tid], minlength=nl)[:nl]
                sh = np.bincount(lp, weights=h[tid], minlength=nl)[:nl]
                out = -_thl1(sg, l1) / (sh + l2 + 1e-15)
                if mds > 0:
                    out = np.clip(out, -mds, mds)
                new_vals = (decay_rate * tree.leaf_value[:nl]
                            + (1.0 - decay_rate) * out * tree.shrinkage)
                tree.leaf_value[:nl] = new_vals
                scores[tid] += new_vals[lp]
        self._model_gen += 1
        return self

    @property
    def current_iteration(self) -> int:
        return self._gbdt.iter if self._gbdt else \
            len(self.trees) // max(1, self.num_tree_per_iteration)

    def num_trees(self) -> int:
        return len(self.trees)

    def num_model_per_iteration(self) -> int:
        return self.num_tree_per_iteration

    # ------------------------------------------------------------------
    def eval_train(self):
        return [(n, m, v, b) for n, m, v, b in self._gbdt.eval_train()]

    def eval_valid(self):
        out = []
        for i, res in enumerate(self._eval_valid_grouped()):
            name = self._name_valid_sets[i] if i < len(
                self._name_valid_sets) else f"valid_{i}"
            out.extend((name, m, v, b) for _, m, v, b in res)
        return out

    def _eval_valid_grouped(self):
        per_set: Dict[str, List] = {}
        res = self._gbdt.eval_valid()
        groups: Dict[str, List] = {}
        for item in res:
            groups.setdefault(item[0], []).append(item)
        return [groups[k] for k in sorted(groups)]

    # ------------------------------------------------------------------
    def predict(self, data, num_iteration: Optional[int] = None,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, start_iteration: int = 0,
                **kwargs) -> np.ndarray:
        if isinstance(data, str):
            # predict straight from a data file (reference
            # LGBM_BoosterPredictForFile, c_api.h:645-704)
            from .io.loader import DatasetLoader
            cfg = Config.from_params({**self.params, **kwargs})
            cfg.header = bool(kwargs.get("data_has_header",
                                         kwargs.get("header", cfg.header)))
            # label-free scoring files: when the file's column count
            # equals the MODEL's feature count there is no label column
            # to strip (the reference passes num_total_model_features to
            # the parser for exactly this detection, predictor.hpp:185)
            nf_model = (self._gbdt.train_data.num_total_features
                        if self._gbdt is not None else
                        self._loaded.get("max_feature_idx", -2) + 1)
            from .io.file_io import open_file
            with open_file(data, errors="replace") as f:
                if cfg.header:
                    f.readline()
                first = f.readline()
            ncols = 0
            if first.strip():
                if ":" in first and "," not in first:
                    ncols = -1          # libsvm: sparse, keep default
                else:
                    for sep in ("\t", ",", " "):
                        if sep in first:
                            ncols = len(first.rstrip("\r\n").split(sep))
                            break
            if ncols == nf_model:
                cfg.label_column = "-1"
                # ambiguity warning: a LABELED file with one fewer
                # feature than the model hits this same branch and would
                # silently shift every feature by one. Flag it when the
                # first column looks label-like (small integers).
                try:
                    tok = first.replace("\t", ",").replace(" ", ",") \
                        .split(",")[0]
                    v = float(tok)
                    if np.isfinite(v) and v == int(v) and 0 <= v <= 100:
                        from .utils import log
                        log.warning(
                            f"treating {data!r} as label-free because its "
                            f"column count ({ncols}) equals the model's "
                            f"feature count, but the first column looks "
                            f"label-like; if this file HAS labels, the "
                            f"features are mis-aligned — score a file "
                            f"with {nf_model + 1} columns or strip the "
                            f"label column")
                except ValueError:
                    pass
            _, feats, _ex = DatasetLoader(cfg).parse_file(data)
            if ncols == -1 and nf_model > 0 and feats.shape[1] < nf_model:
                # ragged LibSVM scoring rows: absent trailing features
                # are zero (reference sparse convention). Dense files
                # with too few columns stay unpadded — a feature-count
                # mismatch is an error, not missing data
                feats = np.pad(feats,
                               ((0, 0), (0, nf_model - feats.shape[1])))
            data = feats
        if hasattr(data, "tocsr") and not isinstance(data, np.ndarray):
            # CSR/CSC input (reference LGBM_BoosterPredictForCSR/CSC,
            # c_api.h:706-910): densify row CHUNKS under a constant
            # ~256 MB byte budget, never the full matrix
            rows_per = max(1, (256 << 20) // (8 * max(1, data.shape[1])))
            if data.shape[0] > rows_per:
                csr = data.tocsr()
                outs = []
                for lo in range(0, csr.shape[0], rows_per):
                    outs.append(self.predict(
                        csr[lo:lo + rows_per].toarray(), num_iteration,
                        raw_score, pred_leaf, pred_contrib,
                        start_iteration, **kwargs))
                return np.concatenate(outs, axis=0)
        if (self.pandas_categorical and hasattr(data, "columns")
                and hasattr(data, "values")):
            # remap predict-time category codes onto the TRAINING
            # categories (reference pandas_categorical model field)
            X = _data_from_pandas(data, self.pandas_categorical)[0]
        else:
            X = _to_matrix(data)
        k = self.num_tree_per_iteration
        if num_iteration is None or num_iteration <= 0:
            num_iteration = (self.best_iteration
                             if self.best_iteration > 0 else -1)
        s_iter = max(int(start_iteration or 0), 0)
        u_spec = num_iteration if num_iteration and num_iteration > 0 else -1
        trees = self.trees[s_iter * k:]
        if u_spec > 0:
            trees = trees[:u_spec * k]
        n = len(X)
        opts = {**self.params, **kwargs}
        obj_name = str(opts.get("objective", self.params.get(
            "objective", ""))).split(" ")[0]
        es_ok_obj = k > 1 or obj_name == "binary"
        es_on = (bool(opts.get("pred_early_stop", False)) and not raw_score
                 and es_ok_obj and not pred_leaf and not pred_contrib)
        from .native import native_available
        # serving-engine policy (serve/ForestEngine): depth-synchronized
        # device traversal with a cached, incrementally-updated stacked
        # forest. "auto" keeps the exact native/host walk on the CPU tier
        # unless no native library exists and the job is big enough to
        # amortize a compile.
        pd = str(opts.get("tpu_predict_device", "auto")).strip().lower()
        import jax
        use_engine = bool(trees) and not pred_contrib and (
            pd in ("on", "device", "true", "1")
            or (pd == "auto"
                and (jax.default_backend() != "cpu"
                     or (not native_available()
                         and n * len(trees) >= (1 << 18)))))
        # serve-engine routing counters on the structured channel: one
        # event per ROUTE CHANGE (not per call), so scoring loops stay
        # quiet while a silent fall-off-the-engine is still visible
        if use_engine:
            self._predict_engine_calls += 1
        else:
            self._predict_fallback_calls += 1
        if use_engine != self._predict_route_last:
            self._predict_route_last = use_engine
            from .utils import log
            log.event("predict_route", engine=bool(use_engine),
                      policy=pd,
                      engine_calls=self._predict_engine_calls,
                      fallback_calls=self._predict_fallback_calls)
        if use_engine:
            eng = self._serve_engine(trees, s_iter, u_spec)
            # pred_early_stop rides the engine as a chunked early-exit
            # (ForestEngine scores freq*k-tree segments and skips the
            # rest once the whole chunk clears the margin) — same
            # reference semantics as the native walk, chunk-granular
            es = None
            if es_on:
                es = (int(opts.get("pred_early_stop_freq", 10)) * k,
                      float(opts.get("pred_early_stop_margin", 10.0)))
            if bool(opts.get("predict_sharded", False)) and not pred_leaf \
                    and es is None:
                raw = eng.predict_sharded(X)
            else:
                raw, leaves = eng.predict(X, pred_leaf=pred_leaf,
                                          early_stop=es)
                if pred_leaf:
                    return leaves
        else:
            # flattened-forest cache for the native predictor (rebuilt when
            # the model mutates or the tree horizon changes)
            flat = None
            if trees and native_available():
                key = (len(trees), k, s_iter, self._model_gen)
                if self._flat_cache is not None \
                        and self._flat_cache[0] == key:
                    flat = self._flat_cache[1]
                else:
                    flat = flatten_forest(trees, k)
                    self._flat_cache = (key, flat)
            if pred_leaf:
                out = _native_predict(trees, X, k, pred_leaf=True, flat=flat)
                if out is not None:
                    return out.astype(np.int32)
                return predict_raw_values(trees, X, leaf_index=True)
            if pred_contrib:
                from .ops.shap import predict_contrib
                return predict_contrib(trees, X, k)
            # prediction early stopping (reference
            # prediction_early_stop.cpp): enabled via params/kwargs,
            # classification objectives only, and the margin test fires at
            # ITERATION boundaries (k trees each)
            es_freq = int(opts.get("pred_early_stop_freq", 10)) * k
            es_margin = float(opts.get("pred_early_stop_margin", 10.0))
            raw = _native_predict(trees, X, k, flat=flat,
                                  es_freq=es_freq if es_on else 0,
                                  es_margin=es_margin)
            if raw is None:
                if es_on:
                    raw = _early_stop_predict_py(trees, X, k, es_freq,
                                                 es_margin)
                else:
                    raw = np.zeros((n, k), np.float64)
                    for cls in range(k):
                        cls_trees = [t for i, t in enumerate(trees)
                                     if i % k == cls]
                        raw[:, cls] = predict_raw_values(cls_trees, X)
        if self._is_average_output():
            raw = raw / max(1, len(trees) // k)
        objective = self._objective_for_predict()
        if not raw_score and objective is not None:
            if k > 1 and objective.name == "multiclass":
                conv = objective.convert_output(raw)
            else:
                conv = np.stack([objective.convert_output(raw[:, c])
                                 for c in range(k)], axis=1)
        else:
            conv = raw
        return conv[:, 0] if k == 1 else conv

    def _serve_engine(self, trees, s_iter: int, u_spec: int):
        """Cached serve/ForestEngine per (start, horizon) slice. The
        engine checks its tree-id prefix on reuse, so trees appended by
        `update()` stack incrementally instead of re-uploading the whole
        forest; any other model mutation restacks from scratch."""
        key = (s_iter, u_spec)
        eng = self._engine_cache.get(key)
        if eng is None:
            from .serve import ForestEngine
            eng = ForestEngine(trees, num_class=self.num_tree_per_iteration)
            if len(self._engine_cache) >= 8:
                self._engine_cache.pop(next(iter(self._engine_cache)))
            self._engine_cache[key] = eng
        else:
            eng.update(trees)
        return eng

    def _is_average_output(self) -> bool:
        if self._loaded is not None:
            return bool(self._loaded.get("average_output"))
        return self._cfg.boosting == "rf"

    def _objective_for_predict(self):
        try:
            if self._gbdt is not None:
                return self._gbdt.objective
            return create_objective(self._cfg)
        except Exception:
            return None

    # ------------------------------------------------------------------
    def model_to_string(self, num_iteration: int = -1) -> str:
        if self._gbdt is not None:
            ds = self._gbdt.train_data
            obj = self._gbdt.objective
            obj_str = self._objective_string(obj)
            out = save_model_to_string(
                self._gbdt.materialized_models(), self._cfg,
                self.num_tree_per_iteration,
                ds.num_total_features - 1, ds.feature_names,
                _feature_infos(ds.mappers), num_iteration, obj_str)
        else:
            # loaded model: re-serialize
            fn = self._loaded.get("feature_names") or []
            out = save_model_to_string(
                self._loaded["trees"], self._cfg,
                self._loaded["num_tree_per_iteration"],
                self._loaded.get("max_feature_idx", max(len(fn) - 1, 0)),
                fn, self._loaded.get("feature_infos"), num_iteration,
                self._loaded.get("objective", ""))
        # reference stores the pandas category lists as a model trailer
        # (python-package basic.py) so predict-time frames stay aligned
        if self.pandas_categorical:
            try:
                out += "\npandas_categorical:" + json.dumps(
                    self.pandas_categorical) + "\n"
            except TypeError:
                pass
        return out

    @staticmethod
    def _objective_string(obj) -> str:
        if obj is None:
            return ""
        extras = {
            "binary": lambda o: f" sigmoid:{o.cfg.sigmoid}",
            "multiclass": lambda o: f" num_class:{o.num_class}",
            "multiclassova": lambda o:
                f" num_class:{o.num_class} sigmoid:{o.cfg.sigmoid}",
            "lambdarank": lambda o: "",
        }
        return obj.name + extras.get(obj.name, lambda o: "")(obj)

    def save_model(self, filename: str, num_iteration: int = -1) -> "Booster":
        from .io.file_io import open_file
        with open_file(filename, "w") as fh:
            fh.write(self.model_to_string(num_iteration))
        return self

    def dump_model(self, num_iteration: int = -1) -> dict:
        if self._gbdt is not None:
            ds = self._gbdt.train_data
            return dump_model_json(
                self._gbdt.materialized_models(), self._cfg,
                self.num_tree_per_iteration,
                ds.num_total_features - 1, ds.feature_names, num_iteration,
                self._objective_string(self._gbdt.objective))
        fn = self._loaded.get("feature_names") or []
        return dump_model_json(
            self._loaded["trees"], self._cfg,
            self._loaded["num_tree_per_iteration"],
            self._loaded.get("max_feature_idx", max(len(fn) - 1, 0)),
            fn, num_iteration, self._loaded.get("objective", ""))

    # ------------------------------------------------------------------
    def feature_importance(self, importance_type: str = "split",
                           iteration: int = -1) -> np.ndarray:
        """reference Booster.feature_importance (basic.py:2410+)."""
        if self._gbdt is not None:
            nf = self._gbdt.train_data.num_total_features
        else:
            nf = self._loaded.get("max_feature_idx", 0) + 1
        imp = np.zeros(nf)
        trees = self.trees
        if iteration and iteration > 0:
            trees = trees[:iteration * self.num_tree_per_iteration]
        for t in trees:
            for node in range(t.num_leaves - 1):
                f = t.split_feature[node]
                if importance_type == "split":
                    imp[f] += 1
                else:
                    imp[f] += t.split_gain[node]
        return imp

    def feature_name(self) -> List[str]:
        if self._gbdt is not None:
            return list(self._gbdt.train_data.feature_names)
        return list(self._loaded.get("feature_names") or [])

    def get_split_value_histogram(self, feature, bins=None,
                                  xgboost_style: bool = False):
        """Histogram of a feature's numerical split thresholds across all
        trees (reference Booster.get_split_value_histogram,
        basic.py:2470+). Returns (hist, bin_edges) or, with xgboost_style,
        a pandas DataFrame / ndarray of (SplitValue, Count)."""
        if isinstance(feature, str):
            names = self.feature_name()
            if feature not in names:
                raise LightGBMError(f"Unknown feature name {feature}")
            feature = names.index(feature)
        values = []
        for t in self.trees:
            for node in range(max(t.num_leaves - 1, 0)):
                if t.split_feature[node] == feature \
                        and not t.node_is_categorical(node):
                    values.append(float(t.threshold[node]))
        values = np.asarray(values, np.float64)
        if bins is None or (isinstance(bins, int)
                            and bins > len(np.unique(values))):
            bins = max(len(np.unique(values)), 1)
        hist, bin_edges = np.histogram(values, bins=bins)
        if xgboost_style:
            ret = np.column_stack((bin_edges[1:], hist))
            ret = ret[ret[:, 1] > 0]
            try:
                import pandas as pd
                return pd.DataFrame(ret, columns=["SplitValue", "Count"])
            except ImportError:
                return ret
        return hist, bin_edges

    def free_dataset(self) -> "Booster":
        return self

    def free_network(self) -> "Booster":
        return self

    def set_network(self, machines, local_listen_port=12400,
                    listen_time_out=120, num_machines=1) -> "Booster":
        # TPU build: collectives ride the jax.sharding mesh, not sockets
        # (reference basic.py:1737; network seam = parallel/ learners)
        import warnings
        warnings.warn(
            "set_network is a no-op on the TPU build: distribution is "
            "configured by tree_learner=data/feature/voting over the "
            "jax.sharding mesh (machines/ports do not apply)",
            stacklevel=2)
        return self

    def __copy__(self):
        return self.__deepcopy__(None)

    def __deepcopy__(self, memo):
        import copy as _copy
        clone = Booster(model_str=self.model_to_string())
        clone.best_iteration = self.best_iteration
        clone.best_score = _copy.deepcopy(self.best_score, memo)
        clone.params = _copy.deepcopy(self.params, memo)
        clone.name_train_set = self.name_train_set
        return clone

    def __getstate__(self):
        # only the model string plus plain-data attributes cross the
        # pickle boundary — the parked telemetry ledger handle
        # (self._telemetry) holds open file state and stays behind
        return {"model_str": self.model_to_string(),
                "best_iteration": self.best_iteration,
                "best_score": self.best_score,
                "params": self.params,
                "name_train_set": self.name_train_set}

    def __setstate__(self, state):
        self.__init__(model_str=state["model_str"])
        self.best_iteration = state["best_iteration"]
        self.best_score = state["best_score"]
        self.params = state.get("params", {})
        self.name_train_set = state.get("name_train_set", "training")
