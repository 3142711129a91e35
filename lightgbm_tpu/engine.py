"""Training entry points: `train` and `cv`.

Re-creates the reference `python-package/lightgbm/engine.py`: the per-
iteration callback loop with EarlyStopException control flow (`engine.py:
239-267`), evals_result plumbing, `init_model` continued training, and
stratified/plain k-fold `cv` (`engine.py:371+`).
"""
from __future__ import annotations

import collections
import copy
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from . import callback as callback_mod
from .basic import Booster, Dataset, LightGBMError
from .callback import EarlyStopException
from .config import Config


def train(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: int = 100,
          valid_sets: Optional[List[Dataset]] = None,
          valid_names: Optional[List[str]] = None,
          fobj: Optional[Callable] = None,
          feval: Optional[Callable] = None,
          init_model: Optional[Union[str, Booster]] = None,
          feature_name: Union[str, List[str]] = "auto",
          categorical_feature: Union[str, List[int]] = "auto",
          early_stopping_rounds: Optional[int] = None,
          evals_result: Optional[Dict] = None,
          verbose_eval: Union[bool, int] = True,
          learning_rates: Optional[Union[List, Callable]] = None,
          keep_training_booster: bool = False,
          callbacks: Optional[List[Callable]] = None) -> Booster:
    """reference engine.py:19-280."""
    params = dict(params)
    # num_boost_round aliases resolve through Config canonicalization
    for alias in ("num_boost_round", "num_iterations", "num_iteration",
                  "n_iter", "num_tree", "num_trees", "num_round",
                  "num_rounds", "n_estimators"):
        if alias in params:
            num_boost_round = int(params.pop(alias))
    for alias in ("early_stopping_round", "early_stopping_rounds",
                  "early_stopping"):
        if alias in params:
            v = params.pop(alias)
            early_stopping_rounds = None if v is None else int(v)
    if fobj is not None:
        params["objective"] = "none"

    if not isinstance(train_set, Dataset):
        raise TypeError("Training only accepts Dataset object")
    train_set._update_params(params)
    if feature_name != "auto":
        train_set.feature_name = feature_name
    if categorical_feature != "auto":
        train_set.categorical_feature = categorical_feature

    # continued training (engine.py:139-164)
    init_booster = None
    if isinstance(init_model, str):
        init_booster = Booster(model_file=init_model)
    elif isinstance(init_model, Booster):
        init_booster = init_model

    booster = Booster(params=params, train_set=train_set)
    # resilience (resilience/): checkpoint manager + auto-resume bundle.
    # With tpu_checkpoint_dir unset both stay None and the loop below
    # adds one None check per round — no fences, no other work
    ckpt_mgr = None
    resume_bundle = None
    _r_cfg = getattr(booster, "_cfg", None)
    if _r_cfg is not None and _r_cfg.tpu_checkpoint_dir:
        from .resilience import checkpoint as _ckpt
        from .resilience import resume as _resume
        ckpt_mgr = _ckpt.CheckpointManager.from_config(_r_cfg)
        resume_bundle = _resume.load_latest(ckpt_mgr)
    if init_booster is not None and resume_bundle is None:
        # a valid checkpoint already contains the init model's trees
        _seed_from_model(booster, init_booster)
    is_valid_contain_train = False
    train_data_name = "training"
    valid_sets = valid_sets or []
    user_named = valid_names is not None
    if valid_names is None:
        valid_names = [f"valid_{i}" for i in range(len(valid_sets))]
    reduced_valid_sets = []
    name_valid_sets = []
    for i, vs in enumerate(valid_sets):
        if vs is train_set:
            is_valid_contain_train = True
            if user_named:
                train_data_name = valid_names[i]
            continue
        vs._update_params(params)
        booster.add_valid(vs, valid_names[i])
        reduced_valid_sets.append(vs)
        name_valid_sets.append(valid_names[i])
    booster.name_train_set = train_data_name

    callbacks = list(callbacks or [])
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        callbacks.append(callback_mod.early_stopping(
            int(early_stopping_rounds),
            bool(params.get("first_metric_only", False)),
            verbose=bool(verbose_eval)))
    if isinstance(verbose_eval, bool) and verbose_eval:
        callbacks.append(callback_mod.print_evaluation())
    elif isinstance(verbose_eval, int):
        callbacks.append(callback_mod.print_evaluation(verbose_eval))
    if evals_result is not None:
        callbacks.append(callback_mod.record_evaluation(evals_result))
    if learning_rates is not None:
        callbacks.append(callback_mod.reset_parameter(
            learning_rate=learning_rates))
    telemetry = getattr(getattr(booster, "_gbdt", None), "telemetry",
                        None)
    if telemetry is not None and not any(
            getattr(cb, "order", 0) == 25 for cb in callbacks):
        # tpu_trace runs fold eval values into the ledger automatically
        callbacks.append(callback_mod.log_telemetry(period=0))
    callbacks_before = [cb for cb in callbacks
                        if getattr(cb, "before_iteration", False)]
    callbacks_after = [cb for cb in callbacks if cb not in callbacks_before]
    callbacks_before.sort(key=lambda cb: getattr(cb, "order", 0))
    callbacks_after.sort(key=lambda cb: getattr(cb, "order", 0))

    # resume after valid sets + callbacks exist: restore() overwrites the
    # replayed valid scores and rehydrates callback closures (early stop)
    start_iter = 0
    resume_warmup_s = 0.0
    if resume_bundle is not None:
        import time as _time
        _t0 = _time.perf_counter()
        start_iter = _resume.restore(booster, resume_bundle,
                                     callbacks=callbacks)
        resume_warmup_s = _time.perf_counter() - _t0
    fault_plan = getattr(getattr(booster, "_gbdt", None), "_fault_plan",
                         None)
    preempted = False
    guard = None
    if ckpt_mgr is not None:
        from .resilience.preempt import PreemptGuard
        guard = PreemptGuard()
        guard.install()

    # main loop (engine.py:239-267)
    try:
        for i in range(start_iter, num_boost_round):
            if fault_plan is not None:
                fault_plan.on_round(i)
            for cb in callbacks_before:
                cb(callback_mod.CallbackEnv(
                    model=booster, params=params, iteration=i,
                    begin_iteration=0, end_iteration=num_boost_round,
                    evaluation_result_list=None, telemetry=telemetry))
            booster.update(fobj=fobj)

            evaluation_result_list = []
            if is_valid_contain_train:
                evaluation_result_list.extend(
                    (train_data_name, m, v, b)
                    for _, m, v, b in booster.eval_train())
            if reduced_valid_sets:
                evaluation_result_list.extend(booster.eval_valid())
            if feval is not None:
                evaluation_result_list.extend(
                    _run_feval(feval, booster, train_data_name,
                               is_valid_contain_train, name_valid_sets))
            try:
                for cb in callbacks_after:
                    cb(callback_mod.CallbackEnv(
                        model=booster, params=params, iteration=i,
                        begin_iteration=0, end_iteration=num_boost_round,
                        evaluation_result_list=evaluation_result_list,
                        telemetry=telemetry))
            except EarlyStopException as es:
                booster.best_iteration = es.best_iteration + 1
                evaluation_result_list = es.best_score
                break
            if guard is not None and guard.triggered:
                # finish-in-flight semantics: round i fully committed
                # above; flush one final checkpoint and stop cleanly
                ckpt_mgr.write(booster, i + 1, callbacks=callbacks,
                               reason=guard.signal_name or "preempt")
                preempted = True
                break
            if ckpt_mgr is not None and ckpt_mgr.due(i + 1):
                ckpt_mgr.write(booster, i + 1, callbacks=callbacks,
                               reason="periodic")
    finally:
        if guard is not None:
            guard.uninstall()
    booster.best_score = collections.defaultdict(collections.OrderedDict)
    for data_name, eval_name, score, _ in (evaluation_result_list or []):
        booster.best_score[data_name][eval_name] = score
    resilience_stats = None
    if ckpt_mgr is not None or start_iter:
        resilience_stats = {"resumed_from": start_iter,
                            "resume_warmup_s": resume_warmup_s,
                            "ckpt_writes": getattr(ckpt_mgr, "writes", 0),
                            "ckpt_write_s": getattr(ckpt_mgr, "write_s",
                                                    0.0),
                            "preempted": preempted}
    booster._preempted = preempted
    booster._resilience = resilience_stats
    if not keep_training_booster:
        # round-trip through the model string (engine.py:271-272)
        fresh = Booster(model_str=booster.model_to_string())
        fresh.best_iteration = booster.best_iteration
        fresh.best_score = booster.best_score
        fresh.params = params
        # the round ledger lives on the training GBDT, which this fresh
        # booster no longer holds — carry the handle so bst.telemetry
        # still resolves after train() returns
        fresh._telemetry = telemetry
        fresh._preempted = preempted
        fresh._resilience = resilience_stats
        return fresh
    return booster


def _seed_from_model(booster: Booster, init_booster: Booster) -> None:
    """Continued training: previous model's predictions become init scores
    (reference engine.py:158-164 / application.cpp:90-93)."""
    gbdt = booster._gbdt
    td = gbdt.train_data
    # replay loaded trees onto the training scores as init score
    from .ops.predict import TreePredictor
    trees = init_booster.trees
    if not trees:
        return
    pred = TreePredictor(trees)
    bundle = None
    if getattr(td, "bundles", None) is not None:
        import jax.numpy as _jnp
        b = td.bundles
        bundle = (_jnp.asarray(b.col), _jnp.asarray(b.off),
                  _jnp.asarray(b.packed.astype(np.int32)))
    leaves = pred.predict_binned_leaves(td.bins, bundle)
    k = gbdt.num_tree_per_iteration
    import jax.numpy as jnp
    for i, tree in enumerate(trees):
        gbdt.train_score.add_tree_by_leaves(
            leaves[i], tree.leaf_value[:tree.num_leaves], i % k)
    gbdt.train_score.has_init_score = True
    # keep the old trees in the model so the final model contains both
    gbdt.models = list(trees) + gbdt.models


def _run_feval(feval, booster: Booster, train_name: str,
               include_train: bool, valid_names: List[str]):
    out = []
    gbdt = booster._gbdt
    if include_train:
        if hasattr(gbdt, "_sync_train_score"):
            gbdt._sync_train_score()
        preds = gbdt.train_score.numpy()
        res = feval(preds[0] if preds.shape[0] == 1 else preds.T,
                    booster._train_set)
        out.extend(_norm_feval(res, train_name))
    for i, su in enumerate(gbdt.valid_scores):
        preds = su.numpy()
        pub = (booster._valid_sets_public[i]
               if i < len(booster._valid_sets_public) else None)
        res = feval(preds[0] if preds.shape[0] == 1 else preds.T, pub)
        name = valid_names[i] if i < len(valid_names) else f"valid_{i}"
        out.extend(_norm_feval(res, name))
    return out


def _norm_feval(res, data_name):
    if isinstance(res, list):
        return [(data_name, n, v, b) for n, v, b in res]
    n, v, b = res
    return [(data_name, n, v, b)]


# ---------------------------------------------------------------------------
# cross validation (reference engine.py:283-580)
# ---------------------------------------------------------------------------
class _CVBooster:
    def __init__(self) -> None:
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name):
        def handler_function(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs)
                    for b in self.boosters]
        return handler_function


def _make_n_folds(full_data: Dataset, folds, nfold: int, params: Dict,
                  seed: int, stratified: bool, shuffle: bool):
    full_data = full_data.construct()
    num_data = full_data.num_data
    if folds is not None:
        if not hasattr(folds, "__iter__") and not hasattr(folds, "split"):
            raise AttributeError("folds should be a generator or iterator")
        if hasattr(folds, "split"):
            group = full_data.get_group()
            group_info = (np.asarray(group, np.int64)
                          if group is not None else None)
            if group_info is not None:
                flatted_group = np.repeat(
                    range(len(group_info)), repeats=group_info)
            else:
                flatted_group = np.zeros(num_data, dtype=np.int64)
            folds = folds.split(X=np.zeros(num_data),
                                y=full_data.get_label(),
                                groups=flatted_group)
    else:
        group = full_data.get_group()
        if group is not None:
            # group-aware folds: split queries (engine.py:320-337)
            group = np.asarray(group, np.int64)
            num_queries = len(group)
            rng = np.random.RandomState(seed)
            q_perm = (rng.permutation(num_queries) if shuffle
                      else np.arange(num_queries))
            q_folds = np.array_split(q_perm, nfold)
            boundaries = np.concatenate([[0], np.cumsum(group)])
            folds = []
            for qf in q_folds:
                test_idx = np.concatenate(
                    [np.arange(boundaries[q], boundaries[q + 1])
                     for q in sorted(qf)]) if len(qf) else np.zeros(0, int)
                train_idx = np.setdiff1d(np.arange(num_data), test_idx)
                folds.append((train_idx, test_idx))
        elif stratified:
            y = np.asarray(full_data.get_label())
            rng = np.random.RandomState(seed)
            folds = []
            test_sets: List[List[int]] = [[] for _ in range(nfold)]
            for cls in np.unique(y):
                cls_idx = np.nonzero(y == cls)[0]
                if shuffle:
                    cls_idx = cls_idx[rng.permutation(len(cls_idx))]
                for f, chunk in enumerate(np.array_split(cls_idx, nfold)):
                    test_sets[f].extend(chunk.tolist())
            all_idx = np.arange(num_data)
            for f in range(nfold):
                te = np.sort(np.asarray(test_sets[f], np.int64))
                folds.append((np.setdiff1d(all_idx, te), te))
        else:
            rng = np.random.RandomState(seed)
            perm = (rng.permutation(num_data) if shuffle
                    else np.arange(num_data))
            chunks = np.array_split(perm, nfold)
            all_idx = np.arange(num_data)
            folds = [(np.setdiff1d(all_idx, np.sort(c)), np.sort(c))
                     for c in chunks]
    ret = []
    for train_idx, test_idx in folds:
        train_sub = full_data.subset(np.sort(np.asarray(train_idx)))
        valid_sub = full_data.subset(np.sort(np.asarray(test_idx)))
        ret.append((train_sub, valid_sub))
    return ret


def _agg_cv_result(raw_results):
    """reference engine.py:355-368."""
    cvmap = collections.OrderedDict()
    metric_type = {}
    for one_result in raw_results:
        for one_line in one_result:
            key = one_line[0] + " " + one_line[1]
            metric_type[key] = one_line[3]
            cvmap.setdefault(key, [])
            cvmap[key].append(one_line[2])
    return [("cv_agg", k, float(np.mean(v)), metric_type[k], float(np.std(v)))
            for k, v in cvmap.items()]


def cv(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True,
       shuffle: bool = True, metrics=None, fobj=None, feval=None,
       init_model=None, feature_name="auto", categorical_feature="auto",
       early_stopping_rounds: Optional[int] = None, fpreproc=None,
       verbose_eval=None, show_stdv: bool = True, seed: int = 0,
       callbacks=None, eval_train_metric: bool = False,
       return_cvbooster: bool = False) -> Dict[str, List[float]]:
    """reference engine.py:371-580."""
    if not isinstance(train_set, Dataset):
        raise TypeError("Training only accepts Dataset object")
    params = dict(params)
    for alias in ("num_boost_round", "num_iterations", "num_iteration",
                  "n_iter", "num_tree", "num_trees", "num_round",
                  "num_rounds", "n_estimators"):
        if alias in params:
            num_boost_round = int(params.pop(alias))
    if metrics is not None:
        params["metric"] = metrics
    cfg_obj = params.get("objective", "")
    stratified = stratified and str(cfg_obj).startswith(
        ("binary", "multiclass")) if cfg_obj else stratified

    train_set._update_params(params)
    folds_data = _make_n_folds(train_set, folds, nfold, params, seed,
                               stratified, shuffle)
    cvbooster = _CVBooster()
    fold_envs = []
    for tr, te in folds_data:
        if fpreproc is not None:
            tr, te, tparams = fpreproc(tr, te, dict(params))
        else:
            tparams = params
        bst = Booster(params=tparams, train_set=tr)
        bst.add_valid(te, "valid")
        cvbooster.append(bst)

    results = collections.defaultdict(list)
    callbacks = list(callbacks or [])
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        callbacks.append(callback_mod.early_stopping(
            int(early_stopping_rounds),
            bool(params.get("first_metric_only", False)),
            verbose=False))
    if isinstance(verbose_eval, bool) and verbose_eval:
        callbacks.append(callback_mod.print_evaluation(show_stdv=show_stdv))
    elif isinstance(verbose_eval, int):
        callbacks.append(callback_mod.print_evaluation(verbose_eval,
                                                       show_stdv))
    callbacks_before = [cb for cb in callbacks
                        if getattr(cb, "before_iteration", False)]
    callbacks_after = [cb for cb in callbacks if cb not in callbacks_before]

    for i in range(num_boost_round):
        for cb in callbacks_before:
            cb(callback_mod.CallbackEnv(
                model=cvbooster, params=params, iteration=i,
                begin_iteration=0, end_iteration=num_boost_round,
                evaluation_result_list=None))
        raw = []
        for bst in cvbooster.boosters:
            bst.update(fobj=fobj)
            one = bst.eval_valid()
            if eval_train_metric:
                one = [("train " + d, m, v, b) for d, m, v, b
                       in bst.eval_train()] + one
            if feval is not None:
                one = one + _run_feval(feval, bst, "training", False,
                                       ["valid"])
            raw.append(one)
        res = _agg_cv_result(raw)
        for _, key, mean, _, std in res:
            results[key + "-mean"].append(mean)
            results[key + "-stdv"].append(std)
        try:
            for cb in callbacks_after:
                cb(callback_mod.CallbackEnv(
                    model=cvbooster, params=params, iteration=i,
                    begin_iteration=0, end_iteration=num_boost_round,
                    evaluation_result_list=[
                        (r[0], r[1], r[2], r[3], r[4]) for r in res]))
        except EarlyStopException as es:
            cvbooster.best_iteration = es.best_iteration + 1
            for k in results:
                results[k] = results[k][:cvbooster.best_iteration]
            break
    if return_cvbooster:
        results["cvbooster"] = cvbooster
    return dict(results)


# many-model sweep training (sweep/): `train`'s fleet sibling,
# re-exported here so `from lightgbm_tpu.engine import train_many`
# mirrors `train`. Bottom-of-module import: sweep.trainer reaches back
# for _seed_from_model lazily, so this line must follow its definition.
from .sweep import train_many  # noqa: E402,F401  isort:skip
