"""ctypes binding for the native C++ helpers (src/native/).

The reference keeps its whole ingest pipeline in C++ (TextReader /
Parser / DatasetLoader with OpenMP); the Python package is a thin ctypes
wrapper over `lib_lightgbm.so` (python-package/lightgbm/basic.py:25-36).
This module is the same seam for the tpu build: the library is loaded via
ctypes, built lazily from source with the system toolchain when the build
for the current sources is missing, and every caller has a pure-Python
fallback, so the package works without a compiler.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional, Tuple

import numpy as np

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
# repo checkout layout first; installed-package layout (_native_src is
# staged into the package by setup.py) as the fallback
_SRC_DIR = os.path.join(os.path.dirname(_PKG_DIR), "src", "native")
if not os.path.isdir(_SRC_DIR):
    _SRC_DIR = os.path.join(_PKG_DIR, "_native_src")
# hashed in THIS order — the Makefile's default HASH is
# `cat $(SRCS) Makefile | sha256sum`, so a manual `make` lands on the
# same file name
_SOURCES = ("text_parser.cpp", "binning.cpp", "predictor.cpp", "Makefile")
_lib: Optional[ctypes.CDLL] = None
_lib_tried = False

FMT_CSV, FMT_TSV, FMT_LIBSVM = 0, 1, 2
_FMT_NAMES = {FMT_CSV: "csv", FMT_TSV: "tsv", FMT_LIBSVM: "libsvm"}


def _build():
    """(path-or-None, reason): the .so built from THESE sources. Its
    file name carries a digest of the sources, so a binary built from
    other sources (a stale one copied along with the tree) is never
    loaded and make's mtime comparison never decides freshness."""
    h = hashlib.sha256()
    try:
        for name in _SOURCES:
            with open(os.path.join(_SRC_DIR, name), "rb") as fh:
                h.update(fh.read())
    except OSError:
        return None, "native sources not present"
    digest = h.hexdigest()[:12]
    path = os.path.join(_SRC_DIR, f"liblgbt_native-{digest}.so")
    if os.path.isfile(path):
        return path, ""
    reason = "build produced no library"
    try:
        subprocess.run(["make", "-C", _SRC_DIR, f"HASH={digest}"],
                       check=True, capture_output=True, timeout=300)
    except (OSError, subprocess.SubprocessError) as e:
        reason = f"build failed ({e})"
    # look again whatever make said: another process building the same
    # digest at the same time may have put the library there (the
    # Makefile renames a per-process temporary into place)
    return (path, "") if os.path.isfile(path) else (None, reason)


def get_lib() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None when unavailable.
    Warns ONCE at default verbosity when the .so fails to build/load —
    ingest and batch predict silently degrading to the Python path was
    too easy to miss otherwise."""
    global _lib, _lib_tried
    if _lib is not None or _lib_tried:
        return _lib
    _lib_tried = True
    path, reason = _build()
    if path is None:
        _warn_unavailable(reason)
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError as e:
        _warn_unavailable(f"load failed: {e}")
        return None
    lib.lgbt_scan.restype = ctypes.c_int32
    lib.lgbt_scan.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32)]
    lib.lgbt_parse.restype = ctypes.c_int32
    lib.lgbt_parse.argtypes = [
        ctypes.c_char_p, ctypes.c_int32, ctypes.c_int32, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double)]
    lib.lgbt_num_threads.restype = ctypes.c_int32
    lib.lgbt_num_threads.argtypes = []
    c = ctypes
    p64, pf64, p32, p8, pu32 = (c.POINTER(c.c_int64), c.POINTER(c.c_double),
                                c.POINTER(c.c_int32), c.POINTER(c.c_int8),
                                c.POINTER(c.c_uint32))
    lib.lgbt_find_bin_numerical.restype = c.c_int32
    lib.lgbt_find_bin_numerical.argtypes = [
        pf64, c.c_int64, c.c_int64, c.c_int32, c.c_int32, pf64]
    lib.lgbt_bin_matrix.restype = c.c_int32
    lib.lgbt_bin_matrix.argtypes = [
        c.c_void_p, c.c_int32, c.c_int64, c.c_int64, p32, c.c_int64, p32,
        p32, p32, pf64, p64, p64, p32, p64, c.c_int32, c.c_void_p]
    lib.lgbt_predict.restype = c.c_int32
    lib.lgbt_predict.argtypes = [
        pf64, c.c_int64, c.c_int64, c.c_int32, p64, p64, p32, p32, p32,
        pf64, p8, pf64, p64, p32, p64, pu32, p32, p32, c.c_int32,
        c.c_int32, c.c_int32, c.c_double, pf64]
    _lib = lib
    return _lib


def _warn_unavailable(reason: str) -> None:
    from .utils import log
    log.warning(
        f"native helper library (liblgbt_native) unavailable — {reason}; "
        f"text parsing, bin finding, and batch prediction fall back to "
        f"the (slower) pure-Python path")


def native_available() -> bool:
    return get_lib() is not None


def parse_file(path: str, label_idx: int = 0
               ) -> Optional[Tuple[np.ndarray, np.ndarray, str]]:
    """Parse a CSV/TSV/LibSVM data file with the native OpenMP parser.

    Returns (labels[f64 N], features[f64 N x F], format_name), or None when
    the native library is unavailable (caller falls back to the Python
    parser). Matches `ops.parser.parse_dense` semantics: NA tokens -> NaN,
    absent libsvm entries -> 0.0.
    """
    lib = get_lib()
    if lib is None:
        return None
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    fmt = ctypes.c_int32()
    rc = lib.lgbt_scan(path.encode(), ctypes.byref(rows), ctypes.byref(cols),
                       ctypes.byref(fmt))
    if rc != 0:
        raise FileNotFoundError(f"data file {path} not found")
    n = rows.value
    if fmt.value == FMT_LIBSVM:
        f = cols.value
        eff_label = -1
    else:
        f = cols.value - (1 if label_idx >= 0 else 0)
        eff_label = label_idx
    f = max(f, 0)
    labels = np.zeros(n, np.float64)
    feats = np.zeros((n, f), np.float64)
    rc = lib.lgbt_parse(
        path.encode(), fmt.value, eff_label, f,
        labels.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        feats.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    if rc != 0:
        raise IOError(f"native parse of {path} failed")
    return labels, feats, _FMT_NAMES[fmt.value]


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def find_bin_numerical(values: np.ndarray, total_sample_cnt: int,
                       max_bin: int, min_data_in_bin: int
                       ) -> Optional[np.ndarray]:
    """Numerical bin-boundary search in C++ (binning.cpp); None when the
    native library is unavailable or the search degenerates (caller falls
    back to the Python implementation)."""
    lib = get_lib()
    if lib is None or max_bin < 2:
        return None
    values = np.ascontiguousarray(values, dtype=np.float64)
    out = np.empty(max_bin + 1, np.float64)
    n = lib.lgbt_find_bin_numerical(
        _ptr(values, ctypes.c_double), len(values), int(total_sample_cnt),
        int(max_bin), int(min_data_in_bin), _ptr(out, ctypes.c_double))
    if n < 0:
        return None
    return out[:n].copy()


def bin_matrix(data: np.ndarray, col_idx: np.ndarray, bin_type: np.ndarray,
               missing: np.ndarray, num_bin: np.ndarray,
               bounds: np.ndarray, bounds_off: np.ndarray,
               cats: np.ndarray, cat_bins: np.ndarray, cats_off: np.ndarray,
               out_dtype) -> Optional[np.ndarray]:
    """Full-matrix value->bin ingest in C++ with OpenMP over rows
    (binning.cpp lgbt_bin_matrix); None when unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    if data.dtype == np.float64:
        dtype_code = 0
    elif data.dtype == np.float32:
        dtype_code = 1
    else:
        return None
    data = np.ascontiguousarray(data)
    n, f_total = data.shape
    f_used = len(col_idx)
    out = np.empty((n, f_used), dtype=out_dtype)
    rc = lib.lgbt_bin_matrix(
        data.ctypes.data_as(ctypes.c_void_p), dtype_code, n, f_total,
        _ptr(np.ascontiguousarray(col_idx, np.int32), ctypes.c_int32),
        f_used,
        _ptr(np.ascontiguousarray(bin_type, np.int32), ctypes.c_int32),
        _ptr(np.ascontiguousarray(missing, np.int32), ctypes.c_int32),
        _ptr(np.ascontiguousarray(num_bin, np.int32), ctypes.c_int32),
        _ptr(np.ascontiguousarray(bounds, np.float64), ctypes.c_double),
        _ptr(np.ascontiguousarray(bounds_off, np.int64), ctypes.c_int64),
        _ptr(np.ascontiguousarray(cats, np.int64), ctypes.c_int64),
        _ptr(np.ascontiguousarray(cat_bins, np.int32), ctypes.c_int32),
        _ptr(np.ascontiguousarray(cats_off, np.int64), ctypes.c_int64),
        1 if out_dtype == np.uint16 else 0,
        out.ctypes.data_as(ctypes.c_void_p))
    if rc != 0:
        return None
    return out


def predict_forest(X: np.ndarray, flat: dict, num_class: int,
                   pred_leaf: bool = False, early_stop_freq: int = 0,
                   early_stop_margin: float = 0.0) -> Optional[np.ndarray]:
    """Batch raw prediction over a flattened forest (predictor.cpp),
    OpenMP over rows; None when the native library is unavailable.
    `flat` is `ops.predict.flatten_forest(trees)`."""
    lib = get_lib()
    if lib is None:
        return None
    X = np.ascontiguousarray(X, dtype=np.float64)
    n, num_feat = X.shape
    t_count = len(flat["num_leaves"])
    if pred_leaf:
        out = np.empty((n, t_count), np.float64)
    else:
        out = np.zeros((n, num_class), np.float64)
    rc = lib.lgbt_predict(
        _ptr(X, ctypes.c_double), n, num_feat, t_count,
        _ptr(flat["node_off"], ctypes.c_int64),
        _ptr(flat["leaf_off"], ctypes.c_int64),
        _ptr(flat["left"], ctypes.c_int32),
        _ptr(flat["right"], ctypes.c_int32),
        _ptr(flat["feat"], ctypes.c_int32),
        _ptr(flat["thresh"], ctypes.c_double),
        _ptr(flat["dtype"], ctypes.c_int8),
        _ptr(flat["leaf_value"], ctypes.c_double),
        _ptr(flat["cat_bnd_off"], ctypes.c_int64),
        _ptr(flat["cat_boundaries"], ctypes.c_int32),
        _ptr(flat["cat_words_off"], ctypes.c_int64),
        _ptr(flat["cat_words"], ctypes.c_uint32),
        _ptr(flat["num_leaves"], ctypes.c_int32),
        _ptr(flat["tree_class"], ctypes.c_int32),
        num_class, 1 if pred_leaf else 0, int(early_stop_freq),
        float(early_stop_margin), _ptr(out, ctypes.c_double))
    if rc != 0:
        return None
    return out if pred_leaf or num_class > 1 else out[:, 0]
