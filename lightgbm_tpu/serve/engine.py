"""Compiled, cached, shape-bucketed batch inference for forest scoring.

The training-era `TreePredictor` re-stacks the forest on host per call and
walks trees one at a time (`lax.scan` + per-tree `while_loop`). This engine
is the serving path the reference covers with `Predictor` /
`Tree::AddPredictionToScore` (predictor.hpp:66-115, tree.cpp:112-204):

* the stacked forest lives on device and is reused across calls — appended
  trees are stacked incrementally and concatenated on device instead of
  re-uploading the whole forest;
* traversal is depth-synchronized: a `[T, N]` node frontier advances one
  level per step for ALL trees at once (`fori_loop` over the forest's exact
  max depth), and the leaf-value gather + per-class accumulation fuse into
  the same jit;
* batch shapes are bucketed to powers of two (and large batches chunked to
  a fixed row count), so repeated predicts with varying N reuse one
  compiled program per bucket.

Raw-feature mode compares f64 thresholds exactly WITHOUT enabling jax x64:
doubles are encoded host-side into monotonic uint64 total-order keys split
into two uint32 planes, so `x <= t` becomes a two-limb unsigned compare.
Leaf routing is therefore bit-exact vs the host f64 walk
(`predict_raw_values`); only the final leaf-value sum runs in f32.

Two serving-density extensions ride on the same traversal:

* **compact dtype plans** (``compact="f16"/"int8"``): thresholds stored
  as f16 (or per-feature affine int8, the `ops/histogram.quantize_gh`
  per-column scale discipline applied to split thresholds), leaf values
  as f16 de-quantized to f32 on output, and the int32 topology arrays
  (children / split features) narrowed to int16. Routing then compares
  f32 values against the de-quantized threshold instead of the exact
  key planes, so compact engines are gated behind a parity check
  against the f64 oracle (serving/registry.py) — never silently wrong;
* **AOT artifacts** (serve/aot.py): the bucketed traversal program can
  be `jax.export`ed ahead of time and re-attached in a fresh process
  (``attach_aot``), so the first scored request performs zero new jax
  traces — `compile_cache.note_trace` is the probe (every `_run` body
  bumps it; a deserialized artifact call never runs the body).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import compile_cache
from ..models.tree import Tree
from ..ops.predict import stack_trees

__all__ = ["ForestEngine", "stack_forest", "compact_stack",
           "COMPACT_PLANS"]


# ---------------------------------------------------------------------------
# f64 total-order key encoding (host side, exact)

def _f64_key_u64(a: np.ndarray) -> np.ndarray:
    """Map float64 -> uint64 preserving numeric order: flip the sign bit for
    non-negatives, bit-complement negatives. -0.0 must be normalized to
    +0.0 by the caller; NaN must be masked out beforehand."""
    b = np.ascontiguousarray(a, np.float64).view(np.int64)
    ub = b.astype(np.uint64)
    return np.where(b >= 0, ub + np.uint64(1 << 63), ~ub)


def _f64_key_planes(a: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    u = _f64_key_u64(a)
    return ((u >> np.uint64(32)).astype(np.uint32),
            (u & np.uint64(0xFFFFFFFF)).astype(np.uint32))


def _scalar_key(v: float) -> Tuple[int, int]:
    hi, lo = _f64_key_planes(np.array([v], np.float64))
    return int(hi[0]), int(lo[0])


# |fv| <= 1e-35 (the reference kZeroThreshold test, tree.h:216-270) in key
# space: key(-1e-35) <= key(fv) <= key(+1e-35)
_KZP = _scalar_key(1e-35)
_KZN = _scalar_key(-1e-35)


def _key_le(ah, al, bh, bl):
    return (ah < bh) | ((ah == bh) & (al <= bl))


def _pow2_ceil(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def _pow2_floor(x: int) -> int:
    return 1 << (max(int(x), 1).bit_length() - 1)


# ---------------------------------------------------------------------------
# forest stacking

def stack_forest(trees: List[Tree], num_class: int = 1,
                 binned: bool = False,
                 class_offset: int = 0) -> Dict[str, object]:
    """Host-side stacking for the serve engine: `stack_trees` plus the
    per-tree class assignment, f32 leaf values, and (raw mode) the uint32
    threshold key planes."""
    stk = stack_trees(trees, binned=binned)
    t_count = len(trees)
    stk["tree_class"] = ((np.arange(t_count, dtype=np.int32) + class_offset)
                         % max(num_class, 1))
    stk["leaf_value_f32"] = stk["leaf_value"].astype(np.float32)
    if not binned:
        thr = stk["threshold"]
        thr = np.where(thr == 0.0, 0.0, thr)      # -0.0 -> +0.0
        stk["thr_hi"], stk["thr_lo"] = _f64_key_planes(thr)
    stk["has_cat"] = bool(np.any(stk["cat_len"] > 0))
    return stk


COMPACT_PLANS = ("off", "f16", "int8")


def _narrow_i16(a: np.ndarray) -> np.ndarray:
    """int16 when the values fit, else the array unchanged (a >32k-leaf
    tree or a >32k-word cat bitset keeps exact int32 addressing)."""
    if a.size and np.int64(a.min()) >= -32768 and np.int64(a.max()) <= 32767:
        return a.astype(np.int16)
    return a


def compact_stack(host: Dict[str, object], plan: str) -> Dict[str, object]:
    """Rewrite a raw-mode host stack (`stack_forest` output) under a
    compact dtype plan.

    ``f16``: thresholds as float16, compared in f32 after upcast.
    ``int8``: per-feature affine quantization — for feature ``j`` with
    numerical-split thresholds ``ts``, ``off = mid(ts)`` and ``scale =
    range(ts) / 254`` (the `quantize_gh` per-column absmax/qmax scale
    discipline, recentered), so a feature whose thresholds span <= 254
    distinct affine steps round-trips near-exactly. Both plans store
    leaf values as f16 (de-quantized to f32 at the output gather) and
    narrow the int32 topology arrays to int16. Exactness is NOT
    promised — the serving registry's parity gate is the contract.
    """
    if plan not in ("f16", "int8"):
        raise ValueError(f"unknown compact plan {plan!r}")
    out = dict(host)
    for key in ("split_feature", "left_child", "right_child",
                "cat_start", "cat_len"):
        out[key] = _narrow_i16(np.asarray(host[key]))
    thr = np.asarray(host["threshold"], np.float64)
    if plan == "f16":
        out["thr_f16"] = thr.astype(np.float16)
    else:
        sf = np.asarray(host["split_feature"], np.int64)
        dt = np.asarray(host["decision_type"], np.int32)
        nl = np.asarray(host["num_leaves"], np.int32)
        m = thr.shape[1]
        # only real numerical internal nodes feed the per-feature
        # stats: zero-padding rows and categorical nodes would drag
        # feature 0's range toward 0.0 for nothing (their threshold is
        # never compared)
        valid = (np.arange(m, dtype=np.int32)[None, :]
                 < np.maximum(nl[:, None] - 1, 0)) & ((dt & 1) == 0)
        nfeat = int(sf.max()) + 1 if sf.size else 1
        t_lo = np.full(nfeat, np.inf)
        t_hi = np.full(nfeat, -np.inf)
        np.minimum.at(t_lo, sf[valid], thr[valid])
        np.maximum.at(t_hi, sf[valid], thr[valid])
        unused = ~np.isfinite(t_lo)
        t_lo[unused] = 0.0
        t_hi[unused] = 0.0
        off = (t_lo + t_hi) / 2.0
        scale = np.maximum((t_hi - t_lo) / 254.0, 1e-30)
        q = np.clip(np.rint((thr - off[sf]) / scale[sf]), -127, 127)
        out["thr_q"] = q.astype(np.int8)
        out["thr_scale"] = scale.astype(np.float32)
        out["thr_off"] = off.astype(np.float32)
    out["leaf_value_f16"] = np.asarray(host["leaf_value"],
                                       np.float64).astype(np.float16)
    return out


_DEVICE_KEYS_RAW = ("split_feature", "decision_type", "left_child",
                    "right_child", "thr_hi", "thr_lo", "cat_start",
                    "cat_len", "cat_words", "leaf_value_f32", "num_leaves",
                    "tree_class")
_DEVICE_KEYS_COMPACT_COMMON = (
    "split_feature", "decision_type", "left_child", "right_child",
    "cat_start", "cat_len", "cat_words", "leaf_value_f16", "num_leaves",
    "tree_class")
_DEVICE_KEYS_COMPACT = {
    "f16": _DEVICE_KEYS_COMPACT_COMMON + ("thr_f16",),
    "int8": _DEVICE_KEYS_COMPACT_COMMON + ("thr_q", "thr_scale",
                                           "thr_off"),
}
# what the same stacked forest costs under compact=off, per element of
# each raw-plan array (f32_device_bytes reports the counterfactual so
# the registry/exporter can say how many bytes a compact plan saved)
_RAW_PLAN_ITEMSIZE = {
    "split_feature": 4, "decision_type": 1, "left_child": 4,
    "right_child": 4, "thr_hi": 4, "thr_lo": 4, "cat_start": 4,
    "cat_len": 4, "cat_words": 4, "leaf_value_f32": 4, "num_leaves": 4,
    "tree_class": 4,
}
_DEVICE_KEYS_BINNED = ("split_feature", "decision_type", "left_child",
                       "right_child", "threshold_in_bin", "default_bin",
                       "num_bin", "cat_start", "cat_len", "cat_words",
                       "leaf_value_f32", "num_leaves", "tree_class")

# packed-route fast path: total decision-table elements (T * nodes * bins)
# above this are not worth the host build / device memory
_ROUTE_TABLE_MAX = 1 << 24
_ROUTE_CHUNK = 256          # microchunk rows; keeps the [T, C] frontier in cache


def _build_packed_route(host: Dict[str, object]) -> Optional[Dict[str, object]]:
    """Precompute, per (tree, node, bin value), the packed int32
    ``(next_slot << k) | split_feature[next_slot]`` so binned traversal is
    two gathers per level (bin lookup + route lookup) instead of eight.

    A node's binned decision — numerical compare, categorical bitset, and
    the missing-bin default — is a pure function of (node, bin value), so
    the whole decision collapses into this table. Slots [0, m) are internal
    nodes; slot m + j is leaf j and routes to itself, so the frontier needs
    no done-row masking — every row just keeps gathering until the loop
    bound. Returns None when the forest doesn't fit the packing (table too
    large, or the slot/feature ids overflow the 31-bit pack)."""
    lc = host["left_child"]
    rc = host["right_child"]
    t_count, m = lc.shape
    leaves = int(host["leaf_value"].shape[1])
    slots = m + leaves
    nbin = host["num_bin"]
    b = int(max(int(nbin.max()) if nbin.size else 0, 2))
    sf = host["split_feature"]
    f_hi = int(sf.max()) + 1 if sf.size else 1
    k = max(int(f_hi - 1).bit_length(), 1)
    if t_count * slots * b > _ROUTE_TABLE_MAX \
            or ((slots + 1) << k) >= (1 << 30):
        return None
    v = np.arange(b, dtype=np.int32)[None, None, :]
    dt = host["decision_type"].astype(np.int32)[:, :, None]
    mt = (dt >> 2) & 3
    default_left = (dt & 2) != 0
    is_default = np.where(mt == 1, v == host["default_bin"][:, :, None],
                          np.where(mt == 2,
                                   v == host["num_bin"][:, :, None] - 1,
                                   False))
    go = np.where(is_default, default_left,
                  v <= host["threshold_in_bin"][:, :, None])
    cat = (dt & 1) != 0
    if cat.any():
        cwords = np.asarray(host["cat_words"], np.uint32)
        widx = host["cat_start"][:, :, None] + (v >> 5)
        w = cwords[np.clip(widx, 0, len(cwords) - 1)]
        cat_go = (((w >> (v & 31).astype(np.uint32)) & 1) != 0) \
            & ((v >> 5) < host["cat_len"][:, :, None])
        go = np.where(cat, cat_go, go)
    nxt = np.where(go, lc[:, :, None], rc[:, :, None]).astype(np.int32)
    # stumps never leave the (zero-filled) root row: send them to leaf 0
    nxt[host["num_leaves"] <= 1] = -1
    slot = np.where(nxt >= 0, nxt, m + ~nxt)
    feat_next = np.where(
        nxt >= 0,
        np.take_along_axis(sf, np.maximum(nxt.reshape(t_count, -1), 0),
                           axis=1).reshape(t_count, m, b),
        0)
    packed = np.empty((t_count, slots, b), np.int32)
    packed[:, :m] = (slot << k) | feat_next
    # leaf slots are fixed points (feature 0 — the gathered bin is unused)
    packed[:, m:] = (np.arange(m, slots, dtype=np.int32)
                     << k)[None, :, None]
    return {
        "packed": packed.reshape(-1),
        "root_sf": sf[:, 0].astype(np.int32),
        "bins": b, "kbits": k, "slots": slots, "leaf_base": m,
    }


class ForestEngine:
    """Device-resident forest + bucketed jit cache for batch scoring.

    `mode="raw"` scores float feature matrices with exact f64 routing;
    `mode="binned"` scores pre-binned uint8 matrices (no EFB bundle — use
    the training-side `TreePredictor` for bundled replay).
    """

    def __init__(self, trees: List[Tree], num_class: int = 1,
                 mode: str = "raw", chunk_rows: Optional[int] = None,
                 min_bucket: int = 256, compact: str = "off") -> None:
        if mode not in ("raw", "binned"):
            raise ValueError(f"unknown engine mode {mode!r}")
        if compact not in COMPACT_PLANS:
            raise ValueError(f"unknown compact plan {compact!r}")
        if compact != "off" and mode == "binned":
            raise ValueError("compact plans require mode='raw' (binned "
                             "thresholds are already uint8)")
        if not trees:
            raise ValueError("ForestEngine needs at least one tree")
        self.mode = mode
        self.compact = compact
        self.num_class = max(int(num_class), 1)
        self.min_bucket = int(min_bucket)
        self._chunk_rows_opt = chunk_rows
        self.compile_count = 0          # bumped at TRACE time only
        self.cache_hits = 0             # chunk dispatches with no new trace
        self.predict_calls = 0
        self.aot_hits = 0               # chunk dispatches via AOT artifact
        self.aot_source: Optional[str] = None
        self.device = None              # jax device pin (to_device); None
                                        # = default-device placement
        self.early_stop_exits = 0       # chunks that exited before all trees
        self._jit_run = jax.jit(self._run)
        self._jit_run_routed = jax.jit(self._run_routed)
        self._sharded_cache: dict = {}
        self._install(trees)
        # HBM accountant owner: one row per live engine, read via
        # device_bytes() (shape metadata only) at snapshot time; a
        # GC'd engine drops off the ledger automatically
        from ..obs import memory as obs_memory
        obs_memory.track("serve/forest", self,
                         lambda e: e.device_bytes())

    # -- forest cache ------------------------------------------------------
    def _install(self, trees: List[Tree]) -> None:
        host = stack_forest(trees, self.num_class, binned=(
            self.mode == "binned"))
        if self.mode == "binned":
            keys = _DEVICE_KEYS_BINNED
            self._f32_bytes = None
        else:
            # counterfactual f32-plan footprint: what this forest would
            # occupy under compact="off" (exporter reports the delta)
            self._f32_bytes = sum(
                int(np.asarray(host[k]).size) * _RAW_PLAN_ITEMSIZE[k]
                for k in _DEVICE_KEYS_RAW)
            if self.compact != "off":
                host = compact_stack(host, self.compact)
                keys = _DEVICE_KEYS_COMPACT[self.compact]
            else:
                keys = _DEVICE_KEYS_RAW
        self._stk = {k: jnp.asarray(host[k]) for k in keys}
        # forest arrays changed shape/content: exported programs and the
        # early-stop sub-stack slices are stale
        self._aot_calls: Dict[int, object] = {}
        self._es_cache: Dict[int, list] = {}
        # engine holds strong refs: tree ids stay unique while cached, so
        # the id-prefix check in update() cannot alias a freed tree
        self.trees = list(trees)
        self._ids = [id(t) for t in trees]
        self.max_depth = int(host["max_depth"])
        self.has_cat = bool(host["has_cat"])
        self.num_trees = len(trees)
        self.chunk_rows = self._chunk_rows_opt or min(
            1 << 16, max(1 << 9,
                         _pow2_floor((1 << 24) // max(self.num_trees, 1))))
        # binned CPU scoring gets the packed-route table (gather-throughput
        # bound there; TPU keeps the dense compare traversal)
        self._route = None
        if self.mode == "binned" and jax.default_backend() == "cpu":
            rt = _build_packed_route(host)
            if rt is not None:
                self._route = {
                    "packed": jnp.asarray(rt["packed"]),
                    "root_sf": jnp.asarray(rt["root_sf"]),
                    "lv_flat": jnp.asarray(
                        host["leaf_value_f32"].reshape(-1)),
                    "tree_class": self._stk["tree_class"],
                }
                self._route_bins = rt["bins"]
                self._route_kbits = rt["kbits"]
                self._route_slots = rt["slots"]
                self._route_leaf_base = rt["leaf_base"]
                self._route_leaves = int(host["leaf_value_f32"].shape[1])
                self.chunk_rows = max(
                    _ROUTE_CHUNK,
                    (self.chunk_rows // _ROUTE_CHUNK) * _ROUTE_CHUNK)

    def device_bytes(self) -> int:
        """Bytes of device memory the resident forest occupies (the
        stacked arrays plus, on the CPU binned path, the packed-route
        table). This is what the serving registry's HBM budget accounts
        against — `.nbytes` on a jax array is shape metadata, no
        transfer happens."""
        total = sum(int(v.nbytes) for v in self._stk.values())
        if self._route is not None:
            total += sum(int(v.nbytes) for v in self._route.values())
        return total

    def f32_device_bytes(self) -> int:
        """What this forest WOULD occupy under ``compact="off"`` — the
        baseline the exporter/registry quote compaction savings against.
        Equals `device_bytes()` when no compact plan is active."""
        if self._f32_bytes is None:
            return self.device_bytes()
        return int(self._f32_bytes)

    def to_device(self, device) -> "ForestEngine":
        """Pin the resident forest onto one jax device (the serving
        placer's per-device replica residency). The stacked arrays are
        committed to `device`; chunk dispatches then run there because
        jit follows the committed operand. Early-stop sub-stacks and
        AOT executables are device-bound state, so both caches are
        dropped (AOT artifacts re-attach only on the default device)."""
        self._stk = {k: jax.device_put(v, device)
                     for k, v in self._stk.items()}
        if self._route is not None:
            self._route = {k: jax.device_put(v, device)
                           for k, v in self._route.items()}
        self._es_cache = {}
        self._aot_calls = {}
        self.device = device
        return self

    def attach_aot(self, calls: Dict[int, object],
                   source: Optional[str] = None) -> None:
        """Install ahead-of-time exported traversal programs, one per shape
        bucket (serve/aot.py `load_artifact`). An attached bucket's chunk
        dispatch goes through the deserialized executable instead of
        `jax.jit(self._run)` — no Python re-trace in a fresh process."""
        self._aot_calls = dict(calls)
        self.aot_source = source

    def update(self, trees: List[Tree]) -> "ForestEngine":
        """Refresh the device forest for a (possibly mutated) tree list.

        When `trees` extends the cached list (training appended trees), only
        the new suffix is stacked on host; the device arrays are padded and
        concatenated in place of a full re-upload. Any other change
        invalidates the cache and restacks from scratch."""
        ids = [id(t) for t in trees]
        if ids == self._ids:
            return self
        n_old = len(self._ids)
        if len(ids) > n_old and ids[:n_old] == self._ids:
            self._append(trees[n_old:])
        else:
            self._install(trees)
        return self

    def _append(self, new_trees: List[Tree]) -> None:
        if self._route is not None or self.compact != "off":
            # the packed-route table (and the per-feature affine scales of
            # a compact plan) mix every per-node field; rebuilding host-side
            # costs about as much as a full restack
            self._install(self.trees + list(new_trees))
            return
        # shapes grow: exported programs and early-stop slices are stale
        self._aot_calls = {}
        self._es_cache = {}
        host = stack_forest(new_trees, self.num_class,
                            binned=(self.mode == "binned"),
                            class_offset=self.num_trees)
        old_words = int(self._stk["cat_words"].shape[0])
        # flat-bitset offsets of the new trees shift past the old words
        host["cat_start"] = np.where(host["cat_len"] > 0,
                                     host["cat_start"] + old_words, 0)
        stk = dict(self._stk)
        m_old = int(stk["left_child"].shape[1])
        l_old = int(stk["leaf_value_f32"].shape[1])

        def cat2(key, new, axis1_old, axis1_new):
            old = stk[key]
            width = max(axis1_old, axis1_new)
            if axis1_old < width:
                old = jnp.pad(old, ((0, 0), (0, width - axis1_old)))
            if axis1_new < width:
                new = np.pad(new, ((0, 0), (0, width - axis1_new)))
            return jnp.concatenate([old, jnp.asarray(new)], axis=0)

        m_new = int(host["left_child"].shape[1])
        l_new = int(host["leaf_value_f32"].shape[1])
        for key in ("split_feature", "decision_type", "left_child",
                    "right_child", "threshold_in_bin", "default_bin",
                    "num_bin", "cat_start", "cat_len", "thr_hi", "thr_lo"):
            if key in stk:
                stk[key] = cat2(key, host[key], m_old, m_new)
        stk["leaf_value_f32"] = cat2("leaf_value_f32",
                                     host["leaf_value_f32"], l_old, l_new)
        for key in ("num_leaves", "tree_class"):
            stk[key] = jnp.concatenate(
                [stk[key], jnp.asarray(host[key])], axis=0)
        stk["cat_words"] = jnp.concatenate(
            [stk["cat_words"], jnp.asarray(host["cat_words"])], axis=0)
        self._stk = stk
        self.trees = self.trees + list(new_trees)
        self._ids = self._ids + [id(t) for t in new_trees]
        self.max_depth = max(self.max_depth, int(host["max_depth"]))
        self.has_cat = self.has_cat or bool(host["has_cat"])
        self.num_trees += len(new_trees)

    # -- traversal ---------------------------------------------------------
    def _go_left_raw(self, stk, planes, feat, safe, d, rows):
        xhi, xlo, xnan = planes[0], planes[1], planes[2]
        th = jnp.take_along_axis(stk["thr_hi"], safe, axis=1)
        tl = jnp.take_along_axis(stk["thr_lo"], safe, axis=1)
        xh = xhi[feat, rows]
        xl = xlo[feat, rows]
        nn = xnan[feat, rows]
        default_left = (d & 2) != 0
        mt = (d >> 2) & 3
        le = _key_le(xh, xl, th, tl)
        near_zero = (_key_le(jnp.uint32(_KZN[0]), jnp.uint32(_KZN[1]),
                             xh, xl)
                     & _key_le(xh, xl, jnp.uint32(_KZP[0]),
                               jnp.uint32(_KZP[1])))
        is_default = ((mt == 1) & near_zero) | ((mt == 2) & nn)
        go = jnp.where(is_default, default_left, le)
        if self.has_cat:
            iv = planes[3][feat, rows]
            cs = jnp.take_along_axis(stk["cat_start"], safe, axis=1)
            cl = jnp.take_along_axis(stk["cat_len"], safe, axis=1)
            w = iv >> 5
            cwords = stk["cat_words"]
            widx = jnp.clip(cs + w, 0, cwords.shape[0] - 1)
            bit = ((cwords[widx] >> (iv & 31).astype(jnp.uint32)) & 1) != 0
            cat_left = bit & (w < cl) & (iv >= 0) & ~(nn & (mt == 2))
            go = jnp.where((d & 1) != 0, cat_left, go)
        return go

    def _go_left_raw_compact(self, stk, planes, feat, safe, d, rows):
        """Compact-plan routing: de-quantized f32 threshold compare on an
        f32 feature plane (no u64 key planes — compactness trades the
        bit-exactness guarantee for bytes; the registry parity gate is
        what stands behind the trade)."""
        xval, xnan = planes[0], planes[1]
        if self.compact == "f16":
            thr = jnp.take_along_axis(stk["thr_f16"], safe,
                                      axis=1).astype(jnp.float32)
        else:
            q = jnp.take_along_axis(stk["thr_q"], safe,
                                    axis=1).astype(jnp.float32)
            thr = q * stk["thr_scale"][feat] + stk["thr_off"][feat]
        x = xval[feat, rows]
        nn = xnan[feat, rows]
        default_left = (d & 2) != 0
        mt = (d >> 2) & 3
        le = x <= thr
        near_zero = jnp.abs(x) <= jnp.float32(1e-35)
        is_default = ((mt == 1) & near_zero) | ((mt == 2) & nn)
        go = jnp.where(is_default, default_left, le)
        if self.has_cat:
            iv = planes[2][feat, rows]
            cs = jnp.take_along_axis(stk["cat_start"], safe,
                                     axis=1).astype(jnp.int32)
            cl = jnp.take_along_axis(stk["cat_len"], safe,
                                     axis=1).astype(jnp.int32)
            w = iv >> 5
            cwords = stk["cat_words"]
            widx = jnp.clip(cs + w, 0, cwords.shape[0] - 1)
            bit = ((cwords[widx] >> (iv & 31).astype(jnp.uint32)) & 1) != 0
            cat_left = bit & (w < cl) & (iv >= 0) & ~(nn & (mt == 2))
            go = jnp.where((d & 1) != 0, cat_left, go)
        return go

    def _go_left_binned(self, stk, planes, feat, safe, d, rows):
        fval = planes[0][feat, rows].astype(jnp.int32)
        tb = jnp.take_along_axis(stk["threshold_in_bin"], safe, axis=1)
        db = jnp.take_along_axis(stk["default_bin"], safe, axis=1)
        nb = jnp.take_along_axis(stk["num_bin"], safe, axis=1)
        default_left = (d & 2) != 0
        mt = (d >> 2) & 3
        is_default = jnp.where(mt == 1, fval == db,
                               jnp.where(mt == 2, fval == nb - 1, False))
        go = jnp.where(is_default, default_left, fval <= tb)
        if self.has_cat:
            cs = jnp.take_along_axis(stk["cat_start"], safe, axis=1)
            cl = jnp.take_along_axis(stk["cat_len"], safe, axis=1)
            cwords = stk["cat_words"]
            widx = jnp.clip(cs + (fval >> 5), 0, cwords.shape[0] - 1)
            bit = ((cwords[widx] >> (fval & 31).astype(jnp.uint32)) & 1) != 0
            cat_left = bit & ((fval >> 5) < cl)
            go = jnp.where((d & 1) != 0, cat_left, go)
        return go

    def _traverse(self, stk, planes):
        n = planes[0].shape[1]
        rows = jnp.arange(n, dtype=jnp.int32)[None, :]
        if self.mode == "binned":
            go_left = self._go_left_binned
        elif self.compact != "off":
            go_left = self._go_left_raw_compact
        else:
            go_left = self._go_left_raw

        def body(_, node):
            safe = jnp.maximum(node, 0)
            # compact plans narrow split_feature to int16; index in int32
            feat = jnp.take_along_axis(stk["split_feature"], safe,
                                       axis=1).astype(jnp.int32)
            d = jnp.take_along_axis(stk["decision_type"], safe,
                                    axis=1).astype(jnp.int32)
            go = go_left(stk, planes, feat, safe, d, rows)
            nxt = jnp.where(go,
                            jnp.take_along_axis(stk["left_child"], safe,
                                                axis=1),
                            jnp.take_along_axis(stk["right_child"], safe,
                                                axis=1))
            return jnp.where(node >= 0, nxt, node)

        node0 = jnp.where(stk["num_leaves"][:, None] <= 1,
                          jnp.full((stk["num_leaves"].shape[0], n), -1,
                                   jnp.int32),
                          jnp.zeros((stk["num_leaves"].shape[0], n),
                                    jnp.int32))
        # depth is read at trace time; any forest change that could grow it
        # also changes T (a shape), forcing the retrace that re-reads it
        node = lax.fori_loop(0, self.max_depth, body, node0)
        return ~node                                   # [T, N] leaf ids

    def _run(self, stk, planes):
        self.compile_count += 1
        compile_cache.note_trace()      # AOT zero-trace probe (ISSUE 16)
        leaf = self._traverse(stk, planes)
        if "leaf_value_f16" in stk:
            # compact plan: de-quantize leaves to f32 at the gather, so
            # the per-class accumulation runs full-precision
            vals = jnp.take_along_axis(stk["leaf_value_f16"], leaf,
                                       axis=1).astype(jnp.float32)
        else:
            vals = jnp.take_along_axis(stk["leaf_value_f32"], leaf, axis=1)
        acc = jnp.zeros((self.num_class, vals.shape[1]), jnp.float32)
        acc = acc.at[stk["tree_class"]].add(vals)
        return acc, leaf

    def _run_routed(self, rt, planes):
        """Packed-route binned scoring: two gathers per level per microchunk
        (bin value, then the fused decision+child+next-feature table), with
        the chunk loop inside the jit (`lax.scan`) so small microchunks —
        which keep the [T, C] frontier cache-resident — cost no dispatch."""
        self.compile_count += 1
        compile_cache.note_trace()
        bt = planes[0]                                   # [F, bucket] uint8
        t_count = self.num_trees
        s, b, k = self._route_slots, self._route_bins, self._route_kbits
        lo_mask = (1 << k) - 1
        chunk = min(_ROUTE_CHUNK, bt.shape[1])
        nch = bt.shape[1] // chunk
        tmb = (jnp.arange(t_count, dtype=jnp.int32) * s * b)[:, None]
        # fold the per-tree leaf-row offset and the leaf-slot base together
        tl = (jnp.arange(t_count, dtype=jnp.int32) * self._route_leaves
              - self._route_leaf_base)[:, None]
        rows = jnp.arange(chunk, dtype=jnp.int32)[None, :]
        packed = rt["packed"]
        lv_flat = rt["lv_flat"]

        def one(carry, ci):
            bc = lax.dynamic_slice(bt, (0, ci * chunk),
                                   (bt.shape[0], chunk))
            bflat = bc.reshape(-1)
            # root level peeled: every tree is at node 0, so its bin values
            # are a per-tree row copy instead of a scalar gather
            v0 = jnp.take(bc, rt["root_sf"], axis=0).astype(jnp.int32)
            p = packed[tmb + v0]

            def body(_, p):
                fval = bflat[(p & lo_mask) * chunk + rows].astype(jnp.int32)
                return packed[tmb + (p >> k) * b + fval]

            p = lax.fori_loop(1, self.max_depth, body, p)
            vals = lv_flat[tl + (p >> k)]
            kc = self.num_class
            if t_count % kc == 0:
                # tree_class is cyclic (i % K) at install time, so the
                # per-class sum is a reshape + reduction, not a scatter
                acc = vals.reshape(-1, kc, chunk).sum(axis=0)
            else:
                acc = jnp.zeros((kc, chunk), jnp.float32)
                acc = acc.at[rt["tree_class"]].add(vals)
            return carry, acc

        _, outs = lax.scan(one, 0, jnp.arange(nch, dtype=jnp.int32))
        return outs.transpose(1, 0, 2).reshape(self.num_class, -1)

    # -- encoding + bucketed driver ---------------------------------------
    def _encode(self, X) -> Tuple[np.ndarray, ...]:
        if self.mode == "binned":
            b = np.asarray(X)
            return (np.ascontiguousarray(b.T),)
        X = np.asarray(X, np.float64)
        nanmask = np.isnan(X)
        Xz = np.where(nanmask, 0.0, X)
        Xz = np.where(Xz == 0.0, 0.0, Xz)             # -0.0 -> +0.0
        if self.compact != "off":
            # compact routing compares plain f32 values, not key planes
            planes = [np.ascontiguousarray(Xz.T.astype(np.float32)),
                      np.ascontiguousarray(nanmask.T)]
            if self.has_cat:
                iv = np.where(Xz < 0, -1.0,
                              np.minimum(np.trunc(Xz), float(2 ** 31 - 2)))
                planes.append(np.ascontiguousarray(iv.T.astype(np.int32)))
            return tuple(planes)
        hi, lo = _f64_key_planes(Xz)
        planes = [np.ascontiguousarray(hi.T), np.ascontiguousarray(lo.T),
                  np.ascontiguousarray(nanmask.T)]
        if self.has_cat:
            # int truncation for categorical codes; huge values clip high
            # and fail the bitset range check, negatives route right
            iv = np.where(Xz < 0, -1.0,
                          np.minimum(np.trunc(Xz), float(2 ** 31 - 2)))
            planes.append(np.ascontiguousarray(iv.T.astype(np.int32)))
        return tuple(planes)

    def _bucket(self, m: int) -> int:
        return min(self.chunk_rows, max(self.min_bucket, _pow2_ceil(m)))

    @staticmethod
    def _pad_cols(p: np.ndarray, width: int) -> np.ndarray:
        m = p.shape[1]
        if m == width:
            return p
        return np.pad(p, ((0, 0), (0, width - m)))

    def _es_segments(self, freq: int) -> list:
        """Device sub-stacks [t0, t1) for chunked early-exit: tree-axis
        slices of the resident arrays (zero-copy views on CPU; a slice of
        a device array on TPU). Shared planes (`cat_words`, the int8
        per-feature scales) stay whole — `cat_start` offsets index the
        global bitset."""
        freq = max(int(freq), 1)
        if freq not in self._es_cache:
            shared = ("cat_words", "thr_scale", "thr_off")
            segs = []
            t0 = 0
            while t0 < self.num_trees:
                t1 = min(t0 + freq, self.num_trees)
                sub = {k: (v if k in shared else v[t0:t1])
                       for k, v in self._stk.items()}
                segs.append(sub)
                t0 = t1
            self._es_cache[freq] = segs
        return self._es_cache[freq]

    def _es_satisfied(self, acc: np.ndarray, margin: float) -> bool:
        """Reference `prediction_early_stop.cpp`: binary stops when every
        row's |margin| clears the threshold, multiclass when every row's
        top1-top2 gap does. Chunk-granular — the whole chunk must agree
        before the remaining trees are skipped."""
        if self.num_class == 1:
            return bool(np.all(np.abs(acc) > margin))
        part = np.sort(acc, axis=0)
        return bool(np.all(part[-1] - part[-2] > margin))

    def predict(self, X, pred_leaf: bool = False,
                early_stop: Optional[Tuple[int, float]] = None
                ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Score a batch. Returns (margins [N, num_class] f64,
        leaves [N, T] int32 or None). Large batches stream through
        fixed-size chunks; small ones pad to a power-of-two bucket, so any
        N inside a bucket reuses the same compiled program.

        `early_stop=(freq_trees, margin)` scores the forest in
        `freq_trees`-tree segments and skips the remainder once the whole
        chunk clears the margin criterion (reference
        `prediction_early_stop.cpp` semantics, chunk-granular).
        """
        from .. import compile_cache
        from ..obs import trace as obs_trace
        from ..utils import log
        planes = self._encode(X)
        n = planes[0].shape[1]
        acc = np.empty((n, self.num_class), np.float64)
        leaves = np.empty((n, self.num_trees), np.int32) if pred_leaf \
            else None
        if pred_leaf:
            early_stop = None           # leaf ids need every tree
        step = self.chunk_rows
        self.predict_calls += 1
        with obs_trace.span("serve.predict", rows=n,
                            trees=self.num_trees):
            for lo in range(0, max(n, 1), step):
                hi = min(lo + step, n)
                m = hi - lo
                bucket = self._bucket(m)   # tail chunks drop to their own
                chunk = tuple(self._pad_cols(p[:, lo:hi], bucket)
                              for p in planes)
                cc0 = self.compile_count
                aot_fn = (self._aot_calls.get(bucket)
                          if early_stop is None and self._route is None
                          else None)
                with obs_trace.span("serve.score", bucket=bucket,
                                    rows=m), \
                        compile_cache.attribution(
                            f"serve:T{self.num_trees}:b{bucket}"):
                    if early_stop is not None and self._route is None:
                        out = self._predict_early_stop(chunk, m, early_stop)
                    elif aot_fn is not None:
                        # deserialized export: dispatch never re-runs the
                        # _run body, so note_trace/compile_count stay put
                        try:
                            out, lf = aot_fn(self._stk, chunk)
                            self.aot_hits += 1
                        except ValueError:
                            # caller planes disagree with the exported
                            # avals (e.g. fewer feature rows than the
                            # artifact was traced with): retire the
                            # bucket's program and serve via the engine
                            # jit — identical to a cold process
                            self._aot_calls.pop(bucket, None)
                            log.event("serve_aot",
                                      status="shape_mismatch",
                                      bucket=bucket)
                            out, lf = self._jit_run(self._stk, chunk)
                        if pred_leaf:
                            leaves[lo:hi] = np.asarray(lf)[:, :m].T
                    elif self._route is not None and not pred_leaf:
                        out = self._jit_run_routed(self._route, chunk)
                    else:
                        out, lf = self._jit_run(self._stk, chunk)
                        if pred_leaf:
                            leaves[lo:hi] = np.asarray(lf)[:, :m].T
                if self.compile_count == cc0:
                    self.cache_hits += 1   # bucket program already compiled
                else:
                    log.event("serve_compile", bucket=bucket,
                              routed=self._route is not None
                              and not pred_leaf,
                              compile_count=self.compile_count)
                acc[lo:hi] = np.asarray(out)[:, :m].T
        return acc, leaves

    def _predict_early_stop(self, chunk, m: int,
                            early_stop: Tuple[int, float]) -> np.ndarray:
        freq, margin = early_stop
        segs = self._es_segments(freq)
        total = np.zeros((self.num_class, chunk[0].shape[1]), np.float32)
        for si, sub in enumerate(segs):
            out, _ = self._jit_run(sub, chunk)
            total += np.asarray(out)
            if si < len(segs) - 1 and self._es_satisfied(
                    total[:, :m], margin):
                self.early_stop_exits += 1
                from ..obs import metrics as obs_metrics
                obs_metrics.note_early_stop()
                break
        return total

    # -- bulk row-sharded scoring -----------------------------------------
    def predict_sharded(self, X, devices=None) -> np.ndarray:
        """Offline/bulk scoring sharded over rows across devices
        (`shard_map` over a 1-D 'rows' mesh). Returns margins
        [N, num_class] f64. Forest arrays are replicated; the traversal is
        embarrassingly row-parallel so no collective runs."""
        from jax.sharding import Mesh, PartitionSpec as P

        devices = list(devices if devices is not None else jax.devices())
        nd = len(devices)
        if nd <= 1:
            return self.predict(X)[0]
        planes = self._encode(X)
        n = planes[0].shape[1]
        padded = max(_pow2_ceil(n), nd * self.min_bucket)
        padded = ((padded + nd - 1) // nd) * nd   # shardable row count
        planes = tuple(self._pad_cols(p, padded) for p in planes)
        key = (padded, nd)
        if key not in self._sharded_cache:
            mesh = Mesh(np.asarray(devices), ("rows",))
            spec_in = tuple(P(None, "rows") for _ in planes)
            fn = jax.shard_map(lambda stk, pl: self._run(stk, pl)[0],
                               mesh=mesh,
                               in_specs=(jax.tree_util.tree_map(
                                   lambda _: P(), self._stk), spec_in),
                               out_specs=P(None, "rows"),
                               check_vma=False)
            self._sharded_cache[key] = jax.jit(fn)
        out = self._sharded_cache[key](self._stk, planes)
        return np.asarray(out)[:, :n].T.astype(np.float64)
