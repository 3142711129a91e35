"""Process-wide XLA program registry and persistent-compile-cache wiring.

Training used to build its jitted programs per ``Booster`` instance: every
``DeviceTreeLearner`` / ``AlignedEngine`` held its own dict of
``jax.jit`` wrappers, so a second model trained on the same shapes paid
the full trace + XLA-compile bill again.  jax's trace cache is keyed on
the *function object*, and a fresh closure per instance is a fresh
function object — a cache that can never hit across instances.

This module fixes that at two levels:

* ``program(key, factory)`` — a process-wide registry of jitted
  programs.  ``key`` must capture everything the factory closure bakes
  into the trace (shapes, static ints, config scalars, and fingerprints
  of any *data* arrays the closure captures).  Two engines with equal
  keys share one jitted callable and therefore one trace per input
  shape.
* ``init_persistent_cache()`` — one-shot wiring of jax's on-disk
  compilation cache at ``cache_dir()`` so a fresh *process* also skips
  XLA compilation.

``note_trace()`` / ``trace_count()`` implement the compile-count
regression contract: every registered program body bumps the counter
when its Python source actually runs (i.e. once per jax trace), so a
test can train twice at the same shape and assert the second run
performed zero traces.  This mirrors ``serve.ForestEngine.compile_count``.
"""
from __future__ import annotations

import contextlib
import hashlib
import os
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

_lock = threading.Lock()
_programs: Dict[Any, Callable] = {}
_trace_count = 0
_tls = threading.local()          # per-thread attribution tag


def note_trace() -> None:
    """Record one jax trace. Call at the top of every registered program
    body — the Python body runs once per trace, never on cache hits."""
    global _trace_count
    _trace_count += 1


def trace_count() -> int:
    return _trace_count


def program_tag(key: Any) -> str:
    """Short human-stable tag for a registry key: its leading name (when
    the key is the conventional ("name", ...) tuple) plus a digest of
    the full shape/config signature. This is what a persistent-cache
    MISS event carries — enough to say WHICH program at WHICH traced
    signature recompiled (the 552 s warm-up attribution question)."""
    name = "program"
    if isinstance(key, tuple) and key and isinstance(key[0], str):
        name = key[0]
    digest = hashlib.sha1(repr(key).encode()).hexdigest()[:10]
    return f"{name}:{digest}"


def current_attribution() -> Optional[str]:
    """The program tag (or explicit `attribution` label) active on this
    thread — what a compile-cache miss fired now would be blamed on."""
    return getattr(_tls, "tag", None)


@contextlib.contextmanager
def attribution(tag: str):
    """Label compiles dispatched inside the block (for paths that do not
    go through `program()`, e.g. the serve engine's bucket programs or
    a bench stage)."""
    prev = getattr(_tls, "tag", None)
    _tls.tag = tag
    try:
        yield
    finally:
        _tls.tag = prev


def _attributed(key: Any, fn: Callable) -> Callable:
    """Wrap a registered program so any compile its dispatch triggers is
    attributed to its registry key (one thread-local store per call;
    the jit trace cache keys on `fn`, which stays stable inside)."""
    tag = program_tag(key)

    def run(*args, **kwargs):
        prev = getattr(_tls, "tag", None)
        _tls.tag = tag
        try:
            return fn(*args, **kwargs)
        finally:
            _tls.tag = prev
    run.__wrapped__ = fn
    return run


def program(key: Any, factory: Callable[[], Callable]) -> Callable:
    """Return the process-wide jitted program for ``key``, building it
    via ``factory()`` on first use. ``key`` must be hashable and must
    cover every value the factory's closure bakes into the trace."""
    fn = _programs.get(key)
    if fn is None:
        with _lock:
            fn = _programs.get(key)
            if fn is None:
                fn = _attributed(key, factory())
                _programs[key] = fn
    return fn


def registry_size() -> int:
    return len(_programs)


def registered_program_tags() -> List[str]:
    """Tags of every registered program (miss-attribution surface: the
    fleet's sweep_round programs show up here next to the sequential
    ones, so a registry dump names what traced)."""
    with _lock:
        return sorted(program_tag(k) for k in _programs)


def clear_programs() -> None:
    """Drop every registered program (tests only — releases the device
    buffers captured by program closures)."""
    with _lock:
        _programs.clear()
    from .obs import phases
    phases.forget()     # its entries hold the same jitted functions


def array_fingerprint(*arrays) -> str:
    """Stable content hash of host/device arrays, for registry keys.

    Program closures legitimately capture data-derived device arrays
    (bin meta tables, objective label/weight buffers). Sharing such a
    program between models is only sound when that captured data is
    identical, so the registry key carries a digest of it.
    """
    h = hashlib.blake2b(digest_size=16)
    for a in arrays:
        if a is None:
            h.update(b"\x00none")
            continue
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def config_signature(cfg) -> Tuple:
    """Hashable snapshot of every Config field (program closures read
    hyperparameters freely, so the whole config is part of the key)."""
    import dataclasses

    items = []
    for f in dataclasses.fields(cfg):
        v = getattr(cfg, f.name)
        if isinstance(v, (list, tuple)):
            v = tuple(v) if all(
                isinstance(x, (int, float, str, bool, type(None)))
                for x in v) else repr(v)
        elif not isinstance(v, (int, float, str, bool, type(None))):
            v = repr(v)
        items.append((f.name, v))
    return tuple(items)


class HashableFn:
    """Wrap a callable so it hashes/compares by an explicit signature.

    ``move_pass`` / ``slot_hist_pass`` take the point-gradient callback
    as a *static* jit argument; jax keys the trace cache on its hash.
    Objectives hand out a fresh closure per instance, so without this
    wrapper every new Booster forced a retrace of the module-level
    kernels even though the closures compute the same function.
    """

    __slots__ = ("fn", "sig")

    def __init__(self, fn: Callable, sig: Any):
        self.fn = fn
        self.sig = ("HashableFn", sig)

    def __call__(self, *args, **kwargs):
        return self.fn(*args, **kwargs)

    def __hash__(self):
        return hash(self.sig)

    def __eq__(self, other):
        return isinstance(other, HashableFn) and self.sig == other.sig

    def __repr__(self):  # keeps jax debug names stable across instances
        return f"HashableFn({self.sig!r})"


_persistent_cache_dir: Optional[str] = None
_pcache_hits = 0
_pcache_misses = 0
_miss_by_program: Dict[str, int] = {}
_hooks_installed = False


def persistent_cache_events() -> Dict[str, int]:
    """Counts of persistent-compile-cache hits/misses observed by the
    jax hooks this process (zeros until `install_cache_event_hooks`)."""
    return {"hits": _pcache_hits, "misses": _pcache_misses}


def miss_attribution() -> Dict[str, int]:
    """Persistent-cache miss counts keyed by the attribution tag active
    when each miss fired (`program_tag` for registry programs, explicit
    `attribution()` labels elsewhere, "unattributed" when none). This is
    the aggregate the CLI folds into trace_summary.json — the per-event
    stream already lands on the structured log channel."""
    return dict(_miss_by_program)


def note_persistent_cache_miss(module_name: str, cache_key: str = "") -> None:
    """Record one persistent-cache miss: bump the counter and emit a
    structured `[Event]` carrying the XLA module name, the cache key,
    and the traced program signature active on this thread — the data
    needed to explain a long warm-up DESPITE compile_cache_hit=true
    (which only says the cache directory was non-empty, not that every
    program hit)."""
    global _pcache_misses
    _pcache_misses += 1
    tag = current_attribution() or "unattributed"
    _miss_by_program[tag] = _miss_by_program.get(tag, 0) + 1
    from .utils import log
    log.event("compile_cache_miss", module=str(module_name),
              key=str(cache_key)[:20], program=current_attribution())


def _note_persistent_cache_hit(module_name: str, cache_key: str = "") -> None:
    global _pcache_hits
    _pcache_hits += 1


def install_cache_event_hooks() -> None:
    """Wrap jax's persistent-cache logging seam
    (`jax._src.compiler.log_persistent_cache_{miss,hit}` — called
    exactly once per compile on the miss/hit path) so every miss lands
    on the structured log channel with program attribution.
    Idempotent."""
    global _hooks_installed
    if _hooks_installed:
        return
    from jax._src import compiler as _jax_compiler
    orig_miss = _jax_compiler.log_persistent_cache_miss
    orig_hit = _jax_compiler.log_persistent_cache_hit

    def miss(module_name, cache_key, *a, **kw):
        note_persistent_cache_miss(getattr(module_name, "name",
                                           module_name), cache_key)
        return orig_miss(module_name, cache_key, *a, **kw)

    def hit(module_name, cache_key, *a, **kw):
        _note_persistent_cache_hit(getattr(module_name, "name",
                                           module_name), cache_key)
        return orig_hit(module_name, cache_key, *a, **kw)

    _jax_compiler.log_persistent_cache_miss = miss
    _jax_compiler.log_persistent_cache_hit = hit
    _hooks_installed = True


def persistent_cache_dir() -> Optional[str]:
    return _persistent_cache_dir


def cache_dir_entries(path: Optional[str]) -> int:
    """Count cache files currently in a compilation-cache directory."""
    if not path or not os.path.isdir(path):
        return 0
    n = 0
    for _root, _dirs, files in os.walk(path):
        n += len(files)
    return n


def cache_dir() -> str:
    """THE persistent-compile-cache location: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed ``<checkout>/.jax_cache``. The path is part
    of jax's cache key, so nothing else in the tree names a directory:
    ``init_persistent_cache`` (called by ``chip_smoke.py`` and
    ``Config.update``) resolves through here."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


def init_persistent_cache() -> str:
    """Point jax's persistent compilation cache at ``cache_dir()``
    (one-shot; returns the directory).

    jax alone would honour the environment variable but keep its 1 s
    ``min_compile_time_secs`` floor (the round loop is dozens of faster
    programs — none would be written) and, on non-TPU backends, leave
    the XLA-client caches off. Both are forced here. Must run before the
    first compile; process entry points call it first thing, and
    ``Config.update`` calls it whenever the environment names a
    directory.
    """
    global _persistent_cache_dir
    if _persistent_cache_dir is not None:
        return _persistent_cache_dir
    path = os.path.abspath(os.path.expanduser(cache_dir()))
    os.makedirs(path, exist_ok=True)

    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # Required for cache hits on the CPU backend; harmless on TPU.
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    install_cache_event_hooks()
    _persistent_cache_dir = path
    return path
