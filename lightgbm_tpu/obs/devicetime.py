"""The chained-k DEVICE-time measurement protocol, hoisted out of
``tools/device_time_r4.py`` / ``tools/device_time_255.py`` so both are
thin CLIs over one implementation.

Protocol: build the kernel chained ``k`` times inside ONE jitted
``fori_loop`` program, warm both the k=1 and k=K variants, time each
over ``reps`` executions ending in a single device_get probe, and report
per-exec seconds as ``(t_K - t_1) / (K - 1)`` — host dispatch
overhead appears identically in both variants and cancels in the delta.

Every measurement runs inside a ``trace.span("devtime.<name>")`` so a
traced process folds the per-term numbers into the span stream, and
``TermTimer`` both logs the human line and accumulates the machine
``terms_ms`` dict the tools print.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import trace

DEFAULT_CHAIN = 8
DEFAULT_REPS = 3


def device_get_probe(x):
    """Pull ONE scalar off the first leaf of `x` — the cheapest full
    device sync (forces every queued program to finish)."""
    import jax
    import numpy as np
    return np.asarray(jax.device_get(
        jax.tree_util.tree_leaves(x)[0].reshape(-1)[:1]))


def chained_device_time(mk_fn: Callable[[int], Callable], *args,
                        chain: int = DEFAULT_CHAIN,
                        reps: int = DEFAULT_REPS
                        ) -> Tuple[float, List[float]]:
    """``mk_fn(k)`` -> jitted fn running the kernel k times; returns
    (per-exec seconds from the k=1 vs k=chain delta, [t_1, t_K] rep
    means). Clamped at 0 — scheduling noise can invert tiny deltas."""
    f1, fK = mk_fn(1), mk_fn(chain)
    for f in (f1, fK):          # compile + warm
        device_get_probe(f(*args))
    ts = []
    for f in (f1, fK):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = f(*args)
        device_get_probe(out)
        ts.append((time.perf_counter() - t0) / reps)
    return max((ts[1] - ts[0]) / (chain - 1), 0.0), ts


class TermTimer:
    """Measure named terms under the chained-k protocol, collecting a
    ``terms_ms`` dict (ms, rounded; None for failed terms) plus stderr
    progress lines — the shared shape of both device-time CLIs."""

    def __init__(self, meta: Optional[Dict[str, Any]] = None,
                 chain: int = DEFAULT_CHAIN, reps: int = DEFAULT_REPS,
                 log: Optional[Callable[[str], None]] = None,
                 catalog: Optional[Dict[str, str]] = None) -> None:
        self.out: Dict[str, Any] = dict(meta or {})
        self.out["terms_ms"] = {}
        self.chain = chain
        self.reps = reps
        self._log = log or (lambda msg: None)
        self._ts: Dict[str, List[float]] = {}
        # term-name registry (obs/terms.py TERMS): when provided, a
        # measure() under a name outside the canonical vocabulary is a
        # programming error, not data — tools pass it so their JSON
        # lines can never drift from the ledger terms_ms vocabulary
        self._catalog = catalog

    def measure(self, name: str, mk_fn: Callable[[int], Callable],
                *args, rows: Optional[int] = None) -> Optional[float]:
        """Time one term; returns per-exec seconds or None on failure
        (failures are logged and recorded as null, never raised — a
        faulting term must not void the other terms' numbers)."""
        if self._catalog is not None and name not in self._catalog:
            raise ValueError(
                f"term {name!r} not in the canonical term table "
                f"(obs/terms.py TERMS: {sorted(self._catalog)})")
        try:
            with trace.span(f"devtime.{name}", chain=self.chain):
                per, ts = chained_device_time(
                    mk_fn, *args, chain=self.chain, reps=self.reps)
        except Exception as e:  # noqa: BLE001 — tool must keep going
            self._log(f"# {name} FAILED {type(e).__name__} "
                      f"{str(e)[:200]}")
            self.out["terms_ms"][name] = None
            return None
        self.out["terms_ms"][name] = round(per * 1e3, 2)
        self._ts[name] = ts
        line = f"# {name}: {per * 1e3:.1f}ms"
        if rows:
            line += f" ({per / rows * 1e9:.2f}ns/row)"
        self._log(line)
        return per

    def derive(self, name: str, minuend: str, subtrahend: str) -> None:
        """terms_ms[name] = max(minuend - subtrahend, 0); the minuend is
        REMOVED (it was only measured to isolate the marginal term)."""
        if self._catalog is not None and name not in self._catalog:
            raise ValueError(
                f"derived term {name!r} not in the canonical term table")
        terms = self.out["terms_ms"]
        if terms.get(minuend) is not None \
                and terms.get(subtrahend) is not None:
            terms[name] = round(
                max(terms.pop(minuend) - terms[subtrahend], 0.0), 2)

    def rep_times(self, name: str) -> Optional[List[float]]:
        return self._ts.get(name)
