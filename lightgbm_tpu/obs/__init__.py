"""Observability: span tracer and always-on seams, phases (the device
programs' own names for their XLA operations), per-round metrics ledger,
live metrics registry, HBM accountant and request traces.

The fenced tracer is OFF by default and costs nothing when off:
`trace.span` returns a shared null context, `trace.fence` returns its
argument without importing jax, and the GBDT round loop takes two
attribute-is-None checks. Enable with the `tpu_trace` / `tpu_trace_dir`
params (both enter `compile_cache.config_signature`, so toggling tracing
retraces rather than silently reusing a differently-fenced program).
"""
from . import (hlo, ledger, memory, metrics, phases,  # noqa: F401
               reqtrace, terms, trace)

__all__ = ["hlo", "ledger", "memory", "metrics", "phases", "reqtrace",
           "terms", "trace"]
