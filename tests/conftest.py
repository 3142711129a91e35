"""Test harness: force an 8-device virtual CPU mesh so distributed learners
are exercised without real multi-chip hardware (SURVEY.md §4: the TPU analogue
of the reference's localhost-socket multi-rank trick).

Both settings must land before jax initialises a backend.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import gc  # noqa: E402

import pytest  # noqa: E402

# Every compiled XLA:CPU executable keeps ~15 memory mappings of its own,
# and one tier-1 process compiles thousands of programs. Past
# vm.max_map_count (65530) the next compile aborts the interpreter —
# which is what the suite did at ~82% once the 42 f64 tests that JAX 0.9
# had broken started compiling again. Half-way to the limit, drop the
# compiled programs between modules (they are rarely shared across
# modules, so nearly nothing recompiles).
_MAP_BUDGET = 30_000


def _mapping_count() -> int:
    try:
        with open("/proc/self/maps") as fh:
            return sum(1 for _ in fh)
    except OSError:                       # no procfs: nothing to bound
        return 0


@pytest.fixture(autouse=True, scope="module")
def _bound_compiled_program_mappings():
    yield
    if _mapping_count() > _MAP_BUDGET:
        from lightgbm_tpu import compile_cache
        compile_cache.clear_programs()
        jax.clear_caches()
        gc.collect()
