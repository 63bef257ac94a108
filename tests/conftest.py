import os
import sys

# tests run on the single real CPU device (the 512-device override is
# exclusively the dry-run's); multi-device list-ranking tests spawn
# subprocesses that set XLA_FLAGS before importing jax.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))  # _hypothesis_compat et al.

import jax  # noqa: E402

jax.config.update("jax_platform_name", "cpu")


import pytest  # noqa: E402


def _mapping_budget() -> int:
    """A third of the kernel's per-process limit on memory mappings."""
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read()) // 3
    except (OSError, ValueError):
        return 0


_MAPPING_BUDGET = _mapping_budget()


@pytest.fixture(autouse=True)
def _bound_memory_mappings():
    """Every XLA:CPU executable holds memory mappings until its jit
    cache entry goes. A module that compiles hundreds of programs
    (test_listrank.py: a new shape per hypothesis example) reaches
    vm.max_map_count, and the next compile then dies inside XLA:CPU
    with SIGSEGV or SIGABRT. Clear the caches after any test that
    leaves the process above a third of the limit."""
    yield
    if _MAPPING_BUDGET:
        with open("/proc/self/maps") as f:
            if sum(1 for _ in f) > _MAPPING_BUDGET:
                jax.clear_caches()


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """XLA:CPU accumulates JIT-compiled executables across this large
    suite (hundreds of distinct programs incl. hypothesis variants);
    without eviction the CPU JIT eventually aborts. Dropping caches at
    module boundaries keeps the long single-process run healthy."""
    yield
    jax.clear_caches()
