"""Test configuration: force a clean CPU JAX with 8 virtual devices.

The tests run on the CPU backend (``JAX_PLATFORMS=cpu``); jaxenv.force_cpu()
pins 8 virtual CPU devices so sharding/collective paths are exercised without
TPU hardware.  The chip itself is only ever reached through ``chip_smoke.py``
and ``benchmarks/run.py``; tests/test_tpu_compile.py compiles for a
described one.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tigerbeetle_tpu import jaxenv  # noqa: E402

# Persistent XLA compile cache (repo-local .jax_cache/, gitignored): the
# kernel suites are compile-dominated on CPU — a warm cache cuts e.g.
# test_transfer_full from ~81 s to ~26 s.  Must be set before the first
# backend init, like the device-count flag.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
jaxenv.enable_compile_cache()

jaxenv.force_cpu(8)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _obs_registry_leak_guard(request):
    """The process-global obs registry must be DISABLED when every test
    ends (the PR 10 metrics-registry leak class: a leaked enable() taxes
    every later test and mixes foreign series into the next snapshot).
    Cost when clean: one attribute read per test.  On a leak: disable,
    reset, and fail the offending test — use registry.enabled_scope() or
    try/finally disable()+reset()."""
    yield
    from tigerbeetle_tpu.obs.metrics import registry

    if registry.enabled:
        registry.disable()
        registry.reset()
        pytest.fail(
            f"{request.node.nodeid} leaked the process-global obs "
            "registry ENABLED at teardown — wrap enable() in "
            "registry.enabled_scope() (obs/metrics.py) or try/finally "
            "disable()+reset()"
        )
