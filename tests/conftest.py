"""Test harness: force an 8-device virtual CPU mesh before jax initializes.

Sharding/parallelism tests run on CPU with
``--xla_force_host_platform_device_count=8`` (SURVEY.md §4 implication) so the
full TP/DP pjit programs compile and execute without TPU hardware.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from runbookai_tpu.utils.cpu_mesh import force_cpu_platform

# RUNBOOK_ON_DEVICE=1 skips the CPU forcing so tests/test_pallas_on_device.py
# can see the session's real accelerator:
#   RUNBOOK_ON_DEVICE=1 pytest tests/test_pallas_on_device.py
if os.environ.get("RUNBOOK_ON_DEVICE", "0") in ("", "0"):
    force_cpu_platform(8)

import jax

# Full-precision matmuls so numerics tests compare exactly.
jax.config.update("jax_default_matmul_precision", "highest")
# Tier-1 keeps no compile cache, whichever entry point a test drives
# (cli.main and chip_smoke place one: utils/compile_cache.py).
jax.config.update("jax_enable_compilation_cache", False)

import asyncio
import inspect

import pytest


@pytest.fixture(autouse=True)
def _stop_leaked_sampler_threads():
    """Stop the sampler loops a test started and did not stop.

    ``JaxTpuClient.from_config`` starts an incident monitor and a metric
    history sampler (and, when configured, fleet supervisors); a test that
    builds a client without ``shutdown()`` leaves their threads scraping
    the registry of a dead engine under every later test (ROADMAP A0: 22
    such loops were alive when the suite segfaulted at 83%). The owner of
    each loop is the bound ``_run`` target's instance."""
    yield
    import threading

    for t in threading.enumerate():
        if t.name in ("incident-monitor", "tsdb-sampler",
                      "fleet-supervisor"):
            owner = getattr(getattr(t, "_target", None), "__self__", None)
            if owner is not None:
                owner.stop()


def _memory_maps() -> tuple[int, int]:
    """(mappings this process holds, the kernel's per-process limit)."""
    with open("/proc/self/maps") as fh:
        held = sum(1 for _ in fh)
    with open("/proc/sys/vm/max_map_count") as fh:
        return held, int(fh.read())


@pytest.fixture(autouse=True, scope="module")
def _bound_compiled_programs():
    """Drop jax's compiled programs once they hold half the memory
    mappings a process may have.

    Every XLA:CPU executable keeps a few mmap'd regions for as long as
    jax caches it, which is for the life of the process; the suite
    compiles tens of thousands. At ``vm.max_map_count`` (65,530) the next
    mmap fails inside LLVM and the run dies with a segmentation fault "in
    an XLA CPU compile" — at 83% of this suite, in whichever test happens
    to compile next (ROADMAP A0; the test always passes alone). Clearing
    the caches between modules unmaps them; only past the half-way mark,
    because every clear is paid for in recompiles."""
    yield
    try:
        held, limit = _memory_maps()
    except OSError:  # no /proc: nothing to bound
        return
    if held > limit // 2:
        jax.clear_caches()


def pytest_collection_modifyitems(config, items):
    # Lightweight asyncio support without requiring pytest-asyncio.
    for item in items:
        if inspect.iscoroutinefunction(getattr(item, "function", None)):
            item.add_marker(pytest.mark.asyncio_inline)


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.function
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None
