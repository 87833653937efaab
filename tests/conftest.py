"""Test harness configuration.

Mirrors the reference's two-runner strategy (SURVEY.md §4): fast in-process
tests on a SIMULATED multi-device mesh — 8 virtual CPU devices via
``xla_force_host_platform_device_count`` — so distributed sharding/collective
paths compile and run without TPU hardware (reference analog:
``testing/trino-testing/.../DistributedQueryRunner.java`` spinning N servers
in one JVM).

Must run before jax initializes, hence environment mutation at import time.
"""

import os
import sys

# tests run on the CPU backend: the plain environment variable, set
# before jax is imported
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# persistent compile cache (repeat test runs skip XLA compilation),
# sub-second compiles included: the suite triggers hundreds of small XLA
# programs (one per page shape/kernel combo) and re-compiling them every
# run costs minutes against the tier-1 budget; disk is cheap
from trino_tpu.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import jax  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _cap_memory_maps():
    """Every compiled XLA executable pins ~30 memory maps for the life
    of the process; a full tier-1 run accumulates enough programs to
    cross the kernel's default ``vm.max_map_count`` (65530) near the
    90% mark, and the failing ``mmap`` surfaces as a segfault (or hang)
    inside XLA's next compile or compile-cache read.  Dropping the
    in-process executable caches between modules once the count gets
    high keeps the run bounded — the on-disk compilation cache makes
    the reload of still-needed kernels cheap."""
    yield
    try:
        with open("/proc/self/maps") as f:
            n = sum(1 for _ in f)
    except OSError:
        return
    if n > 35_000:
        import gc

        jax.clear_caches()
        gc.collect()


@pytest.fixture(autouse=True, scope="module")
def _collect_dead_tables():
    """``exec.memory.resident_table_bytes()`` is process-wide and counts
    a Memory connector's tables until the connector is collected; the
    resident runner kinds (``benchmark/systems/*_resident.py``) hold
    each load to the bytes it added.  A connector an earlier module left
    in a reference cycle, freed by the collector in the middle of a
    load, makes a 288-byte ``region`` read as no bytes at all: collect
    before a module builds anything."""
    import gc

    gc.collect()
    yield


@pytest.fixture(autouse=True)
def _isolate_template_seeds():
    """The round-17 template-seed store is process-global (like the HBO
    stats store); without clearing it between tests, one test's earned
    shapes let a LATER test's fresh runner ride a template on its first
    use — admission-timing assertions then depend on test order."""
    yield
    from trino_tpu.cache import template_seeds

    template_seeds().clear()
