"""Test harness: force the CPU platform with an 8-device fake mesh.

Tests run on the CPU whatever the host holds and whatever JAX_PLATFORMS
says: the platform is pinned through jax.config *before* any backend
initialization.  8 fake CPU devices exercise the same Mesh/pjit/ppermute
code paths as a TPU slice (SURVEY §4: the reference has no distributed
tests at all; this is the strategy it was missing).  What only a chip can
show is chip_smoke.py's and tools/kernel_parity.py's job.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: OPT-IN ONLY (set DLT_TEST_CACHE_DIR).
#
# It was the default for one round and cut warm-run jit waits ~5x — but
# XLA:CPU *executable* serialization is not reliable for this suite's
# largest programs: two independent full-suite runs on 2026-07-31
# SEGFAULTED inside the persistent cache, one in
# compilation_cache.get_executable_and_time (deserialize; the machine-
# feature-mismatch warnings XLA prints there explicitly threaten SIGILL)
# and one in put_executable_and_time (executable.serialize()), both on the
# speculative-decoding while_loop programs with quantized-draft leaves.
# jax_persistent_cache_enable_xla_caches="none" does NOT help — it strips
# XLA-internal sub-caches from entries; the top-level executable
# serialization is the crash site.  A green-but-slower suite beats a fast
# one that segfaults at random, so every run compiles cold unless a cache
# dir is explicitly requested.  CI does NOT request one either (ci.yml
# dropped it in the same change: prefix-restored caches would also cross
# heterogeneous runner CPU generations — the exact machine-feature
# mismatch XLA's loader warns may SIGILL); this knob exists for local
# iteration on a single box at the operator's own risk.  (One knob only:
# to disable, unset DLT_TEST_CACHE_DIR.)
_cache_dir = os.environ.get("DLT_TEST_CACHE_DIR")
if _cache_dir:
    jax.config.update("jax_compilation_cache_dir", _cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "none")

import asyncio
import inspect

import pytest


def pytest_pyfunc_call(pyfuncitem):
    """Minimal async-test support (pytest-asyncio is not in the image):
    coroutine tests run under asyncio.run."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None


def pytest_collection_modifyitems(config, items):
    """``@pytest.mark.fragile_xla_cpu`` — the SINGLE definition of the
    fresh-process isolation mechanism: XLA:CPU segfaults
    nondeterministically in backend_compile_and_load once a long-lived
    process accumulates ~300 tests of compile history (the crash follows
    whatever compiles LAST, not a specific program — see
    tests/runtime/test_isolated.py).  Marked tests skip in the main
    process and run inside test_isolated.py's fresh subprocess
    (DLT_RUN_ISOLATED=1).  Tests carrying the marker must also be listed
    in test_isolated.ISOLATED or they silently lose coverage."""
    if os.environ.get("DLT_RUN_ISOLATED") == "1":
        return
    skip = pytest.mark.skip(
        reason="compile-heavy/fragile on the long-lived XLA:CPU suite "
               "process; exercised fresh-process by "
               "tests/runtime/test_isolated.py"
    )
    for item in items:
        if "fragile_xla_cpu" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 fake CPU devices, got {len(devs)}"
    return devs


@pytest.fixture
def dispatched():
    """``dispatched()`` -> the ops.dispatch.* counts (ops/dispatch.py)
    recorded since the fixture was set up, keyed ``<op>.<path>``."""
    from distributed_llms_tpu.core.observability import METRICS

    def counts() -> dict:
        return {k[len("ops.dispatch."):]: v
                for k, v in METRICS.snapshot()["counters"].items()
                if k.startswith("ops.dispatch.")}

    before = counts()
    return lambda: {k: int(v - before.get(k, 0)) for k, v in counts().items()
                    if v != before.get(k, 0)}


@pytest.fixture
def counted_kernels():
    """``counted_kernels(jaxpr)`` -> for every ``_quant_matmul_2d`` Pallas
    call in the (closed) jaxpr and the jaxprs inside it, in order, whether
    its grid ends at the last row tile that holds a real row (a traced
    bound: ops/quant_matmul.py) or walks every tile, as the parent's call
    does; a counted call has its ``_quant_matmul_2d_padding`` behind it."""
    from jax.extend import core as jex_core

    def walk(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                bounds = eqn.params["grid_mapping"].num_dynamic_grid_bounds
                if name == "_quant_matmul_2d":
                    out.append(bounds == 1)
                    assert eqn.params["grid_mapping"].num_index_operands == 1
                elif name == "_quant_matmul_2d_padding":
                    assert out and out[-1] and bounds == 1
                continue
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if isinstance(sub, jex_core.Jaxpr):
                        walk(sub, out)
        return out

    return lambda jaxpr: walk(getattr(jaxpr, "jaxpr", jaxpr), [])
