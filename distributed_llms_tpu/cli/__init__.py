"""Command-line entry points (``dlt-serve``, ``dlt-coordinator``,
``dlt-host``) and the one step they share before their first jit."""

from __future__ import annotations

import os

# The persistent XLA compilation cache of a TPU run, when the environment
# does not place it: one fixed, git-ignored directory at the root of the
# checkout.  The directory is part of every cache key, so it never moves.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def init_backend() -> dict:
    """Initialise the JAX backend, say once which devices it found, start
    counting compiles (``runtime.compiles_total``) and place the
    compilation cache; returns core.profiling.device_report().

    Call after any ``jax_platforms`` pin and ``jax.distributed``
    initialisation, before the first jit.  Where JAX_COMPILATION_CACHE_DIR
    is set JAX reads it by itself and nothing is set here.  Where it is
    not, a TPU run caches under :data:`COMPILE_CACHE_DIR` — a restarted
    server then skips the compiles it already paid for — and a CPU run
    sets none (XLA:CPU executable serialisation has crashed this
    repository's test suite; see tests/conftest.py)."""
    import jax

    from ..core.observability import get_logger
    from ..core.profiling import count_compiles, device_report

    count_compiles()
    report = device_report()
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache and report["platform"] == "tpu":
        cache = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache)
    get_logger("cli").info(
        "jax backend: platform=%s device_kind=%s count=%d; compilation "
        "cache: %s", report["platform"], report["device_kind"],
        report["count"], cache or "none",
    )
    return report
