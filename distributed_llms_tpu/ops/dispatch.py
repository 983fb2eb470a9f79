"""Kernel dispatch, shared by the four Pallas ops: how a mode is resolved,
where the tensor-parallel mesh comes from, and the record of which
implementation each call took.

Every op chooses between its compiled kernel, the same kernel program in
Pallas interpret mode, and a dense ``jax.numpy`` fallback.  The choice is
made while tracing (from the backend, an environment variable and the
shapes), so nothing in the compiled program says which one ran.
:func:`record` counts each decision in ``METRICS`` as
``ops.dispatch.<op>.<path>`` (exported by ``/metrics``) and, on a TPU run,
logs one WARNING per op and shape that took anything but the kernel.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import threading

import jax

from ..core.observability import METRICS, get_logger

log = get_logger("ops.dispatch")

PATHS = ("kernel", "interpret", "fallback")

# (op, shape) pairs already warned about; trace-time only, so the lock is
# never contended on a hot path.
_warned: set[tuple] = set()
_warned_lock = threading.Lock()

# The mesh of the GSPMD-partitioned jit being traced (tensor-parallel
# serving; parallel.api.ParallelModel.forward sets it).  Under this mesh
# quant_matmul and the decode-attention ops run :func:`per_shard`.
_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "dlt_dispatch_mesh", default=None
)
# Whether the trace is inside a :func:`per_shard` body.
_IN_SHARD: contextvars.ContextVar = contextvars.ContextVar(
    "dlt_dispatch_in_shard", default=False
)


def kernel_mode(env_var: str) -> str:
    """Resolve an op's mode variable: "kernel" (compiled Pallas),
    "interpret" (the kernel's program on the Pallas interpreter — the CPU
    test leg), "fallback" (dense), or unset/"auto": kernel iff the default
    backend is a TPU."""
    mode = os.environ.get(env_var, "auto")
    if mode in PATHS:
        return mode
    return "kernel" if jax.default_backend() == "tpu" else "fallback"


def attention_mode() -> str:
    """DLT_RAGGED_DECODE: "kernel" | "interpret" | "fallback" | "auto"
    (kernel iff TPU): the one mode of the attention kernels, the decode
    ones (ops/decode_attn.py) and, read by models/model.py, the flash
    kernel of an admission."""
    return kernel_mode("DLT_RAGGED_DECODE")


def record(op: str, path: str, shape: tuple) -> None:
    """Count one trace-time dispatch of ``op`` onto ``path``; inside a
    :func:`per_shard` body also count it under ``shard_map`` (the body is
    traced once and runs on every shard of the mesh)."""
    METRICS.inc(f"ops.dispatch.{op}.{path}")
    if _IN_SHARD.get():
        METRICS.inc(f"ops.dispatch.{op}.shard_map")
    if path != "kernel" and jax.default_backend() == "tpu":
        with _warned_lock:
            first = (op, shape) not in _warned
            _warned.add((op, shape))
        if first:
            log.warning(
                "%s took the %s path on a TPU run at shape %s "
                "(compiled kernel not used)", op, path, shape,
            )


@contextlib.contextmanager
def sharded(mesh):
    """Mark the enclosed trace as running under a GSPMD jit on ``mesh``."""
    token = _MESH.set(mesh)
    try:
        yield
    finally:
        _MESH.reset(token)


def mesh():
    """The mesh set by :func:`sharded`, or None on a single-device trace."""
    return _MESH.get()


def per_shard(body, mesh_, in_specs: tuple, out_specs):
    """``body`` on every shard of ``mesh_`` under ``jax.shard_map`` (all
    axes manual).  A Mosaic kernel has no automatic SPMD partitioning:
    traced bare under a GSPMD jit, XLA refuses it."""

    def traced(*args):
        token = _IN_SHARD.set(True)
        try:
            return body(*args)
        finally:
            _IN_SHARD.reset(token)

    return jax.shard_map(
        traced, mesh=mesh_, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def axis(mesh_, name: str, *dims: int) -> str | None:
    """``name`` when that mesh axis is larger than one and divides every
    given dimension (so each shard holds a whole slice), else None."""
    size = mesh_.shape.get(name, 1)
    return name if size > 1 and all(d % size == 0 for d in dims) else None
