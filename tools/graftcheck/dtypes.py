"""GC3 — dtype-promotion lint over hot-function jaxprs.

Two accident classes, both invisible in source review and both caught here
by walking the traced jaxpr:

- GC301 float64 anywhere: with x64 enabled (a stray env flag, a
  ``np.float64`` constant) a hot function silently doubles its FLOPs and
  HBM.  Any f64/c128 aval in the trace fails.
- GC302 unallowlisted bf16->f32 upcast: a ``convert_element_type`` whose
  input is bf16 and output f32 doubles the bandwidth of whatever consumes
  it.  Deliberate stability upcasts (norms, RoPE tables, routers) are
  allowlisted BY FUNCTION NAME — the eqn's source attribution
  (``source_info_util.user_frame``) must land in the contract's
  ``allow_upcast`` set, so a new upcast in new code fails even when old
  ones stay blessed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .core import Finding, walk_eqns

try:
    from jax._src import source_info_util
except Exception:  # pragma: no cover - internal layout moved
    source_info_util = None


_WIDE = {jnp.dtype("float64"), jnp.dtype("complex128")}


def _frame_of(eqn) -> tuple[str, str]:
    if source_info_util is None:
        return ("?", "?")
    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return ("?", "?")
    # function_name is the qualified name; allowlists name the bare one.
    return (frame.file_name.rsplit("/", 1)[-1],
            frame.function_name.rsplit(".", 1)[-1])


def _avals(eqn):
    for v in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            yield aval


def check(contracts=None) -> list[Finding]:
    if contracts is None:
        from .contracts import hot_contracts

        contracts = hot_contracts()
    findings: list[Finding] = []
    for contract in contracts:
        try:
            fn, args = contract.build()
            jaxpr = jax.make_jaxpr(fn)(*args)
        except Exception as exc:
            findings.append(Finding(
                "GC301", contract.path, 0,
                f"{contract.name}: hot function failed to trace: "
                f"{type(exc).__name__}: {str(exc).splitlines()[0][:160]}"))
            continue
        wide_sites: set[tuple[str, str]] = set()
        upcast_sites: set[tuple[str, str]] = set()
        for eqn in walk_eqns(jaxpr):
            for aval in _avals(eqn):
                if aval.dtype in _WIDE:
                    wide_sites.add(_frame_of(eqn))
                    break
            if (source_info_util is not None
                    and eqn.primitive.name == "convert_element_type"
                    and eqn.outvars[0].aval.dtype == jnp.float32
                    and any(getattr(v, "aval", None) is not None
                            and getattr(v.aval, "dtype", None) == jnp.bfloat16
                            for v in eqn.invars)):
                # Source attribution IS the allowlist mechanism: without
                # source_info_util (internal jax layout moved) GC302 must
                # SKIP, not flag every deliberate upcast as "? (?)".
                site = _frame_of(eqn)
                if site[1] not in contract.allow_upcast:
                    upcast_sites.add(site)
        for fname, func in sorted(wide_sites):
            findings.append(Finding(
                "GC301", contract.path, 0,
                f"{contract.name}: float64 reaches the trace via "
                f"{func} ({fname})"))
        for fname, func in sorted(upcast_sites):
            findings.append(Finding(
                "GC302", contract.path, 0,
                f"{contract.name}: bf16->f32 upcast in {func} ({fname}) "
                f"is not in the allowlist "
                f"{sorted(contract.allow_upcast) or '[]'}"))
    return findings
