"""graftcheck core: findings, baseline, jaxpr utilities.

graftcheck is graftlint's semantic sibling: where graftlint reads the AST,
graftcheck EXECUTES the real code under abstract values — ``jax.eval_shape``
over fake meshes, ``jax.make_jaxpr`` over the hot functions, ``.lower()``
over the jitted decode path — so it sees exactly what XLA will see, at zero
FLOPs.  Rule families:

- GC1xx shape/dtype contracts (tools/graftcheck/shapes.py)
- GC2xx sharding-spec audit  (tools/graftcheck/sharding.py)
- GC3xx dtype-promotion lint (tools/graftcheck/dtypes.py)
- GC4xx recompilation hazard (tools/graftcheck/recompile.py)
- GC5xx donation audit       (tools/graftcheck/donation.py)

Findings, suppression-free by design (semantic contracts are fixed or
baselined, never inline-excused), share graftlint's baseline format:
``graftcheck_baseline.txt`` is checked in EMPTY, entries normalize without
line numbers, and ``[xN]`` counts make the baseline a multiset.
"""

from __future__ import annotations

import hashlib
from pathlib import Path

import jax

from tools.graftlint.core import (  # shared format, parametrized by name
    Finding, read_baseline as _read_baseline, split_new,
    write_baseline as _write_baseline,
)

__all__ = [
    "BASELINE_NAME", "Finding", "collect_collectives", "jaxpr_hash",
    "read_baseline", "split_new", "walk_eqns", "write_baseline",
]

BASELINE_NAME = "graftcheck_baseline.txt"

# Collective primitives whose axis names must exist on the mesh they are
# traced under (GC205).  psum2 is what newer lowerings emit for psum.
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum_invariant", "pmax", "pmin", "ppermute", "pbroadcast",
    "all_gather", "all_to_all", "reduce_scatter", "axis_index",
})


def read_baseline(root: Path) -> dict[str, int]:
    return _read_baseline(root, name=BASELINE_NAME)


def write_baseline(root: Path, findings: list[Finding]) -> Path:
    return _write_baseline(
        root, findings, name=BASELINE_NAME, tool="graftcheck"
    )


def walk_eqns(jaxpr):
    """Yield every eqn in a (closed) jaxpr, recursing through call/scan/
    cond/shard_map sub-jaxprs wherever they hide in eqn params."""
    jx = getattr(jaxpr, "jaxpr", jaxpr)
    for eq in jx.eqns:
        yield eq
        for v in eq.params.values():
            for sub in _subjaxprs(v):
                yield from walk_eqns(sub)


def _subjaxprs(v):
    if hasattr(v, "eqns"):  # a raw Jaxpr
        yield v
    elif hasattr(v, "jaxpr"):  # a ClosedJaxpr
        yield v.jaxpr
    elif isinstance(v, (list, tuple)):
        for vv in v:
            yield from _subjaxprs(vv)


def collect_collectives(jaxpr) -> dict[str, set[str]]:
    """primitive name -> set of axis names it targets, over the whole
    jaxpr (sub-jaxprs included)."""
    out: dict[str, set[str]] = {}
    for eq in walk_eqns(jaxpr):
        if eq.primitive.name not in COLLECTIVE_PRIMS:
            continue
        axes = eq.params.get("axis_name", eq.params.get("axes", ()))
        if axes is None:
            axes = ()
        if isinstance(axes, (str, int)):
            axes = (axes,)
        out.setdefault(eq.primitive.name, set()).update(
            str(a) for a in axes
        )
    return out


def jaxpr_hash(fn, *abstract_args, statics: dict | None = None) -> str:
    """Stable hash of the traced program: what the compile cache would key
    on modulo backend — (jaxpr text, abstract input signature, static-arg
    signature).  ``fn`` must close over its static arguments (tracing them
    as inputs would make them unhashable tracers); pass the same values in
    ``statics`` so they contribute to the key verbatim."""
    jaxpr = jax.make_jaxpr(fn)(*abstract_args)
    sig = ",".join(
        f"{tuple(a.shape)}:{a.dtype}" for a in jax.tree.leaves(abstract_args)
    )
    skey = repr(sorted((k, repr(v)) for k, v in (statics or {}).items()))
    return hashlib.sha256(
        (str(jaxpr) + "|" + sig + "|" + skey).encode()
    ).hexdigest()[:16]


def aval_signature(tree) -> str:
    """Compile-key view of a pytree of abstract values."""
    return ",".join(
        f"{tuple(a.shape)}:{a.dtype}" for a in jax.tree.leaves(tree)
    )
