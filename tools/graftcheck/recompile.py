"""GC4 — recompilation-hazard detector.

The "recompile every new seq length" bug costs 20-40 s of XLA wait per
novel shape in the middle of serving traffic, and nothing in a unit test
notices: every individual call is correct.  Each scenario here declares
the CLOSED ladder of jit-visible widths its entry point may produce and a
compile-key budget; the checker sweeps a request-length ladder through the
real width policy, traces the real jitted function at every distinct
width, hashes (jaxpr, abstract signature, static args) per call — the
compile cache's own key, backend aside — and fails when the keys outgrow
the declaration.

- GC401: distinct compile keys exceed the scenario's declared bound.
- GC402: the width policy emitted a width off the declared ladder (the
  bucketing function regressed, e.g. someone padded to the raw length).
"""

from __future__ import annotations

from .core import Finding


def check(scenarios=None) -> list[Finding]:
    if scenarios is None:
        from .contracts import recompile_scenarios

        scenarios = recompile_scenarios()
    findings: list[Finding] = []
    for sc in scenarios:
        allowed = set(sc.allowed_widths)
        widths: list[int] = []
        off_ladder: set[int] = set()
        for n in sc.ladder:
            w = sc.width_of(n)
            widths.append(w)
            if w not in allowed:
                off_ladder.add(w)
        for w in sorted(off_ladder):
            findings.append(Finding(
                "GC402", sc.path, 0,
                f"{sc.name}: width policy produced {w}, off the declared "
                f"ladder {sorted(allowed)}"))
        keys: dict[str, int] = {}
        try:
            for w in sorted(set(widths) - off_ladder):
                keys[sc.trace(w)] = w
        except Exception as exc:
            findings.append(Finding(
                "GC401", sc.path, 0,
                f"{sc.name}: trace failed at width "
                f"{w}: {type(exc).__name__}: "
                f"{str(exc).splitlines()[0][:160]}"))
            continue
        if len(keys) > sc.max_keys:
            findings.append(Finding(
                "GC401", sc.path, 0,
                f"{sc.name}: {len(keys)} compile keys over the request "
                f"ladder exceed the declared bucket count {sc.max_keys} "
                f"(widths {sorted(keys.values())})"))
    return findings


def measure_keys(scenario) -> dict[str, int]:
    """Compile keys a scenario produces: key-hash -> width.  Raises on
    trace failure (a caller that reports the count must not report
    garbage)."""
    out: dict[str, int] = {}
    for n in scenario.ladder:
        w = scenario.width_of(n)
        out[scenario.trace(w)] = w
    return out
