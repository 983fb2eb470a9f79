"""GS1xx — nondeterminism taint on the lockstep decision path.

A multi-process mesh dispatches SPMD programs in lockstep: every process
must take the SAME admission / victim / bite / sync decision in the same
scheduling round, or the next collective deadlocks (one process dispatches
a program its siblings never will) — the Orca-style continuous-batching
discipline every mesh test in this tree assumes.  A wall-clock read, a
global-state RNG draw, an ``id()``/``hash()``, an env read, or a
future-completion-order dependency anywhere in a decision's CALL GRAPH
breaks that silently: host clocks diverge by construction, CPython hashes
and addresses diverge per process, and the bug only fires as a wedged
mesh in production.

**GS101**: a nondeterminism source (:func:`core.source_name` — wall
clocks, ``random``/``np.random``/``os.urandom``/``uuid``/``secrets``,
``id()``/``hash()``, env reads, ``as_completed``) reachable from a
``LOCKSTEP_DECISIONS`` function over the intra-repo call graph.

The lockstep clock policy's two sanctioned escapes are structural, not
suppressions:

- a source read lexically inside a metrics/logging call's arguments
  (``METRICS.observe("...", time.perf_counter() - t0)``) only feeds
  observability — allowlisted via :data:`core.METRICS_BOUNDARY`;
- a function declared in ``HOST_SYNC_SITES`` IS a sync point — the one
  place timer reads belong (``_fetch_chunk``/``_sync_carry`` stamping
  when the device was last seen to finish), because the host is already
  serialized against the device there.

Everything else needs ``# graftsync: lockstep-ok(<reason>)`` on the line
— and the reason should say why the value never crosses a process
boundary.
"""

from __future__ import annotations

import ast

from .core import (Finding, Project, decision_closure, env_subscript,
                   in_sync_sites, load_registries, metrics_nested_calls,
                   source_name, suppressed)

RULE_TAINT = "GS101"


def check(project: Project) -> list[Finding]:
    fns, closure, _decisions = decision_closure(project)
    _, _, sync_sites, _ = load_registries(project)
    findings: list[Finding] = []
    seen: set[tuple[str, int]] = set()
    for key, ce in closure.items():
        if in_sync_sites(key, sync_sites):
            continue  # a declared sync point: clock reads belong here
        info = fns[key]
        allowlisted = metrics_nested_calls(info.node)
        for node in ast.walk(info.node):
            what = None
            if isinstance(node, ast.Call):
                what = source_name(node)
            if what is None:
                what = env_subscript(node)
            if what is None:
                continue
            if id(node) in allowlisted:
                continue  # feeds METRICS/log arguments only
            site = (info.sf.rel, node.lineno)
            if site in seen:
                continue
            seen.add(site)
            if suppressed(info.sf, RULE_TAINT, node.lineno):
                continue
            via = ("" if key == ce.entry else f" via {key.pretty()}")
            findings.append(Finding(
                RULE_TAINT, info.sf.rel, node.lineno,
                f"nondeterministic source '{what}' on the lockstep "
                f"decision path: reachable from {ce.entry.pretty()} "
                f"(LOCKSTEP_DECISIONS '{ce.declared}'){via} — processes "
                f"diverge on this value and SPMD dispatch deadlocks; "
                f"read it at a HOST_SYNC_SITES boundary, inject a "
                f"lockstep clock, or derive it from scheduling state",
            ))
    return sorted(findings, key=lambda f: (f.path, f.line))
