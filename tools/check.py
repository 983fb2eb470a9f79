"""Unified static-analysis front door: ``python -m tools.check``.

Runs ALL FIVE checkers over the repo and merges their exit codes:

- graftlint  (tools/graftlint)  — AST rules GL1xx-GL5xx;
- graftcheck (tools/graftcheck) — semantic contracts GC1xx-GC5xx + GCD;
- graftflow  (tools/graftflow)  — CFG/dataflow rules GF1xx-GF4xx + GFD;
- graftsync  (tools/graftsync)  — lockstep taint rules GS1xx-GS4xx + GSD;
- graftmodel (tools/graftmodel) — protocol model checking GM1xx-GM6xx
  + GMD.

``--only`` scopes a run to rule families ACROSS the tools
(``--only GF2,GC4,GM1``): tools with no selected family are skipped
entirely (graftcheck's tracing is the expensive one), and baseline /
stale accounting is filtered to the selected families so a scoped run
never mis-reports out-of-scope debt as stale.

One deliberate escalation over running the tools separately: a STALE
baseline entry (accepted debt whose finding no longer occurs) is an
ERROR here, not a warning.  Debt that got fixed must leave the baseline
in the same PR — run the matching ``--baseline-write`` to prune — or the
baseline rots into a list nobody can audit.

Per-tool wall time prints on stderr, so the gate's cost stays visible.

Exit status: 0 = all clean and no stale entries; 1 = new findings or
stale entries anywhere; 2 = usage error.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
import time
from pathlib import Path

# family token -> owning tool.  A finding's family is rule[:3]
# ("GL301" -> "GL3", "GCD01" -> "GCD", "GF201" -> "GF2").
FAMILIES = {
    **{f"GL{i}": "graftlint" for i in range(1, 6)},
    **{f"GC{i}": "graftcheck" for i in range(1, 6)}, "GCD": "graftcheck",
    **{f"GF{i}": "graftflow" for i in range(1, 5)}, "GFD": "graftflow",
    **{f"GS{i}": "graftsync" for i in range(1, 5)}, "GSD": "graftsync",
    **{f"GM{i}": "graftmodel" for i in range(1, 7)}, "GMD": "graftmodel",
}

_BASELINE_RULE_RE = re.compile(r":\s*(G[A-Z]{1,2}\d+)\b")


def _family(rule: str) -> str:
    return rule[:3]


def _filter_findings(findings, only):
    if only is None:
        return findings
    return [f for f in findings if _family(f.rule) in only]


def _filter_baseline(baseline: dict, only) -> dict:
    """Keep only baseline entries whose rule family is in scope — an
    out-of-scope entry must read neither as absorbing capacity nor as
    stale debt during a scoped run."""
    if only is None:
        return baseline
    out = {}
    for key, n in baseline.items():
        m = _BASELINE_RULE_RE.search(key)
        if m is not None and _family(m.group(1)) in only:
            out[key] = n
    return out


def _report(tool: str, findings, baseline, only, wall_s: float):
    """-> (new findings, stale entries) after family filtering."""
    from tools.graftlint.core import split_new, stale_entries

    findings = _filter_findings(findings, only)
    baseline = _filter_baseline(baseline, only)
    new, old = split_new(findings, baseline)
    for f in new:
        print(f.render())
    stale = stale_entries(findings, baseline)
    print(f"check: {tool}: {len(new)} new, {len(old)} baselined, "
          f"{len(stale)} stale ({wall_s:.1f}s)", file=sys.stderr)
    for s in stale:
        print(f"check: STALE {tool} baseline entry (fixed debt — prune "
              f"with python -m tools.{tool} --baseline-write):\n  {s}",
              file=sys.stderr)
    return new, stale


def main(argv=None) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    ap = argparse.ArgumentParser(
        prog="python -m tools.check",
        description="run graftlint + graftcheck + graftflow + graftsync "
                    "+ graftmodel with merged exit codes",
    )
    ap.add_argument("--root", default=".", help="repo root to analyze")
    ap.add_argument("--only", default=None,
                    help="comma-separated rule families across all tools, "
                         "e.g. GF2,GC4,GL3; tools with no selected family "
                         "are skipped")
    args = ap.parse_args(argv)
    root = Path(args.root).resolve()
    if not root.is_dir():
        print(f"check: --root {root} is not a directory", file=sys.stderr)
        return 2

    only = None
    if args.only:
        only = {s.strip() for s in args.only.split(",") if s.strip()}
        unknown = only - set(FAMILIES)
        if unknown:
            print(f"check: unknown families {sorted(unknown)}; have "
                  f"{sorted(FAMILIES)}", file=sys.stderr)
            return 2

    def want(tool: str) -> bool:
        return only is None or any(FAMILIES[f] == tool for f in only)

    t_start = time.perf_counter()
    rc = 0
    walls: list[tuple[str, float]] = []

    # -- graftlint (AST) ---------------------------------------------------
    if want("graftlint"):
        from tools import graftlint

        t0 = time.perf_counter()
        project = graftlint.load_project(root)
        findings = graftlint.run_project(project)
        wall = time.perf_counter() - t0
        walls.append(("graftlint", wall))
        new, stale = _report("graftlint", findings,
                             graftlint.read_baseline(root), only, wall)
        rc |= 1 if (new or stale) else 0

    # -- graftflow (CFG/dataflow) ------------------------------------------
    if want("graftflow"):
        from tools import graftflow

        t0 = time.perf_counter()
        gf_only = ({f for f in only if FAMILIES[f] == "graftflow"}
                   if only is not None else None)
        findings = graftflow.run_project(graftflow.load_project(root),
                                         only=gf_only)
        wall = time.perf_counter() - t0
        walls.append(("graftflow", wall))
        new, stale = _report("graftflow", findings,
                             graftflow.read_baseline(root), only, wall)
        rc |= 1 if (new or stale) else 0

    # -- graftsync (lockstep taint) ----------------------------------------
    if want("graftsync"):
        from tools import graftsync

        t0 = time.perf_counter()
        gs_only = ({f for f in only if FAMILIES[f] == "graftsync"}
                   if only is not None else None)
        findings = graftsync.run_project(graftsync.load_project(root),
                                         only=gs_only)
        wall = time.perf_counter() - t0
        walls.append(("graftsync", wall))
        new, stale = _report("graftsync", findings,
                             graftsync.read_baseline(root), only, wall)
        rc |= 1 if (new or stale) else 0

    # -- graftmodel (protocol model checking) ------------------------------
    if want("graftmodel"):
        from tools import graftmodel

        t0 = time.perf_counter()
        gm_only = ({f for f in only if FAMILIES[f] == "graftmodel"}
                   if only is not None else None)
        findings = graftmodel.run_project(graftmodel.load_project(root),
                                          only=gm_only)
        wall = time.perf_counter() - t0
        walls.append(("graftmodel", wall))
        new, stale = _report("graftmodel", findings,
                             graftmodel.read_baseline(root), only, wall)
        rc |= 1 if (new or stale) else 0

    # -- graftcheck (semantic; imports + traces, the expensive one) --------
    if want("graftcheck"):
        from tools import graftcheck

        t0 = time.perf_counter()
        gc_only = ({f for f in only if FAMILIES[f] == "graftcheck"}
                   if only is not None else None)
        findings = graftcheck.run_all(only=gc_only, root=root)
        wall = time.perf_counter() - t0
        walls.append(("graftcheck", wall))
        new, stale = _report("graftcheck", findings,
                             graftcheck.read_baseline(root), only, wall)
        rc |= 1 if (new or stale) else 0

    total = time.perf_counter() - t_start
    per_tool = " ".join(f"{t}={w:.1f}s" for t, w in walls)
    print(f"check: wall {per_tool} total={total:.1f}s", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
