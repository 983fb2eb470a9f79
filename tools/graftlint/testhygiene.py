"""GL501 — test hygiene: no wall-clock ``time.sleep`` in fast tests.

A ``time.sleep`` in a non-``slow`` test is either a hidden race (the test
passes because 50 ms usually suffices — until CI is loaded) or wasted
wall-clock multiplied by every tier-1 run.  The deterministic levers this
tree already owns — the fault plane's ``stall``/``delay`` actions, the
injectable clocks (``profiling.span``, ``ContinuousBatcher``) — replace both
shapes.

Flagged: any ``time.sleep(...)`` (or bare ``sleep`` imported from
``time``) under ``tests/`` whose enclosing function, class, or module is
not marked ``pytest.mark.slow``.  ``time.sleep(0)`` (a bare GIL yield) is
allowed; ``asyncio.sleep`` is not wall-clock blocking and is out of
scope.  Suppress a justified wait with ``# graftlint: ignore[GL501](why)``.
"""

from __future__ import annotations

import ast

from .core import Finding, Project, SourceFile

RULE = "GL501"


def _is_slow_marker(node: ast.AST) -> bool:
    text = ast.unparse(node) if hasattr(ast, "unparse") else ""
    return "mark.slow" in text or text.endswith("slow")


def _module_is_slow(sf: SourceFile) -> bool:
    for node in sf.tree.body:
        if (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name) and t.id == "pytestmark"
                        for t in node.targets)):
            if _is_slow_marker(node.value):
                return True
    return False


def _sleep_from_time(sf: SourceFile) -> bool:
    """Whether bare ``sleep`` in this module is ``time.sleep``."""
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.ImportFrom) and node.module == "time":
            if any(a.name == "sleep" for a in node.names):
                return True
    return False


def _walk(sf: SourceFile, node: ast.AST, slow: bool, bare_sleep: bool,
          findings: list[Finding]) -> None:
    """Uniform descent accumulating ``slow`` at every def/class boundary,
    so a slow-marked test nested under a module-level compound statement
    (``if sys.platform ...:``) keeps its exemption."""
    if isinstance(node, (ast.ClassDef, ast.FunctionDef,
                         ast.AsyncFunctionDef)):
        slow = slow or any(_is_slow_marker(d) for d in node.decorator_list)
    if isinstance(node, ast.Call) and not slow:
        _maybe_flag(sf, node, bare_sleep, findings)
    for child in ast.iter_child_nodes(node):
        _walk(sf, child, slow, bare_sleep, findings)


def _maybe_flag(sf: SourceFile, node: ast.Call, bare_sleep: bool,
                findings: list[Finding]) -> None:
    f = node.func
    is_sleep = (
        (isinstance(f, ast.Attribute) and f.attr == "sleep"
         and isinstance(f.value, ast.Name) and f.value.id == "time")
        or (bare_sleep and isinstance(f, ast.Name) and f.id == "sleep")
    )
    if not is_sleep:
        return
    if (node.args and isinstance(node.args[0], ast.Constant)
            and not node.args[0].value):
        return  # time.sleep(0): a GIL yield, not a wait
    if sf.suppressed(RULE, node.lineno):
        return
    findings.append(Finding(
        RULE, sf.rel, node.lineno,
        "wall-clock time.sleep in a non-slow test — use the fault "
        "plane (stall/delay), an injected clock, or mark the test "
        "slow",
    ))


def check(project: Project) -> list[Finding]:
    findings: list[Finding] = []
    for sf in project.test_files():
        _walk(sf, sf.tree, _module_is_slow(sf), _sleep_from_time(sf),
              findings)
    return findings
