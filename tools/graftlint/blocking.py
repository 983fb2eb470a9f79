"""GL401 — blocking calls in the engine-loop call graph.

``ContinuousBatcher.run`` is the latency floor of serving: every request's
tokens pass through it, and ONE blocking syscall in its call graph stalls
every in-flight row (the engine thread owns the device — nothing else can
dispatch while it waits).  This rule builds the intra-repo call graph from
``ContinuousBatcher.run`` (same-module functions, ``self.*`` methods, and
the known collaborator fields ``self.pool`` -> PagePool,
``self.prefix_cache`` -> PrefixCache, ``self.faults`` -> FaultPlane —
one-step local aliases like ``pc = self.prefix_cache`` included) and flags
any reachable call to:

- ``time.sleep``
- socket construction / connection (``socket.socket``, ``create_connection``)
- ``subprocess.*`` / ``os.system`` / ``os.popen``
- file I/O: builtin ``open``, ``Path.read_text/write_text/read_bytes/
  write_bytes``
- ``requests.*`` / ``urllib.request.*``

A deliberate block (the fault plane's ``stall`` action models a wedged
device call) carries ``# graftlint: ignore[GL401](<reason>)``.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass

from .core import Finding, Project, SourceFile, dotted_name

RULE = "GL401"

ENTRY_CLASS = "ContinuousBatcher"
ENTRY_METHOD = "run"

# self.<field> -> class whose methods the call resolves to.
_FIELD_CLASSES = {
    "pool": "PagePool",
    "prefix_cache": "PrefixCache",
    "faults": "FaultPlane",
}

_BLOCKING_DOTTED = (
    "time.sleep", "socket.socket", "socket.create_connection",
    "os.system", "os.popen",
)
_BLOCKING_PREFIXES = ("subprocess.", "requests.", "urllib.request.")
_BLOCKING_METHODS = {"read_text", "write_text", "read_bytes", "write_bytes"}


@dataclass(frozen=True)
class _FnKey:
    rel: str
    cls: str | None  # None = module-level function
    name: str


def _collect_defs(files: list[SourceFile]) -> dict[_FnKey, tuple[SourceFile, ast.AST]]:
    defs: dict[_FnKey, tuple[SourceFile, ast.AST]] = {}
    for sf in files:
        for node in sf.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs[_FnKey(sf.rel, None, node.name)] = (sf, node)
            elif isinstance(node, ast.ClassDef):
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        defs[_FnKey(sf.rel, node.name, sub.name)] = (sf, sub)
    return defs


def _local_aliases(fn: ast.AST) -> dict[str, str]:
    """{local name: collaborator class} for ``x = self.<known field>``."""
    out: dict[str, str] = {}
    for node in ast.walk(fn):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Attribute)
                and isinstance(node.value.value, ast.Name)
                and node.value.value.id == "self"
                and node.value.attr in _FIELD_CLASSES):
            out[node.targets[0].id] = _FIELD_CLASSES[node.value.attr]
    return out


def _callees(sf: SourceFile, key: _FnKey, fn: ast.AST,
             defs: dict[_FnKey, tuple[SourceFile, ast.AST]]) -> set[_FnKey]:
    aliases = _local_aliases(fn)
    out: set[_FnKey] = set()

    def resolve(cls: str | None, name: str) -> None:
        for cand in defs:
            if cand.name == name and cand.cls == cls:
                out.add(cand)

    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Name):
            resolve(None, f.id)
            # Same-class unbound-style calls are not used in this tree.
        elif isinstance(f, ast.Attribute):
            v = f.value
            if isinstance(v, ast.Name) and v.id == "self":
                resolve(key.cls, f.attr)
            elif (isinstance(v, ast.Attribute)
                    and isinstance(v.value, ast.Name) and v.value.id == "self"
                    and v.attr in _FIELD_CLASSES):
                resolve(_FIELD_CLASSES[v.attr], f.attr)
            elif isinstance(v, ast.Name) and v.id in aliases:
                resolve(aliases[v.id], f.attr)
    return out


def _blocking_calls(sf: SourceFile, fn: ast.AST) -> list[tuple[int, str]]:
    out: list[tuple[int, str]] = []
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        name = dotted_name(node.func)
        if name in _BLOCKING_DOTTED or (
                name is not None and name.startswith(_BLOCKING_PREFIXES)):
            out.append((node.lineno, name))
        elif isinstance(node.func, ast.Name) and node.func.id == "open":
            out.append((node.lineno, "open"))
        elif (isinstance(node.func, ast.Attribute)
                and node.func.attr in _BLOCKING_METHODS):
            out.append((node.lineno, f"<..>.{node.func.attr}"))
    return out


def check(project: Project) -> list[Finding]:
    # The graph spans the batcher module, the page allocator it drives
    # and the fault plane it consults.
    scope = [sf for sf in project.package_files()
             if sf.rel.endswith(("runtime/batcher.py", "runtime/pages.py",
                                 "runtime/faults.py"))
             or sf.rel in ("batcher.py", "pages.py", "faults.py")]
    defs = _collect_defs(scope)
    entry = next((k for k in defs
                  if k.cls == ENTRY_CLASS and k.name == ENTRY_METHOD), None)
    if entry is None:
        return []
    # BFS over the call graph.
    reachable: list[_FnKey] = [entry]
    seen = {entry}
    i = 0
    while i < len(reachable):
        key = reachable[i]
        i += 1
        sf, fn = defs[key]
        for callee in _callees(sf, key, fn, defs):
            if callee not in seen:
                seen.add(callee)
                reachable.append(callee)
    findings: list[Finding] = []
    for key in reachable:
        sf, fn = defs[key]
        where = f"{key.cls}.{key.name}" if key.cls else key.name
        for line, what in _blocking_calls(sf, fn):
            if sf.suppressed(RULE, line):
                continue
            findings.append(Finding(
                RULE, sf.rel, line,
                f"blocking call '{what}' in {where}, reachable from "
                f"{ENTRY_CLASS}.{ENTRY_METHOD} — the engine loop thread "
                f"must never block off-device",
            ))
    return findings
