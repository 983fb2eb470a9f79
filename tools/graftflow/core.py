"""graftflow core: per-function CFGs, an intra-repo call graph, and the
shared plumbing the GF rule families build on.

graftlint (tools/graftlint) reads the AST one statement at a time and
graftcheck (tools/graftcheck) traces the real code under abstract values;
graftflow sits between them: it builds *control-flow graphs* (statement
nodes, normal successors, and EXCEPTION edges from every raising
statement to the innermost handler/finally or out of the function) and an
*interprocedural call graph* (same-module functions, ``self.*`` methods,
known collaborator fields, known module aliases), so it can answer
path-sensitive questions the per-statement rules cannot:

- which locks are held when another lock is acquired, across calls (GF1);
- which blocking calls a coroutine can reach transitively (GF2);
- whether an allocation can reach function exit unreleased along ANY
  path, including the exception edges (GF3);
- which protocol frames/fault sites have live senders and handlers (GF4).

Shared infrastructure is reused from graftlint.core: ``SourceFile`` /
``Project`` / ``load_project``, ``Finding``, and the normalized
line-number-free ``[xN]`` baseline format (file:
``graftflow_baseline.txt``, checked in EMPTY).

Suppressions (both REQUIRE a non-empty reason or they are inert,
graftlint's escape semantics):

- ``# graftflow: ok(<reason>)`` on the finding line suppresses any GF
  rule there;
- ``# graftflow: ignore[GF201](<reason>)`` suppresses only the named
  rule(s).
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field

from tools.graftlint.core import (Finding, Project, SourceFile,  # noqa: F401
                                  dotted_name, expr_text, load_project,
                                  normalize_expr, read_baseline, split_new,
                                  stale_entries, write_baseline)

BASELINE_NAME = "graftflow_baseline.txt"

_SUPPRESS_RE = re.compile(
    r"#\s*graftflow:\s*"
    r"(?:(ok)|ignore\[([A-Z]+\d+(?:\s*,\s*[A-Z]+\d+)*)\])"
    r"\(([^)]*)\)"
)


def suppressed(sf: SourceFile, rule: str, line: int) -> bool:
    """Whether ``rule`` is suppressed on ``line`` (trailing comment, or a
    standalone comment directly above).  A suppression with an EMPTY
    reason is deliberately inert: accepted debt must say why."""
    for m in _SUPPRESS_RE.finditer(sf._comment_for(line)):
        if not m.group(3).strip():
            continue  # reasonless suppressions don't count
        if m.group(1):
            return True
        if rule in re.split(r"\s*,\s*", m.group(2)):
            return True
    return False


# -- shared scope / registries ---------------------------------------------

# ``self.<field>`` -> owning class, for call-graph and lock resolution.
# The threaded serving core's collaborator fields (graftlint's GL401 map,
# widened to the whole runtime + cluster layer).
FIELD_CLASSES: dict[str, str] = {
    "pool": "PagePool",
    "prefix_cache": "PrefixCache",
    "batcher": "ContinuousBatcher",
    "faults": "FaultPlane",
    "fleet": "ReplicaFleet",
    "server": "InferenceServer",
    "router": "ReplicaRouter",
}

# Module-level globals whose methods resolve to a known class.
GLOBAL_CLASSES: dict[str, str] = {
    "METRICS": "Metrics",
}

# Module aliases: ``protocol.send_message(...)`` resolves to the function
# in the file whose stem matches.
MODULE_ALIASES = ("protocol", "kv_transfer", "faults", "batcher", "fleet")

# The modules whose interactions graftflow audits (repo-relative
# suffixes). Everything else is out of scope by design — the single-file
# rules live in graftlint.
SCOPE_SUFFIXES = (
    "runtime/batcher.py", "runtime/pages.py", "runtime/server.py",
    "runtime/router.py", "runtime/faults.py", "core/observability.py",
    "cluster/fleet.py", "cluster/kv_transfer.py", "cluster/protocol.py",
    "cluster/coordinator.py", "cluster/worker.py", "cluster/client.py",
    "cluster/metrics_http.py", "cluster/distributed.py",
)


def scope_files(project: Project) -> list[SourceFile]:
    """Package files graftflow analyzes.  Matching is by path suffix so
    the self-test fixture trees (pkg/runtime/..., pkg/cluster/...) land in
    scope exactly like the real package."""
    return [sf for sf in project.package_files()
            if sf.rel.endswith(SCOPE_SUFFIXES)]


# ONE parser for the module-level ``NAME = {str: str}`` registry idiom
# (FAULT_SITES / METRIC_DOCS / LOCK_ORDER): graftlint's GL3xx rules and
# graftflow must never disagree on what a registry contains.
from tools.graftlint.registry import _literal_dict as literal_strdict  # noqa: E402,F401


# -- function index / call graph -------------------------------------------

@dataclass(frozen=True)
class FnKey:
    rel: str            # repo-relative path of the defining file
    cls: str | None     # None = module-level function
    name: str

    def pretty(self) -> str:
        return f"{self.cls}.{self.name}" if self.cls else self.name


@dataclass
class FnInfo:
    key: FnKey
    sf: SourceFile
    node: ast.FunctionDef | ast.AsyncFunctionDef

    @property
    def is_async(self) -> bool:
        return isinstance(self.node, ast.AsyncFunctionDef)


def collect_functions(files: list[SourceFile]) -> dict[FnKey, FnInfo]:
    """Top-level functions and one-level class methods (the shapes this
    tree uses; nested defs belong to their enclosing function's CFG)."""
    out: dict[FnKey, FnInfo] = {}
    for sf in files:
        for node in sf.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                k = FnKey(sf.rel, None, node.name)
                out[k] = FnInfo(k, sf, node)
            elif isinstance(node, ast.ClassDef):
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        k = FnKey(sf.rel, node.name, sub.name)
                        out[k] = FnInfo(k, sf, sub)
    return out


def local_aliases(fn: ast.AST) -> dict[str, str]:
    """{local name: collaborator class} for ``x = self.<known field>`` —
    one-step aliases, the idiom the hot loops use."""
    out: dict[str, str] = {}
    for node in ast.walk(fn):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Attribute)
                and isinstance(node.value.value, ast.Name)
                and node.value.value.id == "self"
                and node.value.attr in FIELD_CLASSES):
            out[node.targets[0].id] = FIELD_CLASSES[node.value.attr]
    return out


def resolve_call(call: ast.Call, caller: FnKey, aliases: dict[str, str],
                 fns: dict[FnKey, FnInfo]) -> list[FnKey]:
    """Callees a call site may reach, conservatively UNDER-approximated:
    unresolvable receivers contribute no edge (a missed edge can hide a
    finding but never invent one)."""
    f = call.func
    out: list[FnKey] = []

    def by(cls: str | None, name: str, rel: str | None = None) -> None:
        for k in fns:
            if k.name == name and k.cls == cls \
                    and (rel is None or k.rel == rel):
                out.append(k)

    if isinstance(f, ast.Name):
        # Module-level function in the SAME file (imports of single
        # functions across modules are rare in scope; by-name cross-file
        # resolution would invent edges between unrelated helpers).
        by(None, f.id, rel=caller.rel)
    elif isinstance(f, ast.Attribute):
        v = f.value
        if isinstance(v, ast.Name):
            if v.id == "self":
                by(caller.cls, f.attr)
            elif v.id in aliases:
                by(aliases[v.id], f.attr)
            elif v.id in GLOBAL_CLASSES:
                by(GLOBAL_CLASSES[v.id], f.attr)
            elif v.id in MODULE_ALIASES:
                for k in fns:
                    if (k.name == f.attr and k.cls is None
                            and k.rel.endswith(f"/{v.id}.py")):
                        out.append(k)
        elif (isinstance(v, ast.Attribute)
                and isinstance(v.value, ast.Name) and v.value.id == "self"
                and v.attr in FIELD_CLASSES):
            by(FIELD_CLASSES[v.attr], f.attr)
    return out


# -- control-flow graph ----------------------------------------------------

class Node:
    """One CFG node: a statement (or a synthetic entry/exit/join).
    ``succs`` are normal-flow successors; ``exc_succs`` are taken only
    when the statement raises."""

    __slots__ = ("stmt", "kind", "succs", "exc_succs")

    def __init__(self, stmt: ast.stmt | None, kind: str = "stmt") -> None:
        self.stmt = stmt
        self.kind = kind
        self.succs: list["Node"] = []
        self.exc_succs: list["Node"] = []

    def __repr__(self) -> str:  # debugging aid only
        at = getattr(self.stmt, "lineno", "-")
        return f"<{self.kind}@{at}>"


@dataclass
class Cfg:
    entry: Node
    exit: Node          # normal returns / fall-off-the-end
    raise_exit: Node    # an exception left the function
    nodes: list[Node] = field(default_factory=list)


def exec_parts(stmt: ast.stmt) -> list[ast.AST]:
    """The part of a statement its CFG node actually EXECUTES.  Compound
    statements execute only their header (test / iterable / context
    expressions) — their bodies are separate CFG nodes, and a predicate
    that walked the whole subtree would see nested cleanup/release code
    as if it ran unconditionally at the header."""
    if isinstance(stmt, (ast.If, ast.While)):
        return [stmt.test]
    if isinstance(stmt, (ast.For, ast.AsyncFor)):
        return [stmt.target, stmt.iter]
    if isinstance(stmt, (ast.With, ast.AsyncWith)):
        out: list[ast.AST] = []
        for item in stmt.items:
            out.append(item.context_expr)
            if item.optional_vars is not None:
                out.append(item.optional_vars)
        return out
    if isinstance(stmt, ast.Try) or (
            hasattr(ast, "TryStar") and isinstance(stmt, ast.TryStar)):
        return []
    if isinstance(stmt, ast.Match):
        return [stmt.subject]
    if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                         ast.ClassDef)):
        return []  # a nested def runs when called, not where defined
    return [stmt]


# Attribute-call names that cannot realistically raise: bookkeeping on
# stdlib containers/events/locks and the metrics/logging registries.
# Pruning them keeps the exception-edge analyses focused on real raisers
# (submits, device calls, socket writes) instead of flagging every
# ``self._work.set()`` between an acquire and its release.
_INFALLIBLE_ATTRS = frozenset({
    "set", "clear", "inc", "observe", "set_gauge", "set_gauges",
    "append", "appendleft", "extend", "add", "discard", "update",
    "info", "warning", "error", "exception", "debug",
    "perf_counter", "monotonic", "time",
})
_INFALLIBLE_NAMES = frozenset({
    "range", "len", "enumerate", "zip", "isinstance", "list", "sorted",
    "id",
})


def _can_raise(node: ast.AST) -> bool:
    """Whether executing this code may raise: any call/await inside (the
    overwhelmingly dominant source) plus explicit raise/assert — except
    calls to the infallible bookkeeping methods/builtins above.
    Attribute/subscript misses exist but flagging them would drown the
    signal."""
    for sub in ast.walk(node):
        if isinstance(sub, (ast.Await, ast.Raise, ast.Assert)):
            return True
        if isinstance(sub, ast.Call):
            if (isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in _INFALLIBLE_ATTRS):
                continue
            if (isinstance(sub.func, ast.Name)
                    and sub.func.id in _INFALLIBLE_NAMES):
                continue
            return True
    return False


def _catches_all(h: ast.ExceptHandler) -> bool:
    if h.type is None:
        return True
    names = {n.attr if isinstance(n, ast.Attribute) else getattr(n, "id", "")
             for n in ([h.type] if not isinstance(h.type, ast.Tuple)
                       else h.type.elts)}
    return bool(names & {"BaseException", "Exception"})


class _CfgBuilder:
    def __init__(self) -> None:
        self.nodes: list[Node] = []
        self.exit = self._new(None, "exit")
        self.raise_exit = self._new(None, "raise-exit")
        # (head, after) per enclosing loop, for continue/break.
        self._loops: list[tuple[Node, Node]] = []

    def _new(self, stmt: ast.stmt | None, kind: str = "stmt") -> Node:
        n = Node(stmt, kind)
        self.nodes.append(n)
        return n

    def build(self, fn: ast.FunctionDef | ast.AsyncFunctionDef) -> Cfg:
        entry = self._block(fn.body, self.exit, [self.raise_exit])
        return Cfg(entry=entry, exit=self.exit, raise_exit=self.raise_exit,
                   nodes=self.nodes)

    def _block(self, stmts: list[ast.stmt], follow: Node,
               exc: list[Node]) -> Node:
        nxt = follow
        for stmt in reversed(stmts):
            nxt = self._stmt(stmt, nxt, exc)
        return nxt

    def _stmt(self, stmt: ast.stmt, follow: Node, exc: list[Node]) -> Node:
        n = self._new(stmt)
        # Only the statement's EXECUTED part decides its exception edge —
        # a compound statement's body raises from its own nodes.
        raising = any(_can_raise(p) for p in exec_parts(stmt))

        if isinstance(stmt, ast.Return):
            n.succs = [self.exit]
            if raising:
                n.exc_succs = list(exc)
        elif isinstance(stmt, ast.Raise):
            n.succs = []
            n.exc_succs = list(exc)
        elif isinstance(stmt, ast.Break):
            n.succs = [self._loops[-1][1]] if self._loops else [follow]
        elif isinstance(stmt, ast.Continue):
            n.succs = [self._loops[-1][0]] if self._loops else [follow]
        elif isinstance(stmt, ast.If):
            body = self._block(stmt.body, follow, exc)
            orelse = self._block(stmt.orelse, follow, exc)
            n.succs = [body, orelse]
            if raising:
                n.exc_succs = list(exc)
        elif isinstance(stmt, (ast.While, ast.For, ast.AsyncFor)):
            after = self._block(getattr(stmt, "orelse", []), follow, exc)
            self._loops.append((n, follow))
            body = self._block(stmt.body, n, exc)
            self._loops.pop()
            n.succs = [body]
            infinite = (isinstance(stmt, ast.While)
                        and isinstance(stmt.test, ast.Constant)
                        and bool(stmt.test.value))
            if not infinite:
                n.succs.append(after)
            if raising:
                n.exc_succs = list(exc)
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            body = self._block(stmt.body, follow, exc)
            n.succs = [body]
            if raising:  # the __enter__ call
                n.exc_succs = list(exc)
        elif isinstance(stmt, ast.Try) or (
                hasattr(ast, "TryStar") and isinstance(stmt, ast.TryStar)):
            # finally: built ONCE with a fork join — its exit reaches both
            # the normal follow and the exceptional continuation (an
            # over-approximation that never skips a cleanup node, which is
            # all the path analyses care about).
            if stmt.finalbody:
                join = self._new(None, "join")
                join.succs = [follow]
                join.exc_succs = list(exc)
                fin_entry = self._block(stmt.finalbody, join, exc)
                after_body, outer_exc = fin_entry, [fin_entry]
            else:
                after_body, outer_exc = follow, list(exc)
            handler_entries: list[Node] = []
            for h in stmt.handlers:
                handler_entries.append(
                    self._block(h.body, after_body, outer_exc))
            # A catch-all handler (bare except / except BaseException /
            # except Exception) means a body exception cannot skip past
            # the handlers to the outer context.
            inner_exc = handler_entries + (
                [] if any(_catches_all(h) for h in stmt.handlers)
                else outer_exc
            )
            orelse = self._block(stmt.orelse, after_body, inner_exc) \
                if stmt.orelse else after_body
            body = self._block(stmt.body, orelse, inner_exc)
            n.succs = [body]
        elif isinstance(stmt, ast.Match):
            n.succs = [self._block(case.body, follow, exc)
                       for case in stmt.cases] + [follow]
            if raising:
                n.exc_succs = list(exc)
        else:
            n.succs = [follow]
            if raising:
                n.exc_succs = list(exc)
        return n


def build_cfg(fn: ast.FunctionDef | ast.AsyncFunctionDef) -> Cfg:
    return _CfgBuilder().build(fn)


def leaky_paths(start: Node, clears, exits: tuple[Node, ...]) -> Node | None:
    """May-path query: starting AFTER ``start``, is there a path to one of
    ``exits`` that never passes a node for which ``clears(node)`` is true?
    Returns the reached exit node (evidence) or None.

    A clearing node neutralizes ALL its outgoing edges — including its
    exception edges (once the sink statement runs, ownership moved, even
    if something later in the same expression raises).  Callers choose
    the exits that constitute a leak: GF301 passes both exits (an open
    page obligation must not survive ANY way out), GF303 passes only
    ``raise_exit`` (a registration is SUPPOSED to outlive a normal
    return)."""
    seen: set[int] = set()
    # Normal successors only: if the acquiring statement ITSELF raises,
    # the resource was never obtained and there is nothing to leak.
    stack: list[Node] = list(start.succs)
    while stack:
        node = stack.pop()
        if id(node) in seen:
            continue
        seen.add(id(node))
        if node in exits:
            return node
        if node.kind == "stmt" and clears(node):
            continue
        stack += node.succs
        stack += node.exc_succs
    return None


def mentions_name(stmt: ast.stmt, name: str) -> bool:
    """Whether the statement's EXECUTED part (header only, for compound
    statements) mentions the local ``name``."""
    return any(isinstance(sub, ast.Name) and sub.id == name
               for part in exec_parts(stmt)
               for sub in ast.walk(part))
