"""GL1xx — lock-discipline race detector.

Shared instance fields are declared with a trailing (or directly
preceding) comment on their ``__init__`` assignment::

    self.queue: deque[_Request] = deque()  # guarded-by: self._lock

and every OTHER read or write of ``self.queue`` inside the declaring class
must then sit lexically inside ``with self._lock:`` — the bug shape this
catches is exactly PR 3's GIL-reliant queue/row scans: code that happened
to be atomic under CPython's GIL and nothing else.

Two lock spellings are understood:

- a real lock expression (``self._lock``, ``self._submit_lock``): guarded
  means an enclosing ``with <that expression>:`` block;
- the special name ``event-loop``: the field is confined to the asyncio
  event loop — guarded means the INNERMOST enclosing function is an
  ``async def`` (single-threaded by construction; a sync def nested in a
  coroutine runs wherever it is called and needs ``holds(event-loop)``).

Escapes, both requiring a non-empty reason:

- ``# graftlint: unguarded-ok(<reason>)`` on the access line;
- ``# graftlint: holds(<lock>)`` on a ``def`` — the caller holds the lock
  for the whole function (lock-split helpers, loop-confined sync helpers).

``__init__`` is exempt (the object is not yet shared while it runs).

GL102: modules that are REQUIRED to carry annotations (the threaded core:
batcher, server, observability, coordinator) but declare none — so
deleting the annotations can never silently disable the rule.

Known limitation (documented in README): only ``self.<field>`` accesses
inside the declaring class are checked.  Cross-object accesses
(``other.batcher.queue``) are out of AST reach — route them through a
locked accessor on the owning class.
"""

from __future__ import annotations

import ast

from .core import Finding, Project, SourceFile, expr_text

RULE_ACCESS = "GL101"
RULE_MISSING = "GL102"

EVENT_LOOP = "event-loop"

# Modules that must declare at least one guarded-by annotation: the
# threaded serving core whose cross-thread contracts this rule exists for.
REQUIRED_MODULES = (
    "distributed_llms_tpu/runtime/batcher.py",
    "distributed_llms_tpu/runtime/pages.py",
    "distributed_llms_tpu/runtime/server.py",
    "distributed_llms_tpu/core/observability.py",
    "distributed_llms_tpu/cluster/coordinator.py",
)


def _annotated_fields(sf: SourceFile, cls: ast.ClassDef) -> dict[str, str]:
    """{field name: lock expr} for ``self.X = ...`` statements carrying a
    ``# guarded-by:`` comment anywhere in the class body."""
    out: dict[str, str] = {}
    for node in ast.walk(cls):
        targets: list[ast.expr] = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
            targets = [node.target]
        for t in targets:
            if (isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name) and t.value.id == "self"):
                lock = sf.guarded_by(node.lineno)
                if lock is not None:
                    out[t.attr] = lock
    return out


class _AccessChecker(ast.NodeVisitor):
    """Walk one class, tracking the lexical ``with`` stack and the
    enclosing function, flagging unguarded annotated-field accesses."""

    def __init__(self, sf: SourceFile, cls: ast.ClassDef,
                 fields: dict[str, str]) -> None:
        self.sf = sf
        self.cls = cls
        self.fields = fields
        self.findings: list[Finding] = []
        self._with_stack: list[str] = []
        self._fn_stack: list[ast.FunctionDef | ast.AsyncFunctionDef] = []

    # -- scope tracking --------------------------------------------------

    def _visit_fn(self, node) -> None:
        self._fn_stack.append(node)
        outer_with = self._with_stack
        # ``with`` blocks do not cross function boundaries: a closure
        # defined inside a locked region runs whenever it is CALLED, not
        # where it is defined — but holds() annotations do apply.
        self._with_stack = sorted(self.sf.holds_locks(node))
        self.generic_visit(node)
        self._with_stack = outer_with
        self._fn_stack.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    def _visit_with(self, node) -> None:
        held = [expr_text(item.context_expr) for item in node.items]
        self._with_stack.extend(held)
        self.generic_visit(node)
        del self._with_stack[len(self._with_stack) - len(held):]

    visit_With = _visit_with
    visit_AsyncWith = _visit_with

    # -- the check -------------------------------------------------------

    def visit_Attribute(self, node: ast.Attribute) -> None:
        self.generic_visit(node)
        if not (isinstance(node.value, ast.Name) and node.value.id == "self"):
            return
        lock = self.fields.get(node.attr)
        if lock is None or not self._fn_stack:
            return
        fn = self._fn_stack[-1]
        if fn.name == "__init__" and len(self._fn_stack) == 1:
            # Construction: the object is not shared yet.  Deliberately
            # only __init__'s direct body — a closure DEFINED there may be
            # called much later, from any thread.
            return
        if self._guarded(fn, lock):
            return
        if self.sf.suppressed(RULE_ACCESS, node.lineno, lock_alias=True):
            return
        what = ("outside an async def (event-loop-confined field)"
                if lock == EVENT_LOOP else f"outside 'with {lock}:'")
        self.findings.append(Finding(
            RULE_ACCESS, self.sf.rel, node.lineno,
            f"unguarded access to '{self.cls.name}.{node.attr}' "
            f"(guarded-by: {lock}) {what}",
        ))

    def _guarded(self, fn, lock: str) -> bool:
        if lock in self.sf.holds_locks(fn):
            return True
        if lock == EVENT_LOOP:
            # Confinement, not a lock: a coroutine BODY runs on the loop,
            # but a sync def nested inside one runs wherever it is CALLED
            # (run_in_executor, a thread) — only the innermost function
            # counts; off-loop helpers need holds(event-loop).
            return isinstance(self._fn_stack[-1], ast.AsyncFunctionDef)
        return lock in self._with_stack


def check(project: Project) -> list[Finding]:
    findings: list[Finding] = []
    for sf in project.package_files():
        annotated_any = False
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            fields = _annotated_fields(sf, node)
            if not fields:
                continue
            annotated_any = True
            checker = _AccessChecker(sf, node, fields)
            # Visit methods only (class-body statements run once, at
            # definition time, before any instance exists).
            for stmt in node.body:
                if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    checker.visit(stmt)
            findings.extend(checker.findings)
        if sf.rel in REQUIRED_MODULES and not annotated_any:
            findings.append(Finding(
                RULE_MISSING, sf.rel, 1,
                "threaded module declares no '# guarded-by:' annotations "
                "(the lock-discipline rule has nothing to check here — "
                "annotate the shared fields)",
            ))
    return findings
