"""graftlint core: source loading, findings, suppressions, baseline.

graftlint is an AST-based, repo-specific static-analysis suite.  Each rule
module exposes ``check(project) -> list[Finding]``; this module owns the
shared plumbing:

- :class:`SourceFile`: parsed AST + per-line comment map (via ``tokenize``,
  so ``#`` inside string literals never reads as a comment);
- suppression comments
  (``# graftlint: unguarded-ok(<reason>)`` for the lock rule,
  ``# graftlint: ignore[RULE-ID](<reason>)`` for any rule,
  ``# graftlint: holds(<lock>)`` on a ``def`` asserting the caller holds
  the lock) — a suppression with an EMPTY reason is deliberately inert:
  accepted debt must say why;
- the checked-in baseline (``graftlint_baseline.txt``): findings are
  normalized WITHOUT line numbers (line churn must not resurrect debt)
  but WITH occurrence counts (``[xN]`` — one baselined occurrence must
  not absorb a newly added duplicate), and only findings beyond the
  baselined counts fail the gate.
"""

from __future__ import annotations

import ast
import io
import re
import tokenize
from dataclasses import dataclass, field
from pathlib import Path

BASELINE_NAME = "graftlint_baseline.txt"

_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*"
    r"(?:(unguarded-ok)|ignore\[([A-Z]+\d+(?:\s*,\s*[A-Z]+\d+)*)\])"
    r"\(([^)]*)\)"
)
_HOLDS_RE = re.compile(r"#\s*graftlint:\s*holds\(([^)]+)\)")
_GUARDED_BY_RE = re.compile(r"#\s*guarded-by:\s*(\S+)")


@dataclass(frozen=True)
class Finding:
    rule: str      # e.g. "GL101"
    path: str      # repo-relative posix path
    line: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"

    def normalized(self) -> str:
        """Baseline key: no line number, so unrelated edits moving code
        up/down never turn accepted debt into a 'new' finding."""
        return f"{self.path}: {self.rule} {self.message}"


@dataclass
class SourceFile:
    path: Path                 # absolute
    rel: str                   # repo-relative posix
    text: str
    tree: ast.Module
    comments: dict[int, str] = field(default_factory=dict)  # line -> text
    lines: list[str] = field(default_factory=list)

    # -- comment-derived annotations ------------------------------------

    def _standalone_comment(self, line: int) -> bool:
        """Whether ``line`` is a comment-only line (a trailing comment on
        someone else's statement must never annotate the NEXT line)."""
        return (1 <= line <= len(self.lines)
                and self.lines[line - 1].lstrip().startswith("#"))

    def _comment_for(self, line: int) -> str:
        """Comments annotating ``line``: its own trailing comment plus a
        standalone comment line directly above."""
        own = self.comments.get(line, "")
        above = (self.comments.get(line - 1, "")
                 if self._standalone_comment(line - 1) else "")
        return f"{above}\n{own}"

    def suppressions(self, line: int) -> list[tuple[str | None, str]]:
        """(rule-or-None, reason) suppressions on ``line`` (or a
        standalone comment directly above it).  rule None means the
        lock-rule alias ``unguarded-ok``."""
        out: list[tuple[str | None, str]] = []
        for m in _SUPPRESS_RE.finditer(self._comment_for(line)):
            reason = m.group(3).strip()
            if not reason:
                continue  # reasonless suppressions don't count
            if m.group(1):
                out.append((None, reason))
            else:
                for rid in re.split(r"\s*,\s*", m.group(2)):
                    out.append((rid, reason))
        return out

    def suppressed(self, rule: str, line: int, lock_alias: bool = False) -> bool:
        for rid, _reason in self.suppressions(line):
            if rid == rule or (rid is None and lock_alias):
                return True
        return False

    def holds_locks(self, fn: ast.FunctionDef | ast.AsyncFunctionDef) -> set[str]:
        """Locks a ``# graftlint: holds(<lock>)`` annotation asserts are
        held for the whole function (scanned from the first decorator line
        through the ``def`` line, plus the line above)."""
        first = min([fn.lineno] + [d.lineno for d in fn.decorator_list])
        out: set[str] = set()
        for ln in range(first - 1, fn.lineno + 1):
            for m in _HOLDS_RE.finditer(self.comments.get(ln, "")):
                out.add(normalize_expr(m.group(1)))
        return out

    def guarded_by(self, line: int) -> str | None:
        """The ``# guarded-by: <lock>`` annotation on ``line`` or on a
        standalone comment line directly above it."""
        m = _GUARDED_BY_RE.search(self._comment_for(line))
        return normalize_expr(m.group(1)) if m else None


@dataclass
class Project:
    root: Path
    files: list[SourceFile]

    def package_files(self) -> list[SourceFile]:
        """Files outside tests/ and tools/ (the shipped package + scripts)."""
        return [f for f in self.files
                if not f.rel.startswith(("tests/", "tools/"))]

    def test_files(self) -> list[SourceFile]:
        return [f for f in self.files if f.rel.startswith("tests/")]


def normalize_expr(src: str) -> str:
    return src.replace(" ", "")


def expr_text(node: ast.AST) -> str:
    try:
        return normalize_expr(ast.unparse(node))
    except Exception:
        return "<unparseable>"


def dotted_name(node: ast.AST) -> str | None:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _comment_map(text: str) -> dict[int, str]:
    out: dict[int, str] = {}
    try:
        for tok in tokenize.generate_tokens(io.StringIO(text).readline):
            if tok.type == tokenize.COMMENT:
                out[tok.start[0]] = tok.string
    except (tokenize.TokenError, IndentationError):
        pass  # the AST parse decides whether the file is usable at all
    return out


def load_file(root: Path, path: Path) -> SourceFile | None:
    try:
        text = path.read_text(encoding="utf-8")
        tree = ast.parse(text, filename=str(path))
    except (OSError, SyntaxError, ValueError):
        return None
    return SourceFile(
        path=path, rel=path.relative_to(root).as_posix(), text=text,
        tree=tree, comments=_comment_map(text), lines=text.splitlines(),
    )


_SKIP_DIRS = {".git", "__pycache__", ".venv", "venv", "node_modules",
              ".claude", "build", "dist"}


def ignored_dirs(root: Path) -> set[str]:
    """Directories at the walk's ROOT that ``<root>/.gitignore`` lists by
    name (``_chip/``, ``/chiprun_out/``, ``.jax_cache/``: a line that ends
    in a slash and holds no wildcard and no inner slash).  The tools walk
    what the repository tracks: an untracked checkout of a parent commit
    under such a directory is another project, whose copies of the models
    and tests would read as duplicates and drift."""
    try:
        lines = (root / ".gitignore").read_text(encoding="utf-8").splitlines()
    except OSError:
        return set()
    names = (line.strip() for line in lines)
    return {n.strip("/") for n in names
            if n.endswith("/") and not n.startswith(("#", "!"))
            and not any(c in n.strip("/") for c in "*?[/")}


def load_project(root: str | Path) -> Project:
    root = Path(root).resolve()
    files: list[SourceFile] = []
    ignored = ignored_dirs(root)
    for path in sorted(root.rglob("*.py")):
        parts = path.relative_to(root).parts[:-1]
        if parts and parts[0] in ignored:
            continue
        if any(part in _SKIP_DIRS or part.endswith(".egg-info")
               for part in parts):
            continue
        sf = load_file(root, path)
        if sf is not None:
            files.append(sf)
    return Project(root=root, files=files)


# -- baseline ------------------------------------------------------------
#
# The baseline is a MULTISET: identical-message findings (e.g. two
# unguarded accesses to the same field in one file) are tracked by count
# via an ``[xN]`` suffix, so baselining one occurrence never silently
# accepts a second one added later.

_BASELINE_COUNT_RE = re.compile(r"^(.*?)\s*\[x(\d+)\]$")


def read_baseline(root: Path, name: str = BASELINE_NAME) -> dict[str, int]:
    """Normalized entry -> accepted occurrence count.  ``name`` lets sibling
    checkers (tools.graftcheck) share the format with their own file."""
    path = root / name
    out: dict[str, int] = {}
    if not path.exists():
        return out
    for line in path.read_text(encoding="utf-8").splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _BASELINE_COUNT_RE.match(line)
        if m:
            out[m.group(1)] = out.get(m.group(1), 0) + int(m.group(2))
        else:
            out[line] = out.get(line, 0) + 1
    return out


def write_baseline(root: Path, findings: list[Finding],
                   name: str = BASELINE_NAME, tool: str = "graftlint") -> Path:
    path = root / name
    lines = [
        f"# {tool} accepted debt.  One normalized finding per line",
        "# (path: RULE message — no line numbers, so edits moving code",
        "# around never resurrect an entry; repeated identical findings",
        "# carry an [xN] count).  Regenerate deliberately with:",
        f"#   python -m tools.{tool} --baseline-write",
        "# Prefer fixing or suppressing-with-reason at the site over",
        "# baselining; every entry here should be a conscious debt note.",
    ]
    counts: dict[str, int] = {}
    for f in findings:
        counts[f.normalized()] = counts.get(f.normalized(), 0) + 1
    lines += [key if n == 1 else f"{key} [x{n}]"
              for key, n in sorted(counts.items())]
    path.write_text("\n".join(lines) + "\n", encoding="utf-8")
    return path


def stale_entries(findings: list[Finding],
                  baseline: dict[str, int]) -> list[str]:
    """Baseline entries whose accepted count exceeds what still occurs —
    fixed debt that should be pruned with --baseline-write.  Shared by both
    checkers' CLIs and the tools.check front door (which escalates these
    to errors)."""
    counts: dict[str, int] = {}
    for f in findings:
        counts[f.normalized()] = counts.get(f.normalized(), 0) + 1
    return sorted(
        key for key, n in baseline.items() if n > counts.get(key, 0)
    )


def split_new(findings: list[Finding], baseline: dict[str, int]
              ) -> tuple[list[Finding], list[Finding]]:
    """(new, accepted) relative to the baseline.  Each baseline entry
    absorbs at most its accepted count of matching findings."""
    remaining = dict(baseline)
    new: list[Finding] = []
    old: list[Finding] = []
    for f in findings:
        key = f.normalized()
        if remaining.get(key, 0) > 0:
            remaining[key] -= 1
            old.append(f)
        else:
            new.append(f)
    return new, old
