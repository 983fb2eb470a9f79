"""GL3xx — registry drift.

Three string-keyed namespaces in this codebase historically grew by
convention: fault-injection site names, metric names, and ``dlt-serve``
CLI flags.  A typo in any of them is a silent no-op (a fault rule that
never fires, a dashboard counter that never moves, a flag that falls
through to a default).  These rules pin each namespace to a single
declared registry:

- GL301: every ``FaultPlane`` site string used anywhere (``.fire(...)``
  calls, ``_apply_frame_fault`` calls, ``FaultPlane.parse``/``.add``
  literals — in tests too, for dotted site names) must appear in
  ``FAULT_SITES`` in ``runtime/faults.py``.
- GL302: every metric name passed to ``METRICS.inc / set_gauge /
  set_gauges / observe / observe_many`` in the package must appear in
  ``METRIC_DOCS`` in ``core/observability.py``; a ``span("x")`` call
  (``core/profiling.py``) emits the histogram ``x_seconds``, and a
  histogram named in the ``BUCKETED`` tuple beside the registry emits the
  series ``<name>.le_us.*`` (one an edge of its ladder, written by
  ``prometheus_text``).  f-string names are
  checked as patterns (each interpolation becomes ``*``) and must be
  registered VERBATIM as that pattern (e.g. ``faults.fired.*``); a fully
  dynamic name needs an ``ignore[GL302](<reason>)``.
- GL303: every ``dlt-serve`` flag (``cli/serve_main.py``) must be declared
  either in ``_RUNTIME_FLAGS`` (flag -> RuntimeConfig field, field
  existence checked) or ``_SERVER_ONLY_FLAGS`` (server plumbing with no
  config twin) — and in exactly one of them.
- GL304: the README tables rendered from FAULT_SITES / METRIC_DOCS
  (between ``<!-- graftlint:...-sites:begin/end -->`` markers) must match
  the registries byte-for-byte (``--write-docs`` regenerates them).
- GL305: the reverse drift — a registry/declaration entry nothing uses.
"""

from __future__ import annotations

import ast
import fnmatch
import re
from pathlib import Path

from .core import Finding, Project, SourceFile, dotted_name

RULE_FAULT = "GL301"
RULE_METRIC = "GL302"
RULE_FLAG = "GL303"
RULE_DOCS = "GL304"
RULE_UNUSED = "GL305"

FAULTS_MODULE = "runtime/faults.py"
OBS_MODULE = "core/observability.py"
SERVE_MODULE = "cli/serve_main.py"
CONFIG_MODULE = "core/config.py"

_METRIC_METHODS = {"inc", "set_gauge", "observe", "observe_many"}


def _find_module(project: Project, suffix: str) -> SourceFile | None:
    return next((f for f in project.package_files()
                 if f.rel.endswith(suffix)), None)


def _literal_dict(sf: SourceFile, name: str) -> dict[str, str] | None:
    """A module-level ``NAME = {str: str}`` dict literal, else None.
    Also imported by tools/graftflow (LOCK_ORDER / FAULT_SITES reads) —
    the registry idiom must parse identically across the tools."""
    for node in sf.tree.body:
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target] if isinstance(node, ast.AnnAssign)
                   else [])
        for t in targets:
            if isinstance(t, ast.Name) and t.id == name:
                value = node.value
                if isinstance(value, ast.Dict):
                    out: dict[str, str] = {}
                    for k, v in zip(value.keys, value.values):
                        if (isinstance(k, ast.Constant) and isinstance(k.value, str)
                                and isinstance(v, ast.Constant)
                                and isinstance(v.value, str)):
                            out[k.value] = v.value
                    return out
    return None


def _literal_strset(sf: SourceFile, name: str) -> set[str] | None:
    """A module-level ``NAME = frozenset({...})`` / set / tuple of str.
    Also imported by tools/graftflow (MESSAGE_TYPES reads)."""
    for node in sf.tree.body:
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target] if isinstance(node, ast.AnnAssign)
                   else [])
        for t in targets:
            if isinstance(t, ast.Name) and t.id == name:
                consts = [
                    n.value for n in ast.walk(node.value)
                    if isinstance(n, ast.Constant) and isinstance(n.value, str)
                ]
                return set(consts)
    return None


# -- GL301: fault sites ---------------------------------------------------

def _sites_in_spec(spec: str) -> list[str]:
    """Site names out of a fault-spec literal (grammar:
    ``site[/tag]:action[@when][:arg]``, comma-separated)."""
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part or ":" not in part:
            continue
        out.append(part.split(":", 1)[0].partition("/")[0])
    return out


def _fault_site_uses(sf: SourceFile, tests: bool) -> list[tuple[str, int]]:
    """(site, line) pairs used in ``sf``.  In test files only dotted site
    names count — the fault-grammar unit tests use synthetic one-letter
    sites on purpose."""
    uses: list[tuple[str, int]] = []
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        first = node.args[0]
        if not (isinstance(first, ast.Constant) and isinstance(first.value, str)):
            continue
        recv_text = (dotted_name(node.func) or "").lower()
        attr = node.func.attr if isinstance(node.func, ast.Attribute) else None
        fn_name = node.func.id if isinstance(node.func, ast.Name) else None
        if attr == "fire" or fn_name == "_apply_frame_fault":
            uses.append((first.value, node.lineno))
        elif (attr in ("add", "parse")
                and ("fault" in recv_text or "plane" in recv_text)):
            if attr == "add":
                uses.append((first.value, node.lineno))
            else:
                uses.extend((s, node.lineno)
                            for s in _sites_in_spec(first.value))
    if tests:
        uses = [(s, ln) for s, ln in uses if "." in s]
    return uses


def check_fault_sites(project: Project) -> list[Finding]:
    reg_file = _find_module(project, FAULTS_MODULE)
    if reg_file is None:
        return []
    registry = _literal_dict(reg_file, "FAULT_SITES")
    if registry is None:
        return [Finding(RULE_FAULT, reg_file.rel, 1,
                        "no FAULT_SITES registry (dict[str, str] of "
                        "site -> one-line doc) declared")]
    findings: list[Finding] = []
    used: set[str] = set()
    for sf in project.files:
        if sf.rel.startswith("tools/"):
            continue
        for site, line in _fault_site_uses(sf, tests=sf.rel.startswith("tests/")):
            used.add(site)
            if site not in registry and not sf.suppressed(RULE_FAULT, line):
                findings.append(Finding(
                    RULE_FAULT, sf.rel, line,
                    f"fault site '{site}' is not declared in FAULT_SITES "
                    f"({reg_file.rel}) — a typo here is a rule that never "
                    f"fires",
                ))
    for site in sorted(set(registry) - used):
        findings.append(Finding(
            RULE_UNUSED, reg_file.rel, 1,
            f"FAULT_SITES entry '{site}' is fired nowhere in the tree",
        ))
    return findings


# -- GL302: metric names --------------------------------------------------

def _pattern_of(node: ast.expr) -> str | None:
    """A checkable name for a metric-name expression: the literal itself,
    or an f-string collapsed to a ``*`` pattern.  None = fully dynamic."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        parts = []
        for v in node.values:
            if isinstance(v, ast.Constant):
                parts.append(str(v.value))
            else:
                if not parts or parts[-1] != "*":
                    parts.append("*")
        return "".join(parts)
    return None


def _span_call(node: ast.Call) -> bool:
    """``span("x")``, ``profiling.span("x")`` or ``self._span("x")``: the
    one span mechanism (core/profiling.py) observes ``x_seconds``."""
    f = node.func
    name = f.id if isinstance(f, ast.Name) else getattr(f, "attr", None)
    return name in ("span", "_span") and bool(node.args)


def _metric_name_nodes(sf: SourceFile) -> list[tuple[ast.expr, int]]:
    out: list[tuple[ast.expr, int]] = []
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if _span_call(node):
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                arg = ast.copy_location(
                    ast.Constant(arg.value + "_seconds"), arg)
            # A non-literal span name stays as it is: reported as a
            # runtime-computed metric name, like any other.
            out.append((arg, node.lineno))
            continue
        if not (isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name)
                and f.value.id == "METRICS"):
            continue
        if f.attr in _METRIC_METHODS and node.args:
            out.append((node.args[0], node.lineno))
        elif f.attr == "set_gauges" and node.args:
            arg = node.args[0]
            if isinstance(arg, ast.Dict):
                out.extend((k, k.lineno) for k in arg.keys if k is not None)
            elif isinstance(arg, ast.DictComp):
                out.append((arg.key, arg.key.lineno))
            else:
                out.append((arg, node.lineno))
    return out


def _registered(name: str, registry: dict[str, str]) -> bool:
    if name in registry:  # literal entry, or a pattern registered verbatim
        return True
    if "*" not in name:
        return any("*" in key and fnmatch.fnmatchcase(name, key)
                   for key in registry)
    return False


def check_metrics(project: Project) -> list[Finding]:
    reg_file = _find_module(project, OBS_MODULE)
    if reg_file is None:
        return []
    registry = _literal_dict(reg_file, "METRIC_DOCS")
    if registry is None:
        return [Finding(RULE_METRIC, reg_file.rel, 1,
                        "no METRIC_DOCS registry (dict[str, str] of metric "
                        "name/pattern -> one-line doc) declared")]
    findings: list[Finding] = []
    used: set[str] = set()
    for name in sorted(_literal_strset(reg_file, "BUCKETED") or ()):
        # The edge series of a bucketed histogram: no call site names
        # them, the export makes them from the histogram's own name.
        used.add(name + ".le_us.*")
        if name + ".le_us.*" not in registry:
            findings.append(Finding(
                RULE_METRIC, reg_file.rel, 1,
                f"bucketed histogram '{name}' has no '{name}.le_us.*' "
                f"entry in METRIC_DOCS",
            ))
    for sf in project.package_files():
        for name_node, line in _metric_name_nodes(sf):
            pattern = _pattern_of(name_node)
            if pattern is not None:
                # Count the use BEFORE the suppression check: a registered
                # name emitted only at a suppressed site must not draw a
                # false GL305 "emitted nowhere".
                used.add(pattern)
            if sf.suppressed(RULE_METRIC, line):
                continue
            if pattern is None:
                findings.append(Finding(
                    RULE_METRIC, sf.rel, line,
                    "metric name is a runtime-computed expression — "
                    "graftlint cannot check it against METRIC_DOCS; use a "
                    "literal/f-string or ignore[GL302](why)",
                ))
                continue
            if not _registered(pattern, registry):
                findings.append(Finding(
                    RULE_METRIC, sf.rel, line,
                    f"metric '{pattern}' is not declared in METRIC_DOCS "
                    f"({reg_file.rel}) — dashboards can't find what the "
                    f"registry doesn't name",
                ))
    for key in sorted(registry):
        hit = key in used or (
            "*" in key and any(fnmatch.fnmatchcase(u, key)
                               for u in used if "*" not in u))
        if not hit:
            findings.append(Finding(
                RULE_UNUSED, reg_file.rel, 1,
                f"METRIC_DOCS entry '{key}' is emitted nowhere in the "
                f"package",
            ))
    return findings


# -- GL303: dlt-serve flags ----------------------------------------------

def _runtime_fields(project: Project) -> set[str] | None:
    cfg = _find_module(project, CONFIG_MODULE)
    if cfg is None:
        return None
    for node in ast.walk(cfg.tree):
        if isinstance(node, ast.ClassDef) and node.name == "RuntimeConfig":
            return {
                stmt.target.id for stmt in node.body
                if isinstance(stmt, ast.AnnAssign)
                and isinstance(stmt.target, ast.Name)
            }
    return None


def check_cli_flags(project: Project) -> list[Finding]:
    serve = _find_module(project, SERVE_MODULE)
    if serve is None:
        return []
    fields = _runtime_fields(project) or set()
    runtime_flags = _literal_dict(serve, "_RUNTIME_FLAGS")
    server_only = _literal_strset(serve, "_SERVER_ONLY_FLAGS")
    if runtime_flags is None or server_only is None:
        return [Finding(RULE_FLAG, serve.rel, 1,
                        "dlt-serve must declare _RUNTIME_FLAGS (flag -> "
                        "RuntimeConfig field) and _SERVER_ONLY_FLAGS")]
    findings: list[Finding] = []
    flags: list[tuple[str, int]] = []
    for node in ast.walk(serve.tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument"):
            # The long name may not be the first positional (short aliases
            # like add_argument("-p", "--port", ...) come before it).
            for arg in node.args:
                if (isinstance(arg, ast.Constant)
                        and isinstance(arg.value, str)
                        and arg.value.startswith("--")):
                    flags.append((arg.value[2:], node.lineno))
                    break
    seen = set()
    for flag, line in flags:
        seen.add(flag)
        in_rt, in_srv = flag in runtime_flags, flag in server_only
        if in_rt and in_srv:
            findings.append(Finding(
                RULE_FLAG, serve.rel, line,
                f"--{flag} is declared BOTH runtime-backed and "
                f"server-only; pick one",
            ))
        elif not in_rt and not in_srv:
            findings.append(Finding(
                RULE_FLAG, serve.rel, line,
                f"--{flag} is declared in neither _RUNTIME_FLAGS nor "
                f"_SERVER_ONLY_FLAGS — say whether it shadows a "
                f"RuntimeConfig field",
            ))
        elif in_rt and runtime_flags[flag] not in fields:
            findings.append(Finding(
                RULE_FLAG, serve.rel, line,
                f"--{flag} maps to RuntimeConfig.{runtime_flags[flag]}, "
                f"which does not exist",
            ))
    for flag in sorted((set(runtime_flags) | server_only) - seen):
        findings.append(Finding(
            RULE_UNUSED, serve.rel, 1,
            f"declared dlt-serve flag '--{flag}' has no add_argument",
        ))
    return findings


# -- GL304: README tables -------------------------------------------------

def render_fault_table(registry: dict[str, str]) -> str:
    lines = ["| site | fires at |", "| --- | --- |"]
    lines += [f"| `{site}` | {doc} |" for site, doc in sorted(registry.items())]
    return "\n".join(lines)


def render_metric_table(registry: dict[str, str]) -> str:
    lines = ["| metric | meaning |", "| --- | --- |"]
    lines += [f"| `{name}` | {doc} |" for name, doc in sorted(registry.items())]
    return "\n".join(lines)


_MARKERS = {
    "fault-sites": render_fault_table,
    "metrics": render_metric_table,
}


def _marker_re(tag: str) -> re.Pattern[str]:
    return re.compile(
        rf"<!-- graftlint:{tag}:begin -->\n(.*?)<!-- graftlint:{tag}:end -->",
        re.S,
    )


def _registries(project: Project) -> dict[str, dict[str, str]]:
    out = {}
    faults = _find_module(project, FAULTS_MODULE)
    obs = _find_module(project, OBS_MODULE)
    out["fault-sites"] = (_literal_dict(faults, "FAULT_SITES") or {}) \
        if faults else {}
    out["metrics"] = (_literal_dict(obs, "METRIC_DOCS") or {}) if obs else {}
    return out


def check_docs(project: Project) -> list[Finding]:
    readme = project.root / "README.md"
    if not readme.exists():
        return []
    text = readme.read_text(encoding="utf-8")
    regs = _registries(project)
    findings: list[Finding] = []
    for tag, renderer in _MARKERS.items():
        m = _marker_re(tag).search(text)
        if m is None:
            findings.append(Finding(
                RULE_DOCS, "README.md", 1,
                f"missing '<!-- graftlint:{tag}:begin/end -->' block — run "
                f"python -m tools.graftlint --write-docs",
            ))
            continue
        want = renderer(regs[tag])
        if m.group(1).strip() != want.strip():
            line = text[: m.start()].count("\n") + 1
            findings.append(Finding(
                RULE_DOCS, "README.md", line,
                f"'{tag}' table is stale vs the code registry — run "
                f"python -m tools.graftlint --write-docs",
            ))
    return findings


def write_docs(project: Project) -> list[str]:
    """Regenerate the README registry tables in place.  Returns the tags
    rewritten (missing README or marker blocks are skipped, not
    invented)."""
    readme = project.root / "README.md"
    if not readme.exists():
        return []
    text = readme.read_text(encoding="utf-8")
    regs = _registries(project)
    done: list[str] = []
    for tag, renderer in _MARKERS.items():
        pat = _marker_re(tag)
        if pat.search(text) is None:
            continue
        block = (f"<!-- graftlint:{tag}:begin -->\n{renderer(regs[tag])}\n"
                 f"<!-- graftlint:{tag}:end -->")
        # Callable replacement: a backslash in a registry doc string must
        # not be interpreted as a re.sub escape sequence.
        text = pat.sub(lambda _m, _b=block: _b, text)
        done.append(tag)
    readme.write_text(text, encoding="utf-8")
    return done


def check(project: Project) -> list[Finding]:
    return (check_fault_sites(project) + check_metrics(project)
            + check_cli_flags(project) + check_docs(project))
