"""graftlint self-tests: every rule family proven to fire on a seeded
violation, suppressions honored only with a reason, and THE tier-1 gate —
the repo itself must be clean modulo the checked-in baseline."""

from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tools.graftlint import (  # noqa: E402
    load_project, read_baseline, run_project, split_new, write_baseline,
)
from tools.graftlint import blocking, hotpath, locks, registry, testhygiene  # noqa: E402


def _project(tmp_path: Path, files: dict[str, str]):
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text, encoding="utf-8")
    return load_project(tmp_path)


def _rules(findings):
    return sorted(f.rule for f in findings)


# -- GL1xx lock discipline ------------------------------------------------

LOCKED_CLASS = '''
import threading
from collections import deque

class Batcher:
    def __init__(self):
        self._lock = threading.Lock()
        self.queue = deque()  # guarded-by: self._lock

    def submit(self, req):
        with self._lock:
            self.queue.append(req)   # guarded: OK

    def scan(self):
        return list(self.queue)      # VIOLATION: no lock

    # graftlint: holds(self._lock)
    def _scan_locked(self):
        return list(self.queue)      # OK: caller holds the lock

    def excused(self):
        return len(self.queue)  # graftlint: unguarded-ok(test-only probe)

    def no_reason(self):
        return len(self.queue)  # graftlint: unguarded-ok()
'''


def test_lock_rule_fires_on_unguarded_access(tmp_path):
    project = _project(tmp_path, {"pkg/mod.py": LOCKED_CLASS})
    findings = locks.check(project)
    lines = {f.line for f in findings if f.rule == "GL101"}
    assert len(lines) == 2  # scan() and the reasonless suppression
    assert all("guarded-by: self._lock" in f.message for f in findings
               if f.rule == "GL101")


def test_lock_rule_event_loop_confinement(tmp_path):
    src = '''
class Coord:
    def __init__(self):
        self.workers = {}  # guarded-by: event-loop

    async def handle(self):
        return len(self.workers)     # OK: coroutine

    def sync_probe(self):
        return len(self.workers)     # VIOLATION: sync, unannotated

    # graftlint: holds(event-loop)
    def status(self):
        return dict(self.workers)    # OK: declared loop-confined
'''
    findings = locks.check(_project(tmp_path, {"pkg/coord.py": src}))
    assert _rules(findings) == ["GL101"]
    assert "event-loop" in findings[0].message


def test_lock_rule_sync_closure_in_coroutine_is_not_confined(tmp_path):
    """A sync def nested inside an async def runs wherever it is CALLED
    (run_in_executor, a thread) — only the innermost function counts for
    event-loop confinement; holds(event-loop) re-admits it."""
    src = '''
class Coord:
    def __init__(self):
        self.workers = {}  # guarded-by: event-loop

    async def handler(self):
        def off_loop_job():
            return dict(self.workers)    # VIOLATION: escapes the loop
        # graftlint: holds(event-loop)
        def on_loop_helper():
            return len(self.workers)     # OK: declared loop-confined
        return off_loop_job, on_loop_helper
'''
    findings = locks.check(_project(tmp_path, {"pkg/coord.py": src}))
    assert _rules(findings) == ["GL101"]
    assert "off_loop" not in findings[0].message  # message names the field
    assert findings[0].line == 8


def test_lock_rule_requires_annotations_in_threaded_modules(tmp_path):
    findings = locks.check(_project(tmp_path, {
        "distributed_llms_tpu/runtime/server.py": "class S:\n    pass\n",
    }))
    assert _rules(findings) == ["GL102"]


# -- GL2xx hot-path hygiene ----------------------------------------------

HOT_SRC = '''
import jax.numpy as jnp
import numpy as np

def bad_item(x):
    return x.item()                      # GL201

def bad_cast(x):
    return float(jnp.sum(x))             # GL202

def bad_np(x):
    return np.asarray(jnp.exp(x))        # GL203

def bad_branch(x):
    if jnp.any(x > 0):                   # GL204
        return x
    return -x

def fine(cfg, x):
    rot = int(cfg.head_dim * cfg.pct)    # static config math: not flagged
    neg = float(jnp.finfo(jnp.float32).min)  # dtype metadata: not flagged
    if cfg.windowed:                     # host flag: not flagged
        return x
    return rot + neg
'''


def test_hotpath_rules_fire_in_scope(tmp_path):
    findings = hotpath.check(_project(tmp_path, {"pkg/ops/kern.py": HOT_SRC}))
    assert _rules(findings) == ["GL201", "GL202", "GL203", "GL204"]


def test_hotpath_ignores_out_of_scope_files(tmp_path):
    findings = hotpath.check(
        _project(tmp_path, {"pkg/runtime/host_side.py": HOT_SRC}))
    assert findings == []


# -- GL3xx registry drift -------------------------------------------------

FAULTS_MOD = '''
FAULT_SITES: dict[str, str] = {
    "engine.step": "per step",
    "engine.never": "declared but never fired",
}

class FaultPlane:
    def fire(self, site, tag=None):
        return None
'''


def test_fault_site_drift(tmp_path):
    project = _project(tmp_path, {
        "pkg/runtime/faults.py": FAULTS_MOD,
        "pkg/engine.py": (
            "def loop(plane):\n"
            "    plane.fire('engine.step')\n"       # registered: OK
            "    plane.fire('engine.stpe')\n"        # typo: GL301
        ),
        "tests/test_x.py": (
            "from pkg.runtime.faults import FaultPlane\n"
            "def test_y(plane):\n"
            "    plane.add('engine.bogus', 'raise')\n"   # dotted: GL301
            "    plane.add('s', 'drop')\n"               # synthetic: OK
        ),
    })
    findings = registry.check_fault_sites(project)
    assert _rules(findings) == ["GL301", "GL301", "GL305"]
    assert any("engine.stpe" in f.message for f in findings)
    assert any("engine.bogus" in f.message for f in findings)
    assert any("engine.never" in f.message for f in findings)


OBS_MOD = '''
METRIC_DOCS: dict[str, str] = {
    "req.count": "requests",
    "req.by_reason.*": "per-reason requests",
    "stale.gauge": "nothing emits this",
}
'''


def test_metric_drift(tmp_path):
    project = _project(tmp_path, {
        "pkg/core/observability.py": OBS_MOD,
        "pkg/srv.py": (
            "from .core.observability import METRICS\n"
            "def f(reason, name):\n"
            "    METRICS.inc('req.count')\n"             # OK
            "    METRICS.inc(f'req.by_reason.{reason}')\n"  # pattern: OK
            "    METRICS.inc('req.cuont')\n"             # typo: GL302
            "    METRICS.set_gauge(name, 1.0)\n"         # dynamic: GL302
        ),
    })
    findings = registry.check_metrics(project)
    assert _rules(findings) == ["GL302", "GL302", "GL305"]
    assert any("req.cuont" in f.message for f in findings)
    assert any("runtime-computed" in f.message for f in findings)
    assert any("stale.gauge" in f.message for f in findings)


def test_a_bucketed_histogram_emits_its_edge_series(tmp_path):
    """A histogram named in BUCKETED beside the registry exports
    "<name>.le_us.*" (prometheus_text makes the names): the tuple is the
    emitter GL305 counts, and an entry it lacks is GL302's finding;
    observe_many is an emitter like observe."""
    project = _project(tmp_path, {
        "pkg/core/observability.py": (
            "METRICS = None\n"
            "BUCKETED: tuple[str, ...] = ('row.gap_seconds', 'ttft_seconds')\n"
            "METRIC_DOCS: dict[str, str] = {\n"
            "    'row.gap_seconds': 'a bucketed histogram',\n"
            "    'row.gap_seconds.le_us.*': 'its edges',\n"
            "    'ttft_seconds': 'bucketed, its edges not declared',\n"
            "    'wait_seconds.le_us.*': 'edges of a histogram not bucketed',\n"
            "}\n"
        ),
        "pkg/srv.py": (
            "from .core.observability import METRICS\n"
            "def f(gaps):\n"
            "    METRICS.observe_many('row.gap_seconds', gaps)\n"
            "    METRICS.observe('ttft_seconds', 1.0)\n"
        ),
    })
    findings = registry.check_metrics(project)
    assert _rules(findings) == ["GL302", "GL305"]
    assert any("'ttft_seconds.le_us.*'" in f.message for f in findings)
    assert any("'wait_seconds.le_us.*'" in f.message for f in findings)


def test_span_calls_emit_their_seconds_histogram(tmp_path):
    """span("x") (core/profiling.py) observes "x_seconds": the call site
    is the emitter GL302 checks and GL305 counts, through any of the
    spellings the package uses."""
    project = _project(tmp_path, {
        "pkg/core/observability.py": (
            "METRICS = None\n"
            "METRIC_DOCS: dict[str, str] = {\n"
            "    'loop.admit_seconds': 'a span',\n"
            "    'loop.plan_seconds': 'a span',\n"
            "    'engine.idle_seconds': 'a span',\n"
            "    'loop.dead_seconds': 'no span emits this',\n"
            "}\n"
        ),
        "pkg/srv.py": (
            "from .core import profiling\n"
            "from .core.profiling import span\n"
            "class B:\n"
            "    def f(self, name):\n"
            "        with self._span('loop.admit', rid=3):\n"   # OK
            "            pass\n"
            "        with profiling.span('loop.plan'):\n"       # OK
            "            pass\n"
            "        with span('engine.idle'):\n"               # OK
            "            pass\n"
            "        with span('loop.amdit'):\n"                # typo: GL302
            "            pass\n"
            "        with self._span(name):\n"                  # dynamic
            "            pass\n"
        ),
    })
    findings = registry.check_metrics(project)
    assert _rules(findings) == ["GL302", "GL302", "GL305"]
    assert any("loop.amdit_seconds" in f.message for f in findings)
    assert any("runtime-computed" in f.message for f in findings)
    assert any("loop.dead_seconds" in f.message for f in findings)


def test_cli_flag_short_alias_is_not_invisible(tmp_path):
    """add_argument('-p', '--port', ...) declares --port: the long name
    must be found even when a short alias is the first positional."""
    project = _project(tmp_path, {
        "pkg/core/config.py": (
            "from dataclasses import dataclass\n"
            "@dataclass\nclass RuntimeConfig:\n    port: int = 0\n"
        ),
        "pkg/cli/serve_main.py": (
            "_RUNTIME_FLAGS: dict[str, str] = {'port': 'port'}\n"
            "_SERVER_ONLY_FLAGS = frozenset()\n"
            "def main(ap):\n"
            "    ap.add_argument('-p', '--port', type=int)\n"
        ),
    })
    assert registry.check_cli_flags(project) == []


def test_cli_flag_drift(tmp_path):
    project = _project(tmp_path, {
        "pkg/core/config.py": (
            "from dataclasses import dataclass\n"
            "@dataclass\nclass RuntimeConfig:\n    page_size: int = 64\n"
        ),
        "pkg/cli/serve_main.py": (
            "_RUNTIME_FLAGS: dict[str, str] = {\n"
            "    'page-size': 'page_size',\n"
            "    'paged-pages': 'paged_pages',\n"   # field missing: GL303
            "}\n"
            "_SERVER_ONLY_FLAGS = frozenset({'host', 'ghost'})\n"
            "def main(ap):\n"
            "    ap.add_argument('--page-size', type=int)\n"
            "    ap.add_argument('--paged-pages', type=int)\n"
            "    ap.add_argument('--host')\n"
            "    ap.add_argument('--rogue')\n"      # undeclared: GL303
            # 'ghost' declared but never added: GL305
        ),
    })
    findings = registry.check_cli_flags(project)
    assert _rules(findings) == ["GL303", "GL303", "GL305"]
    assert any("rogue" in f.message for f in findings)
    assert any("paged_pages" in f.message for f in findings)
    assert any("ghost" in f.message for f in findings)


# -- GL401 blocking calls in the engine loop ------------------------------

BATCHER_MOD = '''
import time

class ContinuousBatcher:
    def run(self):
        self._admit()
        helper()

    def _admit(self):
        time.sleep(0.1)          # GL401: reachable via run -> _admit

    def submit(self):
        time.sleep(0.1)          # NOT reachable from run: no finding

def helper():
    open("/tmp/x")               # GL401: reachable via run -> helper
'''


def test_blocking_rule_walks_the_run_call_graph(tmp_path):
    findings = blocking.check(
        _project(tmp_path, {"pkg/runtime/batcher.py": BATCHER_MOD}))
    assert _rules(findings) == ["GL401", "GL401"]
    assert {("_admit" in f.message or "helper" in f.message)
            for f in findings} == {True}
    assert not any("submit" in f.message for f in findings)


# -- GL501 test hygiene ---------------------------------------------------

def test_sleep_in_fast_test_fires(tmp_path):
    findings = testhygiene.check(_project(tmp_path, {"tests/test_t.py": (
        "import time, pytest\n"
        "def test_fast():\n"
        "    time.sleep(0.05)\n"          # GL501
        "def test_yield():\n"
        "    time.sleep(0)\n"             # GIL yield: OK
        "@pytest.mark.slow\n"
        "def test_slow():\n"
        "    time.sleep(1.0)\n"           # slow-marked: OK
    )}))
    assert _rules(findings) == ["GL501"]
    assert findings[0].line == 3


def test_slow_test_under_module_level_if_is_exempt(tmp_path):
    """Decorator-aware handling must survive module-level compound
    statements (a platform-guarded slow test is not a violation)."""
    findings = testhygiene.check(_project(tmp_path, {"tests/test_c.py": (
        "import sys, time, pytest\n"
        "if sys.platform != 'win32':\n"
        "    @pytest.mark.slow\n"
        "    def test_long():\n"
        "        time.sleep(1.0)\n"       # slow-marked: OK
        "    def test_fast():\n"
        "        time.sleep(0.5)\n"       # GL501 even under the if
    )}))
    assert _rules(findings) == ["GL501"]
    assert findings[0].line == 7


def test_slow_module_exempt(tmp_path):
    findings = testhygiene.check(_project(tmp_path, {"tests/test_s.py": (
        "import time, pytest\n"
        "pytestmark = pytest.mark.slow\n"
        "def test_anything():\n    time.sleep(0.5)\n"
    )}))
    assert findings == []


# -- strict fault-spec parsing (the GL301 runtime twin) -------------------

def test_fault_plane_strict_parse_rejects_unknown_sites():
    from distributed_llms_tpu.runtime.faults import FAULT_SITES, FaultPlane

    assert FaultPlane.parse("batcher.decode:raise@1", strict=True).rules
    with pytest.raises(ValueError, match="unknown fault site"):
        # graftlint: ignore[GL301](deliberately typo'd site — the assertion IS that strict parsing rejects it)
        FaultPlane.parse("batcher.decod:raise@1", strict=True)
    # Non-strict keeps the grammar tests' synthetic sites working.
    assert FaultPlane.parse("s:drop@1").rules[0].site == "s"
    assert FAULT_SITES  # the registry itself is populated


def test_write_docs_survives_backslash_in_registry_doc(tmp_path):
    """A backslash in a registry doc string must be written verbatim,
    not read as a re.sub escape (bad-escape crash / group mangling)."""
    (tmp_path / "README.md").write_text(
        "# x\n<!-- graftlint:fault-sites:begin -->\nold\n"
        "<!-- graftlint:fault-sites:end -->\n"
        "<!-- graftlint:metrics:begin -->\nold\n"
        "<!-- graftlint:metrics:end -->\n", encoding="utf-8")
    project = _project(tmp_path, {
        "pkg/runtime/faults.py": (
            "FAULT_SITES: dict[str, str] = "
            r"{'a.b': 'fires on \\x00 frames and \\g<1> groups'}"
            "\n"
        ),
        "pkg/core/observability.py": "METRIC_DOCS: dict[str, str] = {}\n",
    })
    assert set(registry.write_docs(project)) == {"fault-sites", "metrics"}
    text = (tmp_path / "README.md").read_text(encoding="utf-8")
    assert r"fires on \x00 frames and \g<1> groups" in text
    # The written tables satisfy the drift check (round-trip).
    assert registry.check_docs(load_project(tmp_path)) == []


def test_baseline_counts_duplicate_findings(tmp_path):
    """Baselining ONE occurrence of a finding must not absorb a second
    identical-message occurrence added later: the baseline is a multiset
    keyed (path, rule, message) with an [xN] count."""
    one = {"tests/test_d.py": (
        "import time\n"
        "def test_a():\n    time.sleep(0.5)\n"
    )}
    two = {"tests/test_d.py": (
        "import time\n"
        "def test_a():\n    time.sleep(0.5)\n"
        "def test_b():\n    time.sleep(0.5)\n"
    )}
    write_baseline(tmp_path, testhygiene.check(_project(tmp_path, one)))
    baseline = read_baseline(tmp_path)
    findings2 = testhygiene.check(_project(tmp_path, two))
    assert len(findings2) == 2
    new, accepted = split_new(findings2, baseline)
    assert len(accepted) == 1 and len(new) == 1  # the added sleep is NEW
    # Re-accepting both round-trips through the [x2] form.
    write_baseline(tmp_path, findings2)
    assert sum(read_baseline(tmp_path).values()) == 2
    assert split_new(findings2, read_baseline(tmp_path))[0] == []


# -- THE tier-1 gate ------------------------------------------------------

def test_repo_is_clean():
    """Zero non-baselined findings over the real tree.  A new violation
    of any rule family fails tier-1 right here."""
    project = load_project(ROOT)
    findings = run_project(project)
    new, _accepted = split_new(findings, read_baseline(ROOT))
    assert not new, "new graftlint findings:\n" + "\n".join(
        f.render() for f in new
    )


def test_a_directory_gitignore_lists_at_the_root_is_not_walked(tmp_path):
    """A parent commit's checkout under ``_chip/`` (git-ignored) is another
    project: a bad file planted there is found nowhere, the same file in a
    tracked directory is."""
    bad = "import time\ndef test_a():\n    time.sleep(0.5)\n"
    project = _project(tmp_path, {
        ".gitignore": "__pycache__/\n/_chip/\nchiprun_out/\n*.pyc\n",
        "_chip/parent/tests/test_z.py": bad,
        "chiprun_out/tests/test_z.py": bad,
        "tests/test_ok.py": "def test_a():\n    assert True\n",
    })
    assert sorted(f.rel for f in project.files) == ["tests/test_ok.py"]
    assert run_project(project) == []
    # ... a directory of that name further down is still walked
    project = _project(tmp_path, {"tests/_chip/test_z.py": bad})
    assert "GL501" in _rules(run_project(project))


def test_cli_exit_codes(tmp_path):
    # Dirty fixture tree -> exit 1 and the finding on stdout ...
    (tmp_path / "tests").mkdir()
    (tmp_path / "tests" / "test_z.py").write_text(
        "import time\ndef test_a():\n    time.sleep(0.5)\n")
    env_root = str(tmp_path)
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--root", env_root],
        capture_output=True, text=True, cwd=ROOT,
    )
    assert r.returncode == 1
    assert "GL501" in r.stdout
    # ... --baseline-write accepts the debt, after which the gate passes.
    subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--root", env_root,
         "--baseline-write"],
        capture_output=True, text=True, cwd=ROOT, check=True,
    )
    r2 = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--root", env_root],
        capture_output=True, text=True, cwd=ROOT,
    )
    assert r2.returncode == 0, r2.stdout + r2.stderr
