"""Self-tests for tools/graftmodel — the protocol model-checking tier.

Each GM family gets seeded-violation tests against a toy fixture tree
(a registry module, a metrics module, a ``*_MODEL`` literal, and a test
file with drills) plus negatives proving a clean tree stays quiet.  The
toy protocol is a two-slot quota ledger: ``admit`` charges a unit,
``finish``/``drop`` refund it, and conservation (``charged == inflight
+ refunded``) is the GM1 law the mutations break.

Also here: the suppression drill (reasonless escapes are inert), the
CLI exit-code roundtrip (1 -> baseline-write -> 0, unknown family -> 2),
the front-door family scoping, and the tier-1 gate — the REAL repo must
model-check clean against the checked-in (empty) baseline.
"""

from __future__ import annotations

import copy
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from tools import graftmodel  # noqa: E402
from tools.graftmodel import load_project, run_project, split_new  # noqa: E402
from tools.graftmodel.core import (discover_models,  # noqa: E402
                                   load_registries)
from tools.graftmodel.docs import check_docs, write_docs  # noqa: E402

REGISTRY_SRC = '''\
ACTIONS = frozenset({"drop", "corrupt", "raise"})

FAULT_SITES = {
    "toy.site": "toy send path",
}

SITE_ACTIONS = {
    "toy.site": "drop, corrupt",
}

PROTOCOL_MODELS = {
    "toy.protocol": "two-slot quota ledger",
}
'''

METRICS_SRC = '''\
METRIC_DOCS = {
    "toy.fallbacks.*": "per-reason toy fallback counters",
}
'''

# Drills for both declared pairs, one per injection idiom the GM601
# scanner understands (plane.add literals, fault-spec strings).
TESTS_SRC = '''\
class _Plane:
    def add(self, *a, **k):
        return None


def test_drop_drill():
    _Plane().add("toy.site", "drop", when="1")


def test_corrupt_drill():
    assert "toy.site/T:corrupt@2"
'''

# The clean toy model: conservation holds on every reachable state, the
# space is 6 states, and every transition fires somewhere.
BASE_MODEL = {
    "name": "toy.protocol",
    "doc": "two-slot quota ledger",
    "params": {"BUDGET": 2},
    "state": {"inflight": 0, "charged": 0, "refunded": 0},
    "actions": [
        {"name": "admit", "guard": "charged < BUDGET",
         "update": {"inflight": "inflight + 1", "charged": "charged + 1"}},
        {"name": "finish", "guard": "inflight > 0",
         "update": {"inflight": "inflight - 1",
                    "refunded": "refunded + 1"}},
    ],
    "faults": [
        {"name": "drop", "site": "toy.site", "action": "drop",
         "guard": "inflight > 0", "metric": "toy.fallbacks.drop",
         "update": {"inflight": "inflight - 1",
                    "refunded": "refunded + 1"}},
    ],
    "invariants": [
        {"rule": "GM1", "name": "ledger-conserved",
         "expr": "charged == inflight + refunded"},
        {"rule": "GM2", "name": "no-negative-parcels",
         "expr": "inflight >= 0"},
        {"rule": "GM3", "name": "refund-at-most-charged",
         "expr": "refunded <= charged"},
        {"rule": "GM4", "name": "bounded-by-budget",
         "expr": "charged <= BUDGET"},
    ],
    "terminal": "inflight == 0",
}


def _toy(mutate=None) -> dict:
    m = copy.deepcopy(BASE_MODEL)
    if mutate:
        mutate(m)
    return m


def _tree(tmp_path, model=None, model_src=None, registry=REGISTRY_SRC,
          metrics=METRICS_SRC, tests=TESTS_SRC, readme=None):
    (tmp_path / "pkg" / "runtime").mkdir(parents=True, exist_ok=True)
    (tmp_path / "pkg" / "core").mkdir(parents=True, exist_ok=True)
    (tmp_path / "tests").mkdir(exist_ok=True)
    (tmp_path / "pkg" / "runtime" / "faults.py").write_text(registry)
    (tmp_path / "pkg" / "core" / "observability.py").write_text(metrics)
    if tests is not None:
        (tmp_path / "tests" / "test_drills.py").write_text(tests)
    if model_src is None:
        model_src = f"TOY_MODEL = {(model or BASE_MODEL)!r}"
    (tmp_path / "pkg" / "proto.py").write_text(model_src + "\n")
    if readme is not None:
        (tmp_path / "README.md").write_text(readme)
    return load_project(tmp_path)


def _rules(findings):
    return sorted(f.rule for f in findings)


def _messages(findings):
    return "\n".join(f.render() for f in findings)


# -- clean tree / exploration stats -----------------------------------------

def test_clean_tree_is_quiet(tmp_path):
    findings = run_project(_tree(tmp_path))
    assert findings == [], _messages(findings)


def test_exploration_stats_are_exact(tmp_path):
    stats = []
    run_project(_tree(tmp_path), only={"GM1"}, stats=stats)
    assert [s["model"] for s in stats] == ["toy.protocol"]
    # 6 reachable ledger states, 9 enabled (state, transition) firings —
    # exact because BFS with fixed transition order is deterministic.
    assert stats[0]["states"] == 6
    assert stats[0]["fired"] == 9


def test_invalid_model_is_excluded_from_exploration(tmp_path):
    # A schema-broken model must surface as GM504, never crash the BFS.
    project = _tree(tmp_path, model=_toy(
        lambda m: m["actions"][0].__setitem__("guard", "inflight +")))
    findings = run_project(project)
    assert "GM504" in _rules(findings)
    assert "does not compile" in _messages(findings)
    assert not [f for f in findings if f.rule.startswith(("GM1", "GM2"))]


# -- GM1: ledger accounting --------------------------------------------------

def test_gm101_lost_refund_reports_shortest_trace(tmp_path):
    def lose_refund(m):
        m["faults"][0]["update"] = {"inflight": "inflight - 1"}
    findings = run_project(_tree(tmp_path, model=_toy(lose_refund)),
                           only={"GM1"})
    assert _rules(findings) == ["GM101"]
    msg = findings[0].message
    assert "ledger-conserved" in msg
    assert "trace: admit -> drop" in msg  # shortest counterexample


def test_gm101_violation_carries_state(tmp_path):
    def lose_refund(m):
        m["faults"][0]["update"] = {"inflight": "inflight - 1"}
    findings = run_project(_tree(tmp_path, model=_toy(lose_refund)),
                           only={"GM1"})
    assert "charged=1" in findings[0].message
    assert "refunded=0" in findings[0].message


def test_gm1_scoped_run_excludes_other_families(tmp_path):
    def break_two(m):
        m["faults"][0]["update"] = {"inflight": "inflight - 1"}  # GM1
        m["invariants"][1]["expr"] = "inflight <= 1"             # GM2
    project = _tree(tmp_path, model=_toy(break_two))
    assert _rules(run_project(project, only={"GM1"})) == ["GM101"]
    assert _rules(run_project(project, only={"GM2"})) == ["GM201"]


# -- GM2: parcel ownership ---------------------------------------------------

def test_gm201_overcommit_violation(tmp_path):
    findings = run_project(_tree(tmp_path, model=_toy(
        lambda m: m["invariants"][1].update(
            name="parked-at-most-one", expr="inflight <= 1"))),
        only={"GM2"})
    assert _rules(findings) == ["GM201"]
    assert "trace: admit -> admit" in findings[0].message


def test_gm201_initial_state_is_checked(tmp_path):
    findings = run_project(_tree(tmp_path, model=_toy(
        lambda m: m["invariants"][1].update(expr="inflight > 0"))),
        only={"GM2"})
    assert _rules(findings) == ["GM201"]
    assert "<initial state>" in findings[0].message


def test_gm201_clean_model_quiet(tmp_path):
    assert run_project(_tree(tmp_path), only={"GM2"}) == []


# -- GM3: at-most-once adoption + fallback metrics ---------------------------

def test_gm301_double_count_violation(tmp_path):
    findings = run_project(_tree(tmp_path, model=_toy(
        lambda m: m["invariants"][2].update(expr="refunded < charged"))),
        only={"GM3"})
    assert _rules(findings) == ["GM301"]


def test_gm302_fault_edge_without_metric(tmp_path):
    findings = run_project(_tree(tmp_path, model=_toy(
        lambda m: m["faults"][0].pop("metric"))), only={"GM3"})
    assert _rules(findings) == ["GM302"]
    assert "declares no fallback metric" in findings[0].message


def test_gm3_clean_model_quiet(tmp_path):
    assert run_project(_tree(tmp_path), only={"GM3"}) == []


# -- GM4: liveness & boundedness ---------------------------------------------

def test_gm401_deadlock_reported_with_trace(tmp_path):
    findings = run_project(_tree(tmp_path, model=_toy(
        lambda m: m.update(terminal="charged == 0"))), only={"GM4"})
    assert _rules(findings) == ["GM401"]
    assert "deadlock" in findings[0].message
    assert "trace:" in findings[0].message


def test_gm402_tagged_invariant(tmp_path):
    findings = run_project(_tree(tmp_path, model=_toy(
        lambda m: m["invariants"][3].update(expr="charged < BUDGET"))),
        only={"GM4"})
    assert _rules(findings) == ["GM402"]
    assert "bounded-by-budget" in findings[0].message


def test_gm403_dead_transition(tmp_path):
    findings = run_project(_tree(tmp_path, model=_toy(
        lambda m: m["actions"].append(
            {"name": "never", "guard": "inflight > BUDGET", "update": {}}))),
        only={"GM4"})
    assert _rules(findings) == ["GM403"]
    assert "'never' is never enabled" in findings[0].message


def test_gm404_unbounded_counter_divergence(tmp_path):
    def leak(m):
        m["state"]["leak"] = 9990  # near VAR_BOUND: trips in a few steps
        m["actions"].append({"name": "leak", "guard": "leak >= 0",
                             "update": {"leak": "leak + 1"}})
    findings = run_project(_tree(tmp_path, model=_toy(leak)), only={"GM4"})
    assert _rules(findings) == ["GM404"]
    assert "'leak'" in findings[0].message
    # GM403 is deliberately skipped for a diverged model.


# -- GM5: model <-> code drift -----------------------------------------------

def test_gm501_unknown_site_and_action(tmp_path):
    findings = run_project(_tree(tmp_path, model=_toy(
        lambda m: m["faults"][0].__setitem__("site", "ghost.site"))),
        only={"GM5"})
    assert _rules(findings) == ["GM501"]
    assert "not declared in FAULT_SITES" in findings[0].message

    findings = run_project(_tree(tmp_path, model=_toy(
        lambda m: m["faults"][0].__setitem__("action", "raise"))),
        only={"GM5"})
    assert _rules(findings) == ["GM501"]
    assert "'toy.site:raise' not declared in SITE_ACTIONS" \
        in findings[0].message


def test_gm502_unknown_metric(tmp_path):
    findings = run_project(_tree(tmp_path, model=_toy(
        lambda m: m["faults"][0].__setitem__("metric", "rogue.counter"))),
        only={"GM5"})
    assert _rules(findings) == ["GM502"]
    assert "not declared in METRIC_DOCS" in findings[0].message


def test_gm503_registry_drift_both_directions(tmp_path):
    dead = REGISTRY_SRC.replace(
        '"toy.protocol": "two-slot quota ledger",',
        '"toy.protocol": "two-slot quota ledger",\n'
        '    "ghost.protocol": "model deleted, entry kept",')
    findings = run_project(_tree(tmp_path, registry=dead), only={"GM5"})
    assert _rules(findings) == ["GM503"]
    assert "dead registry entry" in findings[0].message

    findings = run_project(_tree(tmp_path, model=_toy(
        lambda m: m.update(name="toy.renamed"))), only={"GM5"})
    assert _rules(findings) == ["GM503", "GM503"]
    msgs = _messages(findings)
    assert "'toy.renamed' is not registered" in msgs
    assert "'toy.protocol' has no *_MODEL declaration" in msgs


def test_gm503_site_actions_vs_fault_sites(tmp_path):
    registry = REGISTRY_SRC.replace(
        '"toy.site": "toy send path",',
        '"toy.site": "toy send path",\n    "lonely.site": "undeclared",')
    registry = registry.replace(
        '"toy.site": "drop, corrupt",',
        '"toy.site": "drop, corrupt",\n    "extra.site": "drop",')
    findings = run_project(_tree(tmp_path, registry=registry), only={"GM5"})
    msgs = _messages(findings)
    assert _rules(findings) == ["GM503", "GM503"]
    assert "SITE_ACTIONS site 'extra.site' is not declared" in msgs
    assert "FAULT_SITES site 'lonely.site' has no SITE_ACTIONS" in msgs


def test_gm503_actions_outside_grammar(tmp_path):
    registry = REGISTRY_SRC.replace('"toy.site": "drop, corrupt",',
                                    '"toy.site": "drop, explode",')
    # The model's corrupt-free fault edge still parses; only the grammar
    # violation and the now-undeclared drill pair change, so scope to GM5.
    findings = run_project(_tree(tmp_path, registry=registry), only={"GM5"})
    assert "GM503" in _rules(findings)
    assert "['explode']" in _messages(findings)


def test_gm504_non_literal_model(tmp_path):
    src = ("def build():\n    return {}\n\n"
           "TOY_MODEL = build()")
    findings = run_project(_tree(tmp_path, model_src=src), only={"GM5"})
    assert "GM504" in _rules(findings)
    assert "not a pure literal" in _messages(findings)


def test_gm504_schema_errors(tmp_path):
    findings = run_project(
        _tree(tmp_path, model_src="TOY_MODEL = {'name': 'toy.protocol'}"),
        only={"GM5"})
    assert "GM504" in _rules(findings)
    assert "missing keys" in _messages(findings)

    findings = run_project(_tree(tmp_path, model=_toy(
        lambda m: m["actions"][0]["update"].__setitem__("ghost", "1"))),
        only={"GM5"})
    assert "updates undeclared variable 'ghost'" in _messages(findings)

    findings = run_project(_tree(tmp_path, model=_toy(
        lambda m: m["invariants"][0].update(rule="GM9"))), only={"GM5"})
    assert "rule tag must be one of" in _messages(findings)


# -- GM6: drill coverage -----------------------------------------------------

def test_gm601_undrilled_pair(tmp_path):
    drop_only = ('class _P:\n    def add(self, *a, **k):\n        pass\n\n'
                 'def test_drop():\n    _P().add("toy.site", "drop")\n')
    findings = run_project(_tree(tmp_path, tests=drop_only), only={"GM6"})
    assert _rules(findings) == ["GM601"]
    assert "'toy.site:corrupt' is never injected" in findings[0].message


def test_gm601_spec_strings_count_as_drills(tmp_path):
    spec_only = ('def test_both():\n'
                 '    assert "toy.site:drop@1, toy.site/T:corrupt@2"\n')
    assert run_project(_tree(tmp_path, tests=spec_only), only={"GM6"}) == []


def test_gm601_synthetic_sites_and_dynamic_args_ignored(tmp_path):
    # Drills of undeclared sites and non-literal plane.add args are not
    # coverage of any declared pair: both toy pairs stay undrilled.
    tests = ('class _P:\n    def add(self, *a, **k):\n        pass\n\n'
             'def test_synthetic(site):\n'
             '    assert "other.site:drop@1"\n'
             '    _P().add(site, "corrupt")\n')
    findings = run_project(_tree(tmp_path, tests=tests), only={"GM6"})
    assert _rules(findings) == ["GM601", "GM601"]


# -- GMD: README table drift -------------------------------------------------

_STALE_README = ("# toy\n\n<!-- graftmodel:models:begin -->\nstale\n"
                 "<!-- graftmodel:models:end -->\n\n"
                 "<!-- graftmodel:rules:begin -->\nstale\n"
                 "<!-- graftmodel:rules:end -->\n")


def test_gmd01_stale_tables(tmp_path):
    findings = run_project(_tree(tmp_path, readme=_STALE_README),
                           only={"GMD"})
    assert _rules(findings) == ["GMD01", "GMD01"]
    assert "is stale" in findings[0].message


def test_gmd01_missing_blocks(tmp_path):
    findings = run_project(_tree(tmp_path, readme="# toy\n"), only={"GMD"})
    assert _rules(findings) == ["GMD01", "GMD01"]
    assert "missing" in findings[0].message


def test_gmd01_write_docs_roundtrip(tmp_path):
    project = _tree(tmp_path, readme=_STALE_README)
    decls, _ = discover_models(project)
    regs = load_registries(project)
    assert len(check_docs(tmp_path, decls, regs)) == 2
    assert write_docs(tmp_path, decls, regs)
    assert check_docs(tmp_path, decls, regs) == []
    text = (tmp_path / "README.md").read_text()
    assert "`toy.protocol`" in text and "pkg/proto.py" in text
    assert "GM601" in text  # rules table rendered from RULE_DOCS


# -- suppressions ------------------------------------------------------------

_SUPP_TEMPLATE = '''\
TOY_MODEL = {
    "name": "toy.protocol",
    "doc": "two-slot quota ledger",
    "params": {"BUDGET": 2},
    "state": {"inflight": 0, "charged": 0, "refunded": 0},
    "actions": [
        {"name": "admit", "guard": "charged < BUDGET",
         "update": {"inflight": "inflight + 1", "charged": "charged + 1"}},
        {"name": "finish", "guard": "inflight > 0",
         "update": {"inflight": "inflight - 1",
                    "refunded": "refunded + 1"}},
    ],
    "faults": [
        __COMMENT__
        {"name": "drop", "site": "toy.site", "action": "drop",
         "guard": "inflight > 0",
         "update": {"inflight": "inflight - 1",
                    "refunded": "refunded + 1"}},
    ],
    "invariants": [
        {"rule": "GM1", "name": "ledger-conserved",
         "expr": "charged == inflight + refunded"},
    ],
    "terminal": "inflight == 0",
}
'''


def _supp_project(tmp_path, comment):
    return _tree(tmp_path,
                 model_src=_SUPP_TEMPLATE.replace("__COMMENT__", comment))


def test_suppression_ok_with_reason(tmp_path):
    findings = run_project(
        _supp_project(tmp_path, "# graftmodel: ok(metric lands in PR 21)"),
        only={"GM3"})
    assert findings == [], _messages(findings)


def test_suppression_without_reason_is_inert(tmp_path):
    findings = run_project(_supp_project(tmp_path, "# graftmodel: ok()"),
                           only={"GM3"})
    assert _rules(findings) == ["GM302"]


def test_suppression_rule_scoped_ignore(tmp_path):
    findings = run_project(
        _supp_project(tmp_path,
                      "# graftmodel: ignore[GM302](accepted toy debt)"),
        only={"GM3"})
    assert findings == [], _messages(findings)
    # A different rule's ignore must not absorb the GM302 finding.
    findings = run_project(
        _supp_project(tmp_path,
                      "# graftmodel: ignore[GM501](wrong rule)"),
        only={"GM3"})
    assert _rules(findings) == ["GM302"]


# -- CLI + front door + the tier-1 gate --------------------------------------

def _cli(args, cwd=ROOT):
    return subprocess.run(
        [sys.executable, "-m", "tools.graftmodel", *args],
        capture_output=True, text=True, cwd=cwd,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_cli_exit_codes(tmp_path):
    _tree(tmp_path, model=_toy(lambda m: m["faults"][0].pop("metric")))
    root = ["--root", str(tmp_path)]

    r = _cli(root)
    assert r.returncode == 1, r.stderr
    assert "GM302" in r.stdout
    assert "states," in r.stderr  # per-model exploration counts printed

    r = _cli(root + ["--baseline-write"])
    assert r.returncode == 0, r.stderr
    assert (tmp_path / "graftmodel_baseline.txt").exists()

    r = _cli(root)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "1 baselined" in r.stderr

    r = _cli(root + ["--only", "GM9"])
    assert r.returncode == 2
    assert "unknown families" in r.stderr


def test_check_front_door_scopes_across_tools():
    r = subprocess.run(
        [sys.executable, "-m", "tools.check", "--root", str(ROOT),
         "--only", "GM6,GF2"],
        capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "check: graftmodel:" in r.stderr
    assert "check: graftflow:" in r.stderr
    for skipped in ("graftlint", "graftsync", "graftcheck"):
        assert f"check: {skipped}:" not in r.stderr


def test_a_checkout_under_an_ignored_directory_is_not_a_second_model(tmp_path):
    """``_chip/parent`` holds a parent commit's copy of every model: with
    ``_chip/`` in .gitignore the walk leaves it out; without, the copy
    (here a broken one) is found."""
    def lose_refund(m):
        m["faults"][0]["update"] = {"inflight": "inflight - 1"}
    broken = "TOY_MODEL = " + repr(_toy(lose_refund)) + "\n"
    _tree(tmp_path)
    (tmp_path / "_chip" / "parent" / "pkg").mkdir(parents=True)
    (tmp_path / "_chip" / "parent" / "pkg" / "proto.py").write_text(broken)
    (tmp_path / ".gitignore").write_text("/_chip/\n")
    assert run_project(load_project(tmp_path)) == []
    (tmp_path / ".gitignore").write_text("")
    assert run_project(load_project(tmp_path)) != []


def test_repo_is_clean():
    """The tier-1 gate: the real control-plane models must check clean
    against the checked-in (empty) baseline."""
    findings = run_project(load_project(ROOT))
    new, _ = split_new(findings, graftmodel.read_baseline(ROOT))
    assert not new, "\n".join(f.render() for f in new)
