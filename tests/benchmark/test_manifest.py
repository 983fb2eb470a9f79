"""BENCHMARK.json against the files it names, what a later PR appends to it
by data, and one rehearsal end to end.

Every check of the manifest's structure is a function of a manifest, so that
the file and a copy with a later PR's entries appended go through the same
code (PR 41): a check that breaks when someone appends fails here first.  No
test says how many entries the manifest has: what holds for today's count is
written from ``len`` of the list, and one test appends real files to a copy of
the benchmark's tree and runs this whole directory on it (PR 43)."""

import copy
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys
from xml.etree import ElementTree

import pytest

from benchmark import metrics, run

import test_axk1_metrics
import test_kexaone_metrics
import test_moe_metrics
from declared_cell import check_declared, has_reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def exists(*parts):
    return os.path.exists(os.path.join(ROOT, *parts))


def cells_of(metric, m):
    return metric.get("workloads", [w["name"] for w in m["workloads"]])


def check_keys_and_limits(m):
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["command"] == ["python3", "benchmark/run.py"]
    assert m["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert 1 <= len(m["configs"]) <= 24 and 1 <= len(m["workloads"]) <= 24
    assert 1 <= len(m["end_to_end"]) <= 16 and 1 <= len(m["per_layer"]) <= 128
    # A full check has to fit: 2 + 14 runs a cell, 24 cells at the most.
    runs = 2 + 14 * 24
    assert runs * (m["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_keys_and_limits():
    check_keys_and_limits(manifest())
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def check_names_units_and_entries(m):
    names = []
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
        names += [c["name"], *c["reduced"]]
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        names += [w["name"], w["config"], w["traffic"]]
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert e["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= e["bound"] <= 0.1
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert p["source"] in SOURCES
        assert 1 <= len(p["layer"]) <= 200
    metrics = m["end_to_end"] + m["per_layer"]
    for x in metrics:
        assert UNIT.match(x["unit"]), x
        assert x["better"] in ("lower", "higher")
    names += [x["name"] for x in metrics]
    for n in names:
        assert NAME.match(n), n
    for group in (m["configs"], m["workloads"], metrics):
        got = [x["name"] for x in group]
        assert len(got) == len(set(got))
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert "setup_s" in [e["name"] for e in m["end_to_end"]]


def test_names_units_and_entries():
    check_names_units_and_entries(manifest())


def check_every_file_a_cell_names_exists(m, invented=()):
    """``invented``: names a test made up, which have no files to open."""
    configs = {c["name"]: c for c in m["configs"]}
    used = set()
    for w in m["workloads"]:
        c = configs[w["config"]]
        used.add(w["config"])
        assert c["file"] == f"benchmark/configs/{w['config']}.json"
        if w["name"] in invented:
            continue
        assert exists(c["file"])
        assert exists("benchmark", "traffic", w["traffic"] + ".json")
        assert exists("benchmark", "golden", w["config"] + ".json")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        assert not cfg.get("rehearsal")
    assert used == set(configs)             # every configuration has a cell
    for p in m["per_layer"]:
        assert p["name"] in invented or has_reader(p["name"]), p["name"]
    for e in m["end_to_end"]:
        assert e["name"] in metrics.END_TO_END


def test_every_file_a_cell_names_exists():
    check_every_file_a_cell_names_exists(manifest())


def test_golden_has_the_probes_the_harness_sends():
    """Each configuration's golden holds the probes the harness sends it:
    the lengths its file lists (or the four that a file which lists none is
    sent), cut as ``run_probes`` cuts them."""
    for c in manifest()["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            serve = json.load(f)["serve"]
        with open(os.path.join(ROOT, "benchmark", "golden",
                               c["name"] + ".json")) as f:
            golden = json.load(f)
        room = serve["max_len"] - run.PROBE_TOKENS - 8
        assert [p["bytes"] for p in golden["probes"]] == [
            min(n, room) for n in run.held_to(serve)["probe_bytes"]], c["name"]
        for p in golden["probes"]:
            assert len(p["logprobs"]) == run.PROBE_TOKENS and p["bytes"] >= 32


def check_moves_and_cells(m):
    cells = [w["name"] for w in m["workloads"]]
    e2e = {e["name"]: e for e in m["end_to_end"]}
    for p in m["per_layer"]:
        assert p["moves"] in e2e and p["moves"] != "setup_s"
        for cell in cells_of(p, m):
            assert cell in cells
            assert cell in cells_of(e2e[p["moves"]], m), (p["name"], cell)
    for cell in cells:
        mine = [e["name"] for e in m["end_to_end"] if cell in cells_of(e, m)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(cell in cells_of(p, m) for p in m["per_layer"])
    # The driver's rule, stated once: a cell runs on 1 chip or on 4, and
    # takes four only where what it measures exists only across chips
    # (collectives, a model or state that is sharded, a router over
    # replicas), since it costs four times the chip time in every later
    # check: at most a quarter of the cells, rounded down, and one always.
    assert all(w["chips"] in (1, 4) for w in m["workloads"])
    four = [w["name"] for w in m["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4), four


def test_moves_and_cells():
    check_moves_and_cells(manifest())


# What the accepted cells' own tests declare: (cell, (config, traffic, chips),
# its metrics in order).
DECLARED = [(t.CELL, t.DECLARED, t.NEW) for t in (
    test_moe_metrics, test_axk1_metrics, test_kexaone_metrics)]
LATER = "later-model-int8.later-mix"
BASE = {"unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernels", "moves": "out_tok_s"}


def append_cell(m, config, traffic, chips, own):
    """What one ``model_config`` PR appends to manifest ``m``: a
    configuration, one cell of it and the per-layer metric ``own`` of that
    cell alone, each at the END of its list.  Returns the cell's name."""
    cell = f"{config}.{traffic}"
    m["configs"].append({
        "name": config, "source": f"https://example.org/{config}",
        "file": f"benchmark/configs/{config}.json",
        "reduced": ["num_hidden_layers"], "why": "a layer kind no cell has"})
    m["workloads"].append({
        "name": cell, "config": config, "traffic": traffic, "chips": chips,
        "why": "what a later PR measures, on as many chips as it needs"})
    m["per_layer"].append({"name": own, **BASE, "workloads": [cell]})
    return cell


def appended(chips, earlier=0):
    """A copy of the manifest after ``earlier`` PRs that each appended a
    cell of one chip, and then a later one: its cell on ``chips`` chips, one
    per-layer metric of that cell alone and one of every cell.  Returns the
    copy and the names that were made up."""
    m = copy.deepcopy(manifest())
    invented = set()
    for i in range(earlier):
        own = f"earlier_{i}_roofline"
        invented |= {own, append_cell(m, f"earlier-model-{i}-int8",
                                      "earlier-mix", 1, own)}
    invented |= {"later_scan_roofline", "later_step_mfu",
                 append_cell(m, "later-model-int8", "later-mix", chips,
                             "later_scan_roofline")}
    m["per_layer"].append({"name": "later_step_mfu", **BASE})
    return m, invented


def on_four_chips(m, k):
    """A copy of ``m`` in which exactly ``k`` cells ask for four chips: those
    that do already come first, then the others from the top of the list."""
    m = copy.deepcopy(m)
    cells = sorted(m["workloads"], key=lambda w: w["chips"] != 4)
    for i, w in enumerate(cells):
        w["chips"] = 4 if i < k else 1
    return m


def check_all(m, invented=()):
    check_keys_and_limits(m)
    check_names_units_and_entries(m)
    check_every_file_a_cell_names_exists(m, invented)
    check_moves_and_cells(m)
    for cell, triple, new in DECLARED:
        check_declared(m, cell, triple, new)


@pytest.mark.parametrize("earlier", [0, 1, 2, 4])
@pytest.mark.parametrize("chips", [1, 4])
def test_a_later_pr_appends_by_data(chips, earlier):
    """What the next ``model_config`` PR does, done to a copy, after
    ``earlier`` PRs have done it before: every check of this benchmark's
    manifest passes with its entries appended, and the new cell's own
    declaration is found where it was put."""
    m, invented = appended(chips, earlier)
    assert len(m["workloads"]) == len(manifest()["workloads"]) + earlier + 1
    check_all(m, invented)
    # ... which needs its reader file, and is refused for nothing else.
    with pytest.raises(AssertionError, match="later_scan_roofline"):
        check_declared(m, LATER, ("later-model-int8", "later-mix", chips),
                       ["later_scan_roofline"])
    # An entry put in the MIDDLE of the list parts a cell's metrics: refused
    # here as the driver refuses it (PR 36); no other check minds.
    parted = copy.deepcopy(m)
    cell, triple, new = DECLARED[-1]
    at = [x["name"] for x in parted["per_layer"]].index(new[1])
    parted["per_layer"].insert(at, parted["per_layer"].pop())
    check_names_units_and_entries(parted)
    check_moves_and_cells(parted)
    with pytest.raises(AssertionError):
        check_declared(parted, cell, triple, new)
    # The chip rule bites, written from the count: as many four-chip cells as
    # a quarter of the cells rounded down (one always) pass, and one more is
    # refused, whatever the later cell itself asks for.
    allowed = max(1, len(m["workloads"]) // 4)
    check_moves_and_cells(on_four_chips(m, allowed))
    with pytest.raises(AssertionError):
        check_moves_and_cells(on_four_chips(m, allowed + 1))


@pytest.mark.parametrize("n, allowed", [(6, 1), (7, 1), (8, 2), (9, 2),
                                        (10, 2), (11, 2), (12, 3)])
def test_the_chip_rule_counts_a_quarter_rounded_down(n, allowed):
    """Among ``n`` cells ``allowed`` may ask for four chips and no more; none
    may ask for two.  The manifests are built to their length, so the table
    stands whatever BENCHMARK.json holds."""
    built = {
        "workloads": [{"name": f"model-{i}.mix", "chips": 1}
                      for i in range(n)],
        "end_to_end": [{"name": "out_tok_s"}, {"name": "setup_s"}],
        "per_layer": [{"name": "step_mfu", "moves": "out_tok_s"}]}
    check_moves_and_cells(on_four_chips(built, allowed))
    with pytest.raises(AssertionError):
        check_moves_and_cells(on_four_chips(built, allowed + 1))
    built["workloads"][0]["chips"] = 2
    with pytest.raises(AssertionError):
        check_moves_and_cells(built)


# The appended cell's own test module, as its PR writes it; the names in
# braces are filled in by the test below.
APPENDED_TEST = '''"""The appended cell's declaration, and that this run reads the copy."""
import json
import os

from benchmark import metrics

from declared_cell import check_declared

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = {cell!r}
DECLARED = {declared!r}   # config, traffic, chips
NEW = {new!r}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_is_declared():
    check_declared(manifest(), CELL, DECLARED, NEW)


def test_this_run_reads_the_copy():
    assert len(manifest()["workloads"]) == {cells}
    assert os.path.samefile(ROOT, {copy!r})
    assert os.path.samefile(metrics.LAYER_DIR,
                            os.path.join(ROOT, "benchmark", "layer_metrics"))
'''


# The tests of this file that an inner run of the directory leaves out: the
# two that make such a run themselves and the two that boot a server.
LEFT_OUT = ["test_a_real_cell_appended_to_a_copy_of_the_tree",
            "test_a_state_only_cell_appended_to_a_copy_of_the_tree",
            "test_rehearsal_end_to_end", "test_rehearsal_without_a_pool"]


def copy_of_the_tree(tmp_path):
    """The manifest's ``paths`` copied to a temporary tree (the program is
    not copied)."""
    tree = str(tmp_path / "tree")
    for path in manifest()["paths"]:
        shutil.copytree(os.path.join(ROOT, path), os.path.join(tree, path),
                        ignore=shutil.ignore_patterns("__pycache__"))
    return tree


def run_the_directory_on(tree, tmp_path, m, new, copies, module):
    """Write ``new`` (path: text) and ``copies`` (path: accepted file) into
    ``tree`` as NEW files and ``m`` as its manifest, the one file rewritten;
    run every test of ``tests/benchmark`` there but ``LEFT_OUT``.  All pass,
    as many as the directory has today and the cell's own ``module``."""
    assert not any(os.path.exists(os.path.join(tree, rel))
                   for rel in (*new, *copies))
    for rel, src in copies.items():
        shutil.copy(os.path.join(tree, src), os.path.join(tree, rel))
    for rel, text in {**new, "BENCHMARK.json": json.dumps(m, indent=1)}.items():
        with open(os.path.join(tree, rel), "w") as f:
            f.write(text)
    collected = subprocess.run(
        [sys.executable, "-m", "pytest", os.path.dirname(__file__),
         "--collect-only", "-q", "-o", "addopts=", "-p", "no:cacheprovider"],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    said = re.search(r"(\d+) tests collected", collected.stdout)
    assert collected.returncode == 0 and said, collected.stdout[-2000:]
    today = int(said.group(1))
    me = "tests/benchmark/test_manifest.py::"
    program = importlib.util.find_spec("distributed_llms_tpu").origin
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(PYTHONPATH=os.path.dirname(os.path.dirname(program)),
               JAX_PLATFORMS="cpu")
    report = str(tmp_path / "inner.xml")
    inner = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/benchmark", "-q",
         "-p", "no:cacheprovider", "--rootdir", tree, "--junitxml", report,
         *(arg for name in LEFT_OUT for arg in ("--deselect", me + name))],
        capture_output=True, text=True, timeout=300, env=env, cwd=tree)
    assert inner.returncode == 0, inner.stdout[-6000:] + inner.stderr[-2000:]
    cases = ElementTree.parse(report).getroot().iter("testcase")
    passed = {(c.get("classname"), c.get("name")) for c in cases if not len(c)}
    # Every test the directory has today but those left out, and the new
    # cell's own, which saw the copy's manifest: one cell more than this one.
    assert len(passed) >= today - len(LEFT_OUT) + 2, inner.stdout[-2000:]
    assert {(f"tests.benchmark.{module}", name) for name in (
        "test_the_cell_is_declared", "test_this_run_reads_the_copy")} <= passed
    return passed


def append_counted_cell(m, name, mix, own, every):
    """Append to ``m`` configuration ``name`` with a cell on traffic ``mix``,
    metric ``own`` of that cell alone and ``every`` of every cell, both
    counted by the batcher.  Returns the cell's name and the newest cell's
    configuration under the new name, for the test to write as a file."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           m["workloads"][-1]["config"] + ".json")) as f:
        cfg = json.load(f)
    cell = append_cell(m, name, mix, 1, own)
    counted = {**BASE, "source": "program_counter",
               "layer": "scheduler and batcher"}
    m["per_layer"][-1].update(counted)
    m["per_layer"].append({"name": every, **counted})
    cfg.update(name=name, source=m["configs"][-1]["source"])
    m["configs"][-1]["reduced"] = cfg["reduced"]
    return cell, cfg


def test_a_real_cell_appended_to_a_copy_of_the_tree(tmp_path):
    """What the driver's next ``model_config`` PR does, done with REAL files
    to a copy of the benchmark's tree, and every test of this directory run
    on the result: a check that cannot take an appended configuration, cell,
    reader or entry, in any file here, fails in the PR that writes it."""
    m = manifest()
    tree = copy_of_the_tree(tmp_path)
    # Entries at the END of their lists only: the newest cell's configuration
    # under another name on the same traffic, one metric of the new cell
    # alone and one of every cell, both counted by the batcher.
    donor = m["workloads"][-1]
    name = "appended-model-int8"
    cell, cfg = append_counted_cell(m, name, donor["traffic"],
                                    "appended_useful_share",
                                    "appended_row_fill")
    # New files only: the configuration, the cell's own test, and copies of
    # accepted files under the new names: the golden and two ratio readers.
    new = {
        f"benchmark/configs/{name}.json": json.dumps(cfg),
        "tests/benchmark/test_appended_metrics.py": APPENDED_TEST.format(
            cell=cell, declared=(name, donor["traffic"], 1),
            new=["appended_useful_share"], cells=len(m["workloads"]),
            copy=tree)}
    copies = {
        f"benchmark/golden/{name}.json":
            f"benchmark/golden/{donor['config']}.json",
        "benchmark/layer_metrics/appended_useful_share.json":
            "benchmark/layer_metrics/decode_useful_share.json",
        "benchmark/layer_metrics/appended_row_fill.json":
            "benchmark/layer_metrics/decode_row_fill.json"}
    run_the_directory_on(tree, tmp_path, m, new, copies,
                         "test_appended_metrics")


def test_a_state_only_cell_appended_to_a_copy_of_the_tree(tmp_path):
    """The same once more for the cell PR 49 made room for, whose rows hold
    a recurrent state and no page: a configuration with ``paged_pages`` 0, a
    ``must_dispatch`` and ``probe_bytes`` of its own (one probe past 4,096),
    its golden of those lengths, a mix of 16 callers whose rows reach 30,721
    tokens, the cell, two readers and the cell's own test, all NEW files and
    appended entries.  The checks that count pages, name ``paged_decode`` or
    count four probes pass on the copy without an edit."""
    m = manifest()
    tree = copy_of_the_tree(tmp_path)
    name, mix = "state-model-int8", "long-rows"
    cell, cfg = append_counted_cell(m, name, mix, "state_useful_share",
                                    "state_row_fill")
    probe_bytes = [32, 200, 700, 1500, 6000]
    cfg["serve"] = {
        "slots": 16, "max_len": 32768, "page_size": 64, "paged_pages": 0,
        "chunk_steps": 8, "must_dispatch": ["quant_matmul", "state_decode"],
        "probe_bytes": probe_bytes,
        "extra_argv": [a for a in cfg["serve"]["extra_argv"]
                       if a != "--prefix-cache"]}
    golden = {"device_kind": "TPU v5 lite", "tolerance": run.GOLDEN_TOL,
              "probes": [{"bytes": n, "logprobs": [-8.0] * run.PROBE_TOKENS}
                         for n in probe_bytes]}
    # Prompts 4,096-28,672 and answers 384-2,048, the longest together.
    rows = [(4096 + 24576 * i // 15, 384 + 1664 * i // 15) for i in range(16)]
    assert rows[0] == (4096, 384) and sum(rows[-1]) + 1 == 30721
    traffic_file = {
        "what": "16 callers, one long row each at a time, nothing shared",
        "clients": 16, "preroll_s": 8, "rate_rps": None,
        "sessions": [{"shared": 0, "turns": [[p, a]]} for p, a in rows]}
    new = {
        f"benchmark/configs/{name}.json": json.dumps(cfg),
        f"benchmark/golden/{name}.json": json.dumps(golden),
        f"benchmark/traffic/{mix}.json": json.dumps(traffic_file),
        "tests/benchmark/test_state_metrics.py": APPENDED_TEST.format(
            cell=cell, declared=(name, mix, 1), new=["state_useful_share"],
            cells=len(m["workloads"]), copy=tree)}
    copies = {
        "benchmark/layer_metrics/state_useful_share.json":
            "benchmark/layer_metrics/decode_useful_share.json",
        "benchmark/layer_metrics/state_row_fill.json":
            "benchmark/layer_metrics/decode_row_fill.json"}
    passed = run_the_directory_on(tree, tmp_path, m, new, copies,
                                  "test_state_metrics")
    # The checks this cell could not have passed before PR 49 saw it.
    assert {("tests.benchmark.test_traffic",
             f"test_worst_case_fits_the_pool[{cell}]"),
            ("tests.benchmark.test_held_to",
             f"test_every_configuration_says_what_it_is_held_to[{name}.json]"),
            ("tests.benchmark.test_manifest",
             "test_golden_has_the_probes_the_harness_sends"),
            ("tests.benchmark.test_traffic",
             f"test_scripts_cover_each_cycle_once[{mix}]")} <= passed


def test_benchmark_imports_no_jax():
    """The parent is the load generator; the chip belongs to the child."""
    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmark import client, metrics, traffic, trace_reduce, "
            "kernel_bytes; import benchmark.run; "
            "assert 'jax' not in sys.modules" % ROOT)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def rehearse(mix, *args):
    """One ``--rehearsal`` of ``run.py`` on traffic file ``mix``: the process
    as it ended and, where it printed one, its last line."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--rehearsal", "--workload", mix, "--seed", str(2**31 + 5),
         "--seconds", "3", "--trace", "0", *args],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    return out, json.loads(lines[-1]) if lines else None


@pytest.mark.parametrize("mix", ["rehearsal-docs"])
def test_rehearsal_end_to_end(mix):
    """benchmark/run.py on the CPU with the tiny preset: the shape of the
    last line, and that it can never pass for a device result."""
    out, last = rehearse(mix)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["correct"] is True, out.stdout[-3000:]
    assert last["attempted"] > 10 and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    assert {"prefix_hit_share", "gw_ttft_mean", "requests_in_window",
            "compiles_in_window"} <= set(last["counts"]["layer_metrics_read"])
    # It says what it held the cell to: rehearsal-tiny's file says nothing.
    assert last["held_to"] == {
        "must_dispatch": ["quant_matmul", "paged_decode"], "paged_pages": 40,
        "probe_bytes": [32, 200, 700, 1500]}
    # Without --rehearsal the name is looked up among the manifest's cells,
    # which run on the TPU or not at all: no result line.
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    bad = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", mix, "--seconds", "1"],
        capture_output=True, text=True, timeout=120, env=env, cwd=ROOT)
    assert bad.returncode != 0
    assert not any(ln.startswith("{") for ln in bad.stdout.splitlines())


def test_rehearsal_without_a_pool():
    """The whole of run.py against a server that has no pool
    (``rehearsal-contiguous``: ``paged_pages`` 0, a ``must_dispatch`` and two
    probes of its own): boot, probes, warm-up, pre-roll, window, and every
    reader gives a number or nothing, never an exception."""
    out, last = rehearse("rehearsal-chat",
                         "--rehearsal-config", "rehearsal-contiguous")
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert last["rehearsal"] is True and last["correct"] is True, \
        out.stdout[-3000:]
    assert last["attempted"] > 10 and last["failed"] == 0
    assert last["held_to"] == {
        "must_dispatch": ["quant_matmul", "ragged_decode"], "paged_pages": 0,
        "probe_bytes": [32, 90]}
    assert "--paged-pages 0 " in out.stdout
    assert "--prefix-cache" not in out.stdout
    assert "tokens from the cache" not in out.stdout     # no comparison made
    read = set(last["counts"]["layer_metrics_read"])
    assert {"preemptions", "decode_row_fill", "queue_wait_mean",
            "gw_ttft_mean", "requests_in_window"} <= read
    assert "prefix_hit_share" not in read                # nothing to read
    with open(os.path.join(ROOT, "chiprun_out", "benchmark",
                           f"rehearsal-chat-s{2**31 + 5}-t0",
                           "probes.json")) as f:
        assert [p["bytes"] for p in json.load(f)["probes"]] == [32, 90]
    # Only a file that says it is a rehearsal is rehearsed.
    bad, none = rehearse("rehearsal-chat", "--rehearsal-config",
                         manifest()["configs"][0]["name"])
    assert bad.returncode != 0 and none is None
