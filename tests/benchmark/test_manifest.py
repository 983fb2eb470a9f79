"""BENCHMARK.json against the files it names, and one rehearsal end to end."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import metrics

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


def test_keys_and_limits():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["command"] == ["python3", "benchmark/run.py"]
    assert m["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    # A full check has to fit: 2 + 14 runs a cell, 24 cells at the most.
    runs = 2 + 14 * 24
    assert runs * (m["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_entries():
    m = manifest()
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


def test_every_file_a_cell_names_exists():
    m = manifest()
    configs = {c["name"]: c for c in m["configs"]}
    used = set()
    for w in m["workloads"]:
        c = configs[w["config"]]
        used.add(w["config"])
        assert c["file"] == f"benchmark/configs/{w['config']}.json"
        assert exists(c["file"])
        assert exists("benchmark", "traffic", w["traffic"] + ".json")
        assert exists("benchmark", "golden", w["config"] + ".json")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        assert not cfg.get("rehearsal")
    assert used == set(configs)             # every configuration has a cell
    for p in m["per_layer"]:
        assert exists("benchmark", "layer_metrics", p["name"] + ".json") or \
            exists("benchmark", "layer_metrics", p["name"] + ".py"), p["name"]
    for e in m["end_to_end"]:
        assert e["name"] in metrics.END_TO_END


def test_golden_has_the_probes_the_harness_sends():
    m = manifest()
    for c in m["configs"]:
        with open(os.path.join(ROOT, "benchmark", "golden", c["name"] + ".json")) as f:
            golden = json.load(f)
        assert len(golden["probes"]) == 4
        for p in golden["probes"]:
            assert len(p["logprobs"]) == 8 and p["bytes"] >= 32


def test_moves_and_cells():
    m = manifest()
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
    # No cell asks for four chips: nothing measured here exists only
    # across chips (PERF.md, Open questions).
    assert all(w["chips"] == 1 for w in m["workloads"])


def test_benchmark_imports_no_jax():
    """The parent is the load generator; the chip belongs to the child."""
    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmark import client, metrics, traffic, trace_reduce, "
            "kernel_bytes; import benchmark.run; "
            "assert 'jax' not in sys.modules" % ROOT)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


@pytest.mark.parametrize("mix", ["rehearsal-docs"])
def test_rehearsal_end_to_end(mix):
    """benchmark/run.py on the CPU with the tiny preset: the shape of the
    last line, and that it can never pass for a device result."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--rehearsal", "--workload", mix, "--seed", str(2**31 + 5),
         "--seconds", "3", "--trace", "0"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["correct"] is True, out.stdout[-3000:]
    assert last["attempted"] > 10 and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    assert {"prefix_hit_share", "gw_ttft_mean", "requests_in_window",
            "compiles_in_window"} <= set(last["counts"]["layer_metrics_read"])
    # Without --rehearsal the name is looked up among the manifest's cells,
    # which run on the TPU or not at all: no result line.
    bad = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", mix, "--seconds", "1"],
        capture_output=True, text=True, timeout=120, env=env, cwd=ROOT)
    assert bad.returncode != 0
    assert not any(ln.startswith("{") for ln in bad.stdout.splitlines())
