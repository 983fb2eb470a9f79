"""The per-layer metrics that read the engine thread's account of the
device (PR 36): the six readers on hand-made counters, nothing at a program
that lacks the counters, what their manifest entries have to say, one
admission and 6 s of a trace from the chip, and one rehearsal that reads them all."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import metrics

from test_tracing_metrics import DELTA as PARENT

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

# PARENT's window (the loop's six spans sum to 100 s: 20 admitting, 70
# blocked on the device, 10 on the rest; 100 chunks of 8 steps) as a program
# with the new spans and counters reports it: 50 admissions of 0.4 s each
# (the rounds' 20 s), 0.36 s of it blocked in the admission's one fetch;
# 90,000 prompt tokens prefilled fresh; the device starved for 5 s, 3.5 of
# them inside admission rounds and none in growth (no counter yet); 30 of
# the 50 admissions fresh rows.
DELTA = {
    **PARENT,
    "batcher_admit_row_seconds_sum": 20.0,
    "batcher_admit_wait_device_seconds_sum": 18.0,
    "batcher_prefix_cache_miss_tokens": 90000.0,
    "batcher_decode_chunks": 100.0,
    "batcher_starved_admit_seconds": 3.5,
    "batcher_starved_plan_seconds": 0.5,
    "batcher_starved_dispatch_seconds": 0.25,
    "batcher_starved_deliver_seconds": 0.75,
    "batcher_admit_self_attention": 30.0,
    "batcher_admit_row_cache_attention": 20.0,
}
CTX = {"counters": DELTA, "config": {"serve": {"chunk_steps": 8}}}
SIX = {
    "device_starved_share": (5.0, "%"),
    "starved_admit_share": (70.0, "%"),
    "admit_host_share": (10.0, "%"),
    "admit_wait_ms_per_ktok": (200.0, "ms/ktok"),
    "engine_step_ms": (100.0, "ms"),
    "admit_self_attn_share": (60.0, "%"),
}


@pytest.mark.parametrize("name", sorted(SIX))
def test_readers_on_hand_made_counters(name):
    want, unit = SIX[name]
    assert metrics.read_layer_metric(name, CTX) == (pytest.approx(want), unit)


@pytest.mark.parametrize("name", sorted(SIX))
def test_nothing_at_a_program_without_the_counters(name):
    """The parent's counters have none of the new names: the five readers
    of new spans and counters give nothing, never a 0 that stands for
    "absent", and none raises; PR 35's counters are the parent's too, so
    its share reads there (here: no admission counted yet, nothing)."""
    assert not set(PARENT) & (set(DELTA) - set(PARENT))
    ctx = {**CTX, "counters": PARENT}
    assert metrics.read_layer_metric(name, ctx) is None
    if name == "admit_self_attn_share":
        ctx["counters"] = {**PARENT, "batcher_admit_self_attention": 7.0}
        assert metrics.read_layer_metric(name, ctx) == (100.0, "%")


def test_a_span_never_starved_counts_as_zero():
    """A counter is exported once incremented: with dispatch-ahead on and
    nothing queued a window may charge plan, dispatch and deliver nothing."""
    only_admit = {k: v for k, v in DELTA.items()
                  if "starved" not in k or "admit" in k}
    ctx = {**CTX, "counters": only_admit}
    assert metrics.read_layer_metric("device_starved_share", ctx)[0] == \
        pytest.approx(3.5)
    assert metrics.read_layer_metric("starved_admit_share", ctx)[0] == \
        pytest.approx(100.0)
    no_grow = {k: v for k, v in DELTA.items() if "grow" not in k}
    assert metrics.read_layer_metric(
        "engine_step_ms", {**CTX, "counters": no_grow})[0] == \
        pytest.approx(1e3 * 79.0 / 800)


def _excerpt():
    with open(os.path.join(HERE, "data", "admission_excerpt.json")) as f:
        return json.load(f)


def test_an_admission_as_the_chip_trace_shows_it():
    """The fetch span is a host event inside its row span, and the program
    it waits for runs inside the row and ends before the fetch returns: the
    device finishes a little before the host sees it, which is why the
    starved time is a LOWER bound of the idle time."""
    by_name = {}
    for plane, line, name, start, dur in _excerpt()["events"]:
        by_name.setdefault(name, (plane, start, start + dur))
    _, row0, row1 = by_name["batcher.admit.row"]
    host, wait0, wait1 = by_name["batcher.admit.wait_device"]
    dev, prog0, prog1 = by_name["jit_admit_row_paged"]
    assert not host.startswith("/device:") and dev.startswith("/device:")
    assert row0 <= wait0 < wait1 <= row1
    assert row0 < prog0 and wait0 < prog1 < wait1
    # the turn into the decode span follows the row, span by span
    assert row1 <= by_name["batcher.loop.grow"][1] \
        < by_name["batcher.loop.plan"][1]


def test_starved_seconds_lie_under_the_traces_idle_seconds():
    """The readers on the counters of the excerpt's own 6 s: what the
    engine thread calls starved is less than what the device's modules,
    laid end to end, leave idle."""
    ex = _excerpt()
    ctx = {**CTX, "counters": ex["trace_counters"]}
    got = {n: metrics.read_layer_metric(n, ctx) for n in SIX}
    assert all(v is not None and v[1] == SIX[n][1] for n, v in got.items())
    loop = sum(v for k, v in ex["trace_counters"].items()
               if k.startswith("batcher_loop_") and k.endswith("_sum"))
    starved_s = got["device_starved_share"][0] / 100.0 * loop
    assert 0.0 < starved_s < ex["module_level"]["idle_s"]
    assert 50.0 < got["starved_admit_share"][0] < 100.0
    assert 0.0 < got["admit_host_share"][0] < 20.0


# (source, layer) of the entry each reader is written for.
ENTRY = {
    "device_starved_share": ("program_span", "scheduler and batcher"),
    "starved_admit_share": ("program_span", "scheduler and batcher"),
    "admit_host_share": ("program_span", "scheduler and batcher"),
    "admit_wait_ms_per_ktok": ("program_span", "model step"),
    "engine_step_ms": ("program_span", "model step"),
    "admit_self_attn_share": ("program_counter", "model step"),
}


def test_the_six_are_found_by_name_and_declared_only_whole():
    """The readers lie under their names, where ``run.py`` looks.  Their
    entries of ``BENCHMARK.json`` are a ``benchmark`` PR's to add: a program
    PR may only append, and ``test_kexaone_metrics`` pins ``per_layer[-7:]``.
    Whatever entry one is given reads every cell's whole window."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    for name, (source, layer) in ENTRY.items():
        assert any(os.path.exists(os.path.join(metrics.LAYER_DIR, name + ext))
                   for ext in (".py", ".json"))
        for m in per_layer:
            if m["name"] == name:
                assert m == {
                    "name": name, "unit": SIX[name][1],
                    "better": "higher" if name == "admit_self_attn_share"
                    else "lower",
                    "source": source, "layer": layer, "moves": "out_tok_s"}


def test_rehearsal_reads_the_six():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--rehearsal", "--workload", "rehearsal-docs", "--seed",
         str(2**31 + 36), "--seconds", "3", "--trace", "0"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["failed"] == 0
    assert set(SIX) <= set(last["counts"]["layer_metrics_read"])
