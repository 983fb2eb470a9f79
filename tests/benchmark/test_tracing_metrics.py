"""The per-layer metrics that read the serving path's own spans and
counters (PR 25): the ratio files on hand-made counters, the named paged
kernel on a recorded excerpt, and one rehearsal that reads them all."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import metrics, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

# A window in which 16 slots ran 100 chunks of 8 steps (12,800 legs), 14 of
# them live at the start of each span on average, and delivered 9,856
# tokens; 50 admissions waited 60 s in all; the engine thread spent 20 s
# admitting, 70 s blocked on the device and 10 s on everything else.
DELTA = {
    "batcher_decode_slot_steps": 12800.0,
    "batcher_sched_decode_tokens": 11200.0,
    "batcher_decode_committed_tokens": 9856.0,
    "batcher_queue_wait_seconds_sum": 60.0,
    "batcher_queue_wait_seconds_count": 50.0,
    "batcher_loop_admit_seconds_sum": 20.0,
    "batcher_loop_wait_device_seconds_sum": 70.0,
    "batcher_loop_plan_seconds_sum": 1.0,
    "batcher_loop_dispatch_seconds_sum": 2.0,
    "batcher_loop_deliver_seconds_sum": 6.0,
    "batcher_loop_grow_seconds_sum": 1.0,
    "runtime_compiles_total": 3.0,
}
SIX = ["queue_wait_mean", "decode_row_fill", "decode_useful_share",
       "loop_admit_share", "loop_host_share", "program_compiles"]


@pytest.mark.parametrize("name,want,unit", [
    ("queue_wait_mean", 1200.0, "ms"),
    ("decode_row_fill", 87.5, "%"),
    ("decode_useful_share", 88.0, "%"),
    ("loop_admit_share", 20.0, "%"),
    ("loop_host_share", 10.0, "%"),
    ("program_compiles", 3.0, "count"),
])
def test_span_and_counter_readers(name, want, unit):
    assert metrics.read_layer_metric(name, {"counters": DELTA}) == \
        (pytest.approx(want), unit)


def test_absent_counters_and_the_parent():
    """A histogram is exported once observed, a counter once incremented:
    a window with no growth has no grow sum and reads as 0; a program that
    has none of this (the parent commit) gives nothing, and no error."""
    no_grow = {k: v for k, v in DELTA.items() if "grow" not in k}
    assert metrics.read_layer_metric(
        "loop_host_share", {"counters": no_grow})[0] == pytest.approx(
            100 * 9.0 / 99.0)
    assert metrics.read_layer_metric(
        "program_compiles", {"counters": {}}) == (0.0, "count")
    parent = {"batcher_sched_decode_tokens": 11200.0}
    for name in ("queue_wait_mean", "decode_row_fill", "decode_useful_share",
                 "loop_admit_share", "loop_host_share"):
        assert metrics.read_layer_metric(name, {"counters": parent}) is None
    assert metrics.read_layer_metric("paged_attn_share", {"trace": None}) is None


@pytest.fixture(scope="module")
def events():
    with open(os.path.join(HERE, "data", "trace_excerpt_spans.json")) as f:
        return [trace_reduce.Event(*e) for e in json.load(f)["events"]]


def test_paged_kernel_has_its_name_in_the_trace(events):
    trace = trace_reduce.reduce(events)
    ops = [e for e in events if e.line == trace_reduce.OPS_LINE]
    paged = sum(e.dur_ns for e in ops if e.name == "paged_decode_attn") / 1e9
    assert paged > 0 and not any(e.name == "closed_call" for e in ops)
    share = metrics.read_layer_metric("paged_attn_share", {"trace": trace})
    assert share == (pytest.approx(100 * paged / trace["busy_s"]), "%")
    assert 1.0 < share[0] < 30.0
    # The same excerpt read by the reader that was there: the quantized
    # matmul keeps the name it had.
    assert metrics.read_layer_metric("quant_matmul_share", {"trace": trace})[0] > share[0]
    # Before the kernel had a name there is nothing to read.
    old = dict(trace, op_s={k: v for k, v in trace["op_s"].items()
                            if k != "paged_decode_attn"})
    assert metrics.read_layer_metric("paged_attn_share", {"trace": old}) is None


def test_engine_thread_spans_are_host_events_of_the_excerpt(events):
    host = {e.name for e in events if not e.plane.startswith("/device:")}
    assert "batcher.loop.wait_device" in host
    assert not any(n.startswith("batcher.") and "#" in n for n in host)


def test_rehearsal_reads_the_six_counter_metrics():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--rehearsal", "--workload", "rehearsal-chat", "--seed",
         str(2**31 + 25), "--seconds", "3", "--trace", "0"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["failed"] == 0
    assert set(SIX) <= set(last["counts"]["layer_metrics_read"])
    assert "paged_attn_share" not in last["counts"]["layer_metrics_read"]
