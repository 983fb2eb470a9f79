"""The arithmetic from records, counters and a recorded trace to metrics."""

import json
import math
import os

import pytest

from benchmark import kernel_bytes, metrics, trace_reduce
from benchmark.client import Record

HERE = os.path.dirname(os.path.abspath(__file__))


def rec(phase="window", asked=8, n=8, finish="length", status=200,
        t_send=0.0, t_done=1.0, error=None, lps=None):
    r = Record(phase, 0, 0, 0, 10, 0, asked, t_send)
    r.t_done, r.status, r.finish, r.n_tokens = t_done, status, finish, n
    r.logprobs = [-1.0] * n if lps is None else lps
    r.error = error
    return r


# A window of 10 s: three whole answers in bursts of 8 tokens, one answer
# cut by the window's end after 5 of 16 tokens, one request cut before its
# first token (503), one pre-roll answer that must not count, one failure.
RECORDS = [
    rec(t_send=0.0, t_done=2.0),
    rec(asked=16, n=16, t_send=1.0, t_done=5.0),
    rec(t_send=5.0, t_done=6.0),
    rec(asked=16, n=5, finish="timeout", t_send=8.0, t_done=10.2),
    rec(n=0, finish=None, status=503, t_send=9.9, t_done=10.2,
        error="request shed: deadline expired before any output was produced"),
    rec(phase="preroll", t_send=-3.0, t_done=-1.0),
    rec(n=0, finish=None, status=500, t_send=3.0, t_done=3.1, error="boom"),
]
CTX = {"records": RECORDS, "seconds": 10.0, "t_open": -0.05, "setup_s": 42.5}


@pytest.mark.parametrize("name,want", [
    # 37 tokens by the deadline; the cut is acknowledged at 10.2 s, and the
    # window opened at -0.05 s.
    ("out_tok_s", (8 + 16 + 8 + 5) / 10.25),
    ("setup_s", 42.5),
])
def test_end_to_end(name, want):
    assert metrics.END_TO_END[name](CTX) == pytest.approx(want)


def test_attempted_and_failed():
    assert metrics.attempted_failed(RECORDS) == (6, 1)


@pytest.mark.parametrize("record,failed", [
    (rec(), False),
    (rec(n=7), True),                              # fewer tokens than asked
    (rec(n=3, finish="stop"), False),              # ended on end-of-sequence
    (rec(n=0, finish="stop", lps=[]), True),
    (rec(lps=[-1.0] * 7 + [math.nan]), True),      # a logprob is not finite
    (rec(lps=[-1.0] * 7), True),                   # a logprob is missing
    (rec(n=5, asked=16, finish="timeout"), False),  # cut by the deadline
    (rec(n=0, finish=None, status=503, error="x: deadline expired y"), False),
    (rec(n=0, finish=None, status=503, error="queue full"), True),
    (rec(n=0, finish=None, status=-1, error="refused"), True),
])
def test_what_counts_as_failed(record, failed):
    assert record.failed is failed


@pytest.mark.parametrize("values,q,want", [
    ([], 50, math.nan), ([3.0], 99, 3.0), ([1.0, 2.0, 3.0, 4.0], 50, 2.5),
    ([4.0, 1.0, 3.0, 2.0], 100, 4.0), (list(range(101)), 95, 95.0),
])
def test_percentile(values, q, want):
    got = metrics.percentile(values, q)
    assert (math.isnan(got) and math.isnan(want)) or got == pytest.approx(want)


DELTA = {
    "batcher_prefix_cache_hit_tokens": 600.0,
    "batcher_prefix_cache_miss_tokens": 200.0,
    "server_ttft_seconds_sum": 12.0, "server_ttft_seconds_count": 8.0,
}


@pytest.mark.parametrize("name,want", [
    ("prefix_hit_share", 75.0), ("gw_ttft_mean", 1500.0),
    ("preemptions", 0.0),             # the counter is absent until it moves
])
def test_counter_ratio_readers(name, want):
    value, unit = metrics.read_layer_metric(name, {"counters": DELTA})
    assert value == pytest.approx(want) and unit


def test_a_reader_with_nothing_to_read_returns_nothing():
    assert metrics.read_layer_metric("prefix_hit_share", {"counters": {}}) is None
    ctx = {"trace": None, "config": {"serve": {"chunk_steps": 8}}}
    assert metrics.read_layer_metric("decode_step_ms", ctx) is None
    with pytest.raises(FileNotFoundError):
        metrics.read_layer_metric("no_such_metric", {})


@pytest.mark.parametrize("name,want", [
    ("requests_in_window", 6.0),
    ("req_lat_p50.obs", 2000.0),      # whole answers: 2 s, 4 s, 1 s
    ("req_lat_p95.obs", 3800.0),      # 2 s + 0.9 * (4 s - 2 s)
    ("norm_lat_p50.obs", 250.0), ("compiles_in_window", 2.0),
])
def test_client_and_log_readers(name, want):
    ctx = {**CTX, "compiled_in_window": ["jit_a", "jit_b"]}
    assert metrics.read_layer_metric(name, ctx)[0] == pytest.approx(want)


# -- the trace --------------------------------------------------------
@pytest.fixture(scope="module")
def events():
    with open(os.path.join(HERE, "data", "trace_excerpt.json")) as f:
        return [trace_reduce.Event(*e) for e in json.load(f)["events"]]


def test_union():
    assert trace_reduce.union([(5, 9), (0, 2), (1, 3), (9, 10), (20, 21)]) \
        == [(0, 3), (5, 10), (20, 21)]


@pytest.mark.parametrize("raw,short", [
    ("%fusion.123 = bf16[8]{0} fusion(%p)", "fusion"),
    ("jit_decode_chunk(4817263)", "jit_decode_chunk"),
    ("_quant_matmul_2d.7.3", "_quant_matmul_2d"), ("copy", "copy"),
])
def test_short_name(raw, short):
    assert trace_reduce.short_name(raw) == short


def test_trace_busy_idle_and_kernel_time(events):
    got = trace_reduce.reduce(events)
    ops = [e for e in events if e.line == trace_reduce.OPS_LINE]
    t0 = min(e.start_ns for e in events if e.plane.startswith("/device:"))
    t1 = max(e.end_ns for e in events if e.plane.startswith("/device:"))
    # Busy time by counting covered microseconds, not by merging intervals.
    covered = bytearray((t1 - t0) // 1000 + 1)
    for e in ops:
        a, b = (e.start_ns - t0) // 1000, (e.end_ns - t0) // 1000
        covered[a:b] = b"\x01" * (b - a)
    assert got["busy_s"] == pytest.approx(sum(covered) / 1e6, rel=0.02)
    assert got["window_s"] == pytest.approx((t1 - t0) / 1e9)
    assert 0.0 < 1 - got["busy_s"] / got["window_s"] < 0.5
    by_name = sum(e.dur_ns for e in ops if e.name == "_quant_matmul_2d") / 1e9
    assert got["op_s"]["_quant_matmul_2d"] == pytest.approx(by_name) and by_name > 0
    assert got["module_count"]["jit_admit_row_paged"] == 1
    assert got["module_s"]["jit_admit_row_paged"] == pytest.approx(0.084063318)
    assert got["breakdown"]["device_ops"][0][0] == "jit_admit_row_paged"
    assert len(got["breakdown"]["device_ops"]) <= 10


def test_trace_gap_attribution(events):
    got = trace_reduce.reduce(events)
    gaps = dict(got["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(got["gap_total_s"])
    # The one long gap of the excerpt falls while the batcher admits.
    longest = max(gaps, key=gaps.get)
    assert "_admit_pending" in longest and gaps[longest] > 0.0005


def test_gap_is_named_by_the_innermost_host_event():
    E = trace_reduce.Event
    ev = [
        E("/device:TPU:0", "XLA Ops", "a", 0, 100),
        E("/device:TPU:0", "XLA Ops", "b", 1100, 100),
        E("/host:CPU", "python3", "outer", 0, 5000),
        E("/host:CPU", "python3", "inner", 50, 1100),
        E("/host:CPU", "python3", "elsewhere", 3000, 100),
    ]
    got = trace_reduce.reduce(ev)
    assert got["breakdown"]["idle_gaps"] == [["inner", 1e-6]]
    assert got["busy_s"] == pytest.approx(2e-7) and got["gap_count"] == 1


def test_no_device_plane_is_nothing_to_read():
    E = trace_reduce.Event
    assert trace_reduce.reduce([E("/host:CPU", "python3", "x", 0, 10)]) is None


def test_trace_readers(events):
    config = {"serve": {"chunk_steps": 8}, "num_hidden_layers": 2,
              "matmuls_per_layer": [[128, 256], [256, 128]]}
    # The excerpt was recorded before the row span carried what its
    # admission prefilled (PR 52) and ends with its program: give it the
    # span, and one operation after the program so that it lies whole.
    E = trace_reduce.Event
    prog = next(e for e in events if e.name == "jit_admit_row_paged")

    def with_span(tokens):
        return events + [
            E("/host:CPU", "python3", "batcher.admit.row",
              prog.start_ns - 400_000, prog.dur_ns + 800_000,
              {"rid": 1, "prompt_tokens": tokens, "cached_tokens": 0,
               "bucket": 512, "live_rows": 512}),
            E(prog.plane, trace_reduce.OPS_LINE, "copy", prog.end_ns + 10, 5)]

    trace = trace_reduce.reduce(with_span(500))
    assert [(a["tokens"], a["bucket"], a["program"])
            for a in trace["admissions"]] == [(500, 512, "jit_admit_row_paged")]
    ctx = {"trace": trace, "config": config, "gauges": {},
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           # (not what the readers below take their tokens from)
           "trace_counters": {"batcher_prefix_cache_miss_tokens": 9e9}}
    assert metrics.read_layer_metric("prefill_ms_per_ktok", ctx)[0] == \
        pytest.approx(84.063318 / 0.5)
    share = metrics.read_layer_metric("quant_matmul_share", ctx)[0]
    assert share == pytest.approx(
        100 * trace["op_s"]["_quant_matmul_2d"] / trace["busy_s"])
    gap = metrics.read_layer_metric("host_gap_mean", ctx)[0]
    assert gap == pytest.approx(1e6 * trace["gap_total_s"] / trace["gap_count"])
    # One admission is one pass over the (toy) weights: bound by their
    # bytes at 5 tokens, by the multiply-adds at 500.
    weights = 2 * 2 * 128 * 256
    bytes_pass = weights * (1 + 4 / 128)
    assert kernel_bytes.quant_matmul_bytes_per_pass(config) == bytes_pass
    assert kernel_bytes.quant_matmul_weights(config) == weights
    # The kernel's time is what it spent INSIDE the paired admission: here
    # all of it, the excerpt holds no other program that runs it.
    kernel_s = trace["admissions"][0]["op_s"]["_quant_matmul_2d"]
    assert kernel_s == pytest.approx(trace["op_s"]["_quant_matmul_2d"])
    roof = metrics.read_layer_metric("quant_matmul_roofline", ctx)[0]
    assert roof == pytest.approx(100 * 2 * 500 * weights / 197e12 / kernel_s)
    ctx["trace"] = trace_reduce.reduce(with_span(5))
    roof = metrics.read_layer_metric("quant_matmul_roofline", ctx)[0]
    assert roof == pytest.approx(100 * bytes_pass / 819e9 / kernel_s)
    # The excerpt as it was recorded: its one admission ends the list and
    # no span says what it held, so there is nothing to set against its
    # device time, whatever a counter says.
    ctx["trace"] = trace_reduce.reduce(events)
    assert ctx["trace"]["admissions"] == []
    assert ctx["trace"]["decode"]["count"] == 0
    assert metrics.read_layer_metric("prefill_ms_per_ktok", ctx) is None
    assert metrics.read_layer_metric("quant_matmul_roofline", ctx) is None


def test_quant_matmul_bytes_of_the_real_configurations():
    root = os.path.dirname(os.path.dirname(HERE))
    for name, gb in (("qwen2-7b-int8", 6.73), ("pythia-6.9b-int8", 6.64)):
        with open(os.path.join(root, "benchmark", "configs", name + ".json")) as f:
            config = json.load(f)
        assert kernel_bytes.quant_matmul_bytes_per_pass(config) / 1e9 == \
            pytest.approx(gb, abs=0.01)
