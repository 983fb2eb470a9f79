"""The per-layer metrics that read the gap between a row's deliveries and
the distribution of first tokens (PR 57): ``bucket_quantile`` against exact
percentiles of drawn values, the five readers on hand-made counter
differences, nothing at a program that lacks the series (the parent) or in
a window without a delivery, what their manifest entries say, and one
rehearsal that reads them all."""

import bisect
import json
import os
import random
import subprocess
import sys

import pytest

from benchmark import bucket_quantile, metrics

from declared_cell import has_reader
from test_tracing_metrics import DELTA as LOOP

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

# Twenty edges a decade from 1 ms to 126 s, as a server's names would give
# them: the readers take the ladder from the names, so any ladder does.
EDGES_US = [round(1000 * 10 ** (k / 20)) for k in range(103)]
FIVE = ("row_gap_p50_ms", "row_gap_p99_ms", "row_gap_admit_share",
        "gw_ttft_p50_ms", "gw_ttft_p90_ms")


def exported(series, values_s, edges_us=EDGES_US):
    """What two scrapes differ by after ``values_s`` were observed."""
    out = {f"{series}_le_us_{e}": 0.0 for e in edges_us}
    for v in values_s:
        for e in edges_us[bisect.bisect_left(edges_us, v * 1e6):]:
            out[f"{series}_le_us_{e}"] += 1.0
    out[series + "_count"] = float(len(values_s))
    out[series + "_sum"] = float(sum(values_s))
    return out


def bucket_ms(value_s):
    """(lower, upper] edges, in ms, of the bucket that holds ``value_s``."""
    k = bisect.bisect_left(EDGES_US, value_s * 1e6)
    return EDGES_US[k - 1] / 1e3, EDGES_US[k] / 1e3


def exact(values, q):
    v = sorted(values)
    return v[min(len(v) - 1, int(q * len(v)))]


@pytest.mark.parametrize("q", [0.5, 0.9, 0.99])
@pytest.mark.parametrize("shape", ["lognormal", "two-humps", "uniform"])
def test_a_percentile_lies_within_8_percent_of_the_exact_one(shape, q):
    rng = random.Random(57)
    if shape == "lognormal":        # a chunk of 100 ms, a long tail
        values = [0.1 * rng.lognormvariate(0.0, 0.6) for _ in range(10000)]
    elif shape == "two-humps":      # chunks, and chunks behind an admission
        values = [rng.gauss(0.1, 0.004) if rng.random() < 0.7
                  else rng.gauss(0.45, 0.05) for _ in range(10000)]
    else:
        values = [rng.uniform(0.002, 3.0) for _ in range(10000)]
    got = bucket_quantile.quantile_ms(exported("s", values), "s", q)
    assert got == pytest.approx(1e3 * exact(values, q), rel=0.08)


def test_the_edges_come_from_the_names():
    coarse = [1000, 10000, 100000]
    c = exported("s", [0.004] * 10 + [0.05] * 10, coarse)
    assert bucket_quantile.window_buckets(c, "s") == [
        (1000, 0.0), (10000, 10.0), (100000, 20.0)]
    # Evenly spread inside the bucket that holds it; the first starts at 0.
    assert bucket_quantile.quantile_ms(c, "s", 0.25) == pytest.approx(5.5)
    assert bucket_quantile.quantile_ms(c, "s", 0.75) == pytest.approx(55.0)
    # Over the top edge only _count knows: the top edge, a floor.
    c = exported("s", [0.004] * 10 + [7.0] * 10, coarse)
    assert bucket_quantile.quantile_ms(c, "s", 0.99) == 100.0
    # Another series whose name this one begins is not this one.
    c = {**exported("s", [0.004] * 4, coarse),
         **exported("s_more", [0.05] * 9, coarse)}
    assert bucket_quantile.quantile_ms(c, "s", 0.5) == pytest.approx(5.5)


# A window of 50 s at 16 resident rows: 6,400 chunks' deliveries of 100 ms,
# 1,500 that followed an admission round of 400 ms (0.5 s), 100 behind two
# (0.9 s); the rounds' part of them 1,500 x 0.4 + 100 x 0.8.  500 requests
# whose first token came after 0.2 s, 60 after 2 s (a queue).
GAPS = [0.1] * 6400 + [0.5] * 1500 + [0.9] * 100
TTFT = [0.2] * 500 + [2.0] * 60
# The parent's program reports that window by sums and counts alone.
PARENT = {**LOOP, "server_ttft_seconds_sum": float(sum(TTFT)),
          "server_ttft_seconds_count": float(len(TTFT))}
DELTA = {
    **PARENT,
    **exported("batcher_row_gap_seconds", GAPS),
    **exported("server_ttft_seconds", TTFT),
    "batcher_row_gap_admit_seconds": 1500 * 0.4 + 100 * 0.8,
}
CTX = {"counters": DELTA, "config": {"serve": {"chunk_steps": 8}}}


def test_readers_on_hand_made_counters():
    got = {n: metrics.read_layer_metric(n, CTX) for n in FIVE}
    assert {n: u for n, (_, u) in got.items()} == {
        "row_gap_p50_ms": "ms", "row_gap_p99_ms": "ms",
        "row_gap_admit_share": "%", "gw_ttft_p50_ms": "ms",
        "gw_ttft_p90_ms": "ms"}
    # Every value of a hump is the same: the reading lies in its bucket.
    for name, value_s in (("row_gap_p50_ms", 0.1), ("row_gap_p99_ms", 0.9),
                          ("gw_ttft_p50_ms", 0.2), ("gw_ttft_p90_ms", 2.0)):
        below, upto = bucket_ms(value_s)
        assert below < got[name][0] <= upto, name
        assert got[name][0] == pytest.approx(1e3 * value_s, rel=0.122)
    assert got["row_gap_admit_share"][0] == pytest.approx(
        100.0 * 680.0 / sum(GAPS))
    assert got["gw_ttft_p50_ms"][0] < metrics.read_layer_metric(
        "gw_ttft_mean", CTX)[0]


@pytest.mark.parametrize("name", FIVE)
def test_nothing_at_a_program_without_the_series(name):
    """The parent exports ``server_ttft_seconds_sum`` / ``_count`` and no
    edge, and nothing of the gap: every reader gives nothing, none a 0
    that stands for "absent", none raises."""
    assert "server_ttft_seconds_count" in PARENT
    assert not [k for k in PARENT if "_le_us_" in k or "row_gap" in k]
    assert metrics.read_layer_metric(name, {**CTX, "counters": PARENT}) is None


@pytest.mark.parametrize("name", FIVE)
def test_nothing_in_a_window_without_a_delivery(name):
    """The series are there (the server observed before the window) and
    did not move: no percentile of nothing, no share of 0 s."""
    still = {**DELTA, **{k: 0.0 for k in DELTA if k not in LOOP}}
    assert set(still) == set(DELTA)
    assert metrics.read_layer_metric(name, {**CTX, "counters": still}) is None


def test_rows_that_met_no_round_read_zero():
    """A counter is exported once incremented: a window whose deliveries
    followed no admission round has gaps and no admit counter."""
    c = {k: v for k, v in DELTA.items()
         if k != "batcher_row_gap_admit_seconds"}
    assert metrics.read_layer_metric(
        "row_gap_admit_share", {**CTX, "counters": c}) == (0.0, "%")


def test_the_five_are_declared_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    # Appended together, in this order; a later PR appends after them.
    names = [p["name"] for p in m["per_layer"]]
    at = names.index(FIVE[0])
    mine = m["per_layer"][at:at + 5]
    assert [p["name"] for p in mine] == list(FIVE)
    layers = {p["name"]: (p["unit"], p["better"], p["source"], p["layer"])
              for p in mine}
    gap = ("program_span", "scheduler and batcher")
    assert layers == {
        "row_gap_p50_ms": ("ms", "lower", *gap),
        "row_gap_p99_ms": ("ms", "lower", *gap),
        "row_gap_admit_share": ("%", "lower", *gap),
        "gw_ttft_p50_ms": ("ms", "lower", "program_counter", "gateway"),
        "gw_ttft_p90_ms": ("ms", "lower", "program_counter", "gateway")}
    for p in mine:
        assert "workloads" not in p and p["moves"] == "out_tok_s"
        assert has_reader(p["name"])
    # The arithmetic is no metric: --rehearsal takes every file of
    # layer_metrics/ for one.
    assert not has_reader("bucket_quantile")
    assert os.path.exists(os.path.join(ROOT, "benchmark", "bucket_quantile.py"))


def test_rehearsal_reads_the_five():
    """... and its window's counters hold what the readers' arithmetic
    rests on: every gap under the top edge, the tokens the deliveries
    carried equal to the tokens the decode chunks committed."""
    seed = 2**31 + 57
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--rehearsal", "--workload", "rehearsal-chat", "--seed", str(seed),
         "--seconds", "3", "--trace", "0"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True
    assert set(FIVE) <= set(last["counts"]["layer_metrics_read"])
    with open(os.path.join(ROOT, "chiprun_out", "benchmark",
                           f"rehearsal-chat-s{seed}-t0",
                           "summary.json")) as f:
        c = json.load(f)["counters"]
    # Every delivery of a chunk's carries at least one of its tokens.
    assert 0 < c["batcher_row_gap_seconds_count"] \
        <= c["batcher_decode_committed_tokens"]
    buckets = bucket_quantile.window_buckets(c, "batcher_row_gap_seconds")
    assert len(buckets) >= 50 and buckets[0][0] == 1000
    assert buckets[-1][1] == c["batcher_row_gap_seconds_count"] > 0
    assert [n for _, n in buckets] == sorted(n for _, n in buckets)
    assert 0.0 <= c["batcher_row_gap_admit_seconds"] \
        <= c["batcher_row_gap_seconds_sum"]
