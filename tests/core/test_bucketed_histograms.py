"""Histograms whose percentiles can be differenced over a window
(core/observability.py): the three that ``BUCKETED`` names count on one
ladder of latency edges and export a label-free cumulative series an edge;
every other histogram exports what it always did."""

import re

import pytest

from benchmark import client
from distributed_llms_tpu.core import observability as obs

# benchmark/client.py::Gateway.metrics keeps the lines this matches.
SCRAPED = re.compile(r"([A-Za-z_:][\w:]*) (\S+)")


def scrape(m: obs.Metrics) -> dict[str, float]:
    return {g.group(1): float(g.group(2))
            for line in m.prometheus_text().splitlines()
            if (g := SCRAPED.fullmatch(line))}


def test_the_scrape_here_is_the_benchmarks_own():
    import inspect
    assert SCRAPED.pattern in inspect.getsource(client.Gateway.metrics)


def test_the_ladder():
    us, s = obs.LATENCY_EDGES_US, obs.LATENCY_EDGES_S
    assert all(isinstance(e, int) for e in us)
    assert list(us) == sorted(set(us))
    assert us[0] == 1000 and us[-1] >= 120_000_000
    assert max(b / a for a, b in zip(us, us[1:])) <= 1.26
    # (finer than that bound: a spike reads as the middle of its bucket,
    # and a median has to hold to a tenth)
    assert max(b / a for a, b in zip(us, us[1:])) <= 1.123
    assert s == tuple(e / 1e6 for e in us)


def test_only_these_three_are_bucketed():
    assert obs.BUCKETED == ("batcher.row.gap_seconds", "server.ttft_seconds",
                            "batcher.queue_wait_seconds")
    for name in obs.BUCKETED:
        assert name in obs.METRIC_DOCS
        assert name + ".le_us.*" in obs.METRIC_DOCS


@pytest.mark.parametrize("name", obs.BUCKETED)
def test_a_bucketed_histogram_exports_a_count_an_edge(name):
    m = obs.Metrics()
    values = [0.0004, 0.001, 0.00101, 0.05, 0.05, 3.0, 125.0, 500.0]
    for v in values:
        m.observe(name, v)
    got = scrape(m)
    flat = name.replace(".", "_")
    assert got[flat + "_count"] == len(values)
    assert got[flat + "_sum"] == pytest.approx(sum(values))
    series = {k: v for k, v in got.items() if "_le_us_" in k}
    assert sorted(series) == sorted(
        f"{flat}_le_us_{e}" for e in obs.LATENCY_EDGES_US)
    for e_us, e_s in zip(obs.LATENCY_EDGES_US, obs.LATENCY_EDGES_S):
        # An observation ON an edge is no longer than it.
        assert series[f"{flat}_le_us_{e_us}"] == \
            sum(v <= e_s for v in values), e_us
    # What lies over the top edge is in _count alone.
    assert series[f"{flat}_le_us_{obs.LATENCY_EDGES_US[-1]}"] == \
        len(values) - 1
    # The reservoir's quantile lines stay, for operators.
    assert f'{flat}{{quantile="0.50"}}' in m.prometheus_text()


def test_cumulative_counts_never_fall():
    m = obs.Metrics()
    name = "server.ttft_seconds"
    before = dict.fromkeys(
        (f"server_ttft_seconds_le_us_{e}" for e in obs.LATENCY_EDGES_US), 0.0)
    for k in range(1, 9000):       # past the reservoir's 4,096: it slides,
        m.observe(name, (k % 977) * 1e-3)   # the counts do not
        if k % 1500 == 0:
            now = scrape(m)
            assert all(now[s] >= before[s] for s in before)
            ordered = [now[f"server_ttft_seconds_le_us_{e}"]
                       for e in obs.LATENCY_EDGES_US]
            assert ordered == sorted(ordered)      # and rise along the ladder
            before = {s: now[s] for s in before}
    assert scrape(m)["server_ttft_seconds_count"] == 8999
    assert before[f"server_ttft_seconds_le_us_{obs.LATENCY_EDGES_US[-1]}"] \
        == 7500


def test_any_other_histogram_exports_no_edge_series():
    m = obs.Metrics()
    m.observe("batcher.loop.admit_seconds", 0.2)
    m.observe("server.request_seconds", 0.2)
    text = m.prometheus_text()
    assert "_le_us_" not in text
    assert sorted(scrape(m)) == [
        "batcher_loop_admit_seconds_count", "batcher_loop_admit_seconds_sum",
        "server_request_seconds_count", "server_request_seconds_sum"]


def test_observe_many_is_observe_in_one_acquisition():
    one, many = obs.Metrics(), obs.Metrics()
    values = [0.003 * k for k in range(64)]
    for v in values:
        one.observe("batcher.row.gap_seconds", v)
    acquired = []

    class Counting:
        def __init__(self, lock):
            self.lock = lock

        def __enter__(self):
            acquired.append(1)
            return self.lock.__enter__()

        def __exit__(self, *exc):
            return self.lock.__exit__(*exc)

    many._lock = Counting(many._lock)
    many.observe_many("batcher.row.gap_seconds", values)
    assert len(acquired) == 1
    many.observe_many("t_test.plain_seconds", [1.0, 2.0])
    assert many.get_histogram("t_test.plain_seconds") == (2, 3.0)
    many._lock = many._lock.lock
    got = scrape(many)
    assert {k: v for k, v in got.items() if "t_test" not in k} == scrape(one)
