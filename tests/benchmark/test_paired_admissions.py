"""Tokens against device time (PR 52): ``trace_reduce`` pairs each
``batcher.admit.row`` span with the program it launched, on a recorded list
(``data/paired_admissions.json``: 3.56 s of a trace of
``brumby-14b-int8.long-rows``) and on what each case makes of it; the OLD
rule (a host counter's tokens against everything the trace holds) is the
control that the test would catch a return to."""

import json
import os

import pytest

from benchmark import edge_sweep, kernel_bytes, metrics, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
KERNEL = "_quant_matmul_2d"
# What the three spans of the recording say, and how long their programs ran.
TOKENS = [8049, 6444, 6181]
SECONDS = [0.456612037, 0.38472376, 0.372710818]


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "paired_admissions.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "brumby-14b-int8.json")) as f:
        return json.load(f)


def events_of(recorded, case):
    """The recorded list as ``case`` has it: started later, a stretch of
    the device's events taken out, events added, a span's attribute set, a
    span lost, the stats stripped."""
    how = recorded["cases"][case]
    events = edge_sweep.unpack(recorded)
    if "start_ns" in how:
        events = edge_sweep.cut(events, start_ns=how["start_ns"])
    if "drop_device" in how:
        a, b = how["drop_device"]
        events = [e for e in events if not (
            e.plane.startswith("/device:") and a <= e.start_ns < b)]
    events += [trace_reduce.Event(*e) for e in how.get("add", [])]
    for rid, key, value in how.get("set", []):
        events = [e._replace(stats={**e.stats, key: value})
                  if e.name == trace_reduce.ROW_SPAN and e.stats["rid"] == rid
                  else e for e in events]
    if "lose_span" in how:
        events = [e for e in events if not (
            e.name == trace_reduce.ROW_SPAN
            and e.stats["rid"] == how["lose_span"])]
    if how.get("strip_stats"):
        events = [e._replace(stats=None) for e in events]
    return events, how.get(
        "counter_tokens",
        recorded["trace_counters"]["batcher_prefix_cache_miss_tokens"])


def read(name, trace, config):
    got = metrics.read_layer_metric(name, {
        "trace": trace, "config": config, "peaks": PEAKS, "counters": {},
        # (a counter that no reader may set against device time)
        "trace_counters": {"batcher_prefix_cache_miss_tokens": 9e9,
                           "ret_admit_tokens": 9e9}})
    return None if got is None else got[0]


def paired(trace):
    return [(a["tokens"], pytest.approx(a["seconds"]))
            for a in trace["admissions"]]


def by_hand(trace, config, decode, tokens):
    """``quant_matmul_roofline`` written out: ``decode`` whole programs of
    8 steps, each step the block weights' bytes; an admission at a time the
    larger of that and its tokens' arithmetic; over the kernel's seconds
    inside those programs."""
    per_pass_s = kernel_bytes.quant_matmul_bytes_per_pass(config) / 819e9
    per_token_s = 2 * kernel_bytes.quant_matmul_weights(config) / 197e12
    least = decode * 8 * per_pass_s + sum(
        max(per_pass_s, n * per_token_s) for n in tokens)
    return 100 * least / trace_reduce.inside_s(trace, KERNEL)


def check_a(trace, config, counter_tokens, base):
    assert paired(trace) == list(zip(TOKENS, SECONDS))
    assert [(a["bucket"], a["live_rows"], a["rid"], a["program"])
            for a in trace["admissions"]] == [
        (8192, 8192, 46, "jit_admit_row"), (8192, 6656, 47, "jit_admit_row"),
        (8192, 6400, 48, "jit_admit_row")]
    # Nine decode chunks whole; the two the edges clip are counted by
    # ``module_count`` and are no part of the paired sums.
    assert trace["decode"]["count"] == 9
    assert trace["module_count"]["jit_decode_chunk"] == 11
    assert trace["row_spans"] == {"count": 3, "tokens": sum(TOKENS)}
    # The kernels' seconds inside an admission are its own.
    assert sum(a["op_s"]["retention_prefill"] for a in trace["admissions"]) \
        == pytest.approx(trace["op_s"]["retention_prefill"])
    assert trace_reduce.inside_s(trace, KERNEL) < trace["op_s"][KERNEL]
    assert read("prefill_ms_per_ktok", trace, config) == pytest.approx(
        1e6 * sum(SECONDS) / sum(TOKENS))
    roof = read("quant_matmul_roofline", trace, config)
    assert roof == pytest.approx(by_hand(trace, config, 9, TOKENS))
    assert 85 < roof < 92
    assert 35 < read("ret_admit_roofline", trace, config) < 45
    # Where no launch straddles an edge the old rule reads the same tokens.
    old = edge_sweep.old_rule(trace, counter_tokens, config, PEAKS)
    assert old["prefill_ms_per_ktok"] == pytest.approx(
        read("prefill_ms_per_ktok", trace, config))
    assert 85 < old["quant_matmul_roofline"] < 95


def check_b(trace, config, counter_tokens, base):
    # The fourth admission is in neither side: the readings are (a)'s.
    assert trace["module_count"]["jit_admit_row"] == 4
    assert paired(trace) == list(zip(TOKENS, SECONDS))
    for name in ("quant_matmul_roofline", "prefill_ms_per_ktok",
                 "ret_admit_roofline"):
        assert read(name, trace, config) == pytest.approx(
            read(name, base, config)), name
    # The control: the OLD formula on the same list, with the tokens the
    # host's counter had counted (all launched), reads over 100%, and its
    # time a thousand tokens a quarter under the admissions' own.
    old = edge_sweep.old_rule(trace, counter_tokens, config, PEAKS)
    assert counter_tokens == sum(TOKENS) + 7229
    assert old["quant_matmul_roofline"] > 100
    assert old["prefill_ms_per_ktok"] < 0.8 * read(
        "prefill_ms_per_ktok", trace, config)


def check_c(trace, config, counter_tokens, base):
    # The program without a span is out of both sides: the pairs are (a)'s,
    # the kernel's seconds inside it are counted nowhere.
    assert trace["module_count"]["jit_admit_row"] == 4
    assert paired(trace) == list(zip(TOKENS, SECONDS))
    assert trace["decode"]["count"] == 8
    assert read("prefill_ms_per_ktok", trace, config) == pytest.approx(
        read("prefill_ms_per_ktok", base, config))
    assert read("quant_matmul_roofline", trace, config) == pytest.approx(
        by_hand(trace, config, 8, TOKENS))
    orphan = trace["op_s"][KERNEL] - trace_reduce.inside_s(trace, KERNEL)
    assert orphan > 0.15    # (230 ms of an admission, and the clipped ends)
    # The old rule gives it device time and no tokens: it reads low.
    old = edge_sweep.old_rule(trace, counter_tokens, config, PEAKS)
    assert old["quant_matmul_roofline"] < read(
        "quant_matmul_roofline", trace, config) - 5


def check_d(trace, config, counter_tokens, base):
    # Two whole admission programs, ONE span, which names the admission
    # ahead of it: nothing says which program is whose but the order, and
    # the order starts before the trace.  Left out, not shifted by one:
    # 6,181 tokens are never set against the 0.385 s of the program ahead.
    assert trace["module_count"]["jit_admit_row"] == 2
    assert trace["row_spans"]["count"] == 1
    assert trace["admissions"] == []
    assert read("prefill_ms_per_ktok", trace, config) is None
    # The decode programs alone still read: both sides are theirs.
    assert read("quant_matmul_roofline", trace, config) == pytest.approx(
        by_hand(trace, config, trace["decode"]["count"], []))


def check_none(trace, config, counter_tokens, base):
    assert trace["admissions"] is None
    for name in ("quant_matmul_roofline", "prefill_ms_per_ktok",
                 "ret_admit_roofline"):
        assert read(name, trace, config) is None, name
    # What the other readers take is what it was.
    for key in ("busy_s", "window_s", "op_s", "op_count", "module_s",
                "module_count", "gap_count", "gap_total_s", "decode"):
        assert trace[key] == base[key], key
    assert trace["breakdown"]["device_ops"] == base["breakdown"]["device_ops"]
    assert read("quant_matmul_share", trace, config) == pytest.approx(
        read("quant_matmul_share", base, config))
    assert read("decode_step_ms", trace, config) == pytest.approx(
        read("decode_step_ms", base, config))
    assert read("ret_admit_share", trace, config) == pytest.approx(
        read("ret_admit_share", base, config))


CASES = {
    "a_three_whole": check_a,
    "b_fourth_cut_by_the_end": check_b,
    "c_launched_before_the_trace": check_c,
    "d_behind_an_orphan": check_d,
    "e_unlike_buckets": check_none,
    "f_no_stats": check_none,
    "g_a_lost_span": check_none,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_pairing(recorded, config, case):
    assert sorted(recorded["cases"]) == sorted(CASES)
    events, counter_tokens = events_of(recorded, case)
    base = trace_reduce.reduce(events_of(recorded, "a_three_whole")[0])
    CASES[case](trace_reduce.reduce(events), config, counter_tokens, base)


@pytest.mark.parametrize("late_ms", [0, 100, 300, 400, 700, 1000, 1400])
def test_the_reading_does_not_step_with_the_traces_end(recorded, config,
                                                       late_ms):
    """``edge_sweep`` on the recording: a stop up to 1.4 s earlier leaves
    the new reading within a point of the uncut one; the old rule's, with
    the tokens launched by then, passes 100% while a launch straddles the
    stop (the third admission, and the second behind it)."""
    events = edge_sweep.unpack(recorded)
    rows = edge_sweep.sweep(events, config, PEAKS, 15, 100_000_000)
    row = rows[late_ms // 100]
    assert row["cut_ms"] == late_ms
    new, old = row["new"], row["old"]
    assert abs(new["quant_matmul_roofline"]
               - rows[0]["new"]["quant_matmul_roofline"]) < 1.0
    assert new["quant_matmul_roofline"] < 100
    assert abs(new["prefill_ms_per_ktok"] / rows[0]["new"][
        "prefill_ms_per_ktok"] - 1) < 0.04
    straddles = row["launched_tokens"] > row["paired_tokens"]
    assert straddles == (late_ms in (300, 400, 700, 1000))
    if late_ms in (700, 1000):
        assert old["quant_matmul_roofline"] > 100
        assert old["prefill_ms_per_ktok"] < 0.7 * new["prefill_ms_per_ktok"]
    if not straddles:
        assert old["prefill_ms_per_ktok"] == pytest.approx(
            new["prefill_ms_per_ktok"])


def test_events_keep_their_stats_through_a_file(tmp_path, recorded):
    events = edge_sweep.unpack(recorded)
    path = str(tmp_path / "events.json.gz")
    edge_sweep.save_events(events, path)
    assert edge_sweep.load_events(path) == events
    spans = [e for e in events if e.name == trace_reduce.ROW_SPAN]
    assert [e.stats.get("fetched_rid") for e in spans] == [None, None, 47]
    programs = {e.stats["program"] for e in events
                if e.line == trace_reduce.MODULES_LINE
                and e.name == "jit_admit_row"}
    assert len(programs) == 1 and programs.pop().startswith("jit_admit_row(")
