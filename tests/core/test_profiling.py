"""Profiling subsystem (SURVEY §5.1: absent in the reference)."""

import os

from distributed_llms_tpu.core import profiling
from distributed_llms_tpu.core.observability import METRICS


def test_span_observes_the_fake_clock_interval_and_nests():
    # Deterministic: a fake clock advances by hand instead of sleeping
    # wall-clock time (graftlint GL501 — fast tests don't sleep), so each
    # histogram has an EXACT expected sum.
    fake = {"now": 0.0}

    def clock() -> float:
        return fake["now"]

    for _ in range(3):
        with profiling.span("t_test.outer", clock=clock, rid=7):
            fake["now"] += 0.01
            with profiling.span("t_test.inner", clock=clock):
                fake["now"] += 0.25
            fake["now"] += 0.5
    count, total = METRICS.get_histogram("t_test.outer_seconds")
    assert count == 3 and abs(total - 3 * 0.76) < 1e-9
    count, total = METRICS.get_histogram("t_test.inner_seconds")
    assert count == 3 and abs(total - 3 * 0.25) < 1e-9
    assert METRICS.get_histogram("t_test.never_seconds") == (0, 0.0)
    snap = METRICS.snapshot()["histograms"]["t_test.outer_seconds"]
    assert abs(snap["p50"] - 0.76) < 1e-9


def test_span_observes_when_its_body_raises():
    fake = {"now": 0.0}
    try:
        with profiling.span("t_test.raises", clock=lambda: fake["now"]):
            fake["now"] += 2.0
            raise KeyError("x")
    except KeyError:
        pass
    assert METRICS.get_histogram("t_test.raises_seconds") == (1, 2.0)


def test_trace_writes_capture(tmp_path):
    import jax
    import jax.numpy as jnp

    out = str(tmp_path / "trace")
    with profiling.trace(out):
        with profiling.annotate("matmul-region"):
            x = jnp.ones((8, 8))
            jax.block_until_ready(x @ x)
    found = []
    for root, _, files in os.walk(out):
        found.extend(files)
    assert found, "profiler trace produced no files"


def test_record_memory_stats_returns_dict():
    stats = profiling.record_memory_stats(prefix="testdev")
    # CPU backends may expose no memory_stats; either way we get a dict and
    # any reported values land in the gauges.
    assert isinstance(stats, dict)
    snap = METRICS.snapshot()
    for name in stats:
        assert name in snap["gauges"]


def test_engine_generate_feeds_the_span():
    from distributed_llms_tpu.core.config import RuntimeConfig
    from distributed_llms_tpu.runtime.engine import InferenceEngine

    eng = InferenceEngine.from_preset(
        "gpt2-tiny", rt=RuntimeConfig(max_decode_steps=4, max_seq_len=64),
        vocab_size=512,  # byte tokenizer needs 256 + specials
    )
    count0, sum0 = METRICS.get_histogram("engine.generate_seconds")
    res = eng.generate_text(["ab"], max_new_tokens=4)
    assert res.generated_tokens > 0 and res.tokens_per_second > 0
    count1, sum1 = METRICS.get_histogram("engine.generate_seconds")
    assert count1 == count0 + 1 and sum1 > sum0
    # One histogram a call, from the span alone: the seconds the result
    # reports and the span's bracket the same work.
    assert sum1 - sum0 <= res.seconds + 0.5


def test_compiles_are_counted_from_inside_the_process():
    import jax
    import jax.numpy as jnp

    profiling.count_compiles()
    profiling.count_compiles()                 # registers once
    fn = jax.jit(lambda x: x * 3 + 1)
    x4, x5 = jnp.ones((4,)), jnp.ones((5,))   # their own compiles: before
    c0 = METRICS.get_counter("runtime.compiles_total")
    fn(x4)
    c1 = METRICS.get_counter("runtime.compiles_total")
    s1 = METRICS.get_counter("runtime.compile_seconds")
    fn(x4)
    c2 = METRICS.get_counter("runtime.compiles_total")
    fn(x5)
    c3 = METRICS.get_counter("runtime.compiles_total")
    assert (c1 - c0, c2 - c1, c3 - c2) == (1, 0, 1)
    assert s1 > 0
