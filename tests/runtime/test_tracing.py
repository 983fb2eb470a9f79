"""Spans and counters inside the serving path (core/profiling.span).

Deterministic: the batcher's injectable clock is a fake one that the test
advances, nothing sleeps.  Pinned here: the engine-thread spans appear on
the profiler's timeline under their registered names and partition the
loop's wall time; the occupancy counters count what they say; a request's
queue wait is sampled once per admission; the finished-request ring.
"""

import glob
import os

import numpy as np
import pytest

import jax

from distributed_llms_tpu.core.observability import METRIC_DOCS, METRICS
from distributed_llms_tpu.models import model as model_lib, presets
from distributed_llms_tpu.runtime import batcher as batcher_mod
from distributed_llms_tpu.runtime.batcher import FINISHED_KEEP, ContinuousBatcher

LOOP_SPANS = ("admit", "grow", "plan", "dispatch", "wait_device", "deliver")


@pytest.fixture(scope="module")
def tiny():
    cfg = presets.get_preset("gpt2-tiny", vocab_size=512)
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def paged(tiny, **kw):
    cfg, params = tiny
    kw.setdefault("batch_slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("chunk_steps", 4)
    kw.setdefault("page_size", 16)
    kw.setdefault("paged_pages", 13)
    return ContinuousBatcher(cfg, params, **kw)


def loop_sums() -> dict[str, float]:
    return {s: METRICS.get_histogram(f"batcher.loop.{s}_seconds")[1]
            for s in LOOP_SPANS}


REQS = [([7, 1, 9], 6), ([4, 4, 4, 4, 4, 4], 13), ([100, 3, 5, 2], 3),
        ([9, 8, 7, 6, 5], 9), ([42], 8)]


def test_every_span_is_registered():
    for s in LOOP_SPANS:
        assert f"batcher.loop.{s}_seconds" in METRIC_DOCS
    for name in ("batcher.admit.row_seconds", "server.engine.idle_seconds",
                 "batcher.queue_wait_seconds", "server.pre_submit_seconds",
                 "batcher.decode.slot_steps", "batcher.decode.committed_tokens",
                 "runtime.compiles_total", "runtime.compile_seconds"):
        assert name in METRIC_DOCS


def test_spans_are_host_events_of_the_profile(tiny, tmp_path):
    """Under jax.profiler the engine-thread spans are host events named by
    their fixed strings; a span's attributes ride as metadata, not in the
    name."""
    from jax.profiler import ProfileData

    b = paged(tiny)
    b.submit([5, 6, 7], max_new_tokens=5)
    b.run()                                   # compile outside the trace
    rids = [b.submit(ids, max_new_tokens=n) for ids, n in REQS]
    with jax.profiler.trace(str(tmp_path)):
        b.run()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    names: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("batcher.", "server.")):
                    names.setdefault(ev.name, []).append(dict(ev.stats))
    assert {f"batcher.loop.{s}" for s in LOOP_SPANS} | {"batcher.admit.row"} \
        <= set(names), sorted(names)
    rows = names["batcher.admit.row"]
    assert len(rows) == len(REQS)
    assert sorted(int(r["rid"]) for r in rows) == sorted(rids)
    assert all(int(r["prompt_tokens"]) >= 1 and "bucket" in r
               and "cached_tokens" in r for r in rows)
    # fresh rows: a query is scored against its own bucket of keys
    assert all(int(r["key_slots"]) == int(r["bucket"]) for r in rows)


@pytest.mark.parametrize("overlap", [True, False])
def test_loop_spans_partition_the_loop_wall_time(tiny, monkeypatch, overlap):
    """The engine thread is serial and each piece of its work runs inside
    one batcher.loop.* span: on a clock that only moves when such a piece
    runs, the span sums add up to the loop's wall time, each piece under
    the span that names it.  With dispatch-ahead on, growth rides the
    per-chunk decision (plan); with it off, _grow_rows does it all."""
    now = [0.0]
    b = paged(tiny, clock=lambda: now[0], overlap=overlap)
    cost = {"_span_plan": 0.5, "_overlap_ok": 0.25, "_collect": 1.0,
            "_prehash_queued": 0.125, "_activate_row": 4.0,
            "_alloc_pages": 2.0, "device_get": 16.0, "decode_chunk": 32.0}
    calls = dict.fromkeys(cost, 0)

    def ticking(name, fn):
        def wrapped(*a, **k):
            calls[name] += 1
            now[0] += cost[name]
            return fn(*a, **k)
        return wrapped

    for name in ("_span_plan", "_overlap_ok", "_collect", "_prehash_queued",
                 "_activate_row", "_alloc_pages"):
        monkeypatch.setattr(b, name, ticking(name, getattr(b, name)))
    monkeypatch.setattr(batcher_mod.jax, "device_get",
                        ticking("device_get", jax.device_get))
    monkeypatch.setattr(batcher_mod, "decode_chunk",
                        ticking("decode_chunk", batcher_mod.decode_chunk))
    reqs = REQS + [([11, 12], 40)]            # crosses two page boundaries
    for ids, n in reqs:
        b.submit(ids, max_new_tokens=n)
    before = loop_sums()
    row0 = METRICS.get_histogram("batcher.admit.row_seconds")
    b.run()
    got = {s: v - before[s] for s, v in loop_sums().items()}
    assert sum(got.values()) == pytest.approx(now[0])     # nothing outside
    assert got["wait_device"] == pytest.approx(16.0 * calls["device_get"])
    assert got["dispatch"] == pytest.approx(32.0 * calls["decode_chunk"])
    assert got["deliver"] == pytest.approx(
        calls["_collect"] + 0.125 * calls["_prehash_queued"])
    planning = 0.5 * calls["_span_plan"] + 0.25 * calls["_overlap_ok"]
    assert got["admit"] + got["grow"] + got["plan"] == pytest.approx(
        4.0 * calls["_activate_row"] + 2.0 * calls["_alloc_pages"] + planning)
    assert calls["_alloc_pages"] > len(reqs)  # a row grew past its pages
    if overlap:
        assert calls["_overlap_ok"] and calls["_prehash_queued"]
    else:
        assert got["plan"] == pytest.approx(planning) and got["grow"] > 0
    # The child span: one per admission, holding that admission's work.
    row1 = METRICS.get_histogram("batcher.admit.row_seconds")
    assert row1[0] - row0[0] == len(reqs)
    assert row1[1] - row0[1] == pytest.approx(4.0 * len(reqs))


def test_occupancy_counters(tiny):
    """slot_steps counts the legs the dispatched chunks had room for,
    decode_tokens the legs of span-start live rows, committed_tokens what
    the decode chunks delivered: everything but one admission token per
    admission."""
    b = paged(tiny)
    names = ("batcher.decode.slot_steps", "batcher.sched.decode_tokens",
             "batcher.decode.committed_tokens", "batcher.admitted")
    c0 = {n: METRICS.get_counter(n) for n in names}
    delivered = []
    for ids, n in REQS:
        b.submit(ids, max_new_tokens=n)
    b.run(on_tokens=lambda rid, toks, done, lps: delivered.extend(toks))
    d = {n: METRICS.get_counter(n) - c0[n] for n in names}
    assert len(delivered) == sum(n for _, n in REQS)      # no EOS configured
    assert d["batcher.admitted"] == len(REQS)
    assert d["batcher.decode.committed_tokens"] == len(delivered) - len(REQS)
    assert d["batcher.decode.slot_steps"] == \
        b.overlap_stats["chunks"] * b.b * b.chunk_steps
    assert d["batcher.decode.committed_tokens"] \
        <= d["batcher.sched.decode_tokens"] <= d["batcher.decode.slot_steps"]


def test_queue_wait_has_one_sample_per_admission(tiny):
    """Submit to admission start on the batcher's clock; a preempted
    request's resume is a second admission and a second sample, measured
    from its requeue."""
    now = [100.0]
    b = paged(tiny, paged_pages=9, clock=lambda: now[0])
    reqs = [([7, 1, 9, 2], 44), ([4, 4, 4, 4], 44), ([9, 8, 7, 3], 44)]
    q0 = METRICS.get_histogram("batcher.queue_wait_seconds")
    rids = []
    for ids, n in reqs:
        rids.append(b.submit(ids, max_new_tokens=n))
        now[0] += 1.0                          # submitted a second apart
    b.run()
    q1 = METRICS.get_histogram("batcher.queue_wait_seconds")
    assert b.preemptions >= 1
    assert q1[0] - q0[0] == len(reqs) + b.preemptions
    # The clock stood still during the run: the three first admissions
    # waited 3, 2 and 1 s, every resume 0 s.
    assert q1[1] - q0[1] == pytest.approx(6.0)
    recs = {r["rid"]: r for r in b.finished_requests()}
    assert sorted(recs) == rids
    assert sum(r["residencies"] for r in recs.values()) == \
        len(reqs) + b.preemptions
    assert sorted(r["queue_ms"] for r in recs.values()) == [1e3, 2e3, 3e3]


def test_finished_ring_has_every_field_and_is_bounded(tiny):
    b = paged(tiny)
    rid = b.submit([7, 1, 9], max_new_tokens=6, tenant="acme",
                   pre_submit_s=0.25)
    gone = b.submit([1, 2, 3], max_new_tokens=6)
    assert b.cancel_row(gone)
    b.run()
    recs = b.finished_requests()
    assert [r["rid"] for r in recs] == [gone, rid]
    assert recs[0]["finish"] == "cancelled" and recs[0]["out_tokens"] == 0
    assert recs[1] == {
        "rid": rid, "tenant": "acme", "prompt_tokens": 3, "cached_tokens": 0,
        "out_tokens": 6, "pre_submit_ms": 250.0,
        "queue_ms": recs[1]["queue_ms"], "admit_ms": recs[1]["admit_ms"],
        "decode_ms": recs[1]["decode_ms"], "residencies": 1,
        "finish": "length",
    }
    assert min(recs[1][k] for k in ("queue_ms", "admit_ms", "decode_ms")) >= 0
    assert b.finished_requests(1) == recs[-1:] and b.finished_requests(0) == []
    for i in range(FINISHED_KEEP + 5):         # the ring forgets the oldest
        b.cancel_row(b.submit([1], max_new_tokens=1))
    assert len(b.finished_requests()) == FINISHED_KEEP
    assert b.finished_requests()[-1]["rid"] == gone + FINISHED_KEEP + 5
    assert b.finished_requests()[0]["rid"] == gone + 6


def test_finished_ring_under_concurrent_writers_and_readers(tiny):
    """The ring is the one thing this plane shares between the engine
    thread and the serving loop: appends and reads from many threads at
    once lose nothing and never see a half-built ring."""
    import sys
    import threading

    b = paged(tiny)
    req = batcher_mod._Request(0, [1, 2, 3], 4)
    n_writers, per_writer = 16, 300
    bad: list = []
    stop = threading.Event()

    def write():
        for _ in range(per_writer):
            b._note_finished(req, 4, "length")

    def read():
        while not stop.is_set():
            recs = b.finished_requests(64)
            if len(recs) > 64 or any(r["finish"] != "length" for r in recs):
                bad.append(recs)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        readers = [threading.Thread(target=read) for _ in range(2)]
        writers = [threading.Thread(target=write) for _ in range(n_writers)]
        for t in readers + writers:
            t.start()
        for t in writers:
            t.join(timeout=60)
        stop.set()
        for t in readers:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in readers + writers)
    assert not bad
    assert len(b.finished_requests()) == FINISHED_KEEP  # 4,800 appended
