"""Spans and counters inside the serving path (core/profiling.span).

Deterministic: the batcher's injectable clock is a fake one that the test
advances, nothing sleeps.  Pinned here: the engine-thread spans appear on
the profiler's timeline under their registered names and partition the
loop's wall time; every blocking fetch of the engine thread sits under a
``*.wait_device`` span; the time no program was in flight is charged to the
loop span it fell in; the occupancy counters count what they say; a
request's queue wait is sampled once per admission; the finished-request
ring.
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
HOST_SPANS = ("admit", "grow", "plan", "dispatch", "deliver")


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


def starved() -> dict[str, float]:
    return {s: METRICS.get_counter(f"batcher.starved.{s}_seconds")
            for s in HOST_SPANS}


def ticking_batcher(tiny, monkeypatch, cost, on_tick=None, **kw):
    """A paged batcher on a clock that moves only when one of the named
    pieces runs: methods of the batcher, ``device_get``, or a jitted
    program of the batcher's module.  -> (batcher, now, calls)."""
    now = [0.0]
    b = paged(tiny, clock=lambda: now[0], **kw)
    calls = dict.fromkeys(cost, 0)

    def ticking(name, fn):
        def wrapped(*a, **k):
            calls[name] += 1
            now[0] += cost[name]
            if on_tick is not None:
                on_tick(name)
            return fn(*a, **k)
        return wrapped

    for name in cost:
        if name == "device_get":
            monkeypatch.setattr(batcher_mod.jax, "device_get",
                                ticking(name, jax.device_get))
        elif hasattr(b, name):
            monkeypatch.setattr(b, name, ticking(name, getattr(b, name)))
        else:
            monkeypatch.setattr(batcher_mod, name,
                                ticking(name, getattr(batcher_mod, name)))
    return b, now, calls


# Seconds the fake clock moves in each piece of the loop's work.
COST = {"_span_plan": 0.5, "_overlap_ok": 0.25, "_collect": 1.0,
        "_prehash_queued": 0.125, "_activate_row": 4.0,
        "_alloc_pages": 2.0, "device_get": 16.0, "decode_chunk": 32.0,
        "admit_row_paged": 64.0}
REQS = [([7, 1, 9], 6), ([4, 4, 4, 4, 4, 4], 13), ([100, 3, 5, 2], 3),
        ([9, 8, 7, 6, 5], 9), ([42], 8)]


def test_every_span_is_registered():
    for s in LOOP_SPANS:
        assert f"batcher.loop.{s}_seconds" in METRIC_DOCS
    for s in HOST_SPANS:
        assert f"batcher.starved.{s}_seconds" in METRIC_DOCS
    for name in ("batcher.admit.row_seconds", "server.engine.idle_seconds",
                 "batcher.admit.wait_device_seconds", "batcher.decode.chunks",
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
    spans: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("batcher.", "server.")):
                    names.setdefault(ev.name, []).append(dict(ev.stats))
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    assert {f"batcher.loop.{s}" for s in LOOP_SPANS} | {
        "batcher.admit.row", "batcher.admit.wait_device"} \
        <= set(names), sorted(names)
    # an admission's one fetch is a host event inside a row's: the next
    # admission's (which names it, fetched_rid), the round's last its own
    assert len(spans["batcher.admit.wait_device"]) == len(REQS)
    for w0, w1 in spans["batcher.admit.wait_device"]:
        assert any(r0 <= w0 <= w1 <= r1
                   for r0, r1 in spans["batcher.admit.row"])
    rows = names["batcher.admit.row"]
    assert len(rows) == len(REQS)
    assert sorted(int(r["rid"]) for r in rows) == sorted(rids)
    ahead = {int(r["fetched_rid"]): int(r["rid"]) for r in rows
             if "fetched_rid" in r}
    assert ahead and all(a in rids and a < b for a, b in ahead.items())
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
    cost = COST
    in_row, reserved_in_rows = [0], [0]

    def on_tick(name):
        reserved_in_rows[0] += name == "_alloc_pages" and in_row[0]

    b, now, calls = ticking_batcher(tiny, monkeypatch, cost, on_tick,
                                    overlap=overlap)
    span = b._span

    class rows_counted:
        def __init__(self, name, **attrs):
            self.inner, self.row = span(name, **attrs), \
                name == "batcher.admit.row"

        def __enter__(self):
            in_row[0] += self.row
            return self.inner.__enter__()

        def __exit__(self, *exc):
            in_row[0] -= self.row
            return self.inner.__exit__(*exc)

    monkeypatch.setattr(b, "_span", rows_counted)
    reqs = REQS + [([11, 12], 40)]            # crosses two page boundaries
    for ids, n in reqs:
        b.submit(ids, max_new_tokens=n)
    before = loop_sums()
    row0 = METRICS.get_histogram("batcher.admit.row_seconds")
    wait0 = METRICS.get_histogram("batcher.admit.wait_device_seconds")
    b.run()
    got = {s: v - before[s] for s, v in loop_sums().items()}
    assert sum(got.values()) == pytest.approx(now[0])     # nothing outside
    # an admission's fetch counts under admit, a chunk's under wait_device
    assert calls["admit_row_paged"] == len(reqs)
    assert got["wait_device"] == pytest.approx(
        16.0 * (calls["device_get"] - len(reqs)))
    assert got["dispatch"] == pytest.approx(32.0 * calls["decode_chunk"])
    assert got["deliver"] == pytest.approx(
        calls["_collect"] + 0.125 * calls["_prehash_queued"])
    planning = 0.5 * calls["_span_plan"] + 0.25 * calls["_overlap_ok"]
    assert got["admit"] + got["grow"] + got["plan"] == pytest.approx(
        4.0 * calls["_activate_row"] + 2.0 * calls["_alloc_pages"] + planning
        + (64.0 + 16.0) * len(reqs))
    assert calls["_alloc_pages"] > len(reqs)  # a row grew past its pages
    if overlap:
        assert calls["_overlap_ok"] and calls["_prehash_queued"]
    else:
        assert got["plan"] == pytest.approx(planning) and got["grow"] > 0
    # The child spans: one row and one fetch per admission; the rows hold
    # every admission's program, fetch and activation (pipelined: the fetch
    # and the activation of the admission before) and the pages of the
    # admissions selected inside them, all but each round's first; the
    # fetch its wait.
    row1 = METRICS.get_histogram("batcher.admit.row_seconds")
    wait1 = METRICS.get_histogram("batcher.admit.wait_device_seconds")
    assert row1[0] - row0[0] == wait1[0] - wait0[0] == len(reqs)
    assert wait1[1] - wait0[1] == pytest.approx(16.0 * len(reqs))
    assert 0 < reserved_in_rows[0] < len(reqs)
    assert row1[1] - row0[1] == pytest.approx(
        (64.0 + 16.0 + 4.0) * len(reqs) + 2.0 * reserved_in_rows[0])


@pytest.mark.parametrize("overlap", [True, False])
def test_starved_time_is_charged_to_the_span_it_fell_in(tiny, monkeypatch,
                                                        overlap):
    """The device is starved from a blocking fetch that returned the
    newest program's output to the next call that dispatches one.  A
    shadow of that rule kept by the test (programs and fetches in order:
    the device runs them so) says, for every piece of host work that
    ticks the clock, whether it ran starved and in which loop span: each
    batcher.starved.* counter equals its sum, and together they never
    exceed the five host spans."""
    cost = COST
    programs = ("decode_chunk", "admit_row_paged")
    shadow = {"dispatched": 0, "fetched": 0, "seen": False, "at": None}
    want = dict.fromkeys(HOST_SPANS, 0.0)

    def on_tick(name):
        if name in programs:            # the call that dispatches: the
            shadow["dispatched"] += 1   # program's own time is not starved
        elif name == "device_get":      # blocked: something is in flight
            assert shadow["dispatched"] > shadow["fetched"]
            shadow["fetched"] += 1
            shadow["seen"] = True
        elif shadow["seen"] and shadow["dispatched"] == shadow["fetched"]:
            want[shadow["at"]] += cost[name]

    b, now, calls = ticking_batcher(tiny, monkeypatch, cost, on_tick,
                                    overlap=overlap)
    span = b._span

    def spying(name, **attrs):
        if name.startswith("batcher.loop."):
            shadow["at"] = name.rsplit(".", 1)[1]
        return span(name, **attrs)

    monkeypatch.setattr(b, "_span", spying)
    for ids, n in REQS + [([11, 12], 40)]:
        b.submit(ids, max_new_tokens=n)
    s0, l0 = starved(), loop_sums()
    b.run()
    got = {k: v - s0[k] for k, v in starved().items()}
    loop = {k: v - l0[k] for k, v in loop_sums().items()}
    assert got == pytest.approx(want)
    assert all(got[k] <= loop[k] + 1e-9 for k in HOST_SPANS)
    assert sum(got.values()) <= sum(loop[k] for k in HOST_SPANS) + 1e-9
    # an admission's activation and the turn into the span are starved
    # (pipelined: the activation of a round's LAST admission, the others
    # run behind a launch: tests/runtime/test_admit_pipeline.py) ...
    if overlap:
        assert 4.0 <= got["admit"] < 4.0 * calls["_activate_row"]
    else:
        assert got["admit"] >= 4.0 * (calls["_activate_row"] - 1)
    assert got["plan"] > 0 and got["deliver"] > 0
    # ... a program's own dispatch call never is
    assert got["dispatch"] == 0.0
    if not overlap:                     # every delivery follows a sync
        assert got["deliver"] == pytest.approx(1.0 * calls["_collect"])


def test_a_steady_span_dispatched_ahead_starves_nothing(tiny, monkeypatch):
    """One request decoding alone with dispatch-ahead on: chunk N+1 is in
    flight while chunk N is fetched and delivered, so the span charges
    plan, dispatch and deliver nothing but the turn into it (the first
    plan, after the admission's fetch) and the last delivery (after the
    sync that ends it)."""
    cost = {"_span_plan": 0.5, "_overlap_ok": 0.25, "_collect": 1.0,
            "_prehash_queued": 0.125, "_activate_row": 4.0,
            "device_get": 16.0, "decode_chunk": 32.0}
    b, now, calls = ticking_batcher(tiny, monkeypatch, cost, paged_pages=None,
                                    overlap=True)
    b.submit([7, 1, 9], max_new_tokens=33)
    s0 = starved()
    chunks0 = METRICS.get_counter("batcher.decode.chunks")
    b.run()
    got = {k: v - s0[k] for k, v in starved().items()}
    assert METRICS.get_counter("batcher.decode.chunks") - chunks0 == 8
    assert calls["_overlap_ok"] == 8 and calls["_collect"] == 8
    assert got == pytest.approx({"admit": 4.0, "grow": 0.0, "plan": 0.5,
                                 "dispatch": 0.0, "deliver": 1.0})


def _host_reads_outside_wait_device(b, monkeypatch):
    """Patch every way a device array's value reaches the host (they all
    read ``ArrayImpl._value``: ``int()``, ``float()``, ``np.asarray``,
    ``jax.device_get``) to note a read made while no ``*.wait_device`` span
    is open, and an admission's fetch span opened outside every
    ``batcher.admit.row``.  This backend enforces no device-to-host
    transfer guard."""
    from jax._src.array import ArrayImpl

    waiting, in_row, outside = [0], [0], []
    span, value = b._span, ArrayImpl.__dict__["_value"]

    class watched:
        def __init__(self, name, **attrs):
            self.inner, self.name = span(name, **attrs), name
            self.wait = name.endswith(".wait_device")
            self.row = name == "batcher.admit.row"

        def __enter__(self):
            if self.name == "batcher.admit.wait_device" and not in_row[0]:
                outside.append("an admission's fetch outside a row span")
            waiting[0] += self.wait
            in_row[0] += self.row
            return self.inner.__enter__()

        def __exit__(self, *exc):
            waiting[0] -= self.wait
            in_row[0] -= self.row
            return self.inner.__exit__(*exc)

    def read(arr):
        if not waiting[0]:
            import traceback
            outside.append("".join(traceback.format_stack(limit=6)))
        return value.fget(arr)

    monkeypatch.setattr(b, "_span", watched)
    monkeypatch.setattr(ArrayImpl, "_value", property(read))
    return outside


@pytest.mark.parametrize("kind", ["fresh", "prefix-hit", "experts",
                                  "retention", "state-space"])
def test_no_blocking_fetch_outside_a_wait_device_span(tiny, monkeypatch, kind):
    """On the paths the benchmark's cells run (admit_row_paged,
    admit_row_auto_paged behind cached pages, decode_chunk, and an expert
    model's counts) the engine thread reads a device value only under a
    ``*.wait_device`` span: an admission's ONE explicit fetch, a chunk's;
    and every admission's fetch lies inside a ``batcher.admit.row``, the
    next admission's or its own."""
    if kind == "experts":
        cfg = presets.get_preset("lfm2-tiny")
        b = ContinuousBatcher(cfg, model_lib.init_params(jax.random.key(0), cfg),
                              batch_slots=3, max_len=64, chunk_steps=4,
                              paged_pages=24, page_size=8)
    elif kind == "state-space":  # (a state beside the pool: the paged
        # programs, whose counts of the scan ride out behind the experts')
        cfg = presets.get_preset("nemotron3-super-tiny")
        b = ContinuousBatcher(cfg, model_lib.init_params(jax.random.key(0), cfg),
                              batch_slots=3, max_len=64, chunk_steps=4,
                              paged_pages=24, page_size=8)
    elif kind == "retention":  # (no pool: admit_row and the contiguous
        # decode_chunk, whose counts of the state's work ride out too)
        cfg = presets.get_preset("brumby-tiny")
        b = ContinuousBatcher(cfg, model_lib.init_params(jax.random.key(0), cfg),
                              batch_slots=3, max_len=64, chunk_steps=4)
    else:
        b = paged(tiny, prefix_cache=kind == "prefix-hit")
    ret0 = METRICS.get_counter("ret.decode.row_steps")
    ssm0 = METRICS.get_counter("ssm.decode.row_steps")
    doc = list(range(40, 75))                 # two full pages and a bit
    if kind == "prefix-hit":
        b.submit(doc + [3], max_new_tokens=2)
        b.run()                               # the pages are cached now
    hits0 = METRICS.get_counter("batcher.prefix_cache.hit_tokens")
    moe0 = METRICS.get_counter("moe.layer_passes")
    rids = [b.submit(doc + [5, 6], max_new_tokens=9),
            b.submit([7, 1, 9], max_new_tokens=6)]
    outside = _host_reads_outside_wait_device(b, monkeypatch)
    out = b.run()
    assert not outside, outside[0]
    assert [len(out[r]) for r in rids] == [9, 6]
    if kind == "prefix-hit":
        assert METRICS.get_counter("batcher.prefix_cache.hit_tokens") > hits0
    if kind == "experts":
        assert METRICS.get_counter("moe.layer_passes") > moe0
    if kind == "retention":  # 8 and 5 decode steps behind the admissions
        assert METRICS.get_counter("ret.decode.row_steps") == ret0 + 13
    if kind == "state-space":
        assert METRICS.get_counter("ssm.decode.row_steps") == ssm0 + 13
        assert METRICS.get_counter("moe.layer_passes") > moe0


def _spec_models():
    cfg = presets.get_preset("llama-tiny", vocab_size=512)
    dcfg = presets.get_preset("llama-tiny", vocab_size=512, num_layers=2)
    return (cfg, model_lib.init_params(jax.random.key(0), cfg),
            dcfg, model_lib.init_params(jax.random.key(99), dcfg))


@pytest.mark.parametrize("kind", [
    "prefix-hit", "experts",
    pytest.param("speculative", marks=pytest.mark.fragile_xla_cpu)])
def test_the_explicit_fetch_changes_no_token(tiny, monkeypatch, kind):
    """Tokens and logprobs are those of the implicit synchronisations the
    explicit fetch replaced: with ``_fetch_admission`` handing back the
    device arrays unfetched, ``int(tok)``, ``float(lp)`` and the counts'
    ``int(x)`` synchronise one by one as they did before."""
    def make():
        if kind == "experts":
            cfg = presets.get_preset("lfm2-tiny")
            return ContinuousBatcher(
                cfg, model_lib.init_params(jax.random.key(0), cfg),
                batch_slots=3, max_len=64, chunk_steps=4, paged_pages=24,
                page_size=8)
        if kind == "speculative":
            cfg, params, dcfg, dparams = _spec_models()
            return ContinuousBatcher(
                cfg, params, batch_slots=2, max_len=64, chunk_steps=4,
                paged_pages=24, page_size=16, prefix_cache=True, spec_k=3,
                draft_params=dparams, draft_cfg=dcfg)
        return paged(tiny, prefix_cache=True)

    doc = list(range(40, 75))
    jobs = [(doc + [3], 4), (doc + [5, 6], 9), ([7, 1, 9], 6),
            (doc + [8], 7)]

    def serve(b):
        first = b.submit(*jobs[0][:1], max_new_tokens=jobs[0][1])
        b.run()                               # later prompts hit its pages
        rids = [first] + [b.submit(ids, max_new_tokens=n)
                          for ids, n in jobs[1:]]
        out = b.run()
        return ([out[r] for r in rids],
                [b.result_logprobs[r] for r in rids])

    explicit = serve(make())
    implicit_b = make()
    monkeypatch.setattr(implicit_b, "_fetch_admission",
                        lambda ticket, *outs: outs)
    assert serve(implicit_b) == explicit
    assert [len(t) for t in explicit[0]] == [n for _, n in jobs]


def test_occupancy_counters(tiny):
    """slot_steps counts the legs the dispatched chunks had room for,
    decode_tokens the legs of span-start live rows, committed_tokens what
    the decode chunks delivered: everything but one admission token per
    admission."""
    b = paged(tiny)
    names = ("batcher.decode.slot_steps", "batcher.sched.decode_tokens",
             "batcher.decode.committed_tokens", "batcher.admitted",
             "batcher.decode.chunks")
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
        d["batcher.decode.chunks"] * b.b * b.chunk_steps
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
        # 6 tokens at chunk_steps 4: the first token and two chunks.
        "deliveries": 3, "max_gap_ms": recs[1]["max_gap_ms"],
        "stalled_ms": recs[1]["stalled_ms"],
    }
    assert 0 <= recs[1]["stalled_ms"] <= recs[1]["decode_ms"]
    assert recs[1]["max_gap_ms"] > 0 and recs[0]["deliveries"] == 0
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


# -- a model whose router reads the block's input (PR 45) -----------------
def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk_eqns(sub)


def test_the_router_runs_before_the_operator_and_every_scope_is_named():
    """``smallthinker-tiny``: in program order the first operation under
    ``moe_route`` (the router's logits, read from the block's input) comes
    before the first under ``full_attn`` and ``swa_attn``, the routing's
    rest and ``moe_experts`` behind them; the five scopes a trace is read
    by are in the lowered program's locations.  K-EXAONE's router, which
    reads the FFN norm's output, runs behind its operator."""
    from distributed_llms_tpu.models import model as model_lib
    from distributed_llms_tpu.models.presets import get_preset

    def first_seen(name):
        cfg = get_preset(name)
        params = jax.eval_shape(
            lambda: model_lib.init_params(jax.random.key(0), cfg))
        toks = np.zeros((1, 16), np.int32)

        def fwd(params):
            return model_lib.forward(params, cfg, toks)[0]

        order = []
        for eqn in _walk_eqns(jax.make_jaxpr(fwd)(params).jaxpr):
            stack = str(eqn.source_info.name_stack)
            for scope in ("moe_route", "full_attn", "swa_attn",
                          "moe_experts", "head"):
                if scope in stack and scope not in order:
                    order.append(scope)
        text = jax.jit(fwd).lower(params).as_text(debug_info=True)
        assert all(scope in text for scope in order)
        return order

    assert first_seen("smallthinker-tiny") == [
        "moe_route", "full_attn", "moe_experts", "swa_attn", "head"]
    assert first_seen("k-exaone-tiny")[:2] == ["swa_attn", "moe_route"]


def test_a_retention_models_scopes_in_program_order():
    """``brumby-tiny``: the projections, norms, rotation and gate under
    ``ret_qkvg``, then the operator under ``retention``, then ``mlp``, a
    layer; ``head`` last; all in the lowered program's locations, in an
    admission's program and in a decode step's."""
    from distributed_llms_tpu.models import kv_cache, model as model_lib
    from distributed_llms_tpu.models.presets import get_preset

    cfg = get_preset("brumby-tiny")
    params = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.key(0), cfg))
    cache = jax.eval_shape(lambda: kv_cache.init_cache(cfg, 2, 32))

    def admission(params):
        return model_lib.forward(params, cfg, np.zeros((1, 16), np.int32))[0]

    def step(params, cache):
        lens = np.asarray([5, 9], np.int32)
        return model_lib.forward(
            params, cfg, np.zeros((2, 1), np.int32), positions=lens[:, None],
            cache=cache, cache_index=lens,
            seq_lens=np.ones((2,), np.int32))[0]

    for fn, args in ((admission, (params,)), (step, (params, cache))):
        order = []
        for eqn in _walk_eqns(jax.make_jaxpr(fn)(*args).jaxpr):
            stack = str(eqn.source_info.name_stack)
            for scope in ("ret_qkvg", "retention", "mlp", "head"):
                if scope in stack and scope not in order:
                    order.append(scope)
        assert order == ["ret_qkvg", "retention", "mlp", "head"]
        text = jax.jit(fn).lower(*args).as_text(debug_info=True)
        assert all(scope in text for scope in order)


def test_a_retention_models_counters_are_added_at_delivery(monkeypatch):
    """``ret.*`` leave each admission and each decode chunk as outputs and
    are added on the host: a chunk's inside ``batcher.loop.deliver``, an
    admission's behind its one fetch inside ``batcher.admit.row``, whose
    span carries the ``chunks`` the scan walks; the gauge is the slots'
    state."""
    from distributed_llms_tpu.models import model as model_lib
    from distributed_llms_tpu.models.presets import get_preset

    for name in ("ret.admit.tokens", "ret.admit.chunks",
                 "ret.decode.row_steps", "ret.decode.resident_tokens",
                 "batcher.ret_state_bytes"):
        assert name in METRIC_DOCS
    cfg = get_preset("brumby-tiny")
    params = model_lib.init_params(jax.random.key(0), cfg)
    b = batcher_mod.ContinuousBatcher(
        cfg, params, batch_slots=2, max_len=128, chunk_steps=4, eos_id=-1)
    open_spans, seen, attrs = [], [], []
    span = b._span

    class watched:
        def __init__(self, name, **kw):
            self.name, self.inner = name, span(name, **kw)
            if name == "batcher.admit.row":
                attrs.append(kw)

        def __enter__(self):
            open_spans.append(self.name)
            return self.inner.__enter__()

        def __exit__(self, *exc):
            open_spans.pop()
            return self.inner.__exit__(*exc)

    inc = METRICS.inc

    def watching(name, n=1):
        if name.startswith("ret."):
            seen.append((name, n, tuple(open_spans)))
        return inc(name, n)

    monkeypatch.setattr(b, "_span", watched)
    monkeypatch.setattr(METRICS, "inc", watching)
    before = METRICS.snapshot()["counters"]
    b.submit(list(range(1, 71)), max_new_tokens=6)  # 70 tokens: 2 chunks
    b.run()
    delta = {k: METRICS.snapshot()["counters"].get(k, 0) - before.get(k, 0)
             for k in ("ret.admit.tokens", "ret.admit.chunks",
                       "ret.decode.row_steps", "ret.decode.resident_tokens")}
    assert delta == {"ret.admit.tokens": 70, "ret.admit.chunks": 2,
                     "ret.decode.row_steps": 5,
                     "ret.decode.resident_tokens": sum(range(71, 76))}
    assert [a["chunks"] for a in attrs] == [2]
    for name, n, spans in seen:
        if n and name.startswith("ret.decode"):
            assert spans[-1] == "batcher.loop.deliver", (name, spans)
        if n and name.startswith("ret.admit"):
            assert "batcher.loop.wait_device" not in spans
    assert METRICS.snapshot()["gauges"]["batcher.ret_state_bytes"] == \
        2 * 2 * 2 * 66 * 128 * 128 * 4


def test_a_state_space_models_scopes_in_program_order():
    """``nemotron3-super-tiny``: a Mamba-2 layer's projection under
    ``ssm_proj``, its convolution under ``ssm_conv``, the scan (or the
    recurrence step) under ``ssm_scan``; an expert layer's latent
    projections under ``moe_latent`` on either side of ``moe_experts``;
    ``head`` last; in an admission's program and in a decode step's against
    the pool."""
    from distributed_llms_tpu.models import kv_cache, model as model_lib
    from distributed_llms_tpu.models.presets import get_preset

    cfg = get_preset("nemotron3-super-tiny")
    params = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.key(0), cfg))
    pool = jax.eval_shape(lambda: kv_cache.make_pool(cfg, 9, 8, slots=2))

    def admission(params):
        return model_lib.forward(params, cfg, np.zeros((1, 16), np.int32))[0]

    def step(params, pool):
        lens = np.asarray([5, 9], np.int32)
        return model_lib.forward(
            params, cfg, np.zeros((2, 1), np.int32), positions=lens[:, None],
            cache=pool, cache_index=lens,
            kv_tables=jax.numpy.asarray([[1, 2], [3, 4]], "int32"),
            seq_lens=np.ones((2,), np.int32))[0]

    want = ["ssm_proj", "ssm_conv", "ssm_scan", "moe_route", "moe_latent",
            "moe_experts", "shared_expert", "head"]
    for fn, args in ((admission, (params,)), (step, (params, pool))):
        order = []
        for eqn in _walk_eqns(jax.make_jaxpr(fn)(*args).jaxpr):
            stack = str(eqn.source_info.name_stack)
            for scope in want:
                if scope in stack and scope not in order:
                    order.append(scope)
        assert order == want
        text = jax.jit(fn).lower(*args).as_text(debug_info=True)
        assert all(scope in text for scope in order)


def test_a_state_space_models_counters_are_added_at_delivery(monkeypatch):
    """``ssm.*`` leave each admission and each decode chunk as outputs
    behind the experts' counts and are added on the host: a chunk's inside
    ``batcher.loop.deliver``, an admission's behind its one fetch inside
    ``batcher.admit.row``, whose span carries the ``chunks`` the scan
    walks; the gauge is the slots' states and taps."""
    from distributed_llms_tpu.models import model as model_lib
    from distributed_llms_tpu.models.presets import get_preset

    for name in ("ssm.admit.tokens", "ssm.admit.chunks",
                 "ssm.decode.row_steps", "batcher.ssm_state_bytes"):
        assert name in METRIC_DOCS
    cfg = get_preset("nemotron3-super-tiny")
    params = model_lib.init_params(jax.random.key(0), cfg)
    b = batcher_mod.ContinuousBatcher(
        cfg, params, batch_slots=2, max_len=256, chunk_steps=4, eos_id=-1,
        paged_pages=40, page_size=16)
    open_spans, seen, attrs = [], [], []
    span = b._span

    class watched:
        def __init__(self, name, **kw):
            self.name, self.inner = name, span(name, **kw)
            if name == "batcher.admit.row":
                attrs.append(kw)

        def __enter__(self):
            open_spans.append(self.name)
            return self.inner.__enter__()

        def __exit__(self, *exc):
            open_spans.pop()
            return self.inner.__exit__(*exc)

    inc = METRICS.inc

    def watching(name, n=1):
        if name.startswith("ssm."):
            seen.append((name, n, tuple(open_spans)))
        return inc(name, n)

    monkeypatch.setattr(b, "_span", watched)
    monkeypatch.setattr(METRICS, "inc", watching)
    names = ("ssm.admit.tokens", "ssm.admit.chunks", "ssm.decode.row_steps",
             "moe.routed_pairs", "moe.held_pairs")
    before = METRICS.snapshot()["counters"]
    b.submit(list(range(1, 141)), max_new_tokens=6)  # 140 tokens: 2 chunks
    b.run()
    after = METRICS.snapshot()["counters"]
    delta = {k: after.get(k, 0) - before.get(k, 0) for k in names}
    assert delta["ssm.admit.tokens"] == 140
    assert delta["ssm.admit.chunks"] == 2
    assert delta["ssm.decode.row_steps"] == 5
    assert delta["moe.routed_pairs"] == 3 * 6 * 145
    assert 0 < delta["moe.held_pairs"] < delta["moe.routed_pairs"]
    assert [a["chunks"] for a in attrs] == [2]
    for name, n, spans in seen:
        if n and name.startswith("ssm.decode"):
            assert spans[-1] == "batcher.loop.deliver", (name, spans)
        if n and name.startswith("ssm.admit"):
            assert "batcher.loop.wait_device" not in spans
    assert METRICS.snapshot()["gauges"]["batcher.ssm_state_bytes"] == \
        2 * 3 * (16 * 64 * 128 * 4 + 3 * 1536 * 4)


def test_the_rings_counters_and_gauges_of_a_windowed_model():
    """``swa.decode.ring_tokens`` is the window times the row-steps that
    decoded (what the rings hold room for), ``swa.decode.window_tokens``
    the live part of it, both delivered with the expert counts."""
    from distributed_llms_tpu.models import model as model_lib
    from distributed_llms_tpu.models.presets import get_preset

    assert "swa.decode.ring_tokens" in METRIC_DOCS
    cfg = get_preset("smallthinker-tiny")
    params = model_lib.init_params(jax.random.key(0), cfg)
    names = ("swa.decode.ring_tokens", "swa.decode.window_tokens",
             "attn.decode.resident_tokens", "moe.routed_pairs",
             "moe.layer_passes", "moe.experts_touched",
             "moe.max_load_tokens")
    before = METRICS.snapshot()["counters"]
    b = batcher_mod.ContinuousBatcher(
        cfg, params, batch_slots=4, max_len=64, chunk_steps=4,
        paged_pages=24, page_size=8)
    b.submit([7, 1, 9, 4, 2], max_new_tokens=4)
    b.submit(list(range(1, 20)), max_new_tokens=7)
    b.run()
    snap = METRICS.snapshot()
    delta = {k: snap["counters"].get(k, 0) - before.get(k, 0) for k in names}
    real = (5 + 3) + (19 + 6)  # prompt tokens + decoded tokens fed back
    assert delta["moe.routed_pairs"] == real * 6 * 8
    # Decode steps read lengths 6, 7, 8 and 20 .. 25: nine row-steps.
    assert delta["swa.decode.ring_tokens"] == 9 * 8
    assert delta["swa.decode.window_tokens"] == 6 + 7 + 8 + 6 * 8
    assert delta["attn.decode.resident_tokens"] == 6 + 7 + 8 + sum(
        range(20, 26))
    assert 0 < delta["moe.experts_touched"] <= 16 * delta["moe.layer_passes"]
    assert delta["moe.max_load_tokens"] > 0
    assert snap["gauges"]["batcher.window_state_bytes"] == \
        2 * 6 * 4 * 8 * 2 * 16 * 4
    assert snap["gauges"]["batcher.pool_token_bytes"] == 2 * 2 * 2 * 16 * 4


@pytest.mark.parametrize("name,kernel", [
    ("k-exaone-tiny", True), ("llama-tiny", True), ("llama-tiny", False)])
def test_counters_say_how_many_pairs_the_flash_kernel_scores(
        name, kernel, monkeypatch):
    """A fresh row adds ``ops.flash.live_tiles``' two counts, summed over
    the attention layers by kind, to ``batcher.admit.attn_pairs`` and
    ``..._live`` (equal where no count reaches the kernel: the dense
    families; nothing where the admission takes the dense body), and the
    span carries ``attn_pairs_live``; a continuation behind a cached prefix
    adds nothing."""
    from distributed_llms_tpu.models.presets import get_preset
    from distributed_llms_tpu.ops.flash import live_tiles

    for name_ in ("batcher.admit.attn_pairs", "batcher.admit.attn_pairs_live"):
        assert "flash" in METRIC_DOCS[name_]
    assert "attn_pairs_live" in METRIC_DOCS["batcher.admit.row_seconds"]
    monkeypatch.setenv("DLT_QUANT_MATMUL", "fallback")
    monkeypatch.setenv("DLT_RAGGED_DECODE",
                       "interpret" if kernel else "fallback")
    hybrid = name != "llama-tiny"
    cfg = get_preset(name, max_seq_len=2048)
    params = model_lib.init_params(jax.random.key(0), cfg)
    b = ContinuousBatcher(
        cfg, params, batch_slots=2, max_len=2048, chunk_steps=2,
        paged_pages=300, page_size=16, prefix_cache=not hybrid)
    spans = []
    real = b._span

    def span(name, **attrs):
        if name == "batcher.admit.row":
            spans.append(attrs)
        return real(name, **attrs)

    monkeypatch.setattr(b, "_span", span)
    names = ("batcher.admit.attn_pairs", "batcher.admit.attn_pairs_live")
    before = [METRICS.get_counter(n) for n in names]
    prompt = [int(x) for x in np.random.RandomState(3).randint(
        0, 256, 1100)]  # bucket 2,048
    b.submit(prompt, max_new_tokens=1)
    b.run()
    if not hybrid:  # the same prompt again, behind its cached pages
        b.submit(prompt + [7, 8, 9], max_new_tokens=1)
        b.run()
        assert spans[1]["cached_tokens"] > 0
    got = tuple(METRICS.get_counter(n) - was for n, was in zip(names, before))
    if not kernel:
        want = (0, 0)
    elif hybrid:
        full, band = (live_tiles(2048, 1100, 1024),
                      live_tiles(2048, 1100, 512, cfg.sliding_window))
        assert full == (3 * 1024 ** 2, 3 * 1024 ** 2)  # 1,100 > one tile
        assert band == (7 * 512 ** 2, 5 * 512 ** 2)
        n_full, n_band = len(cfg.attn_layers), len(cfg.swa_layers)
        want = tuple(n_full * f + n_band * w for f, w in zip(full, band))
    else:
        want = (cfg.num_layers * 3 * 1024 ** 2,) * 2
    assert got == want
    assert [a["attn_pairs_live"] for a in spans] == [want[1], 0][:len(spans)]


# -- the gap between a row's deliveries (batcher.row.gap_seconds) ------------

GAP = ("batcher.row.gap_admit_seconds", "batcher.decode.committed_tokens")


def gap_reads():
    return (METRICS.get_histogram("batcher.row.gap_seconds"),
            {n: METRICS.get_counter(n) for n in GAP})


def gaps_since(before):
    (n0, s0), c0 = before
    (n1, s1), c1 = gap_reads()
    return n1 - n0, s1 - s0, {k: c1[k] - c0[k] for k in GAP}


class Shadow:
    """The test's own record of a run, on the fake clock: when each rid was
    handed tokens (``on_tokens``) and when the engine thread was inside
    ``batcher.loop.admit``.  From them, without the batcher's stamps, what
    the gaps must come to."""

    def __init__(self, b, now, monkeypatch, then=None):
        self.now, self.then = now, then
        self.delivered: dict[int, list[tuple[float, int]]] = {}
        self.rounds: list[list[float]] = []
        self.resumed: dict[int, list[float]] = {}
        span = b._span
        shadow = self

        class watched:
            def __init__(self, name, **attrs):
                self.inner = span(name, **attrs)
                self.admit = name == "batcher.loop.admit"

            def __enter__(self):
                if self.admit:
                    shadow.rounds.append([now[0], None])
                return self.inner.__enter__()

            def __exit__(self, *exc):
                if self.admit:
                    shadow.rounds[-1][1] = now[0]
                return self.inner.__exit__(*exc)

        monkeypatch.setattr(b, "_span", watched)

    def on_tokens(self, rid, toks, done, lps):
        if toks:
            self.delivered.setdefault(rid, []).append((self.now[0], len(toks)))
            if self.rounds and self.rounds[-1][1] is None \
                    and len(self.delivered[rid]) > 1:
                # Inside a round, and not the request's first: the
                # admission token of a resume after a preemption.
                self.resumed.setdefault(rid, []).append(self.now[0])
        if self.then is not None:
            self.then(rid, toks, done)

    def in_rounds(self, t0, t1):
        return sum(max(0.0, min(t1, b) - max(t0, a)) for a, b in self.rounds)

    def gaps(self, rid):
        """[(gap, its part inside admission rounds)] of ``rid``: between
        every two times it was handed tokens."""
        at = [t for t, _ in self.delivered[rid]]
        return [(b - a, self.in_rounds(a, b)) for a, b in zip(at, at[1:])]

    def chunk_tokens(self):
        """Tokens the chunks brought: all but each request's first and
        the token of each re-admission."""
        return sum(k for d in self.delivered.values() for _, k in d) \
            - len(self.delivered) - sum(map(len, self.resumed.values()))


@pytest.mark.parametrize("overlap", [True, False])
def test_a_gap_is_the_interval_between_two_deliveries(tiny, monkeypatch,
                                                      overlap):
    """Two rows and one admission round between two chunks, scripted: A
    decodes alone, B is submitted from A's first chunk and admitted before
    A's second.  Every delivery but a request's first token closes a gap;
    the gaps add up to first token -> last delivery; a gap's admit part is
    the admission rounds that fell in it: B's whole round for the resident
    A, nothing for a chunk with no round before it."""
    b, now, calls = ticking_batcher(tiny, monkeypatch, COST, batch_slots=2,
                                    overlap=overlap)
    later = []

    def then(rid, toks, done):
        if not later and len(shadow.delivered.get(rid, [])) == 2:
            later.append(b.submit([4, 4, 4, 4], max_new_tokens=9))

    shadow = Shadow(b, now, monkeypatch, then)
    a = b.submit([7, 1, 9], max_new_tokens=13)
    before = gap_reads()
    b.run(on_tokens=shadow.on_tokens)
    n, total, c = gaps_since(before)
    rid_b, = later
    want = {rid: shadow.gaps(rid) for rid in (a, rid_b)}
    # 13 tokens: the first and three chunks of 4; 9: the first and two.
    assert [len(want[a]), len(want[rid_b])] == [3, 2]
    deliveries = sum(len(d) for d in shadow.delivered.values())
    assert n == deliveries - calls["_activate_row"] == 5
    assert total == pytest.approx(sum(g for w in want.values() for g, _ in w))
    assert total == pytest.approx(sum(
        d[-1][0] - d[0][0] for d in shadow.delivered.values()))
    assert c["batcher.row.gap_admit_seconds"] == pytest.approx(
        sum(p for w in want.values() for _, p in w))
    assert c["batcher.decode.committed_tokens"] == 13 + 9 - 2 \
        == shadow.chunk_tokens()
    recs = {r["rid"]: r for r in b.finished_requests()}
    for rid, w in want.items():
        r = recs[rid]
        assert r["deliveries"] == len(w) + 1
        assert r["max_gap_ms"] == pytest.approx(1e3 * max(g for g, _ in w))
        assert r["stalled_ms"] == pytest.approx(1e3 * sum(p for _, p in w))
        assert r["stalled_ms"] <= r["decode_ms"] + 1e-6
    # B's round: its pages, its program, its fetch and its activation.
    b_round = 2.0 + 64.0 + 16.0 + 4.0
    # It fell in ONE of A's gaps: the second, or with a chunk dispatched
    # ahead of the delivery that submitted B, the third.
    parts_a = [p for _, p in want[a]]
    assert parts_a == pytest.approx(
        [0.0, 0.0, b_round] if overlap else [0.0, b_round, 0.0])
    assert recs[a]["stalled_ms"] == pytest.approx(1e3 * b_round)
    # B's first token is stamped as its round ends and opens its first
    # gap: it waited through no round.
    assert recs[rid_b]["stalled_ms"] == 0.0
    if not overlap:
        # plan, the chunk's dispatch and fetch, the delivery: a chunk.
        chunk = 0.5 + 32.0 + 16.0 + 1.0
        assert [g for g, _ in want[a]] == pytest.approx(
            [chunk, chunk + b_round, chunk])


def test_a_first_token_and_a_bare_done_observe_no_gap(tiny):
    """The admission's first token opens a gap and observes nothing; a row
    finished by that token is published by a ``done`` that brings none."""
    now = [10.0]
    b = paged(tiny, clock=lambda: now[0])
    one = b.submit([7, 1, 9], max_new_tokens=1)
    before = gap_reads()
    got = []
    b.run(on_tokens=lambda rid, toks, done, lps: got.append((toks, done)))
    assert [len(t) for t, _ in got] == [1, 0] and got[-1][1]
    n, total, c = gaps_since(before)
    assert (n, total) == (0, 0.0) and not any(c.values())
    rec, = [r for r in b.finished_requests() if r["rid"] == one]
    assert (rec["deliveries"], rec["max_gap_ms"], rec["stalled_ms"]) == \
        (1, 0.0, 0.0)


@pytest.mark.parametrize("resume", ["recompute", "swap"])
def test_a_preempted_rows_gap_spans_its_requeue(tiny, monkeypatch, resume):
    """The stamps live on the timeline the resume request shares: the gap
    across a preemption is ONE gap, the requeue wait inside it, from the
    row's last chunk before it to the next time the caller is handed a
    token.  A recompute's re-admission samples one and streams it, so that
    token closes the gap, inside its own round; a swap restore samples
    none, and the resumed row's first chunk does.  Either way a request's
    gaps add up to first token -> last delivery."""
    cost = {"decode_chunk": 32.0, "device_get": 16.0, "_collect": 1.0,
            "admit_row_paged": 64.0}
    kw = {"host_pages": 16} if resume == "swap" else {}
    b, now, calls = ticking_batcher(tiny, monkeypatch, cost, paged_pages=9,
                                    **kw)
    shadow = Shadow(b, now, monkeypatch)
    reqs = [([7, 1, 9, 2], 44), ([4, 4, 4, 4], 44), ([9, 8, 7, 3], 44)]
    rids = [b.submit(ids, max_new_tokens=n) for ids, n in reqs]
    before = gap_reads()
    swaps0 = METRICS.get_counter("batcher.kv_swaps.in")
    b.run(on_tokens=shadow.on_tokens)
    n, total, c = gaps_since(before)
    assert b.preemptions >= 1
    swapped = METRICS.get_counter("batcher.kv_swaps.in") - swaps0
    assert (swapped >= 1) == (resume == "swap")
    recs = {r["rid"]: r for r in b.finished_requests()}
    assert sorted(recs) == rids
    assert n == sum(r["deliveries"] for r in recs.values()) - len(reqs)
    assert total == pytest.approx(sum(
        d[-1][0] - d[0][0] for d in shadow.delivered.values()))
    assert c["batcher.row.gap_admit_seconds"] == pytest.approx(sum(
        p for rid in rids for _, p in shadow.gaps(rid)))
    assert c["batcher.decode.committed_tokens"] == shadow.chunk_tokens()
    back = [r for r in recs.values() if r["residencies"] > 1]
    stayed = [r for r in recs.values() if r["residencies"] == 1]
    assert back and stayed
    for r in recs.values():
        want = shadow.gaps(r["rid"])
        assert r["deliveries"] == len(want) + 1
        assert r["max_gap_ms"] == pytest.approx(1e3 * max(g for g, _ in want))
        assert r["stalled_ms"] == pytest.approx(1e3 * sum(p for _, p in want))
    for r in back:
        assert r["max_gap_ms"] > max(s["max_gap_ms"] for s in stayed)
        again = shadow.resumed.get(r["rid"], [])
        if resume == "swap":
            assert not again                  # no token but the chunks'
            continue
        # The re-admission's token CLOSES the longest gap, the requeue wait
        # before it and its own admission up to the token inside; the next
        # chunk's gap starts there and holds the rest of the round.
        at = [t for t, _ in shadow.delivered[r["rid"]]]
        assert len(again) == r["residencies"] - 1
        k = at.index(again[0])
        assert at[k] - at[k - 1] == pytest.approx(1e-3 * r["max_gap_ms"])
        gap, part = shadow.gaps(r["rid"])[k - 1]
        assert 64.0 <= part < gap             # its own program at the least
        assert at[k + 1] - at[k] < gap


def test_a_cancelled_rows_last_interval_is_dropped(tiny, monkeypatch):
    cost = {"decode_chunk": 32.0, "device_get": 16.0, "_collect": 1.0}
    b, now, calls = ticking_batcher(tiny, monkeypatch, cost)
    seen = []

    def on_tokens(rid, toks, done, lps):
        seen.append(now[0])
        if len(seen) == 3:                    # first token, two chunks
            assert b.cancel_row(rid)

    rid = b.submit([7, 1, 9], max_new_tokens=40)
    before = gap_reads()
    b.run(on_tokens=on_tokens)
    n, total, c = gaps_since(before)
    rec, = [r for r in b.finished_requests() if r["rid"] == rid]
    assert rec["finish"] == "cancelled" and rec["deliveries"] == 3
    assert n == 2
    assert c["batcher.decode.committed_tokens"] == 8 == rec["out_tokens"] - 1
    assert total == pytest.approx(seen[2] - seen[0])
    assert now[0] > seen[2]                   # the chunk in flight: dropped


@pytest.mark.parametrize("kind", [
    "contiguous", "chunked-alternate", "chunked-mixed",
    pytest.param("speculative", marks=pytest.mark.fragile_xla_cpu)])
def test_every_schedule_delivers_through_the_same_stamps(tiny, kind):
    """The speculative and the mixed schedule deliver through ``_collect``,
    and a chunked prefill's finish opens a gap like any admission: in each
    the gaps are the deliveries less one a request, carry every token but
    each request's first, and add up to first token -> last delivery on a
    clock that moves a second a reading."""
    now = [0.0]

    def clock():
        now[0] += 1.0
        return now[0]

    if kind == "speculative":
        cfg, params, dcfg, dparams = _spec_models()
        b = ContinuousBatcher(cfg, params, batch_slots=2, max_len=64,
                              chunk_steps=4, paged_pages=24, page_size=16,
                              spec_k=3, draft_params=dparams, draft_cfg=dcfg,
                              clock=clock)
    elif kind == "contiguous":
        b = paged(tiny, paged_pages=None, clock=clock)
    else:
        b = paged(tiny, prefill_chunk=8, clock=clock,
                  schedule=kind.split("-")[1])
    jobs = [(list(range(40, 75)), 9), ([7, 1, 9], 14), ([5] * 20, 6)]
    before = gap_reads()
    chunks0 = METRICS.get_counter("batcher.prefill_chunks")
    got: dict[int, list[int]] = {}
    rids = [b.submit(ids, max_new_tokens=n) for ids, n in jobs]
    b.run(on_tokens=lambda rid, toks, done, lps:
          got.setdefault(rid, []).append(len(toks)) if toks else None)
    n, total, c = gaps_since(before)
    if kind.startswith("chunked"):
        assert METRICS.get_counter("batcher.prefill_chunks") > chunks0
    assert [sum(got[r]) for r in rids] == [k for _, k in jobs]
    assert n == sum(len(d) for d in got.values()) - len(jobs)
    assert c["batcher.decode.committed_tokens"] == \
        sum(k for _, k in jobs) - len(jobs)
    recs = {r["rid"]: r for r in b.finished_requests()}
    assert [recs[r]["deliveries"] for r in rids] == [len(got[r]) for r in rids]
    assert all(0 < recs[r]["max_gap_ms"] and
               0 <= recs[r]["stalled_ms"] <= recs[r]["decode_ms"]
               for r in rids)
    assert 0 <= c["batcher.row.gap_admit_seconds"] < total
