"""Dispatch-ahead engine loop (runtime/batcher.py overlap plane).

The contract pinned here is EXACTNESS: with overlap on (the default),
temp-0 outputs — tokens, logprobs, streaming delivery sequences — are
byte-identical to the fully-synchronous loop (overlap off) across every
composition the engine serves: plain decode, automatic prefix caching,
chunked prefill, pool-pressure preemption with swap restore, int8 KV
pages, and speculative decoding.  Plus: the overlap plane actually
engages (dispatched-ahead chunks counted, device gap ~0 for them), every
sync trigger fires when it must (arrival mid-span, cancel mid-span,
growth under pressure), the batched digest chain equals the old per-page
construction, and a dispatched-ahead chunk still crashes/stalls/recovers
through the serving supervisor exactly.
"""

import asyncio
import hashlib
import json

import numpy as np
import pytest

import jax

from distributed_llms_tpu.core.observability import METRICS
from distributed_llms_tpu.models import model as model_lib, presets
from distributed_llms_tpu.runtime.batcher import ContinuousBatcher
from distributed_llms_tpu.runtime.faults import FaultPlane
from distributed_llms_tpu.runtime.pages import PrefixCache
from distributed_llms_tpu.runtime.server import InferenceServer
from distributed_llms_tpu.runtime.tokenizer import ByteTokenizer


@pytest.fixture(scope="module")
def tiny():
    cfg = presets.get_preset("llama-tiny", vocab_size=512)
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def mk(tiny, overlap, **kw):
    cfg, params = tiny
    tok = ByteTokenizer()
    kw.setdefault("batch_slots", 3)
    kw.setdefault("max_len", 96)
    kw.setdefault("chunk_steps", 4)
    return ContinuousBatcher(
        cfg, params, tokenizer=tok, eos_id=tok.eos_id, pad_id=tok.pad_id,
        overlap=overlap, **kw,
    )


PAGED = dict(paged_pages=24, page_size=16, prefix_cache=True)


def drive(b, reqs, **submit_kw):
    rids = [b.submit(p, max_new_tokens=n, **submit_kw) for p, n in reqs]
    res = b.run()
    return [res[r] for r in rids], [b.result_logprobs[r] for r in rids]


def both_legs(tiny, reqs, batcher_kw=None, submit_kw=None):
    """Run the same requests with overlap off and on; return
    ((toks_off, lps_off), (toks_on, lps_on), batcher_on)."""
    b_off = mk(tiny, False, **(batcher_kw or {}))
    off = drive(b_off, reqs, **(submit_kw or {}))
    b_on = mk(tiny, True, **(batcher_kw or {}))
    on = drive(b_on, reqs, **(submit_kw or {}))
    return off, on, b_on


# -- exactness across the composition matrix --------------------------------


def test_plain_decode_exact_on_vs_off(tiny):
    """Contiguous mode, staggered budgets (rows finish at different
    chunks): tokens AND logprobs byte-identical, overlap on vs off."""
    reqs = [("hello world", 17), ("abcdef", 9), ("xyz!", 23)]
    off, on, b_on = both_legs(tiny, reqs)
    assert on == off
    assert b_on.overlap_stats["dispatched_ahead"] > 0


def test_prefix_cache_exact_and_hit_accounting(tiny):
    """Paged + automatic prefix caching: shared-prefix traffic hits the
    cache identically (cached-token accounting equal) and bytes match."""
    shared = "the shared system prompt padding " * 2
    reqs = [(shared + "a", 10), (shared + "b", 10), ("solo", 8)]

    def leg(overlap):
        b = mk(tiny, overlap, **PAGED)
        rids = [b.submit(p, max_new_tokens=n) for p, n in reqs]
        res = b.run()
        cached = [b.prefix_cached_tokens[r] for r in rids]
        b.assert_pool_consistent()
        return [res[r] for r in rids], cached

    off, cached_off = leg(False)
    on, cached_on = leg(True)
    assert on == off
    assert cached_on == cached_off
    assert max(cached_on) >= 16  # the shared prefix really was served hot


def test_chunked_prefill_exact(tiny):
    """Chunked prefill (paged + prefix cache): a long prompt admitted in
    chunks composes with the overlap plane — under the default mixed
    schedule the bites ride the fused span and only the finishing splice
    syncs; under alternate every prefill round syncs (the scheduler's
    sync_triggers hook, runtime/scheduler.py).  Bytes identical on/off
    either way."""
    long_prompt = "a long prompt that must chunk " * 2
    reqs = [(long_prompt, 12), ("short", 10)]
    kw = dict(prefill_chunk=16, **PAGED)
    off, on, b_on = both_legs(tiny, reqs, batcher_kw=kw)
    assert on == off
    b_on.assert_pool_consistent()


def test_growth_and_preemption_exact_under_pressure(tiny):
    """A pool too small for both rows' full depth: growth escalates to
    preemption (exact recompute) mid-run.  Preemption only ever runs
    against synced mirrors — the span syncs when growth would need
    pressure — and the reunited streams stay byte-identical."""
    reqs = [("a" * 20, 40), ("b" * 25, 40)]
    kw = dict(paged_pages=8, page_size=16, prefix_cache=True,
              batch_slots=2)
    off, on, b_on = both_legs(tiny, reqs, batcher_kw=kw)
    assert on == off
    assert b_on.preemptions > 0  # the pressure leg really ran
    b_on.assert_pool_consistent()


def test_swap_preemption_exact(tiny):
    """Host-tier swap-preemption under the same pressure: victims park
    raw pages and restore byte-exact, overlap on vs off."""
    swaps0 = METRICS.get_counter("batcher.kv_swaps.in")
    reqs = [("a" * 20, 40), ("b" * 25, 40)]
    kw = dict(paged_pages=8, page_size=16, prefix_cache=True,
              batch_slots=2, host_pages=16)
    off, on, b_on = both_legs(tiny, reqs, batcher_kw=kw)
    assert on == off
    assert METRICS.get_counter("batcher.kv_swaps.in") > swaps0
    b_on.assert_pool_consistent()


def test_int8_kv_exact_on_vs_off(tiny):
    """int8 KV pages (deterministic quantized decode): overlap on vs off
    byte-identical at the quantized width too."""
    reqs = [("hello int8", 14), ("quant!", 10)]
    kw = dict(paged_pages=24, page_size=16, prefix_cache=True, kv_bits=8)
    off, on, b_on = both_legs(tiny, reqs, batcher_kw=kw)
    assert on == off
    b_on.assert_pool_consistent()


def test_per_request_sampling_exact(tiny):
    """Per-request sampling (traced per-row path) with a seeded RNG:
    the span plan keeps one compiled program and the RNG stream is
    chunk-aligned, so even sampled outputs match for a single batch."""
    reqs = [("sampled a", 12), ("sampled b", 12)]
    off, on, _ = both_legs(tiny, reqs,
                           submit_kw=dict(temperature=0.8, top_k=7))
    assert on == off


@pytest.mark.fragile_xla_cpu  # spec programs: fresh-process isolation
def test_speculative_exact_on_vs_off(tiny):
    """Speculative rounds chain device-resident exactly like plain
    chunks (draft cache included): greedy spec, overlap on vs off."""
    cfg, params = tiny
    dcfg = presets.get_preset("llama-tiny", vocab_size=512, num_layers=2)
    dparams = model_lib.init_params(jax.random.key(99), dcfg)
    reqs = [([7, 1, 9, 4, 2], 11), ([4, 4, 4], 7), ([11, 12], 13)]
    kw = dict(draft_params=dparams, draft_cfg=dcfg, spec_k=3,
              batch_slots=2, max_len=64)
    off, on, b_on = both_legs(tiny, reqs, batcher_kw=kw)
    assert on == off
    assert b_on.overlap_stats["dispatched_ahead"] > 0


# -- the overlap plane itself ------------------------------------------------


def test_streaming_deliveries_identical(tiny):
    """The full on_tokens sequence — rids, token groups, done flags —
    is identical on vs off (delivery shifts one dispatch later in wall
    time, never in content)."""
    reqs = [("stream me", 10), ("and me", 14)]
    streams = []
    for overlap in (False, True):
        b = mk(tiny, overlap)
        sink = []
        for p, n in reqs:
            b.submit(p, max_new_tokens=n)
        b.run(on_tokens=lambda rid, t, d, l, s=sink:
              s.append((rid, tuple(t), d, tuple(l or []))))
        streams.append(sink)
    assert streams[0] == streams[1]


def test_dispatch_ahead_engages_and_counts(tiny, monkeypatch):
    """Steady decode with nothing queued: nearly every chunk dispatches
    ahead (its delivery starves the device of nothing, by construction),
    the span ends in exactly one carry sync, chunk count matches the
    synchronous leg (no ghost chunks), and the METRICS mirrors move."""
    names = ("batcher.overlap.dispatched_ahead", "batcher.overlap.carry_syncs",
             "batcher.decode.chunks", "batcher.starved.deliver_seconds")

    def drive_counted(overlap):
        # A clock that moves one second in every delivery and nowhere else.
        now = [0.0]
        b = mk(tiny, overlap, clock=lambda: now[0])
        collect = b._collect

        def ticking(*a, **k):
            now[0] += 1.0
            return collect(*a, **k)

        monkeypatch.setattr(b, "_collect", ticking)
        c0 = {n: METRICS.get_counter(n) for n in names}
        drive(b, [("steady state", 33)])
        return b, {n.rsplit(".", 1)[1]: METRICS.get_counter(n) - c0[n]
                   for n in names}

    b_off, off = drive_counted(False)
    b_on, on = drive_counted(True)
    s = b_on.overlap_stats
    assert on["chunks"] == off["chunks"]  # no ghosts
    assert s["dispatched_ahead"] == on["chunks"] - 1  # all but the first
    assert s["carry_syncs"] == 1
    # Every delivery of the synchronous leg ran with nothing in flight; of
    # the dispatched-ahead leg's, only the one after the sync that ends
    # the span: the chunks dispatched ahead left the device no gap.
    assert off["deliver_seconds"] == off["chunks"]
    assert on["deliver_seconds"] == 1.0
    assert b_off.overlap_stats["dispatched_ahead"] == 0  # off leg: none
    assert on["dispatched_ahead"] == s["dispatched_ahead"]
    assert on["carry_syncs"] == 1


def test_arrival_mid_span_syncs_and_admits(tiny):
    """A request submitted mid-span (from the streaming callback, i.e.
    during a dispatched-ahead chunk's host window) forces a sync at the
    next boundary and admits — and the late arrival's tokens equal its
    solo run (temp-0 recompute-exactness, unchanged by overlap)."""
    b_solo = mk(tiny, True)
    r = b_solo.submit("late arrival", max_new_tokens=8)
    want_late = b_solo.run()[r]

    b = mk(tiny, True)
    first = b.submit("first request", max_new_tokens=24)
    late = []

    def cb(rid, toks, done, lps):
        if rid == first and not late and len(b.rows[0].emitted or []) >= 9:
            late.append(b.submit("late arrival", max_new_tokens=8))

    res = b.run(on_tokens=cb)
    assert late and res[late[0]] == want_late
    assert b.overlap_stats["carry_syncs"] >= 2  # the arrival split the span


def test_cancel_mid_span_stops_row(tiny):
    """cancel_row from the delivery callback while the carry is device-
    resident: the row stops at the next boundary (no budget-long ghost
    decode), nothing resurrects at the sync, and the pool audits clean."""
    b = mk(tiny, True, **PAGED)
    rid = b.submit("cancel me please", max_new_tokens=64)
    seen = []

    def cb(r, toks, done, lps):
        seen.extend(toks)
        if len(seen) >= 6:
            b.cancel_row(rid)

    res = b.run(on_tokens=cb)
    # Cancelled shortly after the 6th token: chunks already dispatched
    # ahead may land, a fresh budget-worth of decode must not.
    assert 6 <= len(res[rid]) <= 6 + 3 * b.chunk_steps
    assert not b.active.any() and b.rows[0].rid is None
    b.assert_pool_consistent()


def test_rng_stream_aligned_after_eos_ghost(tiny):
    """An all-rows-EOS mid-span dispatches one ghost chunk ahead; its
    RNG split is REFUNDED (a ghost samples nothing), so the engine's
    sampled stream stays aligned with the synchronous loop — a LATER
    sampled request produces identical tokens, overlap on vs off."""
    cfg, params = tiny
    tok = ByteTokenizer()

    def build(overlap, eos_id):
        return ContinuousBatcher(
            cfg, params, tokenizer=tok, eos_id=eos_id, pad_id=tok.pad_id,
            batch_slots=3, max_len=96, chunk_steps=4, overlap=overlap,
        )

    # Greedy probe: a token the run actually emits mid-span.
    probe = build(False, -1)
    r = probe.submit("ghost drill", max_new_tokens=33)
    eos_tok = probe.run()[r][7]

    def leg(overlap):
        b = build(overlap, eos_tok)
        r1 = b.submit("ghost drill", max_new_tokens=33)
        first = b.run()[r1]
        r2 = b.submit("then sampled", max_new_tokens=12, temperature=0.9)
        return first, b.run()[r2], b

    first_off, second_off, _ = leg(False)
    first_on, second_on, b_on = leg(True)
    assert first_on == first_off
    assert first_on[-1] == eos_tok and len(first_on) < 33  # EOS really hit
    # The ghost was dispatched (chunks exceed the synchronous count by
    # one) yet the sampled follow-up is identical: the split was refunded.
    assert second_on == second_off


def test_digest_chain_matches_per_page_reference(tiny):
    """The batched one-conversion digest chain is byte-identical to the
    old per-page np.asarray construction, at both kv widths."""
    ids = list(np.random.RandomState(3).randint(1, 500, size=77))
    for kv_bits, seed in ((16, b"dlt-prefix-cache-v1"),
                          (8, b"dlt-prefix-cache-v1:kv8")):
        prev, ref = seed, []
        for i in range(4):
            h = hashlib.blake2b(prev, digest_size=16)
            h.update(np.asarray(ids[i * 16: (i + 1) * 16],
                                np.int64).tobytes())
            prev = h.digest()
            ref.append(prev)
        assert PrefixCache.page_digests(ids, 16, 4, kv_bits=kv_bits) == ref


def test_prehash_fills_queued_digests(tiny):
    """The overlapped host window pre-hashes queued prompts: digests are
    memoized on the queued request, and the later admission serves the
    identical cache hit (prehash is a pure move of when the hash runs)."""
    b = mk(tiny, True, **PAGED)
    b.submit("x" * 40, max_new_tokens=4)
    req = b.queue_snapshot()[0]
    assert req.digests is None
    b._prehash_queued()
    want = b._page_digests(req.ids, len(req.ids) // 16)
    assert req.digests == want
    b._prehash_queued()  # idempotent
    assert req.digests == want
    res = b.run()
    assert len(res[req.rid]) == 4
    b.assert_pool_consistent()


def test_engine_config_plumbing(tiny):
    """RuntimeConfig.overlap flows through engine.continuous_batcher
    (explicit argument wins; default is on)."""
    from distributed_llms_tpu.core.config import RuntimeConfig
    from distributed_llms_tpu.runtime.engine import InferenceEngine

    assert RuntimeConfig().overlap is True
    eng = InferenceEngine.from_preset("llama-tiny", vocab_size=512)
    assert eng.continuous_batcher(batch_slots=2, max_len=64).overlap is True
    eng.rt = RuntimeConfig(overlap=False)
    assert eng.continuous_batcher(batch_slots=2, max_len=64).overlap is False
    assert eng.continuous_batcher(
        batch_slots=2, max_len=64, overlap=True
    ).overlap is True


# -- fault plane: crash / stall with a dispatched-ahead chunk in flight ------


async def _request(host, port, method, path, body=None):
    reader, writer = await asyncio.open_connection(host, port)
    payload = b"" if body is None else json.dumps(body).encode()
    writer.write(
        f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
        f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload
    )
    await writer.drain()
    status = int((await reader.readline()).split()[1])
    while (await reader.readline()) not in (b"\r\n", b"\n", b""):
        pass
    data = await reader.read()
    writer.close()
    return status, data


def run_with_server(batcher, fn, **srv_kw):
    async def driver():
        srv = InferenceServer(batcher, model_name="tiny", host="127.0.0.1",
                              port=0, **srv_kw)
        host, port = await srv.start()
        try:
            return await asyncio.wait_for(fn(host, port, srv), timeout=600)
        finally:
            await srv.stop()

    return asyncio.run(driver())


def _server_batcher(tiny, faults=None):
    # Contiguous mode: a fault-armed PAGED engine deliberately stays on
    # the synchronous growth path (_grow_ahead returns False so drill
    # windows count exactly), which would keep these drills from ever
    # having a dispatched-ahead chunk in flight.
    return mk(tiny, True, batch_slots=2, faults=faults)


def test_supervisor_recovers_crash_at_dispatched_ahead_chunk(tiny):
    """batcher.decode raise@2 with one streaming request: the first
    chunk is in flight when the rule fires at the DISPATCHED-AHEAD
    boundary.  The supervisor respawns; the partially-streamed request
    fails structured; the engine then serves the same prompt byte-exact
    (and /healthz reports exactly one restart)."""
    b_ref = _server_batcher(tiny)
    r = b_ref.submit("crash drill", max_new_tokens=12)
    want = b_ref.tokenizer.decode(b_ref.run()[r])

    plane = FaultPlane.parse("batcher.decode:raise@2")
    restarts0 = METRICS.get_counter("server.engine_restarts")

    async def fn(host, port, srv):
        status, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "crash drill", "max_tokens": 12},
        )
        body = json.loads(raw)
        assert status == 500 and body["error"]["type"] == "engine_error"
        assert plane.rules[0].fired == 1
        # The respawn serves the same prompt byte-exact.
        status, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "crash drill", "max_tokens": 12},
        )
        assert status == 200
        assert json.loads(raw)["choices"][0]["text"] == want
        assert METRICS.get_counter("server.engine_restarts") - restarts0 == 1
        srv.batcher.assert_pool_consistent()

    run_with_server(_server_batcher(tiny, faults=plane), fn)


def test_supervisor_readmits_zero_streamed_exactly_overlap_on(tiny):
    """The PR-2 acceptance contract with the overlap plane ON: slots
    full, a queued request has streamed nothing when the engine crashes;
    the supervisor re-admits it under its original rid and its temp-0
    text is identical to an unfaulted run."""
    prompts = ["alpha", "bravo!", "charlie?"]
    wants = {}
    for p in prompts:
        b = _server_batcher(tiny)
        r = b.submit(p, max_new_tokens=8)
        wants[p] = b.tokenizer.decode(b.run()[r])

    plane = FaultPlane.parse("batcher.decode:raise@1")
    retried0 = METRICS.get_counter("server.requests_retried")

    async def fn(host, port, srv):
        outs = await asyncio.gather(*[
            _request(host, port, "POST", "/v1/completions",
                     {"prompt": p, "max_tokens": 8})
            for p in prompts
        ])
        completed = 0
        for (status, raw), p in zip(outs, prompts):
            body = json.loads(raw)
            if status == 200:
                assert body["choices"][0]["text"] == wants[p], p
                completed += 1
            else:
                assert body["error"]["type"] == "engine_error"
        # 2 slots admitted (and streamed) before the crash; the queued
        # third re-admits and completes exactly.
        assert completed >= 1
        assert METRICS.get_counter("server.requests_retried") > retried0
        srv.batcher.assert_pool_consistent()

    run_with_server(_server_batcher(tiny, faults=plane), fn)


def test_watchdog_trips_on_wedged_overlapped_chunk(tiny):
    """batcher.decode stall@2 fires at the dispatched-ahead boundary (a
    chunk already in flight): the engine thread wedges with work pending
    and /healthz flips unhealthy until the stall clears."""
    plane = FaultPlane.parse("batcher.decode:stall@2:1.2")

    async def fn(host, port, srv):
        req_task = asyncio.create_task(_request(
            host, port, "POST", "/v1/completions",
            {"prompt": "wedge", "max_tokens": 16},
        ))
        unhealthy_seen = False
        for _ in range(100):
            status, raw = await _request(host, port, "GET", "/healthz")
            if status == 503 and json.loads(raw)["engine_stalled"]:
                unhealthy_seen = True
                break
            await asyncio.sleep(0.05)
        assert unhealthy_seen, "watchdog never flipped /healthz"
        status, _ = await req_task
        assert status == 200
        assert plane.rules[0].fired == 1

    run_with_server(_server_batcher(tiny, faults=plane), fn,
                    watchdog_timeout_s=0.3)
