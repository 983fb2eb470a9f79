"""Disaggregated prefill/decode serving (ISSUE 7 acceptance).

The contract pinned here: a fleet with dedicated PREFILL-role replicas
(admission/chunked prefill + KV page export) and DECODE-role replicas
(verified import + decode) behind the router's handoff plane serves
every request temp-0 BYTE-EXACT vs a colocated reference — and every
way the handoff can fail (prefill crash/stall/partition mid-handoff,
frame corruption, duplicate delivery, digest mismatch, transfer-retry
exhaustion, an empty prefill tier) either heals transparently (retry,
idempotent re-delivery) or degrades to COLOCATED prefill on the decode
replica, never to wrong bytes.  Pool audits stay clean on both roles.

The chaos acceptance test (2 prefill + 2 decode under storm surviving a
prefill crash mid-handoff + a corrupted frame + a stalled transfer) is
tier-1; the bigger storm variant is marked slow.
"""

import asyncio
import json

import pytest

import jax

from distributed_llms_tpu.cluster.fleet import ReplicaFleet
from distributed_llms_tpu.core.observability import METRICS
from distributed_llms_tpu.models import model as model_lib, presets
from distributed_llms_tpu.runtime.batcher import ContinuousBatcher
from distributed_llms_tpu.runtime.faults import FaultPlane
from distributed_llms_tpu.runtime.router import ReplicaRouter
from distributed_llms_tpu.runtime.server import InferenceServer
from distributed_llms_tpu.runtime.tokenizer import ByteTokenizer

PAGE = 16


@pytest.fixture(scope="module")
def tiny():
    cfg = presets.get_preset("llama-tiny", vocab_size=512)
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def _replica_batcher(tiny, pages=12, **bkw):
    cfg, params = tiny
    tok = ByteTokenizer()
    return ContinuousBatcher(
        cfg, params, tokenizer=tok, eos_id=tok.eos_id, pad_id=tok.pad_id,
        batch_slots=2, max_len=96, chunk_steps=4,
        paged_pages=pages, page_size=PAGE, prefix_cache=True, **bkw,
    )


@pytest.fixture(scope="module")
def warmed(tiny):
    """Warm the process-wide jit cache with the replicas' program shapes
    (paged admission, cache-hit admission — the handed-off request's
    path — and decode) so the fast watchdogs below never mistake a cold
    compile for a wedged engine."""
    b = _replica_batcher(tiny)
    for prompt in ("warm short", "a much longer warming prompt xxxx!!",
                   "a much longer warming prompt xxxx!!"):
        b.submit(prompt, max_new_tokens=4)
        b.run()
    return tiny


def role_factory(tiny, role, batcher_kw=None, **srv_kw):
    srv_kw.setdefault("watchdog_timeout_s", 2.0)
    bkw = batcher_kw or {}

    def make_server():
        return InferenceServer(
            _replica_batcher(tiny, **bkw), model_name="tiny",
            host="127.0.0.1", port=0,
            batcher_factory=lambda: _replica_batcher(tiny, **bkw),
            role=role, **srv_kw,
        )

    return make_server


def run_with_disagg_fleet(tiny, n_prefill, n_decode, fn, faults=None,
                          srv_kw=None, router_kw=None, batcher_kw=None):
    """Boot an (n_prefill prefill + n_decode decode)-role fleet behind a
    handoff-enabled router, wait healthy, run ``fn``, tear down.  The
    shared ``faults`` plane serves the event-loop sites (xfer.*,
    prefill.crash, replica.*, router.*): every server's batcher gets it
    too, which is safe here because batcher.* rules are never armed on
    it in these tests."""

    async def driver():
        factories = (
            [role_factory(tiny, "prefill", batcher_kw=batcher_kw,
                          **(srv_kw or {}))] * n_prefill
            + [role_factory(tiny, "decode", batcher_kw=batcher_kw,
                            **(srv_kw or {}))] * n_decode
        )
        names = [f"p{i}" for i in range(n_prefill)] \
            + [f"d{i}" for i in range(n_decode)]
        fleet = ReplicaFleet(factories, names=names,
                             probe_interval_s=0.05, probe_timeout_s=2.0,
                             faults=faults)
        router = ReplicaRouter(
            fleet, host="127.0.0.1", port=0, tokenizer=ByteTokenizer(),
            page_size=PAGE, handoff=True, faults=faults,
            **(router_kw or {}),
        )
        await fleet.start()
        if faults is not None:
            # xfer.send / prefill.crash fire on the serving replicas'
            # own planes (batcher.faults); xfer.recv / xfer.verify on the
            # decode replicas'.  Point them all at the shared plane so a
            # test arms ONE rule set.
            for h in fleet.replicas:
                h.server.batcher.faults = faults
        host, port = await router.start()
        try:
            assert await fleet.wait_healthy(timeout_s=120.0)
            return await asyncio.wait_for(
                fn(host, port, fleet, router), timeout=600
            )
        finally:
            await router.stop()
            await fleet.stop()

    return asyncio.run(driver())


async def _request(host, port, method, path, body=None):
    reader, writer = await asyncio.open_connection(host, port)
    payload = b"" if body is None else json.dumps(body).encode()
    writer.write(
        f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
        f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload
    )
    await writer.drain()
    status = int((await reader.readline()).split()[1])
    headers = {}
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b"\n", b""):
            break
        name, _, value = line.decode("latin-1").partition(":")
        headers[name.strip().lower()] = value.strip()
    data = await reader.read()
    writer.close()
    return status, headers, data


def expected_texts(tiny, reqs):
    """Reference texts from one roomy, un-faulted COLOCATED batcher —
    byte-exactness must be invariant to where prefill ran."""
    cfg, params = tiny
    tok = ByteTokenizer()
    b = ContinuousBatcher(
        cfg, params, tokenizer=tok, eos_id=tok.eos_id, pad_id=tok.pad_id,
        batch_slots=4, max_len=96, chunk_steps=4, paged_pages=40,
        page_size=PAGE,
    )
    rids = [b.submit(p, max_new_tokens=n) for p, n in reqs]
    res = b.run()
    return {p: tok.decode(res[rid]) for rid, (p, n) in zip(rids, reqs)}


LONG = "disaggregate this considerable prompt please! "  # > 2 full pages


def _audit_all(fleet):
    for h in fleet.replicas:
        if h.server is not None and h.server._engine is not None \
                and h.server._engine.is_alive():
            h.server.batcher.assert_pool_consistent()


# -- the happy path ---------------------------------------------------------


def test_disagg_roundtrip_exact_and_offloads_prefill(warmed):
    tiny = warmed
    """A long prompt is prefilled on the prefill tier, its KV pages ship
    verified to the decode replica, and the decode admission serves the
    prompt from the imported pages (usage.cached_tokens proves it) —
    output byte-exact vs a colocated reference."""
    reqs = [(LONG + "tail one", 8), ("tiny", 4)]
    wants = expected_texts(tiny, reqs)

    async def fn(host, port, fleet, router):
        h0 = METRICS.get_counter("router.handoffs")
        imp0 = METRICS.get_counter("batcher.kv_pages_imported")
        status, _, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": reqs[0][0], "max_tokens": reqs[0][1]},
        )
        body = json.loads(raw)
        assert status == 200, body
        assert body["choices"][0]["text"] == wants[reqs[0][0]]
        # The decode replica served the shipped pages from its cache.
        cached = body["usage"]["prompt_tokens_details"]["cached_tokens"]
        assert cached >= PAGE, body["usage"]
        assert METRICS.get_counter("router.handoffs") > h0
        assert METRICS.get_counter("batcher.kv_pages_imported") > imp0
        # The SAME prompt again: the decode replica provably already
        # holds the run (epoch-valid affinity), so the router must skip
        # the redundant multi-MB transfer — and still serve exact bytes
        # from the resident pages.
        h1 = METRICS.get_counter("router.handoffs")
        sk0 = METRICS.get_counter("router.handoff_skips")
        status, _, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": reqs[0][0], "max_tokens": reqs[0][1]},
        )
        body = json.loads(raw)
        assert status == 200, body
        assert body["choices"][0]["text"] == wants[reqs[0][0]]
        assert body["usage"]["prompt_tokens_details"]["cached_tokens"] \
            >= cached
        assert METRICS.get_counter("router.handoffs") == h1
        assert METRICS.get_counter("router.handoff_skips") > sk0
        # A prompt under one full page skips the handoff plane entirely
        # (nothing exportable) and still completes exactly.
        status, _, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": reqs[1][0], "max_tokens": reqs[1][1]},
        )
        body = json.loads(raw)
        assert status == 200, body
        assert body["choices"][0]["text"] == wants[reqs[1][0]]
        # Roles hold: completions never land on the prefill tier.
        assert all(
            h.last_report.get("role") == h.role for h in fleet.replicas
            if h.last_report
        )
        _audit_all(fleet)

    run_with_disagg_fleet(tiny, 1, 1, fn)


def test_chunked_prefill_on_prefill_role_exports_complete_pages(warmed):
    """The chunked-prefill x disaggregation corner: a prefill-role
    replica whose admission takes the CHUNKED path (prompt >
    prefill_chunk, consumed in bites across scheduling rounds) must
    still publish the prompt's FULL digest-chained page run and export
    every full page for the handoff — and the decode replica must serve
    the forwarded request from those imported pages, byte-exact vs a
    monolithic colocated reference."""
    tiny = warmed
    prompt = LONG + "tail one"
    reqs = [(prompt, 8)]
    wants = expected_texts(tiny, reqs)
    tok_ids = ByteTokenizer().encode(prompt)
    n_exportable = (len(tok_ids) - 1) // PAGE  # capped one page short
    assert n_exportable >= 2  # the corner needs a multi-page chunked run

    # Warm the CHUNKED program shapes (prefill_chunk_step + chunked
    # finish + cache-hit chunked continuation) before any watchdog is
    # armed — the jit cache is process-wide, so the fleet's replicas
    # never mistake a cold compile for a wedged engine.
    b = _replica_batcher(tiny, prefill_chunk=PAGE)
    for _ in range(2):  # second pass takes the cache-hit chunked path
        b.submit(prompt, max_new_tokens=2)
        b.run()

    async def fn(host, port, fleet, router):
        exp0 = METRICS.get_counter("batcher.kv_pages_exported")
        imp0 = METRICS.get_counter("batcher.kv_pages_imported")
        h0 = METRICS.get_counter("router.handoffs")
        ch0 = METRICS.get_counter("batcher.prefill_chunks")
        status, _, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": prompt, "max_tokens": 8},
        )
        body = json.loads(raw)
        assert status == 200, body
        # Byte-exact vs the monolithic colocated reference: chunked
        # prefill, the handoff, AND the imported-page continuation all
        # compose without changing a single token.
        assert body["choices"][0]["text"] == wants[prompt]
        assert METRICS.get_counter("router.handoffs") > h0
        # The prefill replica exported the COMPLETE run (every full page
        # the chunked finish published), not just a prefix of it ...
        assert METRICS.get_counter("batcher.kv_pages_exported") - exp0 \
            == n_exportable
        # ... the decode replica adopted them ...
        assert METRICS.get_counter("batcher.kv_pages_imported") - imp0 \
            == n_exportable
        # ... and its (also chunked) admission served the prompt from
        # the imported pages rather than re-prefilling it.
        cached = body["usage"]["prompt_tokens_details"]["cached_tokens"]
        assert cached >= n_exportable * PAGE, body["usage"]
        # The CHUNKED path really ran (not a silent monolithic
        # fallback): the prefill replica bit the uncached prompt off in
        # PAGE-sized chunks (ceil(len/PAGE) bites) — a regression to
        # monolithic admission would leave the counter flat.
        bites = METRICS.get_counter("batcher.prefill_chunks") - ch0
        assert bites >= -(-len(tok_ids) // PAGE), bites
        _audit_all(fleet)

    run_with_disagg_fleet(
        tiny, 1, 1, fn,
        batcher_kw={"prefill_chunk": PAGE},
        srv_kw={"watchdog_timeout_s": 10.0},
    )


# -- transfer-level faults heal in place ------------------------------------


def test_handoff_corrupt_frame_and_dup_delivery_absorbed(warmed):
    tiny = warmed
    """A corrupted first transfer attempt is rejected by the receiver's
    checksum verify and NACKed; the jittered retry succeeds — the
    request never notices.  A duplicated frame is absorbed idempotently
    via the digest check (no double import)."""
    plane = FaultPlane()
    corrupt = plane.add("xfer.send", "corrupt", when="1")
    dup = plane.add("xfer.send", "dup", when="3")
    # Distinct FIRST pages: a shared leading page would make the second
    # request's digest run affinity-warm on the decode replica and skip
    # its handoff entirely (the optimization the roundtrip test pins).
    reqs = [("corrupt leg " + LONG, 8), ("dup leg!!!! " + LONG, 8)]
    wants = expected_texts(tiny, reqs)

    async def fn(host, port, fleet, router):
        vf0 = METRICS.get_counter("xfer.verify_failures")
        rt0 = METRICS.get_counter("xfer.retries")
        dd0 = METRICS.get_counter("xfer.dup_deliveries")
        fb0 = METRICS.get_counter("router.handoff_fallbacks")
        for p, n in reqs:
            status, _, raw = await _request(
                host, port, "POST", "/v1/completions",
                {"prompt": p, "max_tokens": n},
            )
            body = json.loads(raw)
            assert status == 200, body
            assert body["choices"][0]["text"] == wants[p], p
        assert corrupt.fired == 1
        assert dup.fired == 1
        assert METRICS.get_counter("xfer.verify_failures") > vf0
        assert METRICS.get_counter("xfer.retries") > rt0
        assert METRICS.get_counter("xfer.dup_deliveries") > dd0
        # Both healed inside the transfer plane: no degradation needed.
        assert METRICS.get_counter("router.handoff_fallbacks") == fb0
        _audit_all(fleet)

    run_with_disagg_fleet(tiny, 1, 1, fn, faults=plane)


# -- the degradation ladder -------------------------------------------------


def test_verify_rejection_exhausts_retries_falls_back_colocated(warmed):
    tiny = warmed
    """Every delivery failing verification (digest mismatch) exhausts the
    bounded transfer retries; the handoff reports failure and the router
    serves the request COLOCATED on the decode replica — byte-exact."""
    plane = FaultPlane()
    rule = plane.add("xfer.verify", "corrupt", when="*")
    reqs = [(LONG + "mismatch leg", 8)]
    wants = expected_texts(tiny, reqs)

    async def fn(host, port, fleet, router):
        fb0 = METRICS.get_counter("router.handoff_fallbacks")
        status, _, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": reqs[0][0], "max_tokens": reqs[0][1]},
        )
        body = json.loads(raw)
        assert status == 200, body
        assert body["choices"][0]["text"] == wants[reqs[0][0]]
        assert rule.fired >= 2  # initial attempt + >= 1 retry, all rejected
        assert METRICS.get_counter("router.handoff_fallbacks") > fb0
        _audit_all(fleet)

    run_with_disagg_fleet(tiny, 1, 1, fn, faults=plane,
                          srv_kw=dict(xfer_max_retries=1,
                                      xfer_attempt_s=2.0))


def test_transfer_stall_past_deadline_falls_back_colocated(warmed):
    tiny = warmed
    """A transfer stalled past the router's handoff deadline degrades to
    colocated prefill — the client sees only (slightly later) exact
    bytes."""
    plane = FaultPlane()
    rule = plane.add("xfer.send", "delay", when="1", arg=5.0)
    reqs = [(LONG + "stalled leg!", 8)]
    wants = expected_texts(tiny, reqs)

    async def fn(host, port, fleet, router):
        fb0 = METRICS.get_counter("router.handoff_fallbacks")
        status, _, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": reqs[0][0], "max_tokens": reqs[0][1]},
        )
        body = json.loads(raw)
        assert status == 200, body
        assert body["choices"][0]["text"] == wants[reqs[0][0]]
        assert rule.fired == 1
        assert METRICS.get_counter("router.handoff_fallbacks") > fb0
        _audit_all(fleet)

    run_with_disagg_fleet(tiny, 1, 1, fn, faults=plane,
                          router_kw=dict(handoff_deadline_s=1.0))


def test_prefill_crash_mid_handoff_falls_back_colocated(warmed):
    tiny = warmed
    """The prefill replica dies ABRUPTLY serving the handoff (sockets
    severed unflushed): the router observes the reset, degrades to
    colocated prefill, and the request completes exactly.  With the
    prefill tier dead, LATER requests skip the handoff plane entirely
    (no_prefill_replica) and still complete exactly."""
    plane = FaultPlane()
    rule = plane.add("prefill.crash", "close", when="1")
    # Distinct first pages: request 2 must attempt its OWN handoff (a
    # shared leading page would be affinity-warm and skip the plane).
    reqs = [("crash victim " + LONG, 8), ("after crash! " + LONG, 8)]
    wants = expected_texts(tiny, reqs)

    async def fn(host, port, fleet, router):
        fb0 = METRICS.get_counter("router.handoff_fallbacks")
        for p, n in reqs:
            status, _, raw = await _request(
                host, port, "POST", "/v1/completions",
                {"prompt": p, "max_tokens": n},
            )
            body = json.loads(raw)
            assert status == 200, body
            assert body["choices"][0]["text"] == wants[p], p
        assert rule.fired == 1
        assert METRICS.get_counter("router.handoff_fallbacks") - fb0 >= 2
        # The probe loop marks the self-killed prefill replica unhealthy;
        # completions keep flowing off the decode tier regardless.
        for _ in range(200):
            if fleet["p0"].state != "healthy":
                break
            await asyncio.sleep(0.02)
        assert fleet["p0"].state != "healthy"
        _audit_all(fleet)

    run_with_disagg_fleet(tiny, 1, 1, fn, faults=plane)


# -- import-plane unit invariants -------------------------------------------


def test_kv_import_partial_overlap_allocates_only_missing(warmed):
    tiny = warmed
    """A transfer whose digest chain PARTIALLY overlaps already-resident
    content imports only the missing pages: no capacity demanded for
    pages it does not need, no scatter for content that would lose
    first-writer-wins, full duplicates absorbed with zero pool work —
    and the pool audits clean throughout."""
    import numpy as np

    from distributed_llms_tpu.runtime.pages import PrefixCache

    b = _replica_batcher(tiny)
    l, _nb, blk, kvh, hd = b.cache.k.shape
    ids_a = list(range(1, 2 * PAGE + 1))          # pages A1, A2
    ids_b = ids_a[:PAGE] + list(range(100, 100 + PAGE))  # A1 shared, B2 new
    dig_a = PrefixCache.page_digests(ids_a, PAGE, 2)
    dig_b = PrefixCache.page_digests(ids_b, PAGE, 2)
    assert dig_a[0] == dig_b[0] and dig_a[1] != dig_b[1]

    def payload(seed):
        shape = (l, 2, blk, kvh, hd)
        k = np.full(shape, float(seed), np.float32)
        return k, k + 1.0

    results = []
    imp0 = METRICS.get_counter("batcher.kv_pages_imported")
    ka, va = payload(1)
    b.submit_kv_import(dig_a, ka, va, lambda ok, r: results.append((ok, r)))
    b._drain_kv_imports()
    assert results[-1] == (True, "imported")
    after_a = b.pool.stats()  # A1+A2 parked content-cached in the LRU
    assert after_a["cached_pages"] == 2
    kb, vb = payload(2)
    b.submit_kv_import(dig_b, kb, vb, lambda ok, r: results.append((ok, r)))
    b._drain_kv_imports()
    assert results[-1] == (True, "imported")
    # Only B2 allocated: exactly one page moved free -> content-cached.
    after_b = b.pool.stats()
    assert after_b["free_pages"] == after_a["free_pages"] - 1
    assert after_b["cached_pages"] == after_a["cached_pages"] + 1
    assert METRICS.get_counter("batcher.kv_pages_imported") - imp0 == 3
    # Exact duplicate: zero pool work, acked as such.
    b.submit_kv_import(dig_a, ka, va, lambda ok, r: results.append((ok, r)))
    b._drain_kv_imports()
    assert results[-1] == (True, "duplicate")
    assert b.pool.stats() == after_b
    b.assert_pool_consistent()


# -- THE chaos acceptance test ----------------------------------------------


def _disagg_storm(warmed, n_req, n_new):
    tiny = warmed
    # Distinct first pages so every request attempts its own handoff
    # (shared leading pages would be affinity-warm after the first).
    reqs = [(f"storm {i:02d} " + LONG, n_new) for i in range(n_req)]
    wants = expected_texts(tiny, reqs)
    plane = FaultPlane()
    # One prefill replica crashes abruptly mid-handoff, one transfer
    # frame is corrupted in flight (retry heals it), one transfer stalls
    # past the handoff deadline (degrades to colocated) — all while the
    # storm runs at ~1.5x the decode tier's pool capacity.
    crash = plane.add("prefill.crash", "close", when="2")
    corrupt = plane.add("xfer.send", "corrupt", when="3")
    stall = plane.add("xfer.send", "delay", when="5", arg=6.0)

    async def one(host, port, i, p, n):
        if i % 5 == 4:  # a streamed minority rides along
            reader, writer = await asyncio.open_connection(host, port)
            payload = json.dumps(
                {"prompt": p, "max_tokens": n, "stream": True}
            ).encode()
            writer.write(
                f"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
                f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload
            )
            await writer.drain()
            raw = await reader.read()
            writer.close()
            return ("sse", raw)
        return ("http", await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": p, "max_tokens": n},
        ))

    async def fn(host, port, fleet, router):
        fb0 = METRICS.get_counter("router.handoff_fallbacks")
        ho0 = METRICS.get_counter("router.handoffs")

        async def staggered(i, p, n):
            await asyncio.sleep(i * 0.06)
            return await one(host, port, i, p, n)

        tasks = [asyncio.create_task(staggered(i, p, n))
                 for i, (p, n) in enumerate(reqs)]
        outs = await asyncio.gather(*tasks)

        completed = shed = stream_failed = 0
        for (kind, out), (p, n) in zip(outs, reqs):
            if kind == "http":
                status, headers, raw = out
                body = json.loads(raw)
                if status == 200:
                    assert body["choices"][0]["text"] == wants[p], p
                    completed += 1
                else:
                    assert status in (429, 503), (status, body)
                    assert body["error"]["type"] in (
                        "overloaded_error", "engine_error",
                    ), body
                    assert int(headers["retry-after"]) >= 1
                    shed += 1
            else:
                head, _, text = out.decode().partition("\r\n\r\n")
                status_line = head.split("\r\n", 1)[0]
                if "200" not in status_line:
                    assert any(c in status_line for c in ("429", "503")), head
                    assert ("overloaded_error" in text
                            or "engine_error" in text), text
                    shed += 1
                elif "engine_error" in text:
                    stream_failed += 1
                else:
                    assert "[DONE]" in text, text
                    got = "".join(
                        json.loads(line[len("data: "):])["choices"][0]["text"]
                        for line in text.split("\n\n")
                        if line.startswith("data: ")
                        and not line.startswith("data: [DONE]")
                    )
                    assert got == wants[p], p
                    completed += 1
        assert completed + shed + stream_failed == n_req
        assert completed >= 3, (completed, shed, stream_failed)
        # Every armed drill actually fired, and every handoff failure was
        # COUNTED as a degradation (crash + stall at minimum; the
        # crashed prefill replica also costs later handoffs their tier
        # when it was the only one picked).
        assert crash.fired == 1, "prefill crash never fired"
        assert corrupt.fired >= 1, "frame corruption never fired"
        assert stall.fired >= 1, "transfer stall never fired"
        assert METRICS.get_counter("router.handoff_fallbacks") - fb0 >= 2
        assert METRICS.get_counter("router.handoffs") > ho0
        # Fleet steady state: surviving replicas drain, pools audit clean
        # on BOTH roles.
        for _ in range(400):
            if all(not h.inflight for h in fleet.replicas):
                break
            await asyncio.sleep(0.02)
        for h in fleet.replicas:
            if h.server._engine is not None and h.server._engine.is_alive():
                for _ in range(200):
                    if all(r.rid is None for r in h.server.batcher.rows):
                        break
                    await asyncio.sleep(0.05)
                h.server.batcher.assert_pool_consistent()
        alive_decode = [
            h for h in fleet.replicas if h.role == "decode"
            and h.server._engine is not None and h.server._engine.is_alive()
        ]
        assert len(alive_decode) == 2, "a decode replica died in the storm"

    run_with_disagg_fleet(tiny, 2, 2, fn, faults=plane,
                          router_kw=dict(handoff_deadline_s=2.5))


def test_chaos_disagg_storm(warmed):
    """ISSUE 7 acceptance: a 2-prefill + 2-decode fleet under storm
    survives one prefill crash mid-handoff, one corrupted transfer
    frame, and one stalled transfer — every completion byte-exact vs an
    unfaulted colocated reference, every handoff failure degraded to
    colocated prefill or a structured 429/503/engine_error, pool audits
    clean on both roles."""
    _disagg_storm(warmed, n_req=10, n_new=16)


@pytest.mark.slow
def test_chaos_disagg_storm_big(warmed):
    """The bigger storm variant: more offered load, same invariants."""
    _disagg_storm(warmed, n_req=18, n_new=24)
