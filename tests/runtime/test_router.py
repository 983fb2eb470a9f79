"""Replica-fleet serving (runtime/router.py + cluster/fleet.py).

The acceptance contract pinned here, one level up from PR 2's in-process
supervisor: a fleet of N independent server/batcher replicas behind the
health-aware router survives replica CRASH (abrupt, unflushed), engine
STALL past the watchdog, network PARTITION, and rolling DRAIN/RESPAWN —
and through all of it every request that completes is temp-0 byte-exact
(zero-streamed requests re-admit VERBATIM on a healthy replica) and every
request that fails carries a structured, retryable error: 429/503 +
Retry-After before any bytes streamed, an in-stream ``engine_error`` event
after (deltas cannot be retracted).  Surviving replicas' page pools audit
clean afterward.

Also here: placement policy (least committed-token load, prefix-cache
session affinity with a load-spill guard, the ``router.place`` veto site)
and ``ServingClient``'s client-side multi-endpoint failover.
"""

import asyncio
import json
import threading

import pytest

import jax

from distributed_llms_tpu.cluster.client import ServingClient
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


def _replica_batcher(tiny):
    cfg, params = tiny
    tok = ByteTokenizer()
    return ContinuousBatcher(
        cfg, params, tokenizer=tok, eos_id=tok.eos_id, pad_id=tok.pad_id,
        batch_slots=2, max_len=96, chunk_steps=4,
        paged_pages=8, page_size=PAGE, prefix_cache=True,
    )


@pytest.fixture(scope="module")
def warmed(tiny):
    """Warm the process-wide jit cache with the replicas' exact program
    shapes (paged admission across the prompt buckets, cache-hit
    admission, decode): replicas then serve first requests in
    milliseconds, so the fast watchdogs these tests run never mistake a
    cold compile for a wedged engine."""
    b = _replica_batcher(tiny)
    for prompt in ("warm short", "a much longer warming prompt xxxx",
                   "warm short"):  # repeat: cache-hit admission path
        b.submit(prompt, max_new_tokens=4)
        b.run()
    return tiny


def server_factory(tiny, **srv_kw):
    """() -> a fresh, unstarted replica: full server/batcher stack with
    its own supervisor and a small paged pool (7 usable pages = 112
    tokens).  The watchdog is slack; a stall drill sets its victim's to
    ``FAST_WATCHDOG``.  At 0.4 s for every replica it took a first chunk
    on a busy CPU for a wedged engine, marked the survivors unhealthy too
    and answered 503 (4 of 22 runs of this file beside five busy workers,
    PR 30)."""
    srv_kw.setdefault("watchdog_timeout_s", 60.0)

    def make_server():
        return InferenceServer(
            _replica_batcher(tiny), model_name="tiny", host="127.0.0.1",
            port=0, batcher_factory=lambda: _replica_batcher(tiny), **srv_kw,
        )

    return make_server


FAST_WATCHDOG = 0.4  # seconds: a stall drill's victim flips /healthz quickly


def run_with_fleet(tiny, n, fn, faults=None, srv_kw=None, router_kw=None):
    """Boot an n-replica fleet + router, wait until every replica probes
    healthy, run ``fn(host, port, fleet, router)``, tear down."""

    async def driver():
        fleet = ReplicaFleet(
            [server_factory(tiny, **(srv_kw or {}))] * n,
            probe_interval_s=0.05, probe_timeout_s=2.0, faults=faults,
        )
        router = ReplicaRouter(
            fleet, host="127.0.0.1", port=0, tokenizer=ByteTokenizer(),
            page_size=PAGE, faults=faults, **(router_kw or {}),
        )
        await fleet.start()
        host, port = await router.start()
        try:
            for _ in range(200):
                if all(h.state == "healthy" for h in fleet.replicas):
                    break
                await asyncio.sleep(0.02)
            assert all(h.state == "healthy" for h in fleet.replicas)
            return await asyncio.wait_for(
                fn(host, port, fleet, router), timeout=600
            )
        finally:
            await router.stop()
            await fleet.stop()

    return asyncio.run(driver())


async def _request(host, port, method, path, body=None):
    """Raw request; returns (status, headers dict, body bytes)."""
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
    """Reference texts from one roomy, un-faulted batcher (exactness is
    batching- and replica-invariant at temperature 0)."""
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


async def _wait_inflight(fleet):
    """The replica currently holding >= 1 in-flight router request."""
    for _ in range(1000):
        for h in fleet.replicas:
            if h.inflight and h.state == "healthy":
                return h
        await asyncio.sleep(0.005)
    raise AssertionError("no request ever went in flight")


# -- placement --------------------------------------------------------------


def test_placement_prefix_affinity_and_least_load(warmed):
    tiny = warmed
    """Same-prefix traffic sticks to the replica that already holds the
    pages (affinity hit counter moves); disjoint traffic balances to the
    least-committed replica."""
    shared = "shared system prompt! " * 2  # > 1 full 16-token page
    reqs = [(shared + "tail one", 4), (shared + "tail two", 4),
            ("completely different", 4)]
    wants = expected_texts(tiny, reqs)

    async def fn(host, port, fleet, router):
        hits0 = METRICS.get_counter("router.affinity_hits")
        for p, n in reqs:
            status, _, raw = await _request(
                host, port, "POST", "/v1/completions",
                {"prompt": p, "max_tokens": n},
            )
            body = json.loads(raw)
            assert status == 200, body
            assert body["choices"][0]["text"] == wants[p], p
        # Request 2 shared request 1's full first page: affinity hit.
        assert METRICS.get_counter("router.affinity_hits") > hits0
        assert router._affinity  # digests recorded for future placement

    run_with_fleet(tiny, 2, fn)


def test_affinity_invalidated_after_respawn(warmed):
    tiny = warmed
    """Affinity hygiene: a drained/respawned replica comes back with a
    COLD pool and prefix cache — affinity entries recorded against its
    previous life (epoch) must read as misses, so stale stickiness can
    never beat least-loaded placement at a cache that no longer holds
    the pages."""
    shared = "sticky system prompt!! " * 2  # > 1 full 16-token page
    reqs = [(shared + "aaa", 4), (shared + "bbb", 4)]
    wants = expected_texts(tiny, reqs)

    async def fn(host, port, fleet, router):
        status, _, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": reqs[0][0], "max_tokens": 4},
        )
        assert status == 200
        assert json.loads(raw)["choices"][0]["text"] == wants[reqs[0][0]]
        digests = router._digests(ByteTokenizer().encode(reqs[1][0]))
        sticky = {router._affinity_lookup(d) for d in digests} - {None}
        assert sticky, "placement never recorded affinity"
        (name,) = sticky
        # Drain + respawn the sticky replica: fresh pool, bumped epoch.
        await fleet.drain(name, drain_timeout_s=15.0)
        assert fleet[name].restarts == 1
        # Every entry pointing at the old life now reads as a miss (and
        # is dropped), rather than steering traffic at a cold cache.
        assert all(router._affinity_lookup(d) is None for d in digests)
        hits0 = METRICS.get_counter("router.affinity_hits")
        status, _, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": reqs[1][0], "max_tokens": 4},
        )
        assert status == 200
        assert json.loads(raw)["choices"][0]["text"] == wants[reqs[1][0]]
        # The shared-prefix request placed WITHOUT a (stale) affinity hit.
        assert METRICS.get_counter("router.affinity_hits") == hits0

    run_with_fleet(tiny, 2, fn)


def test_router_place_drop_vetoes_choice(warmed):
    tiny = warmed
    """A ``router.place ... drop`` rule vetoes the chosen replica: the
    request spills to the next-best candidate and still completes."""
    plane = FaultPlane()
    rule = plane.add("router.place", "drop", when="1")
    wants = expected_texts(tiny, [("veto me", 4)])

    async def fn(host, port, fleet, router):
        status, _, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "veto me", "max_tokens": 4},
        )
        assert status == 200
        assert json.loads(raw)["choices"][0]["text"] == wants["veto me"]
        assert rule.fired == 1

    run_with_fleet(tiny, 2, fn, faults=plane)


# -- exact failover ---------------------------------------------------------


def test_crash_failover_zero_streamed_exact(warmed):
    tiny = warmed
    """A replica killed abruptly mid-request: the zero-streamed (buffered)
    request is re-sent verbatim to the surviving replica and completes
    with byte-exact temp-0 text; the failover is counted and timed."""
    reqs = [("failover target request", 32)]
    wants = expected_texts(tiny, reqs)

    async def fn(host, port, fleet, router):
        f0 = METRICS.get_counter("router.failovers")
        task = asyncio.create_task(_request(
            host, port, "POST", "/v1/completions",
            {"prompt": reqs[0][0], "max_tokens": reqs[0][1]},
        ))
        victim = await _wait_inflight(fleet)
        await fleet.kill(victim.name)
        status, headers, raw = await task
        body = json.loads(raw)
        assert status == 200, body
        assert body["choices"][0]["text"] == wants[reqs[0][0]]
        assert METRICS.get_counter("router.failovers") > f0
        rec = METRICS.snapshot()["histograms"].get("router.failover_seconds")
        assert rec and rec["count"] >= 1
        # The survivor's pool audits clean.
        for h in fleet.replicas:
            if h.state != "dead":
                h.server.batcher.assert_pool_consistent()

    run_with_fleet(tiny, 2, fn)


def test_chaos_crash_close_drill_fails_over_exact(warmed):
    tiny = warmed
    """The fleet's own chaos site: a ``replica.crash ... close`` rule
    kills the in-flight replica at the next probe tick (no direct
    fleet.kill from the test) — the zero-streamed request re-sends
    verbatim to the survivor and completes byte-exact.

    The drill needs the request still in flight when the tick lands, and
    32 tokens of a warmed tiny model take less than one 50 ms probe
    interval on an idle CPU (the request finished first and nothing
    failed over: this test failed in every driver run from PR 21 to
    PR 29).  So no clock decides it: every replica's decode chunks wait on
    ``held`` until the rule has fired, and the test waits on the rule,
    bounded by run_with_fleet's own limit and by nothing shorter."""
    plane = FaultPlane()
    reqs = [("chaos crash request", 32)]
    wants = expected_texts(tiny, reqs)
    held = threading.Event()

    class HoldDecode(FaultPlane):
        def fire(self, site, *args, **kw):
            if site == "batcher.decode":
                held.wait(120.0)
            return super().fire(site, *args, **kw)

    async def fn(host, port, fleet, router):
        f0 = METRICS.get_counter("router.failovers")
        for h in fleet.replicas:
            h.server.batcher.faults = HoldDecode()
        try:
            task = asyncio.create_task(_request(
                host, port, "POST", "/v1/completions",
                {"prompt": reqs[0][0], "max_tokens": reqs[0][1]},
            ))
            victim = await _wait_inflight(fleet)
            rule = plane.add("replica.crash", "close", when="1",
                             tag=victim.name)
            while not rule.fired:  # the kill lands at the next probe tick
                await asyncio.sleep(0.01)
        finally:
            held.set()
        status, _, raw = await task
        body = json.loads(raw)
        assert status == 200, body
        assert body["choices"][0]["text"] == wants[reqs[0][0]]
        assert METRICS.get_counter("router.failovers") > f0
        assert victim.state == "dead"
        for h in fleet.replicas:
            if h.state != "dead":
                h.server.batcher.assert_pool_consistent()

    run_with_fleet(tiny, 2, fn, faults=plane)


def test_stall_past_watchdog_fails_over(warmed):
    tiny = warmed
    """A replica whose engine wedges past the watchdog flips its own
    /healthz unhealthy; the fleet probe aborts the in-flight proxy and the
    zero-streamed request completes exactly on the other replica."""
    plane = FaultPlane()
    reqs = [("stalled engine request", 32)]
    wants = expected_texts(tiny, reqs)

    async def fn(host, port, fleet, router):
        f0 = METRICS.get_counter("router.failovers")
        # Both replicas idle -> the first placement deterministically goes
        # least-loaded by name: r0.  Wedge r0's engine 2s (watchdog 0.4s)
        # BEFORE sending, so its FIRST decode chunk stalls: /healthz flips
        # stalled, the probe marks it unhealthy, the proxy aborts.
        victim = fleet["r0"]
        victim.server.watchdog_timeout_s = FAST_WATCHDOG
        rule = plane.add("replica.stall", "delay", when="1", arg=2.0,
                         tag="r0")
        for _ in range(200):  # the wedge arms at the next probe tick
            if rule.fired:
                break
            await asyncio.sleep(0.01)
        assert rule.fired == 1
        status, _, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": reqs[0][0], "max_tokens": reqs[0][1]},
        )
        body = json.loads(raw)
        assert status == 200, body
        assert body["choices"][0]["text"] == wants[reqs[0][0]]
        assert METRICS.get_counter("router.failovers") > f0
        # The stalled replica heals once the wedge passes.
        for _ in range(400):
            if victim.state == "healthy":
                break
            await asyncio.sleep(0.02)
        assert victim.state == "healthy"
        victim.server.batcher.assert_pool_consistent()

    run_with_fleet(tiny, 2, fn, faults=plane)


def test_partition_fails_over_and_heals(warmed):
    tiny = warmed
    """A partitioned replica (unreachable from the router, engine alive):
    its in-flight request migrates, placement avoids it, and it returns to
    rotation when the partition heals."""
    plane = FaultPlane()
    reqs = [("partitioned request", 32)]
    wants = expected_texts(tiny, reqs)

    async def fn(host, port, fleet, router):
        f0 = METRICS.get_counter("router.failovers")
        # Slow r0's decode (50ms per chunk) so the request reliably spans
        # several probe ticks — the partition then lands MID-FLIGHT.
        fleet["r0"].server.batcher.faults = FaultPlane.parse(
            "batcher.decode:stall@1+:0.05"
        )
        task = asyncio.create_task(_request(
            host, port, "POST", "/v1/completions",
            {"prompt": reqs[0][0], "max_tokens": reqs[0][1]},
        ))
        victim = await _wait_inflight(fleet)
        assert victim.name == "r0"  # deterministic least-loaded tiebreak
        plane.add("replica.partition", "drop", when="1", arg=0.8,
                  tag=victim.name)
        status, _, raw = await task
        assert status == 200
        assert json.loads(raw)["choices"][0]["text"] == wants[reqs[0][0]]
        assert METRICS.get_counter("router.failovers") > f0
        now = asyncio.get_running_loop().time()
        assert not victim.routable(now), "partitioned replica stayed routable"
        for _ in range(400):
            now = asyncio.get_running_loop().time()
            if victim.routable(now):
                break
            await asyncio.sleep(0.02)
        assert victim.routable(now), "partition never healed"

    run_with_fleet(tiny, 2, fn, faults=plane)


def test_streamed_failure_is_structured_engine_error(warmed):
    tiny = warmed
    """A replica dying after SSE deltas reached the client cannot fail
    over (deltas are irretractable): the stream ends with a structured
    engine_error event — the PR-2 mailbox contract one level up."""

    async def fn(host, port, fleet, router):
        # Slow r0's decode so the kill reliably lands mid-stream.
        fleet["r0"].server.batcher.faults = FaultPlane.parse(
            "batcher.decode:stall@1+:0.05"
        )
        reader, writer = await asyncio.open_connection(host, port)
        payload = json.dumps({
            "prompt": "stream then die", "max_tokens": 64, "stream": True,
        }).encode()
        writer.write(
            f"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload
        )
        await writer.drain()
        victim = await _wait_inflight(fleet)
        assert victim.name == "r0"
        # Wait for the first SSE data bytes (the router held headers until
        # real payload, so anything readable means deltas flowed).
        first = await reader.read(512)
        assert b"data:" in first
        await fleet.kill(victim.name)
        rest = await reader.read()
        writer.close()
        text = (first + rest).decode()
        assert "engine_error" in text, text
        # The structured error TERMINATES the stream: no completion
        # sentinel may follow it (a [DONE] after the error would tell
        # clients the truncated output completed normally).
        assert "[DONE]" not in text.split("engine_error", 1)[-1], text
        assert METRICS.get_counter("router.failed_streamed") >= 1

    run_with_fleet(tiny, 2, fn)


# -- rolling drain/respawn --------------------------------------------------


def test_rolling_restart_zero_downtime(warmed):
    tiny = warmed
    """rolling_restart drains + respawns every replica one at a time
    while a steady trickle of requests keeps completing exactly — the
    zero-downtime fleet restart."""
    reqs = [(f"rolling req {i}", 6) for i in range(10)]
    wants = expected_texts(tiny, reqs)

    async def fn(host, port, fleet, router):
        outs = []

        async def trickle():
            for p, n in reqs:
                outs.append((p, await _request(
                    host, port, "POST", "/v1/completions",
                    {"prompt": p, "max_tokens": n},
                )))
                await asyncio.sleep(0.05)

        t = asyncio.create_task(trickle())
        await fleet.rolling_restart(drain_timeout_s=15.0)
        await t
        for p, (status, _h, raw) in outs:
            body = json.loads(raw)
            assert status == 200, (p, body)
            assert body["choices"][0]["text"] == wants[p], p
        assert all(h.restarts == 1 for h in fleet.replicas)
        assert all(h.state == "healthy" for h in fleet.replicas)
        for h in fleet.replicas:
            h.server.batcher.assert_pool_consistent()

    run_with_fleet(tiny, 2, fn)


# -- router front door ------------------------------------------------------


def test_router_healthz_metrics_and_no_replica_shed(warmed):
    tiny = warmed
    async def fn(host, port, fleet, router):
        status, _, raw = await _request(host, port, "GET", "/healthz")
        report = json.loads(raw)
        assert status == 200 and report["healthy"] == 2
        assert set(report["replicas"]) == {"r0", "r1"}
        # Kill the whole fleet: /healthz flips 503 and a completion sheds
        # structured + Retry-After instead of hanging.
        for h in list(fleet.replicas):
            await fleet.kill(h.name)
        status, headers, raw = await _request(host, port, "GET", "/healthz")
        assert status == 503
        assert int(headers["retry-after"]) >= 1
        status, headers, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "nobody home", "max_tokens": 4},
        )
        body = json.loads(raw)
        assert status == 503
        assert body["error"]["type"] == "overloaded_error"
        assert int(headers["retry-after"]) >= 1
        status, _, raw = await _request(host, port, "GET", "/metrics")
        text = raw.decode()
        for fam in ("router_placements", "router_replicas_healthy",
                    "router_replica_kills"):
            assert fam in text, fam

    run_with_fleet(tiny, 2, fn)


# -- client-side failover (ServingClient endpoints) -------------------------


def test_serving_client_endpoint_failover(warmed):
    tiny = warmed
    """ServingClient with an endpoints list fails over client-side: a
    dead endpoint rotates to the live one immediately (no backoff sleep
    against a severed socket)."""

    async def driver():
        s1 = server_factory(tiny)()
        s2 = server_factory(tiny)()
        h1, p1 = await s1.start()
        h2, p2 = await s2.start()
        try:
            await s1.kill()  # endpoint 1 is a dead socket
            client = ServingClient(
                endpoints=[(h1, p1), (h2, p2)], max_retries=4,
                backoff_base_s=0.05, backoff_cap_s=0.2,
            )
            status, body = await client.completions(
                {"prompt": "fail over to me", "max_tokens": 4}
            )
            assert status == 200, body
            assert client.failovers >= 1
            assert client.retries_taken == 0, "slept at a dead endpoint"
        finally:
            await s2.stop()

    asyncio.run(driver())


# -- THE chaos acceptance test ----------------------------------------------


def test_chaos_fleet_crash_stall_drain_storm(warmed):
    tiny = warmed
    """ISSUE 6 acceptance: a 3-replica fleet under >= 1.5x offered load
    survives one abrupt replica CRASH, one engine STALL past the watchdog,
    and one rolling DRAIN/RESPAWN — every completed request is temp-0
    byte-exact, every unstreamed failure is structured 429/503 with
    Retry-After, every streamed failure a structured engine_error event,
    and the page pool audits clean on every surviving replica."""
    n_req, n_new = 14, 24
    reqs = [(f"chaos storm request {i:02d}", n_new) for i in range(n_req)]
    wants = expected_texts(tiny, reqs)
    # Offered: 14 x (~22 prompt + 24 new) ~ 644 tokens vs 3 x 112 = 336
    # pool capacity ~ 1.9x.
    plane = FaultPlane()

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
        kills0 = METRICS.get_counter("router.replica_kills")

        async def staggered(i, p, n):
            await asyncio.sleep(i * 0.06)
            return await one(host, port, i, p, n)

        tasks = [asyncio.create_task(staggered(i, p, n))
                 for i, (p, n) in enumerate(reqs)]
        # Phase 1 — CRASH r0 once real work is in flight on it.
        for _ in range(1000):
            if fleet["r0"].inflight:
                break
            await asyncio.sleep(0.005)
        await fleet.kill("r0")
        # Phase 2 — STALL r1's engine past the watchdog (heals in 1.2s).
        await asyncio.sleep(0.1)
        fleet["r1"].server.watchdog_timeout_s = FAST_WATCHDOG
        plane.add("replica.stall", "delay", when="1", arg=1.2, tag="r1")
        for _ in range(600):  # wait for the stall to be observed + healed
            if fleet["r1"].state == "healthy" and plane.rules[-1].fired:
                break
            await asyncio.sleep(0.02)
        # Phase 3 — rolling DRAIN/RESPAWN of r2 while traffic continues.
        await fleet.drain("r2", drain_timeout_s=20.0)
        outs = await asyncio.gather(*tasks)

        completed = shed = stream_failed = 0
        for (kind, out), (p, n) in zip(outs, reqs):
            if kind == "http":
                status, headers, raw = out
                body = json.loads(raw)
                if status == 200:
                    assert body["choices"][0]["finish_reason"] == "length", body
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
                    # Shed before any stream began: plain structured
                    # 429/503 with Retry-After, same as the HTTP legs.
                    assert any(c in status_line for c in ("429", "503")), head
                    assert ("overloaded_error" in text
                            or "engine_error" in text), text
                    assert "retry-after" in head.lower(), head
                    shed += 1
                elif "engine_error" in text:
                    stream_failed += 1  # structured mid-stream failure
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
        assert METRICS.get_counter("router.replica_kills") - kills0 == 1
        assert plane.rules[-1].fired >= 1, "stall never fired"
        assert fleet["r2"].restarts == 1
        # The failover plane actually exercised.  (Recovery LATENCY is
        # stamped by the deterministic replica-failover bench row — in a
        # full storm a failed-over request may legitimately end shed when
        # the rest of the fleet is stalled/draining at that instant, so
        # the histogram sample is not guaranteed here.)
        assert METRICS.get_counter("router.failovers") >= 1
        # Fleet steady state: the two surviving replicas are healthy and
        # their pools audit clean once traffic drains.
        for _ in range(400):
            if all(not h.inflight for h in fleet.replicas):
                break
            await asyncio.sleep(0.02)
        survivors = [h for h in fleet.replicas if h.state != "dead"]
        assert {h.name for h in survivors} == {"r1", "r2"}
        for _ in range(400):  # probes flip survivors healthy as they drain
            if all(h.state == "healthy" for h in survivors):
                break
            await asyncio.sleep(0.02)
        for h in survivors:
            assert h.state == "healthy", (h.name, h.state)
            for _ in range(200):
                if all(r.rid is None for r in h.server.batcher.rows):
                    break
                await asyncio.sleep(0.05)
            h.server.batcher.assert_pool_consistent()

    run_with_fleet(tiny, 3, fn, faults=plane)
