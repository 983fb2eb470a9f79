"""Deterministic fault injection (runtime/faults.py) and the serving
supervisor built on it (runtime/server.py).

The acceptance contract pinned here is the crash-only one: with N in-flight
requests and an injected decode-step fault, the engine restarts
automatically, every zero-streamed request completes with temp-0 tokens
IDENTICAL to an uninjected run, partially-streamed requests receive a
structured error, the page pool audits clean afterward, and
``server_engine_restarts`` increments exactly once.  Plus: per-request
deadlines (finish_reason "timeout", rows verifiably freed) and the engine
watchdog flipping /healthz.
"""

import asyncio
import json

import jax
import pytest

from distributed_llms_tpu.core.observability import METRICS
from distributed_llms_tpu.models import model as model_lib, presets
from distributed_llms_tpu.runtime.batcher import ContinuousBatcher
from distributed_llms_tpu.runtime.faults import (
    FaultPlane, FaultRule, InjectedFault,
)
from distributed_llms_tpu.runtime.server import InferenceServer
from distributed_llms_tpu.runtime.tokenizer import ByteTokenizer


@pytest.fixture(scope="module")
def tiny():
    cfg = presets.get_preset("llama-tiny", vocab_size=512)
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def make_batcher(tiny, faults=None, **kw):
    cfg, params = tiny
    tok = ByteTokenizer()
    kw.setdefault("batch_slots", 2)
    kw.setdefault("max_len", 96)
    kw.setdefault("chunk_steps", 4)
    kw.setdefault("paged_pages", 13)
    kw.setdefault("page_size", 16)
    return ContinuousBatcher(
        cfg, params, tokenizer=tok, eos_id=tok.eos_id, pad_id=tok.pad_id,
        faults=faults, **kw
    )


def expected_text(tiny, prompt: str, n_new: int) -> str:
    b = make_batcher(tiny)
    rid = b.submit(prompt, max_new_tokens=n_new)
    return b.tokenizer.decode(b.run()[rid])


# -- spec grammar -----------------------------------------------------------


def test_parse_grammar():
    plane = FaultPlane.parse(
        "batcher.decode:raise@3,proto.send/HEARTBEAT:drop@2+,"
        "batcher.decode:stall@1:0.5,proto.recv:close@*"
    )
    assert [r.action for r in plane.rules] == ["raise", "drop", "stall", "close"]
    r0, r1, r2, r3 = plane.rules
    assert (r0.first, r0.last, r0.tag) == (3, 3, None)
    assert (r1.first, r1.last, r1.tag) == (2, None, "HEARTBEAT")
    assert (r2.arg, r2.first) == (0.5, 1)
    assert (r3.first, r3.last) == (1, None)
    # Round-trips through describe() -> parse().
    again = FaultPlane.parse(plane.describe())
    assert [r.describe() for r in again.rules] == \
        [r.describe() for r in plane.rules]
    assert FaultPlane.parse(None).rules == []
    assert FaultPlane.parse(" ").rules == []


def test_parse_rejects_malformed():
    for bad in ("decode", "decode:explode", "decode:raise@0",
                "decode:stall@1", ":raise", "decode:delay@2"):
        with pytest.raises(ValueError):
            FaultPlane.parse(bad)


def test_fire_windows_and_tags():
    plane = FaultPlane.parse("s:drop@2,s/T:drop@1+")
    # Untagged hits: only the windowed untagged rule counts them.
    assert plane.fire("s") is None          # hit 1: not due
    assert plane.fire("s").action == "drop"  # hit 2: fires
    assert plane.fire("s") is None          # hit 3: window passed
    # Tagged hits match BOTH rules; the first due rule wins.
    assert plane.fire("s", tag="T").action == "drop"
    assert plane.fire("s", tag="X") is None  # tag mismatch for rule 2
    assert plane.rules[1].fired == 1
    # add() arms mid-run.
    rule = plane.add("s", "drop", when="*")
    assert plane.fire("s").action == "drop"
    assert rule.fired == 1


def test_raise_and_stall_applied_by_fire():
    import time

    plane = FaultPlane.parse("a:raise@1,b:stall@1:0.05")
    with pytest.raises(InjectedFault, match="injected fault at a"):
        plane.fire("a")
    t0 = time.perf_counter()
    assert plane.fire("b").action == "stall"
    assert time.perf_counter() - t0 >= 0.05


# -- batcher-level injection ------------------------------------------------


def test_decode_raise_propagates_and_respawn_is_exact(tiny):
    want = expected_text(tiny, "hello", 8)
    plane = FaultPlane.parse("batcher.decode:raise@1")
    b = make_batcher(tiny, faults=plane)
    b.submit("hello", max_new_tokens=8)
    with pytest.raises(InjectedFault):
        b.run()
    # The crash-recovery primitive: a respawn rebuilds pool + caches fresh
    # and (the rule having fired) decodes the same request exactly.
    b2 = b.respawn()
    b2._next_rid = b._next_rid
    rid = b2.submit("hello", max_new_tokens=8)
    assert b2.tokenizer.decode(b2.run()[rid]) == want
    b2.assert_pool_consistent()
    assert plane.rules[0].fired == 1  # shared plane: fired stays fired


def test_admit_raise_propagates_and_respawn_is_exact(tiny):
    """A crash inside the admission round (batcher.admit) propagates out
    of run(); the respawned engine admits and decodes the same request
    exactly — the admission leg of the crash-recovery contract."""
    want = expected_text(tiny, "hello", 8)
    plane = FaultPlane.parse("batcher.admit:raise@1")
    b = make_batcher(tiny, faults=plane)
    b.submit("hello", max_new_tokens=8)
    with pytest.raises(InjectedFault):
        b.run()
    b2 = b.respawn()
    b2._next_rid = b._next_rid
    rid = b2.submit("hello", max_new_tokens=8)
    assert b2.tokenizer.decode(b2.run()[rid]) == want
    b2.assert_pool_consistent()
    assert plane.rules[0].fired == 1


def test_page_alloc_exhaust_backpressures_then_serves(tiny):
    """An injected dry pool takes the real back-pressure path (requeue,
    FIFO preserved) and the request completes exactly once the rule's
    window passes."""
    want = expected_text(tiny, "pool", 6)
    plane = FaultPlane.parse("batcher.page_alloc:exhaust@1")
    b = make_batcher(tiny, faults=plane)
    rid = b.submit("pool", max_new_tokens=6)
    res = b.run()
    assert b.tokenizer.decode(res[rid]) == want
    assert plane.rules[0].fired == 1
    b.assert_pool_consistent()


def test_pool_audit_catches_leaks(tiny):
    b = make_batcher(tiny)
    rid = b.submit("audit me", max_new_tokens=4)
    b.run()
    b.assert_pool_consistent()
    # Sabotage: a dangling refcount (the recovery-path leak class) and a
    # page missing from every partition must both fail the audit.
    page = b.free_pages.pop()
    with pytest.raises(AssertionError, match="leaked"):
        b.assert_pool_consistent()
    b.pool.page_refs[page] = 1
    with pytest.raises(AssertionError, match="diverge"):
        b.assert_pool_consistent()
    del b.pool.page_refs[page]
    b.free_pages.append(page)
    b.assert_pool_consistent()
    assert rid in b.results


# -- the serving supervisor -------------------------------------------------


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


def test_supervisor_restart_retries_and_fails_structured(tiny):
    """THE crash acceptance test (see module docstring)."""
    prompts = ["alpha", "bravo!", "charlie?", "delta d"]
    wants = {p: expected_text(tiny, p, 8) for p in prompts}
    # batch_slots=2: two requests admit (and stream their admission token)
    # before the first decode chunk crashes; the other two sit queued with
    # zero streamed tokens.
    plane = FaultPlane.parse("batcher.decode:raise@1")
    restarts0 = METRICS.get_counter("server.engine_restarts")
    retried0 = METRICS.get_counter("server.requests_retried")

    async def fn(host, port, srv):
        outs = await asyncio.gather(*[
            _request(host, port, "POST", "/v1/completions",
                     {"prompt": p, "max_tokens": 8})
            for p in prompts
        ])
        completed, errored = [], []
        for (status, raw), p in zip(outs, prompts):
            body = json.loads(raw)
            if status == 200:
                # Zero-streamed at crash time: re-admitted, temp-0 tokens
                # identical to the uninjected run.
                assert body["choices"][0]["text"] == wants[p], p
                completed.append(p)
            else:
                # Partially streamed: structured engine error.
                assert status == 500
                assert body["error"]["type"] == "engine_error", body
                assert "restarted" in body["error"]["message"]
                errored.append(p)
        assert len(completed) == 2 and len(errored) == 2, (completed, errored)
        # Exactly one restart; both retried requests counted.
        assert METRICS.get_counter("server.engine_restarts") - restarts0 == 1
        assert METRICS.get_counter("server.requests_retried") - retried0 == 2
        # The fresh pool audits clean once everything drained.
        for _ in range(100):
            if all(r.rid is None for r in srv.batcher.rows):
                break
            await asyncio.sleep(0.05)
        srv.batcher.assert_pool_consistent()
        # /healthz reports the restart and a healthy engine.
        status, raw = await _request(host, port, "GET", "/healthz")
        health = json.loads(raw)
        assert status == 200 and health["engine_restarts"] == 1

    run_with_server(make_batcher(tiny, faults=plane), fn)


def test_retry_budget_exhausts_to_structured_error(tiny):
    """A crash on EVERY chunk re-admits only max_request_retries times,
    then fails the request with the structured restart error instead of
    looping forever."""
    plane = FaultPlane.parse("batcher.decode:raise@1+")

    async def fn(host, port, srv):
        status, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "doomed", "max_tokens": 8},
        )
        # The request streamed its admission token before each crash, so
        # the FIRST restart already fails it partially-streamed — bounded
        # either way, never an infinite supervisor loop.
        assert status == 500
        assert json.loads(raw)["error"]["type"] == "engine_error"
        assert srv._restarts >= 1

    run_with_server(make_batcher(tiny, faults=plane), fn,
                    max_request_retries=1)


def test_request_timeout_returns_partial_and_frees_row(tiny):
    """Deadline acceptance: timeout_s expires mid-generation ->
    finish_reason "timeout" with the tokens produced so far, and the row's
    pages are verifiably freed afterward."""
    plane = FaultPlane.parse("batcher.decode:stall@1+:0.1")

    async def fn(host, port, srv):
        status, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "slow", "max_tokens": 64, "timeout_s": 0.25},
        )
        assert status == 200
        out = json.loads(raw)
        assert out["choices"][0]["finish_reason"] == "timeout"
        assert 0 < out["usage"]["completion_tokens"] < 64
        # The row must actually free (engine acked the deadline cancel).
        for _ in range(100):
            if all(r.rid is None for r in srv.batcher.rows):
                break
            await asyncio.sleep(0.05)
        assert all(r.rid is None for r in srv.batcher.rows)
        srv.batcher.assert_pool_consistent()

    run_with_server(make_batcher(tiny, faults=plane), fn)


def test_server_default_timeout_applies(tiny):
    plane = FaultPlane.parse("batcher.decode:stall@1+:0.1")

    async def fn(host, port, srv):
        status, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "slow", "max_tokens": 64},
        )
        assert status == 200
        assert json.loads(raw)["choices"][0]["finish_reason"] == "timeout"
        # Bad timeout values 400.
        for bad in (0, -1, "soon", True):
            status, _ = await _request(
                host, port, "POST", "/v1/completions",
                {"prompt": "x", "max_tokens": 2, "timeout_s": bad},
            )
            assert status == 400, bad

    run_with_server(make_batcher(tiny, faults=plane), fn,
                    request_timeout_s=0.25)


def test_timeout_of_queued_request_is_shed_503(tiny):
    """A request whose deadline expires while it is still QUEUED (slot
    held by another row) is SHED at the next chunk boundary — a 503 with
    Retry-After and a structured overloaded_error, NOT an empty 200
    "timeout": nothing was ever produced, so the client should retry
    elsewhere/later (PR 2 answered 200 here, admitted-doomed style)."""
    import time

    plane = FaultPlane.parse("batcher.decode:stall@1+:0.05")
    shed0 = METRICS.get_counter("server.requests_shed_total")

    async def fn(host, port, srv):
        long_task = asyncio.create_task(_request(
            host, port, "POST", "/v1/completions",
            {"prompt": "slot hog", "max_tokens": 48},
        ))
        for _ in range(500):
            if srv._requests:
                break
            await asyncio.sleep(0.01)
        assert srv._requests
        t0 = time.perf_counter()
        status, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "queued", "max_tokens": 8, "timeout_s": 0.2},
        )
        dt = time.perf_counter() - t0
        assert status == 503, raw
        out = json.loads(raw)
        assert out["error"]["type"] == "overloaded_error", out
        assert "shed" in out["error"]["message"]
        # Chunk-boundary shed, nowhere near the 10 s grace fallback.
        assert dt < 5.0, dt
        assert METRICS.get_counter("server.requests_shed_total") > shed0
        status, _ = await long_task
        assert status == 200

    run_with_server(make_batcher(tiny, batch_slots=1, faults=plane), fn)


def test_unrecoverable_engine_rejects_new_requests(tiny):
    """When the respawn itself fails, in-flight requests get the
    structured engine error, NEW requests get an immediate 500 instead of
    hanging on a dead queue, and /healthz goes (and stays) unhealthy."""
    plane = FaultPlane.parse("batcher.decode:raise@1")

    def bad_factory():
        raise RuntimeError("no memory left for a fresh pool")

    async def fn(host, port, srv):
        status, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "doomed", "max_tokens": 8},
        )
        assert status == 500
        assert json.loads(raw)["error"]["message"] == "engine unrecoverable"
        status, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "after the fall", "max_tokens": 2},
        )
        assert status == 500
        assert json.loads(raw)["error"]["type"] == "engine_error"
        status, raw = await _request(host, port, "GET", "/healthz")
        assert status == 503
        assert json.loads(raw)["engine_alive"] is False

    run_with_server(make_batcher(tiny, faults=plane), fn,
                    batcher_factory=bad_factory)


def test_watchdog_flips_healthz_on_stall(tiny):
    """A stalled engine (wedged chunk) with in-flight work flips /healthz
    unhealthy; it reports healthy again once the work drains."""
    plane = FaultPlane.parse("batcher.decode:stall@2:1.2")

    async def fn(host, port, srv):
        req_task = asyncio.create_task(_request(
            host, port, "POST", "/v1/completions",
            {"prompt": "wedge", "max_tokens": 16},
        ))
        unhealthy_seen = False
        for _ in range(100):
            status, raw = await _request(host, port, "GET", "/healthz")
            health = json.loads(raw)
            if status == 503 and health["engine_stalled"]:
                unhealthy_seen = True
                break
            await asyncio.sleep(0.05)
        assert unhealthy_seen, "watchdog never flipped /healthz"
        status, _ = await req_task
        assert status == 200
        for _ in range(100):
            status, raw = await _request(host, port, "GET", "/healthz")
            if status == 200:
                break
            await asyncio.sleep(0.05)
        assert status == 200

    run_with_server(make_batcher(tiny, faults=plane), fn,
                    watchdog_timeout_s=0.3)


def test_healthz_unhealthy_while_draining(tiny):
    async def fn(host, port, srv):
        status, raw = await _request(host, port, "GET", "/healthz")
        assert status == 200
        # An in-flight request holds the drain open long enough to observe
        # the draining state (an empty drain completes immediately).
        req_task = asyncio.create_task(_request(
            host, port, "POST", "/v1/completions",
            {"prompt": "hold the drain open", "max_tokens": 32},
        ))
        for _ in range(500):
            if srv._requests:
                break
            await asyncio.sleep(0.01)
        assert srv._requests
        stop_task = asyncio.create_task(srv.stop(drain_timeout=30.0))
        await asyncio.sleep(0)  # let stop() flip _draining
        status, raw = await _request(host, port, "GET", "/healthz")
        assert status == 503
        assert json.loads(raw)["status"] == "draining"
        status, _ = await req_task  # drains to completion
        assert status == 200
        await stop_task

    run_with_server(make_batcher(tiny), fn)


def test_streamed_timeout_carries_finish_reason(tiny):
    plane = FaultPlane.parse("batcher.decode:stall@1+:0.1")

    async def fn(host, port, srv):
        reader, writer = await asyncio.open_connection(host, port)
        body = json.dumps({"prompt": "slow", "max_tokens": 64,
                           "timeout_s": 0.25, "stream": True}).encode()
        writer.write(
            f"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
            f"Content-Length: {len(body)}\r\n\r\n".encode() + body
        )
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        assert status == 200
        finish = None
        while True:
            line = await reader.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            data = line[len(b"data: "):]
            if data == b"[DONE]":
                break
            ev = json.loads(data)
            fr = ev["choices"][0].get("finish_reason")
            if fr is not None:
                finish = fr
        writer.close()
        assert finish == "timeout"

    run_with_server(make_batcher(tiny, faults=plane), fn)


def test_watchdog_counts_batcher_held_rows(tiny):
    """The stall predicate must key on engine-held work, not just open
    HTTP handlers: once timed-out handlers answer their clients and leave
    _requests, a wedged engine still pins rows/pages — /healthz must keep
    reporting stalled rather than telling the load balancer "healthy"."""
    import time

    async def fn(host, port, srv):
        status, _ = await _request(host, port, "GET", "/healthz")
        assert status == 200
        # A wedged engine, reconstructed piecewise: a batcher-held row
        # with no open handler, and no progress for ages.
        srv.batcher.rows[0].rid = 12345
        srv._last_progress -= 10 * srv.watchdog_timeout_s
        status, raw = await _request(host, port, "GET", "/healthz")
        health = json.loads(raw)
        assert status == 503, health
        assert health["engine_stalled"] is True
        assert health["inflight_requests"] == 0
        # Row released + progress resumes -> healthy again.
        srv.batcher.rows[0].rid = None
        srv._last_progress = time.monotonic()
        status, _ = await _request(host, port, "GET", "/healthz")
        assert status == 200

    run_with_server(make_batcher(tiny), fn, watchdog_timeout_s=0.3)


def test_stop_hit_before_deadline_reports_stop(tiny):
    """A stop-sequence hit followed by the deadline expiring during the
    cancel-ack drain is a STOP, not a timeout: the response legitimately
    terminated before the deadline; only the row-free ack was late."""
    # The stop must first show in the FIRST decode chunk's delivery (the
    # admission's token, then chunk_steps=4 more), so that the chunk after
    # it carries the cancel's ack.  Which characters a random tiny model
    # emits changes with its initialisation (most ids of the 512 are not
    # text at all), so the stop is read off the expected stream and the
    # premise is asserted, not assumed: "halt", this test's prompt until
    # PR 30, had come to emit no text in its first chunk, and the test
    # failed as a timeout in every driver run from PR 21 to PR 29.
    prompt = "stop"
    head = expected_text(tiny, prompt, 1)
    first = expected_text(tiny, prompt, 5)
    assert first.startswith(head)
    stop = next((c for c in first[len(head):] if c not in head), None)
    assert stop is not None, (head, first)
    # Every chunk after the first (the ack carrier) stalls past the
    # deadline but inside the server's 10 s ack grace.  overlap=False,
    # because with dispatch-ahead the engine fires chunk 2's site BEFORE
    # it delivers chunk 1 and the stall would hold the stop itself back:
    # the order this test is about is fire 1, deliver 1, fire 2.  The
    # deadline is the server's wall clock and the first chunk shares a CPU
    # with five other test workers: 4 s it cannot miss where the old 0.6 s
    # could, and a stall longer than the deadline outlasts it wherever in
    # those 4 s the first chunk lands.
    plane = FaultPlane.parse("batcher.decode:stall@2+:5.0")

    async def fn(host, port, srv):
        status, raw = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": prompt, "max_tokens": 64, "timeout_s": 4.0,
             "stop": [stop]},
        )
        assert status == 200
        out = json.loads(raw)
        assert out["choices"][0]["finish_reason"] == "stop", out
        assert out["choices"][0]["text"] == first[:first.index(stop)]
        # The ack drained: row freed, pool clean.
        for _ in range(100):
            if all(r.rid is None for r in srv.batcher.rows):
                break
            await asyncio.sleep(0.05)
        assert all(r.rid is None for r in srv.batcher.rows)
        srv.batcher.assert_pool_consistent()

    run_with_server(make_batcher(tiny, faults=plane, overlap=False), fn)
