"""HTTP serving gateway (runtime/server.py).

Strategy: a real asyncio server on an ephemeral port, driven by a raw
asyncio HTTP/SSE client (no client-library dependency — the same
fake-wire-but-real-sockets idea as the reference's protocol tests,
tests/network/test_protocol.py, upgraded from mocks to a live loopback).
Determinism: greedy sampling makes every response text equal the decode of
a solo batcher run on an identical fresh batcher.
"""

import asyncio
import json
import re

import jax
import pytest

from distributed_llms_tpu.core import observability
from distributed_llms_tpu.models import model as model_lib, presets
from distributed_llms_tpu.runtime.batcher import ContinuousBatcher
from distributed_llms_tpu.runtime.server import InferenceServer
from distributed_llms_tpu.runtime.tokenizer import ByteTokenizer


@pytest.fixture(scope="module")
def tiny():
    cfg = presets.get_preset("llama-tiny", vocab_size=512)
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def make_batcher(tiny, **kw):
    cfg, params = tiny
    tok = ByteTokenizer()
    kw.setdefault("batch_slots", 4)
    kw.setdefault("max_len", 96)
    kw.setdefault("chunk_steps", 4)
    return ContinuousBatcher(
        cfg, params, tokenizer=tok, eos_id=tok.eos_id, pad_id=tok.pad_id, **kw
    )


def expected_text(tiny, prompt: str, n_new: int) -> str:
    """Greedy reference: a solo run on a fresh identical batcher."""
    b = make_batcher(tiny)
    rid = b.submit(prompt, max_new_tokens=n_new)
    return b.tokenizer.decode(b.run()[rid])


async def _request(host, port, method, path, body=None, read_body=True):
    """Minimal HTTP/1.1 client.  Returns (status, raw_body_bytes)."""
    reader, writer = await asyncio.open_connection(host, port)
    payload = b"" if body is None else json.dumps(body).encode()
    writer.write(
        f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
        f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload
    )
    await writer.drain()
    status_line = await reader.readline()
    status = int(status_line.split()[1])
    while (await reader.readline()) not in (b"\r\n", b"\n", b""):
        pass
    data = await reader.read() if read_body else b""
    writer.close()
    return status, data


async def _sse_events(host, port, path, body):
    """POST and parse the SSE stream into a list of data payloads."""
    reader, writer = await asyncio.open_connection(host, port)
    payload = json.dumps(body).encode()
    writer.write(
        f"POST {path} HTTP/1.1\r\nHost: t\r\n"
        f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload
    )
    await writer.drain()
    status = int((await reader.readline()).split()[1])
    while (await reader.readline()) not in (b"\r\n", b"\n", b""):
        pass
    events = []
    while True:
        line = await reader.readline()
        if not line:
            break
        line = line.strip()
        if not line.startswith(b"data: "):
            continue
        data = line[len(b"data: "):]
        if data == b"[DONE]":
            events.append("[DONE]")
            break
        events.append(json.loads(data))
    writer.close()
    return status, events


def run_with_server(batcher, fn, **srv_kw):
    """Start an InferenceServer on an ephemeral port, run fn(host, port)."""

    async def driver():
        srv = InferenceServer(batcher, model_name="tiny", host="127.0.0.1",
                              port=0, **srv_kw)
        host, port = await srv.start()
        try:
            return await asyncio.wait_for(fn(host, port, srv), timeout=600)
        finally:
            await srv.stop()

    return asyncio.run(driver())


# -- basics ----------------------------------------------------------------


def test_health_models_metrics(tiny):
    async def fn(host, port, srv):
        # /healthz is a real readiness report now: JSON body, 200 only
        # while the engine thread is alive, unstalled, and not draining.
        status, body = await _request(host, port, "GET", "/healthz")
        assert status == 200
        health = json.loads(body)
        assert health["status"] == "ok"
        assert health["engine_alive"] is True
        assert health["draining"] is False
        assert health["engine_restarts"] == 0
        assert "seconds_since_last_chunk" in health
        # The backend the replica really runs on, and where its weights
        # sit (a failed TPU init is otherwise a silent CPU run).
        assert health["device"] == {
            "platform": "cpu", "device_kind": "cpu", "count": 8,
            "weights_on": [0],
        }
        status, body = await _request(host, port, "GET", "/v1/models")
        assert status == 200
        models = json.loads(body)
        assert models["data"][0]["id"] == "tiny"
        status, body = await _request(host, port, "GET", "/metrics")
        assert status == 200
        status, _ = await _request(host, port, "GET", "/nope")
        assert status == 404
        # Latency histograms appear after serving a request: TTFT (first
        # mailbox delivery) and end-to-end request duration.
        status, _ = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "hi", "max_tokens": 3},
        )
        assert status == 200
        _, body = await _request(host, port, "GET", "/metrics")
        assert b"server_ttft_seconds" in body
        assert b"server_request_seconds" in body

    run_with_server(make_batcher(tiny), fn)


def test_completion_matches_solo_run(tiny):
    want = expected_text(tiny, "hello", 8)

    async def fn(host, port, srv):
        status, body = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "hello", "max_tokens": 8},
        )
        assert status == 200
        out = json.loads(body)
        assert out["object"] == "text_completion"
        choice = out["choices"][0]
        assert choice["text"] == want
        assert choice["finish_reason"] in ("length", "stop")
        assert out["usage"]["prompt_tokens"] == len(
            ByteTokenizer().encode("hello")
        )
        assert out["usage"]["completion_tokens"] == 8

    run_with_server(make_batcher(tiny), fn)


def test_concurrent_requests_each_match_solo(tiny):
    prompts = ["alpha", "bravo bravo", "charlie!", "d"]
    wants = [expected_text(tiny, p, 6) for p in prompts]

    async def fn(host, port, srv):
        outs = await asyncio.gather(*[
            _request(host, port, "POST", "/v1/completions",
                     {"prompt": p, "max_tokens": 6})
            for p in prompts
        ])
        for (status, body), want in zip(outs, wants):
            assert status == 200
            assert json.loads(body)["choices"][0]["text"] == want

    run_with_server(make_batcher(tiny), fn)


def test_streaming_concatenates_to_blocking_text(tiny):
    want = expected_text(tiny, "stream me", 10)

    async def fn(host, port, srv):
        status, events = await _sse_events(
            host, port, "/v1/completions",
            {"prompt": "stream me", "max_tokens": 10, "stream": True},
        )
        assert status == 200
        assert events[-1] == "[DONE]"
        text = "".join(e["choices"][0]["text"] for e in events[:-1])
        assert text == want
        finals = [e for e in events[:-1]
                  if e["choices"][0]["finish_reason"] is not None]
        assert len(finals) == 1

    run_with_server(make_batcher(tiny), fn)


def test_chat_completion_and_stream(tiny):
    tok = ByteTokenizer()
    messages = [{"role": "user", "content": "hi"}]
    want = expected_text(tiny, tok.apply_chat_template(messages), 6)

    async def fn(host, port, srv):
        status, body = await _request(
            host, port, "POST", "/v1/chat/completions",
            {"messages": messages, "max_tokens": 6},
        )
        assert status == 200
        out = json.loads(body)
        assert out["object"] == "chat.completion"
        assert out["choices"][0]["message"] == {
            "role": "assistant", "content": want,
        }
        status, events = await _sse_events(
            host, port, "/v1/chat/completions",
            {"messages": messages, "max_tokens": 6, "stream": True},
        )
        assert status == 200
        assert events[0]["choices"][0]["delta"] == {"role": "assistant"}
        text = "".join(
            e["choices"][0]["delta"].get("content", "")
            for e in events[1:-1]
        )
        assert text == want

    run_with_server(make_batcher(tiny), fn)


# -- stop sequences and cancellation ---------------------------------------


def test_stop_sequence_truncates_and_frees_row(tiny):
    full = expected_text(tiny, "stopper", 24)
    # Random byte-level output decodes to few chars (ids >= 256 are dropped,
    # invalid UTF-8 collapses to U+FFFD) — use a mid-text single char as the
    # stop string and compute the expected cut the same way the server does.
    assert len(full) >= 2
    stop = full[len(full) // 2]
    want = full[: full.find(stop)]

    async def fn(host, port, srv):
        status, body = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "stopper", "max_tokens": 12, "stop": stop},
        )
        assert status == 200
        out = json.loads(body)
        assert out["choices"][0]["text"] == want
        assert out["choices"][0]["finish_reason"] == "stop"
        # The cancelled row must actually free: all slots empty soon after.
        for _ in range(100):
            if all(r.rid is None for r in srv.batcher.rows):
                break
            await asyncio.sleep(0.05)
        assert all(r.rid is None for r in srv.batcher.rows)
        assert not srv._cancelled

    run_with_server(make_batcher(tiny), fn)


def test_client_disconnect_cancels_row(tiny):
    async def fn(host, port, srv):
        reader, writer = await asyncio.open_connection(host, port)
        body = json.dumps({
            "prompt": "bye now", "max_tokens": 100, "stream": True,
        }).encode()
        writer.write(
            f"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
            f"Content-Length: {len(body)}\r\n\r\n".encode() + body
        )
        await writer.drain()
        await reader.readline()  # status line — generation is live
        # Read a couple of SSE lines so at least one delivery happened.
        for _ in range(6):
            await reader.readline()
        writer.close()
        await writer.wait_closed()
        # The server must notice the dead socket at the next delta write
        # and cancel the row; the long token budget means this only ends
        # quickly IF cancellation works.
        for _ in range(200):
            if all(r.rid is None for r in srv.batcher.rows) and not srv._requests:
                break
            await asyncio.sleep(0.05)
        assert all(r.rid is None for r in srv.batcher.rows)
        assert not srv._requests

    run_with_server(make_batcher(tiny, max_len=128), fn)


# -- request validation ----------------------------------------------------


def test_bad_requests_rejected(tiny):
    async def fn(host, port, srv):
        cases = [
            ({}, 400),                                      # no prompt
            ({"prompt": ""}, 400),
            ({"prompt": "x", "max_tokens": 0}, 400),
            ({"prompt": "x", "max_tokens": True}, 400),     # bool is not int
            ({"prompt": "x", "n": 9}, 400),              # n capped at 8
            ({"prompt": "x", "n": 0}, 400),
            ({"prompt": "x", "temperature": -0.1}, 400),
            ({"prompt": "x", "top_p": 0.0}, 400),
            ({"prompt": "x", "top_k": -1}, 400),
            ({"prompt": "x", "top_k": 1.5}, 400),
            ({"prompt": "x", "top_k": True}, 400),
            ({"prompt": "x", "top_k": 2**40}, 400),  # > int32: 400, not crash
            ({"prompt": "x", "prefix_cache": "yes"}, 400),
            ({"prompt": "x", "stop": ["a", "b", "c", "d", "e"]}, 400),
            ({"prompt": "x" * 500, "max_tokens": 8}, 400),  # exceeds max_len
            ({"prompt": "x", "prefix": "nope"}, 400),       # unknown prefix
        ]
        for body, want_status in cases:
            status, _ = await _request(host, port, "POST", "/v1/completions", body)
            assert status == want_status, body
        # Malformed JSON body.
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(
            b"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
            b"Content-Length: 5\r\n\r\n{oops"
        )
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        assert status == 400
        writer.close()
        # Per-request sampling rides the batcher's per-row path — top_k
        # included (no longer rejected as engine-wide).
        status, _ = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "ok", "max_tokens": 2, "temperature": 0.9,
             "top_p": 0.95, "top_k": 7},
        )
        assert status == 200

    run_with_server(make_batcher(tiny), fn)


def test_prefix_cache_usage_and_metrics(tiny):
    """Through a paged prefix-cache-enabled gateway: a repeated prompt's
    second request reports its cached prompt tokens in
    usage.prompt_tokens_details, text stays the deterministic greedy
    decode, the opt-out knob works, and the cache counters show on
    /metrics."""
    shared = "shared system prompt " * 3  # > one 16-token page of bytes
    prompt = shared + "tail"
    want = expected_text(tiny, prompt, 6)

    async def fn(host, port, srv):
        outs = []
        for body in (
            {"prompt": prompt, "max_tokens": 6},
            {"prompt": prompt, "max_tokens": 6},
            {"prompt": prompt, "max_tokens": 6, "prefix_cache": False},
        ):
            status, raw = await _request(
                host, port, "POST", "/v1/completions", body
            )
            assert status == 200
            outs.append(json.loads(raw))
        for out in outs:
            assert out["choices"][0]["text"] == want
        first, second, opted_out = outs
        assert first["usage"]["prompt_tokens_details"]["cached_tokens"] == 0
        assert second["usage"]["prompt_tokens_details"]["cached_tokens"] > 0
        assert opted_out["usage"]["prompt_tokens_details"]["cached_tokens"] == 0
        _, body = await _request(host, port, "GET", "/metrics")
        assert b"batcher_prefix_cache_hit_tokens" in body
        assert b"batcher_prefix_cache_lookups" in body

    run_with_server(
        make_batcher(tiny, max_len=96, paged_pages=19, page_size=16,
                     prefix_cache=True),
        fn,
    )


def test_chunked_body_rejected(tiny):
    async def fn(host, port, srv):
        # Only Content-Length bodies are read; chunked must fail loudly
        # (501), not as a misleading "'prompt' missing" 400.
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(
            b"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
            b"Transfer-Encoding: chunked\r\n\r\n"
        )
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        assert status == 501
        writer.close()

    run_with_server(make_batcher(tiny), fn)


def test_graceful_drain_finishes_in_flight(tiny):
    """stop(drain_timeout>0): new requests get 500 immediately, in-flight
    ones run to completion (full token budget, finish_reason length) —
    the SIGTERM semantics of dlt-serve --drain-timeout."""
    async def fn(host, port, srv):
        # 64 tokens of budget (~16 scheduling chunks) so the request is
        # reliably still in flight when the drain starts — 24 used to
        # complete inside one poll interval on a warm jit cache and flake
        # the srv._requests check below.
        req_task = asyncio.create_task(_request(
            host, port, "POST", "/v1/completions",
            {"prompt": "hello", "max_tokens": 64},
        ))
        for _ in range(500):  # wait until the request is registered
            if srv._requests:
                break
            await asyncio.sleep(0.01)
        assert srv._requests
        stop_task = asyncio.create_task(srv.stop(drain_timeout=60.0))
        await asyncio.sleep(0)  # let stop() flip _draining
        status_new, body_new = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "x", "max_tokens": 2},
        )
        # 503, not 500: load balancers treat it as retry-elsewhere.
        assert status_new == 503 and b"draining" in body_new
        status, body = await req_task
        assert status == 200
        out = json.loads(body)
        assert out["usage"]["completion_tokens"] == 64  # NOT cancelled
        await stop_task

    run_with_server(make_batcher(tiny), fn)


def test_force_stop_cuts_graceful_drain_short(tiny):
    """Second-SIGTERM semantics: force_stop() mid-drain cancels in-flight
    rows at their next chunk instead of letting them run to completion —
    the drain returns promptly and the client gets a PARTIAL response.
    A stall fault paces every chunk so the request is deterministically
    still in flight when the force-stop lands (a warm jit cache can
    otherwise finish 64 tokens inside the test's reaction time)."""
    from distributed_llms_tpu.runtime.faults import FaultPlane

    plane = FaultPlane.parse("batcher.decode:stall@1+:0.05")

    async def fn(host, port, srv):
        req_task = asyncio.create_task(_request(
            host, port, "POST", "/v1/completions",
            {"prompt": "linger", "max_tokens": 64},
        ))
        for _ in range(500):  # wait until the request is in flight
            if srv._requests:
                break
            await asyncio.sleep(0.01)
        assert srv._requests
        t0 = asyncio.get_running_loop().time()
        stop_task = asyncio.create_task(srv.stop(drain_timeout=60.0))
        await asyncio.sleep(0.05)  # the drain is now waiting on the request
        assert not stop_task.done()
        srv.force_stop()  # second SIGTERM: cut the drain short
        status, body = await req_task
        await asyncio.wait_for(stop_task, timeout=30)
        # Nowhere near the 60 s drain deadline.
        assert asyncio.get_running_loop().time() - t0 < 30
        assert status == 200
        out = json.loads(body)
        # Cancelled at a chunk boundary: fewer tokens than requested.
        assert 0 < out["usage"]["completion_tokens"] < 64

    run_with_server(make_batcher(tiny, max_len=128, faults=plane), fn)


def test_force_stop_with_just_queued_request(tiny):
    """Shutdown racing a just-queued request: the request lands in the
    batcher queue as force_stop() flips _stopping — the engine's stopping
    drain must still answer its mailbox (a structured shutdown error), not
    strand the handler forever."""
    from distributed_llms_tpu.runtime.server import _Mailbox

    async def fn(host, port, srv):
        rid = srv.batcher.next_rid
        mbox = _Mailbox()
        srv._requests[rid] = mbox
        assert srv.batcher.submit("raced", max_new_tokens=8) == rid
        srv.force_stop()  # immediate: skips the drain entirely
        srv._work.set()
        toks, done, err, _lps = await asyncio.wait_for(mbox.queue.get(), 10)
        assert done and err == "server is shutting down"
        srv._requests.pop(rid, None)

    run_with_server(make_batcher(tiny), fn)


def test_shutdown_drains_pending_request(tiny):
    from distributed_llms_tpu.runtime.server import _Mailbox

    async def fn(host, port, srv):
        # Emulate the shutdown race: a request lands in the batcher queue
        # just as stop() flips _stopping (so the stop()-time cancel sweep
        # missed it).  The engine's stopping path must fail it — without
        # the drain its mailbox would never be notified and the handler
        # would hang forever.
        rid = srv.batcher.next_rid
        mbox = _Mailbox()
        srv._requests[rid] = mbox
        assert srv.batcher.submit("hi", max_new_tokens=4) == rid
        srv._stopping = True
        srv._work.set()
        toks, done, err, _lps = await asyncio.wait_for(mbox.queue.get(), 10)
        assert done and err == "server is shutting down"
        srv._requests.pop(rid, None)

    run_with_server(make_batcher(tiny), fn)


def test_max_pending_backpressure(tiny):
    async def fn(host, port, srv):
        # Fill the in-flight table beyond the cap; the extras get 429.
        results = await asyncio.gather(*[
            _request(host, port, "POST", "/v1/completions",
                     {"prompt": f"req {i}", "max_tokens": 4})
            for i in range(6)
        ])
        statuses = sorted(s for s, _ in results)
        assert statuses.count(200) >= 2
        assert all(s in (200, 429) for s in statuses)

    run_with_server(make_batcher(tiny, batch_slots=2), fn, max_pending=2)


def test_token_id_prompt_and_prefix(tiny):
    b = make_batcher(tiny)
    b.register_prefix("sys", "system says: ")

    want_b = make_batcher(tiny)
    want_b.register_prefix("sys", "system says: ")
    rid = want_b.submit("query", max_new_tokens=5, prefix="sys")
    want = want_b.tokenizer.decode(want_b.run()[rid])

    async def fn(host, port, srv):
        # Raw token-id prompt.
        ids = ByteTokenizer().encode("raw ids")
        status, body = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": ids, "max_tokens": 3},
        )
        assert status == 200
        assert json.loads(body)["usage"]["prompt_tokens"] == len(ids)
        # Registered-prefix extension reuses the cached system-prompt KV.
        status, body = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "query", "max_tokens": 5, "prefix": "sys"},
        )
        assert status == 200
        assert json.loads(body)["choices"][0]["text"] == want

    run_with_server(b, fn)


def test_logprobs_blocking_and_stream(tiny):
    async def fn(host, port, srv):
        status, body = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "lp please", "max_tokens": 6, "logprobs": True},
        )
        assert status == 200
        out = json.loads(body)
        lp = out["choices"][0]["logprobs"]
        assert len(lp["tokens"]) == len(lp["token_logprobs"]) == 6
        assert all(v <= 1e-6 for v in lp["token_logprobs"])
        # Streaming: per-chunk logprob slices reassemble the same list.
        status, events = await _sse_events(
            host, port, "/v1/completions",
            {"prompt": "lp please", "max_tokens": 6, "logprobs": 0,
             "stream": True},
        )
        assert status == 200
        got = []
        for e in events[:-1]:
            f = e["choices"][0].get("logprobs")
            if f:
                got.extend(f["token_logprobs"])
        assert got == lp["token_logprobs"]
        # Chat shape.
        status, body = await _request(
            host, port, "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "x"}],
             "max_tokens": 3, "logprobs": True},
        )
        assert status == 200
        content = json.loads(body)["choices"][0]["logprobs"]["content"]
        assert len(content) == 3 and all("logprob" in c for c in content)
        # Top-alternative counts are not supported.
        status, _ = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "x", "logprobs": 3},
        )
        assert status == 400

    run_with_server(make_batcher(tiny), fn)


def test_n_choices_blocking_and_stream(tiny):
    want = expected_text(tiny, "multi", 5)

    async def fn(host, port, srv):
        # Greedy n=3: all choices identical to the solo run, indices 0..2.
        status, body = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "multi", "max_tokens": 5, "n": 3},
        )
        assert status == 200
        out = json.loads(body)
        assert [c["index"] for c in out["choices"]] == [0, 1, 2]
        assert all(c["text"] == want for c in out["choices"])
        assert out["usage"]["completion_tokens"] == 15
        # Streaming n=2: chunks carry per-choice indices; each choice's
        # concatenation equals the solo text, one finish per choice.
        status, events = await _sse_events(
            host, port, "/v1/completions",
            {"prompt": "multi", "max_tokens": 5, "n": 2, "stream": True},
        )
        assert status == 200
        texts = {0: "", 1: ""}
        finals = {0: 0, 1: 0}
        for e in events[:-1]:
            c = e["choices"][0]
            texts[c["index"]] += c["text"]
            finals[c["index"]] += c["finish_reason"] is not None
        assert texts == {0: want, 1: want}
        assert finals == {0: 1, 1: 1}
        # Validation.
        status, _ = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "x", "n": 9},
        )
        assert status == 400
        status, _ = await _request(
            host, port, "POST", "/v1/completions",
            {"prompt": "x", "n": 0},
        )
        assert status == 400

    run_with_server(make_batcher(tiny), fn)


# -- tracing: the slow-request record and per-delivery events ----------------


def test_debug_requests_lists_finished_requests(tiny):
    """GET /debug/requests: the batcher's finished ring as JSON, every
    field there, `n` bounding the answer."""
    fields = {"rid", "tenant", "prompt_tokens", "cached_tokens",
              "out_tokens", "pre_submit_ms", "queue_ms", "admit_ms",
              "decode_ms", "residencies", "finish",
              "deliveries", "max_gap_ms", "stalled_ms"}

    async def fn(host, port, srv):
        status, body = await _request(host, port, "GET", "/debug/requests")
        assert status == 200 and json.loads(body) == {"requests": []}
        for i, prompt in enumerate(["first one", "second"]):
            status, _ = await _request(
                host, port, "POST", "/v1/completions",
                {"prompt": prompt, "max_tokens": 5 + i, "tenant": "acme"},
            )
            assert status == 200
        status, body = await _request(host, port, "GET", "/debug/requests")
        recs = json.loads(body)["requests"]
        assert status == 200 and len(recs) == 2
        for rec, prompt, n in zip(recs, ["first one", "second"], [5, 6]):
            assert set(rec) == fields
            assert rec["tenant"] == "acme" and rec["finish"] == "length"
            assert rec["prompt_tokens"] == len(srv.batcher.tokenizer.encode(prompt))
            assert rec["out_tokens"] == n and rec["residencies"] == 1
            assert rec["pre_submit_ms"] > 0 and rec["decode_ms"] >= 0
            assert rec["deliveries"] >= 2 and rec["max_gap_ms"] > 0
            assert 0 <= rec["stalled_ms"] <= rec["decode_ms"]
        status, body = await _request(host, port, "GET", "/debug/requests?n=1")
        assert json.loads(body)["requests"] == recs[-1:]
        status, _ = await _request(host, port, "GET", "/debug/requests?n=x")
        assert status == 400
        # The gateway's own part of a request's time is a histogram too.
        status, body = await _request(host, port, "GET", "/metrics")
        assert b"server_pre_submit_seconds_count" in body
        assert b"batcher_queue_wait_seconds_count" in body
        # ... and first tokens, queue waits and the gap between deliveries
        # are counted an edge of the latency ladder, label-free.
        scraped = dict(line.split(" ") for line in body.decode().splitlines()
                       if re.fullmatch(r"[A-Za-z_:][\w:]* \S+", line))
        top = observability.LATENCY_EDGES_US[-1]
        assert float(scraped[f"server_ttft_seconds_le_us_{top}"]) == \
            float(scraped["server_ttft_seconds_count"]) >= 2
        assert float(scraped[f"batcher_queue_wait_seconds_le_us_{top}"]) >= 2
        assert float(scraped[f"batcher_row_gap_seconds_le_us_{top}"]) == \
            float(scraped["batcher_row_gap_seconds_count"]) >= 2
        assert not [k for k in scraped if "_le_us_" in k and not k.startswith(
            ("server_ttft_seconds_", "batcher_queue_wait_seconds_",
             "batcher_row_gap_seconds_"))]
        assert b"server_engine_idle_seconds_count" in body

    run_with_server(make_batcher(tiny), fn)


class _MuteTokenizer(ByteTokenizer):
    """Every token decodes to no text: what a real vocabulary gives under
    random weights (a byte of a multi-byte character)."""

    def decode(self, ids):
        return ""


def test_stream_with_logprobs_has_an_event_per_delivery(tiny):
    """With logprobs asked, a delivery that carries tokens is an SSE event
    even when its text delta is empty: a client can time every delivery
    (and the first).  Without logprobs the stream keeps its old shape:
    nothing to say, one final event."""
    cfg, params = tiny
    tok = _MuteTokenizer()
    b = ContinuousBatcher(cfg, params, tokenizer=tok, eos_id=-1,
                          pad_id=tok.pad_id, batch_slots=2, max_len=96,
                          chunk_steps=4)

    async def fn(host, port, srv):
        deliveries = []
        real = srv._deliver

        def spy(rid, toks, done, lps=None):
            deliveries.append(len(toks))
            return real(rid, toks, done, lps)

        srv._deliver = spy
        status, events = await _sse_events(
            host, port, "/v1/completions",
            {"prompt": "abc", "max_tokens": 9, "logprobs": True,
             "stream": True},
        )
        assert status == 200 and events[-1] == "[DONE]"
        carried = [len(e["choices"][0]["logprobs"]["tokens"])
                   for e in events[:-1]]
        assert all(e["choices"][0]["text"] == "" for e in events[:-1])
        assert sum(carried) == 9
        # Admission token, then chunk by chunk; the last event is the
        # finish, which carries whatever the done delivery brought.
        with_tokens = [n for n in deliveries if n]
        assert len(with_tokens) >= 3
        assert [n for n in carried if n] == with_tokens
        assert events[-2]["choices"][0]["finish_reason"] == "length"
        assert all(e["choices"][0]["finish_reason"] is None
                   for e in events[:-2])
        status, events = await _sse_events(
            host, port, "/v1/completions",
            {"prompt": "abc", "max_tokens": 9, "stream": True},
        )
        assert status == 200 and len(events) == 2      # finish + [DONE]

    run_with_server(b, fn)
