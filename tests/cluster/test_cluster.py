"""Control-plane tests: real asyncio sockets on localhost (the reference
faked its wire with mocked sockets, SURVEY §4; these run the actual stack),
plus fault-injection: dead-worker eviction and task retry — capabilities the
reference planned (plan.md:430-436) but never built.  The fault paths are
provoked DETERMINISTICALLY via runtime/faults.py (drop heartbeats, sever a
reply connection) instead of killing tasks and sleeping past wall-clock
deadlines."""

import asyncio
import json

import pytest

from distributed_llms_tpu.cluster import protocol
from distributed_llms_tpu.cluster.client import CoordinatorClient
from distributed_llms_tpu.cluster.coordinator import Coordinator
from distributed_llms_tpu.cluster.worker import WorkerHost
from distributed_llms_tpu.core.config import ClusterConfig, RuntimeConfig
from distributed_llms_tpu.runtime.faults import FaultPlane


def fast_cfg(**kw):
    return ClusterConfig(
        coordinator_host="127.0.0.1", coordinator_port=0,
        heartbeat_interval_s=0.2, heartbeat_timeout_s=0.6,
        connect_retry_s=0.1, connect_max_retries=3, task_timeout_s=10.0, **kw
    )


# ---------------------------------------------------------------------------
# KV-handoff frames (cluster/kv_transfer.py over KV_PAGES / KV_ACK)
# ---------------------------------------------------------------------------

import numpy as np

from distributed_llms_tpu.cluster import kv_transfer
from distributed_llms_tpu.runtime.pages import PrefixCache


def _kv_payload(page_size=4, n_pages=2, tid="tx1"):
    ids = list(range(1, page_size * n_pages + 3))  # a few suffix tokens too
    digests = PrefixCache.page_digests(ids, page_size, n_pages)
    shape = (2, n_pages, page_size, 1, 2)  # [L, P, BLK, KVH, HD]
    k = np.arange(np.prod(shape), dtype=np.float32).reshape(shape)
    return kv_transfer.KVTransferPayload(
        transfer_id=tid, token_ids=ids[: page_size * n_pages],
        page_size=page_size, digests=digests, k_pages=k, v_pages=k + 1.0,
    )


async def _kv_receiver(faults=None):
    """A minimal decode-side listener: verified payloads land in
    ``imported``; duplicates dedup on digests exactly like the batcher's
    import does.  Returns (server, port, stats, imported)."""
    stats = kv_transfer.ReceiverStats()
    imported: list = []
    resident: set = set()

    async def import_fn(payload):
        if all(d in resident for d in payload.digests):
            return True, "duplicate"
        resident.update(payload.digests)
        imported.append(payload)
        return True, "imported"

    async def handle(reader, writer):
        await kv_transfer.handle_kv_connection(
            reader, writer, page_digests_fn=PrefixCache.page_digests,
            import_fn=import_fn, faults=faults, stats=stats,
        )

    server = await asyncio.start_server(handle, "127.0.0.1", 0)
    return server, server.sockets[0].getsockname()[1], stats, imported


@pytest.mark.asyncio
async def test_kv_frame_roundtrip_and_dup_delivery_idempotent():
    """A KV_PAGES frame round-trips verified; re-delivering the SAME
    transfer (a retry racing a delayed ack) acks ok WITHOUT re-importing
    — idempotence via the digest check, the dup-safety the sender's
    retry loop leans on."""
    server, port, stats, imported = await _kv_receiver()
    try:
        msg = kv_transfer.encode_kv_pages(_kv_payload())
        r1 = await kv_transfer.send_kv_pages("127.0.0.1", port, msg,
                                             attempt_s=5.0)
        assert r1.ok and r1.reason == "imported" and r1.attempts == 1
        r2 = await kv_transfer.send_kv_pages("127.0.0.1", port, msg,
                                             attempt_s=5.0)
        assert r2.ok and r2.reason == "duplicate"
        assert len(imported) == 1  # the payload landed exactly once
        assert stats.duplicates == 1
        got = imported[0]
        np.testing.assert_array_equal(got.k_pages, _kv_payload().k_pages)
        np.testing.assert_array_equal(got.v_pages, _kv_payload().v_pages)
    finally:
        server.close()


@pytest.mark.asyncio
async def test_kv_frame_drop_times_out_then_retry_succeeds():
    """A dropped frame (receiver pretends it was lost; no ack) times the
    sender out; the jittered retry delivers."""
    plane = FaultPlane()
    rule = plane.add("xfer.recv", "drop", when="1")
    server, port, stats, imported = await _kv_receiver(faults=plane)
    try:
        msg = kv_transfer.encode_kv_pages(_kv_payload(tid="txdrop"))
        res = await kv_transfer.send_kv_pages(
            "127.0.0.1", port, msg, attempt_s=0.3, max_retries=3,
            backoff_base_s=0.01,
        )
        assert res.ok and res.attempts == 2
        assert rule.fired == 1
        assert len(imported) == 1
    finally:
        server.close()


@pytest.mark.asyncio
async def test_kv_corrupt_payload_rejected_then_clean_retry_succeeds():
    """An in-flight bit-flip fails the receiver's checksum verify and is
    NACKed (never imported); the clean retry succeeds."""
    plane = FaultPlane()
    rule = plane.add("xfer.send", "corrupt", when="1")
    server, port, stats, imported = await _kv_receiver()
    try:
        msg = kv_transfer.encode_kv_pages(_kv_payload(tid="txcorrupt"))
        res = await kv_transfer.send_kv_pages(
            "127.0.0.1", port, msg, faults=plane, attempt_s=5.0,
            max_retries=2, backoff_base_s=0.01,
        )
        assert res.ok and res.attempts == 2
        assert rule.fired == 1
        assert stats.rejected == 1
        assert stats.last_reason == "imported"
        assert len(imported) == 1
    finally:
        server.close()


@pytest.mark.asyncio
async def test_kv_send_drop_swallowed_then_retry_succeeds():
    """A sender-side drop (the wire never sees the frame) times the
    sender out on the missing ack; the retry delivers — the mirror of the
    receiver-side drop drill above."""
    plane = FaultPlane()
    rule = plane.add("xfer.send", "drop", when="1")
    server, port, stats, imported = await _kv_receiver()
    try:
        msg = kv_transfer.encode_kv_pages(_kv_payload(tid="txsdrop"))
        res = await kv_transfer.send_kv_pages(
            "127.0.0.1", port, msg, faults=plane, attempt_s=0.3,
            max_retries=3, backoff_base_s=0.01,
        )
        assert res.ok and res.attempts == 2
        assert rule.fired == 1
        assert stats.rejected == 0  # swallowed, never seen — not NACKed
        assert len(imported) == 1
    finally:
        server.close()


@pytest.mark.asyncio
async def test_kv_recv_corrupt_nacked_then_clean_retry_succeeds():
    """A receiver-side bit-flip (corruption after the wire, before
    verify) fails the checksum and is NACKed; the byte-identical retry
    arrives clean and imports."""
    plane = FaultPlane()
    rule = plane.add("xfer.recv", "corrupt", when="1")
    server, port, stats, imported = await _kv_receiver(faults=plane)
    try:
        msg = kv_transfer.encode_kv_pages(_kv_payload(tid="txrcorrupt"))
        res = await kv_transfer.send_kv_pages(
            "127.0.0.1", port, msg, attempt_s=5.0, max_retries=2,
            backoff_base_s=0.01,
        )
        assert res.ok and res.attempts == 2
        assert rule.fired == 1
        assert stats.rejected == 1
        assert len(imported) == 1
    finally:
        server.close()


def test_kv_digest_chain_mismatch_rejected():
    """A frame whose digests do not commit to its carried tokens (a
    sender-side hashing bug: checksum INTACT, chain wrong) must be
    rejected — publishing those pages would serve wrong KV to every
    later prefix match."""
    p = _kv_payload()
    wrong = _kv_payload()
    wrong.token_ids = [t + 1 for t in wrong.token_ids]  # different prompt,
    #   digests left as the original prompt's — checksum recomputed clean
    msg = kv_transfer.encode_kv_pages(wrong)
    msg["payload"]["digests"] = [d.hex() for d in p.digests]
    import base64 as _b64
    kb = _b64.b64decode(msg["payload"]["k"])
    vb = _b64.b64decode(msg["payload"]["v"])
    msg["payload"]["checksum"] = kv_transfer.checksum(
        wrong.token_ids, p.digests, kb, vb
    )
    got, reason = kv_transfer.verify_and_decode(
        msg, PrefixCache.page_digests
    )
    assert got is None and reason == "digest mismatch"


@pytest.mark.asyncio
async def test_kv_oversized_frame_rejected_at_send(monkeypatch):
    """A transfer exceeding MAX_FRAME fails LOUDLY at the sender with a
    permanent (non-retried) failure — never a silent connection drop or
    a half-written stream."""
    server, port, stats, imported = await _kv_receiver()
    try:
        monkeypatch.setattr(protocol, "MAX_FRAME", 4096)
        msg = kv_transfer.encode_kv_pages(
            _kv_payload(page_size=16, n_pages=8, tid="txbig")
        )
        res = await kv_transfer.send_kv_pages("127.0.0.1", port, msg,
                                              max_retries=3)
        assert not res.ok and res.attempts == 0
        assert "frame too large" in res.reason
        assert not imported
    finally:
        server.close()


# ---------------------------------------------------------------------------
# protocol framing
# ---------------------------------------------------------------------------

def test_encode_decode_roundtrip():
    msg = protocol.message("REGISTER", {"capabilities": {"platform": "cpu"}})
    raw = protocol.encode(msg)
    n, flags = protocol.decode_header(raw[:8])
    assert n == len(raw) - 8
    assert flags == 0  # small frame: uncompressed
    assert json.loads(raw[8:]) == msg


def test_encode_compresses_large_frames():
    big = protocol.message("RESULT", {"text": ["x" * 100_000]})
    raw = protocol.encode(big)
    n, flags = protocol.decode_header(raw[:8])
    assert flags == 1
    assert n < 10_000  # zlib shrank 100kB of 'x'
    import zlib

    assert json.loads(zlib.decompress(raw[8:])) == big


@pytest.mark.asyncio
async def test_compressed_and_batched_over_the_wire():
    """Large (compressed) frames and BATCH frames round-trip through the real
    coordinator socket."""
    coord = Coordinator(fast_cfg())
    await coord.start()
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", coord.port)
        big_caps = {"note": "y" * 50_000}
        await protocol.send_messages(
            writer,
            [
                protocol.message("REGISTER", {"worker_id": "b", "capabilities": big_caps}),
                protocol.message("HEARTBEAT", {}),
            ],
        )
        ack = await protocol.receive_message(reader, timeout=5)
        assert ack["type"] == "REGISTER_ACK"
        for _ in range(50):
            if "b" in coord.workers:
                break
            await asyncio.sleep(0.02)
        assert coord.workers["b"].capabilities == big_caps
        writer.close()
    finally:
        await coord.stop()


def test_unbatch_rejects_nested_and_invalid():
    with pytest.raises(protocol.ProtocolError, match="messages"):
        protocol.unbatch({"type": "BATCH", "payload": {}})
    with pytest.raises(protocol.ProtocolError, match="invalid batched"):
        protocol.unbatch(protocol.batch([protocol.batch([])]))


def test_encode_rejects_unknown_type():
    with pytest.raises(protocol.ProtocolError, match="unknown message type"):
        protocol.encode({"type": "EVIL"})


def test_decode_rejects_oversized():
    import struct

    with pytest.raises(protocol.ProtocolError, match="too large"):
        protocol.decode_header(struct.pack(">Q", protocol.MAX_FRAME + 1))


@pytest.mark.asyncio
async def test_receive_timeout():
    coord = Coordinator(fast_cfg())
    host, port = await coord.start()
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", coord.port)
        with pytest.raises(asyncio.TimeoutError):
            await protocol.receive_message(reader, timeout=0.2)
        writer.close()
    finally:
        await coord.stop()


@pytest.mark.asyncio
async def test_protocol_frame_faults_close_delay_drop():
    """The fault plane wired into protocol framing: close severs the
    stream mid-request, delay stalls a frame, drop swallows one on receive
    — all deterministic, all through the REAL coordinator socket."""
    import time

    coord = Coordinator(fast_cfg())
    await coord.start()
    try:
        # close: the client's GET_STATUS send dies with a connection error.
        protocol.set_fault_plane(
            FaultPlane.parse("proto.send/GET_STATUS:close@1")
        )
        with pytest.raises(ConnectionError, match="fault injection"):
            async with CoordinatorClient("127.0.0.1", coord.port) as c:
                await c.status()
        # delay: the same request completes, measurably later.
        protocol.set_fault_plane(
            FaultPlane.parse("proto.send/GET_STATUS:delay@1:0.2")
        )
        t0 = time.perf_counter()
        async with CoordinatorClient("127.0.0.1", coord.port) as c:
            status = await c.status()
        assert time.perf_counter() - t0 >= 0.2
        assert "workers" in status
        # drop on receive: the first RESULT frame is "lost in flight"; the
        # client's read times out even though the coordinator answered.
        protocol.set_fault_plane(
            FaultPlane.parse("proto.recv/RESULT:drop@1")
        )
        with pytest.raises(asyncio.TimeoutError):
            async with CoordinatorClient("127.0.0.1", coord.port) as c:
                await c.request("GET_STATUS", timeout=0.5)
    finally:
        protocol.set_fault_plane(None)  # global hook: ALWAYS uninstall
        await coord.stop()


# ---------------------------------------------------------------------------
# registration / heartbeat / eviction
# ---------------------------------------------------------------------------

class FakeEngine:
    def generate_text(self, prompts, max_new_tokens=None):
        import types

        n = max_new_tokens or 4
        return types.SimpleNamespace(
            text=[p + "!" for p in prompts],
            generated_tokens=n * len(prompts),
            seconds=0.01,
            tokens_per_second=float(n * len(prompts)) / 0.01,
        )


def fake_factory(store_dir, shards, rt):
    return FakeEngine()


async def start_worker(coord, factory=fake_factory, **kw):
    w = WorkerHost("127.0.0.1", coord.port, cfg=fast_cfg(), engine_factory=factory, **kw)
    task = asyncio.create_task(w.run())
    for _ in range(100):
        if w.worker_id is not None:
            break
        await asyncio.sleep(0.02)
    assert w.worker_id is not None, "worker failed to register"
    return w, task


@pytest.mark.asyncio
async def test_register_heartbeat_and_eviction():
    """Deadline eviction (reference never evicted: D10), provoked by FAULT
    INJECTION: the worker stays alive but a `worker.heartbeat:drop@1+` rule
    swallows every beat — exactly a silently-wedged host, with no task
    killing and no fixed sleeps (poll loops bound the waits)."""
    plane = FaultPlane()
    coord = Coordinator(fast_cfg())
    await coord.start()
    try:
        w, wt = await start_worker(coord, faults=plane)
        assert w.worker_id in coord.workers
        # heartbeats keep it alive past the timeout window
        await asyncio.sleep(0.9)
        assert w.worker_id in coord.workers

        # Arm the fault mid-run: every subsequent heartbeat is dropped.
        rule = plane.add("worker.heartbeat", "drop", when="1+")
        for _ in range(200):  # poll-wait for the deadline eviction
            if w.worker_id not in coord.workers:
                break
            await asyncio.sleep(0.05)
        assert w.worker_id not in coord.workers
        assert rule.fired >= 1  # beats were really dropped, not just late
        wt.cancel()
    finally:
        await coord.stop()


@pytest.mark.asyncio
async def test_stable_id_reregistration_survives_stale_close():
    """A host restarting under a stable id (e.g. its StatefulSet pod name)
    replaces its registration; the stale connection's close must not evict
    the fresh one."""
    import dataclasses

    coord = Coordinator(dataclasses.replace(fast_cfg(), heartbeat_timeout_s=60.0))
    await coord.start()
    try:
        async def register(wid):
            reader, writer = await asyncio.open_connection("127.0.0.1", coord.port)
            await protocol.send_message(
                writer, protocol.message("REGISTER", {"worker_id": wid, "capabilities": {}})
            )
            ack = await protocol.receive_message(reader, timeout=5)
            assert ack["payload"]["worker_id"] == wid
            return reader, writer

        r1, w1 = await register("pod-0")
        old_info = coord.workers["pod-0"]
        r2, w2 = await register("pod-0")  # restart: same id, new connection
        assert coord.workers["pod-0"].writer is not old_info.writer
        # Stale socket closes (either side) -> registration must survive.
        w1.close()
        await asyncio.sleep(0.3)
        assert "pod-0" in coord.workers
        assert coord.workers["pod-0"].writer is not old_info.writer
        w2.close()
        await asyncio.sleep(0.3)
        assert "pod-0" not in coord.workers  # real close still evicts
    finally:
        await coord.stop()


@pytest.mark.asyncio
async def test_stable_id_rejoin_replaces_shards(tmp_path):
    """A stable-id rejoin is a fresh process with nothing loaded: the
    coordinator must re-send PLACE_SHARDS for its assignment instead of
    routing generates at an empty worker."""
    import dataclasses

    coord = Coordinator(dataclasses.replace(fast_cfg(), heartbeat_timeout_s=60.0))
    await coord.start()
    try:
        async def register(wid):
            reader, writer = await asyncio.open_connection("127.0.0.1", coord.port)
            await protocol.send_message(
                writer, protocol.message("REGISTER", {"worker_id": wid, "capabilities": {}})
            )
            ack = await protocol.receive_message(reader, timeout=5)
            assert ack["type"] == "REGISTER_ACK"
            return reader, writer

        r1, w1 = await register("pod-0")
        coord.plan_shards(2, store_dir=str(tmp_path))
        # Drain the initial PLACE_SHARDS (ack it so place_shards resolves).
        place_task = asyncio.create_task(coord.place_shards())
        msg = await protocol.receive_message(r1, timeout=5)
        assert msg["type"] == "PLACE_SHARDS"
        await protocol.send_message(
            w1, protocol.message("RESULT", {"loaded": [0, 1], "resident": "x"},
                                 msg_id=msg["msg_id"])
        )
        await place_task

        # Restart: same id, new connection -> expect a fresh PLACE_SHARDS.
        w1.close()
        r2, w2 = await register("pod-0")
        msg2 = await protocol.receive_message(r2, timeout=5)
        assert msg2["type"] == "PLACE_SHARDS"
        assert sorted(msg2["payload"]["shards"]) == [0, 1]
        w2.close()
    finally:
        await coord.stop()


@pytest.mark.asyncio
async def test_plan_place_generate_roundtrip(tmp_path):
    coord = Coordinator(fast_cfg())
    await coord.start()
    try:
        w, wt = await start_worker(coord)
        coord.plan_shards(2, store_dir=str(tmp_path))
        assert set(coord.shard_assignment) == {0, 1}
        placed = await coord.place_shards()
        assert placed[w.worker_id]["loaded"] == [0, 1]
        out = await coord.generate(["hello"], max_new_tokens=3)
        assert out["text"] == ["hello!"]
        wt.cancel()
    finally:
        await coord.stop()


@pytest.mark.asyncio
async def test_task_retry_on_worker_death(tmp_path):
    """Task dispatched to a worker that dies mid-flight is retried on the
    survivor (planned in the reference, never built).  Deterministic via
    fault injection: the victim's `worker.result/GENERATE:close@1` rule
    severs its connection at the exact moment it would reply — no
    sleep-until-in-flight sampling, no task cancellation."""
    calls = []

    def factory(store_dir, shards, rt):
        calls.append(shards)
        return FakeEngine()

    # The dispatcher picks the lowest idle worker id, and ids assign in
    # registration order — the FIRST worker is deterministically the victim.
    victim_plane = FaultPlane.parse("worker.result/GENERATE:close@1")
    coord = Coordinator(fast_cfg())
    await coord.start()
    try:
        w1, t1 = await start_worker(coord, factory=factory,
                                    rt=RuntimeConfig(), faults=victim_plane)
        w2, t2 = await start_worker(coord, factory=factory)
        coord.plan_shards(2, store_dir=str(tmp_path))
        await coord.place_shards()
        assert len(calls) == 2  # both workers built engines

        out = await asyncio.wait_for(
            coord.generate(["x"], max_new_tokens=2), timeout=15
        )
        assert out["text"] == ["x!"]
        assert victim_plane.rules[0].fired == 1  # the victim really died
        assert w1.worker_id not in coord.workers  # ...and was evicted
        for t in (t1, t2):
            t.cancel()
    finally:
        await coord.stop()


@pytest.mark.asyncio
async def test_task_retry_on_injected_handler_fault(tmp_path):
    """An InjectedFault inside a worker's command handler surfaces as an
    ERROR reply and the coordinator retries — the handler-crash leg of the
    retry contract, distinct from connection death above."""
    coord = Coordinator(fast_cfg())
    await coord.start()
    try:
        plane = FaultPlane.parse("worker.handle/GENERATE:raise@1")
        w, wt = await start_worker(coord, faults=plane)
        coord.plan_shards(1, store_dir=str(tmp_path))
        await coord.place_shards()
        out = await asyncio.wait_for(
            coord.generate(["y"], max_new_tokens=2), timeout=15
        )
        assert out["text"] == ["y!"]
        assert plane.rules[0].fired == 1
        assert w.worker_id in coord.workers  # handler crash, not death
        wt.cancel()
    finally:
        await coord.stop()


@pytest.mark.asyncio
async def test_dispatch_drop_times_out_submitter_then_retry_lands(tmp_path):
    """A coordinator.dispatch drop models the dispatch lost in flight:
    the task stays assigned and unanswered, the submitter's wait_for
    timeout fires, and a fresh submit dispatches normally — the
    submitter-timeout leg of the retry contract."""
    plane = FaultPlane.parse("coordinator.dispatch/GENERATE:drop@1")
    coord = Coordinator(fast_cfg(), faults=plane)
    await coord.start()
    try:
        w, wt = await start_worker(coord)
        coord.plan_shards(1, store_dir=str(tmp_path))
        await coord.place_shards()
        with pytest.raises(asyncio.TimeoutError):
            await coord.generate(["z"], max_new_tokens=2, timeout=1.0)
        assert plane.rules[0].fired == 1
        out = await asyncio.wait_for(
            coord.generate(["z"], max_new_tokens=2), timeout=15
        )
        assert out["text"] == ["z!"]
        assert w.worker_id in coord.workers  # nothing died — only the wire
        wt.cancel()
    finally:
        await coord.stop()


@pytest.mark.asyncio
async def test_schedule_computation_and_shutdown_broadcast(tmp_path):
    """The two frame types that had handlers but no sender until
    graftflow's GF401 flagged them: SCHEDULE_COMPUTATION dispatches
    through the same engine path as GENERATE, and shutdown_workers
    broadcasts SHUTDOWN — every worker answers ``{"ok": True}`` and
    stops its loops (graceful fleet retirement), with per-worker
    error strings instead of a failed broadcast when one is gone."""
    coord = Coordinator(fast_cfg())
    await coord.start()
    try:
        w, wt = await start_worker(coord)
        coord.plan_shards(1, store_dir=str(tmp_path))
        await coord.place_shards()
        out = await asyncio.wait_for(
            coord.schedule_computation(
                {"prompts": ["z"], "max_new_tokens": 2}), timeout=15
        )
        assert out["text"] == ["z!"]
        replies = await asyncio.wait_for(coord.shutdown_workers(), timeout=15)
        assert replies == {w.worker_id: {"ok": True}}
        # The worker's run loop really exits (stop() flips its event).
        await asyncio.wait_for(wt, timeout=10)
    finally:
        await coord.stop()


@pytest.mark.asyncio
async def test_generate_without_placement_errors_then_retries_exhaust(tmp_path):
    coord = Coordinator(fast_cfg())
    await coord.start()
    try:
        w, wt = await start_worker(coord)
        # no PLACE_SHARDS: worker raises, coordinator retries, then fails
        with pytest.raises(RuntimeError, match="failed after"):
            await coord.generate(["x"])
        wt.cancel()
    finally:
        await coord.stop()


async def register_fake(coord, wid, caps):
    """Raw-protocol registration with custom capabilities."""
    reader, writer = await asyncio.open_connection("127.0.0.1", coord.port)
    await protocol.send_message(
        writer, protocol.message("REGISTER", {"worker_id": wid, "capabilities": caps})
    )
    ack = await protocol.receive_message(reader, timeout=5)
    assert ack["type"] == "REGISTER_ACK"
    return reader, writer


@pytest.mark.asyncio
async def test_capacity_aware_plan():
    """Workers advertising more capacity receive proportionally more shards
    (the reference recorded capabilities but never used them, SURVEY §2.2)."""
    coord = Coordinator(fast_cfg())
    await coord.start()
    try:
        r1, w1 = await register_fake(coord, "big", {"num_devices": 3})
        r2, w2 = await register_fake(coord, "small", {"num_devices": 1})
        plan = coord.plan_shards(4)
        counts = {"big": 0, "small": 0}
        for wid in plan.values():
            counts[wid] += 1
        assert counts == {"big": 3, "small": 1}
        # round_robin parity policy still splits 2/2
        plan_rr = coord.plan_shards(4, policy="round_robin")
        assert sorted(plan_rr.values()) == ["big", "big", "small", "small"]
        w1.close(), w2.close()
    finally:
        await coord.stop()


@pytest.mark.asyncio
async def test_eviction_reassigns_shards(tmp_path):
    """Dynamic reassignment on pool change (plan.md:423-428, never built):
    a dead worker's shards move to the survivor and get re-placed."""
    calls: list[tuple[str, list[int]]] = []

    def factory(store_dir, shards, rt):
        calls.append(("w", shards))
        return FakeEngine()

    coord = Coordinator(fast_cfg())
    await coord.start()
    try:
        w1, t1 = await start_worker(coord, factory=factory)
        w2, t2 = await start_worker(coord, factory=factory)
        coord.plan_shards(4, store_dir=str(tmp_path))
        await coord.place_shards()
        assert len(calls) == 2

        t1.cancel()  # dies silently -> deadline eviction
        for _ in range(100):
            await asyncio.sleep(0.05)
            if (
                w1.worker_id not in coord.workers
                and set(coord.shard_assignment.values()) == {w2.worker_id}
                and len(calls) >= 3
            ):
                break
        assert set(coord.shard_assignment.values()) == {w2.worker_id}
        assert sorted(coord.shard_assignment) == [0, 1, 2, 3]
        assert sorted(calls[-1][1]) == [0, 1, 2, 3]  # survivor re-placed all
        t2.cancel()
    finally:
        await coord.stop()


@pytest.mark.asyncio
async def test_rebalance_after_join(tmp_path):
    """A worker joining after placement takes over shards via rebalance()."""
    calls: list[list[int]] = []

    def factory(store_dir, shards, rt):
        calls.append(shards)
        return FakeEngine()

    coord = Coordinator(fast_cfg())
    await coord.start()
    try:
        w1, t1 = await start_worker(coord, factory=factory)
        coord.plan_shards(4, store_dir=str(tmp_path))
        await coord.place_shards()
        assert set(coord.shard_assignment.values()) == {w1.worker_id}

        w2, t2 = await start_worker(coord, factory=factory)
        plan = await coord.rebalance()
        assert set(plan.values()) == {w1.worker_id, w2.worker_id}
        per = {}
        for s, wid in plan.items():
            per.setdefault(wid, []).append(s)
        assert sorted(len(v) for v in per.values()) == [2, 2]
        t1.cancel(), t2.cancel()
    finally:
        await coord.stop()


@pytest.mark.asyncio
async def test_status_and_metrics_client(tmp_path):
    coord = Coordinator(fast_cfg())
    await coord.start()
    try:
        w, wt = await start_worker(coord)
        async with CoordinatorClient("127.0.0.1", coord.port) as c:
            status = await c.status()
            assert w.worker_id in status["workers"]
            metrics = await c.metrics()
            assert "counters" in metrics
        wt.cancel()
    finally:
        await coord.stop()


@pytest.mark.asyncio
async def test_worker_process_registers():
    """Process-isolated local simulation (the reference's planned
    multiprocessing mode, plan.md:225-233): a separate interpreter running
    host_main registers with the coordinator."""
    import subprocess
    import sys

    coord = Coordinator(fast_cfg())
    await coord.start()
    import pathlib

    repo_root = str(pathlib.Path(__file__).resolve().parents[2])
    proc = subprocess.Popen(
        [sys.executable, "-m", "distributed_llms_tpu.cli.host_main",
         "--host", "127.0.0.1", "--port", str(coord.port), "--platform", "cpu"],
        cwd=repo_root,
    )
    try:
        for _ in range(300):  # jax import in the child takes a few seconds
            if coord.workers:
                break
            await asyncio.sleep(0.1)
        assert coord.workers, "worker process never registered"
    finally:
        proc.terminate()
        proc.wait(timeout=10)
        await coord.stop()


@pytest.mark.asyncio
async def test_worker_connect_retry_fails_cleanly():
    w = WorkerHost("127.0.0.1", 1, cfg=fast_cfg())  # port 1: nothing there
    with pytest.raises(ConnectionError, match="could not reach"):
        await w.run()


@pytest.mark.asyncio
async def test_mesh_parallel_serving_end_to_end(tmp_path):
    """The reference's core promise — split one model across devices and
    serve it (src/master/node.py:84-138) — through the PRODUCT path:
    coordinator -> worker -> ParallelModel(dp=2, pp=2, tp=2) -> decoded
    text, exact-matching the single-device engine."""
    import jax

    from distributed_llms_tpu.checkpoint import store as store_lib
    from distributed_llms_tpu.core.config import MeshConfig
    from distributed_llms_tpu.models import model as model_lib, presets
    from distributed_llms_tpu.runtime.engine import InferenceEngine

    # vocab 512 >= the byte tokenizer's 259 ids (256 bytes + specials)
    cfg = presets.get_preset("llama-tiny", vocab_size=512)
    params = model_lib.init_params(jax.random.key(0), cfg)
    store_lib.save_shards(params, str(tmp_path), num_shards=2, model_config=cfg)

    rt = RuntimeConfig(microbatches=2, max_decode_steps=8)
    mesh_cfg = MeshConfig(data=2, pipe=2, model=2)
    import dataclasses

    # Pipelined generate compiles on CPU need a roomy task deadline, and the
    # compile holds the GIL in bursts that can starve the worker's heartbeat
    # task — so eviction must be lenient too (fast eviction is covered by the
    # dedicated eviction tests above).
    ccfg = dataclasses.replace(
        fast_cfg(), task_timeout_s=180.0, heartbeat_timeout_s=180.0
    )
    coord = Coordinator(ccfg)
    await coord.start()
    try:
        w = WorkerHost("127.0.0.1", coord.port, cfg=ccfg, rt=rt, mesh_cfg=mesh_cfg)
        wt = asyncio.create_task(w.run())
        for _ in range(100):
            if w.worker_id is not None:
                break
            await asyncio.sleep(0.02)
        assert w.worker_id is not None

        coord.plan_shards(2, store_dir=str(tmp_path))
        placed = await coord.place_shards()
        assert "mesh" in placed[w.worker_id]["resident"]
        assert w.engine.parallel is not None and w.engine.parallel.pipelined

        out = await coord.generate(["hello world"], max_new_tokens=8)

        ref = InferenceEngine.from_store(str(tmp_path), rt=rt)
        expect = ref.generate_text(["hello world"], max_new_tokens=8)
        assert out["text"] == expect.text
        wt.cancel()
    finally:
        await coord.stop()
