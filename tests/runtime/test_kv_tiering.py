"""KV memory tiering (runtime/batcher.py, PR 9): int8 quantized KV pages
plus the async host-RAM offload tier behind the paged pool.

The acceptance contract pinned here:

- **Offload is exact.**  Every bf16 host-tier path — swap-preemption
  (raw pages parked at preempt, scattered back at restore) and
  prefix-cache spill/restore (cold pages captured ahead of LRU eviction,
  restored on a later hit) — produces temp-0 streams BYTE-EXACT against
  the untier'd reference.  Verification failures (corrupt drills) degrade
  to exact recompute / cold prefill, never to wrong tokens.
- **Quantization is parity-bounded.**  int8 pages (``kv_bits=8``) are
  deterministic and hit pinned greedy token-agreement thresholds vs the
  bf16 reference; offload paths under int8 are byte-exact against the
  *int8* unpreempted run (raw quantized bytes round-trip verbatim).
- **The audit spans tiers.**  ``assert_pool_consistent()`` extends to the
  host tier: every swap parcel must be owned by exactly one queued resume
  request, budget accounting must balance — run after every workload
  here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.checkpoint.quantize import (kv_dequantize,
                                                      kv_quantize)
from distributed_llms_tpu.core.observability import METRICS
from distributed_llms_tpu.models import model as model_lib, presets
from distributed_llms_tpu.models.kv_cache import QuantKVCache, page_bytes
from distributed_llms_tpu.runtime import generate as gen_lib
from distributed_llms_tpu.runtime.batcher import ContinuousBatcher
from distributed_llms_tpu.runtime.faults import FaultPlane
from distributed_llms_tpu.runtime.kv_tier import HostTier
from distributed_llms_tpu.runtime.pages import PrefixCache


@pytest.fixture(scope="module")
def tiny():
    cfg = presets.get_preset("llama-tiny", vocab_size=512)
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def tiny_gpt2():
    cfg = presets.get_preset("gpt2-tiny", vocab_size=512)
    params = model_lib.init_params(jax.random.key(1), cfg)
    return cfg, params


def solo(cfg, params, ids, n_new):
    out = gen_lib.generate_tokens(
        params, cfg, jnp.asarray([ids], jnp.int32),
        jnp.asarray([len(ids)], jnp.int32), jax.random.key(9),
        max_new_tokens=n_new, eos_id=-1, pad_id=0,
    )
    return np.asarray(out)[0].tolist()


def _paged(cfg, params, **kw):
    kw.setdefault("batch_slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("chunk_steps", 4)
    kw.setdefault("page_size", 16)
    kw.setdefault("paged_pages", 9)
    return ContinuousBatcher(cfg, params, **kw)


def _counter(name):
    return METRICS.get_counter(name)


STORM = [([7, 1, 9, 2], 44), ([4, 4, 4, 4], 44), ([9, 8, 7, 3], 44)]


def _run_storm(b, reqs=STORM):
    rids = [b.submit(ids, max_new_tokens=n) for ids, n in reqs]
    res = b.run()
    b.assert_pool_consistent()
    return rids, res


# -- configuration contract -------------------------------------------------


def test_int8_requires_paged_pool(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="paged"):
        ContinuousBatcher(cfg, params, batch_slots=2, max_len=64, kv_bits=8)
    with pytest.raises(ValueError, match="kv_bits"):
        _paged(cfg, params, kv_bits=4)


def test_host_tier_requires_paged_pool(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="paged"):
        ContinuousBatcher(cfg, params, batch_slots=2, max_len=64,
                          host_pages=8)
    with pytest.raises(ValueError, match="host_pages"):
        _paged(cfg, params, host_pages=-1)


def test_int8_pool_storage_and_capacity(tiny):
    """Pool storage is int8 + f32 scales; logical token capacity is
    unchanged (same page count), so capacity per POOL BYTE grows by the
    byte ratio — >= 1.8x at head_dim 64 (the acceptance floor)."""
    cfg, params = tiny
    b = _paged(cfg, params, kv_bits=8)
    assert isinstance(b.cache, QuantKVCache)
    assert b.cache.k.dtype == jnp.int8 and b.cache.v.dtype == jnp.int8
    assert b.cache.k_scale.dtype == jnp.float32
    b16 = _paged(cfg, params)
    assert b.capacity_tokens() == b16.capacity_tokens()
    ratio = (page_bytes(cfg, 16, 16) / page_bytes(cfg, 16, 8))
    assert ratio >= 1.8, f"int8 pages only {ratio:.2f}x denser"
    b.assert_pool_consistent()
    b16.assert_pool_consistent()


def test_kv_quantize_round_trip_is_stable():
    """Re-quantizing a dequantized parcel reproduces identical int8 data
    and scales — the property that keeps a kv-bits-8 handoff byte-stable
    (export dequantizes, import re-quantizes)."""
    x = jax.random.normal(jax.random.key(3), (4, 8, 2, 16), jnp.bfloat16)
    data, scale = kv_quantize(x)
    full = kv_dequantize(data, scale, jnp.bfloat16)
    data2, scale2 = kv_quantize(full)
    np.testing.assert_array_equal(np.asarray(data), np.asarray(data2))
    np.testing.assert_array_equal(np.asarray(scale), np.asarray(scale2))


def test_digests_salted_by_kv_bits(tiny):
    """Digest chains fold in the KV width: int8 pages can never alias
    bf16 pages — while sharing WITHIN a width stays content-addressed."""
    ids = list(range(48))
    d16 = PrefixCache.page_digests(ids, 16, 3)
    d8 = PrefixCache.page_digests(ids, 16, 3, kv_bits=8)
    assert d16 != d8 and all(a != b for a, b in zip(d16, d8))
    # Default-width digests are unchanged by the new parameter.
    assert d16 == PrefixCache.page_digests(ids, 16, 3, kv_bits=16)
    cfg, params = tiny
    b = _paged(cfg, params, paged_pages=17, prefix_cache=True, kv_bits=8)
    shared = list(range(40, 60)) + [3] * 5
    r1 = b.submit(shared + [9], max_new_tokens=8)
    b.run()
    r2 = b.submit(shared + [11], max_new_tokens=8)
    b.run()
    assert b.prefix_cached_tokens[r2] > 0, "int8 pages did not share"
    b.assert_pool_consistent()


# -- int8 serving quality ---------------------------------------------------


def _agreement(cfg, params, prompts, n_new=24):
    b16 = _paged(cfg, params, batch_slots=4, paged_pages=17)
    b8 = _paged(cfg, params, batch_slots=4, paged_pages=17, kv_bits=8)
    r16 = [b16.submit(p, max_new_tokens=n_new) for p in prompts]
    o16 = b16.run()
    r8 = [b8.submit(p, max_new_tokens=n_new) for p in prompts]
    o8 = b8.run()
    b16.assert_pool_consistent()
    b8.assert_pool_consistent()
    tot = sum(len(o16[r]) for r in r16)
    agree = sum(
        sum(i == j for i, j in zip(o16[a], o8[b]))
        for a, b in zip(r16, r8)
    )
    return agree / tot, [o8[r] for r in r8]


PROMPTS = [[7, 1, 9, 2], [4, 4, 4, 4], [9, 8, 7, 3], [11, 5],
           [100, 200, 50, 60, 70, 80, 90, 10], [3] * 12]


def test_int8_greedy_token_agreement_gpt2(tiny_gpt2):
    """gpt2 short runs: int8 pages agree with the bf16 reference at the
    pinned threshold (measured 1.0 at pinning time; floor 0.9)."""
    cfg, params = tiny_gpt2
    frac, _ = _agreement(cfg, params, PROMPTS)
    assert frac >= 0.9, f"gpt2 int8 agreement {frac:.3f} < 0.9"


def test_int8_greedy_token_agreement_and_determinism(tiny):
    """llama-tiny: agreement floor 0.7 (greedy divergence cascades after
    a first flipped token — measured 0.88 at pinning time), and two int8
    runs are byte-identical (quantization is deterministic)."""
    cfg, params = tiny
    frac, outs = _agreement(cfg, params, PROMPTS)
    assert frac >= 0.7, f"int8 agreement {frac:.3f} < 0.7"
    _, outs2 = _agreement(cfg, params, PROMPTS)
    assert outs == outs2, "int8 serving is not deterministic"


# -- swap-preemption (host tier) -------------------------------------------


def test_swap_preempt_byte_exact_vs_solo(tiny):
    """Overcommitted storm with the host tier armed: victims SWAP out
    instead of recomputing, and every stream equals its solo run."""
    cfg, params = tiny
    b = _paged(cfg, params, host_pages=16)
    out0 = _counter("batcher.kv_swaps.out")
    in0 = _counter("batcher.kv_swaps.in")
    rids, res = _run_storm(b)
    for rid, (ids, n) in zip(rids, STORM):
        assert res[rid] == solo(cfg, params, ids, n), f"rid {rid} diverged"
    assert _counter("batcher.kv_swaps.out") - out0 >= 1
    assert _counter("batcher.kv_swaps.in") - in0 >= 1
    assert b.preemptions >= 1
    assert sorted(b.free_pages) == list(range(1, 9))


def test_swap_restore_equals_recompute_and_streams_once(tiny):
    """The same storm with and without the host tier produces identical
    results (bf16 offload is lossless); streamed deliveries across a
    swap restore never re-deliver and fire done exactly once."""
    cfg, params = tiny
    swaps0 = _counter("batcher.kv_swaps.out")
    b_re = _paged(cfg, params)
    _, res_re = _run_storm(b_re)
    assert _counter("batcher.kv_swaps.out") == swaps0  # no tier, no swaps
    assert b_re.preemptions >= 1

    b_sw = _paged(cfg, params, host_pages=16)
    deliveries: dict[int, list[int]] = {}
    dones: dict[int, int] = {}

    def on_tokens(rid, toks, done, lps):
        deliveries.setdefault(rid, []).extend(toks)
        if done:
            dones[rid] = dones.get(rid, 0) + 1

    rids = [b_sw.submit(ids, max_new_tokens=n) for ids, n in STORM]
    res_sw = b_sw.run(on_tokens=on_tokens)
    b_sw.assert_pool_consistent()
    assert _counter("batcher.kv_swaps.out") > swaps0
    assert {r: res_sw[r] for r in rids} == {r: res_re[r] for r in rids}
    for rid in rids:
        assert deliveries[rid] == res_sw[rid], "stream diverged from result"
        assert dones[rid] == 1


def test_swap_falls_back_when_host_budget_dry(tiny):
    """A 1-page host tier cannot hold any victim: every preemption falls
    back to exact recompute and the fallback counter says so."""
    cfg, params = tiny
    fb0 = _counter("batcher.kv_swaps.fallback")
    in0 = _counter("batcher.kv_swaps.in")
    b = _paged(cfg, params, host_pages=1)
    rids, res = _run_storm(b)
    for rid, (ids, n) in zip(rids, STORM):
        assert res[rid] == solo(cfg, params, ids, n)
    assert b.preemptions >= 1
    assert _counter("batcher.kv_swaps.fallback") - fb0 >= 1
    assert _counter("batcher.kv_swaps.in") == in0


def test_int8_swap_preempt_byte_exact_vs_unpreempted_int8(tiny):
    """Under int8 pages the swap parcel carries the RAW quantized bytes:
    a preempted-and-restored stream is byte-identical to the int8 run
    that was never under pressure (stronger than recompute could be)."""
    cfg, params = tiny
    ref = _paged(cfg, params, batch_slots=3, paged_pages=17, kv_bits=8)
    rids_ref = [ref.submit(ids, max_new_tokens=n) for ids, n in STORM]
    res_ref = ref.run()

    b = _paged(cfg, params, kv_bits=8, host_pages=16)
    out0 = _counter("batcher.kv_swaps.out")
    rids, res = _run_storm(b)
    assert _counter("batcher.kv_swaps.out") - out0 >= 1
    for r, rr in zip(rids, rids_ref):
        assert res[r] == res_ref[rr], "int8 swap restore moved tokens"


def test_swapped_request_cancel_and_audit(tiny):
    """A swap parcel whose request is cancelled while queued is freed
    (the audit would otherwise catch the stranded handle); mid-flight the
    audit accounts the queued parcel."""
    cfg, params = tiny
    b = _paged(cfg, params, host_pages=16)
    rids = [b.submit(ids, max_new_tokens=n) for ids, n in STORM]
    # Admit everything, then preempt a resident row by hand.
    b._admit_pending()
    victim = next(i for i in range(b.b) if b.rows[i].rid is not None
                  and b.rows[i].pages)
    vrid = b.rows[victim].rid
    b._preempt_row(victim, "test")
    queued = [r for r in b.queue_snapshot() if r.rid == vrid]
    assert queued and queued[0].swap_handle is not None
    b.assert_pool_consistent()  # parcel owned by the queued request: clean
    assert b.cancel_row(vrid)
    assert b.host_tier.stats()["swap_parcels"] == 0
    b.assert_pool_consistent()
    res = b.run()
    for rid, (ids, n) in zip(rids, STORM):
        if rid != vrid:
            assert res[rid] == solo(cfg, params, ids, n)


def test_host_tier_audit_catches_stranded_handle(tiny):
    """The cross-tier audit fails on a parcel no queued request owns —
    the host-RAM analogue of a dangling refcount."""
    cfg, params = tiny
    b = _paged(cfg, params, host_pages=16)
    h = b.host_tier.park_swap((np.zeros((2, 2)),), 2)
    assert h is not None
    with pytest.raises(AssertionError, match="swap handles"):
        b.assert_pool_consistent()
    b.host_tier.drop_swap(h)
    b.assert_pool_consistent()


# -- prefix-cache spill tier ------------------------------------------------


SHARED = list(range(40, 60)) + [3] * 5  # 25 tokens -> 3 full pages of 8


def _spill_batcher(cfg, params, **kw):
    return _paged(cfg, params, batch_slots=2, page_size=8, paged_pages=17,
                  prefix_cache=True, **kw)


def _evict_cache(b, n=3):
    """Push unrelated long prompts through until the shared pages fall
    off the device LRU."""
    for i in range(n):
        b.submit([200 + i] * 30 + [i], max_new_tokens=20)
    b.run()


def test_host_spill_restore_byte_exact_vs_device_hit(tiny):
    """Warm cache -> eviction pressure -> re-hit: with the host tier the
    evicted run restores (counted) and the hit's stream is byte-exact vs
    a plain device hit; cached-token accounting matches too."""
    cfg, params = tiny
    # Reference: plain device hit, no eviction in between.
    ref = _spill_batcher(cfg, params)
    ref.submit(SHARED + [9, 9], max_new_tokens=12)
    ref.run()
    r_hit = ref.submit(SHARED + [9, 9], max_new_tokens=12)
    hit_tokens = ref.run()[r_hit]
    hit_cached = ref.prefix_cached_tokens[r_hit]
    assert hit_cached == 24  # 3 full pages of 8

    b = _spill_batcher(cfg, params, host_pages=32)
    b.submit(SHARED + [9, 9], max_new_tokens=12)
    b.run()
    sp0 = _counter("batcher.host_tier.spilled_pages")
    rs0 = _counter("batcher.host_tier.restored_pages")
    _evict_cache(b)
    assert _counter("batcher.host_tier.spilled_pages") - sp0 >= 1
    r2 = b.submit(SHARED + [9, 9], max_new_tokens=12)
    out = b.run()[r2]
    assert _counter("batcher.host_tier.restored_pages") - rs0 >= 1
    assert out == hit_tokens, "spill-restored hit moved tokens"
    assert b.prefix_cached_tokens[r2] == hit_cached, (
        "restore did not recover the full cached run"
    )
    b.assert_pool_consistent()


def test_spill_restore_bridges_evicted_head(tiny):
    """LRU evicts a run's HEAD pages first: the tiered match restores the
    host-parked head and still reaches the device-resident tail — a
    device-only match would miss the whole run."""
    cfg, params = tiny
    b = _spill_batcher(cfg, params, host_pages=32)
    b.submit(SHARED + [9, 9], max_new_tokens=12)
    b.run()
    # One small alloc evicts exactly the oldest (head) cached page.
    _evict_cache(b, n=1)
    r2 = b.submit(SHARED + [9, 9], max_new_tokens=12)
    b.run()
    assert b.prefix_cached_tokens[r2] == 24, (
        f"tiered match only found {b.prefix_cached_tokens[r2]} tokens"
    )
    b.assert_pool_consistent()


def test_spill_restore_composes_with_chunked_prefill(tiny):
    """A chunked (long-prompt) admission consults the host tier too: the
    restored run seeds the transient row and only the suffix chunks."""
    cfg, params = tiny
    ref = _spill_batcher(cfg, params, prefill_chunk=8)
    ref.submit(SHARED + [9, 9], max_new_tokens=12)
    ref.run()
    r_hit = ref.submit(SHARED + [9, 9], max_new_tokens=12)
    hit_tokens = ref.run()[r_hit]

    b = _spill_batcher(cfg, params, prefill_chunk=8, host_pages=32)
    b.submit(SHARED + [9, 9], max_new_tokens=12)
    b.run()
    _evict_cache(b)
    r2 = b.submit(SHARED + [9, 9], max_new_tokens=12)
    out = b.run()[r2]
    assert out == hit_tokens
    assert b.prefix_cached_tokens[r2] == 24
    b.assert_pool_consistent()


# -- int8 x chunked prefill x preemption composition ------------------------


def test_int8_chunked_prefill_with_preemption_matches_monolithic(tiny):
    """The full composition: int8 pages + chunked prefill + host-tier
    swap under pool pressure — streams equal the int8 monolithic
    unpressured run (chunked prefill accumulates the same KV, the splice
    quantizes the same bytes, and swap restores them verbatim)."""
    cfg, params = tiny
    reqs = [(list(range(30)), 30), ([4, 4, 4, 4], 40), ([9, 8, 7, 3], 40)]
    ref = _paged(cfg, params, batch_slots=3, paged_pages=33, kv_bits=8)
    rr = [ref.submit(ids, max_new_tokens=n) for ids, n in reqs]
    res_ref = ref.run()

    b = _paged(cfg, params, kv_bits=8, host_pages=24, prefill_chunk=8,
               paged_pages=9)
    rids = [b.submit(ids, max_new_tokens=n) for ids, n in reqs]
    res = b.run()
    b.assert_pool_consistent()
    assert b.preemptions >= 1
    for a, c in zip(rids, rr):
        assert res[a] == res_ref[c], "int8 x chunked x swap moved tokens"


# -- fault drills (one per new site) ----------------------------------------


def test_drill_swap_out_drop_falls_back_exact(tiny):
    cfg, params = tiny
    faults = FaultPlane()
    rule = faults.add("kv.swap_out", "drop", when="*")
    b = _paged(cfg, params, host_pages=16, faults=faults)
    rids, res = _run_storm(b)
    for rid, (ids, n) in zip(rids, STORM):
        assert res[rid] == solo(cfg, params, ids, n)
    assert rule.fired >= 1
    assert b.host_tier.stats()["swap_parcels"] == 0


def test_drill_swap_out_corrupt_detected_and_exact(tiny):
    """A parcel corrupted in host storage fails checksum verification at
    restore and the request recomputes — outputs stay exact."""
    cfg, params = tiny
    faults = FaultPlane()
    rule = faults.add("kv.swap_out", "corrupt", when="1")
    fb0 = _counter("batcher.kv_swaps.fallback")
    b = _paged(cfg, params, host_pages=16, faults=faults)
    rids, res = _run_storm(b)
    for rid, (ids, n) in zip(rids, STORM):
        assert res[rid] == solo(cfg, params, ids, n)
    assert rule.fired == 1
    assert _counter("batcher.kv_swaps.fallback") - fb0 >= 1


def test_drill_swap_in_drop_falls_back_exact(tiny):
    cfg, params = tiny
    faults = FaultPlane()
    rule = faults.add("kv.swap_in", "drop", when="1")
    b = _paged(cfg, params, host_pages=16, faults=faults)
    rids, res = _run_storm(b)
    for rid, (ids, n) in zip(rids, STORM):
        assert res[rid] == solo(cfg, params, ids, n)
    assert rule.fired == 1


def test_drill_swap_in_corrupt_detected_and_exact(tiny):
    """A parcel corrupted on the restore path fails checksum verification
    — the resume falls back to exact recompute instead of splicing bad
    KV, and the fallback is metered."""
    cfg, params = tiny
    faults = FaultPlane()
    rule = faults.add("kv.swap_in", "corrupt", when="1")
    fb0 = _counter("batcher.kv_swaps.fallback")
    b = _paged(cfg, params, host_pages=16, faults=faults)
    rids, res = _run_storm(b)
    for rid, (ids, n) in zip(rids, STORM):
        assert res[rid] == solo(cfg, params, ids, n)
    assert rule.fired == 1
    assert _counter("batcher.kv_swaps.fallback") - fb0 >= 1


def test_drill_spill_drop_degrades_to_cold_prefill(tiny):
    """kv.spill drop: nothing moves to the host — the later hit misses
    (cold prefill), tokens unchanged."""
    cfg, params = tiny
    faults = FaultPlane()
    faults.add("kv.spill", "drop", when="*", tag="out")
    sp0 = _counter("batcher.host_tier.spilled_pages")
    b = _spill_batcher(cfg, params, host_pages=32, faults=faults)
    r1 = b.submit(SHARED + [9, 9], max_new_tokens=12)
    first = b.run()[r1]
    _evict_cache(b)
    r2 = b.submit(SHARED + [9, 9], max_new_tokens=12)
    out = b.run()[r2]
    assert _counter("batcher.host_tier.spilled_pages") == sp0
    assert out == first  # cold prefill recomputes the same bytes (bf16)
    b.assert_pool_consistent()


def test_drill_spill_corrupt_detected(tiny):
    """Corrupted spilled pages are rejected at restore (checksum) — the
    hit degrades toward cold prefill instead of reading bad KV."""
    cfg, params = tiny
    faults = FaultPlane()
    rule = faults.add("kv.spill", "corrupt", when="*", tag="out")
    b = _spill_batcher(cfg, params, host_pages=32, faults=faults)
    r1 = b.submit(SHARED + [9, 9], max_new_tokens=12)
    first = b.run()[r1]
    _evict_cache(b)
    r2 = b.submit(SHARED + [9, 9], max_new_tokens=12)
    out = b.run()[r2]
    assert rule.fired >= 1
    assert out == first
    b.assert_pool_consistent()


# -- server-level drive ------------------------------------------------------


def test_server_serves_int8_with_host_tier(tiny):
    """End to end through the HTTP gateway: an int8 + host-tier batcher
    behind InferenceServer serves an overcommitted burst — completions
    arrive, usage reports cached tokens on the shared-prefix repeat, and
    the pool audits clean across tiers."""
    import asyncio

    from distributed_llms_tpu.cluster.client import ServingClient

    cfg, params = tiny
    from distributed_llms_tpu.runtime.server import InferenceServer
    from distributed_llms_tpu.runtime.tokenizer import ByteTokenizer

    tok = ByteTokenizer()

    def mk():
        return ContinuousBatcher(
            cfg, params, tokenizer=tok, eos_id=tok.eos_id, pad_id=tok.pad_id,
            batch_slots=3, max_len=64, chunk_steps=4, page_size=16,
            paged_pages=9, prefix_cache=True, kv_bits=8, host_pages=16,
        )

    async def drive():
        srv = InferenceServer(mk(), model_name="tiered", host="127.0.0.1",
                              port=0)
        host, port = await srv.start()
        c = ServingClient(host, port, max_retries=0)
        outs = await asyncio.gather(*[
            c.completions({"prompt": f"tier burst {i}", "max_tokens": 24})
            for i in range(4)
        ])
        assert all(s == 200 for s, _ in outs), outs
        # Shared-prefix repeat: int8 pages share content-addressed.
        s1, o1 = await c.completions(
            {"prompt": "shared prefix " * 4, "max_tokens": 4})
        s2, o2 = await c.completions(
            {"prompt": "shared prefix " * 4, "max_tokens": 4})
        assert s1 == 200 and s2 == 200
        cached = o2["usage"]["prompt_tokens_details"]["cached_tokens"]
        assert cached > 0
        srv.batcher.assert_pool_consistent()
        await srv.stop()

    asyncio.run(drive())
