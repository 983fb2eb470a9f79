"""Paged KV cache for the continuous batcher (vLLM-style block tables,
TPU-native static shapes — ops/decode_attn.paged_decode_attention).

Invariants pinned here:
- exact tokens: paged serving equals solo generate_tokens per request;
- memory: the pool is SMALLER than batch_slots * max_len yet serves the
  same workload (rows allocate only prompt+budget pages);
- backpressure: a dry pool queues requests instead of overcommitting, and
  freed pages are reused by later requests.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_llms_tpu.models import model as model_lib, presets
from distributed_llms_tpu.runtime import generate as gen_lib
from distributed_llms_tpu.runtime.batcher import ContinuousBatcher


@pytest.fixture(scope="module")
def tiny():
    cfg = presets.get_preset("llama-tiny", vocab_size=512)
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def solo(cfg, params, ids, n_new, eos_id=-1):
    arr = jnp.asarray([ids], jnp.int32)
    lens = jnp.asarray([len(ids)], jnp.int32)
    out = gen_lib.generate_tokens(
        params, cfg, arr, lens, jax.random.key(9), max_new_tokens=n_new,
        eos_id=eos_id, pad_id=0,
    )
    toks = np.asarray(out)[0].tolist()
    if eos_id >= 0 and eos_id in toks:
        toks = toks[: toks.index(eos_id) + 1]
    return toks


def _paged(cfg, params, **kw):
    kw.setdefault("batch_slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("chunk_steps", 4)
    kw.setdefault("page_size", 16)
    kw.setdefault("paged_pages", 9)  # 8 usable + scratch — vs 3*64/16 = 12
    return ContinuousBatcher(cfg, params, **kw)


def test_head_dim_16_model_shows_its_fallback(tiny, monkeypatch, dispatched):
    """llama-tiny's head dim (16) cannot tile the paged kernel: even with
    the kernel asked for, every decode trace takes the dense path — and
    the dispatch record (ops/dispatch.py) says so instead of staying
    silent."""
    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    cfg, params = tiny
    # Shapes no other test compiles: the record is written while tracing,
    # and a jit cache hit traces nothing.
    b = _paged(cfg, params, batch_slots=5, max_len=80, paged_pages=13)
    rid = b.submit([7, 1, 9], max_new_tokens=5)
    assert b.run()[rid] == solo(cfg, params, [7, 1, 9], 5)
    took = dispatched()
    assert took.get("paged_decode.fallback", 0) >= 1
    assert "paged_decode.interpret" not in took


def test_paged_mixed_budgets_match_solo(tiny):
    """More requests than slots, mixed lengths/budgets, pool smaller than
    slots*max_len — every request equals its solo run."""
    cfg, params = tiny
    reqs = [
        ([7, 1, 9], 6),
        ([4, 4, 4, 4, 4, 4], 12),
        ([100, 3, 5, 2], 3),
        ([9, 8, 7, 6, 5], 9),
        ([11, 12], 15),
        ([42], 8),
    ]
    b = _paged(cfg, params)
    rids = [b.submit(ids, max_new_tokens=n) for ids, n in reqs]
    res = b.run()
    for rid, (ids, n) in zip(rids, reqs):
        assert res[rid] == solo(cfg, params, ids, n), f"request {rid} diverged"
    # Every page returned to the pool at the end, and the allocator's
    # partition/refcount invariants audit clean (PagePool.assert_consistent
    # — the recovery-path leak detector, also run after every supervisor
    # engine restart).
    assert sorted(b.free_pages) == list(range(1, 9))
    b.assert_pool_consistent()


def test_paged_backpressure_and_reuse(tiny):
    """A pool too small for all requests at once serves them anyway by
    queueing admissions until pages free up."""
    cfg, params = tiny
    # Each request needs ceil((2+14)/16)=1 page; pool has 2 usable pages,
    # so at most 2 of the 5 requests can be in flight.
    b = _paged(cfg, params, paged_pages=3, batch_slots=3, max_len=32,
               page_size=16)
    reqs = [([5, i], 14) for i in range(5)]
    rids = [b.submit(ids, max_new_tokens=n) for ids, n in reqs]
    res = b.run()
    for rid, (ids, n) in zip(rids, reqs):
        assert res[rid] == solo(cfg, params, ids, n), f"request {rid} diverged"
    assert sorted(b.free_pages) == [1, 2]
    b.assert_pool_consistent()


def test_paged_prefix_caching(tiny):
    cfg, params = tiny
    b = _paged(cfg, params)
    prefix = [3, 1, 4, 1, 5]
    b.register_prefix("sys", prefix)
    suffix = [9, 2, 6]
    rid = b.submit(suffix, max_new_tokens=8, prefix="sys")
    res = b.run()
    assert res[rid] == solo(cfg, params, prefix + suffix, 8)


def test_paged_kernel_program_runs(tiny, monkeypatch):
    """With a kernel-tileable model (head_dim 128) the paged Pallas program
    (not the gather fallback) serves decode — spy on pallas_call."""
    from distributed_llms_tpu.ops import decode_attn

    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    calls = []
    orig = decode_attn.pl.pallas_call
    monkeypatch.setattr(
        decode_attn.pl, "pallas_call",
        lambda *a, **kw: calls.append(1) or orig(*a, **kw),
    )
    cfg = presets.get_preset(
        "llama-tiny", vocab_size=512, hidden_size=256, num_heads=2,
        num_kv_heads=2,
    )
    params = model_lib.init_params(jax.random.key(0), cfg)
    b = ContinuousBatcher(
        cfg, params, batch_slots=2, max_len=64, chunk_steps=4,
        paged_pages=9, page_size=16,
    )
    reqs = [([7, 1, 9], 6), ([4, 4], 9)]
    rids = [b.submit(ids, max_new_tokens=n) for ids, n in reqs]
    res = b.run()
    assert calls, "paged kernel did not run"
    for rid, (ids, n) in zip(rids, reqs):
        assert res[rid] == solo(cfg, params, ids, n)


def test_runtime_config_knobs_reach_engine_batcher(tiny):
    """RuntimeConfig.paged_pages/page_size flow through
    engine.continuous_batcher (the path the cluster worker uses); a mesh
    whose KV heads cannot shard degrades (config-inherited) or rejects
    (explicit), while a divisible mesh serves paged natively."""
    from distributed_llms_tpu.core.config import MeshConfig, RuntimeConfig
    from distributed_llms_tpu.parallel.api import make_parallel_model
    from distributed_llms_tpu.runtime.engine import InferenceEngine

    cfg, params = tiny
    rt = RuntimeConfig(max_seq_len=64, paged_pages=9, page_size=16)
    eng = InferenceEngine(cfg, rt, params)
    b = eng.continuous_batcher(batch_slots=2)
    assert b.paged and b.page_size == 16 and len(b.free_pages) == 8
    rid = b.submit([5, 6, 7], max_new_tokens=4)
    assert b.run()[rid] == solo(cfg, params, [5, 6, 7], 4)
    # paged_pages=0 explicitly opts back into contiguous.
    assert not eng.continuous_batcher(batch_slots=2, paged_pages=0).paged

    # llama-tiny has 2 KV heads: model=4 cannot shard the pool.
    pm = make_parallel_model(cfg, MeshConfig(data=2, model=4))
    mesh_eng = InferenceEngine(cfg, rt, params, parallel=pm)
    # Config-INHERITED paged on a NON-DIVISIBLE mesh degrades to
    # contiguous (a shared cluster config must not error mesh workers'
    # requests)...
    assert not mesh_eng.continuous_batcher(batch_slots=2).paged
    # ...but an EXPLICIT request on that mesh raises.
    with pytest.raises(ValueError, match="does not divide"):
        mesh_eng.continuous_batcher(paged_pages=9)
    # A DIVISIBLE mesh serves paged natively (mesh-native paged serving —
    # pool sharded on KV heads; byte-exactness pinned in
    # tests/runtime/test_mesh_paged.py).
    pm2 = make_parallel_model(cfg, MeshConfig(data=4, model=2))
    mesh_eng2 = InferenceEngine(cfg, rt, params, parallel=pm2)
    b2 = mesh_eng2.continuous_batcher(batch_slots=4)
    assert b2.paged and b2.pm is not None


def test_paged_batcher_over_quantized_weights(monkeypatch):
    """Weight-only quantized serving composes with PAGED batching (the
    contiguous leg is pinned by test_batcher.py): int8-resident blocks flow
    through the paged admission prefill and decode chunks into the fused
    dequant-matmul PROGRAM — a kernel-tileable config (hidden 256) plus a
    spy on _quant_matmul_2d proves the kernel (not the dequant fallback)
    ran — and tokens equal the quantized solo decode."""
    from distributed_llms_tpu.checkpoint import quantize as quant_lib
    from distributed_llms_tpu.ops import quant_matmul as qm

    monkeypatch.setenv("DLT_QUANT_MATMUL", "interpret")
    calls = []
    orig = qm._quant_matmul_2d
    monkeypatch.setattr(
        qm, "_quant_matmul_2d",
        lambda *a, **kw: calls.append(1) or orig(*a, **kw),
    )
    cfg = presets.get_preset(
        "llama-tiny", vocab_size=512, hidden_size=256, intermediate_size=256,
        num_heads=2, num_kv_heads=2,
    )
    params = model_lib.init_params(jax.random.key(0), cfg)
    qparams = {
        **params, "blocks": quant_lib.quantize_tree(params["blocks"], bits=8)
    }
    b = ContinuousBatcher(
        cfg, qparams, batch_slots=2, max_len=64, chunk_steps=4,
        paged_pages=9, page_size=16,
    )
    reqs = [([7, 1, 9], 6), ([4, 4, 4, 4], 9)]
    rids = [b.submit(ids, max_new_tokens=n) for ids, n in reqs]
    res = b.run()
    assert calls, "fused dequant-matmul program did not run"
    for rid, (ids, n) in zip(rids, reqs):
        assert res[rid] == solo(cfg, qparams, ids, n), f"req {rid} diverged"


def test_paged_rejects_bad_config(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="multiple of page_size"):
        ContinuousBatcher(cfg, params, max_len=60, paged_pages=8, page_size=16)
    with pytest.raises(ValueError, match="full-depth row"):
        ContinuousBatcher(cfg, params, max_len=64, paged_pages=3, page_size=16)
