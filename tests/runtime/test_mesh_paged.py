"""Mesh-native paged serving (runtime/batcher.py, PR 11): the paged KV
pool — and every feature stacked on it since PR 1 — serves on pure
data/tensor-parallel GSPMD meshes.

The acceptance contract pinned here:

- **Bytes are the contract.**  A tensor-parallel paged batcher serves
  temp-0 token streams BYTE-IDENTICAL to the single-device paged engine
  across the composition matrix: plain paged decode, automatic
  prefix-cache hits, chunked prefill, preemption + host-tier swap
  restore, the int8 QuantKVCache pool, and the dispatch-ahead overlap
  loop on or off.  Sharding changes placement, never results.
- **The pool actually shards.**  Every pool leaf splits its KV-head axis
  over 'model' (models.kv_cache.pool_specs) — per-chip pool bytes
  divide by tp, which is the capacity claim of ROADMAP item 3.
- **Illegal layouts fail at construction.**  KV heads that do not divide
  over 'model', and the still-unsupported paged x pipelined combination,
  are rejected in milliseconds, not at the first decode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.core.config import MeshConfig, RuntimeConfig
from distributed_llms_tpu.core.observability import METRICS
from distributed_llms_tpu.models import model as model_lib, presets
from distributed_llms_tpu.models.kv_cache import QuantKVCache, pool_specs
from distributed_llms_tpu.parallel import api as api_lib
from distributed_llms_tpu.runtime import generate as gen_lib
from distributed_llms_tpu.runtime.batcher import ContinuousBatcher


@pytest.fixture(scope="module")
def tiny():
    cfg = presets.get_preset("llama-tiny", vocab_size=512)  # 2 KV heads
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def solo(cfg, params, ids, n_new):
    out = gen_lib.generate_tokens(
        params, cfg, jnp.asarray([ids], jnp.int32),
        jnp.asarray([len(ids)], jnp.int32), jax.random.key(9),
        max_new_tokens=n_new, eos_id=-1, pad_id=0,
    )
    return np.asarray(out)[0].tolist()


def _pm(cfg, devices8, data=1, model=2):
    return api_lib.make_parallel_model(
        cfg, MeshConfig(data=data, model=model),
        devices=devices8[: data * model],
    )


PAGED_KW = dict(batch_slots=2, max_len=64, chunk_steps=4, page_size=16,
                paged_pages=14)


def _ref(cfg, params, **kw):
    return ContinuousBatcher(cfg, params, **{**PAGED_KW, **kw})


def _mesh(cfg, params, devices8, data=1, model=2, **kw):
    pm = _pm(cfg, devices8, data=data, model=model)
    return ContinuousBatcher(
        cfg, pm.shard_params(params), parallel=pm, **{**PAGED_KW, **kw}
    )


def _drive(b, reqs):
    rids = [b.submit(ids, max_new_tokens=n) for ids, n in reqs]
    res = b.run()
    b.assert_pool_consistent()
    return [res[r] for r in rids]


REQS = [([7, 1, 9], 6), ([4, 4, 4, 4, 4, 4], 12), ([100, 3, 5, 2], 3),
        ([11, 12], 15)]


# -- sharding layout --------------------------------------------------------


def test_pool_shards_kv_heads_over_model(tiny, devices8):
    """The tentpole's capacity claim: every pool leaf splits its KV-head
    axis over 'model' — per-chip pool bytes are 1/tp of the global pool."""
    cfg, params = tiny
    b = _mesh(cfg, params, devices8)
    for leaf in (b.cache.k, b.cache.v):
        assert not leaf.sharding.is_fully_replicated
        shard = leaf.sharding.shard_shape(leaf.shape)
        assert shard[3] == cfg.num_kv_heads // 2  # KV-head axis halves
        assert shard[:3] + shard[4:] == leaf.shape[:3] + leaf.shape[4:]
    # The spec registry matches what the batcher built (graftcheck GC2
    # audits the same function over the fake-mesh ladder).
    specs = pool_specs(cfg, b.pm.mesh, b.cache)
    assert tuple(specs.k) == (None, None, None, "model", None)


def test_int8_pool_shards_scales_with_pages(tiny, devices8):
    cfg, params = tiny
    b = _mesh(cfg, params, devices8, kv_bits=8)
    assert isinstance(b.cache, QuantKVCache)
    for leaf in (b.cache.k, b.cache.v, b.cache.k_scale, b.cache.v_scale):
        assert not leaf.sharding.is_fully_replicated
        assert leaf.sharding.shard_shape(leaf.shape)[3] \
            == cfg.num_kv_heads // 2
    specs = pool_specs(cfg, b.pm.mesh, b.cache)
    assert tuple(specs.k_scale) == (None, None, None, "model")


# -- byte-exactness matrix --------------------------------------------------


def test_mesh_paged_matches_single_device(tiny, devices8):
    """Plain paged serving on tp2: mixed budgets, slot reuse — byte-equal
    to the single-device paged engine AND to solo decodes."""
    cfg, params = tiny
    got_ref = _drive(_ref(cfg, params), REQS)
    got = _drive(_mesh(cfg, params, devices8), REQS)
    assert got == got_ref
    for out, (ids, n) in zip(got, REQS):
        assert out == solo(cfg, params, ids, n)


def test_mesh_paged_dp_x_tp(tiny, devices8):
    """data=2 x model=2: the scheduling plane replicates, the pool shards
    heads — results still byte-equal to the single-device paged engine."""
    cfg, params = tiny
    got_ref = _drive(_ref(cfg, params), REQS)
    got = _drive(_mesh(cfg, params, devices8, data=2, model=2), REQS)
    assert got == got_ref


def test_mesh_prefix_cache_hit_byte_exact(tiny, devices8):
    """Automatic prefix caching on the sharded pool: the second request's
    cached head is served from shared (sharded) pages; accounting and
    bytes match the single-device paged engine."""
    cfg, params = tiny
    shared = list(range(40, 58)) + [3, 3]
    reqs = [(shared + [11, 12], 6), (shared + [42], 8), ([4, 4, 4], 4)]

    ref = _ref(cfg, params, prefix_cache=True)
    got_ref = _drive(ref, reqs)
    b = _mesh(cfg, params, devices8, prefix_cache=True)
    got = _drive(b, reqs)
    assert got == got_ref
    assert b.prefix_cache.hit_tokens > 0, "mesh pool never shared pages"
    assert b.prefix_cached_tokens == ref.prefix_cached_tokens


def test_mesh_chunked_prefill_byte_exact(tiny, devices8):
    """Chunked prefill on the mesh (the guard lift): a long prompt chunks
    through prefill_chunk_step(pm=...) and finishes into sharded pool
    pages — bytes equal the single-device chunked run AND the monolithic
    mesh run."""
    cfg, params = tiny
    long = list(range(1, 40))
    reqs = [(long, 8), ([7, 7, 7], 6)]
    got_ref = _drive(_ref(cfg, params, prefill_chunk=8), reqs)
    got = _drive(_mesh(cfg, params, devices8, prefill_chunk=8), reqs)
    assert got == got_ref
    got_mono = _drive(_mesh(cfg, params, devices8), reqs)
    assert got == got_mono


def test_mesh_preempt_swap_byte_exact(tiny, devices8):
    """Overcommitted storm on a tight sharded pool with the host tier
    armed: victims swap raw SHARDED pages out to host RAM and restore
    byte-exact — streams equal the single-device run and solo decodes."""
    cfg, params = tiny
    storm = [([7, 1, 9, 2], 40), ([4, 4, 4, 4], 40), ([9, 8, 7, 3], 40)]
    kw = dict(batch_slots=3, paged_pages=9, host_pages=16)
    out0 = METRICS.get_counter("batcher.kv_swaps.out")
    got_ref = _drive(_ref(cfg, params, **kw), storm)
    b = _mesh(cfg, params, devices8, **kw)
    got = _drive(b, storm)
    assert got == got_ref
    for out, (ids, n) in zip(got, storm):
        assert out == solo(cfg, params, ids, n)
    assert b.preemptions >= 1, "storm never pressured the mesh pool"
    assert METRICS.get_counter("batcher.kv_swaps.out") > out0


def test_mesh_int8_pool_byte_exact_vs_single_device_int8(tiny, devices8):
    """int8 pages on the mesh: quantization is deterministic, so the tp2
    int8 stream is byte-identical to the single-device int8 stream (the
    int8-vs-bf16 parity bound is pinned in test_kv_tiering)."""
    cfg, params = tiny
    got_ref = _drive(_ref(cfg, params, kv_bits=8), REQS)
    got = _drive(_mesh(cfg, params, devices8, kv_bits=8), REQS)
    assert got == got_ref


def test_mesh_overlap_on_off_byte_exact(tiny, devices8):
    """The dispatch-ahead loop is mesh-legal (no more degrade): overlap on
    and off serve identical bytes on tp2, and the on-leg actually
    dispatches ahead."""
    cfg, params = tiny
    reqs = [([7, 1, 9], 24), ([4, 4, 4, 4], 24)]
    b_on = _mesh(cfg, params, devices8, overlap=True)
    got_on = _drive(b_on, reqs)
    assert b_on.overlap, "mesh batcher degraded the overlap loop"
    b_off = _mesh(cfg, params, devices8, overlap=False)
    assert got_on == _drive(b_off, reqs)
    assert b_on.overlap_stats["dispatched_ahead"] >= 1
    assert got_on == _drive(_ref(cfg, params, overlap=True), reqs)


# -- config rejections ------------------------------------------------------


def test_rejects_nondivisible_kv_heads(tiny, devices8):
    """llama-tiny has 2 KV heads: a model=4 mesh cannot shard the pool —
    construction must fail loudly, naming both numbers."""
    cfg, params = tiny
    pm = _pm(cfg, devices8, model=4)
    with pytest.raises(ValueError, match="num_kv_heads 2.*'model' \\(4\\)"):
        ContinuousBatcher(cfg, pm.shard_params(params), parallel=pm,
                          **PAGED_KW)


def test_rejects_paged_on_pipelined_mesh(tiny, devices8):
    cfg, params = tiny
    pm = api_lib.make_parallel_model(cfg, MeshConfig(pipe=2, model=4))
    with pytest.raises(ValueError, match="data/tensor-parallel"):
        ContinuousBatcher(cfg, params, parallel=pm, **PAGED_KW)


def test_engine_policy_explicit_vs_inherited(tiny, devices8, tmp_path):
    """engine.continuous_batcher on a mesh engine now passes paged mode
    through; only a non-divisible head count degrades (config-inherited)
    or errors (explicit)."""
    from distributed_llms_tpu.checkpoint import store as store_lib
    from distributed_llms_tpu.runtime.engine import InferenceEngine

    cfg, params = tiny
    store_lib.save_shards(params, str(tmp_path), num_shards=1,
                          model_config=cfg)
    eng = InferenceEngine.from_store(
        str(tmp_path), rt=RuntimeConfig(max_decode_steps=8),
        mesh_cfg=MeshConfig(data=4, model=2),
    )
    b = eng.continuous_batcher(batch_slots=4, max_len=64, paged_pages=14,
                               page_size=16, prefix_cache=True)
    assert b.paged and b.pm is not None and b.prefix_cache is not None
    rid = b.submit([5, 6, 7], max_new_tokens=5)
    assert b.run()[rid] == solo(cfg, params, [5, 6, 7], 5)

    eng4 = InferenceEngine.from_store(
        str(tmp_path), rt=RuntimeConfig(max_decode_steps=8, paged_pages=14,
                                        page_size=16),
        mesh_cfg=MeshConfig(data=2, model=4),
    )
    # Config-inherited paged_pages on a non-divisible mesh degrades...
    b4 = eng4.continuous_batcher(batch_slots=2, max_len=64)
    assert not b4.paged
    # ...an explicit request errors.
    with pytest.raises(ValueError, match="does not divide"):
        eng4.continuous_batcher(batch_slots=2, max_len=64, paged_pages=14)
