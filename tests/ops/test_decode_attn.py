"""Ragged decode attention (ops/decode_attn.py, VERDICT r3 weak #5).

Parity: the kernel program (interpret mode on CPU — same program the TPU
compiles) must match the dense prefix-masked reference, and the batcher's
exact-token invariant must hold end-to-end with the ragged path active.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_llms_tpu.ops import decode_attn


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(key), shape, dtype)


# How a paged case hands over its pool: None is one layer's rank-4 pages;
# a number is that layer of a 3-layer stack [L, NB, BLK, KVH, D] whose
# other layers hold noise, read through the kernel's ``layer`` operand.
STACKS = [None, 0, 2]


def _stacked(pool, layer, key=7):
    """``pool`` as STACKS says: itself, or that layer of a noisy stack."""
    if layer is None:
        return pool
    noise = _rand(key, (3, *pool.shape)) * 40.0
    return noise.astype(pool.dtype).at[layer].set(pool)


def _layer_kw(layer) -> dict:
    return {} if layer is None else {"layer": layer}


@pytest.mark.parametrize(
    "b,s,h,kvh,d,lengths",
    [
        (4, 256, 8, 8, 128, [1, 100, 256, 17]),       # MHA, mixed depths
        (2, 512, 8, 2, 128, [512, 300]),              # GQA g=4, partial block
        (3, 256, 4, 4, 128, [1, 1, 1]),               # minimum depth
        (1, 1024, 16, 8, 128, [769]),                 # many blocks, ragged tail
        (2, 384, 4, 4, 128, [129, 384]),              # 128-mult, not 256-mult:
        #   block stepping must keep the kernel (bk=128), not fall back dense
    ],
)
def test_kernel_matches_dense_reference(monkeypatch, dispatched, b, s, h,
                                        kvh, d, lengths):
    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    q = _rand(0, (b, 1, h, d))
    k = _rand(1, (b, s, kvh, d))
    v = _rand(2, (b, s, kvh, d))
    ln = jnp.asarray(lengths, jnp.int32)
    got = decode_attn.ragged_decode_attention(q, k, v, ln)
    assert dispatched() == {"ragged_decode.interpret": 1}
    want = decode_attn._dense_reference(q, k, v, ln)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


# Round-5 windowed tests run fresh-process via test_isolated.py (shared
# marker — tests/conftest.py).
@pytest.mark.fragile_xla_cpu
@pytest.mark.parametrize(
    "b,s,h,kvh,d,lengths,window",
    [
        (4, 256, 8, 8, 128, [1, 100, 256, 17], 5),    # tiny window, mixed
        (2, 512, 8, 2, 128, [512, 300], 256),         # window == block size
        (1, 1024, 16, 8, 128, [769], 130),            # band crosses blocks
        (2, 256, 4, 4, 128, [200, 9], 1024),          # window > depth: no-op
        (2, 384, 4, 4, 128, [384, 130], 3),           # window inside one blk
    ],
)
def test_windowed_kernel_matches_dense(monkeypatch, b, s, h, kvh, d,
                                       lengths, window):
    """Sliding-window band: the kernel reads only [length - window,
    length) per row (first/last block clamps + in-block mask) and must
    match the dense windowed reference bit-for-tolerance."""
    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    q = _rand(0, (b, 1, h, d))
    k = _rand(1, (b, s, kvh, d))
    v = _rand(2, (b, s, kvh, d))
    ln = jnp.asarray(lengths, jnp.int32)
    got = decode_attn.ragged_decode_attention(q, k, v, ln, window=window)
    want = decode_attn._dense_reference(q, k, v, ln, window=window)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_block_stepping_keeps_kernel_at_384(monkeypatch):
    """Cache width 384 (a 128-multiple but not a 256-multiple) must step the
    K block down to 128 and stay on the kernel — not silently serve the
    dense full-width fallback."""
    import jax.experimental.pallas as pl_mod

    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    calls = []
    orig = pl_mod.pallas_call
    monkeypatch.setattr(
        decode_attn.pl, "pallas_call",
        lambda *a, **kw: calls.append(1) or orig(*a, **kw),
    )
    q = _rand(0, (2, 1, 4, 128))
    k = _rand(1, (2, 384, 4, 128))
    v = _rand(2, (2, 384, 4, 128))
    ln = jnp.asarray([129, 384], jnp.int32)
    got = decode_attn.ragged_decode_attention(q, k, v, ln)
    assert calls, "kernel was not used for the 384-wide cache"
    want = decode_attn._dense_reference(q, k, v, ln)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


# What walking a row's pages a run at a time makes new.  A run is a
# mebibyte of pages: 4 of these float32 pages of 64 (256 keys), 16 of the
# int8 ones (1,024 keys), and 19 page slots a row are a whole number of
# neither.  Rows of length 1, ending inside a run (300), on a page boundary
# inside one (64), on a run's last slot (512; int8: 1024), on the first
# key of the next run (513; 1025), and filling every slot (1216), all in
# one call.
RUN_WALK = (8, 160, 64, 19, 8, 4, 128,
            [1, 300, 512, 513, 1024, 1216, 1025, 64])
# The same walk over a pool of ONE KV head (runs of 16 pages), and over
# pages as large as a run.
RUN_WALK_1 = (4, 80, 64, 19, 7, 1, 128, [1025, 1216, 1, 1024])
RUN_WALK_512 = (3, 16, 512, 3, 4, 2, 128, [1, 513, 1536])


@pytest.mark.parametrize("stack", STACKS)
@pytest.mark.parametrize(
    "b,pool,blk,pages,h,kvh,d,lengths,tables_are,quant",
    [
        (3, 32, 64, 4, 8, 4, 128, [1, 130, 256], "scattered", False),
        #   GQA, scattered pages
        (2, 16, 128, 2, 4, 4, 128, [255, 7], "scattered", False),
        #   page == K block
        (4, 64, 8, 8, 8, 8, 128, [64, 1, 33, 17], "scattered", False),
        #   tiny 8-slot pages
        (2, 16, 64, 2, 7, 1, 128, [100, 128], "scattered", False),
        #   ONE KV head (qwen2's shard under mesh.model=4): the kernel
        #   takes the pool without its head axis
        (*RUN_WALK, "scattered", False),
        (*RUN_WALK, "scattered", True),  # the int8 leg
        # Page ids repeated between rows: a shared prefix of ten pages.
        (*RUN_WALK, "shared", False),
        (*RUN_WALK, "shared", True),
        # Junk ids past a row's depth name a page of NaNs (int8: of NaN
        # scales): one fetched would show in the answer.
        (*RUN_WALK, "junk", False),
        (*RUN_WALK, "junk", True),
        (*RUN_WALK_1, "scattered", False),
        (*RUN_WALK_1, "junk", True),
        (*RUN_WALK_512, "junk", False),
    ],
)
def test_paged_matches_contiguous(monkeypatch, dispatched, b, pool, blk,
                                  pages, h, kvh, d, lengths, tables_are,
                                  quant, stack):
    """Rows' KV scattered over a shuffled page pool must attend exactly like
    the same data laid out contiguously — read as one layer's pages or out
    of a stack of layers, float pages or int8 pages with their scales."""
    from distributed_llms_tpu.checkpoint.quantize import (
        kv_dequantize, kv_quantize)

    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    rng = np.random.RandomState(0)
    # Distinct physical pages per (row, logical page); the last page of
    # the pool is no row's.
    perm = rng.permutation(pool - 1)[: b * pages]
    tables = perm.reshape(b, pages)
    q = _rand(0, (b, 1, h, d))
    k_rows = _rand(1, (b, pages * blk, kvh, d))
    v_rows = _rand(2, (b, pages * blk, kvh, d))
    if tables_are == "shared":  # every later row starts as row 0 does
        share = min(10, pages)
        tables[1:, :share] = tables[0, :share]
        k_rows = k_rows.at[1:, : share * blk].set(k_rows[0, : share * blk])
        v_rows = v_rows.at[1:, : share * blk].set(v_rows[0, : share * blk])
    ln = jnp.asarray(lengths, jnp.int32)

    def to_pool(rows, fill, junk):
        tail = rows.shape[2:]
        pages_ = jnp.full((pool, blk, *tail), fill, rows.dtype).at[
            tables.reshape(-1)
        ].set(rows.reshape(b * pages, blk, *tail))
        return _stacked(pages_.at[pool - 1].set(junk), stack, 7)

    scales = {}
    if quant:
        (kq, ks), (vq, vs) = kv_quantize(k_rows), kv_quantize(v_rows)
        k_rows, v_rows = (kv_dequantize(kq, ks, q.dtype),
                          kv_dequantize(vq, vs, q.dtype))
        k_pool, v_pool = to_pool(kq, 0, 99), to_pool(vq, 0, 99)
        scales = dict(k_scale=to_pool(ks, 1.0, np.nan),
                      v_scale=to_pool(vs, 1.0, np.nan))
    else:
        k_pool, v_pool = (to_pool(k_rows, 0.0, np.nan),
                          to_pool(v_rows, 0.0, np.nan))
    if tables_are == "junk":
        held = -(-np.asarray(lengths) // blk)
        tables = np.where(np.arange(pages)[None, :] < held[:, None], tables,
                          pool - 1)
    got = decode_attn.paged_decode_attention(
        q, k_pool, v_pool, ln, jnp.asarray(tables, jnp.int32), **scales,
        **_layer_kw(stack))
    assert dispatched() == {"paged_decode.interpret": 1}
    want = decode_attn._dense_reference(q, k_rows, v_rows, ln)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


# The latent (MLA) leg: pages of 8 tokens, 19 page slots a row.  The run the
# kernel works out is 16 pages (whole blocks of 4 under the 19), so a full
# row is two runs; 8 is two blocks a run, 6 two blocks of 3 (a run that
# tools/paged_attn_bench.py names and no block of 4 divides).
LATENT_SHAPES = {"toy": (8, 256, 128), "64x640": (64, 640, 512)}
LATENT_SLOTS, LATENT_BLK = 19, 8


def _latent_depths(run: int) -> dict:
    """The depths the walk treats apart, in tokens, by name."""
    blk = LATENT_BLK
    block = decode_attn._latent_block_pages(run) * blk
    return {
        "empty": 0, "one": 1, "inside-block": block - 3,
        "block-last": block, "block-next": block + 1,
        "run-last": run * blk, "run-next": run * blk + 1,
        "full": LATENT_SLOTS * blk, "shared": 2 * blk + 5,
    }


@pytest.mark.parametrize("case", list(_latent_depths(8)))
@pytest.mark.parametrize("run", [None, 8, 6])
@pytest.mark.parametrize("shape", list(LATENT_SHAPES))
def test_latent_paged_matches_its_dense_form(dispatched, shape, run, case):
    """``_mla_paged_impl``'s kernel (the interpreter) against its own dense
    fallback: a row at one of the depths the walk by runs and blocks treats
    apart, between a full row and a row of one token, read out of layer 2
    of a 3-layer stack.  Every table slot past a row's depth names a page
    of NaNs (one fetched, or one dead page's value multiplied by anything
    but the buffer's leftovers, would show); ``shared``: the row starts
    with the pages of the row before it.  A row of no token answers zeros
    and disturbs no neighbour."""
    h, w, latent = LATENT_SHAPES[shape]
    blk, pages = LATENT_BLK, LATENT_SLOTS
    worked_out = decode_attn._latent_run_pages(blk, w, jnp.float32, pages)
    assert worked_out == 16 and decode_attn._latent_block_pages(6) == 3
    lengths = [pages * blk, _latent_depths(run or worked_out)[case], 1]
    b, pool = len(lengths), 3 * pages + 1
    tables = np.random.RandomState(0).permutation(pool - 1).reshape(b, pages)
    lanes = (jnp.arange(w) < latent + w // 10).astype(jnp.float32)
    q = _rand(0, (b, 1, h, w)) * lanes
    rows = _rand(1, (b, pages * blk, w)) * lanes
    if case == "shared":
        tables[1, :2] = tables[0, :2]
        rows = rows.at[1, : 2 * blk].set(rows[0, : 2 * blk])
    # (A row of no token still takes one wholly masked turn with the page
    # its first slot names, as every leg of the walk does.)
    held = np.maximum(-(-np.asarray(lengths) // blk), 1)
    junk = np.where(np.arange(pages)[None, :] < held[:, None], tables,
                    pool - 1)
    page_pool = jnp.zeros((pool, blk, w)).at[tables.reshape(-1)].set(
        rows.reshape(b * pages, blk, w))
    call = dict(latent=latent, scale=0.3 * w ** -0.5)
    args = (jnp.asarray(lengths, jnp.int32),)
    layer = jnp.asarray([2], jnp.int32)
    got = decode_attn._mla_paged_impl(
        q, _stacked(page_pool.at[pool - 1].set(np.nan), 2), *args,
        jnp.asarray(junk, jnp.int32), layer, mode="interpret", run=run,
        **call)
    want = decode_attn._mla_paged_impl(
        q, _stacked(page_pool, 2), *args, jnp.asarray(tables, jnp.int32),
        layer, mode="fallback", **call)
    assert dispatched()["mla_paged_decode.interpret"] == 1
    assert got.shape == (b, 1, h, latent)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(got)[~live].any()


@pytest.mark.parametrize("stack", STACKS)
def test_paged_fallback_matches_reference(monkeypatch, dispatched, stack):
    """The dense fallback (untileable head_dim) gathers pages correctly,
    out of the layer asked for, and leaves its trace on the dispatch
    record."""
    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    b, pool, blk, pages, h, d = 2, 8, 16, 2, 4, 64  # d=64: fallback path
    tables = jnp.asarray([[3, 0], [5, 7]], jnp.int32)
    q = _rand(0, (b, 1, h, d))
    k_rows = _rand(1, (b, pages * blk, h, d))
    v_rows = _rand(2, (b, pages * blk, h, d))
    k_pool = jnp.zeros((pool, blk, h, d)).at[tables.reshape(-1)].set(
        k_rows.reshape(b * pages, blk, h, d)
    )
    v_pool = jnp.zeros((pool, blk, h, d)).at[tables.reshape(-1)].set(
        v_rows.reshape(b * pages, blk, h, d)
    )
    ln = jnp.asarray([17, 32], jnp.int32)
    got = decode_attn.paged_decode_attention(
        q, _stacked(k_pool, stack, 7), _stacked(v_pool, stack, 8), ln,
        tables, **_layer_kw(stack))
    assert dispatched() == {"paged_decode.fallback": 1}
    want = decode_attn._dense_reference(q, k_rows, v_rows, ln)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_untileable_head_dim_falls_back(monkeypatch, dispatched):
    """d=64 is not a 128-lane multiple: the dense fallback must serve it."""
    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    q = _rand(0, (2, 1, 4, 64))
    k = _rand(1, (2, 128, 4, 64))
    v = _rand(2, (2, 128, 4, 64))
    ln = jnp.asarray([5, 99], jnp.int32)
    got = decode_attn.ragged_decode_attention(q, k, v, ln)
    assert dispatched() == {"ragged_decode.fallback": 1}
    want = decode_attn._dense_reference(q, k, v, ln)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("stack", STACKS)
@pytest.mark.parametrize("quant", [False, True])
def test_sharded_kernels_match_single_shard(monkeypatch, devices8, dispatched,
                                            quant, stack):
    """Under a tensor-parallel mesh (dispatch.sharded) the ragged and paged
    kernels run per shard inside shard_map — each shard its local KV-head
    slice, no collective — and equal the single-shard call (bit for bit,
    but for float pages' last bit), bf16 and int8 pages alike, the pool
    one layer's pages or a stack of layers; the record counts the traces
    under ``shard_map``."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_llms_tpu.checkpoint.quantize import kv_quantize
    from distributed_llms_tpu.ops import dispatch

    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    mesh = Mesh(np.array(devices8[:2]).reshape(1, 2), ("data", "model"))
    b, s, blk, h, kvh, d = 2, 128, 64, 4, 2, 128
    q = _rand(0, (b, 1, h, d))
    k, v = _rand(1, (b, s, kvh, d)), _rand(2, (b, s, kvh, d))
    ln = jnp.asarray([70, 128], jnp.int32)
    tables = jnp.asarray([[2, 0], [1, 3]], jnp.int32)

    def pool(rows):  # row-major pages of a [B, S, ...] array
        pages = jnp.zeros((4, blk, *rows.shape[2:]), rows.dtype).at[
            tables.reshape(-1)].set(rows.reshape(b * 2, blk, *rows.shape[2:]))
        return _stacked(pages, stack)

    scales, pool_scales = {}, {}
    if quant:
        (k, ks), (v, vs) = kv_quantize(k), kv_quantize(v)
        scales = dict(k_scale=ks, v_scale=vs)
        pool_scales = dict(k_scale=pool(ks), v_scale=pool(vs))
    want_r = decode_attn.ragged_decode_attention(q, k, v, ln, **scales)
    layer = _layer_kw(stack)
    want_p = decode_attn.paged_decode_attention(
        q, pool(k), pool(v), ln, tables, **pool_scales, **layer)

    def put(x, heads_axis=2):  # shard the (KV-)head axis over 'model'
        spec = [None] * x.ndim
        spec[heads_axis] = "model"
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))

    put_pool = lambda x: put(x, 2 if stack is None else 3)
    # A jit of its own a case: the record is written while tracing, and a
    # cache hit (the ragged call is the same under every ``stack``) traces
    # nothing.
    fresh = lambda fn: jax.jit(lambda *a, **kw: fn(*a, **kw))
    base = dispatched()
    with dispatch.sharded(mesh):
        got_r = fresh(decode_attn.ragged_decode_attention)(
            put(q), put(k), put(v), ln,
            **{n: put(x) for n, x in scales.items()})
        got_p = fresh(decode_attn.paged_decode_attention)(
            put(q), put_pool(pool(k)), put_pool(pool(v)), ln, tables,
            **{n: put_pool(x) for n, x in pool_scales.items()}, **layer)
    np.testing.assert_array_equal(np.asarray(got_r), np.asarray(want_r))
    if quant:
        np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
    else:
        # Float pages go through one product for all the heads a shard
        # holds: its sums run over the other heads' rows too, as zeros,
        # so two heads to a shard and one round the last bit apart.
        np.testing.assert_allclose(
            np.asarray(got_p), np.asarray(want_p), rtol=1e-6, atol=1e-6)
    new = {k_: v_ - base.get(k_, 0) for k_, v_ in dispatched().items()}
    assert new["ragged_decode.shard_map"] == new["paged_decode.shard_map"] == 1


def test_batcher_exact_tokens_with_ragged_decode(monkeypatch):
    """End-to-end: the ContinuousBatcher with the ragged kernel (interpret)
    emits tokens identical to solo generate_tokens — scheduling AND the
    ragged read change nothing about results.  head_dim 128 AND max_len 128
    make the cache kernel-tileable, so the kernel PROGRAM (not the dense
    fallback) is what runs — the spy is on pallas_call itself, which the
    fallback never reaches."""
    from distributed_llms_tpu.models import model as model_lib, presets
    from distributed_llms_tpu.runtime import generate as gen_lib
    from distributed_llms_tpu.runtime.batcher import ContinuousBatcher

    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    calls = []
    orig = decode_attn.pl.pallas_call
    monkeypatch.setattr(
        decode_attn.pl, "pallas_call",
        lambda *a, **kw: calls.append(1) or orig(*a, **kw),
    )
    cfg = presets.get_preset(
        "llama-tiny", vocab_size=512, hidden_size=256, num_heads=2,
        num_kv_heads=2,  # head_dim 128 — kernel-tileable
    )
    params = model_lib.init_params(jax.random.key(0), cfg)
    b = ContinuousBatcher(cfg, params, batch_slots=2, max_len=128, chunk_steps=4)
    assert b.cfg_decode.ragged_decode
    reqs = [([7, 1, 9], 6), ([4, 4, 4, 4, 4], 9), ([11, 12], 3)]
    rids = [b.submit(ids, max_new_tokens=n) for ids, n in reqs]
    res = b.run()
    assert calls, "ragged decode attention did not run"
    for rid, (ids, n) in zip(rids, reqs):
        solo = gen_lib.generate_tokens(
            params, cfg, jnp.asarray([ids], jnp.int32),
            jnp.asarray([len(ids)], jnp.int32), jax.random.key(9),
            max_new_tokens=n,
        )
        assert res[rid] == np.asarray(solo)[0].tolist(), f"req {rid} diverged"


@pytest.mark.fragile_xla_cpu
def test_batcher_windowed_ragged_matches_solo(monkeypatch):
    """Sliding-window model through the batcher's ragged kernel path
    (interpret): mixed budgets crossing the window boundary must match the
    solo dense-windowed decode token-for-token — the kernel's slot-space
    band equals the dense path's position-space window exactly under the
    contiguous layout."""
    from distributed_llms_tpu.models import model as model_lib, presets
    from distributed_llms_tpu.runtime import generate as gen_lib
    from distributed_llms_tpu.runtime.batcher import ContinuousBatcher

    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    calls = []
    orig = decode_attn.pl.pallas_call
    monkeypatch.setattr(
        decode_attn.pl, "pallas_call",
        lambda *a, **kw: calls.append(1) or orig(*a, **kw),
    )
    cfg = presets.get_preset(
        "llama-tiny", vocab_size=512, hidden_size=256, num_heads=2,
        num_kv_heads=2, sliding_window=5,  # head_dim 128 — kernel-tileable
    )
    params = model_lib.init_params(jax.random.key(0), cfg)
    b = ContinuousBatcher(cfg, params, batch_slots=2, max_len=128,
                          chunk_steps=4)
    assert b.cfg_decode.ragged_decode and b.cfg_decode.sliding_window == 5
    reqs = [([7, 1, 9, 4, 2, 8, 3], 9), ([4, 4, 4], 7), ([11, 12], 12)]
    rids = [b.submit(ids, max_new_tokens=n) for ids, n in reqs]
    res = b.run()
    assert calls, "ragged decode attention did not run"
    for rid, (ids, n) in zip(rids, reqs):
        solo = gen_lib.generate_tokens(
            params, cfg, jnp.asarray([ids], jnp.int32),
            jnp.asarray([len(ids)], jnp.int32), jax.random.key(9),
            max_new_tokens=n,
        )
        assert res[rid] == np.asarray(solo)[0].tolist(), f"req {rid} diverged"
