"""Windowed and full attention layers mixed (three with a window and a
rotation, one over the whole prefix without), QK-norm, a ring of window
tokens a row beside pages for the full layers only, experts routed by
sigmoid scores beside a shared one and a chip's share of them (K-EXAONE)
against the plain reference, on the CPU with ``k-exaone-tiny`` in float32.
Logits are compared, never sampled tokens.  Tolerance 2e-5 on logits of
about unit size: float32 end to end on both sides, so what differs is the
order of summation (the served path reads a ring in slot order and a
prefix in pages, the reference one masked score matrix a layer)."""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.models import kv_cache, layers, model as model_lib
from distributed_llms_tpu.models.presets import get_preset
from distributed_llms_tpu.models.reference import exaone_moe
from tools.reference_check import reference_cfg

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ATOL = 2e-5
W = 8  # the tiny preset's window


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset("k-exaone-tiny")
    return cfg, model_lib.init_params(jax.random.key(0), cfg)


def reference(params, cfg, tokens, experts_held=None, **changed):
    tree = dict(params, layers=list(model_lib.hybrid_layers(params, cfg)))
    return np.asarray(exaone_moe.forward(
        tree, {**reference_cfg(cfg), **changed}, tokens,
        experts_held=experts_held))


def tokens_of(n, seed=0):
    return np.random.RandomState(seed).randint(0, 256, (n,)).astype(np.int32)


def share_of(params, cfg, first, count):
    """The model a chip holds: experts [first, first + count) of every
    expert layer's stacks, the router whole."""
    blocks = dict(params["blocks"])
    blocks["moe"] = dict(blocks["moe"], experts=jax.tree.map(
        lambda a: a[:, first: first + count], blocks["moe"]["experts"]))
    return (dataclasses.replace(cfg, experts_held=count, experts_offset=first),
            dict(params, blocks=blocks))


def admit(params, cfg, toks, bucket, row_len=64):
    """A bucket-padded admission of ``toks`` into a fresh row cache: the
    logits, the row cache (full layers' rows and the rings) and the
    counts."""
    padded = np.zeros((1, bucket), np.int32)
    padded[0, : len(toks)] = toks
    return model_lib.forward(
        params, cfg, jnp.asarray(padded),
        positions=jnp.arange(bucket, dtype=jnp.int32)[None],
        cache=kv_cache.init_cache(cfg, 1, row_len), cache_index=0,
        seq_lens=jnp.asarray([len(toks)], jnp.int32), return_aux=True)


def through_pool_and_ring(params, cfg, toks, n, bucket, slot=1, slots=3):
    """Logits [len(toks) - n + 1, V]: the last prompt position of an
    admission of the first ``n`` tokens, then a decode step a further
    token, through a pool of 8-token pages and the slot's rings."""
    blk, pages = 8, 12
    logits, row, _ = admit(params, cfg, toks[:n], bucket, pages * blk)
    out = [np.asarray(logits[0, n - 1])]
    page_list = jnp.arange(1, pages + 1, dtype=jnp.int32)
    pool = kv_cache.write_row(
        kv_cache.make_pool(cfg, pages + 1, blk, slots=slots), page_list, row,
        slot)
    tables = jnp.zeros((slots, pages), jnp.int32).at[slot].set(page_list)
    one = jnp.zeros((slots,), jnp.int32).at[slot].set(1)
    for j in range(n, len(toks)):
        lg, pool = _step(params, cfg, pool, one * int(toks[j]), one * j,
                         tables, one)
        out.append(np.asarray(lg[slot, 0]))
    return np.stack(out), pool


@functools.partial(jax.jit, static_argnums=(1,), donate_argnums=(2,))
def _step(params, cfg, pool, last, lens, tables, active):
    return model_lib.forward(
        params, cfg, last[:, None], positions=lens[:, None], cache=pool,
        cache_index=lens, kv_tables=tables, seq_lens=active)


def test_the_benchmarks_reference_is_a_copy():
    with open(os.path.join(ROOT, "distributed_llms_tpu", "models",
                           "reference", "exaone_moe.py"), "rb") as f:
        mine = f.read()
    with open(os.path.join(ROOT, "benchmark", "reference", "exaone_moe.py"),
              "rb") as f:
        assert f.read() == mine


def test_forward_without_a_cache_is_the_reference(tiny):
    cfg, params = tiny
    toks = tokens_of(45)  # five windows
    logits, _ = model_lib.forward(params, cfg, jnp.asarray(toks)[None])
    np.testing.assert_allclose(
        np.asarray(logits[0]), reference(params, cfg, toks), atol=ATOL)


@pytest.mark.parametrize("held", [None, (4, 4)])
@pytest.mark.parametrize("n,bucket", [(3, 8), (8, 8), (21, 32)])
def test_prefill_then_decode_through_pool_and_ring(tiny, n, bucket, held):
    """(a), (c) A padded prefill of a prompt shorter than the window, of
    exactly the window and of nearly three windows, its full layers' pages
    written into the pool and its rings into the slot AT THE TRUE LENGTH,
    then 27 decode steps, more than three wraps of the ring, each against
    the reference's full forward; the whole model and a chip's share of its
    experts."""
    cfg, params = tiny
    if held:
        cfg, params = share_of(params, cfg, *held)
    toks = tokens_of(n + 27, seed=n)
    served, _ = through_pool_and_ring(params, cfg, toks, n, bucket)
    ref = reference(params, cfg, toks, experts_held=held)[n - 1:]
    np.testing.assert_allclose(served, ref, atol=ATOL)


def test_a_padded_admission_leaves_the_ring_at_the_true_length(tiny):
    """(c) The rings after a prompt of 21 padded to 32 are those after the
    same 21 tokens unpadded, entry for entry: positions 13-20 at 13 mod 8
    .. 20 mod 8, nothing of the padding."""
    cfg, params = tiny
    toks = tokens_of(21, seed=3)
    _, padded, _ = admit(params, cfg, toks, 32)
    _, exact, _ = admit(params, cfg, toks, 21)
    np.testing.assert_array_equal(padded.ring_k, exact.ring_k)
    np.testing.assert_array_equal(padded.ring_v, exact.ring_v)
    assert padded.k.shape[0] == len(cfg.attn_layers) == 2
    assert padded.ring_k.shape == (6, 1, W, 2, 16)


@pytest.mark.parametrize("level", ["reference", "program"])
def test_the_shares_add_up(tiny, level):
    """(d) Over the 8 shares of 2 of the tiny model's 16 experts, the
    routed parts summed and the shared expert counted once are the uncut
    layer."""
    cfg, params = tiny
    u = jax.random.normal(jax.random.key(5), (1, 19, cfg.hidden_size))
    if level == "reference":
        p = list(model_lib.hybrid_layers(params, cfg))[3]["mlp"]
        rc = reference_cfg(cfg)
        with jax.default_matmul_precision("highest"):
            whole = exaone_moe.experts(u[0], p, rc)
            parts = sum(
                exaone_moe.experts(
                    u[0], dict(p, experts=jax.tree.map(
                        lambda a: a[first: first + 2], p["experts"])),
                    rc, experts_held=(first, 2), shared=first == 0)
                for first in range(0, 16, 2))
    else:
        def layer(cfg, params):
            moe = params["blocks"]["moe"]
            return layers.moe_dropless(u, moe, cfg, layer=2)[0]

        shared = layers.mlp_swiglu(
            u, jax.tree.map(lambda a: a[2], params["blocks"]["moe"]["shared"]))
        whole = layer(cfg, params) + shared
        parts = shared + sum(
            layer(*share_of(params, cfg, first, 2))
            for first in range(0, 16, 2))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               atol=ATOL)


@pytest.mark.parametrize("mode", ["fallback", "interpret"])
def test_the_windows_edge(tiny, mode, monkeypatch):
    """(e) Changing the token at p - window changes no windowed layer's
    output at p; changing the one at p - window + 1 does (the dense path,
    and the flash kernel's program, whose band skips tiles, on the
    interpreter)."""
    cfg, params = tiny
    monkeypatch.setenv("DLT_RAGGED_DECODE", mode)
    p0 = jax.tree.map(lambda a: a[0], params["blocks"]["swa"])
    h = jax.random.normal(jax.random.key(9), (1, 32, cfg.hidden_size))
    call = model_lib.call_of((1, 32), jnp.arange(32, dtype=jnp.int32)[None])

    def out_at(h, p):
        return np.asarray(model_lib.mixed_attention(
            h, p0, cfg, "swa", call, None, 0)[0][0, p])

    p = 29
    base = out_at(h, p)
    np.testing.assert_array_equal(out_at(h.at[0, p - W].add(1.0), p), base)
    assert np.abs(out_at(h.at[0, p - W + 1].add(1.0), p) - base).max() > 1e-4
    # ... and a full layer reads it, with no rotation.
    full = model_lib.mixed_attention(
        h.at[0, p - W].add(1.0), jax.tree.map(
            lambda a: a[0], params["blocks"]["attn"]), cfg, "attn", call,
        None, 0)[0][0, p]
    same = model_lib.mixed_attention(
        h, jax.tree.map(lambda a: a[0], params["blocks"]["attn"]), cfg,
        "attn", call, None, 0)[0][0, p]
    assert np.abs(np.asarray(full - same)).max() > 1e-4


@pytest.mark.parametrize("wrong", [
    {"full_rope": True}, {"sliding_window": 2 * W}, {"qk_norm": False}])
def test_a_wrong_model_fails_the_tolerance(tiny, wrong):
    """(f) Rotation on the full layers, a window of twice 8 and no QK-norm
    each move the reference's logits a thousand tolerances away from what
    pool and ring serve."""
    cfg, params = tiny
    toks = tokens_of(21 + 27, seed=21)
    served, _ = through_pool_and_ring(params, cfg, toks, 21, 32)
    ref = reference(params, cfg, toks, **wrong)[20:]
    assert np.abs(served - ref).max() > 1000 * ATOL


def test_bytes_of_the_real_preset():
    """(g) The pool's layer axis is the full layers', a page of 64 tokens
    is 786,432 bytes, the rings 301,989,888 whatever the rows hold, and
    the weights ISSUE 34's 9.54 GB."""
    cfg = get_preset("k-exaone-ep8")
    pool = jax.eval_shape(
        lambda: kv_cache.make_pool(cfg, 3712, 64, slots=64))
    assert pool.k.shape == pool.v.shape == (3, 3712, 64, 8, 128)
    assert pool.ring_k.shape == pool.ring_v.shape == (9, 64, 128, 8, 128)
    assert pool.conv is None
    assert kv_cache.page_bytes(cfg, 64) == 786_432 == 64 * 12_288
    rings = sum(x.size * x.dtype.itemsize for x in (pool.ring_k, pool.ring_v))
    assert rings == 301_989_888
    params = jax.eval_shape(
        lambda k: model_lib.init_params_quantized(k, cfg, 8),
        jax.ShapeDtypeStruct((), jax.random.key(0).dtype))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert abs(nbytes / 1e9 - 9.54) < 0.01
    experts = params["blocks"]["moe"]["experts"]
    assert experts["w_gate_up"].data.shape == (11, 16, 6144, 4096)
    assert params["blocks"]["moe"]["router"].shape == (11, 6144, 128)
    assert params["embed"]["wte"].shape == (19200, 6144)
    assert model_lib.layer_runs(cfg) == (
        ((("swa", "dense"),), 1),
        ((("swa", "moe"), ("swa", "moe"), ("attn", "moe"), ("swa", "moe")), 2),
        ((("swa", "moe"),), 2), ((("attn", "moe"),), 1))


def test_an_admission_in_blocks_is_the_admission_in_one_piece(
        tiny, monkeypatch):
    """(i) Attention a tile at a time (the flash kernel's program, on the
    interpreter: band and prefix), FFNs 16 tokens at a time: the logits,
    the full layers' rows and the rings of an admission of 64 are those of
    the same admission in one piece through the dense path; the expert
    layers count the same pairs, in four passes for one."""
    cfg, params = tiny
    toks = tokens_of(53, seed=8)
    whole = admit(params, cfg, toks, 64)
    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    monkeypatch.setattr(model_lib, "_TOKEN_BLOCK", 16)
    blocks = admit(params, cfg, toks, 64)
    np.testing.assert_allclose(blocks[0][0, :53], whole[0][0, :53], atol=ATOL)
    for f in ("k", "v", "ring_k", "ring_v"):
        np.testing.assert_allclose(
            getattr(blocks[1], f), getattr(whole[1], f), atol=ATOL)
    assert int(blocks[2][0]) == int(whole[2][0]) == 53 * 4 * 7
    assert (int(whole[2][1]), int(blocks[2][1])) == (7, 28)


def test_a_row_must_start_at_its_start_and_decode_from_the_pool(tiny):
    cfg, params = tiny
    toks = jnp.asarray(tokens_of(8))[None]
    with pytest.raises(ValueError, match="from its start"):
        model_lib.forward(params, cfg, toks,
                          cache=kv_cache.init_cache(cfg, 1, 32),
                          cache_index=jnp.int32(8))
    with pytest.raises(ValueError, match="page pool and the rings"):
        model_lib.forward(
            params, cfg, toks[:, :1], cache=kv_cache.init_cache(cfg, 1, 32),
            cache_index=jnp.asarray([8]), positions=jnp.asarray([[8]]),
            attn_mask=jnp.ones((1, 1, 1, 32), bool))


def test_the_window_is_the_windowed_kinds_own():
    from distributed_llms_tpu.core.config import ModelConfig

    cfg = get_preset("k-exaone-tiny")
    assert cfg.model_window is None and cfg.sliding_window == W
    assert cfg.attn_layers == (3, 7)
    assert cfg.swa_layers == (0, 1, 2, 4, 5, 6)
    assert get_preset("mistral-7b").model_window == 4096
    with pytest.raises(ValueError, match="give both or neither"):
        dataclasses.replace(cfg, sliding_window=None)
    with pytest.raises(ValueError, match="give both or neither"):
        dataclasses.replace(get_preset("lfm2-tiny"), sliding_window=8)
    assert ModelConfig().attn_rope
