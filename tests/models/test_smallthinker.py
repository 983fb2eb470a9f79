"""A full layer without rotation then three windowed ones with it, 64
ReLU-gated experts a layer routed on the block's INPUT (no dense layer, no
shared expert), a ring of window tokens a row beside pages for the full
layers (SmallThinker) against the plain reference, on the CPU with
``smallthinker-tiny`` in float32.  Logits are compared, never sampled
tokens.  Tolerance 2e-5 on logits of about unit size: float32 end to end
on both sides, so what differs is the order of summation (the served path
reads a ring in slot order and a prefix in pages, the reference one masked
score matrix a layer); the wrong models move the logits by 0.7 and more."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.models import kv_cache, model as model_lib
from distributed_llms_tpu.models.presets import get_preset
from distributed_llms_tpu.models.reference import smallthinker
from distributed_llms_tpu.ops import decode_attn
from tools.reference_check import reference_cfg

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ATOL = 2e-5
W = 8  # the tiny preset's window


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset("smallthinker-tiny")
    return cfg, model_lib.init_params(jax.random.key(0), cfg)


def reference(params, cfg, tokens, **changed):
    tree = dict(params, layers=list(model_lib.hybrid_layers(params, cfg)))
    return np.asarray(smallthinker.forward(
        tree, {**reference_cfg(cfg), **changed}, tokens))


def tokens_of(n, seed=0):
    return np.random.RandomState(seed).randint(0, 256, (n,)).astype(np.int32)


def admit(params, cfg, toks, bucket, row_len=64, **kw):
    """A bucket-padded admission of ``toks`` into a fresh row cache: the
    logits, the row cache (full layers' rows and the rings), the counts."""
    padded = np.zeros((1, bucket), np.int32)
    padded[0, : len(toks)] = toks
    return model_lib.forward(
        params, cfg, jnp.asarray(padded),
        positions=jnp.arange(bucket, dtype=jnp.int32)[None],
        cache=kv_cache.init_cache(cfg, 1, row_len), cache_index=0,
        seq_lens=jnp.asarray([len(toks)], jnp.int32), return_aux=True, **kw)


@functools.partial(jax.jit, static_argnums=(1,), donate_argnums=(2,))
def _step(params, cfg, pool, last, lens, tables, active):
    return model_lib.forward(
        params, cfg, last[:, None], positions=lens[:, None], cache=pool,
        cache_index=lens, kv_tables=tables, seq_lens=active)


def through_pool_and_ring(params, cfg, toks, n, bucket, slot=1, slots=3):
    """Logits [len(toks) - n + 1, V]: the last prompt position of an
    admission of the first ``n`` tokens (the head reads that position
    alone, as the batcher's admissions ask), then a decode step a further
    token, through a pool of 8-token pages and the slot's rings."""
    blk, pages = 8, 12
    logits, row, _ = admit(params, cfg, toks[:n], bucket, pages * blk,
                           logits_at=jnp.asarray([n - 1]))
    assert logits.shape[:2] == (1, 1)
    out = [np.asarray(logits[0, 0])]
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
    return np.stack(out)


def test_the_benchmarks_reference_is_a_copy():
    with open(os.path.join(ROOT, "distributed_llms_tpu", "models",
                           "reference", "smallthinker.py"), "rb") as f:
        mine = f.read()
    with open(os.path.join(ROOT, "benchmark", "reference", "smallthinker.py"),
              "rb") as f:
        assert f.read() == mine


def test_the_reference_imports_nothing_of_the_served_path():
    import ast

    with open(smallthinker.__file__) as f:
        tree = ast.parse(f.read())
    imported = {a.name if isinstance(n, ast.Import) else n.module
                for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom))
                for a in n.names}
    assert imported == {"__future__", "jax", "jax.numpy"}


def test_forward_without_a_cache_is_the_reference(tiny):
    cfg, params = tiny
    toks = tokens_of(45)  # five windows
    logits, _ = model_lib.forward(params, cfg, jnp.asarray(toks)[None])
    np.testing.assert_allclose(
        np.asarray(logits[0]), reference(params, cfg, toks), atol=ATOL)


@pytest.mark.parametrize("n,bucket", [(3, 8), (8, 8), (21, 32), (33, 64)])
def test_prefill_then_decode_through_pool_and_ring(tiny, n, bucket):
    """A padded prefill of a prompt shorter than the window (the row then
    CROSSES the window while it decodes: 3 tokens, 27 steps), of exactly
    the window, and two admitted past it, the full layers' pages written
    into the pool and the rings into the slot AT THE TRUE LENGTH, then 27
    decode steps, more than three wraps of the ring, each against the
    reference's full forward."""
    cfg, params = tiny
    toks = tokens_of(n + 27, seed=n)
    served = through_pool_and_ring(params, cfg, toks, n, bucket)
    np.testing.assert_allclose(
        served, reference(params, cfg, toks)[n - 1:], atol=ATOL)


def test_a_padded_admission_leaves_the_ring_at_the_true_length(tiny):
    """The rings after a prompt of 21 padded to 32 are those after the same
    21 tokens unpadded, entry for entry: positions 13-20 at 13 mod 8 .. 20
    mod 8, nothing of the padding."""
    cfg, params = tiny
    toks = tokens_of(21, seed=3)
    _, padded, _ = admit(params, cfg, toks, 32)
    _, exact, _ = admit(params, cfg, toks, 21)
    np.testing.assert_array_equal(padded.ring_k, exact.ring_k)
    np.testing.assert_array_equal(padded.ring_v, exact.ring_v)
    assert padded.k.shape[0] == len(cfg.attn_layers) == 2
    assert padded.ring_k.shape == (6, 1, W, 2, 16)


def test_an_admission_in_blocks_is_the_admission_in_one_piece(
        tiny, monkeypatch):
    """Attention a tile at a time (the flash kernel's program, on the
    interpreter: band and prefix), FFNs 16 tokens at a time with the
    router's logits, taken from the block's input BEFORE attention,
    carried through the blocks: the logits, the full layers' rows and the
    rings of an admission of 64 are those of the same admission in one
    piece through the dense path; the expert layers count the same pairs,
    in four passes for one."""
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
    assert int(blocks[2][0]) == int(whole[2][0]) == 53 * 6 * 8
    assert (int(whole[2][1]), int(blocks[2][1])) == (8, 32)


def test_a_ring_walked_in_blocks_is_the_dense_body(monkeypatch):
    """A window of four blocks of 8 tokens, the kernel's program on the
    interpreter: rows of 5, 32 and 17 live entries give the dense body's
    numbers, and nothing past a row's count is read: NaNs there (another
    row's leftovers could be anything) change nothing."""
    lw, b, w, kvh, g, d = 2, 3, 32, 2, 2, 128
    keys = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(keys[0], (b, 1, kvh * g, d))
    ring_k, ring_v = (jax.random.normal(k, (lw, b, w, kvh, d))
                      for k in keys[1:])
    counts = jnp.asarray([5, 32, 17], jnp.int32)
    monkeypatch.setenv("DLT_RAGGED_DECODE", "fallback")
    want = decode_attn.swa_decode_attention(q, ring_k, ring_v, counts, 1)
    dead = jnp.arange(w)[None, :, None, None] >= counts[:, None, None, None]
    ring_k, ring_v = (jnp.where(dead[None], jnp.nan, x)
                      for x in (ring_k, ring_v))
    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    monkeypatch.setattr(decode_attn, "ring_block", lambda *_: 8)
    got = decode_attn.swa_decode_attention(q, ring_k, ring_v, counts, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)


def test_a_small_ring_is_one_page_and_a_large_one_blocks():
    """K-EXAONE's 128 tokens at 8 heads of 128 stay the one page they were;
    4,096 tokens at 4 heads of 128 are 64 blocks of 64; a window that is no
    multiple of the block stays whole."""
    assert decode_attn.ring_block(128, 8, 128, jnp.bfloat16) == 128
    assert decode_attn.ring_block(4096, 4, 128, jnp.bfloat16) == 64
    assert decode_attn.ring_block(4000, 4, 128, jnp.bfloat16) == 4000
    assert decode_attn.ring_block(8, 2, 16, jnp.float32) == 8


@pytest.mark.parametrize("name", [
    "gpt2-tiny", "opt-tiny", "llama-tiny", "neox-tiny", "lfm2-tiny",
    "smallthinker-tiny"])
def test_the_head_over_one_position_is_that_row_of_the_head_over_all(name):
    """What every admission now asks of the forward: the final norm and the
    head over the last real position's hidden state alone give the logits
    (so the first token and its logprob) the head over all T positions
    gives at that position, in every family."""
    cfg = get_preset(name)
    params = model_lib.init_params(jax.random.key(1), cfg)
    toks, n = jnp.asarray(tokens_of(16, seed=2))[None], 11
    kw = ({"seq_lens": jnp.asarray([n])} if cfg.family == "hybrid" else {})
    every, _ = model_lib.forward(params, cfg, toks, **kw)
    one, _ = model_lib.forward(params, cfg, toks, **kw,
                               logits_at=jnp.asarray([n - 1]))
    assert one.shape == (1, 1, every.shape[-1])
    np.testing.assert_allclose(np.asarray(one[0, 0]),
                               np.asarray(every[0, n - 1]), atol=1e-6)
    assert int(jnp.argmax(one[0, 0])) == int(jnp.argmax(every[0, n - 1]))


@pytest.mark.parametrize("wrong", [
    {"gate_act": "silu"}, {"router_input": "ffn_norm"},
    {"router_input": "attn_norm"}, {"full_rope": True}, {"swa_rope": False},
    {"sliding_window": W + 1}, {"norm_topk_prob": False},
    {"score_fn": "sigmoid"}], ids=lambda w: "-".join(map(str, *w.items())))
def test_a_wrong_model_fails_the_tolerance(tiny, wrong):
    """silu for relu, the router on the FFN norm's output or on
    ``input_layernorm``'s, a rotation on the full layers, none on the
    windowed ones, the window one key wider, a softmax over all experts
    that is not renormalised and a sigmoid router each move the
    reference's logits a thousand tolerances away from what pool and ring
    serve."""
    cfg, params = tiny
    toks = tokens_of(21 + 27, seed=21)
    served = through_pool_and_ring(params, cfg, toks, 21, 32)
    ref = reference(params, cfg, toks, **wrong)[20:]
    assert np.abs(served - ref).max() > 1000 * ATOL


def test_bytes_of_the_real_preset():
    """ISSUE 45's arithmetic against the program's leaves: 12 layers as one
    scanned run of period 4; weights 6.49 GB (a layer 0.4116 GB in int8,
    embedding and head 1.556 GB in bf16); the pool's layer axis the 3 full
    layers', 6,144 bytes a token and 393,216 a page, 3,712 pages 1.46 GB;
    the rings 2,415,919,104 bytes whatever the rows hold."""
    cfg = get_preset("smallthinker-pp4")
    assert model_lib.layer_runs(cfg) == ((
        (("attn", "moe"), ("swa", "moe"), ("swa", "moe"), ("swa", "moe")),
        3),)
    assert cfg.attn_layers == (0, 4, 8) and len(cfg.swa_layers) == 9
    pool = jax.eval_shape(
        lambda: kv_cache.make_pool(cfg, 3712, 64, slots=32))
    assert pool.k.shape == pool.v.shape == (3, 3712, 64, 4, 128)
    assert pool.ring_k.shape == pool.ring_v.shape == (9, 32, 4096, 4, 128)
    assert pool.conv is None
    assert kv_cache.page_bytes(cfg, 64) == 393_216 == 64 * 6_144
    assert abs(3712 * 393_216 / 1e9 - 1.46) < 0.005
    rings = sum(x.size * x.dtype.itemsize for x in (pool.ring_k, pool.ring_v))
    assert rings == 2_415_919_104 == 9 * 32 * 8_388_608
    params = jax.eval_shape(
        lambda k: model_lib.init_params_quantized(k, cfg, 8),
        jax.ShapeDtypeStruct((), jax.random.key(0).dtype))

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    assert abs(nbytes(params) / 1e9 - 6.49) < 0.01
    assert abs(nbytes(params["blocks"]) / 12 / 1e9 - 0.4116) < 0.0002
    assert abs(nbytes((params["embed"], params["lm_head"])) / 1e9
               - 1.556) < 0.001
    experts = params["blocks"]["moe"]["experts"]
    assert experts["w_gate_up"].data.shape == (12, 64, 2560, 1536)
    assert experts["w_down"].data.shape == (12, 64, 768, 2560)
    assert params["blocks"]["moe"]["router"].shape == (12, 2560, 64)
    assert params["blocks"]["moe"]["router"].dtype == jnp.float32
    assert "shared" not in params["blocks"]["moe"]
    assert params["blocks"]["dense"]["w_gate"].data.size == 0
    assert params["embed"]["wte"].shape == (151936, 2560)
    assert params["lm_head"]["w"].shape == (2560, 151936)


def test_the_presets_new_fields():
    from distributed_llms_tpu.core.config import ModelConfig
    import dataclasses

    cfg = get_preset("smallthinker-pp4")
    assert (cfg.gate_act, cfg.moe_router_input) == ("relu", "block_input")
    assert ModelConfig().moe_router_input == "ffn_norm"
    with pytest.raises(ValueError, match="moe_router_input"):
        dataclasses.replace(cfg, moe_router_input="attention")
    with pytest.raises(ValueError, match="hybrid family's"):
        dataclasses.replace(get_preset("moe-tiny"),
                            moe_router_input="block_input")
    with pytest.raises(ValueError, match="gate_act"):
        dataclasses.replace(cfg, gate_act="swish")
