"""Flash attention kernel vs the dense reference (CPU interpret mode).

Mirrors the reference's unit-test strategy (SURVEY §4: per-layer tests with
real tensors) for the net-new Pallas kernel: every dispatch mode is checked
against ``layers.dot_product_attention`` with the equivalent mask.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.core.config import ModelConfig
from distributed_llms_tpu.models import layers, model as model_lib
from distributed_llms_tpu.ops.flash import flash_attention


def _qkv(b=2, t=37, h=4, kvh=2, d=16, s=None, seed=0):
    s = s or t
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kvh, d), jnp.float32)
    return q, k, v


def _dense(q, k, v, mask):
    g = q.shape[2] // k.shape[2]
    return layers.dot_product_attention(
        q, layers.repeat_kv(k, g), layers.repeat_kv(v, g), mask
    )


def test_static_causal_matches_dense():
    q, k, v = _qkv()
    b, t = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    ref = _dense(q, k, v, layers.causal_mask(pos, pos))
    out = flash_attention(q, k, v, block_q=16, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_dynamic_positions_match_dense():
    q, k, v = _qkv(seed=1)
    b, t = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    ref = _dense(q, k, v, layers.causal_mask(pos, pos))
    # Passing positions explicitly forces the dynamic kernel.
    out = flash_attention(
        q, k, v, q_positions=pos, k_positions=pos, block_q=16, block_k=128
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_cached_prefill_k_valid():
    # Prefill into a longer padded cache: only the first T slots are valid.
    t, s = 23, 64
    q, k, v = _qkv(t=t, s=s, seed=2)
    b = q.shape[0]
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    kpos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    k_valid = kpos < t
    ref = _dense(q, k, v, layers.causal_mask(pos, kpos, k_valid))
    out = flash_attention(
        q, k, v, q_positions=pos, k_positions=kpos, k_valid=k_valid,
        block_q=16, block_k=128,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_non_causal():
    q, k, v = _qkv(seed=3)
    ref = _dense(q, k, v, None)
    out = flash_attention(q, k, v, causal=False, block_q=16, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_mha_no_gqa():
    q, k, v = _qkv(h=4, kvh=4, seed=4)
    b, t = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    ref = _dense(q, k, v, layers.causal_mask(pos, pos))
    out = flash_attention(q, k, v, block_q=16, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_model_forward_flash_matches_dot(family):
    cfg_dot = ModelConfig(
        family=family, vocab_size=128, hidden_size=32, intermediate_size=64,
        num_layers=2, num_heads=4, num_kv_heads=2 if family == "llama" else 4,
        max_seq_len=64, dtype="float32", attn_impl="dot",
    )
    cfg_flash = ModelConfig(**{**cfg_dot.__dict__, "attn_impl": "flash"})
    params = model_lib.init_params(jax.random.key(0), cfg_dot)
    tokens = jax.random.randint(jax.random.key(1), (2, 17), 0, 128, dtype=jnp.int32)
    ref, _ = model_lib.forward(params, cfg_dot, tokens)
    out, _ = model_lib.forward(params, cfg_flash, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


# Round-5 windowed-kernel tests: compile-heavy, so they run fresh-process
# via tests/runtime/test_isolated.py (shared marker — tests/conftest.py).
@pytest.mark.fragile_xla_cpu
@pytest.mark.parametrize("window", [1, 3, 37, 200])
def test_windowed_static_matches_dense(window):
    """Static-causal path with a sliding window: every tile class (fully
    visible, boundary on the diagonal, boundary on the window's lower
    edge, dead above, dead below) vs the dense windowed mask.  t=200 with
    16/128 tiles crosses all of them; window >= t degenerates to plain
    causal."""
    q, k, v = _qkv(t=200, seed=7)
    b, t = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    ref = _dense(q, k, v, layers.causal_mask(pos, pos, window=window))
    out = flash_attention(q, k, v, block_q=16, block_k=128, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.fragile_xla_cpu
def test_windowed_dynamic_matches_dense():
    """Dynamic path (explicit positions + validity) with a window: padded
    cache prefill where only the first T slots are valid."""
    t, s, window = 23, 64, 5
    q, k, v = _qkv(t=t, s=s, seed=8)
    b = q.shape[0]
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    kpos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    k_valid = kpos < t
    ref = _dense(q, k, v, layers.causal_mask(pos, kpos, k_valid,
                                             window=window))
    out = flash_attention(
        q, k, v, q_positions=pos, k_positions=kpos, k_valid=k_valid,
        block_q=16, block_k=128, window=window,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_windowed_validation():
    q, k, v = _qkv(seed=9)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=3)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, window=0)


@pytest.mark.fragile_xla_cpu
def test_windowed_grad_matches_dot():
    """Gradients through the windowed flash forward (dense-recompute
    backward must carry the window) vs the windowed dot path."""
    import dataclasses

    cfg = ModelConfig(
        family="llama", vocab_size=64, hidden_size=32, intermediate_size=64,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=32,
        dtype="float32", attn_impl="flash", sliding_window=3,
    )
    cfg_dot = dataclasses.replace(cfg, attn_impl="dot")
    params = model_lib.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 9), 0, 64, dtype=jnp.int32)

    def loss(p, c):
        lg, _ = model_lib.forward(p, c, toks)
        return jnp.mean(lg**2)

    g1 = jax.grad(lambda p: loss(p, cfg))(params)
    g2 = jax.grad(lambda p: loss(p, cfg_dot))(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_grad_through_flash_matches_dot():
    import dataclasses

    cfg = ModelConfig(
        family="llama", vocab_size=64, hidden_size=32, intermediate_size=64,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=32,
        dtype="float32", attn_impl="flash",
    )
    cfg_dot = dataclasses.replace(cfg, attn_impl="dot")
    params = model_lib.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 9), 0, 64, dtype=jnp.int32)

    def loss(p, c):
        lg, _ = model_lib.forward(p, c, toks)
        return jnp.mean(lg**2)

    g1 = jax.grad(lambda p: loss(p, cfg))(params)
    g2 = jax.grad(lambda p: loss(p, cfg_dot))(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_offset_positions_match_dot():
    import dataclasses

    cfg = ModelConfig(
        family="llama", vocab_size=64, hidden_size=32, intermediate_size=64,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=64,
        dtype="float32", attn_impl="flash",
    )
    cfg_dot = dataclasses.replace(cfg, attn_impl="dot")
    params = model_lib.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 9), 0, 64, dtype=jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(9, dtype=jnp.int32) + 5, (2, 9))
    l1, _ = model_lib.forward(params, cfg, toks, positions=pos)
    l2, _ = model_lib.forward(params, cfg_dot, toks, positions=pos)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-4)


def test_generate_flash_matches_dot(monkeypatch, dispatched):
    """The one-shot prefill into a padded cache (no mask, a traced offset of
    0: models.model._continuation_attention) on the kernel's interpreter leg
    against the dense body.  Since PR 47 the body is chosen by what the call
    can see and not by ``attn_impl``, which still picks the no-cache route."""
    from distributed_llms_tpu.runtime import generate as gen_lib

    cfg_dot = ModelConfig(
        family="llama", vocab_size=128, hidden_size=32, intermediate_size=64,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=64,
        dtype="float32", attn_impl="dot",
    )
    cfg_flash = ModelConfig(**{**cfg_dot.__dict__, "attn_impl": "flash"})
    params = model_lib.init_params(jax.random.key(0), cfg_dot)
    prompt = jax.random.randint(jax.random.key(1), (2, 9), 0, 128, dtype=jnp.int32)
    lens = jnp.array([5, 9], dtype=jnp.int32)
    rng = jax.random.key(2)
    ref = gen_lib.generate_tokens(params, cfg_dot, prompt, lens, rng, max_new_tokens=6)
    assert "flash.interpret" not in dispatched()
    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    out = gen_lib.generate_tokens(params, cfg_flash, prompt, lens, rng, max_new_tokens=6)
    assert dispatched().get("flash.interpret", 0) > 0
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# -- an admission's fresh row (models.model._self_attention), PR 35 ---------

def _admission_case(t, h, kvh, d, dv, seed):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (1, t, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, t, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, t, kvh, dv), jnp.float32)
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    return q, k, v, layers.causal_mask(pos, pos)


@pytest.mark.parametrize("h,kvh,t", [
    (28, 4, 64), (28, 4, 2048),  # qwen2-7b
    (32, 32, 256),  # pythia-6.9b
    (64, 8, 320),  # k-exaone
])
def test_the_cells_head_layouts_at_an_admissions_tiles(h, kvh, t):
    """The static-causal path as an admission calls it: heads of 128, the
    cells' query heads over their key heads, tiles of 1,024."""
    q, k, v, mask = _admission_case(t, h, kvh, 128, 128, seed=t)
    out = flash_attention(q, k, v, block_q=1024, block_k=1024, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_dense(q, k, v, mask)), atol=2e-5)


@pytest.mark.parametrize("t", [64, 1024])
@pytest.mark.parametrize("lanes", [192, 256])
def test_latent_heads_a_scale_and_a_value_width_of_its_own(t, lanes):
    """Latent attention's expanded heads: 64 heads of 192 for q and k, 128
    for v, YaRN's scale.  ``lanes`` 192: the widths as they are (what
    _self_attention hands the kernel, on the interpreter here); 256: zero
    lanes on q and k, which add nothing to a score."""
    scale = 0.1147
    q, k, v, mask = _admission_case(t, 64, 64, 192, 128, seed=7 + t)
    want = layers.dot_product_attention(q, k, v, mask, scale)
    pad = ((0, 0),) * 3 + ((0, lanes - 192),)
    out = flash_attention(
        jnp.pad(q, pad), jnp.pad(k, pad), v, block_q=1024, block_k=1024,
        interpret=True, scale=scale)
    assert out.shape == (1, t, 64, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_a_scale_reaches_the_backward_pass():
    q, k, v, mask = _admission_case(24, 4, 2, 16, 8, seed=11)
    f = lambda q, k, v: jnp.sum(flash_attention(  # noqa: E731
        q, k, v, block_q=16, block_k=128, interpret=True, scale=0.3) ** 2)
    g = lambda q, k, v: jnp.sum(layers.dot_product_attention(  # noqa: E731
        q, layers.repeat_kv(k, 2), layers.repeat_kv(v, 2), mask, 0.3) ** 2)
    for got, want in zip(jax.grad(f, (0, 1, 2))(q, k, v),
                         jax.grad(g, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)


# The shapes an admission of the two hybrid configurations hands the kernel
# (query heads over key heads, the windowed layers' band in tiles of 512,
# the full layers in tiles of 1,024), on heads of 16 to keep the
# interpreter's steps short: (T, window, block, H, KVH).
_ADMISSIONS = {
    "full-1024-28over4": (4096, None, 1024, 28, 4),
    "band128-512-64over8": (2048, 128, 512, 64, 8),
    "band4096-512-28over4": (6144, 4096, 512, 28, 4),
}


@functools.lru_cache(maxsize=None)
def _whole_call(case):
    """(q, k, v, the call without ``rows``) of a case."""
    t, window, block, h, kvh = _ADMISSIONS[case]
    q, k, v = _qkv(b=1, t=t, h=h, kvh=kvh, d=16, seed=t)
    return q, k, v, flash_attention(
        q, k, v, window=window, block_q=block, block_k=block, interpret=True)


@pytest.mark.parametrize("real", ["1", "edge", "edge+1", "T-3", "T"])
@pytest.mark.parametrize("case", list(_ADMISSIONS))
def test_the_grid_ends_at_the_last_tile_that_holds_a_real_row(case, real):
    """``rows``: the real tokens' outputs are the call's without it bit for
    bit, the Q tiles past the last live one come back as zeros, and nothing
    that lies past that tile (NaN in q, k and v) reaches a real row."""
    t, window, block, _, _ = _ADMISSIONS[case]
    real = {"1": 1, "edge": 2 * block, "edge+1": 2 * block + 1,
            "T-3": t - 3, "T": t}[real]
    q, k, v, want = _whole_call(case)
    live = -(-real // block) * block
    rows = jnp.asarray([real], jnp.int32)
    kw = dict(window=window, block_q=block, block_k=block, interpret=True)
    got = flash_attention(q, k, v, rows=rows, **kw)
    np.testing.assert_array_equal(
        np.asarray(got[:, :live]), np.asarray(want[:, :live]))
    assert not np.asarray(got[:, live:]).any()
    poison = lambda a: a.at[:, live:].set(jnp.nan)  # noqa: E731
    got = flash_attention(poison(q), poison(k), poison(v), rows=rows, **kw)
    np.testing.assert_array_equal(
        np.asarray(got[:, :live]), np.asarray(want[:, :live]))
    assert not np.asarray(got[:, live:]).any()


def test_rows_is_one_sequences_count_on_the_static_causal_path():
    q, k, v = _qkv(b=2, t=64)
    rows = jnp.asarray([5], jnp.int32)
    with pytest.raises(ValueError, match="ONE right-padded sequence"):
        flash_attention(q, k, v, rows=rows, block_q=16, block_k=128)
    pos = jnp.arange(64, dtype=jnp.int32)[None]
    with pytest.raises(ValueError, match="ONE right-padded sequence"):
        flash_attention(q[:1], k[:1], v[:1], q_positions=pos,
                        k_positions=pos, rows=rows, block_q=16, block_k=128)


@pytest.mark.parametrize("t,rows,block,window", [
    (4096, 2982, 1024, None), (4096, 2982, 512, 4096), (4096, 1, 512, 128),
    (2048, 1543, 1024, None), (2048, 1024, 512, 128), (2048, 1025, 512, 700),
    (1024, 3, 1024, None), (1024, 3, 512, 128), (512, 100, 512, 128),
    (3072, 3072, 512, 1), (3072, 2049, 1024, None), (320, 7, 1024, None),
])
def test_live_tiles_counts_what_the_grid_visits(t, rows, block, window):
    """``live_tiles`` against a count over every (row, column) pair: a tile
    is live if it holds a pair inside the causal band and the window, and
    visited if besides its Q tile holds a real row (a call of one Q tile
    visits it whatever the count)."""
    from distributed_llms_tpu.ops.flash import live_tiles

    r, c = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = c <= r
    if window is not None:
        seen &= c > r - window
    bq, bk = min(block, t), min(block, -(-t // 128) * 128)
    before = now = 0
    for q0 in range(0, t, bq):
        for k0 in range(0, t, bk):
            if seen[q0:q0 + bq, k0:k0 + bk].any():
                before += bq * bk
                now += bq * bk if q0 < rows or t <= bq else 0
    assert live_tiles(t, rows, block, window) == (before, now)
    assert 0 < now <= before


# -- a row's continuation: the diagonal shifted by ``start`` (PR 47) ---------

_S, _BK = 1024, 256  # a row cache of four tiles of keys


@pytest.mark.parametrize("prefix", ["start", "inside", "edge", "end"])
@pytest.mark.parametrize("heads,window", [((7, 1), None), ((2, 2), 200)])
@pytest.mark.parametrize("tq", [8, 64, 128])
def test_a_continuation_walks_the_tiles_that_hold_a_key(
        tq, heads, window, prefix):
    """``flash_attention(start=)`` as a continuation calls it, against
    ``_dense_reference`` on the clean row: Tq new tokens behind a run of
    ``prefix`` slots (none; one that ends inside a tile; one whose new
    tokens fill a tile to its edge; one that fills the row), the seven query
    heads of a KV group over their one head and one over one, a window and
    none.  Every key past the new tokens is NaN, and every value past the
    LAST LIVE TILE (a masked key's score is replaced; a masked value is
    weighted by 0, which a NaN survives, and the row caches hold numbers
    there): nothing past the live tiles is read."""
    from distributed_llms_tpu.ops import flash

    h, kvh = heads
    n = {"start": 0, "inside": 100, "edge": 2 * _BK - tq, "end": _S - tq}[prefix]
    keys = n + tq
    ks = jax.random.split(jax.random.key(tq + n), 3)
    q = jax.random.normal(ks[0], (1, tq, h, 128), jnp.float32)
    k = jax.random.normal(ks[1], (1, _S, kvh, 128), jnp.float32)
    v = jax.random.normal(ks[2], (1, _S, kvh, 128), jnp.float32)
    qpos = (n + jnp.arange(tq, dtype=jnp.int32))[None]
    ref = flash._dense_reference(
        q, k, v, qpos, None, (jnp.arange(_S) < keys)[None], True, window)
    live = flash.live_keys(_S, keys, _BK)
    assert keys <= live < keys + _BK and live % _BK == 0
    slot = jnp.arange(_S)[None, :, None, None]
    out = flash_attention(
        q, jnp.where(slot >= keys, jnp.nan, k),
        jnp.where(slot >= live, jnp.nan, v), window=window, block_q=1024,
        block_k=_BK, interpret=True, start=jnp.asarray([n], jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("tq,heads,window,bq,bk", [
    (300, (7, 1), None, 128, 256),  # a head's run is three tiles of queries
    (300, (4, 2), 100, 128, 128),  # ... whose padded rows pass the last key
    (256, (6, 3), 300, 256, 128), (20, (4, 2), None, 1024, 512)])
def test_a_long_suffixs_heads_are_runs_of_whole_tiles(
        tq, heads, window, bq, bk):
    """Where the heads of a KV group do not fit one tile of queries, each
    head's run of rows is padded to whole tiles and a tile lies inside one
    run."""
    from distributed_llms_tpu.ops import flash

    h, kvh = heads
    s, n, d = (96, 30, 16) if tq == 20 else (1024, 513, 128)
    ks = jax.random.split(jax.random.key(tq), 3)
    q = jax.random.normal(ks[0], (1, tq, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, s, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, s, kvh, d), jnp.float32)
    qpos = (n + jnp.arange(tq, dtype=jnp.int32))[None]
    ref = flash._dense_reference(
        q, k, v, qpos, None, (jnp.arange(s) < n + tq)[None], True, window)
    slot = jnp.arange(s)[None, :, None, None]
    out = flash_attention(
        q, jnp.where(slot >= n + tq, jnp.nan, k),
        jnp.where(slot >= flash.live_keys(s, n + tq, bk), jnp.nan, v),
        window=window, block_q=bq, block_k=bk, interpret=True,
        start=jnp.asarray([n], jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_start_stands_for_the_positions_and_the_validity_vector():
    from distributed_llms_tpu.ops import flash

    q, k, v = _qkv(b=1, t=8, s=64)
    start = jnp.asarray([32], jnp.int32)
    qpos = (32 + jnp.arange(8, dtype=jnp.int32))[None]
    for extra in ({"k_valid": jnp.ones((1, 64), bool)},
                  {"k_positions": jnp.arange(64, dtype=jnp.int32)[None]},
                  {"q_positions": qpos}, {"causal": False},
                  {"rows": jnp.asarray([8], jnp.int32)}):
        with pytest.raises(ValueError, match="start"):
            flash_attention(q, k, v, start=start, **extra)
    # ... and is the dynamic path's call with them spelt out: the queries at
    # 32 onward, the cache's first 40 slots.
    got = flash_attention(q, k, v, start=start, interpret=True)
    want = flash_attention(
        q, k, v, q_positions=qpos, interpret=True,
        k_valid=(jnp.arange(64) < 40)[None])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    assert flash.live_keys(64, 40, 512) == 128  # (one tile, padded to lanes)
    assert flash.live_keys(4096, 1408, 512) == 1536
    assert flash.live_keys(4096, 4096, 512) == 4096
