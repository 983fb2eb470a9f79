"""An admission's quantized matmuls skip the row tiles that hold only
padding (PR 39): for every family that serves a cell, the admission with
the count of real rows against the same admission without it (the parent's
route: the ``rows`` of ``models.model.call_of``'s record None), which must agree to the
last bit on everything a real token leaves behind; what the traced programs
hold; and the batcher's two counters.  Since PR 46 the flash kernel of the
two configurations of windowed and full attention layers takes the same
count: its grid ends at the last tile of queries that holds a real token."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.core.observability import METRICS
from distributed_llms_tpu.models import kv_cache, model as model_lib
from distributed_llms_tpu.models.presets import get_preset
from distributed_llms_tpu.runtime import batcher as batcher_lib

# The tiny presets at widths the kernel tiles (K and N in whole blocks of
# 128): llama with grouped heads (qwen2's block), neox (pythia's), the
# hybrid of convolutions, attention and experts (lfm2's), latent attention
# (A.X-K1's), window and full attention mixed (K-EXAONE's).
_DENSE = dict(hidden_size=256, intermediate_size=256, num_heads=2,
              head_dim=128, max_seq_len=2048)
_HYBRID = dict(hidden_size=128, intermediate_size=256, num_kv_heads=4,
               head_dim=32, moe_intermediate_size=128, max_seq_len=2048)
WIDE = {
    "llama-tiny": dict(_DENSE, num_kv_heads=1),
    "neox-tiny": dict(_DENSE, num_kv_heads=2),
    "lfm2-tiny": _HYBRID,
    "ax-k1-tiny": dict(
        hidden_size=128, intermediate_size=256, moe_intermediate_size=128,
        head_dim=32, q_lora_rank=128, kv_lora_rank=128, qk_nope_head_dim=24,
        qk_rope_head_dim=8, v_head_dim=32, max_seq_len=2048),
    "k-exaone-tiny": _HYBRID,
}
T = 768  # three row tiles: two are the call without a count


@functools.lru_cache(maxsize=None)
def wide(name):
    cfg = get_preset(name, **WIDE[name])
    return cfg, model_lib.init_params_quantized(jax.random.key(0), cfg, 8)


def tokens(n, seed=3):
    return jnp.asarray(np.random.RandomState(seed).randint(0, 256, n),
                       jnp.int32)


@pytest.fixture(autouse=True)
def interpreted_kernel(monkeypatch):
    monkeypatch.setenv("DLT_QUANT_MATMUL", "interpret")


def first_token(logits):
    """Of an admission's logits, the last real position's alone ([1, 1, V]:
    models.model.forward's ``logits_at``, PR 45)."""
    lp = jax.nn.log_softmax(logits[0, 0].astype(jnp.float32))
    return int(jnp.argmax(lp)), float(jnp.max(lp))


def admit(name, t, n, monkeypatch, counted, s=None):
    """``_prefill_row`` of ``n`` real tokens in a bucket of ``t``, told the
    count or (the parent's route) not: (logits, row cache, expert counts)."""
    cfg, params = wide(name)
    with monkeypatch.context() as mp:
        if not counted:
            real = model_lib.call_of
            mp.setattr(model_lib, "call_of", lambda *a, **kw:
                       dataclasses.replace(real(*a, **kw), rows=None))
        return jax.jit(lambda p: batcher_lib._prefill_row(
            model_lib.forward, params, cfg, jnp.float32, s or t, p,
            jnp.int32(n)))(tokens(t))


def same_to_the_bit(got, want, n):
    """Everything ``n`` real tokens leave behind: the first token and its
    logprob, their keys and values, the state that is not keys and values
    (kept at the true length), the expert layers' counts."""
    assert got[0].shape[:2] == (1, 1)
    assert first_token(got[0]) == first_token(want[0])
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        if g.ndim >= 3 and g.shape[2] >= n:  # [L, 1, S, ...]: slots by position
            g, w = g[:, :, :n], w[:, :, :n]
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    for g, w in zip(got[2:], want[2:]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("n", [100, 300])
@pytest.mark.parametrize("name", list(WIDE))
def test_a_short_prompt_in_a_wide_bucket_is_the_parents_admission(
        name, n, monkeypatch, dispatched):
    got = admit(name, T, n, monkeypatch, True)
    assert dispatched().get("quant_matmul.interpret", 0) > 0
    want = admit(name, T, n, monkeypatch, False)
    same_to_the_bit(got, want, n)
    if n <= 256:  # the second row tile's products were never computed
        assert not np.array_equal(np.asarray(got[1].k[:, :, 256:]),
                                  np.asarray(want[1].k[:, :, 256:]))


def test_blocked_ffn_counts_what_is_left_a_block(monkeypatch):
    """K-EXAONE's FFNs run a block of tokens at a time: block i is told
    ``clip(n - i * block, 0, block)`` real rows, here a length inside the
    second block of four (blocks of 768 for the test: three row tiles each),
    and a block that is all padding computes nothing."""
    monkeypatch.setattr(model_lib, "_TOKEN_BLOCK", 768)
    n, t = 768 + 130, 3072
    seen = []
    real = model_lib.layer_of

    def spy(blocks, layer, rows=None):
        seen.append(rows)
        return real(blocks, layer, rows)

    monkeypatch.setattr(model_lib, "layer_of", spy)
    got = admit("k-exaone-tiny", t, n, monkeypatch, True)
    assert seen and all(r is not None and r.shape == (1,) for r in seen)
    monkeypatch.setattr(model_lib, "layer_of", real)
    same_to_the_bit(got, admit("k-exaone-tiny", t, n, monkeypatch, False), n)
    left = jnp.clip(n - 768 * jnp.arange(4), 0, 768)
    np.testing.assert_array_equal(np.asarray(left), [768, 130, 0, 0])


def test_a_continuation_counts_the_suffix(monkeypatch, counted_kernels):
    """Behind a cached prefix the real rows are the suffix's: the suffix's
    logits and the slots it writes equal the route without a count."""
    cfg, params = wide("llama-tiny")
    prefix, n, s = 64, 140, 64 + T
    row = jax.jit(lambda p: batcher_lib._prefill_row(
        model_lib.forward, params, cfg, jnp.float32, s, p))(
            tokens(prefix, seed=5))[1]

    def run(clen):
        return jax.jit(lambda c: batcher_lib._prefill_row_with_prefix(
            model_lib.forward, params, cfg, row, jnp.int32(prefix), c,
            jnp.int32(n), clen))(tokens(T, seed=6))

    want, got = run(None), run(jnp.int32(n))
    same_to_the_bit(
        (got[0], jax.tree.map(lambda a: a[:, :, prefix:], got[1])),
        (want[0], jax.tree.map(lambda a: a[:, :, prefix:], want[1])), n)
    traced = jax.make_jaxpr(lambda c: batcher_lib._prefill_row_with_prefix(
        model_lib.forward, params, cfg, row, jnp.int32(prefix), c,
        jnp.int32(n), jnp.int32(n)))(tokens(T, seed=6))
    assert set(counted_kernels(traced)) == {True}


# -- what the traced programs hold: the set-up guard ----------------------
def _programs(name, bucket):
    """(admission at ``bucket``, decode chunk) of the tiny preset as the
    batcher jits them, traced."""
    cfg, params = wide(name)
    pool = kv_cache.make_pool(cfg, 2 * bucket // 16 + 2, 16, slots=2)
    pages = jnp.arange(1, bucket // 16 + 1, dtype=jnp.int32)
    extra = {"slot": jnp.int32(1)} if cfg.family == "hybrid" else {}
    admission = jax.make_jaxpr(
        lambda pool, prompt: batcher_lib.admit_row_paged(
            params, cfg, pool, pages, prompt, jnp.int32(bucket - 5),
            jax.random.key(1), **extra))(pool, tokens(bucket))
    b = batcher_lib.ContinuousBatcher(
        cfg, params, batch_slots=2, max_len=bucket, chunk_steps=2,
        paged_pages=2 * bucket // 16 + 2, page_size=16)
    b.submit([1, 2, 3], max_new_tokens=4)
    return admission, b


@pytest.mark.parametrize("name", ["llama-tiny", "lfm2-tiny"])
def test_set_up_guard(name, monkeypatch, counted_kernels):
    """What set-up pays for, a program: the kernel's body is traced once a
    distinct (shapes, tile) call and no more (the inner ``jax.jit`` of
    ``_quant_matmul_2d`` shares a trace between equal calls: a closed-over
    tracer or a static argument that differs a call would not); an
    admission of one or two row tiles and the decode chunk hold the parent's
    call only, a grid over every row tile, and no kernel that writes
    padding; in an admission of three row tiles every call's grid ends with
    the real rows.  The kernel's body is ONE function either way, traced
    under no branch: a branch around it cost five times its trace on the
    server's host (PERF.md, PR 39)."""
    from distributed_llms_tpu.ops import quant_matmul as qm

    bodies, calls = [], set()
    real_kernel, real_2d = qm._kernel, qm._quant_matmul_2d

    def kernel(*a, **kw):
        bodies.append(1)
        return real_kernel(*a, **kw)

    def call(x, q, s, layer, real=None, **kw):
        calls.add((x.shape, str(x.dtype), q.shape, s.shape, real is None,
                   tuple(sorted(kw.items()))))
        return real_2d(x, q, s, layer, real, **kw)

    monkeypatch.setattr(qm, "_kernel", kernel)
    monkeypatch.setattr(qm, "_quant_matmul_2d", call)
    jax.clear_caches()  # the kernel's traces of earlier tests
    wide_adm, b = _programs(name, T)
    assert set(counted_kernels(wide_adm)) == {True}
    for bucket in (256, 512):
        narrow, _ = _programs(name, bucket)
        assert set(counted_kernels(narrow)) == {False}
    assert 0 < len(bodies) <= len(calls), (len(bodies), len(calls))

    chunks = []
    real_chunk = batcher_lib.decode_chunk

    def spy(*a, **kw):
        chunks.append(real_chunk.trace(*a, **kw).jaxpr)
        return real_chunk(*a, **kw)

    monkeypatch.setattr(batcher_lib, "decode_chunk", spy)
    b.run()
    assert chunks and all(set(counted_kernels(c)) == {False} for c in chunks)


def test_counters_say_how_often_the_kernel_skips(monkeypatch):
    """``batcher.admit.matmul_rows`` is each admission's bucket and
    ``..._live`` the rows of its tiles that hold a real token, the whole
    bucket where it is one tile or two; the span carries ``live_rows``."""
    monkeypatch.setenv("DLT_QUANT_MATMUL", "fallback")
    cfg = get_preset("llama-tiny", max_seq_len=1024)
    params = model_lib.init_params(jax.random.key(0), cfg)
    b = batcher_lib.ContinuousBatcher(
        cfg, params, batch_slots=2, max_len=1024, chunk_steps=2,
        paged_pages=140, page_size=16)
    spans = []
    real = b._span

    def span(name, **attrs):
        if name == "batcher.admit.row":
            spans.append(attrs)
        return real(name, **attrs)

    monkeypatch.setattr(b, "_span", span)
    names = ("batcher.admit.matmul_rows", "batcher.admit.matmul_rows_live")
    before = [METRICS.get_counter(n) for n in names]
    for n in (100, 300, 600):  # buckets 128, 512, 1024
        b.submit([int(x) for x in tokens(n)], max_new_tokens=1)
    b.run()
    rows, live = (METRICS.get_counter(n) - was
                  for n, was in zip(names, before))
    assert (rows, live) == (128 + 512 + 1024, 128 + 512 + 768)
    assert [(a["bucket"], a["live_rows"]) for a in spans] == [
        (128, 128), (512, 512), (1024, 768)]


# -- the flash kernel's grid ends with the real tokens (PR 46) -------------
@functools.lru_cache(maxsize=None)
def banded(name, window):
    """The tiny preset at a window that the kernel's tiles of 512 cut."""
    cfg = get_preset(name, max_seq_len=2048, sliding_window=window)
    return cfg, model_lib.init_params_quantized(jax.random.key(0), cfg, 8)


@pytest.mark.parametrize("n", [700, 1030])  # 1 of 2 and 2 of 4 tiles of
#   queries live; 2 of 2 and 3 of 4
@pytest.mark.parametrize("name,window", [
    ("k-exaone-tiny", 8), ("smallthinker-tiny", 600)])
def test_a_short_prompt_ends_the_flash_kernels_grid(
        name, window, n, monkeypatch, dispatched):
    """``forward`` told ``seq_lens`` against the same call with the flash
    kernel left without the count (the parent's route): the first token,
    the pages' first ``n`` slots and the rings to the last bit, on the
    kernel's interpreter leg."""
    monkeypatch.setenv("DLT_QUANT_MATMUL", "fallback")
    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    cfg, params = banded(name, window)
    t, counts = 2048, []
    real = model_lib._self_attention
    jax.clear_caches()  # the record is written while the kernel is traced

    def admit(counted):
        def spy(q, k, v, positions, w=None, scale=None, rows=None):
            counts.append(rows is not None)
            return real(q, k, v, positions, w, scale,
                        rows if counted else None)

        with monkeypatch.context() as mp:
            mp.setattr(model_lib, "_self_attention", spy)
            return jax.jit(lambda p: batcher_lib._prefill_row(
                model_lib.forward, params, cfg, jnp.float32, t, p,
                jnp.int32(n)))(tokens(t))

    got, want = admit(True), admit(False)
    assert counts and all(counts)  # mixed_attention hands every layer one
    assert dispatched().get("flash.interpret", 0) > 0
    assert "flash.fallback" not in dispatched()
    same_to_the_bit(got, want, n)
    if n <= 1024:  # the full layers' second tile of queries was not scored:
        # the deeper layers' keys there come of zeros and not of attention
        assert not np.array_equal(np.asarray(got[1].k[1:, :, 1024:]),
                                  np.asarray(want[1].k[1:, :, 1024:]))
