"""A row's continuation (PR 47): the suffix behind a cached or named prefix
and a chunk of a chunked prefill are scored by ``models.model.
_continuation_attention`` from a scalar write offset and no mask: the flash
kernel with its diagonal shifted (``ops.flash.flash_attention(start=)``) on
the kernel's legs, the dense body over every slot elsewhere.  Here on the
interpreter's leg (``DLT_RAGGED_DECODE=interpret``), against the dense body
and against the same prompts served cold."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_llms_tpu.core.observability import METRICS
from distributed_llms_tpu.models import kv_cache, model as model_lib, presets
from distributed_llms_tpu.runtime import batcher as batcher_lib
from distributed_llms_tpu.runtime import generate as gen_lib
from distributed_llms_tpu.runtime.batcher import ContinuousBatcher
from distributed_llms_tpu.runtime.session import continuation_mask


@pytest.fixture(scope="module")
def tiny():
    cfg = presets.get_preset("llama-tiny", vocab_size=512)
    return cfg, model_lib.init_params(jax.random.key(0), cfg)


def solo(cfg, params, ids, n_new):
    out = gen_lib.generate_tokens(
        params, cfg, jnp.asarray([ids], jnp.int32),
        jnp.asarray([len(ids)], jnp.int32), jax.random.key(9),
        max_new_tokens=n_new,
    )
    return np.asarray(out)[0].tolist()


def counters(*names):
    c = METRICS.snapshot()["counters"]
    return [c.get(n, 0) for n in names]


@pytest.mark.parametrize("window", [None, 9])
@pytest.mark.parametrize("prefix_len,tc", [(16, 8), (21, 16), (40, 24)])
def test_forward_without_a_mask_is_the_call_with_the_continuation_mask(
        tiny, prefix_len, tc, window, monkeypatch, dispatched):
    """``forward`` on a continuation with ``attn_mask=None`` (the kernel,
    interpreted) against the same call handed ``continuation_mask`` (the
    dense body over all 64 slots): the chunk's logits and the row cache, to
    the tolerance the kernel's parity tests hold it to against
    ``_dense_reference``."""
    cfg, params = tiny
    if window:
        cfg = presets.get_preset("llama-tiny", vocab_size=512,
                                 sliding_window=window)
    s = 64
    ids = jax.random.randint(jax.random.key(prefix_len), (1, prefix_len + tc),
                             1, 500, dtype=jnp.int32)
    _, row = model_lib.forward(
        params, cfg, ids[:, :prefix_len], cache=kv_cache.init_cache(
            cfg, 1, s, dtype=jnp.float32), cache_index=0)
    positions = (prefix_len + jnp.arange(tc, dtype=jnp.int32))[None]
    slots = jnp.arange(s, dtype=jnp.int32)
    call = dict(positions=positions, cache=row,
                cache_index=jnp.int32(prefix_len))
    want, row_dense = model_lib.forward(
        params, cfg, ids[:, prefix_len:], **call, attn_mask=continuation_mask(
            (slots < prefix_len)[None], prefix_len, tc, slots))
    before = dispatched()  # (the prefix's own prefill: the CPU's fallback)
    assert "flash.interpret" not in before
    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    got, row_kernel = model_lib.forward(params, cfg, ids[:, prefix_len:], **call)
    assert dispatched().get("flash.interpret", 0) > 0
    assert dispatched().get("flash.fallback") == before.get("flash.fallback")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(row_kernel.k), np.asarray(row_dense.k), atol=2e-5)


def test_a_decode_step_and_a_gapped_window_stay_on_the_dense_body(
        tiny, monkeypatch, dispatched):
    """One token a call is a decode step, and a windowed model whose caller
    maps the slots' positions is the gapped layout: neither is the kernel's,
    on any leg."""
    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    cfg, params = tiny
    row = kv_cache.init_cache(cfg, 1, 32, dtype=jnp.float32)
    tok = jnp.asarray([[7]], jnp.int32)
    model_lib.forward(params, cfg, tok, cache=row, cache_index=jnp.int32(5))
    windowed = presets.get_preset("llama-tiny", vocab_size=512,
                                  sliding_window=4)
    model_lib.forward(
        params, windowed, jnp.asarray([[7, 8, 9]], jnp.int32), cache=row,
        cache_index=jnp.int32(5),
        key_positions=jnp.arange(32, dtype=jnp.int32)[None])
    assert not dispatched()


SHARED = list(np.random.RandomState(7).randint(1, 500, size=40))


def test_a_prefix_cache_hit_takes_the_kernel_and_serves_the_cold_answer(
        tiny, monkeypatch, dispatched):
    """A batcher on the interpreter's leg: the second request hits the
    first one's pages and is admitted by ``admit_row_auto_paged``, whose
    continuation the flash kernel scores; its tokens and its first token's
    logprob are those of the same prompt served cold, and the counters say
    which slots were scored."""
    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    jax.clear_caches()  # the record is written while the kernel is traced
    _, params = tiny  # (a rotary model's weights do not know its max_seq_len)
    cfg = presets.get_preset("llama-tiny", vocab_size=512, max_seq_len=1024)
    prompt = SHARED + [4, 4]

    def serve(prefix_cache, warm):
        b = ContinuousBatcher(
            cfg, params, batch_slots=3, max_len=640, chunk_steps=4,
            page_size=16, paged_pages=48, prefix_cache=prefix_cache)
        if warm:
            b.submit(SHARED + [7, 1, 9], max_new_tokens=2)
            b.run()
        before = counters("batcher.admit.cont_keys",
                          "batcher.admit.cont_keys_live")
        took = dispatched()
        rid = b.submit(prompt, max_new_tokens=5)
        out = b.run()[rid]
        took = {k: v - took.get(k, 0) for k, v in dispatched().items()}
        after = counters("batcher.admit.cont_keys",
                         "batcher.admit.cont_keys_live")
        return (out, b.result_logprobs[rid][0], b.prefix_cached_tokens.get(rid, 0),
                took, [a - c for a, c in zip(after, before)])

    cold, cold_lp, cached, _, scored = serve(False, False)
    assert cached == 0 and scored == [0, 0]  # a fresh row scores no cache
    hit, hit_lp, cached, took, (cont_keys, live) = serve(True, True)
    assert cached == 32
    assert hit == cold
    assert abs(hit_lp - cold_lp) < 1e-4
    assert took.get("flash.interpret", 0) > 0 and "flash.fallback" not in took
    # The suffix's bucket behind 32 cached tokens: one tile of 512 keys of
    # the row's 640 slots, of which the prompt's 42 hold a key.
    assert live == len(prompt) == 42
    assert live <= cont_keys == 512 < 640


def _named(cfg, params, **kw):
    b = ContinuousBatcher(cfg, params, batch_slots=2, max_len=64,
                          chunk_steps=4, **kw)
    b.register_prefix("sys", SHARED[:21])
    rid = b.submit(SHARED[21:30], max_new_tokens=4, prefix="sys")
    return b, rid, SHARED[:30]


def _hit(cfg, params):
    b = ContinuousBatcher(cfg, params, batch_slots=2, max_len=64,
                          chunk_steps=4, page_size=16, paged_pages=16,
                          prefix_cache=True)
    b.submit(SHARED[:34], max_new_tokens=2)
    b.run()
    return b, b.submit(SHARED[:32] + [5, 6, 7], max_new_tokens=4), \
        SHARED[:32] + [5, 6, 7]


def _chunked(cfg, params):
    b = ContinuousBatcher(cfg, params, batch_slots=2, max_len=64,
                          chunk_steps=4, prefill_chunk=8)
    return b, b.submit(SHARED[:30], max_new_tokens=4), SHARED[:30]


@pytest.mark.parametrize("caller,make", [
    ("admit_row_with_prefix", _named),
    ("admit_row_with_prefix_paged",
     lambda c, p: _named(c, p, page_size=16, paged_pages=16)),
    ("admit_row_auto_paged", _hit),
    ("prefill_chunk_step", _chunked),
])
def test_every_caller_of_the_prefix_prefill_takes_the_continuation_body(
        tiny, caller, make, monkeypatch, dispatched):
    """The four programs that call ``_prefill_row_with_prefix`` (a named
    prefix, contiguous and paged; a prefix cache's hit; a chunk of a chunked
    prefill) hand the model no mask, reach ``_continuation_attention`` with a
    traced scalar offset, take the kernel on its interpreter's leg, and emit
    the tokens of the whole prompt decoded alone."""
    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    jax.clear_caches()
    cfg, params = tiny
    seen = []
    real = model_lib._continuation_attention

    def spy(q, ck, cv, positions, cache_index, *rest):
        seen.append((q.shape[1], ck.shape[1],
                     isinstance(cache_index, jax.core.Tracer)))
        return real(q, ck, cv, positions, cache_index, *rest)

    monkeypatch.setattr(model_lib, "_continuation_attention", spy)
    called = []
    program = getattr(batcher_lib, caller)
    monkeypatch.setattr(
        batcher_lib, caller,
        lambda *a, **kw: called.append(1) or program(*a, **kw))
    b, rid, whole = make(cfg, params)
    out = b.run()[rid]
    assert called, f"{caller} did not run"
    assert seen and all(t > 1 and traced for t, _, traced in seen)
    assert dispatched().get("flash.interpret", 0) > 0
    assert "flash.fallback" not in dispatched()
    monkeypatch.setenv("DLT_RAGGED_DECODE", "fallback")
    assert out == solo(cfg, params, whole, 4)
