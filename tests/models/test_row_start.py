"""An admission's fresh row attends among its own T tokens, not to the row
cache's ``max_len`` slots (PR 35): the start the model sees while tracing
(runtime.batcher._prefill_row passes the Python 0) against the traced
start it replaced, over the families that serve a cell; what the jitted
admission program then holds; and the batcher's two counters."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.core.observability import METRICS
from distributed_llms_tpu.models import kv_cache, model as model_lib
from distributed_llms_tpu.models.presets import get_preset
from distributed_llms_tpu.runtime import batcher as batcher_lib

# llama with grouped heads (qwen2's block), neox (pythia's), the hybrid of
# convolutions and attention (lfm2's), latent attention (A.X-K1's).
PRESETS = ["llama-tiny", "neox-tiny", "lfm2-tiny", "ax-k1-tiny"]
T, S = 56, 80  # a bucket and a row cache: no width of a tiny preset


@functools.lru_cache(maxsize=None)
def tiny(name):
    cfg = get_preset(name)
    return cfg, model_lib.init_params(jax.random.key(0), cfg)


def tokens(n=T, seed=3):
    return jnp.asarray(np.random.RandomState(seed).randint(0, 256, n),
                       jnp.int32)


def traced_start(params, cfg, prompt, plen):
    """The route before PR 35: the same call with the start a tracer, so
    the T queries are scored against all S slots under a mask."""
    state = ({"seq_lens": plen[None], "return_aux": True}
             if cfg.family == "hybrid" else {})
    return jax.jit(lambda start: model_lib.forward(
        params, cfg, prompt[None, :],
        positions=jnp.arange(prompt.shape[0], dtype=jnp.int32)[None, :],
        cache=kv_cache.init_cache(cfg, 1, S), cache_index=start, **state,
    ))(jnp.int32(0))


@pytest.mark.parametrize("name", PRESETS)
def test_the_static_start_is_the_traced_start(name):
    """Logits and the row cache's first T slots within float32 tolerance
    (another order of summation: T keys, not S), slots past T untouched,
    state that is not keys and values at the true length either way."""
    cfg, params = tiny(name)
    prompt, plen = tokens(), jnp.int32(T - 5)
    want = traced_start(params, cfg, prompt, plen)
    got = jax.jit(lambda: batcher_lib._prefill_row(
        model_lib.forward, params, cfg, jnp.float32, S, prompt, plen))()
    # (the admission's head reads the last real position alone: PR 45)
    assert got[0].shape == (1, 1, want[0].shape[-1])
    np.testing.assert_allclose(np.asarray(got[0][0, 0]),
                               np.asarray(want[0][0, T - 6]),
                               atol=3e-5, rtol=1e-5)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=3e-5, rtol=1e-5)
    assert not np.asarray(got[1].k[:, :, T:]).any()
    if len(got) > 2:  # the expert layers' counts of the real tokens
        np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def admission_eqns(name, prompt_len=T):
    cfg, params = tiny(name)
    pool = kv_cache.make_pool(cfg, 8, 16, slots=2)
    page_list = jnp.asarray([1, 2, 3, 4, 0], jnp.int32)  # 5 x 16 = S
    extra = {"slot": jnp.int32(1)} if cfg.family == "hybrid" else {}
    closed = jax.make_jaxpr(lambda pool, prompt: batcher_lib.admit_row_paged(
        params, cfg, pool, page_list, prompt, jnp.int32(prompt_len - 5),
        jax.random.key(1), **extra))(pool, tokens(prompt_len))
    return list(_walk(closed.jaxpr))


@pytest.mark.parametrize("name", PRESETS + ["k-exaone-tiny"])
def test_no_admission_holds_a_bucket_by_row_cache_intermediate(name):
    """``admit_row_paged`` at a bucket T < S: nothing in the program has T
    and S among its dimensions (a score matrix, a mask, probabilities),
    no matrix product writes S rows (latent attention's W_kvb over the row
    cache), and no key or value is repeated to the query heads over S."""
    cfg, _ = tiny(name)
    shapes = [(eqn.primitive.name, tuple(v.aval.shape))
              for eqn in admission_eqns(name) for v in eqn.outvars
              if hasattr(v.aval, "shape")]
    assert any(T in s for _, s in shapes) and any(S in s for _, s in shapes)
    both = [(p, s) for p, s in shapes if T in s and S in s]
    assert not both, both[:5]
    products = [(p, s) for p, s in shapes if p == "dot_general" and S in s]
    assert not products, products[:5]
    # keys or values repeated to every query head over the row cache
    if cfg.num_heads != cfg.num_kv_heads:
        repeated = [(p, s) for p, s in shapes
                    if S in s and cfg.num_heads in s[s.index(S):]]
        assert not repeated, repeated[:5]


@pytest.mark.parametrize("name", PRESETS + ["k-exaone-tiny"])
def test_every_attention_layer_of_an_admission_is_self_attention(
        name, monkeypatch):
    """The "start" kind fires under ``jit``, in ``_attention``,
    ``mla_attention`` and ``mixed_attention`` alike: one function scores a
    row's start, and it is handed T keys."""
    seen = []
    real = model_lib._self_attention

    def spy(q, k, v, *a, **kw):
        seen.append((q.shape[1], k.shape[1], v.shape[1]))
        return real(q, k, v, *a, **kw)

    monkeypatch.setattr(model_lib, "_self_attention", spy)
    cfg, params = tiny(name)
    prompt = tokens(T, seed=9)  # (a shape no other test of this file jits)
    jax.jit(lambda: batcher_lib._prefill_row(
        model_lib.forward, params, cfg, jnp.float32, S, prompt,
        jnp.int32(T)))()
    # Layers under one scan are traced once; a hybrid model has a scan a
    # run of like layers.
    assert seen and set(seen) == {(T, T, T)}


MASK = jnp.ones((2, 1, 4, 8), bool)
ROWS = jnp.zeros((2,), jnp.int32)  # one write slot a row
TABLES = jnp.zeros((2, 1), jnp.int32)
POS = jnp.zeros((2, 4), jnp.int32)  # (a row's offset makes no arange)


@pytest.mark.parametrize("facts, traced, kind", [
    # each kind ...
    (dict(), False, "plain"),
    (dict(cached=True, cache_index=0), False, "start"),
    (dict(cached=True, cache_index=3), False, "continuation"),
    (dict(cached=True, cache_index=3, attn_mask=MASK), False, "masked"),
    (dict(cached=True, cache_index=ROWS, attn_mask=MASK, positions=POS),
     False, "decode"),
    (dict(cached=True, cache_index=ROWS, kv_tables=TABLES, positions=POS),
     False, "decode"),
    # ... and each near miss of a start: the row cache's route is kept
    (dict(cached=True, cache_index=np.int32(0)), False, "start"),
    (dict(cached=True, cache_index=0), True, "continuation"),
    (dict(cached=True, cache_index=0, attn_mask=MASK), False, "masked"),
    (dict(cached=True, cache_index=0,
          key_positions=jnp.zeros((2, 8), jnp.int32)), False, "continuation"),
    (dict(cache_index=0), False, "plain"),  # an offset and no cache
    (dict(cache_index=ROWS, kv_tables=TABLES, positions=POS), False, "plain"),
])
def test_the_kind_of_a_call_is_decided_once(facts, traced, kind):
    """``models.model.call_of``: a traced zero (``jnp.int32(0)`` under
    ``jit``), a caller's mask or a map of the slots' positions is no start,
    and the facts come back as they went in."""
    seen = []

    def build(index):
        seen.append(model_lib.call_of(
            (2, 4), **{**facts, "cache_index": index}))
        return jnp.int32(0)

    if traced:
        jax.jit(build)(jnp.int32(facts["cache_index"]))
    else:
        build(facts.get("cache_index"))
    call, = seen
    assert call.kind == kind
    assert call.cached == facts.get("cached", False)
    assert call.positions.shape == (2, 4)
    assert call.std_layout == ("positions" not in facts and (
        facts.get("cache_index") is None or not call.cached))
    for name in ("attn_mask", "key_positions", "kv_tables"):
        assert getattr(call, name) is facts.get(name)
    assert call.rows is None and call.token_mask is None


@pytest.mark.parametrize("batch", [1, 2])
def test_what_follows_from_the_facts_of_a_call(batch):
    """The positions ``forward`` makes itself (the standard layout, but not
    behind a cache's offset), a lone row's count of real rows, and the mask
    of the real tokens."""
    lens = jnp.asarray([3, 1][:batch], jnp.int32)
    call = model_lib.call_of((batch, 4), seq_lens=lens)
    assert call.std_layout and call.kind == "plain"
    np.testing.assert_array_equal(
        np.asarray(call.positions), np.tile(np.arange(4), (batch, 1)))
    np.testing.assert_array_equal(
        np.asarray(call.token_mask),
        np.arange(4)[None] < np.asarray(lens)[:, None])
    if batch == 1:  # ONE right-padded sequence: its real rows run from the top
        np.testing.assert_array_equal(np.asarray(call.rows), [3])
    else:
        assert call.rows is None
    behind = model_lib.call_of((batch, 4), cache_index=5, cached=True)
    assert not behind.std_layout and int(behind.positions[0, 0]) == 5
    own = model_lib.call_of((batch, 4), positions=call.positions)
    assert not own.std_layout and own.positions is call.positions


def test_the_two_counters_add_up_to_the_admissions():
    """A prompt sent twice through a prefix cache: the first admission is
    a fresh row (its own bucket of keys), the second continues behind the
    cached pages (the row cache's every slot); the span says which."""
    cfg, params = tiny("llama-tiny")
    b = batcher_lib.ContinuousBatcher(
        cfg, params, batch_slots=2, max_len=96, chunk_steps=4,
        paged_pages=13, page_size=16, prefix_cache=True)
    names = ("batcher.admit.self_attention",
             "batcher.admit.row_cache_attention", "batcher.admitted")
    before = [METRICS.get_counter(n) for n in names]
    ids = [int(x) for x in tokens(40, seed=5)]
    for tail in ([1, 2], [3, 4], [5]):
        b.submit(ids + tail, max_new_tokens=2)
        b.run()
    fresh, behind, admitted = (
        METRICS.get_counter(n) - was for n, was in zip(names, before))
    assert (fresh, behind, admitted) == (1, 2, 3)
