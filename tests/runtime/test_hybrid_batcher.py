"""A model with two kinds of state in the ContinuousBatcher: keys and values
of its attention layers in pages, each convolution layer's last gated inputs
in a batch slot.  ``lfm2-tiny`` in float32 on the CPU, against the plain
reference (models/reference/lfm2_moe.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.core.observability import METRICS
from distributed_llms_tpu.models import kv_cache, model as model_lib
from distributed_llms_tpu.models.presets import get_preset
from distributed_llms_tpu.models.reference import lfm2_moe
from distributed_llms_tpu.runtime.batcher import ContinuousBatcher
from tools.reference_check import reference_cfg


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset("lfm2-tiny")
    return cfg, model_lib.init_params(jax.random.key(0), cfg)


def batcher(cfg, params, **kw):
    kw = {"batch_slots": 4, "max_len": 64, "chunk_steps": 4,
          "paged_pages": 24, "page_size": 8, **kw}
    return ContinuousBatcher(cfg, params, **kw)


def prompt(n, seed):
    return [int(x) for x in np.random.RandomState(seed).randint(0, 256, n)]


def reference_logits(params, cfg, tokens):
    tree = dict(params, layers=list(model_lib.hybrid_layers(params, cfg)))
    return lfm2_moe.forward(tree, reference_cfg(cfg), jnp.asarray(tokens))


def held_to_reference(params, cfg, ids, toks, lps):
    """The served tokens are the reference's greedy ones and each chosen
    token's logprob is the reference's: logits compared where they decide."""
    ref = reference_logits(params, cfg, ids + toks[:-1])[len(ids) - 1:]
    assert toks == [int(jnp.argmax(r)) for r in ref]
    want = [float(jax.nn.log_softmax(r)[t]) for r, t in zip(ref, toks)]
    np.testing.assert_allclose(lps, want, atol=2e-5)


@pytest.mark.parametrize("n", [1, 5, 9, 33])
def test_admission_at_an_unpadded_length_then_decode(tiny, n):
    """Lengths that are no bucket (buckets are 8, 16, 64) and length 1: the
    admission hands the pages and the state of the TRUE length to the slot,
    and 10 decode steps go on from both."""
    cfg, params = tiny
    b = batcher(cfg, params)
    ids = prompt(n, n)
    rid = b.submit(ids, max_new_tokens=11)
    out = b.run()
    held_to_reference(params, cfg, ids, out[rid], b.result_logprobs[rid])


def test_rows_do_not_depend_on_their_batch_mates(tiny):
    """No drops, and finished and free rows are harmless: six requests of
    unlike lengths and budgets through four slots (so slots are reused and
    rows finish mid-chunk) give each request its solo stream, logprobs
    included, and the reference's."""
    cfg, params = tiny
    jobs = [(prompt(n, 10 + n), m)
            for n, m in ((5, 6), (9, 11), (33, 5), (1, 9), (17, 3), (12, 7))]
    b = batcher(cfg, params)
    rids = [b.submit(ids, max_new_tokens=m) for ids, m in jobs]
    out = b.run()
    for rid, (ids, m) in zip(rids, jobs):
        solo = batcher(cfg, params)
        srid = solo.submit(ids, max_new_tokens=m)
        assert solo.run()[srid] == out[rid]
        np.testing.assert_allclose(
            b.result_logprobs[rid], solo.result_logprobs[srid], atol=1e-6)
        held_to_reference(params, cfg, ids, out[rid], b.result_logprobs[rid])


def test_expert_counters_count_real_tokens_only(tiny):
    cfg, params = tiny
    before = METRICS.snapshot()["counters"]
    b = batcher(cfg, params)
    b.submit(prompt(5, 1), max_new_tokens=4)
    b.submit(prompt(9, 2), max_new_tokens=7)
    b.run()
    after = METRICS.snapshot()["counters"]
    delta = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("moe.routed_pairs", "moe.layer_passes",
                       "moe.experts_touched", "moe.max_load_tokens")}
    real = (5 + 3) + (9 + 6)  # prompt tokens + decoded tokens fed back
    assert delta["moe.routed_pairs"] == real * 2 * 6
    # 2 admissions and 6 decode steps with a live row, 6 expert layers each;
    # the two steps of the second chunk in which nothing decodes count none.
    assert delta["moe.layer_passes"] == (2 + 6) * 6
    assert 0 < delta["moe.experts_touched"] <= 8 * delta["moe.layer_passes"]
    assert delta["moe.max_load_tokens"] * 8 >= delta["moe.routed_pairs"]


def test_pool_counts_attention_layers_and_the_state_has_a_gauge(tiny):
    cfg, params = tiny
    b = batcher(cfg, params)
    assert b.cache.k.shape == (2, 24, 8, 2, 16)  # 2 attention layers of 8
    assert b.cache.conv.shape == (6, 4, 2, 64)   # 6 conv layers, 4 slots
    assert kv_cache.page_bytes(cfg, 8) == 2 * 2 * 8 * 2 * 16 * 4
    assert METRICS.snapshot()["gauges"]["batcher.conv_state_bytes"] == \
        6 * 4 * 2 * 64 * 4
    assert b.capacity_tokens() == 23 * 8


def test_narrow_heads_lie_folded_in_the_pool():
    cfg = get_preset("lfm2-8b-a1b")
    pool = jax.eval_shape(
        lambda: kv_cache.make_pool(cfg, 512, 64, slots=16))
    assert pool.k.shape == (6, 512, 64, 4, 128)  # 8 heads of 64, two a row
    assert pool.conv.shape == (18, 16, 2, 2048)


REFUSED = {
    "prefix_cache": dict(prefix_cache=True),
    "kv_bits": dict(kv_bits=8),
    "host_pages": dict(host_pages=4),
    "prefill_chunk": dict(prefill_chunk=16),
    "token_budget": dict(token_budget=32),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_what_cannot_carry_the_state_refuses_at_start_up(tiny, name):
    cfg, params = tiny
    with pytest.raises(ValueError, match=f"{name} is not supported.*convolution"):
        batcher(cfg, params, **REFUSED[name])


def test_speculative_and_unpaged_and_mesh_refuse(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="speculative is not supported"):
        batcher(cfg, params, draft_params=params, draft_cfg=cfg)
    with pytest.raises(ValueError, match="pass paged_pages"):
        batcher(cfg, params, paged_pages=None)
    with pytest.raises(ValueError, match="mesh is not supported"):
        kv_cache.refuse_unpaged_state(cfg, mesh=True)
    # ... and every other family is let through whatever is asked.
    kv_cache.refuse_unpaged_state(
        get_preset("llama-tiny"), mesh=True, prefix_cache=True, kv_bits=8)


@pytest.mark.parametrize("call,name", [
    (lambda b: b.register_prefix("sys", [1, 2, 3]), "named_prefix"),
    (lambda b: b.submit_kv_import([], None, None, None), "kv_import"),
    (lambda b: b.submit_kv_export([1, 2], None), "kv_export"),
    (lambda b: b.export_prefix_pages([1, 2]), "kv_export"),
])
def test_moving_pages_refuses_by_name(tiny, call, name):
    cfg, params = tiny
    with pytest.raises(ValueError, match=f"{name} is not supported"):
        call(batcher(cfg, params))


def test_the_engine_refuses_sessions_padded_generate_and_spec_decode(tiny):
    from distributed_llms_tpu.core.config import RuntimeConfig
    from distributed_llms_tpu.runtime.engine import InferenceEngine

    cfg, params = tiny
    import dataclasses

    cfg = dataclasses.replace(cfg, vocab_size=512)
    params = model_lib.init_params(jax.random.key(0), cfg)
    eng = InferenceEngine(cfg, RuntimeConfig(), params)
    with pytest.raises(ValueError, match="sessions is not supported"):
        eng.start_session(["hello"])
    with pytest.raises(ValueError, match="padded_generate is not supported"):
        eng.generate_text(["hello", "hi there"])
    with pytest.raises(ValueError, match="speculative is not supported"):
        InferenceEngine(cfg, RuntimeConfig(spec_decode=True), params)
    b = eng.continuous_batcher(batch_slots=2, max_len=64, paged_pages=12,
                               page_size=8)
    rid = b.submit("hello", max_new_tokens=3)
    assert len(b.run()[rid]) == 3
