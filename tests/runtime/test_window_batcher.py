"""Windowed and full attention layers mixed in the ContinuousBatcher: pages
for the full layers only, a ring of window tokens a batch slot for the
rest, what the rings refuse, and their counters.  ``k-exaone-tiny`` (window
8) in float32 on the CPU, against the plain reference
(models/reference/exaone_moe.py)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.core.config import RuntimeConfig
from distributed_llms_tpu.core.observability import METRICS
from distributed_llms_tpu.models import kv_cache, model as model_lib
from distributed_llms_tpu.models.presets import get_preset
from distributed_llms_tpu.models.reference import exaone_moe
from distributed_llms_tpu.runtime.batcher import ContinuousBatcher
from distributed_llms_tpu.runtime.engine import InferenceEngine
from tools.reference_check import reference_cfg


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset("k-exaone-tiny")
    return cfg, model_lib.init_params(jax.random.key(0), cfg)


def batcher(cfg, params, **kw):
    kw = {"batch_slots": 4, "max_len": 64, "chunk_steps": 4,
          "paged_pages": 24, "page_size": 8, **kw}
    return ContinuousBatcher(cfg, params, **kw)


def prompt(n, seed):
    return [int(x) for x in np.random.RandomState(seed).randint(0, 256, n)]


def held_to_reference(params, cfg, ids, toks, lps, atol=2e-5):
    """The served tokens are the reference's greedy ones and each chosen
    token's logprob is the reference's: logits compared where they decide
    (float32 on both sides: the order of summation is what differs)."""
    tree = dict(params, layers=list(model_lib.hybrid_layers(params, cfg)))
    ref = exaone_moe.forward(tree, reference_cfg(cfg),
                             jnp.asarray(ids + toks[:-1]))[len(ids) - 1:]
    assert toks == [int(jnp.argmax(r)) for r in ref]
    want = [float(jax.nn.log_softmax(r)[t]) for r, t in zip(ref, toks)]
    np.testing.assert_allclose(lps, want, atol=atol)


def test_rows_of_unlike_length_and_a_slot_a_longer_row_has_left(tiny):
    """(b) A row shorter than the window and one of five windows decode
    side by side in two slots, each as the reference has it; then a short
    row is admitted into the slot the long one has just left, whose rings
    hold the long row's leftovers: they are not read."""
    cfg, params = tiny
    b = batcher(cfg, params, batch_slots=2)
    jobs = [(prompt(43, 2), 5), (prompt(5, 1), 20), (prompt(3, 3), 12)]
    rids = [b.submit(ids, max_new_tokens=m) for ids, m in jobs]
    out = b.run()
    for rid, (ids, _) in zip(rids, jobs):
        held_to_reference(params, cfg, ids, out[rid], b.result_logprobs[rid])
    assert isinstance(b.cache, kv_cache.HybridCache)
    assert b.cache.k.shape == (2, 24, 8, 2, 16)   # the 2 full layers' pages
    assert b.cache.ring_k.shape == (6, 2, 8, 2, 16)  # 6 windowed, 2 slots


def test_rows_do_not_depend_on_their_batch_mates(tiny):
    cfg, params = tiny
    jobs = [(prompt(n, 10 + n), m)
            for n, m in ((5, 6), (9, 11), (33, 5), (1, 9), (17, 3))]
    b = batcher(cfg, params, batch_slots=3)
    rids = [b.submit(ids, max_new_tokens=m) for ids, m in jobs]
    out = b.run()
    for rid, (ids, m) in zip(rids, jobs):
        solo = batcher(cfg, params)
        srid = solo.submit(ids, max_new_tokens=m)
        assert solo.run()[srid] == out[rid]
        np.testing.assert_allclose(
            b.result_logprobs[rid], solo.result_logprobs[srid], atol=1e-6)


def test_counters_and_gauges_of_pages_and_rings(tiny):
    """The tokens the two decode kernels read (the window's never more
    than 8 a row a step), held pairs beside routed pairs, the rings' bytes
    and the pool's bytes a token."""
    cfg, params = tiny
    blocks = dict(params["blocks"])
    blocks["moe"] = dict(blocks["moe"], experts=jax.tree.map(
        lambda a: a[:, 4:8], blocks["moe"]["experts"]))
    cfg = dataclasses.replace(cfg, experts_held=4, experts_offset=4)
    before = METRICS.snapshot()["counters"]
    b = batcher(cfg, dict(params, blocks=blocks))
    b.submit(prompt(5, 1), max_new_tokens=4)
    b.submit(prompt(19, 2), max_new_tokens=7)
    b.run()
    snap = METRICS.snapshot()
    delta = {k: snap["counters"].get(k, 0) - before.get(k, 0)
             for k in ("moe.routed_pairs", "moe.held_pairs",
                       "moe.layer_passes", "moe.experts_touched",
                       "attn.decode.resident_tokens",
                       "swa.decode.window_tokens",
                       "mla.decode.resident_tokens")}
    real = (5 + 3) + (19 + 6)  # prompt tokens + decoded tokens fed back
    assert delta["moe.routed_pairs"] == real * 4 * 7
    assert 0 < delta["moe.held_pairs"] < delta["moe.routed_pairs"]
    assert delta["moe.experts_touched"] <= 4 * delta["moe.layer_passes"]
    # Decode steps read lengths 6, 7, 8 and 20 .. 25.
    assert delta["attn.decode.resident_tokens"] == 6 + 7 + 8 + sum(
        range(20, 26))
    assert delta["swa.decode.window_tokens"] == 6 + 7 + 8 + 6 * 8
    assert delta["mla.decode.resident_tokens"] == 0
    assert snap["gauges"]["batcher.window_state_bytes"] == \
        2 * 6 * 4 * 8 * 2 * 16 * 4
    assert snap["gauges"]["batcher.pool_token_bytes"] == 2 * 2 * 2 * 16 * 4
    assert kv_cache.page_bytes(cfg, 8) == 8 * 2 * 2 * 2 * 16 * 4
    assert b.capacity_tokens() == 23 * 8


REFUSED = {
    "prefix_cache": dict(prefix_cache=True),
    "kv_bits": dict(kv_bits=8),
    "host_pages": dict(host_pages=4),
    "prefill_chunk": dict(prefill_chunk=16),
    "token_budget": dict(token_budget=32),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_what_cannot_carry_the_rings_refuses_at_start_up(tiny, name):
    """(h)"""
    cfg, params = tiny
    with pytest.raises(ValueError, match=f"{name} is not supported.*rings"):
        batcher(cfg, params, **REFUSED[name])


def test_speculative_and_unpaged_and_mesh_refuse(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="speculative is not supported"):
        batcher(cfg, params, draft_params=params, draft_cfg=cfg)
    with pytest.raises(ValueError, match="pass paged_pages"):
        batcher(cfg, params, paged_pages=None)
    with pytest.raises(ValueError, match="mesh is not supported.*rings"):
        kv_cache.refuse_unpaged_state(cfg, mesh=True)


@pytest.mark.parametrize("call,name", [
    (lambda b: b.register_prefix("sys", [1, 2, 3]), "named_prefix"),
    (lambda b: b.submit_kv_import([], None, None, None), "kv_import"),
    (lambda b: b.submit_kv_export([1, 2], None), "kv_export"),
    (lambda b: b.export_prefix_pages([1, 2]), "kv_export"),
])
def test_moving_pages_refuses_by_name(tiny, call, name):
    cfg, params = tiny
    with pytest.raises(ValueError, match=f"{name} is not supported.*rings"):
        call(batcher(cfg, params))


def test_the_engine_refuses_sessions_padded_generate_and_spec_decode(tiny):
    cfg, _ = tiny
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
    rid = b.submit("hello there, hello", max_new_tokens=3)
    assert len(b.run()[rid]) == 3


def test_a_preempted_row_is_admitted_again_from_its_tokens(tiny):
    """A pool too small for both rows' growth preempts one; it is admitted
    again from its tokens (pages AND rings rebuilt) and ends on the stream
    it would have had alone."""
    cfg, params = tiny
    jobs = [(prompt(20, 31), 30), (prompt(22, 32), 30)]
    b = batcher(cfg, params, batch_slots=2, paged_pages=11)
    rids = [b.submit(ids, max_new_tokens=m) for ids, m in jobs]
    out = b.run()
    assert b.preemptions > 0
    for rid, (ids, m) in zip(rids, jobs):
        solo = batcher(cfg, params)
        srid = solo.submit(ids, max_new_tokens=m)
        assert solo.run()[srid] == out[rid]
