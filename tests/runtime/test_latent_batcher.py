"""Latent (MLA) pages in the ContinuousBatcher: admission into the latent
pool, decode in the absorbed form, the prefix cache on latent pages, what
the format refuses, and its counters.  ``ax-k1-tiny`` in float32 on the
CPU, against the plain reference (models/reference/axk1.py)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.core.config import RuntimeConfig
from distributed_llms_tpu.core.observability import METRICS
from distributed_llms_tpu.models import kv_cache, model as model_lib
from distributed_llms_tpu.models.presets import get_preset
from distributed_llms_tpu.models.reference import axk1
from distributed_llms_tpu.ops import decode_attn
from distributed_llms_tpu.runtime.batcher import ContinuousBatcher
from distributed_llms_tpu.runtime.engine import InferenceEngine
from tools.reference_check import reference_cfg


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset("ax-k1-tiny")
    return cfg, model_lib.init_params(jax.random.key(0), cfg)


def batcher(cfg, params, **kw):
    kw = {"batch_slots": 4, "max_len": 64, "chunk_steps": 4,
          "paged_pages": 24, "page_size": 8, **kw}
    return ContinuousBatcher(cfg, params, **kw)


def scored_keys(n, blk, w, dtype, p):
    """The latent kernel's blocks by hand: a row of ``n`` tokens holds
    ceil(n / blk) pages (one at least), walked a run at a time, and the
    products cover each run's live pages in whole blocks."""
    run = decode_attn._latent_run_pages(blk, w, dtype, p)
    block = decode_attn._latent_block_pages(run)
    pages = min(max(-(-n // blk), 1), p)
    return sum(-(-min(run, pages - first) // block) * block * blk
               for first in range(0, pages, run))


def prompt(n, seed):
    return [int(x) for x in np.random.RandomState(seed).randint(0, 256, n)]


def held_to_reference(params, cfg, ids, toks, lps, atol=2e-5):
    """The served tokens are the reference's greedy ones and each chosen
    token's logprob is the reference's: logits compared where they decide
    (float32 on both sides: the order of summation is what differs)."""
    tree = dict(params, layers=list(model_lib.hybrid_layers(params, cfg)))
    ref = axk1.forward(tree, reference_cfg(cfg),
                       jnp.asarray(ids + toks[:-1]))[len(ids) - 1:]
    assert toks == [int(jnp.argmax(r)) for r in ref]
    want = [float(jax.nn.log_softmax(r)[t]) for r, t in zip(ref, toks)]
    np.testing.assert_allclose(lps, want, atol=atol)


def test_two_rows_of_unlike_length(tiny):
    """(b) Rows of 5 and 33 tokens decode side by side, at unlike depths of
    the latent pool, each as the reference has it."""
    cfg, params = tiny
    b = batcher(cfg, params)
    jobs = [(prompt(5, 1), 9), (prompt(33, 2), 6)]
    rids = [b.submit(ids, max_new_tokens=m) for ids, m in jobs]
    out = b.run()
    for rid, (ids, _) in zip(rids, jobs):
        held_to_reference(params, cfg, ids, out[rid], b.result_logprobs[rid])
    assert isinstance(b.cache, kv_cache.LatentCache)
    assert b.cache.k.shape == (4, 24, 8, 128)


def test_a_suffix_behind_a_cached_run_is_the_whole_prompt(tiny):
    """(c) The prefix cache on latent pages: a prompt sent again with a new
    suffix is served its first three pages from the cache, the suffix is
    admitted behind the cached run (which is expanded), and tokens and
    logprobs are those of the same prompt sent with the cache off, and the
    reference's."""
    cfg, params = tiny
    b = batcher(cfg, params, prefix_cache=True)
    shared = prompt(26, 7)
    first, second = shared + prompt(5, 8), shared + prompt(7, 9)
    r1 = b.submit(first, max_new_tokens=5)
    b.run()
    before = b.prefix_cache.hit_tokens
    r2 = b.submit(second, max_new_tokens=6)
    out = b.run()
    assert b.prefix_cache.hit_tokens - before == 24  # three pages of 8
    cold = batcher(cfg, params)
    rc = cold.submit(second, max_new_tokens=6)
    assert cold.run()[rc] == out[r2]
    np.testing.assert_allclose(b.result_logprobs[r2],
                               cold.result_logprobs[rc], atol=2e-5)
    held_to_reference(params, cfg, second, out[r2], b.result_logprobs[r2])
    del r1


def test_counters_of_a_chips_share(tiny):
    """Held pairs beside routed pairs, touched and load over the held
    experts, the tokens the decode kernel read, and the page's bytes."""
    cfg, params = tiny
    blocks = dict(params["blocks"])
    blocks["moe"] = dict(blocks["moe"], experts=jax.tree.map(
        lambda a: a[:, 4:8], blocks["moe"]["experts"]))
    cfg = dataclasses.replace(cfg, experts_held=4, experts_offset=4)
    before = METRICS.snapshot()["counters"]
    b = batcher(cfg, dict(params, blocks=blocks))
    b.submit(prompt(5, 1), max_new_tokens=4)
    b.submit(prompt(9, 2), max_new_tokens=7)
    b.run()
    after = METRICS.snapshot()
    d = {k: after["counters"].get(k, 0) - before.get(k, 0)
         for k in ("moe.routed_pairs", "moe.held_pairs", "moe.layer_passes",
                   "moe.experts_touched", "moe.max_load_tokens",
                   "mla.decode.resident_tokens", "mla.decode.scored_keys")}
    real = (5 + 3) + (9 + 6)  # prompt tokens + decoded tokens fed back
    assert d["moe.routed_pairs"] == real * 4 * 3
    assert 0 < d["moe.held_pairs"] < d["moe.routed_pairs"]
    assert d["moe.layer_passes"] == (2 + 6) * 3
    assert 0 < d["moe.experts_touched"] <= 4 * d["moe.layer_passes"]
    assert d["moe.max_load_tokens"] <= d["moe.held_pairs"]
    # Decode steps read rows of 6, 7, 8 and of 10 .. 15 tokens.
    rows = [6, 7, 8, *range(10, 16)]
    assert d["mla.decode.resident_tokens"] == sum(rows)
    # ... and the kernel's products covered their pages in whole blocks.
    assert d["mla.decode.scored_keys"] == sum(
        scored_keys(n, 8, cfg.latent_width, jnp.float32, b.pages_per_row)
        for n in rows)
    assert d["mla.decode.scored_keys"] >= d["mla.decode.resident_tokens"]
    assert after["gauges"]["batcher.latent_page_bytes"] == \
        kv_cache.page_bytes(cfg, 8) == 4 * 8 * 128 * 4


@pytest.mark.parametrize("blk,w,dtype,p", [
    (64, 640, jnp.bfloat16, 64),  # the cell's: runs of 12 pages
    (64, 640, jnp.bfloat16, 19),  # page slots no run divides
    (8, 128, jnp.float32, 8),  # the tiny model's: a row is one run
    (8, 256, jnp.float32, 3),  # fewer slots than a block
])
def test_scored_keys_are_the_kernels_blocks(blk, w, dtype, p):
    """``decode_attn.mla_scored_keys``, which the decode program counts
    ``mla.decode.scored_keys`` with, is the walk's arithmetic at every
    depth that walk treats apart, and never under the tokens held."""
    run = decode_attn._latent_run_pages(blk, w, dtype, p)
    block = decode_attn._latent_block_pages(run) * blk
    lengths = sorted({0, 1, block - 1, block, block + 1, run * blk,
                      run * blk + 1, p * blk - 1, p * blk})
    got = [int(x) for x in decode_attn.mla_scored_keys(
        jnp.asarray(lengths, jnp.int32), blk, w, dtype, p)]
    assert got == [scored_keys(n, blk, w, dtype, p) for n in lengths]
    assert all(g >= min(n, p * blk) and g % block == 0
               for g, n in zip(got, lengths))


REFUSED = {
    "kv_bits": dict(kv_bits=8),
    "host_pages": dict(host_pages=8),
    "prefill_chunk": dict(prefill_chunk=8),
    "token_budget": dict(token_budget=16),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_the_latent_format_refuses_by_name(tiny, name):
    cfg, params = tiny
    with pytest.raises(ValueError, match=f"{name} is not supported for "
                                         "latent"):
        batcher(cfg, params, **REFUSED[name])


def test_the_latent_format_refuses_the_rest_by_name(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="page pool only"):
        batcher(cfg, params, paged_pages=None)
    for name in ("speculative", "mesh", "sessions", "padded_generate",
                 "kv_import", "kv_export", "named_prefix"):
        with pytest.raises(ValueError, match=f"{name} is not supported "
                                             "for latent"):
            kv_cache.refuse_unpaged_state(cfg, **{name: True})
    b = batcher(cfg, params)
    with pytest.raises(ValueError, match="named_prefix"):
        b.register_prefix("p", prompt(8, 0))
    with pytest.raises(ValueError, match="kv_export"):
        b.export_prefix_pages(prompt(8, 0))
    with pytest.raises(ValueError, match="kv_export"):
        b.submit_kv_export(prompt(8, 0), lambda payload: None)
    with pytest.raises(ValueError, match="kv_import"):
        b.submit_kv_import([], None, None, lambda ok, why: None)
    cfg = dataclasses.replace(cfg, vocab_size=512)  # the byte tokenizer's
    params = model_lib.init_params(jax.random.key(0), cfg)
    eng = InferenceEngine(cfg, RuntimeConfig(), params)
    with pytest.raises(ValueError, match="sessions is not supported"):
        eng.start_session(["hello"])
    with pytest.raises(ValueError, match="padded_generate is not supported"):
        eng.generate_text(["hello", "hi there"])
    with pytest.raises(ValueError, match="speculative is not supported"):
        InferenceEngine(cfg, RuntimeConfig(spec_decode=True), params)
    served = eng.continuous_batcher(batch_slots=2, max_len=64, paged_pages=12,
                                    page_size=8, prefix_cache=True)
    rid = served.submit("hello", max_new_tokens=3)
    assert len(served.run()[rid]) == 3
    # The prefix cache is NOT refused: a row's whole state is its pages.
    kv_cache.refuse_unpaged_state(cfg, prefix_cache=True, paged_pages=8)
