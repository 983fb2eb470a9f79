"""The hybrid family (LFM2-MoE) against its plain reference, on the CPU with
``lfm2-tiny`` in float32: the forward without a cache, a padded prefill
that leaves the convolution state of the prompt's TRUE length, decode
through both kinds of state, the routing rule, and the layout."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.core.config import ModelConfig
from distributed_llms_tpu.models import kv_cache, layers, model as model_lib
from distributed_llms_tpu.models.presets import get_preset
from distributed_llms_tpu.models.reference import lfm2_moe
from tools.reference_check import reference_cfg

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset("lfm2-tiny")
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def reference(params, cfg, tokens):
    tree = dict(params, layers=list(model_lib.hybrid_layers(params, cfg)))
    return np.asarray(lfm2_moe.forward(tree, reference_cfg(cfg), tokens))


def tokens_of(n, seed=0):
    return np.random.RandomState(seed).randint(0, 256, (n,)).astype(np.int32)


def test_forward_without_a_cache_is_the_reference(tiny):
    cfg, params = tiny
    toks = tokens_of(33)
    logits, _ = model_lib.forward(params, cfg, jnp.asarray(toks)[None])
    np.testing.assert_allclose(
        np.asarray(logits[0]), reference(params, cfg, toks), atol=2e-5)


@pytest.mark.parametrize("n,bucket", [(1, 8), (5, 8), (9, 16), (33, 64)])
def test_padded_prefill_then_decode_is_the_reference(tiny, n, bucket):
    """The prompt goes in right-padded to its bucket with its true length;
    the state it leaves is that of token n, so the 6 decoded positions that
    follow agree with the reference's full forward."""
    cfg, params = tiny
    toks = tokens_of(n + 6, seed=n)
    ref = reference(params, cfg, toks)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = toks[:n]
    cache = kv_cache.init_cache(cfg, 1, 80)
    logits, cache, stats = model_lib.forward(
        params, cfg, jnp.asarray(padded), cache=cache,
        cache_index=jnp.int32(0), seq_lens=jnp.asarray([n], jnp.int32),
        return_aux=True)
    np.testing.assert_allclose(np.asarray(logits[0, :n]), ref[:n], atol=2e-5)
    # Real tokens only are counted: n tokens x 2 experts x 6 expert layers;
    # the counts are a by-product (forward's aux), no leaf of the cache.
    assert [int(x) for x in stats[:2]] == [n * 2 * 6, 6]
    assert set(vars(cache)) == {"k", "v", "conv", "ring_k", "ring_v",
                                "ret_s", "ret_z", "ssm_h", "ssm_conv",
                                "gdn_s", "gdn_conv"}
    assert cache.ret_s is None  # (a retention layer's state: none here)
    assert cache.ssm_h is None  # (a state-space layer's: none here)
    assert cache.ring_k is None  # (the windowed layers' rings: none here)
    for t in range(n, n + 6):
        logits, cache = model_lib.forward(
            params, cfg, jnp.asarray(toks[t: t + 1])[None],
            positions=jnp.asarray([[t]], jnp.int32), cache=cache,
            cache_index=jnp.int32(t))
        np.testing.assert_allclose(np.asarray(logits[0, 0]), ref[t], atol=2e-5)


def test_state_at_the_buckets_end_would_be_wrong(tiny):
    """What ``seq_lens`` is for: without it the padded prefill leaves the
    convolution state of the bucket's last (padding) positions."""
    cfg, params = tiny
    toks = tokens_of(5)
    padded = np.zeros((1, 8), np.int32)
    padded[0, :5] = toks
    run = lambda **kw: model_lib.forward(  # noqa: E731
        params, cfg, jnp.asarray(padded), cache=kv_cache.init_cache(cfg, 1, 16),
        cache_index=jnp.int32(0), **kw)[1].conv
    right = run(seq_lens=jnp.asarray([5], jnp.int32))
    assert float(jnp.max(jnp.abs(right - run()))) > 1e-3
    exact = model_lib.forward(
        params, cfg, jnp.asarray(toks)[None],
        cache=kv_cache.init_cache(cfg, 1, 16), cache_index=jnp.int32(0))[1]
    # (a row's start is scored over its own T keys, 8 and 5 here: the sums
    # run in another order, some float32 ulps apart)
    np.testing.assert_allclose(np.asarray(right), np.asarray(exact.conv),
                               atol=1e-5)


def test_a_row_without_a_real_token_keeps_its_state():
    p = {"in_proj": jnp.ones((4, 12)) * 0.1, "taps": jnp.ones((4, 3)),
         "out_proj": jnp.eye(4)}
    x = jnp.arange(8, dtype=jnp.float32).reshape(2, 1, 4)
    state = jnp.arange(16, dtype=jnp.float32).reshape(2, 2, 4)
    _, new = layers.short_conv(x, p, state, jnp.asarray([0, 1], jnp.int32))
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(state[0]))
    np.testing.assert_array_equal(np.asarray(new[1, 0]), np.asarray(state[1, 1]))


SIGMOID = ModelConfig(
    family="llama", num_experts=8, num_experts_per_token=2,
    moe_score_fn="sigmoid", moe_expert_bias=True)


def test_the_bias_picks_and_does_not_weigh():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0, -2.0, -3.0, -4.0, -5.0]])
    w0, i0 = layers.route_experts(logits, SIGMOID, jnp.zeros((8,)))
    assert sorted(np.asarray(i0[0])) == [0, 1]
    bias = jnp.zeros((8,)).at[4].set(1.0)  # lifts expert 4 over expert 1
    w1, i1 = layers.route_experts(logits, SIGMOID, bias)
    assert sorted(np.asarray(i1[0])) == [0, 4]
    s = jax.nn.sigmoid(logits[0])
    want = np.asarray([s[0], s[4]]) / (float(s[0] + s[4]) + 1e-6)
    got = dict(zip(np.asarray(i1[0]).tolist(), np.asarray(w1[0]).tolist()))
    np.testing.assert_allclose([got[0], got[4]], want, rtol=1e-6)


def test_weights_sum_to_one_and_the_scaling_factor_scales():
    import dataclasses

    logits = jax.random.normal(jax.random.key(1), (64, 8))
    w, _ = layers.route_experts(logits, SIGMOID, None)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)
    assert float(jnp.max(jnp.abs(w.sum(-1) - 1.0))) <= 1e-5
    scaled, _ = layers.route_experts(
        logits, dataclasses.replace(SIGMOID, moe_routed_scale=2.5), None)
    np.testing.assert_allclose(np.asarray(scaled), 2.5 * np.asarray(w),
                               rtol=1e-6)
    raw, _ = layers.route_experts(
        logits, dataclasses.replace(SIGMOID, moe_norm_topk=False), None)
    assert float(jnp.max(jnp.abs(raw.sum(-1) - 1.0))) > 1e-2


def test_softmax_routing_is_mixtrals():
    cfg = ModelConfig(family="llama", num_experts=8, num_experts_per_token=2)
    logits = jax.random.normal(jax.random.key(2), (16, 8))
    w, idx = layers.route_experts(logits, cfg)
    topv, topi = jax.lax.top_k(logits, 2)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(topi))
    np.testing.assert_allclose(np.asarray(w), np.asarray(jax.nn.softmax(topv)),
                               rtol=1e-6)


def test_the_published_pattern_is_three_runs():
    runs = model_lib.layer_runs(get_preset("lfm2-8b-a1b"))
    assert [(len(unit), reps) for unit, reps in runs] == [(1, 2), (4, 4), (3, 2)]
    cfg = get_preset("lfm2-8b-a1b")
    assert cfg.attn_layers == (2, 6, 10, 14, 18, 21)
    assert len(cfg.conv_layers) == 18 and cfg.head_dim_ == 64


def test_parameter_count_is_the_cards():
    shapes = jax.eval_shape(
        lambda k: model_lib.init_params(k, get_preset("lfm2-8b-a1b")),
        jax.random.key(0))
    n = model_lib.count_params(shapes)
    assert 8.33e9 < n < 8.35e9
    experts = shapes["blocks"]["moe"]["experts"]
    assert sum(x.size for x in jax.tree.leaves(experts)) == 7_751_073_792


def test_quantized_init_keeps_router_norms_taps_and_bias_float():
    from distributed_llms_tpu.checkpoint.quantize import QuantizedTensor

    cfg = get_preset("lfm2-tiny")
    params = model_lib.init_params_quantized(jax.random.key(0), cfg, 8)
    blocks = params["blocks"]
    is_q = lambda x: isinstance(x, QuantizedTensor)  # noqa: E731
    assert is_q(blocks["conv"]["in_proj"]) and is_q(blocks["attn"]["wo"])
    assert is_q(blocks["dense"]["w_down"])
    stack = blocks["moe"]["experts"]["w_gate_up"]
    assert is_q(stack) and stack.block_axis == -2
    assert stack.scale.shape == (6, 8, 1, 64)  # [L, E, K / block, N]
    for leaf in (blocks["moe"]["router"], blocks["moe"]["expert_bias"],
                 blocks["conv"]["taps"], blocks["attn"]["q_norm"],
                 blocks["conv"]["ln1"]["scale"]):
        assert not is_q(leaf)
    assert blocks["moe"]["router"].dtype == jnp.float32
    assert float(jnp.std(blocks["moe"]["expert_bias"])) > 0.03  # drawn


def test_the_benchmarks_reference_is_a_copy():
    with open(os.path.join(ROOT, "benchmark", "reference", "lfm2_moe.py"), "rb") as f:
        theirs = f.read()
    with open(lfm2_moe.__file__, "rb") as f:
        assert f.read() == theirs
