"""Mixture-of-experts layer + expert parallelism (net-new vs the reference:
SURVEY §2.3 lists MoE as absent)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.core.config import MeshConfig, ModelConfig
from distributed_llms_tpu.models import layers, model as model_lib
from distributed_llms_tpu.models.presets import get_preset


def _moe_params(rng, d, e, f, dtype=jnp.float32):
    ks = jax.random.split(rng, 4)
    return {
        "router": jax.random.normal(ks[0], (d, e), dtype) * d**-0.5,
        "w_gate": jax.random.normal(ks[1], (e, d, f), dtype) * d**-0.5,
        "w_up": jax.random.normal(ks[2], (e, d, f), dtype) * d**-0.5,
        "w_down": jax.random.normal(ks[3], (e, f, d), dtype) * f**-0.5,
    }


def _reference_moe(x, p, k):
    """Per-token explicit top-k expert mix — no capacity, no dispatch
    tensors.  Ground truth when nothing overflows."""
    b, t, d = x.shape
    xf = np.asarray(x, np.float32).reshape(-1, d)
    logits = xf @ np.asarray(p["router"], np.float32)
    out = np.zeros_like(xf)
    for s in range(xf.shape[0]):
        idx = np.argsort(-logits[s])[:k]
        g = np.exp(logits[s][idx] - logits[s][idx].max())
        g = g / g.sum()
        for w, ei in zip(g, idx):
            gate = xf[s] @ np.asarray(p["w_gate"])[ei]
            up = xf[s] @ np.asarray(p["w_up"])[ei]
            h = (gate / (1 + np.exp(-gate))) * up  # silu(gate) * up
            out[s] += w * (h @ np.asarray(p["w_down"])[ei])
    return out.reshape(b, t, d)


def test_moe_matches_per_token_reference_when_lossless():
    cfg = ModelConfig(
        family="llama", num_experts=4, num_experts_per_token=2,
        moe_capacity_factor=4.0,  # capacity >= all tokens: nothing dropped
    )
    d, e, f = 16, 4, 32
    p = _moe_params(jax.random.key(0), d, e, f)
    x = jax.random.normal(jax.random.key(1), (2, 5, d), jnp.float32)
    out, aux = layers.moe_swiglu(x, p, cfg)
    ref = _reference_moe(x, p, 2)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4, rtol=1e-4)
    # balanced-ish random routing with nothing dropped: aux near 1
    assert 0.5 < float(aux) < 2.0


def test_moe_capacity_drops_tokens_to_zero():
    # capacity factor so small every expert holds 1 slot; dropped tokens
    # contribute exactly zero (GShard semantics), output stays finite.
    cfg = ModelConfig(
        family="llama", num_experts=2, num_experts_per_token=1,
        moe_capacity_factor=0.01,
    )
    d, e, f = 8, 2, 16
    p = _moe_params(jax.random.key(0), d, e, f)
    x = jax.random.normal(jax.random.key(1), (1, 16, d), jnp.float32)
    out, _ = layers.moe_swiglu(x, p, cfg)
    assert bool(jnp.all(jnp.isfinite(out)))
    zero_rows = int(jnp.sum(jnp.all(out[0] == 0.0, axis=-1)))
    assert zero_rows >= 14  # 16 tokens, 2 experts x 1 slot

def test_dropless_layer_matches_per_token_reference():
    """``moe_capacity=False`` on the llama family's leaves: every token
    gets its k experts, at a capacity factor that would drop nearly all."""
    cfg = ModelConfig(
        family="llama", num_experts=4, num_experts_per_token=2,
        moe_capacity_factor=0.01, moe_capacity=False,
    )
    d, e, f = 16, 4, 32
    p = _moe_params(jax.random.key(0), d, e, f)
    x = jax.random.normal(jax.random.key(1), (2, 5, d), jnp.float32)
    out = layers.moe_dropless_layer(x, p, cfg)
    np.testing.assert_allclose(
        np.asarray(out), _reference_moe(x, p, 2), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("capacity", [False, True])
def test_moe_capacity_selects_the_expert_layer_of_a_model(capacity):
    """The field selects, in the model: without the capacity rule a row's
    logits are those of its solo forward whatever rows share the batch;
    with it, at a capacity that holds one token an expert, they are not."""
    cfg = dataclasses.replace(
        get_preset("moe-tiny"), dtype="float32", moe_capacity=capacity,
        moe_capacity_factor=0.01)
    params = model_lib.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(
        jax.random.key(1), (3, 9), 0, cfg.vocab_size, dtype=jnp.int32)
    batch, _ = model_lib.forward(params, cfg, toks)
    solo, _ = model_lib.forward(params, cfg, toks[1:2])
    same = np.allclose(np.asarray(batch[1]), np.asarray(solo[0]), atol=1e-4)
    assert same == (not capacity)


def test_a_served_model_never_drops():
    """The batcher takes a model's capacity rule away: moe-tiny at a
    capacity of one token an expert gives each request its solo stream
    whatever rows share the batch."""
    from distributed_llms_tpu.runtime.batcher import ContinuousBatcher

    cfg = dataclasses.replace(
        get_preset("moe-tiny"), dtype="float32", moe_capacity_factor=0.01)
    params = model_lib.init_params(jax.random.key(0), cfg)
    rs = np.random.RandomState(0)
    jobs = [[int(x) for x in rs.randint(0, 256, n)] for n in (5, 9, 12)]

    def serve(prompts):
        b = ContinuousBatcher(cfg, params, batch_slots=4, max_len=64,
                              chunk_steps=4, paged_pages=24, page_size=8)
        assert not b.cfg.moe_capacity
        rids = [b.submit(ids, max_new_tokens=6) for ids in prompts]
        out = b.run()
        return [out[r] for r in rids]

    together = serve(jobs)
    assert together == [serve([ids])[0] for ids in jobs]


def test_the_hybrid_family_has_no_capacity_path():
    with pytest.raises(ValueError, match="moe_capacity=False"):
        dataclasses.replace(get_preset("lfm2-tiny"), moe_capacity=True)


def test_moe_model_forward_and_grad():
    cfg = get_preset("moe-tiny")
    params = model_lib.init_params(jax.random.key(0), cfg)
    assert "router" in params["blocks"]["mlp"]
    toks = jax.random.randint(jax.random.key(1), (2, 9), 0, cfg.vocab_size, dtype=jnp.int32)
    logits, _ = model_lib.forward(params, cfg, toks)
    assert logits.shape == (2, 9, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))

    def loss(p):
        lg, _, aux = model_lib.forward(p, cfg, toks, return_aux=True)
        return jnp.mean(lg**2) + cfg.moe_aux_loss_weight * aux

    g = jax.grad(loss)(params)
    for leaf in jax.tree.leaves(g):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    # aux must reach the router weights (load-balance gradient signal)
    assert float(jnp.max(jnp.abs(g["blocks"]["mlp"]["router"]))) > 0


def test_moe_trainer_includes_aux_loss():
    from distributed_llms_tpu.runtime import train

    cfg = get_preset("moe-tiny")
    params = model_lib.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 9), 0, cfg.vocab_size, dtype=jnp.int32)
    loss_with = train.lm_loss(params, cfg, toks)
    loss_no_aux = train.lm_loss(
        params, dataclasses.replace(cfg, moe_aux_loss_weight=0.0), toks
    )
    assert float(loss_with) != float(loss_no_aux)


def test_moe_rejects_gpt2():
    cfg = ModelConfig(family="gpt2", num_experts=4)
    with pytest.raises(ValueError, match="llama"):
        model_lib.init_params(jax.random.key(0), cfg)


def test_moe_expert_parallel_matches_single_device():
    from distributed_llms_tpu.parallel.api import make_parallel_model

    cfg = get_preset("moe-tiny")
    params = model_lib.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab_size, dtype=jnp.int32)
    ref, _ = model_lib.forward(params, cfg, toks)

    pm = make_parallel_model(cfg, MeshConfig(data=2, expert=4), devices=jax.devices())
    sp = pm.shard_params(params)
    # expert-stacked weights really live sharded over the expert axis
    spec = sp["blocks"]["mlp"]["w_gate"].sharding.spec
    assert "expert" in str(spec)
    out, _ = pm.forward(sp, toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)


def test_moe_generate_decodes():
    from distributed_llms_tpu.runtime import generate as gen_lib

    cfg = get_preset("moe-tiny")
    params = model_lib.init_params(jax.random.key(0), cfg)
    prompt = jax.random.randint(jax.random.key(1), (2, 7), 0, cfg.vocab_size, dtype=jnp.int32)
    lens = jnp.array([4, 7], dtype=jnp.int32)
    out = gen_lib.generate_tokens(
        params, cfg, prompt, lens, jax.random.key(2), max_new_tokens=5
    )
    assert out.shape == (2, 5)
    assert bool(jnp.all(out >= 0)) and bool(jnp.all(out < cfg.vocab_size))
