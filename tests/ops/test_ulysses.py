"""Ulysses (all-to-all head-scatter) sequence parallelism on the fake mesh.

Same strategy as the ring tests: exercise the real collective on 8 fake CPU
devices — identical code path to a TPU slice over ICI (SURVEY §4's missing
distributed-test layer)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_llms_tpu.core.config import MeshConfig, ModelConfig
from distributed_llms_tpu.core.mesh import mesh_from_devices
from distributed_llms_tpu.models import layers, model as model_lib
from distributed_llms_tpu.ops import ulysses


def _reference(q, k, v, positions, causal, q_per_kv):
    kf = layers.repeat_kv(k, q_per_kv)
    vf = layers.repeat_kv(v, q_per_kv)
    mask = layers.causal_mask(positions, positions) if causal else None
    return layers.dot_product_attention(q, kf, vf, mask)


def _run(mesh, q, k, v, positions, causal=True):
    sh = P(None, "seq", None, None)
    ps = P(None, "seq")
    return jax.shard_map(
        lambda q, k, v, p: ulysses.ulysses_attention(
            q, k, v, p, axis_name="seq", causal=causal
        ),
        mesh=mesh,
        in_specs=(sh, sh, sh, ps),
        out_specs=sh,
        axis_names={"seq"},
    )(q, k, v, positions)


@pytest.mark.parametrize(
    "seq_devices,heads,kv_heads,causal",
    [
        (4, 8, 4, True),
        (4, 8, 4, False),
        (2, 4, 2, True),
        (8, 8, 8, True),
    ],
)
def test_ulysses_matches_full_attention(seq_devices, heads, kv_heads, causal):
    mesh = mesh_from_devices({"seq": seq_devices}, jax.devices()[:seq_devices])
    b, t, d = 2, 32, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, t, heads, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, kv_heads, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, kv_heads, d)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    out = _run(mesh, q, k, v, positions, causal)
    want = _reference(q, k, v, positions, causal, heads // kv_heads)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_ulysses_rejects_indivisible_heads():
    mesh = mesh_from_devices({"seq": 4}, jax.devices()[:4])
    b, t, d = 1, 8, 4
    q = jnp.ones((b, t, 8, d), jnp.float32)
    k = jnp.ones((b, t, 2, d), jnp.float32)  # kvh=2 not divisible by seq=4
    v = jnp.ones((b, t, 2, d), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    with pytest.raises(ValueError, match="ring"):
        _run(mesh, q, k, v, positions)


def test_ulysses_grad():
    mesh = mesh_from_devices({"seq": 4}, jax.devices()[:4])
    b, t, h, d = 1, 16, 4, 8
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    def loss(q, k, v):
        return jnp.sum(_run(mesh, q, k, v, positions) ** 2)

    g = jax.jit(jax.grad(loss))(q, k, v)

    def loss_ref(q, k, v):
        return jnp.sum(_reference(q, k, v, positions, True, 1) ** 2)

    g_ref = jax.grad(loss_ref)(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-4, rtol=1e-4)


def test_parallel_model_ulysses_forward_matches_single_device():
    from distributed_llms_tpu.parallel.api import make_parallel_model

    cfg = ModelConfig(
        family="llama", vocab_size=128, hidden_size=32, intermediate_size=64,
        num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=64,
        dtype="float32", attn_impl="ulysses",
    )
    params = model_lib.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 128, dtype=jnp.int32)

    ref_cfg = dataclasses.replace(cfg, attn_impl="dot")
    ref, _ = model_lib.forward(params, ref_cfg, tokens)

    pm = make_parallel_model(cfg, MeshConfig(data=2, seq=4), devices=jax.devices())
    sp = pm.shard_params(params)
    out, _ = pm.forward(sp, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)
