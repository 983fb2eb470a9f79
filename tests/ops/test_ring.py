"""Ring attention vs. full attention on the 8-device fake mesh.

The reference has zero distributed tests and zero sequence parallelism
(SURVEY §4, §5.7); this exercises the real ppermute ring on 8 fake CPU
devices — the same code path a TPU slice runs over ICI.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.core.mesh import mesh_from_devices
from distributed_llms_tpu.models import layers
from distributed_llms_tpu.ops import ring


def _reference(q, k, v, positions, causal, q_per_kv):
    kf = layers.repeat_kv(k, q_per_kv)
    vf = layers.repeat_kv(v, q_per_kv)
    mask = layers.causal_mask(positions, positions) if causal else None
    return layers.dot_product_attention(q, kf, vf, mask)


@pytest.mark.parametrize(
    "seq_devices,heads,kv_heads,causal",
    [
        (8, 4, 4, True),
        (8, 4, 4, False),
        (4, 8, 2, True),  # GQA, seq=4 (other axes trivial)
        (2, 4, 1, True),  # MQA
    ],
)
def test_ring_matches_full_attention(seq_devices, heads, kv_heads, causal):
    mesh = mesh_from_devices({"seq": seq_devices}, jax.devices()[:seq_devices])
    b, t, d = 2, 32, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, t, heads, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, kv_heads, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, kv_heads, d)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    out = ring.ring_self_attention(mesh, q, k, v, positions, causal=causal)
    want = _reference(q, k, v, positions, causal, heads // kv_heads)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_ring_under_jit_and_grad():
    """Ring attention must jit and differentiate (training path)."""
    mesh = mesh_from_devices({"seq": 4}, jax.devices()[:4])
    b, t, h, d = 1, 16, 2, 8
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    def loss(q, k, v):
        return jnp.sum(ring.ring_self_attention(mesh, q, k, v, positions) ** 2)

    g = jax.jit(jax.grad(loss))(q, k, v)
    assert g.shape == q.shape
    assert bool(jnp.all(jnp.isfinite(g)))

    def loss_ref(q, k, v):
        return jnp.sum(_reference(q, k, v, positions, True, 1) ** 2)

    g_ref = jax.grad(loss_ref)(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-4, rtol=1e-4)


def test_ring_fully_masked_rows_are_zero():
    """k_valid=False everywhere -> output 0, no NaNs (online-softmax edge)."""
    mesh = mesh_from_devices({"seq": 2}, jax.devices()[:2])
    b, t, h, d = 1, 8, 2, 4
    q = jnp.ones((b, t, h, d), jnp.float32)
    k = jnp.ones((b, t, h, d), jnp.float32)
    v = jnp.ones((b, t, h, d), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    from jax.sharding import PartitionSpec as P

    def fn(q, k, v, qp, kp, kv):
        return ring.ring_attention(
            q, k, v, qp, kp, axis_name="seq", causal=True, k_valid=kv
        )
    sh = P(None, "seq", None, None)
    ps = P(None, "seq")
    out = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(sh, sh, sh, ps, ps, ps),
        out_specs=sh,
        axis_names={"seq"},
    )(q, k, v, positions, positions, jnp.zeros((b, t), bool))
    assert bool(jnp.all(out == 0.0))
    assert bool(jnp.all(jnp.isfinite(out)))
