"""The expert kernel (ops/moe_experts.py) in interpret mode against
dequantized einsums: the same kernel program the chip compiles, on the
Pallas interpreter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.checkpoint import quantize as quant_lib
from distributed_llms_tpu.ops import moe_experts

L, E, D, F, S, K = 2, 8, 256, 128, 40, 2


@pytest.fixture(scope="module")
def stacks():
    rs = np.random.RandomState(0)
    w13 = jnp.asarray(rs.randn(L, E, D, 2 * F) * D**-0.5, jnp.float32)
    w2 = jnp.asarray(rs.randn(L, E, F, D) * F**-0.5, jnp.float32)
    q13 = quant_lib.quantize(w13, block_axis=-2)
    q2 = quant_lib.quantize(w2, block_axis=-2)
    x = jnp.asarray(rs.randn(S, D), jnp.float32)
    # Expert 7 gets no token, expert 3 gets one of every token's two.
    topi = jnp.asarray(rs.randint(0, E - 1, (S, K)), jnp.int32).at[:, 0].set(3)
    return x, topi, q13, q2


def einsum_reference(x, topi, q13, q2, layer):
    d13 = quant_lib.dequantize(q13)[layer]
    d2 = quant_lib.dequantize(q2)[layer]
    g = jnp.einsum("sd,skdf->skf", x, d13[topi])
    h = jax.nn.silu(g[..., :F]) * g[..., F:]
    return jnp.einsum("skf,skfd->skd", h, d2[topi])


def test_blocks_along_the_contracted_axis_round_trip(stacks):
    _, _, q13, _ = stacks
    assert q13.data.shape == (L, E, D, 2 * F) and q13.data.dtype == jnp.int8
    assert q13.scale.shape == (L, E, D // 128, 2 * F) and q13.block_axis == -2
    again = quant_lib.quantize(quant_lib.dequantize(q13), block_axis=-2)
    np.testing.assert_array_equal(np.asarray(again.data), np.asarray(q13.data))


@pytest.mark.parametrize("mode", ["interpret", "fallback"])
@pytest.mark.parametrize("layer", [0, 1])
def test_grouped_kernel_matches_dequantized_einsum(stacks, monkeypatch,
                                                   dispatched, mode, layer):
    x, topi, q13, q2 = stacks
    monkeypatch.setenv("DLT_MOE_EXPERTS", mode)
    y = moe_experts.grouped_swiglu(x, topi, q13, q2, layer)
    assert dispatched() == {f"moe_experts.{mode}": 1}
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(einsum_reference(x, topi, q13, q2, layer)),
        atol=2e-5)


def test_a_traced_layer_index_reads_that_layer(stacks, monkeypatch):
    x, topi, q13, q2 = stacks
    monkeypatch.setenv("DLT_MOE_EXPERTS", "interpret")
    y = jax.jit(lambda l: moe_experts.grouped_swiglu(x, topi, q13, q2, l))(
        jnp.int32(1))
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(einsum_reference(x, topi, q13, q2, 1)),
        atol=2e-5)


def test_float_stacks_take_the_ragged_fallback(stacks, dispatched):
    x, topi, q13, q2 = stacks
    y = moe_experts.grouped_swiglu(
        x, topi, quant_lib.dequantize(q13), quant_lib.dequantize(q2), 1)
    assert dispatched() == {"moe_experts.fallback": 1}
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(einsum_reference(x, topi, q13, q2, 1)),
        atol=2e-5)


def test_tiles_at_the_published_widths():
    """LFM2's experts in tiles of whole rows, each one run of bytes in HBM:
    [2048, 3584] in two K steps, [1792, 2048] whole (14 scale rows are no
    multiple of 8, so K is not cut); columns are cut only when rows cannot
    be; tiles of 16 rows for a decode step, 256 for the largest admission."""
    assert moe_experts._tiles(2048, 3584, 128) == (1024, 3584)
    assert moe_experts._tiles(1792, 2048, 128) == (1792, 2048)
    assert moe_experts._tiles(1792, 4096, 128) == (1792, 2048)
    assert moe_experts._tiles(2048, 3584, 64) is None
    assert moe_experts.row_tile(64, 32) == 16
    assert moe_experts.row_tile(8192, 32) == 256


@pytest.mark.parametrize("mode", ["interpret", "fallback"])
def test_a_share_that_no_pair_fell_on_gives_zeros(monkeypatch, mode):
    """A chip's share of the experts (``of_experts``) in a step in which
    every pair names an absent expert: no tile at all (the index maps must
    not name block -1), and every pair's output is zeros; with some pairs
    held, theirs are the held experts' and the rest zeros."""
    monkeypatch.setenv("DLT_MOE_EXPERTS", mode)
    e, d, f, s, k = 4, 128, 128, 8, 2
    rng = np.random.RandomState(0)
    w13, w2 = (
        quant_lib.quantize(
            jnp.asarray(rng.randn(1, e, kd, n), jnp.float32) * kd ** -0.5,
            block_axis=-2)
        for kd, n in ((d, 2 * f), (f, d)))
    x = jnp.asarray(rng.randn(s, d), jnp.float32)
    absent = jnp.asarray(rng.randint(e, 16, (s, k)), jnp.int32)
    y = moe_experts.grouped_swiglu(x, absent, w13, w2, 0, of_experts=16)
    assert y.shape == (s, k, d) and not np.asarray(y).any()
    mixed = absent.at[::2, 0].set(jnp.arange(s // 2) % e).at[1, 1].set(-3)
    y = moe_experts.grouped_swiglu(x, mixed, w13, w2, 0, of_experts=16)
    whole = moe_experts.grouped_swiglu(
        x, jnp.clip(mixed, 0, e - 1), w13, w2, 0)
    held = np.asarray((mixed >= 0) & (mixed < e))
    np.testing.assert_allclose(np.asarray(y)[held], np.asarray(whole)[held],
                               atol=1e-5)
    assert not np.asarray(y)[~held].any()
