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


# -- the grouped list (PR 56) ------------------------------------------------
# The list is built by a product on the MXU for the pairs that are live (a
# held expert's AND a real token's).  The form it replaced, one-hot sums and
# a cumulative sum over [P, E], is the reference here: the same integers for
# every live pair, and so the same tiles and the same bits.

def _one_hot_list(eid, live, e, bm, rows):
    oh = jax.nn.one_hot(jnp.where(live, eid, e), e, dtype=jnp.int32)  # [P, E]
    counts = jnp.sum(oh, axis=0)
    rank = jnp.sum((jnp.cumsum(oh, axis=0) - 1) * oh, axis=1)
    padded = -(-counts // bm) * bm
    ends = jnp.cumsum(padded)
    dest = jnp.sum(oh * (ends - padded), axis=1) + rank
    return jnp.where(live, dest, rows), counts, ends


def _share_case(seed, s=96, k=4, e=8, of=32, d=128, f=128, real=70):
    rs = np.random.RandomState(seed)
    w13, w2 = (
        quant_lib.quantize(
            jnp.asarray(rs.randn(1, e, kd, n), jnp.float32) * kd ** -0.5,
            block_axis=-2)
        for kd, n in ((d, 2 * f), (f, d)))
    x = jnp.asarray(rs.randn(s, d), jnp.float32)
    topi = jnp.asarray(
        np.argsort(rs.rand(s, of), axis=1)[:, :k], jnp.int32)
    return x, topi, w13, w2, jnp.arange(s) < real


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("of", [None, 32])
@pytest.mark.parametrize("p,e,bm", [(64, 32, 16), (80, 8, 16),
                                     (1408, 128, 16), (5000, 12, 64),
                                     (45056, 128, 256)])
def test_the_list_gives_the_one_hot_forms_integers(p, e, bm, of, masked):
    """dest, counts and ends (src, tile_expert and num_tiles are read off
    them) for every live pair, at a decode step's sizes (lfm2's 64 pairs,
    nemotron's 1,408) and an admission block's (nemotron's 2,048 x 22 over
    128 held; 5,000 is no multiple of the ranks' block)."""
    rs = np.random.RandomState(p + e)
    eid = jnp.asarray(rs.randint(0, of or e, p), jnp.int32)
    live = eid < e
    if masked:
        live = live & (jnp.arange(p) < int(0.77 * p))
    rows = (-(-p // bm) + e) * bm
    want = _one_hot_list(eid, live, e, bm, rows)
    got = jax.jit(moe_experts._grouped_list, static_argnums=(2, 3, 4))(
        eid, live, e, bm, rows)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert (np.asarray(got[0])[~np.asarray(live)] == rows).all()


@pytest.mark.parametrize("mode", ["interpret", "fallback"])
@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("of", [None, 32])
def test_a_real_tokens_pairs_are_the_one_hot_forms_bits(
        monkeypatch, mode, of, masked):
    """The pairs' outputs of the real tokens are what the one-hot form
    gives without a mask, bit for bit (the kernel's tiles are the same
    tiles, and a row of a tile does not depend on its neighbours); a
    masked token's are zeros."""
    monkeypatch.setenv("DLT_MOE_EXPERTS", mode)
    x, topi, w13, w2, mask = _share_case(3)
    if of is None:
        topi = topi % 8
    got = moe_experts.grouped_swiglu(
        x, topi, w13, w2, 0, of_experts=of,
        token_mask=mask if masked else None)
    monkeypatch.setattr(moe_experts, "_grouped_list", _one_hot_list)
    want = moe_experts.grouped_swiglu(x, topi, w13, w2, 0, of_experts=of)
    real = np.asarray(mask) if masked else np.ones(len(x), bool)
    np.testing.assert_array_equal(np.asarray(got)[real],
                                  np.asarray(want)[real])
    assert not np.asarray(got)[~real].any()


@pytest.mark.parametrize("mode", ["interpret", "fallback"])
@pytest.mark.parametrize("held", ["all", "none"])
def test_the_worst_cases_with_a_masked_tail(monkeypatch, mode, held):
    """Every pair on a held expert (the list's static size is for that: no
    pair may be dropped), and no pair on one (no tile at all), each with a
    masked tail."""
    monkeypatch.setenv("DLT_MOE_EXPERTS", mode)
    x, topi, w13, w2, mask = _share_case(5)
    topi = topi % 8 if held == "all" else 8 + topi % 24
    y = moe_experts.grouped_swiglu(
        x, topi, w13, w2, 0, of_experts=32, token_mask=mask)
    monkeypatch.setattr(moe_experts, "_grouped_list", _one_hot_list)
    want = moe_experts.grouped_swiglu(x, topi, w13, w2, 0, of_experts=32)
    real = np.asarray(mask)
    np.testing.assert_array_equal(np.asarray(y)[real], np.asarray(want)[real])
    assert not np.asarray(y)[~real].any()
    assert np.asarray(y)[real].any() == (held == "all")


@pytest.mark.parametrize("mode", ["interpret", "fallback"])
def test_a_real_token_does_not_depend_on_what_the_masked_ones_held(
        monkeypatch, mode):
    monkeypatch.setenv("DLT_MOE_EXPERTS", mode)
    x, topi, w13, w2, mask = _share_case(6)
    other = jnp.where(mask[:, None], topi, (topi + 3) % 32)
    junk = jnp.where(mask[:, None], x, 1e3)
    a = moe_experts.grouped_swiglu(
        x, topi, w13, w2, 0, of_experts=32, token_mask=mask)
    b = moe_experts.grouped_swiglu(
        junk, other, w13, w2, 0, of_experts=32, token_mask=mask)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.asarray(a)[~np.asarray(mask)].any()


@pytest.mark.parametrize("preset", ["lfm2-tiny", "ax-k1-tiny",
                                    "nemotron3-super-tiny"])
def test_the_routers_summed_scores_are_the_gathered_ones(preset):
    """``layers.route_experts(summed=True)`` takes the picked scores by a
    one-hot sum over E where the default gathers them: the weights, their
    normaliser included, are equal bit for bit under ``jit`` (without the
    barrier XLA folds the one-hot sum into the normaliser's and a quarter
    of the weights move by an ulp)."""
    from distributed_llms_tpu.models import layers
    from distributed_llms_tpu.models.presets import get_preset

    cfg = get_preset(preset)
    rs = np.random.RandomState(0)
    logits = jnp.asarray(rs.randn(256, cfg.num_experts) * 2, jnp.float32)
    bias = (jnp.asarray(rs.randn(cfg.num_experts) * 0.01, jnp.float32)
            if cfg.moe_expert_bias else None)
    (w0, t0), (w1, t1) = (
        jax.jit(lambda l: layers.route_experts(l, cfg, bias, summed=s))(logits)
        for s in (False, True))
    np.testing.assert_array_equal(np.asarray(t0), np.asarray(t1))
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w1))
