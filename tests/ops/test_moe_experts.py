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


# -- the combine's operand (PR 58) -------------------------------------------
# Where a chip holds a share of the experts and the grouped list outgrows
# fast memory, the pairs' rows are fetched by the pairs that hold one
# (``_pairs_rows``, the kernel ``moe_combine``) and no longer gathered for
# every pair.  The gather is the reference: the same array, zeros included.

def _bits(a):
    return np.asarray(jax.lax.bitcast_convert_type(a, jnp.uint16))


def _listed(s, k, e, of, masked, seed=0):
    """A routing of ``s`` tokens (the last fifth padding, when masked) and
    its list: (live, rows, dest, ends)."""
    rs = np.random.RandomState(seed)
    p = s * k
    eid = jnp.asarray(np.argsort(rs.rand(s, of), axis=1)[:, :k].reshape(p),
                      jnp.int32)
    live = eid < e
    if masked:
        live = live & (jnp.arange(p) // k < s - s // 5)
    bm, rows = moe_experts.list_shape(p, e, of)
    dest, _, ends = moe_experts._grouped_list(eid, live, e, bm, rows)
    return live, rows, dest, ends


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("held", ["share", "all"])
@pytest.mark.parametrize("k", [4, 6, 8, 22])
def test_the_fetched_rows_are_the_gathers_bits(k, held, masked):
    """``_pairs_rows`` on the Pallas interpreter against ``yp.at[dest].get(
    mode="fill", fill_value=0)``: every 16 bits of [P, D], a dead pair's
    +0.0 and a held pair's -0.0 among them, at the four configurations'
    k, with a share of the experts and all of them, with and without
    padding tokens; the experts' groups end inside a tile (the last tile
    of each is part padding, and its rows are fetched beside a real one's
    and never placed)."""
    e, of = (8, 32) if held == "share" else (32, 32)
    live, rows, dest, ends = _listed(64, k, e, of, masked, seed=k)
    assert int(ends[-1]) > int(jnp.sum(live)) > 0  # tiles hold padding
    rs = np.random.RandomState(1)
    yp = jnp.asarray(rs.randn(rows, 256), jnp.bfloat16)
    yp = yp.at[::5].set(-0.0)
    want = yp.at[dest].get(mode="fill", fill_value=0)
    got = moe_experts._pairs_rows(yp, dest, interpret=True)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))
    dead = ~np.asarray(live)
    assert not _bits(got)[dead].any()  # +0.0, as the gather's fill


def _bf16_share(seed, s, k, e, of, d=256, f=128):
    rs = np.random.RandomState(seed)
    w13, w2 = (
        quant_lib.quantize(
            jnp.asarray(rs.randn(1, e, kd, n), jnp.float32) * kd ** -0.5,
            block_axis=-2)
        for kd, n in ((d, 2 * f), (f, d)))
    x = jnp.asarray(rs.randn(s, d), jnp.bfloat16)
    topi = jnp.asarray(np.argsort(rs.rand(s, of), axis=1)[:, :k], jnp.int32)
    return x, topi, w13, w2, jnp.arange(s) < s - s // 5


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("k", [4, 8, 22])
def test_the_pairs_outputs_do_not_depend_on_who_fills_them(
        monkeypatch, dispatched, k, masked):
    """``grouped_swiglu`` with the rows fetched (the size rule set aside:
    the tests' lists are small, their rows narrow) against the same call
    with the gather,
    bit for bit, and the count it hands out: the held pairs of the real
    tokens where the kernel ran, 0 where the gather did."""
    monkeypatch.setenv("DLT_MOE_EXPERTS", "interpret")
    x, topi, w13, w2, mask = _bf16_share(k, 64, k, 8, 32)
    kw = dict(of_experts=32, token_mask=mask if masked else None,
              count_fetched=True)
    want, none = moe_experts.grouped_swiglu(x, topi, w13, w2, 0, **kw)
    assert dispatched() == {"moe_experts.interpret": 1}  # the gather: unnamed
    monkeypatch.setattr(moe_experts, "_COPY_MIN_BYTES", 0)
    monkeypatch.setattr(moe_experts, "_COPY_PAIR_BYTES", 0)
    got, fetched = moe_experts.grouped_swiglu(x, topi, w13, w2, 0, **kw)
    assert dispatched() == {"moe_experts.interpret": 2,
                            "moe_combine.interpret": 1}
    np.testing.assert_array_equal(_bits(got), _bits(want))
    held = np.asarray(topi < 8)
    if masked:
        held = held & np.asarray(mask)[:, None]
    assert int(none) == 0 and int(fetched) == held.sum() > 0


@pytest.mark.parametrize("why", ["every expert held", "float32 rows",
                                 "a small list", "narrow rows",
                                 "float stacks"])
def test_who_keeps_the_gather(monkeypatch, dispatched, why):
    """The rows are fetched only for a share of the experts, 16-bit rows,
    a list of ``_COPY_MIN_BYTES``, rows of ``_COPY_PAIR_BYTES`` a held pair
    and the int8 kernel's leg; every other
    call gathers as before and records no ``moe_combine`` (a ``fallback``
    count on the chip would fail the benchmark's ``correct``)."""
    monkeypatch.setenv("DLT_MOE_EXPERTS", "interpret")
    x, topi, w13, w2, _ = _bf16_share(0, 32, 4, 8, 32)
    of = 32
    if why != "a small list":
        monkeypatch.setattr(moe_experts, "_COPY_MIN_BYTES", 0)
    if why != "narrow rows":
        monkeypatch.setattr(moe_experts, "_COPY_PAIR_BYTES", 0)
    if why == "every expert held":
        topi, of = topi % 8, None
    elif why == "float32 rows":
        x = x.astype(jnp.float32)
    elif why == "float stacks":
        w13, w2 = quant_lib.dequantize(w13), quant_lib.dequantize(w2)
    _, fetched = moe_experts.grouped_swiglu(
        x, topi, w13, w2, 0, of_experts=of, count_fetched=True)
    assert int(fetched) == 0
    assert not [k for k in dispatched() if k.startswith("moe_combine")]


def test_the_copys_block_at_the_published_widths():
    """Blocks of pairs whose fetched tiles, two blocks' of them, fit the
    stage: 256 pairs at nemotron's rows of 1,024, 64 at A.X-K1's 7,168 and
    K-EXAONE's 6,144; a pair count that is no multiple of 32, or a width
    that is no multiple of 128 lanes, keeps the gather."""
    assert moe_experts._copy_block(2048 * 22, 1024) == 256
    assert moe_experts._copy_block(2048 * 8, 7168) == 64
    assert moe_experts._copy_block(2048 * 8, 6144) == 64
    assert moe_experts._copy_block(1024 * 8, 7168) == 64
    assert moe_experts._copy_block(96, 256) == 32
    assert moe_experts._copy_block(80, 256) is None
    assert moe_experts._copy_block(64, 200) is None
    # The rule of grouped_swiglu, at the cells' blocks: the list of a
    # 2,048-token and a 1,024-token block of A.X-K1 and K-EXAONE is over
    # the line, a 512-token block's and every decode step's under it.
    def list_bytes(s, k, e, of, d):
        return moe_experts.list_shape(s * k, e, of)[1] * d * 2
    line = moe_experts._COPY_MIN_BYTES
    assert list_bytes(2048, 8, 12, 192, 7168) > line
    assert list_bytes(1024, 8, 16, 128, 6144) > line
    assert list_bytes(512, 8, 12, 192, 7168) > line  # 69.7 MB: from HBM
    assert list_bytes(512, 8, 16, 128, 6144) < line  # 62.9: fast memory
    assert list_bytes(256, 8, 12, 192, 7168) < line
    assert list_bytes(64, 8, 12, 192, 7168) < line
    # ... and the rows the gather moves a held pair: nemotron's four of
    # 2 KB stay gathered, A.X-K1's sixteen of 14 KB are not.
    pair = moe_experts._COPY_PAIR_BYTES
    assert 1024 * 2 * 512 < pair * 128
    assert 7168 * 2 * 192 > pair * 12 and 6144 * 2 * 128 > pair * 16


@pytest.mark.parametrize("base,k", [
    ("lfm2-tiny", 4), ("lfm2-tiny", 8), ("ax-k1-tiny", 8),
    ("k-exaone-tiny", 4), ("k-exaone-tiny", 8), ("k-exaone-tiny", 22)])
def test_moe_dropless_is_the_gathers_output_with_the_rows_fetched(
        monkeypatch, base, k):
    """``layers.moe_dropless`` under ``jit`` at a share of 8 of 32 experts
    behind three routing rules, padding tokens among the rows: the layer's
    output with the rows fetched is the output with the gather (the parent's
    two lines, which the size rule keeps for the tests' small lists), bit
    for bit, and the sixth count is the fifth where the kernel ran and 0
    where it did not.  (The sum over k is XLA's in both; on the CPU it
    fuses the gather into the reduce and sums lfm2's sigmoid weights at
    k = 22 in another order, so 22 is held at K-EXAONE's rule; on the chip
    the two are separate operations, PERF.md section 6, PR 58.)"""
    import dataclasses

    from distributed_llms_tpu.models import layers
    from distributed_llms_tpu.models.presets import get_preset

    monkeypatch.setenv("DLT_MOE_EXPERTS", "interpret")
    d, f, e, of = 128, 128, 8, 32
    cfg = dataclasses.replace(
        get_preset(base), num_experts=of, experts_held=e, experts_offset=8,
        num_experts_per_token=k, hidden_size=d)
    rs = np.random.RandomState(k)
    p = {"router": jnp.asarray(rs.randn(1, d, of), jnp.float32),
         "experts": {
             "w_gate_up": quant_lib.quantize(jnp.asarray(
                 rs.randn(1, e, d, 2 * f) * d ** -0.5, jnp.float32),
                 block_axis=-2),
             "w_down": quant_lib.quantize(jnp.asarray(
                 rs.randn(1, e, f, d) * f ** -0.5, jnp.float32),
                 block_axis=-2)}}
    if cfg.moe_expert_bias:
        p["expert_bias"] = jnp.asarray(rs.randn(1, of) * 0.1, jnp.float32)
    x = jnp.asarray(rs.randn(2, 32, d), jnp.bfloat16)
    mask = jnp.arange(64).reshape(2, 32) % 32 < 27

    def run():
        return jax.jit(
            lambda x: layers.moe_dropless(x, p, cfg, token_mask=mask))(x)

    want, gathered = run()
    monkeypatch.setattr(moe_experts, "_COPY_MIN_BYTES", 0)
    monkeypatch.setattr(moe_experts, "_COPY_PAIR_BYTES", 0)
    got, fetched = run()
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert np.asarray(want, np.float32).any()
    np.testing.assert_array_equal(gathered[:5], fetched[:5])
    routed, _, _, _, held, rows = (int(c) for c in fetched)
    assert int(gathered[5]) == 0 and 0 < rows == held < routed
    assert routed == 2 * 27 * k
