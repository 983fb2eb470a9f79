"""Latent attention, experts routed by groups, a shared expert, a chip's
share of the experts and YaRN (A.X-K1) against the plain reference, on the
CPU with ``ax-k1-tiny`` in float32.  Logits are compared, never sampled
tokens.  Tolerance 2e-5 on logits of about unit size: float32 end to end on
both sides, so what differs is the order of summation (the served path
contracts through the latent, the reference expands every head)."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.models import kv_cache, layers, model as model_lib
from distributed_llms_tpu.models.presets import get_preset
from distributed_llms_tpu.models.reference import axk1
from tools.reference_check import reference_cfg

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ATOL = 2e-5


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset("ax-k1-tiny")
    return cfg, model_lib.init_params(jax.random.key(0), cfg)


def reference(params, cfg, tokens, **kw):
    tree = dict(params, layers=list(model_lib.hybrid_layers(params, cfg)))
    return np.asarray(axk1.forward(tree, reference_cfg(cfg), tokens, **kw))


def tokens_of(n, seed=0):
    return np.random.RandomState(seed).randint(0, 256, (n,)).astype(np.int32)


def share_of(params, cfg, first, count):
    """The model a chip holds: experts [first, first + count) of every
    expert layer's stacks, the router whole."""
    blocks = dict(params["blocks"])
    blocks["moe"] = dict(blocks["moe"], experts=jax.tree.map(
        lambda a: a[:, first: first + count], blocks["moe"]["experts"]))
    return (dataclasses.replace(cfg, experts_held=count, experts_offset=first),
            dict(params, blocks=blocks))


def test_the_benchmarks_reference_is_a_copy():
    with open(os.path.join(ROOT, "distributed_llms_tpu", "models",
                           "reference", "axk1.py"), "rb") as f:
        mine = f.read()
    with open(os.path.join(ROOT, "benchmark", "reference", "axk1.py"),
              "rb") as f:
        assert f.read() == mine


def test_forward_without_a_cache_is_the_reference(tiny):
    cfg, params = tiny
    toks = tokens_of(70)  # past the original context of 32: YaRN is live
    logits, _ = model_lib.forward(params, cfg, jnp.asarray(toks)[None])
    np.testing.assert_allclose(
        np.asarray(logits[0]), reference(params, cfg, toks), atol=ATOL)


@pytest.mark.parametrize("held", [None, (4, 4)])
@pytest.mark.parametrize("n,bucket", [(1, 8), (9, 16), (33, 64)])
def test_prefill_then_decode_through_the_latent_pool(tiny, n, bucket, held):
    """(a) A padded prefill into a transient latent row, its pages written
    into the pool, then 8 decode steps that read the pool in the absorbed
    form, each against the reference's full forward; the whole model and a
    chip's share of its experts."""
    cfg, params = tiny
    if held:
        cfg, params = share_of(params, cfg, *held)
    toks = tokens_of(n + 8, seed=n)
    ref = reference(params, cfg, toks, experts_held=held)
    blk, pages = 8, 12
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = toks[:n]
    row = kv_cache.init_cache(cfg, 1, pages * blk)
    logits, row, stats = model_lib.forward(
        params, cfg, jnp.asarray(padded), cache=row,
        cache_index=jnp.int32(0), seq_lens=jnp.asarray([n], jnp.int32),
        return_aux=True)
    np.testing.assert_allclose(np.asarray(logits[0, :n]), ref[:n], atol=ATOL)
    assert [int(x) for x in stats[:2]] == [n * 4 * 3, 3]
    assert len(stats) == (6 if held else 4)  # (a share: held pairs, fetched)
    page_list = jnp.asarray(2 + np.arange(pages)[::-1].copy(), jnp.int32)
    pool = kv_cache.write_row(
        kv_cache.make_pool(cfg, 16, blk), page_list, row)
    assert set(vars(pool)) == {"k"} and pool.k.shape == (4, 16, blk, 128)
    tables = jnp.zeros((3, pages), jnp.int32).at[1].set(page_list)
    for t in range(n, n + 8):
        last = jnp.zeros((3,), jnp.int32).at[1].set(int(toks[t]))
        lens = jnp.zeros((3,), jnp.int32).at[1].set(t)
        logits, pool = model_lib.forward(
            params, cfg, last[:, None], positions=lens[:, None], cache=pool,
            cache_index=lens, kv_tables=tables)
        np.testing.assert_allclose(np.asarray(logits[1, 0]), ref[t], atol=ATOL)


def test_the_shares_add_up(tiny):
    """(d) Over every share of the experts (four chips of four), the routed
    parts summed and the shared expert counted once are the uncut layer:
    the reference's, and the served layer's share by share."""
    cfg, params = tiny
    ref_cfg = reference_cfg(cfg)
    u = jnp.asarray(np.random.RandomState(3).randn(9, 64), jnp.float32)
    for layer in list(model_lib.hybrid_layers(params, cfg))[1:]:
        p = layer["mlp"]
        with jax.default_matmul_precision("highest"):
            whole = axk1.experts(u, p, ref_cfg)
            parts = []
            for first in range(0, 16, 4):
                held = dict(p, experts=jax.tree.map(
                    lambda a: a[first: first + 4], p["experts"]))
                parts.append(axk1.experts(
                    u, held, ref_cfg, (first, 4), shared=first == 0))
        np.testing.assert_allclose(sum(parts), whole, atol=ATOL)
    # The served layer computes the same share as the reference's.
    for first in range(0, 16, 4):
        c, prm = share_of(params, cfg, first, 4)
        y, stats = layers.moe_dropless(
            u[None], prm["blocks"]["moe"], c, layer=1)
        p = list(model_lib.hybrid_layers(prm, c))[2]["mlp"]
        with jax.default_matmul_precision("highest"):
            want = axk1.experts(u, p, ref_cfg, (first, 4), shared=False)
        np.testing.assert_allclose(np.asarray(y[0]), want, atol=ATOL)
        assert int(stats[0]) == 9 * 4 and 0 <= int(stats[4]) <= 9 * 4
    total = sum(int(layers.moe_dropless(
        u[None], share_of(params, cfg, f, 4)[1]["blocks"]["moe"],
        share_of(params, cfg, f, 4)[0], layer=1)[1][4])
        for f in range(0, 16, 4))
    assert total == 9 * 4  # every routed pair is held by exactly one chip


def test_absorbed_decode_is_expanded_attention(tiny):
    """(e) One layer's attention of a new token: against latent pages (the
    absorbed form) and against the same latents as a contiguous row (keys
    and values expanded)."""
    cfg, params = tiny
    p = model_lib.layer_of(params["blocks"]["mla"], 2)
    rng = np.random.RandomState(5)
    n, blk = 21, 8
    rows = jnp.asarray(rng.randn(1, 32, cfg.latent_width), jnp.float32)
    rows = rows.at[..., 40:].set(0.0).at[:, n:].set(0.0)
    x = jnp.asarray(rng.randn(1, 1, 64), jnp.float32)
    pos = jnp.asarray([[n]], jnp.int32)
    mask = (jnp.arange(32) <= n)[None, None, None, :]
    at = jnp.asarray([n], jnp.int32)
    want, new_rows = model_lib.mla_attention(
        x, p, cfg, model_lib.call_of((1, 1), pos, at, mask, cached=True),
        rows)
    pool = kv_cache.LatentCache(k=jnp.zeros(
        (4, 9, blk, cfg.latent_width)).at[2, 1:5].set(
            rows[0].reshape(4, blk, -1)))
    got, pool = model_lib.mla_attention(
        x, p, cfg, model_lib.call_of(
            (1, 1), pos, at, kv_tables=jnp.asarray([[1, 2, 3, 4]], jnp.int32),
            cached=True), pool, jnp.int32(2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)
    np.testing.assert_array_equal(  # both cached the same new row
        np.asarray(pool.k[2, 1:5]).reshape(32, -1), np.asarray(new_rows[0]))


def test_group_selection_keeps_the_best_groups_only():
    """(f) A token whose 8 largest scores span 5 groups gets experts of its
    4 best groups only; with one group the same scores pick all 8."""
    cfg = dataclasses.replace(
        get_preset("ax-k1-ep16"), num_layers=2, layer_types=("mla",) * 2)
    logits = np.full((1, 192), -4.0, np.float32)
    # Groups are runs of 24.  Two high scores in each of groups 0-2, one in
    # groups 3 and 4 (group 4's the smaller): the 8 largest span 5 groups.
    top = {0: 3.0, 1: 2.9, 24: 2.8, 25: 2.7, 48: 2.6, 49: 2.5, 72: 2.4,
           96: 2.3, 97: -1.0, 73: -1.5}
    for e, v in top.items():
        logits[0, e] = v
    w, idx = layers.route_experts(jnp.asarray(logits), cfg)
    assert sorted(int(i) for i in idx[0]) == [0, 1, 24, 25, 48, 49, 72, 73]
    assert 96 not in idx[0]  # group 4 is the fifth best: none of its experts
    np.testing.assert_allclose(float(jnp.sum(w)), 2.5, rtol=1e-6)
    flat = dataclasses.replace(cfg, moe_n_group=1, moe_topk_group=1)
    _, idx = layers.route_experts(jnp.asarray(logits), flat)
    assert sorted(int(i) for i in idx[0]) == [0, 1, 24, 25, 48, 49, 72, 96]


def test_yarn_table_against_the_formula():
    """(g) A.X-K1's rope frequencies against the formula written out, and
    the rotation at positions on both sides of the original 4,096."""
    cfg = get_preset("ax-k1-ep16")
    d, theta, factor, span = 64, 10000.0, 32.0, 4096
    inv = np.asarray(layers.yarn_frequencies(d, theta, factor, span))
    f = theta ** (-np.arange(0, d, 2) / d)

    def corr(r):
        return d * np.log(span / (2 * np.pi * r)) / (2 * np.log(theta))

    low, high = np.floor(corr(32)), np.ceil(corr(1))
    assert (low, high) == (10, 23)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    np.testing.assert_allclose(inv, f / factor * ramp + f * (1 - ramp),
                               rtol=1e-6)
    np.testing.assert_allclose(inv[:10], f[:10], rtol=1e-6)   # kept
    np.testing.assert_allclose(inv[23:], f[23:] / 32, rtol=1e-6)  # stretched
    np.testing.assert_allclose(model_lib.mla_scale(cfg),
                               (0.1 * np.log(32) + 1) ** 2 / np.sqrt(192),
                               rtol=1e-6)
    pos = np.asarray([[7, 4095, 4097, 100000]], np.int32)
    x = np.random.RandomState(0).randn(1, 4, 2, d).astype(np.float32)
    got = np.asarray(model_lib.mla_rope(jnp.asarray(x), jnp.asarray(pos), cfg))
    ang = pos[0][:, None].astype(np.float64) * inv[None, :].astype(np.float64)
    x1, x2 = x[0, :, :, 0::2], x[0, :, :, 1::2]
    want = np.stack([x1 * np.cos(ang)[:, None] - x2 * np.sin(ang)[:, None],
                     x2 * np.cos(ang)[:, None] + x1 * np.sin(ang)[:, None]],
                    axis=-1).reshape(4, 2, d)
    # float32 angles at position 100,000 carry 1e-2 rad of rounding in the
    # fastest pairs; the slow ones, which YaRN moved, are exact to 1e-4.
    np.testing.assert_allclose(got[0][:3], want[:3], atol=2e-3)
    np.testing.assert_allclose(got[0][3, :, 46:], want[3, :, 46:], atol=1e-3)


def test_config_refuses_what_it_cannot_mean():
    cfg = get_preset("ax-k1-tiny")
    with pytest.raises(ValueError, match="every layer's or none's"):
        dataclasses.replace(cfg, kv_lora_rank=0)
    with pytest.raises(ValueError, match="groups"):
        dataclasses.replace(cfg, moe_topk_group=1, num_experts_per_token=8)
    with pytest.raises(ValueError, match="experts_held"):
        dataclasses.replace(cfg, experts_held=12, experts_offset=8)
    with pytest.raises(ValueError, match="rope_scaling_type"):
        dataclasses.replace(cfg, rope_scaling_type="linear")
