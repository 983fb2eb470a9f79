"""The seam of models/kv_cache.py, the one owner of how a page of keys and
values is stored: each movement of pages holds for every format (the
full-width pool, the int8 pool, the hybrid pool with its folded heads and
its state that is not paged), and nothing under runtime/, parallel/ or
cluster/ tells one format from another."""

import dataclasses
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_llms_tpu.checkpoint.quantize import (kv_dequantize,
                                                      kv_quantize)
from distributed_llms_tpu.models import kv_cache
from distributed_llms_tpu.models.presets import get_preset

PAGES, BLK, SLOTS = 12, 8, 3
PACKAGE = pathlib.Path(kv_cache.__file__).resolve().parents[1]


def _dense(kv_bits):
    return get_preset("llama-tiny"), dict(kv_bits=kv_bits,
                                          dtype=jnp.bfloat16)


def _hybrid():
    # two KV heads of 64: they lie folded, one 128-lane row, in the pool
    return dataclasses.replace(get_preset("lfm2-tiny"), head_dim=64), \
        dict(slots=SLOTS)


FORMATS = {"bf16": lambda: _dense(16), "int8": lambda: _dense(8),
           "hybrid": _hybrid}


@pytest.fixture(params=sorted(FORMATS))
def fmt(request):
    """(name, cfg, a pool full of noise): a write that strays, or a leaf
    that is dropped, shows wherever it lands."""
    cfg, kw = FORMATS[request.param]()
    pool = kv_cache.make_pool(cfg, PAGES, BLK, **kw)
    keys = iter(jax.random.split(jax.random.key(5), 8))

    def noise(x):
        r = jax.random.normal(next(keys), x.shape) * 4.0
        if x.dtype == jnp.int8:
            return jnp.clip(jnp.round(r * 8), -127, 127).astype(jnp.int8)
        return (jnp.abs(r) + 0.5 if request.param == "int8" else r
                ).astype(x.dtype)

    return request.param, cfg, jax.tree.map(noise, pool)


def _row(cfg, pool, pages):
    """A transient row cache of ``pages`` pages, as a prefill leaves it:
    full-width [L, 1, P*BLK, KVH, HD] in the pool's row dtype."""
    row = kv_cache.init_cache(cfg, 1, pages * BLK,
                              dtype=kv_cache.row_dtype(pool))
    keys = iter(jax.random.split(jax.random.key(9), 4))
    return jax.tree.map(
        lambda x: jax.random.normal(next(keys), x.shape).astype(x.dtype),
        row)


def test_write_row_then_gather_row_returns_the_row(fmt):
    name, cfg, pool = fmt
    row = _row(cfg, pool, 3)
    page_list = jnp.asarray([7, 2, 9], jnp.int32)
    before = jax.tree.map(np.asarray, pool)
    # (op by op, as the reference below is computed: a fused x / scale
    # may round one element in thousands the other way)
    new = kv_cache.write_row(pool, page_list, row, jnp.int32(1))
    got_k, got_v = kv_cache.gather_row(new, page_list)
    for got, want in ((got_k, row.k), (got_v, row.v)):
        if name == "int8":  # kv_quantize's round trip, nothing further
            want = kv_dequantize(*kv_quantize(want), want.dtype)
        # (a folded pool hands its own last two axes back: the same bytes)
        np.testing.assert_array_equal(
            np.asarray(got).reshape(want.shape), np.asarray(want))
    # ... and no other page moved.
    others = np.setdiff1d(np.arange(PAGES), np.asarray(page_list))
    for leaf, old in zip(jax.tree.leaves(new), jax.tree.leaves(before)):
        if leaf.shape[1:3] == (PAGES, BLK):
            np.testing.assert_array_equal(
                np.asarray(leaf)[:, others], old[:, others])
    if name == "hybrid":  # the state that is not paged lands in its slot
        np.testing.assert_array_equal(
            np.asarray(new.conv[:, 1]), np.asarray(row.conv[:, 0]))
        np.testing.assert_array_equal(
            np.asarray(new.conv[:, 0]), before.conv[:, 0])


def test_export_raw_then_import_raw_restores_the_pool_bit_for_bit(fmt):
    _, _, pool = fmt
    page_list = jnp.asarray([3, 1, 5, 0], jnp.int32)  # scratch-padded
    parcel = kv_cache.export_raw(pool, page_list)
    wiped = kv_cache.import_raw(
        pool, page_list, *(jnp.zeros_like(x) for x in parcel))
    assert not all(
        np.array_equal(a, b) for a, b in
        zip(jax.tree.leaves(wiped), jax.tree.leaves(pool)))
    back = kv_cache.import_raw(wiped, page_list, *parcel)
    assert jax.tree.structure(back) == jax.tree.structure(pool)
    for got, want in zip(jax.tree.leaves(back), jax.tree.leaves(pool)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pool_specs_has_the_pools_tree_structure(fmt):
    _, cfg, pool = fmt
    mesh = jax.sharding.AbstractMesh((1, 2), ("data", "model"))
    specs = kv_cache.pool_specs(cfg, mesh, jax.eval_shape(lambda: pool))
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    assert jax.tree.structure(specs, is_leaf=is_spec) \
        == jax.tree.structure(pool)
    # tree.map over (pool, specs) is what constrain() does on a mesh
    ranks = jax.tree.map(lambda x, s: (x.ndim, len(s)), pool, specs)
    for ndim, spec_len in jax.tree.leaves(
            ranks, is_leaf=lambda x: isinstance(x, tuple)):
        assert spec_len <= ndim
    assert specs.k[3] == specs.v[3] == "model"


def test_page_bytes_is_what_a_page_of_the_pool_holds(fmt):
    name, cfg, pool = fmt
    paged = [x for x in jax.tree.leaves(pool)
             if x.shape[1:3] == (PAGES, BLK)]
    assert len(paged) == (4 if name == "int8" else 2)
    want = sum(x.nbytes for x in paged) // PAGES
    assert kv_cache.page_bytes(
        cfg, BLK, kv_bits=8 if name == "int8" else 16,
        dtype=kv_cache.row_dtype(pool)) == want


def test_nothing_above_the_seam_tells_one_format_from_another():
    """runtime/, parallel/ and cluster/ hold a pool and hand it on; which
    format it is, and its scales, are models/kv_cache.py's to know."""
    banned = re.compile(
        r"isinstance\([^)]*(KVCache|QuantKVCache|HybridCache|LatentCache)"
        r"|\.k_scale")
    found = [
        f"{path.relative_to(PACKAGE)}:{n}: {line.strip()}"
        for sub in ("runtime", "parallel", "cluster")
        for path in sorted((PACKAGE / sub).rglob("*.py"))
        for n, line in enumerate(path.read_text().splitlines(), 1)
        if banned.search(line)
    ]
    assert not found, "\n".join(found)
    model = (PACKAGE / "models" / "model.py").read_text()
    for spelling in ("len(pool)", ".k_scale", "cache_sk", "cache_sv"):
        assert spelling not in model, spelling


def test_the_host_side_of_the_pool_imports_no_jax():
    """The router and the gateway hash pages with PrefixCache.page_digests:
    they must not load an engine's dependencies to do it."""
    code = ("import sys, distributed_llms_tpu.runtime.pages as p; "
            "assert 'jax' not in sys.modules, 'pages imports jax'; "
            "assert p.PagePool and p.PrefixCache.page_digests")
    subprocess.run([sys.executable, "-c", code], check=True,
                   cwd=PACKAGE.parent)


# -- a state a slot and no page: a model of retention layers (PR 50) --------

def test_a_retention_models_cache_is_its_state_and_no_key():
    """``slot_state`` sizes the leaves: [ret layers, rows, KVH, 65, 128,
    128] float32 and its normaliser [.., 128, 128], whatever the
    activations' dtype; the stacks of keys and values count zero layers."""
    from distributed_llms_tpu.ops import retention

    cfg = dataclasses.replace(get_preset("brumby-tiny"), dtype="bfloat16")
    state = kv_cache.slot_state(cfg, 3, jnp.bfloat16)
    assert set(state) == {"ret_s", "ret_z"}
    assert state["ret_s"].shape == (2, 3, 2, 65, 128, 128)
    assert state["ret_z"].shape == (2, 3, 2, 128, 128)
    assert state["ret_s"].dtype == state["ret_z"].dtype == jnp.float32
    cache = kv_cache.init_cache(cfg, 3, 64)
    assert isinstance(cache, kv_cache.HybridCache)
    assert cache.k.shape == cache.v.shape == (0, 3, 64, 2, 128)
    assert cache.conv is None and cache.ring_k is None
    assert kv_cache.format_bytes(cache, cfg) == {
        "ret_state": 3.0 * 2 * retention.state_bytes(2)}
    assert retention.state_bytes(8) == 34_603_008  # a row a layer, served
    assert kv_cache.pages_are_private(cfg)
    # the other hybrids' caches carry no such leaf
    other = kv_cache.make_pool(get_preset("lfm2-tiny"), PAGES, BLK,
                               slots=SLOTS)
    assert other.ret_s is None and other.ret_z is None


def test_splice_slot_overwrites_one_slots_state_and_no_others():
    cfg = get_preset("brumby-tiny")
    keys = iter(jax.random.split(jax.random.key(3), 8))
    noise = lambda x: jax.random.normal(next(keys), x.shape).astype(x.dtype)
    cache = jax.tree.map(noise, kv_cache.init_cache(cfg, 3, 16))
    row = jax.tree.map(noise, kv_cache.init_cache(cfg, 1, 16))
    out = kv_cache.splice_slot(cache, jnp.int32(1), row)
    for f in ("ret_s", "ret_z"):
        got = np.asarray(getattr(out, f))
        np.testing.assert_array_equal(got[:, 1], np.asarray(
            getattr(row, f))[:, 0])
        np.testing.assert_array_equal(
            got[:, [0, 2]], np.asarray(getattr(cache, f))[:, [0, 2]])


def test_the_states_refusal_table_names_every_feature_with_a_reason():
    cfg = get_preset("brumby-tiny")
    names = set(kv_cache._STATE_REFUSALS)
    assert names == set(kv_cache._RING_REFUSALS) | {"paged_pages"}
    assert names - {"prefix_cache", "paged_pages"} == set(
        kv_cache._LATENT_REFUSALS)
    for name, why in kv_cache._STATE_REFUSALS.items():
        with pytest.raises(ValueError) as e:
            kv_cache.refuse_unpaged_state(cfg, **{name: True})
        assert str(e.value).startswith(f"{name} is not supported")
        assert why in str(e.value)
    # nothing asked for, nothing refused: served WITHOUT a pool
    kv_cache.refuse_unpaged_state(
        cfg, paged_pages=None, prefix_cache=False, kv_bits=False,
        host_pages=0, speculative=False, prefill_chunk=None,
        token_budget=None, mesh=False)


# -- a state a slot BESIDE the pool: Mamba-2 beside attention (PR 55) --------

def test_a_state_space_models_pool_holds_pages_and_a_state_a_slot():
    """``slot_state`` sizes the leaves: [ssm layers, rows, R, N, 128] float32
    whatever the activations' dtype, and the convolution's last K - 1 inputs
    in that dtype; the pool counts the attention layers alone."""
    from distributed_llms_tpu.ops import ssm

    cfg = dataclasses.replace(get_preset("nemotron3-super-tiny"),
                              dtype="bfloat16")
    state = kv_cache.slot_state(cfg, 3, jnp.bfloat16)
    assert set(state) == {"ssm_h", "ssm_conv"}
    assert state["ssm_h"].shape == (3, 3, 8, 128, 128)
    assert state["ssm_h"].dtype == jnp.float32
    assert state["ssm_conv"].shape == (3, 3, 3, 1536)
    assert state["ssm_conv"].dtype == jnp.bfloat16
    pool = kv_cache.make_pool(cfg, PAGES, BLK, slots=3)
    assert isinstance(pool, kv_cache.HybridCache)
    assert pool.k.shape[:2] == pool.v.shape[:2] == (1, PAGES)
    assert pool.conv is None and pool.ret_s is None
    assert kv_cache.format_bytes(pool, cfg) == {
        "ssm_state": 3.0 * 3 * (ssm.state_bytes(16, 64, 128) + 3 * 1536 * 2)}
    assert kv_cache.pages_are_private(cfg)
    assert {"ssm_h", "ssm_conv"} <= set(kv_cache._SLOT_FIELDS)


def test_write_row_puts_a_rows_pages_in_the_pool_and_its_state_in_its_slot():
    cfg = get_preset("nemotron3-super-tiny")
    keys = iter(jax.random.split(jax.random.key(4), 16))
    noise = lambda x: jax.random.normal(next(keys), x.shape).astype(x.dtype)
    pool = jax.tree.map(noise, kv_cache.make_pool(cfg, 9, 8, slots=3))
    row = jax.tree.map(noise, kv_cache.init_cache(cfg, 1, 16))
    out = kv_cache.write_row(pool, jnp.asarray([4, 7], jnp.int32), row,
                             jnp.int32(2))
    for f in ("ssm_h", "ssm_conv"):
        got = np.asarray(getattr(out, f))
        np.testing.assert_array_equal(got[:, 2], np.asarray(
            getattr(row, f))[:, 0])
        np.testing.assert_array_equal(
            got[:, :2], np.asarray(getattr(pool, f))[:, :2])
    got = np.asarray(out.k).reshape(1, 9, 8, -1)
    np.testing.assert_array_equal(
        got[0, [4, 7]].reshape(16, -1), np.asarray(row.k)[0, 0].reshape(16, -1))
    np.testing.assert_array_equal(
        got[0, [0, 1, 2, 3, 5, 6, 8]],
        np.asarray(pool.k).reshape(1, 9, 8, -1)[0, [0, 1, 2, 3, 5, 6, 8]])


def test_the_state_beside_a_pool_refuses_all_but_the_pool():
    cfg = get_preset("nemotron3-super-tiny")
    for name, why in kv_cache._STATE_REFUSALS.items():
        if name == "paged_pages":  # the pool IS what serves it
            kv_cache.refuse_unpaged_state(cfg, paged_pages=40)
            continue
        with pytest.raises(ValueError) as e:
            kv_cache.refuse_unpaged_state(cfg, paged_pages=40, **{name: True})
        assert str(e.value).startswith(f"{name} is not supported")
        assert "recurrent state beside their pages" in str(e.value)
        assert why in str(e.value)
    with pytest.raises(ValueError, match="pass paged_pages"):
        kv_cache.refuse_unpaged_state(cfg, paged_pages=None)
    kv_cache.refuse_unpaged_state(
        cfg, paged_pages=40, prefix_cache=False, kv_bits=False,
        host_pages=0, speculative=False, prefill_chunk=None,
        token_budget=None, mesh=False)
