"""The KV page pool is the layer scan's carry, written where it lies.

Paged serving keeps every layer's pages in one stack [L, NB, BLK, KVH, HD]
(models.model.run_blocks, ops/decode_attn.paged_decode_attention).  Handed
to the scan layer by layer it is copied whole four times a decode step on
the TPU; these tests hold the program's STRUCTURE (the pool leaves are
carries, never scanned inputs or stacked outputs) and the write's reach (a
layer's K/V lands in that layer, at the rows' (page, off), nowhere else),
for the bf16 and the int8 pool.  What the compiler makes of it for the
v5e is tests/runtime/test_aot_pool.py's.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_llms_tpu.models import kv_cache, model as model_lib, presets
from distributed_llms_tpu.runtime import batcher as batcher_lib

LAYERS, PAGES, BLK, SLOTS, STEPS = 3, 7, 16, 2, 2


@pytest.fixture(scope="module")
def tiny():
    cfg = presets.get_preset("llama-tiny", vocab_size=512, num_layers=LAYERS)
    return cfg, model_lib.init_params(jax.random.key(0), cfg)


def _noise_pool(cfg, kv_bits):
    """A pool full of noise: a write that strays shows wherever it lands."""
    pool = kv_cache.make_pool(cfg, PAGES, BLK, kv_bits=kv_bits)
    keys = iter(jax.random.split(jax.random.key(3), 4))

    def noise(x):
        r = jax.random.normal(next(keys), x.shape) * 20.0
        return (jnp.abs(r) + 0.5 if x.dtype == jnp.float32 and kv_bits == 8
                else r).astype(x.dtype)

    return jax.tree.map(noise, pool)


def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


@pytest.mark.parametrize("kv_bits", [16, 8])
def test_pool_leaves_are_carries_of_the_layer_scan(tiny, kv_bits):
    """In paged ``decode_chunk`` the pool's leaves ride the layer scan as
    carries: no constant, scanned input or stacked output of it (nor of
    the step scan around it) has a pool leaf's shape."""
    cfg, params = tiny
    pool = kv_cache.make_pool(cfg, PAGES, BLK, kv_bits=kv_bits)
    shapes = {x.shape for x in jax.tree.leaves(pool)}
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda pool: batcher_lib.decode_chunk(
            params, cfg, pool, i32(SLOTS), i32(SLOTS),
            jnp.zeros((SLOTS, 1), bool), jnp.ones((SLOTS,), bool),
            i32(SLOTS) + 9, jax.random.key(0), STEPS,
            tables=i32(SLOTS, 64 // BLK),
        )
    )(pool)
    scans = {e.params["length"]: e for e in _scans(jaxpr.jaxpr)}
    assert set(scans) == {STEPS, LAYERS}  # the step scan, the layer scan
    for eqn in scans.values():
        nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
        consts, carry, xs = (eqn.invars[:nc], eqn.invars[nc:nc + nk],
                             eqn.invars[nc + nk:])
        ys = eqn.outvars[nk:]
        pooled = lambda vs: [v.aval.shape for v in vs if v.aval.shape in shapes]
        assert len(pooled(carry)) == len(jax.tree.leaves(pool))
        assert pooled(eqn.outvars[:nk]) == pooled(carry)
        assert not pooled(consts) and not pooled(xs) and not pooled(ys)
        # Nor is a layer handed over as a slice of its own.
        sliced = {s[1:] for s in shapes}
        assert not [v for v in (*xs, *ys) if v.aval.shape[1:] in sliced]


@pytest.mark.parametrize("kv_bits", [16, 8])
def test_a_layers_write_lands_in_that_layer_only(tiny, kv_bits):
    """One paged forward step changes, in EVERY layer of every pool leaf,
    exactly the rows' (page, off) entries — and nothing else anywhere."""
    cfg, params = tiny
    before = _noise_pool(cfg, kv_bits)
    tables = jnp.asarray([[2, 5, 0, 0], [4, 1, 6, 0]], jnp.int32)
    lens = jnp.asarray([17, 35], jnp.int32)  # -> (page 5, off 1), (6, 3)
    _, after = model_lib.forward(
        params, cfg, jnp.asarray([[7], [9]], jnp.int32),
        positions=lens[:, None], cache=before, cache_index=lens,
        kv_tables=tables,
    )
    want = np.zeros((LAYERS, PAGES, BLK), bool)
    want[:, 5, 1] = want[:, 6, 3] = True
    for old, new in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        assert new.shape == old.shape and new.dtype == old.dtype
        changed = np.asarray(old != new).reshape(LAYERS, PAGES, BLK, -1)
        np.testing.assert_array_equal(changed.any(-1), want)
