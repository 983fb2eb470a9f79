"""The paged decode kernel at a head of 64 lanes (LFM2): two heads to a
128-lane pool row, the 128-wide kernel body, in interpret mode against the
dense path."""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.ops import decode_attn

L, KVH, D, H, B = 3, 4, 64, 16, 3


# (pages in the pool, page, page slots a row, lengths): tiny pages walked in
# one run, and pages of 64 walked 8 to a run over 11 slots — rows of length
# 1, on the first key of the second run, and filling every slot.
@pytest.fixture(scope="module", params=[(12, 8, 4, [5, 17, 32]),
                                        (40, 64, 11, [1, 513, 704])],
                ids=["one-run", "run-walk"])
def case(request):
    nb, blk, p, lengths = request.param
    rs = np.random.RandomState(0)
    kvh, d = decode_attn.pool_head_shape(KVH, D, fold_narrow=True)
    assert (kvh, d) == (2, 128)
    k = jnp.asarray(rs.randn(L, nb, blk, kvh, d), jnp.float32)
    v = jnp.asarray(rs.randn(L, nb, blk, kvh, d), jnp.float32)
    q = jnp.asarray(rs.randn(B, 1, H, D), jnp.float32)
    tables = jnp.asarray(rs.randint(1, nb, (B, p)), jnp.int32)
    return q, k, v, jnp.asarray(lengths, jnp.int32), tables


def dense(q, k, v, lengths, tables, layer):
    """Plain softmax attention over each row's gathered pages, the pool read
    as what it holds: [.., KVH, D] rows."""
    k, v = (x[layer][tables].reshape(B, -1, KVH, D) for x in (k, v))
    out = np.zeros((B, 1, H, D), np.float32)
    for b in range(B):
        n = int(lengths[b])
        for h in range(H):
            kk, vv = k[b, :n, h // (H // KVH)], v[b, :n, h // (H // KVH)]
            s = np.asarray(kk @ q[b, 0, h]) / np.sqrt(D)
            w = np.exp(s - s.max())
            out[b, 0, h] = (w / w.sum()) @ np.asarray(vv)
    return out


@pytest.mark.parametrize("mode", ["interpret", "fallback"])
def test_head_64_from_the_128_lane_kernel(case, monkeypatch, dispatched, mode):
    q, k, v, lengths, tables = case
    monkeypatch.setenv("DLT_RAGGED_DECODE", mode)
    out = decode_attn.paged_decode_attention(q, k, v, lengths, tables, layer=1)
    assert dispatched() == {f"paged_decode.{mode}": 1}
    np.testing.assert_allclose(
        np.asarray(out), dense(q, k, v, lengths, tables, 1), atol=2e-5)


def test_only_narrow_heads_fold():
    assert decode_attn.pool_head_shape(4, 128, fold_narrow=True) == (4, 128)
    assert decode_attn.pool_head_shape(8, 64, fold_narrow=False) == (8, 64)
    assert decode_attn.pool_head_shape(3, 64, fold_narrow=True) == (3, 64)
    assert decode_attn.pool_head_shape(8, 32, fold_narrow=True) == (2, 128)
