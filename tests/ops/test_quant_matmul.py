"""Interpret-mode parity tests for the fused dequant-matmul Pallas kernel.

The kernel (ops/quant_matmul.py) is the serving hot path for weight-only
quantized models; every quantized-serving test on the CPU backend otherwise
exercises only the dequantize+einsum fallback.  These tests run the kernel's
exact program via Pallas interpret mode and compare against the fallback,
covering the matrix the kernel special-cases: bits {8, 4}, k_lead {1, 2}
(qkv/mlp vs wo), weights with two output axes, M values that exercise the
padding path (decode-shaped M=1, odd M, multi-tile M), and the stacked form
the layer scans hand over: every layer's weight in one operand, read at an
index.

Reference's quantization design: /root/reference/snippets.md:675-833 (absmax
int8 + packed int4, dequantize-before-use); the fused kernel is the
TPU-native replacement for that dequantize step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.checkpoint.quantize import dequantize, quantize
from distributed_llms_tpu.ops import quant_matmul as qm


def _fallback(x, qt, eq):
    w = dequantize(qt, x.dtype)
    return jnp.einsum(eq, x, w)


def _make(shape, bits, pack_axis=-2, seed=0, **axes):
    w = jax.random.normal(jax.random.key(seed), shape, jnp.float32)
    return quantize(w, bits=bits, block=128, pack_axis=pack_axis, **axes)


@pytest.fixture
def kernel_calls(monkeypatch):
    """Count invocations of the Pallas kernel so parity tests prove the
    kernel path was actually taken (not fallback == fallback)."""
    calls = []
    orig = qm._quant_matmul_2d

    def spy(*args, **kwargs):
        calls.append(kwargs)
        return orig(*args, **kwargs)

    monkeypatch.setattr(qm, "_quant_matmul_2d", spy)
    return calls


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("m", [1, 7, 16])
def test_parity_2d_klead1(bits, m, kernel_calls):
    """Standard [K, N] weight (w_in/w_gate/w_up/w_down layout), including
    decode-shaped M=1 and odd M=7 (both need M padding to the 16-row tile)."""
    qt = _make((256, 256), bits, pack_axis=-2)
    x = jax.random.normal(jax.random.key(1), (m, 256), jnp.float32)
    got = qm.quant_contract(x, qt, 1, "mk,kn->mn", interpret=True)
    want = _fallback(x, qt, "mk,kn->mn")
    assert len(kernel_calls) == 1, "kernel path not taken (shapes untileable?)"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bits", [8, 4])
def test_parity_qkv_layout(bits, kernel_calls):
    """wq/wk/wv layout [D, H, hd]: stored as the matrix [H * hd, D] (int4
    pairs down its rows, the output columns); output restores the [H, hd]
    tail."""
    qt = _make((256, 2, 128), bits, n_axes=2)
    assert qt.data.shape == (256 // (1 if bits == 8 else 2), 256)
    assert qt.scale.shape == (2, 256)
    x = jax.random.normal(jax.random.key(2), (4, 9, 256), jnp.float32)
    got = qm.quant_contract(x, qt, 1, "btd,dhk->bthk", interpret=True)
    want = _fallback(x, qt, "btd,dhk->bthk")
    assert len(kernel_calls) == 1
    assert got.shape == (4, 9, 2, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bits", [8, 4])
def test_parity_wo_layout_klead2(bits, kernel_calls):
    """wo layout [H, hd, D] with k_lead=2: both leading axes contract, and
    flatten to the stored matrix's lanes; int4 pairs lie down its rows, D."""
    qt = _make((2, 128, 256), bits, k_axes=2)
    x = jax.random.normal(jax.random.key(3), (4, 9, 2, 128), jnp.float32)
    got = qm.quant_contract(x, qt, 2, "bthk,hkd->btd", interpret=True)
    want = _fallback(x, qt, "bthk,hkd->btd")
    assert len(kernel_calls) == 1
    assert got.shape == (4, 9, 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_parity_multitile(kernel_calls):
    """M, K, N all larger than one tile (grid > 1 on every axis) so the
    K-accumulator reset/flush logic is exercised across grid steps."""
    qt = _make((512, 384), 8, pack_axis=-2)
    x = jax.random.normal(jax.random.key(4), (300, 512), jnp.float32)
    got = qm.quant_contract(x, qt, 1, "mk,kn->mn", interpret=True)
    want = _fallback(x, qt, "mk,kn->mn")
    assert len(kernel_calls) == 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_parity_bf16_activations(kernel_calls):
    """Serving runs bf16 activations; kernel accumulates f32 like the
    fallback einsum, but tiled K order differs — tolerance is bf16-scale."""
    qt = _make((256, 256), 8, pack_axis=-2)
    x = jax.random.normal(jax.random.key(5), (8, 256), jnp.bfloat16)
    got = qm.quant_contract(x, qt, 1, "mk,kn->mn", interpret=True)
    want = _fallback(x, qt, "mk,kn->mn")
    assert len(kernel_calls) == 1
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=2e-2, atol=2e-2
    )


def test_untileable_falls_back(kernel_calls, dispatched):
    """K not divisible by any tile candidate → clean fallback, same answer,
    and the dispatch record says so."""
    qt = _make((100, 256), 8, pack_axis=-2)
    x = jax.random.normal(jax.random.key(6), (4, 100), jnp.float32)
    got = qm.quant_contract(x, qt, 1, "mk,kn->mn", interpret=True)
    want = _fallback(x, qt, "mk,kn->mn")
    assert len(kernel_calls) == 0
    assert dispatched() == {"quant_matmul.fallback": 1}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_int4_wrong_pack_axis_falls_back(kernel_calls):
    """int4 packed along the stored matrix's lanes cannot use the sublane
    unpack — must fall back rather than miscompute."""
    qt = _make((256, 256), 4, pack_axis=-1)  # pairs along K, the lanes
    x = jax.random.normal(jax.random.key(7), (4, 256), jnp.float32)
    got = qm.quant_contract(x, qt, 1, "mk,kn->mn", interpret=True)
    want = _fallback(x, qt, "mk,kn->mn")
    assert len(kernel_calls) == 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_env_interpret_mode(monkeypatch, kernel_calls, dispatched):
    """DLT_QUANT_MATMUL=interpret (the CI leg) routes through the kernel in
    interpret mode without the caller passing interpret=True."""
    monkeypatch.setenv("DLT_QUANT_MATMUL", "interpret")
    qt = _make((256, 256), 8, pack_axis=-2)
    x = jax.random.normal(jax.random.key(8), (4, 256), jnp.float32)
    got = qm.quant_contract(x, qt, 1, "mk,kn->mn")
    want = _fallback(x, qt, "mk,kn->mn")
    assert len(kernel_calls) == 1
    assert dispatched() == {"quant_matmul.interpret": 1,
                            "quant_matmul.k_minor": 1}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_env_fallback_mode(monkeypatch, kernel_calls, dispatched):
    """DLT_QUANT_MATMUL=fallback forces einsum even where tileable."""
    monkeypatch.setenv("DLT_QUANT_MATMUL", "fallback")
    qt = _make((256, 256), 8, pack_axis=-2)
    x = jax.random.normal(jax.random.key(9), (4, 256), jnp.float32)
    qm.quant_contract(x, qt, 1, "mk,kn->mn")
    assert len(kernel_calls) == 0
    assert dispatched() == {"quant_matmul.fallback": 1}


def _stack(layers, k_lead, bits, shape):
    """A stack of ``layers`` weights of ``shape`` quantized as one leaf."""
    axes = dict(k_axes=2) if k_lead == 2 else {}
    return _make((layers, *shape), bits, seed=11, **axes)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("k_lead,shape,x_tail,eq", [
    (1, (256, 384), (256,), "mk,kn->mn"),
    (2, (2, 128, 256), (2, 128), "mhk,hkd->md"),
])
@pytest.mark.parametrize("m", [1, 16, 300])
def test_stacked_parity(bits, k_lead, shape, x_tail, eq, m, kernel_calls,
                        dispatched):
    """The stack [L, N, K] goes into the kernel whole and ``at(layer)``
    names the layer, traced inside a lax.scan as a layer scan has it: each
    layer's result equals dequantize + einsum on that layer's slice, and
    the kernel is handed the stack itself, never a slice."""
    qt = _stack(3, k_lead, bits, shape)
    x = jax.random.normal(jax.random.key(12), (m, *x_tail), jnp.float32)

    def body(_, layer):
        return None, qm.quant_contract(x, qt.at(layer), k_lead, eq,
                                       interpret=True)

    _, got = jax.lax.scan(body, None, jnp.arange(3, dtype=jnp.int32))
    assert len(kernel_calls) == 1  # one trace of the scan body
    assert dispatched() == {"quant_matmul.interpret": 1,
                            "quant_matmul.k_minor": 1,
                            "quant_matmul.stacked": 1}
    want = jnp.einsum("l" + eq.replace(",", ",l").replace("->", "->l"),
                      jnp.broadcast_to(x, (3, *x.shape)), dequantize(qt))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    # ...and to the last bit what the kernel gives for the layer's slice.
    one = jax.tree.map(lambda a: a[2], qt)
    np.testing.assert_array_equal(
        np.asarray(got[2]),
        np.asarray(qm.quant_contract(x, one, k_lead, eq, interpret=True)))


@pytest.mark.parametrize("bits", [8, 4])
def test_stack_of_one(bits, kernel_calls, dispatched):
    """A weight that is no stack is a stack of one read at layer 0: the
    same kernel, and not counted as stacked."""
    qt = _make((1, 256, 256), bits, seed=13)
    x = jax.random.normal(jax.random.key(14), (4, 256), jnp.float32)
    got = qm.quant_contract(x, qt.at(0), 1, "mk,kn->mn", interpret=True)
    one = jax.tree.map(lambda a: a[0], qt)
    want = qm.quant_contract(x, one, 1, "mk,kn->mn", interpret=True)
    assert len(kernel_calls) == 2
    assert dispatched() == {"quant_matmul.interpret": 2,
                            "quant_matmul.k_minor": 2}
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_fallback(x, one, "mk,kn->mn")),
        rtol=2e-5, atol=2e-5)


def test_stacked_fallback_reads_the_layer(monkeypatch, dispatched):
    """DLT_QUANT_MATMUL=fallback on a stack read at an index dequantizes
    that layer's slice."""
    monkeypatch.setenv("DLT_QUANT_MATMUL", "fallback")
    qt = _make((3, 256, 256), 8, seed=15)
    x = jax.random.normal(jax.random.key(16), (4, 256), jnp.float32)
    got = qm.quant_contract(x, qt.at(jnp.int32(1)), 1, "mk,kn->mn")
    want = x @ dequantize(qt)[1]
    assert dispatched() == {"quant_matmul.fallback": 1}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_scales_are_stored_lane_dense():
    """The stored scales AND the stored weight have K on the last axis
    ([N/block, K] beside [N, K]): the layout the kernel's BlockSpecs read,
    a block's scales a row over its 128 weight rows, so no served program
    re-lays either out and the kernel turns nothing."""
    qt = _make((3, 512, 384), 8)
    assert qt.data.shape == (3, 384, 512) and qt.scale.shape == (3, 3, 512)
    w = jax.random.normal(jax.random.key(0), (3, 512, 384), jnp.float32)
    absmax = jnp.max(jnp.abs(w.reshape(3, 512, 3, 128)), axis=-1)
    np.testing.assert_allclose(
        np.asarray(qt.scale), np.asarray(jnp.swapaxes(absmax / 127.0, 1, 2)),
        rtol=1e-6)
    # the weight's values are the [K, N] matrix's, laid down turned
    scale_kn = jnp.repeat(jnp.swapaxes(qt.scale, 1, 2), 128, axis=2)
    np.testing.assert_array_equal(
        np.asarray(qt.data),
        np.asarray(jnp.swapaxes(jnp.round(w / scale_kn), 1, 2), np.int8))
    # int4: the same matrix, pairs down its rows
    assert _make((3, 512, 384), 4).data.shape == (3, 192, 512)


@pytest.mark.parametrize("bits,shape", [(4, (512, 384)), (4, (512, 768)),
                                        (4, (1024, 1536)), (8, (2048, 1536)),
                                        (8, (256, 640))])
def test_scale_rows_shared_by_tiles_or_past_the_end(bits, shape, kernel_calls):
    """A tile whose own scale rows do not fill 8 sublanes reads a block of 8
    that several j-tiles share (int4's tiles of 1, 2 and 4 blocks; int8
    [2048, 1536]: tiles of 4 blocks, 12 rows in blocks of 8, the second half
    past the end), and a block may be longer than the scales are (3 or 6
    rows): each tile still finds its own rows.  [256, 640] is one tile, its
    5 rows the whole of the scales."""
    qt = _make((2, *shape), bits, seed=17)
    x = jax.random.normal(jax.random.key(18), (5, shape[0]), jnp.float32)
    got = qm.quant_contract(x, qt.at(1), 1, "mk,kn->mn", interpret=True)
    want = x @ dequantize(qt)[1]
    assert len(kernel_calls) == 1
    bn, _, _ = kernel_calls[0]["tiles"]
    assert (bn // 128) == {384: 1, 768: 2, 1536: 4, 640: 5}[shape[1]]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def _parent_arithmetic(x, q_kn, scale, ck=512):
    """What the kernel of PRs 29-32 computed from the [K, N] matrix: every
    weight ``bf16(f32(q) * s)``, K summed in float32 in runs of ``ck``."""
    k, n = q_kn.shape
    nb = scale.shape[0]
    w = (q_kn.astype(jnp.float32).reshape(k, nb, n // nb)
         * scale.T[:, :, None]).reshape(k, n).astype(x.dtype)
    acc = jnp.zeros((x.shape[0], n), jnp.float32)
    for r in range(0, k, ck):
        acc += jnp.dot(x[:, r:r + ck], w[r:r + ck],
                       preferred_element_type=jnp.float32)
    return w, acc.astype(x.dtype)


# one block weight of each cell's configuration: qwen2-7b's wk, pythia-6.9b's
# wq, lfm2-8b-a1b's wk, ax-k1's wq_a
@pytest.mark.parametrize("k,n", [(3584, 512), (4096, 4096), (2048, 512),
                                 (7168, 1536)])
@pytest.mark.parametrize("m", [16, 256])
def test_kernel_against_the_parents_arithmetic(k, n, m, kernel_calls):
    """The lane-dense kernel on the turned leaf gives what the [K, N]
    kernel gave: rows of x that pick one k each hand back the dequantized
    weights themselves, equal bit for bit to ``bf16(f32(q) * s)``; for
    random rows the outputs are equal, or one bf16 ulp apart where the
    backend sums a run's partial products in another order."""
    w = jax.random.normal(jax.random.key(k + n), (k, n), jnp.float32)
    qt = quantize(w, bits=8, block=128)
    q_kn = jnp.swapaxes(qt.data, 0, 1)
    picks = jax.random.permutation(jax.random.key(m), k)[:m]
    x_pick = jax.nn.one_hot(picks, k, dtype=jnp.bfloat16)
    x_rand = jax.random.normal(jax.random.key(m + 1), (m, k), jnp.bfloat16)
    w_ref, _ = _parent_arithmetic(x_pick, q_kn, qt.scale)
    got_w = qm.quant_contract(x_pick, qt, 1, "mk,kn->mn", interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got_w, np.float32), np.asarray(w_ref[picks], np.float32))
    _, want = _parent_arithmetic(x_rand, q_kn, qt.scale)
    got = qm.quant_contract(x_rand, qt, 1, "mk,kn->mn", interpret=True)
    assert len(kernel_calls) == 2
    g = np.asarray(got).view(np.int16).astype(np.int32)
    r = np.asarray(want).view(np.int16).astype(np.int32)
    assert np.max(np.abs(g - r)) <= 1, "more than one bf16 ulp apart"


def test_k_minor_counts_the_kernels_traces(dispatched):
    """``ops.dispatch.quant_matmul.k_minor`` counts the traces that took
    the lane-dense leg: every kernel (or interpret) trace, no fallback."""
    qt = _make((2, 256, 256), 8, seed=19)
    x = jax.random.normal(jax.random.key(20), (4, 256), jnp.float32)
    qm.quant_contract(x, qt.at(0), 1, "mk,kn->mn", interpret=True)
    qm.quant_contract(x[:3], qt.at(1), 1, "mk,kn->mn", interpret=True)
    assert dispatched() == {"quant_matmul.interpret": 2,
                            "quant_matmul.k_minor": 2,
                            "quant_matmul.stacked": 2}
    bad = _make((100, 256), 8)  # untileable: the fallback counts nothing
    qm.quant_contract(x[:, :100], bad, 1, "mk,kn->mn", interpret=True)
    assert dispatched()["quant_matmul.k_minor"] == 2
    assert dispatched()["quant_matmul.fallback"] == 1


# -- the count of real rows (PR 39) ---------------------------------------
_SMALL_TILE = 128 * 512  # forces tiles of [128, 512]: a grid over N and K


@pytest.mark.parametrize("rows", [0, 1, 256, 257, -1, None],
                         ids=lambda r: f"rows{r}")
@pytest.mark.parametrize("m", [768, 1024, 2048])
@pytest.mark.parametrize("stack", ["index", "one", "index-tiled"])
@pytest.mark.parametrize("bits", [8, 4])
def test_row_tiles_of_padding_are_skipped(bits, stack, m, rows, monkeypatch,
                                          kernel_calls, counted_kernels):
    """A call of more than two row tiles that is told how many rows are real:
    every row of a tile that holds a real row equals, bit for bit, the call
    without a count, and the tiles past them are zeros, whether the kernel
    walks one weight tile or a grid of them ("tiled")."""
    rows = {-1: m - 1, None: m}.get(rows, rows)
    k, n = (1024, 256) if stack == "index-tiled" else (256, 256)
    if stack == "index-tiled":
        monkeypatch.setattr(qm, "_TILE_BYTES", _SMALL_TILE)
        assert qm._tiles(k, n, 128, bits)[:2] in ((128, 512), (256, 512))
    qt = _make((1 if stack == "one" else 3, k, n), bits, seed=21)
    qt = jax.tree.map(lambda a: a[0], qt) if stack == "one" else qt.at(
        jnp.int32(2))
    x = jax.random.normal(jax.random.key(22), (1, m, k), jnp.bfloat16)

    def run(count):
        return np.asarray(qm.quant_contract(
            x, qt, 1, "btk,kn->btn", interpret=True, rows=count
        )[0].astype(jnp.float32))

    want, got = run(None), run(jnp.array([rows], jnp.int32))
    live = -(-rows // 256) * 256
    np.testing.assert_array_equal(got[:live], want[:live])
    assert not got[live:].any()
    np.testing.assert_allclose(
        want, np.asarray(_fallback(x, qt, "btk,kn->btn")[0], np.float32),
        rtol=2e-2, atol=2e-1)
    assert len(kernel_calls) == 2
    # what the two calls trace to: a grid over every row tile, then one
    # that ends with the real rows
    f = lambda c: jax.make_jaxpr(lambda x_: qm.quant_contract(  # noqa: E731
        x_, qt, 1, "btk,kn->btn", interpret=True, rows=c))(x)
    assert counted_kernels(f(None)) == [False]
    assert counted_kernels(f(jnp.array([rows], jnp.int32))) == [True]


@pytest.mark.parametrize("m", [1, 16, 64, 256, 512])
@pytest.mark.parametrize("bits", [8, 4])
def test_one_row_tile_makes_the_parents_call(bits, m, counted_kernels):
    """A count handed to a call of one row tile is dropped, and of two (a
    bucket of 512 holds more than 256 tokens): what is traced is the call
    without it, to the letter (a decode step, a bucket of 512 or less)."""
    qt = _make((3, 256, 256), bits, seed=23).at(jnp.int32(1))
    x = jax.random.normal(jax.random.key(24), (1, m, 256), jnp.bfloat16)

    def traced(count):
        return jax.make_jaxpr(lambda x_: qm.quant_contract(
            x_, qt, 1, "btk,kn->btn", interpret=True, rows=count))(x)

    plain, counted = traced(None), traced(jnp.array([m // 2], jnp.int32))
    assert counted_kernels(plain) == counted_kernels(counted) == [False]
    assert str(plain) == str(counted)


def test_live_rows_is_the_kernels_rule():
    """The batcher's counter reckons what the kernel computes."""
    assert qm.live_rows(2048, 1100) == 1280
    assert qm.live_rows(2048, 0) == 0
    assert qm.live_rows(2048, 2048) == qm.live_rows(2048, 1793) == 2048
    assert qm.live_rows(256, 3) == 256 and qm.live_rows(64, 3) == 64
    assert qm.live_rows(512, 256) == qm.live_rows(512, 257) == 512
    assert qm.live_rows(768, 256) == 256 and qm.live_rows(768, 257) == 512
