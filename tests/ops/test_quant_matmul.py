"""Interpret-mode parity tests for the fused dequant-matmul Pallas kernel.

The kernel (ops/quant_matmul.py) is the serving hot path for weight-only
quantized models; every quantized-serving test on the CPU backend otherwise
exercises only the dequantize+einsum fallback.  These tests run the kernel's
exact program via Pallas interpret mode and compare against the fallback,
covering the matrix the kernel special-cases: bits {8, 4}, k_lead {1, 2}
(qkv/mlp vs wo), pack_axis {-2, -3}, and M values that exercise the padding
path (decode-shaped M=1, odd M, multi-tile M).

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


def _make(shape, bits, pack_axis, seed=0):
    w = jax.random.normal(jax.random.key(seed), shape, jnp.float32)
    return quantize(w, bits=bits, block=128, pack_axis=pack_axis)


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


@pytest.mark.parametrize("bits,pack_axis", [(8, -2), (4, -3)])
def test_parity_qkv_layout(bits, pack_axis, kernel_calls):
    """wq/wk/wv layout [D, H, hd]: reduction axis is axis 0, so int4 packs
    along -3; output restores the [H, hd] tail."""
    qt = _make((256, 2, 128), bits, pack_axis=pack_axis)
    x = jax.random.normal(jax.random.key(2), (4, 9, 256), jnp.float32)
    got = qm.quant_contract(x, qt, 1, "btd,dhk->bthk", interpret=True)
    want = _fallback(x, qt, "btd,dhk->bthk")
    assert len(kernel_calls) == 1
    assert got.shape == (4, 9, 2, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bits", [8, 4])
def test_parity_wo_layout_klead2(bits, kernel_calls):
    """wo layout [H, hd, D] with k_lead=2: both leading axes contract; int4
    packs along -2 (hd — the last K axis)."""
    qt = _make((2, 128, 256), bits, pack_axis=-2)
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
    """int4 packed along a non-K axis cannot use the sublane unpack — must
    fall back rather than miscompute."""
    qt = _make((256, 256), 4, pack_axis=-1)  # packed along N, not K
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
    assert dispatched() == {"quant_matmul.interpret": 1}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_env_fallback_mode(monkeypatch, kernel_calls, dispatched):
    """DLT_QUANT_MATMUL=fallback forces einsum even where tileable."""
    monkeypatch.setenv("DLT_QUANT_MATMUL", "fallback")
    qt = _make((256, 256), 8, pack_axis=-2)
    x = jax.random.normal(jax.random.key(9), (4, 256), jnp.float32)
    qm.quant_contract(x, qt, 1, "mk,kn->mn")
    assert len(kernel_calls) == 0
    assert dispatched() == {"quant_matmul.fallback": 1}
