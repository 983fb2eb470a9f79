"""Weight-only quantized serving (SURVEY §7 hard part 6): block weights stay
int8/int4 in device memory and the blockwise dequant fuses into each layer's
matmuls at use — vs round 1 where the store could quantize but serving always
rehydrated to full dtype at load."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.checkpoint import quantize as quant_lib
from distributed_llms_tpu.checkpoint import store as store_lib
from distributed_llms_tpu.core.config import RuntimeConfig
from distributed_llms_tpu.models import model as model_lib, presets
from distributed_llms_tpu.runtime.engine import InferenceEngine


@pytest.mark.parametrize("name", ["llama-tiny", "gpt2-tiny"])
def test_quantized_blocks_forward_matches_dequantized(name):
    """Dequant-at-use == dequant-at-load: the weights bit for bit (same
    q*scale op), the logits to float32 rounding (a weight dequantized
    inside the program is turned there, [N, K] -> [K, N], and XLA may sum
    a contraction it fuses with that in another order)."""
    cfg = presets.get_preset(name)
    params = model_lib.init_params(jax.random.key(0), cfg)
    qblocks = quant_lib.quantize_tree(params["blocks"], bits=8, block=32)
    deq = {**params, "blocks": quant_lib.dequantize_tree(qblocks, jnp.dtype(cfg.dtype))}
    live = {**params, "blocks": qblocks}
    toks = jax.random.randint(jax.random.key(1), (2, 7), 0, cfg.vocab_size, dtype=jnp.int32)
    ref, _ = model_lib.forward(deq, cfg, toks)
    out, _ = model_lib.forward(live, cfg, toks)
    at_use = jax.jit(lambda b: quant_lib.dequantize_tree(
        b, jnp.dtype(cfg.dtype)))(qblocks)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), deq["blocks"], at_use)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("quantization", ["int8", "int4"])
def test_engine_serves_quantized_store(tmp_path, quantization):
    """serve_quantized=True keeps block weights quantized in memory and
    generates the same tokens as serving the dequantized store."""
    cfg = presets.get_preset("llama-tiny", vocab_size=512)
    params = model_lib.init_params(jax.random.key(0), cfg)
    store_lib.save_shards(
        params, str(tmp_path), num_shards=2, model_config=cfg,
        quantization=quantization, quant_block=32,
    )
    rt = RuntimeConfig(max_decode_steps=6)
    ref = InferenceEngine.from_store(str(tmp_path), rt=rt)
    eng = InferenceEngine.from_store(
        str(tmp_path), rt=RuntimeConfig(max_decode_steps=6, serve_quantized=True)
    )
    # Block weights really are resident quantized.
    qleaves = [
        x for x in jax.tree.leaves(
            eng.params["blocks"],
            is_leaf=lambda x: isinstance(x, quant_lib.QuantizedTensor),
        )
        if isinstance(x, quant_lib.QuantizedTensor)
    ]
    assert qleaves, "no QuantizedTensor leaves survived into the engine"
    assert quant_lib.tree_bytes(eng.params["blocks"]) < quant_lib.tree_bytes(
        ref.params["blocks"]
    )
    out_ref = ref.generate_text(["hello world", "hi"])
    out = eng.generate_text(["hello world", "hi"])
    assert out.text == out_ref.text


@pytest.mark.parametrize("quantization", ["int8", "int4"])
def test_sessions_over_quantized_weights(tmp_path, quantization):
    """Multi-turn sessions with quantized-resident block weights: both the
    first turn and a continuation must match the engine serving the same
    store dequantized (identical q*scale math, dequant-at-use vs at-load)."""
    cfg = presets.get_preset("llama-tiny", vocab_size=512)
    params = model_lib.init_params(jax.random.key(0), cfg)
    store_lib.save_shards(
        params, str(tmp_path), num_shards=1, model_config=cfg,
        quantization=quantization, quant_block=32,
    )
    rt_q = RuntimeConfig(max_decode_steps=5, serve_quantized=True, max_seq_len=64)
    rt_d = RuntimeConfig(max_decode_steps=5, max_seq_len=64)
    eng_q = InferenceEngine.from_store(str(tmp_path), rt=rt_q)
    eng_d = InferenceEngine.from_store(str(tmp_path), rt=rt_d)
    sid_q, first_q = eng_q.start_session(["hello world"])
    sid_d, first_d = eng_d.start_session(["hello world"])
    assert first_q.tokens.tolist() == first_d.tokens.tolist()
    more_q = eng_q.continue_session(sid_q, [" again"])
    more_d = eng_d.continue_session(sid_d, [" again"])
    assert more_q.tokens.tolist() == more_d.tokens.tolist()


def test_serve_quantized_requires_quantized_store(tmp_path):
    cfg = presets.get_preset("llama-tiny", vocab_size=512)
    params = model_lib.init_params(jax.random.key(0), cfg)
    store_lib.save_shards(params, str(tmp_path), num_shards=1, model_config=cfg)
    with pytest.raises(ValueError, match="serve_quantized"):
        InferenceEngine.from_store(
            str(tmp_path), rt=RuntimeConfig(serve_quantized=True)
        )


def test_dequantize_is_slice_safe():
    """dequantize must work on a per-layer slice of a stacked QuantizedTensor
    (what lax.scan hands the block body), not just the full [L, ...] tree."""
    x = jax.random.normal(jax.random.key(0), (4, 8, 16), jnp.float32)
    qt = quant_lib.quantize(x, bits=8, block=8)
    sliced = quant_lib.QuantizedTensor(
        data=qt.data[1], scale=qt.scale[1], bits=qt.bits, orig_shape=qt.orig_shape
    )
    full = quant_lib.dequantize(qt)
    np.testing.assert_array_equal(np.asarray(full[1]), np.asarray(quant_lib.dequantize(sliced)))
