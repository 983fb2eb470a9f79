"""The layer scans hand each quantized matmul its whole stack and the
layer's index, not a slice.

On the TPU a slice of a stacked weight handed to the Pallas kernel is a copy
of the layer's weights in every step (tests/runtime/test_aot_pool.py holds
what the compiler makes of it).  Here, on the CPU with the kernel's program
under the Pallas interpreter: the logits through ``run_blocks`` (paged and
contiguous) and ``run_layers`` are, to the last bit, what the same scans
give when every leaf is sliced a layer as before PR 29, and the dispatch
record says every kernel trace got a stack (``quant_matmul.stacked``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.checkpoint.quantize import QuantizedTensor
from distributed_llms_tpu.core.config import ModelConfig
from distributed_llms_tpu.models import kv_cache, model as model_lib
from distributed_llms_tpu.runtime import batcher as batcher_lib

# Widths the kernel can tile: heads of 128 (a scale block a head), the FFN
# three pieces of 128.  qkv biases as Qwen2 has them.
DENSE = ModelConfig(
    family="llama", vocab_size=256, hidden_size=256, intermediate_size=384,
    num_layers=3, num_heads=2, num_kv_heads=1, head_dim=128, max_seq_len=128,
    tie_embeddings=False, dtype="float32", qkv_bias=True,
)
NEOX = ModelConfig(
    family="neox", vocab_size=256, hidden_size=256, intermediate_size=384,
    num_layers=2, num_heads=2, num_kv_heads=2, head_dim=128, max_seq_len=128,
    rotary_pct=0.25, parallel_residual=True, tie_embeddings=False,
    dtype="float32", activation="gelu_exact",
)
HYBRID = ModelConfig(
    family="hybrid", vocab_size=256, hidden_size=128, intermediate_size=256,
    moe_intermediate_size=128, num_layers=8, num_dense_layers=2, num_heads=2,
    num_kv_heads=2, head_dim=64, max_seq_len=128, rope_theta=1e6,
    tie_embeddings=True, qk_norm=True, dtype="float32", conv_kernel=3,
    layer_types=("conv", "conv", "attn", "conv", "conv", "conv", "attn",
                 "conv"),
    num_experts=4, num_experts_per_token=2, moe_score_fn="sigmoid",
    moe_expert_bias=True, moe_capacity=False,
)
PAGES, BLK = 7, 16


def _sliced_layer_of(blocks, layer, rows=None):
    """``layer_of`` as the scans had it before PR 29: every leaf sliced
    (and no count of real rows carried)."""
    del rows
    return jax.tree.map(lambda a: a[layer], blocks)


def _prefill_contiguous(params, cfg):
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 9)),
                         jnp.int32)
    kw = {}
    if cfg.family == "hybrid":
        kw["seq_lens"] = jnp.asarray([9, 6], jnp.int32)
    logits, cache = model_lib.forward(
        params, cfg, tokens, cache=kv_cache.init_cache(cfg, 2, 32),
        cache_index=jnp.int32(0), **kw)
    return logits, cache


def _decode_paged(params, cfg):
    pool = kv_cache.make_pool(cfg, PAGES, BLK, slots=2)
    lens = jnp.asarray([17, 35], jnp.int32)
    kw = {}
    if cfg.family == "hybrid":
        kw["seq_lens"] = jnp.asarray([1, 1], jnp.int32)
    logits, pool = model_lib.forward(
        params, cfg, jnp.asarray([[7], [9]], jnp.int32),
        positions=lens[:, None], cache=pool, cache_index=lens,
        kv_tables=jnp.asarray([[2, 5, 0, 0], [4, 1, 6, 0]], jnp.int32), **kw)
    return logits, pool


@pytest.mark.parametrize("run", [_prefill_contiguous, _decode_paged])
@pytest.mark.parametrize(
    "cfg,bits", [(DENSE, 8), (DENSE, 4), (NEOX, 8), (HYBRID, 8)],
    ids=["llama-int8", "llama-int4", "neox-int8", "hybrid-int8"])
def test_stack_and_index_give_the_sliced_leafs_logits(
        cfg, run, bits, monkeypatch, dispatched):
    monkeypatch.setenv("DLT_QUANT_MATMUL", "interpret")
    params = model_lib.init_params_quantized(jax.random.key(0), cfg, bits)
    stacks = [q for q in jax.tree.leaves(
        params["blocks"], is_leaf=lambda x: isinstance(x, QuantizedTensor))
        if isinstance(q, QuantizedTensor) and q.block_axis == -1]
    assert stacks and all(q.data.ndim == 3 for q in stacks)

    got, state = run(params, cfg)
    took = dispatched()
    # Every kernel trace was handed a stack and an index: no call site
    # slices a quantized leaf (a value below says one still does).
    assert took["quant_matmul.interpret"] == took["quant_matmul.stacked"] > 0
    assert "quant_matmul.fallback" not in took

    monkeypatch.setattr(model_lib, "layer_of", _sliced_layer_of)
    want, want_state = run(params, cfg)
    assert dispatched().get("quant_matmul.stacked") == took[
        "quant_matmul.stacked"]  # the sliced leaves went in as stacks of one
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(want_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fallback_reads_the_layer_out_of_the_stack(monkeypatch, dispatched):
    """DLT_QUANT_MATMUL=fallback (and a float weight) still take a layer's
    slice: the same logits as the kernel's, within the order of summation."""
    params = model_lib.init_params_quantized(jax.random.key(0), DENSE, 8)
    monkeypatch.setenv("DLT_QUANT_MATMUL", "interpret")
    got, _ = _prefill_contiguous(params, DENSE)
    monkeypatch.setenv("DLT_QUANT_MATMUL", "fallback")
    before = dispatched()
    want, _ = _prefill_contiguous(params, DENSE)
    after = dispatched()
    assert after["quant_matmul.fallback"] > 0
    assert after.get("quant_matmul.stacked") == before.get(
        "quant_matmul.stacked")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_layer_of_slices_everything_but_matrix_stacks():
    """Norms, biases, float weights and the capacity path's expert stacks
    [L, E, D, F] are sliced; a quantized [L, N, K] stays whole and carries
    the index."""
    params = model_lib.init_params_quantized(jax.random.key(0), DENSE, 8)
    p = model_lib.layer_of(params["blocks"], jnp.int32(1))
    wq = p["attn"]["wq"]
    assert wq.data.shape == (3, 256, 256) and int(wq.layer) == 1
    assert wq.tail_shape == ((256,), (2, 128))
    assert p["attn"]["wo"].tail_shape == ((2, 128), (256,))
    assert p["ln1"]["scale"].shape == (256,)
    assert p["attn"]["bq"].shape == (2, 128)
    experts = QuantizedTensor(
        data=jnp.zeros((3, 4, 8, 16), jnp.int8),
        scale=jnp.ones((3, 4, 1, 8), jnp.float32), bits=8,
        orig_shape=(3, 4, 8, 16))
    one = model_lib.layer_of({"w": experts}, 2)["w"]
    assert one.layer is None and one.data.shape == (4, 8, 16)
