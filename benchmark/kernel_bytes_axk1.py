"""The bytes and operations that A.X-K1's two own kernels have to move and
do, from the configuration's shapes: the numerators of
``mla_attn_roofline`` and ``held_experts_roofline``, and the sizes that
tests/benchmark/test_axk1_metrics.py holds to the configuration's numbers
written out.

Kept with the benchmark, and apart from ``kernel_bytes.py`` and
``kernel_bytes_moe.py``, so that no later PR can change what a kernel's
roofline share is measured against.
"""

from __future__ import annotations

QUANT_BLOCK = 128     # absmax block of an int8 weight
SCALE_BYTES = 4       # float32 scales
BF16 = 2


def latent_values(config: dict) -> int:
    """Values of a token's cached row a layer: the latent and the one
    rotated key every head shares (576)."""
    return config["kv_lora_rank"] + config["qk_rope_head_dim"]


def latent_bytes_per_token_layer(config: dict) -> int:
    """Bytes the decode kernel has to read for one resident token in one
    layer: the row's values in bf16 (1,152).  The pool stores the row in
    whole 128-lane rows (1,280), as the device would tile 576 lanes
    anyway; the pad is not counted, so the share reads low, never high."""
    return latent_values(config) * BF16


def latent_ops_per_token_layer(config: dict) -> int:
    """Multiplies and adds of one resident token in one layer in the
    absorbed form: every head's score over the row's 576 values and its
    weighted sum over the 512 of the latent (139,264 at 64 heads)."""
    return 2 * config["num_attention_heads"] * (
        latent_values(config) + config["kv_lora_rank"])


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def expert_weights(config: dict) -> int:
    """Weights of ONE expert, routed or shared: gate, up and down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def int8_bytes(weights: float) -> float:
    """Bytes of int8 block weights as a kernel streams them: the data and
    one float32 scale a block of 128."""
    return weights * (1 + SCALE_BYTES / QUANT_BLOCK)


def expert_bytes(config: dict) -> float:
    return int8_bytes(expert_weights(config))


def held_experts_bytes(config: dict) -> float:
    """Every held routed expert of every expert layer: what a pass streams
    when each has at least one token (``n_routed_experts`` is the number
    HELD; the router's outputs are ``router_outputs``)."""
    return (expert_layers(config) * config["n_routed_experts"]
            * expert_bytes(config))


def held_flops(config: dict, held_pairs: float) -> float:
    """Multiplies and adds of ``held_pairs`` (token, expert) pairs that
    fell on a held expert, summed over the layers already."""
    return 2.0 * held_pairs * expert_weights(config)


def attention_weights_per_layer(config: dict) -> int:
    """W_qa, W_qb, W_kva, W_kvb and W_o of one layer."""
    c = config
    h = c["num_attention_heads"]
    return (c["hidden_size"] * c["q_lora_rank"]
            + c["q_lora_rank"] * h * (c["qk_nope_head_dim"]
                                      + c["qk_rope_head_dim"])
            + c["hidden_size"] * latent_values(c)
            + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
            + h * c["v_head_dim"] * c["hidden_size"])


def weight_bytes(config: dict, wkv_bf16: bool = True) -> float:
    """Bytes of the weights this chip holds: int8 block weights with their
    scales, the routers in float32, embedding and head in bf16 (norms, a few
    hundred KB, left out).  ``wkv_bf16``: W_kva and W_kvb as the program
    stores them (bfloat16); False gives the all-int8 reckoning of ISSUE 32
    (9.50 GB)."""
    c = config
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    wkv = (d * latent_values(c) + c["kv_lora_rank"] * c["num_attention_heads"]
           * (c["qk_nope_head_dim"] + c["v_head_dim"]))
    attn = attention_weights_per_layer(c) - wkv
    block = (layers * attn + 3 * d * c["intermediate_size"]
             * c["first_k_dense_replace"]
             + expert_layers(c) * (c["n_routed_experts"]
                                   + c["n_shared_experts"])
             * expert_weights(c))
    return (int8_bytes(block)
            + layers * (wkv * BF16 if wkv_bf16 else int8_bytes(wkv))
            + expert_layers(c) * d * c["router_outputs"] * 4
            + 2 * c["vocab_size"] * d * BF16)
