"""The bytes and operations that K-EXAONE's three own kernels have to move
and do, from the configuration's shapes: the numerators of
``full_attn_roofline``, ``swa_attn_roofline`` and ``ep8_experts_roofline``,
and the sizes that tests/benchmark/test_kexaone_metrics.py holds to the
configuration's numbers written out.

Kept with the benchmark, and apart from ``kernel_bytes.py``,
``kernel_bytes_moe.py`` and ``kernel_bytes_axk1.py``, so that no later PR
can change what a kernel's roofline share is measured against.  (The
expert reckoning is ``kernel_bytes_axk1``'s under this configuration's
keys: ``num_experts`` is the number HELD where A.X-K1's file says
``n_routed_experts``.)
"""

from __future__ import annotations

QUANT_BLOCK = 128     # absmax block of an int8 weight
SCALE_BYTES = 4       # float32 scales
BF16 = 2


def kv_bytes_per_token_layer(config: dict) -> int:
    """Bytes a decode kernel has to read for one token it attends to in
    one layer: the keys and the values of every key/value head in bf16
    (8 x 128 x 2 x 2 = 4,096)."""
    return config["num_key_value_heads"] * config["head_dim"] * 2 * BF16


def held_layer_types(config: dict) -> list:
    """``layer_types`` of the layers this chip holds: the file keeps the
    published list whole, and stage 0's ``num_hidden_layers`` layers are
    its first entries."""
    return config["layer_types"][: config["num_hidden_layers"]]


def full_layers(config: dict) -> int:
    """Layers that attend the whole prefix: their keys and values are
    paged (3 of the 12 held)."""
    return held_layer_types(config).count("full_attention")


def window_layers(config: dict) -> int:
    """Layers that attend the last ``sliding_window`` positions: their
    keys and values lie in a ring a row (9 of the 12 held)."""
    return held_layer_types(config).count("sliding_attention")


def pool_bytes_per_token(config: dict) -> int:
    """What a resident token costs the page pool (12,288)."""
    return full_layers(config) * kv_bytes_per_token_layer(config)


def ring_bytes(config: dict) -> int:
    """The windowed layers' rings of every batch slot, whatever the rows
    hold (9 x 64 x 128 x 4,096 = 301,989,888)."""
    return (window_layers(config) * config["serve"]["slots"]
            * config["sliding_window"] * kv_bytes_per_token_layer(config))


def attn_ops_per_token_layer(config: dict) -> int:
    """Multiplies and adds of one attended token in one layer: every query
    head's score and weighted sum over its 128 dims (32,768 at 64 heads:
    8 a byte, far under the chip's 240, so the kernels' least time is
    their bytes')."""
    return 2 * config["num_attention_heads"] * 2 * config["head_dim"]


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
    when each has at least one token (``num_experts`` is the number HELD;
    the router's outputs are ``router_outputs``)."""
    return (expert_layers(config) * config["num_experts"]
            * expert_bytes(config))


def held_flops(config: dict, held_pairs: float) -> float:
    """Multiplies and adds of ``held_pairs`` (token, expert) pairs that
    fell on a held expert, summed over the layers already."""
    return 2.0 * held_pairs * expert_weights(config)


def attention_weights_per_layer(config: dict) -> int:
    """W_q, W_k, W_v and W_o of one layer (113,246,208)."""
    c = config
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return c["hidden_size"] * (q + 2 * kv) + q * c["hidden_size"]


def quant_matmul_weights(config: dict) -> int:
    """The int8 weights ``_quant_matmul_2d`` streams a pass: every layer's
    attention, the dense layer and the shared experts (2,113,929,216)."""
    c = config
    return (c["num_hidden_layers"] * attention_weights_per_layer(c)
            + 3 * c["hidden_size"] * c["intermediate_size"]
            * c["first_k_dense_replace"]
            + expert_layers(c) * c["num_shared_experts"] * expert_weights(c))


def weight_bytes(config: dict) -> float:
    """Bytes of the weights this chip holds: int8 block weights with their
    scales, the routers in float32, embedding and head in bf16 (norms, a
    few hundred KB, left out): 9.54 GB."""
    c = config
    block = quant_matmul_weights(c) + (
        expert_layers(c) * c["num_experts"] * expert_weights(c))
    return (int8_bytes(block)
            + expert_layers(c) * c["hidden_size"] * c["router_outputs"] * 4
            + 2 * c["vocab_size"] * c["hidden_size"] * BF16)


def decode_attn_least_s(ctx: dict, counter: str, layers: int) -> float | None:
    """The least time the traced decode steps' attention kernel of
    ``layers`` layers can take: the tokens it attends to a step (the
    WHOLE-WINDOW counter ``counter`` over the steps dispatched,
    ``batcher.decode.slot_steps`` / slots: steady over a window, where a
    count from the 6-s counter window would not be the trace's) x the
    traced ``jit_decode_chunk`` programs x ``chunk_steps`` x the layers x
    a token's bytes, over peak HBM bandwidth.  None where a counter is
    missing.  Admissions do not run the decode kernels."""
    t, peaks, c, config = (ctx["trace"], ctx["peaks"], ctx["counters"],
                           ctx["config"])
    slot_steps = c.get("batcher_decode_slot_steps", 0.0)
    tokens = c.get(counter, 0.0)
    if not t or not peaks or not slot_steps or not tokens:
        return None
    per_step = tokens / (slot_steps / config["serve"]["slots"])
    steps = config["serve"]["chunk_steps"] * sum(
        n for name, n in t["module_count"].items()
        if name.startswith("jit_decode_chunk"))
    return (steps * per_step * layers * kv_bytes_per_token_layer(config)
            / peaks["hbm_bytes_per_s"])
