"""The bytes and operations the expert kernel (``moe_experts``,
ops/moe_experts.py) has to move and do, from the configuration's shapes:
the numerators of ``moe_experts_roofline``.

Kept with the benchmark, and apart from ``kernel_bytes.py``, so that no
later PR can change what the kernel's roofline share is measured against.
"""

from __future__ import annotations

QUANT_BLOCK = 128     # absmax block along the contracted axis
SCALE_BYTES = 4       # float32 scales


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["num_dense_layers"]


def expert_weights(config: dict) -> int:
    """Weights of ONE expert: gate, up and down projections."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def expert_bytes(config: dict) -> float:
    """Bytes of one expert as the kernel streams it: int8 and its scales."""
    return expert_weights(config) * (1 + SCALE_BYTES / QUANT_BLOCK)


def all_experts_bytes(config: dict) -> float:
    """Every expert of every expert layer: what a pass streams when each
    expert has at least one token."""
    return expert_layers(config) * config["num_experts"] * expert_bytes(config)


def touched_bytes_per_pass(config: dict, touched_share: float) -> float:
    """Bytes one forward pass has to stream when ``touched_share`` of the
    experts have a token: an expert nobody chose is not read, one chosen is
    read at least once.  Activations are left out (a lower bound)."""
    return all_experts_bytes(config) * touched_share


def routed_flops(config: dict, tokens: float) -> float:
    """Multiplies and adds of ``tokens`` tokens through the expert layers:
    each goes through ``num_experts_per_tok`` experts in every expert
    layer, one multiply and one add a weight."""
    return (2.0 * tokens * config["num_experts_per_tok"]
            * expert_layers(config) * expert_weights(config))
