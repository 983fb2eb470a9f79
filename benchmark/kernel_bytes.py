"""The bytes a kernel has to move, from shapes: the roofline's numerator.

Kept with the benchmark so that no later PR can change what a kernel's
roofline share is measured against.
"""

from __future__ import annotations

QUANT_BLOCK = 128     # absmax block along K (ops/quant_matmul.py)
SCALE_BYTES = 4       # float32 scales


def quant_matmul_bytes_per_pass(config: dict) -> float:
    """Bytes of int8 block weights and their scales that one forward pass
    through the whole stack streams from HBM, whatever the number of rows:
    every ``[K, N]`` of the configuration's ``matmuls_per_layer``, times the
    layers.  Activations (rows x (K + N) x 2 bytes a matmul: under 1% of
    this at 16 rows, a few percent in a 1,500-token prefill) are left out,
    so the share reported is a little low, never high."""
    per_layer = sum(
        k * n * (1 + SCALE_BYTES / QUANT_BLOCK)
        for k, n in config["matmuls_per_layer"]
    )
    return per_layer * config["num_hidden_layers"]


def quant_matmul_weights(config: dict) -> float:
    """Block matmul weights of the whole stack (each costs one multiply and
    one add a token)."""
    return config["num_hidden_layers"] * sum(
        k * n for k, n in config["matmuls_per_layer"])
