"""The bytes and operations that Qwen3-Next's kernels have to move and do,
from the configuration's shapes under its own (the published) keys: the
numerators of ``gdn_decode_roofline``, ``gdn_admit_roofline`` and
``tiny_experts_roofline``, and the sizes that
tests/benchmark/test_qwen3next_metrics.py holds to ISSUE 59's numbers written
out.

A new file that imports the accepted ones and edits none.  Every numerator
reads the same work whatever implements it: a state is value heads x keys x
values float32 values however a kernel lays them out; an expert is its three
int8 matrices with their scales; the scan's operations are the chunked form's
at the configuration's chunk length, the triangle's counted as ONE forward
substitution of the chunk's right-hand sides, all as multiplies and adds at
the peak bf16 rate (a kernel that works in float32, or inverts the triangle
by products, reads low, never high).
"""

from __future__ import annotations

from benchmark import kernel_bytes_kexaone

F32 = 4
BF16 = 2


def layers(config: dict) -> int:
    """The layers this chip holds (12), every one with the expert layer."""
    return config["num_hidden_layers"]


def attn_layers(config: dict) -> int:
    """Layers 3, 7, 11, ...: one of every ``full_attention_interval`` (3)."""
    return layers(config) // config["full_attention_interval"]


def gdn_layers(config: dict) -> int:
    """The other three of every four (9)."""
    return layers(config) - attn_layers(config)


def key_width(config: dict) -> int:
    return config["linear_num_key_heads"] * config["linear_key_head_dim"]


def value_width(config: dict) -> int:
    return config["linear_num_value_heads"] * config["linear_value_head_dim"]


def state_bytes_row_layer(config: dict) -> int:
    """One row's state in one layer, float32: value heads x keys x values
    (32 x 128 x 128 x 4 = 2,097,152)."""
    return (config["linear_num_value_heads"] * config["linear_key_head_dim"]
            * config["linear_value_head_dim"] * F32)


def taps_bytes_row_layer(config: dict) -> int:
    """The convolution's last K - 1 inputs of one row in one layer, bf16,
    over [q | k | v] (3 x 8,192 x 2 = 49,152)."""
    return ((config["linear_conv_kernel_dim"] - 1)
            * (2 * key_width(config) + value_width(config)) * BF16)


def served_state_bytes(config: dict) -> int:
    """States and taps of every slot and delta-rule layer, whatever the rows
    hold (64 x 9 x 2,146,304 = 1,236,271,104: gauge
    batcher_gdn_state_bytes)."""
    return (config["serve"]["slots"] * gdn_layers(config)
            * (state_bytes_row_layer(config) + taps_bytes_row_layer(config)))


def page_bytes(config: dict) -> int:
    """One page of the pool: the attention layers' keys and values of
    ``page_size`` tokens in bf16 (3 x 2 x 256 x 2 x 2 x 64 = 393,216)."""
    return (attn_layers(config) * config["serve"]["page_size"]
            * kernel_bytes_kexaone.kv_bytes_per_token_layer(config))


def expert_weights(config: dict) -> int:
    """Weights of ONE routed expert: gate, up and down (3 x 2,048 x 512 =
    3,145,728)."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def held_expert_weights(config: dict) -> int:
    """Every held routed expert of every layer (``num_experts`` is the number
    HELD; the router's outputs are ``router_outputs``): 12 x 128 x 3,145,728
    = 4,831,838,208."""
    return layers(config) * config["num_experts"] * expert_weights(config)


def held_experts_bytes(config: dict) -> float:
    """What a pass streams when every held expert has a token: the int8
    tiles of the three matrices and their scales."""
    return kernel_bytes_kexaone.int8_bytes(held_expert_weights(config))


def held_flops(config: dict, held_pairs: float) -> float:
    """Multiplies and adds of ``held_pairs`` (token, expert) pairs that fell
    on a held expert, summed over the layers already."""
    return 2.0 * held_pairs * expert_weights(config)


def quant_matmul_weights(config: dict) -> int:
    """The int8 weights ``_quant_matmul_2d`` streams a pass: every delta-rule
    layer's ``W_qkvz`` and ``W_out`` (33,554,432), every attention layer's
    four, ``W_q`` twice as wide for the gate (27,262,976), every layer's
    shared expert (3,145,728): 421,527,552."""
    c = config
    d = c["hidden_size"]
    gdn = d * (2 * key_width(c) + 2 * value_width(c)) + value_width(c) * d
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    attn = d * (2 * q + 2 * kv) + q * d
    shared = 3 * d * c["shared_expert_intermediate_size"]
    return (gdn_layers(c) * gdn + attn_layers(c) * attn
            + layers(c) * shared)


def weight_bytes(config: dict) -> float:
    """Bytes of the weights this chip holds: int8 block weights with their
    scales, the routers in float32, embedding and head in bf16 (norms, taps,
    ``W_ba`` and a head's scalars, a few MB, left out): 5.78 GB."""
    c = config
    return (kernel_bytes_kexaone.int8_bytes(
                quant_matmul_weights(c) + held_expert_weights(c))
            + layers(c) * c["hidden_size"] * c["router_outputs"] * F32
            + 2 * c["vocab_size"] * c["hidden_size"] * BF16)


def rows_a_step(ctx: dict):
    """Rows that took a recurrence step, a decode step dispatched: the
    counters of the traced part where the run has them (counted INSIDE the
    trace), the whole window's otherwise.  A ratio of two host counters of
    one window, so no device time enters it.  None where one is missing."""
    slots = ctx["config"]["serve"]["slots"]
    for counters in (ctx.get("trace_counters") or {}, ctx["counters"]):
        slot_steps = counters.get("batcher_decode_slot_steps", 0.0)
        row_steps = counters.get("gdn_decode_row_steps", 0.0)
        if slot_steps and row_steps:
            return row_steps / (slot_steps / slots)
    return None


def decode_least_s(ctx: dict):
    """The least time ``gdn_decode`` can take in the decode programs that lie
    WHOLE inside the trace: their steps x :func:`rows_a_step` x the
    delta-rule layers x the state read and written once, over peak HBM
    bandwidth (set it against the kernel's seconds inside those same
    programs).  The taps are moved by the layer's convolution, not by the
    kernel, and are left out: a little low, never high.  None where
    something is missing."""
    t, peaks, config = ctx["trace"], ctx["peaks"], ctx["config"]
    rows = rows_a_step(ctx)
    if not t or not peaks or not rows or not t.get("decode"):
        return None
    steps = config["serve"]["chunk_steps"] * t["decode"]["count"]
    return (steps * rows * gdn_layers(config) * 2
            * state_bytes_row_layer(config) / peaks["hbm_bytes_per_s"])


def admit_ops(config: dict, tokens: float) -> float:
    """Multiplies and adds of the chunked scan for ``tokens`` real tokens of
    one row, every delta-rule layer.  A token and a KEY head: the causal half
    of its chunk's pairs for ``K K^T`` and for ``Q K^T`` (2 dk each).  A
    token and a VALUE head: the triangle, ONE forward substitution of the
    chunk's right-hand sides ``[K o gamma | V]`` (the causal half, dk + dv);
    ``W S``, ``Q S`` and the state's update (dk dv each); the chunk's
    ``tril(Q K^T o G) V'`` (the causal half, dv)."""
    c = config
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    half = (c["gdn_chunk_size"] + 1) / 2
    return gdn_layers(c) * tokens * 2 * (
        c["linear_num_key_heads"] * 2 * half * dk
        + c["linear_num_value_heads"] * (
            half * (dk + dv) + 3 * dk * dv + half * dv))


def admit_bytes(config: dict, tokens: float) -> float:
    """Bytes the scan reads and writes for ``tokens`` tokens, every
    delta-rule layer: q, k and v in and o out (bf16), g and beta in
    (float32)."""
    c = config
    return gdn_layers(c) * tokens * (
        2 * (key_width(c) + value_width(c)) * BF16
        + 2 * c["linear_num_value_heads"] * F32)


def admit_least_s(ctx: dict):
    """The least time ``gdn_prefill`` can take for the admissions the trace
    pairs with their ``batcher.admit.row`` span, an admission at a time the
    larger of its bytes over peak HBM bandwidth and its operations over the
    peak bf16 rate (set it against ``trace_reduce.inside_s``).  None where
    something is missing."""
    t, peaks, config = ctx["trace"], ctx["peaks"], ctx["config"]
    if not t or not peaks or not t.get("admissions"):
        return None
    return sum(max(
        admit_bytes(config, a["tokens"]) / peaks["hbm_bytes_per_s"],
        admit_ops(config, a["tokens"]) / peaks["bf16_flops_per_s"])
        for a in t["admissions"])
