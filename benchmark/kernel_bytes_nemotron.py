"""The bytes and operations that Nemotron-H's kernels have to move and do,
from the configuration's shapes under its own (the published) keys: the
numerators of ``ssm_decode_roofline``, ``ssm_admit_roofline`` and
``latent_experts_roofline``, and the sizes that
tests/benchmark/test_nemotron_metrics.py holds to ISSUE 55's numbers written
out.

A new file that imports the accepted ones and edits none.  Every numerator
reads the same work whatever implements it: a state is heads x head size x
state size float32 values however a kernel lays them out; an expert is its
two int8 matrices with their scales; the scan's operations are the chunked
form's at the configuration's chunk length, counted as multiplies and adds
at the peak bf16 rate (a kernel that works in float32 reads low, never
high).
"""

from __future__ import annotations

from benchmark import kernel_bytes_kexaone

F32 = 4
BF16 = 2


def held_pattern(config: dict) -> str:
    """The sub-layers this chip holds: the file keeps the published
    ``hybrid_override_pattern`` whole, and the stage's ``num_hidden_layers``
    sub-layers are its first characters (``MEMEMEM*EMEMEMEM*EMEME``)."""
    return config["hybrid_override_pattern"][: config["num_hidden_layers"]]


def ssm_layers(config: dict) -> int:
    return held_pattern(config).count("M")


def attn_layers(config: dict) -> int:
    return held_pattern(config).count("*")


def expert_layers(config: dict) -> int:
    return held_pattern(config).count("E")


def inner(config: dict) -> int:
    """Mamba-2's d_inner (8,192)."""
    return config["mamba_num_heads"] * config["mamba_head_dim"]


def state_bytes_row_layer(config: dict) -> int:
    """One row's state in one layer, float32: heads x head size x state
    size (128 x 64 x 128 x 4 = 4,194,304)."""
    return inner(config) * config["ssm_state_size"] * F32


def taps_bytes_row_layer(config: dict) -> int:
    """The convolution's last K - 1 inputs of one row in one layer, bf16,
    over [x | B | C] (3 x 10,240 x 2 = 61,440)."""
    width = inner(config) + 2 * config["n_groups"] * config["ssm_state_size"]
    return (config["conv_kernel"] - 1) * width * BF16


def served_state_bytes(config: dict) -> int:
    """States and taps of every slot and state-space layer, whatever the
    rows hold (64 x 10 x 4,255,744 = 2,723,676,160: gauge
    batcher_ssm_state_bytes)."""
    return (config["serve"]["slots"] * ssm_layers(config)
            * (state_bytes_row_layer(config) + taps_bytes_row_layer(config)))


def page_bytes(config: dict) -> int:
    """One page of the pool: the attention layers' keys and values of
    ``page_size`` tokens in bf16 (2 x 2 x 128 x 2 x 2 x 64 = 131,072)."""
    return (attn_layers(config) * config["serve"]["page_size"]
            * kernel_bytes_kexaone.kv_bytes_per_token_layer(config))


def expert_weights(config: dict) -> int:
    """Weights of ONE routed expert: two matrices on the latent, no gate
    (2 x 1,024 x 2,688 = 5,505,024)."""
    return 2 * config["moe_latent_size"] * config["moe_intermediate_size"]


def held_expert_weights(config: dict) -> int:
    """Every held routed expert of every expert layer (``n_routed_experts``
    is the number HELD; the router's outputs are ``router_outputs``):
    10 x 128 x 5,505,024 = 7,046,430,720."""
    return (expert_layers(config) * config["n_routed_experts"]
            * expert_weights(config))


def held_experts_bytes(config: dict) -> float:
    """What a pass streams when every held expert has a token: the int8
    tiles of ``U`` and ``V`` and their scales."""
    return kernel_bytes_kexaone.int8_bytes(held_expert_weights(config))


def held_flops(config: dict, held_pairs: float) -> float:
    """Multiplies and adds of ``held_pairs`` (token, expert) pairs that
    fell on a held expert, summed over the layers already."""
    return 2.0 * held_pairs * expert_weights(config)


def quant_matmul_weights(config: dict) -> int:
    """The int8 weights ``_quant_matmul_2d`` streams a pass: every Mamba-2
    layer's ``W_in`` and ``W_out`` (109,576,192), every attention layer's
    four (35,651,584), every expert layer's shared expert and the latent's
    two projections (52,428,800): 1,691,353,088."""
    c = config
    d, n = c["hidden_size"], c["ssm_state_size"]
    mamba = d * (2 * inner(c) + 2 * c["n_groups"] * n
                 + c["mamba_num_heads"]) + inner(c) * d
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    attn = d * (q + 2 * kv) + q * d
    ffn = 2 * d * (c["moe_shared_expert_intermediate_size"]
                   + c["moe_latent_size"])
    return (ssm_layers(c) * mamba + attn_layers(c) * attn
            + expert_layers(c) * ffn)


def weight_bytes(config: dict) -> float:
    """Bytes of the weights this chip holds: int8 block weights with their
    scales, the routers in float32, embedding and head in bf16 (norms, taps
    and a head's scalars, a few MB, left out): 9.63 GB."""
    c = config
    return (kernel_bytes_kexaone.int8_bytes(
                quant_matmul_weights(c) + held_expert_weights(c))
            + expert_layers(c) * c["hidden_size"] * c["router_outputs"] * F32
            + 2 * c["vocab_size"] * c["hidden_size"] * BF16)


def rows_a_step(ctx: dict):
    """Rows that took a recurrence step, a decode step dispatched: the
    counters of the traced part where the run has them (counted INSIDE the
    trace), the whole window's otherwise.  A ratio of two host counters of
    one window, so no device time enters it.  None where one is missing."""
    slots = ctx["config"]["serve"]["slots"]
    for counters in (ctx.get("trace_counters") or {}, ctx["counters"]):
        slot_steps = counters.get("batcher_decode_slot_steps", 0.0)
        row_steps = counters.get("ssm_decode_row_steps", 0.0)
        if slot_steps and row_steps:
            return row_steps / (slot_steps / slots)
    return None


def decode_least_s(ctx: dict):
    """The least time ``ssm_decode`` can take in the decode programs that
    lie WHOLE inside the trace: their steps x :func:`rows_a_step` x the
    state-space layers x the state read and written once, over peak HBM
    bandwidth (set it against the kernel's seconds inside those same
    programs).  None where something is missing."""
    t, peaks, config = ctx["trace"], ctx["peaks"], ctx["config"]
    rows = rows_a_step(ctx)
    if not t or not peaks or not rows or not t.get("decode"):
        return None
    steps = config["serve"]["chunk_steps"] * t["decode"]["count"]
    return (steps * rows * ssm_layers(config) * 2
            * state_bytes_row_layer(config) / peaks["hbm_bytes_per_s"])


def admit_ops(config: dict, tokens: float) -> float:
    """Multiplies and adds of the chunked scan for ``tokens`` real tokens of
    one row, every state-space layer: among a chunk's tokens the causal
    half of its pairs (a group's ``C . B``, 2 N, and a head's weighted
    ``dt x``, 2 P); for every token and head the state's readout and its
    update (2 P N each)."""
    c = config
    p, n = c["mamba_head_dim"], c["ssm_state_size"]
    half = (c["chunk_size"] + 1) / 2
    return ssm_layers(c) * tokens * (
        c["n_groups"] * half * 2 * n
        + c["mamba_num_heads"] * (half * 2 * p + 2 * 2 * p * n))


def admit_bytes(config: dict, tokens: float) -> float:
    """Bytes the scan reads and writes for ``tokens`` tokens, every
    state-space layer: x in and y out (bf16), B and C in, dt in (float32)."""
    c = config
    n = c["n_groups"] * c["ssm_state_size"]
    return ssm_layers(c) * tokens * (
        2 * inner(c) * BF16 + 2 * n * BF16 + c["mamba_num_heads"] * F32)


def admit_least_s(ctx: dict):
    """The least time ``ssm_prefill`` can take for the admissions the trace
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
