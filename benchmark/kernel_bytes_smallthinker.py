"""The bytes and operations that SmallThinker's three own kernels have to
move and do, from the configuration's shapes under its own (the published)
keys: the numerators of ``st_full_attn_roofline``, ``st_swa_attn_roofline``
and ``st_experts_roofline``, and the sizes that
tests/benchmark/test_smallthinker_metrics.py holds to ISSUE 45's numbers
written out.

A new file that imports the accepted ones and edits none: the attention
reckoning is ``kernel_bytes_kexaone.decode_attn_least_s``'s over this
configuration's layer counts (``sliding_window_layout`` where K-EXAONE's
file has ``layer_types``) with the tokens a step counted INSIDE the trace,
the expert reckoning ``kernel_bytes_moe``'s
(every expert held, as in LFM2's cell) under the keys
``moe_num_primary_experts``, ``moe_num_active_primary_experts`` and
``moe_ffn_hidden_size``.
"""

from __future__ import annotations

from benchmark import kernel_bytes_kexaone, kernel_bytes_moe, trace_reduce

BF16 = 2


def held_layout(config: dict) -> list:
    """``sliding_window_layout`` of the layers this chip holds: the file
    keeps the published list whole, and stage 0's ``num_hidden_layers``
    layers are its first entries (1: windowed and rotated; 0: full)."""
    return config["sliding_window_layout"][: config["num_hidden_layers"]]


def full_layers(config: dict) -> int:
    """Layers that attend the whole prefix, whose keys and values are
    paged (3 of the 12 held)."""
    return held_layout(config).count(0)


def window_layers(config: dict) -> int:
    """Layers that attend the last ``sliding_window_size`` positions out
    of a ring a row (9 of the 12 held)."""
    return held_layout(config).count(1)


def kv_bytes_per_token_layer(config: dict) -> int:
    """Keys and values of one token in one layer, bf16 (4 x 128 x 2 x 2 =
    2,048)."""
    return kernel_bytes_kexaone.kv_bytes_per_token_layer(config)


def pool_bytes_per_token(config: dict) -> int:
    """What a resident token costs the page pool (6,144)."""
    return full_layers(config) * kv_bytes_per_token_layer(config)


def ring_bytes(config: dict) -> int:
    """The windowed layers' rings of every batch slot, whatever the rows
    hold (9 x 32 x 4,096 x 2,048 = 2,415,919,104)."""
    return (window_layers(config) * config["serve"]["slots"]
            * config["sliding_window_size"] * kv_bytes_per_token_layer(config))


def moe_keys(config: dict) -> dict:
    """The configuration under the keys ``kernel_bytes_moe`` reads: every
    layer an expert layer, every expert held."""
    return {
        "num_hidden_layers": config["num_hidden_layers"],
        "num_dense_layers": 0,
        "hidden_size": config["hidden_size"],
        "moe_intermediate_size": config["moe_ffn_hidden_size"],
        "num_experts": config["moe_num_primary_experts"],
        "num_experts_per_tok": config["moe_num_active_primary_experts"],
    }


def expert_weights(config: dict) -> int:
    """Weights of ONE expert: gate, up and down (3 x 2,560 x 768 =
    5,898,240)."""
    return kernel_bytes_moe.expert_weights(moe_keys(config))


def all_experts_bytes(config: dict) -> float:
    """Every expert of every layer as the kernel streams them, int8 and
    scales (12 x 64 x 5,898,240 x 1.03125 = 4.671 GB)."""
    return kernel_bytes_moe.all_experts_bytes(moe_keys(config))


def attention_weights_per_layer(config: dict) -> int:
    """W_q, W_k, W_v and W_o of one layer (20,971,520)."""
    c = config
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return c["hidden_size"] * (q + 2 * kv) + q * c["hidden_size"]


def layer_bytes(config: dict) -> float:
    """One layer's weights: 64 experts and the attention in int8 blocks,
    the router in float32 (0.4116 GB; norms left out)."""
    c = config
    block = (c["moe_num_primary_experts"] * expert_weights(c)
             + attention_weights_per_layer(c))
    return (kernel_bytes_kexaone.int8_bytes(block)
            + c["hidden_size"] * c["moe_num_primary_experts"] * 4)


def weight_bytes(config: dict) -> float:
    """Bytes of the weights this chip holds: the layers, embedding and head
    in bf16, untied (6.49 GB)."""
    c = config
    return (c["num_hidden_layers"] * layer_bytes(c)
            + 2 * c["vocab_size"] * c["hidden_size"] * BF16)


def decode_attn_least_s(ctx: dict, counter: str, layers: int):
    """The least time the traced decode steps' attention kernel of
    ``layers`` layers can take: the tokens it attends a step x the traced
    ``jit_decode_chunk`` programs x ``chunk_steps`` x the layers x 2,048
    bytes, over peak HBM bandwidth.  The tokens a step are those of the
    COUNTER WINDOW INSIDE THE TRACE (``trace_counters``: ``counter`` over
    the steps dispatched there, ``batcher.decode.slot_steps`` / slots), not
    the whole window's as ``kernel_bytes_kexaone.decode_attn_least_s`` has
    them: this cell's 32 long rows come and go in waves, so what the rows
    hold in any 6 s lies 15% to either side of the window's mean (PERF.md
    section 6, PR 45), and a kernel near its roofline would read over
    100% in a trough.  None where a counter is missing."""
    t, peaks, config = ctx["trace"], ctx["peaks"], ctx["config"]
    tc = ctx.get("trace_counters") or {}
    slot_steps = tc.get("batcher_decode_slot_steps", 0.0)
    tokens = tc.get(counter, 0.0)
    if not t or not peaks or not slot_steps or not tokens:
        return None
    per_step = tokens / (slot_steps / config["serve"]["slots"])
    steps = config["serve"]["chunk_steps"] * sum(
        n for name, n in t["module_count"].items()
        if name.startswith("jit_decode_chunk"))
    return (steps * per_step * layers * kv_bytes_per_token_layer(config)
            / peaks["hbm_bytes_per_s"])


def experts_least_s(ctx: dict):
    """The least time the expert kernel can take for the programs the
    trace holds whole (``moe_experts_roofline``'s reckoning): for each step
    of the whole decode programs the touched experts' bytes over peak HBM
    bandwidth; for each admission the trace pairs with its
    ``batcher.admit.row`` span the larger of the same (once an admission,
    though one above 2,048 tokens streams the stacks once a block: a lower
    bound) and the arithmetic of its own span's
    ``prompt_tokens - cached_tokens`` over the peak bf16 rate
    (``trace_reduce.least_s``, PR 52: tokens against device time, nothing
    from ``trace_counters``; set it against ``trace_reduce.inside_s``, the
    kernel's seconds inside those same programs).  The share of experts
    touched is a ratio of WHOLE-WINDOW counters.  None where something is
    missing."""
    t, peaks = ctx["trace"], ctx["peaks"]
    c, config = ctx["counters"], ctx["config"]
    passes = c.get("moe_layer_passes", 0.0)
    if not t or not peaks or not passes:
        return None
    keys = moe_keys(config)
    touched = c.get("moe_experts_touched", 0.0) / (
        keys["num_experts"] * passes)
    per_pass_s = (kernel_bytes_moe.touched_bytes_per_pass(keys, touched)
                  / peaks["hbm_bytes_per_s"])
    return trace_reduce.least_s(
        t, config["serve"]["chunk_steps"], per_pass_s,
        lambda tokens: max(per_pass_s,
                           kernel_bytes_moe.routed_flops(keys, tokens)
                           / peaks["bf16_flops_per_s"]))
