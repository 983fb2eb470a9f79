"""The bytes and operations that Brumby's two own kernels have to move and
do, from the configuration's shapes under its own (the published) keys: the
numerators of ``ret_decode_roofline`` and ``ret_admit_roofline``, and the
sizes that tests/benchmark/test_brumby_metrics.py holds to ISSUE 50's
numbers written out.

A new file that imports the accepted ones and edits none.  Everything is
reckoned at the SYMMETRIC size of the state, whatever layout the kernels
keep: a key's 128 entries have 128 x 129 / 2 = 8,256 distinct products, a
key/value head's state is 8,256 x 128 values and its normaliser 8,256, in
float32.  The served layout (65 whole cyclic diagonals of the square and
the normaliser as a 128 x 128 matrix, ops/retention.py) holds 1.5% more; a
kernel that kept the full square would read under 50%, none reads over
100%.
"""

from __future__ import annotations

from benchmark import kernel_bytes, kernel_bytes_kexaone

F32 = 4
BF16 = 2


def products(config: dict) -> int:
    """Distinct products of pairs of a head's entries (8,256)."""
    d = config["head_dim"]
    return d * (d + 1) // 2


def state_bytes_row_layer(config: dict) -> int:
    """One row's state and normaliser in one layer at the symmetric size,
    float32 (8 x 8,256 x 129 x 4 = 34,080,768)."""
    return (config["num_key_value_heads"] * products(config)
            * (config["head_dim"] + 1) * F32)


def served_state_bytes(config: dict) -> int:
    """What the served layout holds for every slot and layer: 65 diagonals
    of 128 x 128 and a 128 x 128 normaliser a key/value head
    (16 x 10 x 34,603,008 = 5,536,481,280: gauge batcher_ret_state_bytes)."""
    d = config["head_dim"]
    per = config["num_key_value_heads"] * (d // 2 + 2) * d * d * F32
    return config["serve"]["slots"] * config["num_hidden_layers"] * per


def kv_bytes_per_token_layer(config: dict) -> int:
    """Keys and values of one token in one layer in bf16, what a model with
    a key cache would hold instead (8 x 128 x 2 x 2 = 4,096)."""
    return kernel_bytes_kexaone.kv_bytes_per_token_layer(config)


def layer_weights(config: dict) -> int:
    """Block matmul weights of one layer (330,301,440)."""
    return kernel_bytes.quant_matmul_weights(config) // config[
        "num_hidden_layers"]


def weight_bytes(config: dict) -> float:
    """Bytes of the weights this chip holds: the int8 blocks, embedding and
    head in bf16, untied (6.52 GB; the gate's 5120 x 8 and the norms left
    out)."""
    c = config
    return (kernel_bytes_kexaone.int8_bytes(kernel_bytes.quant_matmul_weights(c))
            + 2 * c["vocab_size"] * c["hidden_size"] * BF16)


def decode_least_s(ctx: dict):
    """The least time the traced decode steps' ``retention_decode`` can
    take: the rows that took a step, counted INSIDE the trace
    (``trace_counters``: ``ret.decode.row_steps`` over the steps dispatched
    there), x the traced ``jit_decode_chunk`` programs x ``chunk_steps`` x
    the layers x the state read and written once, over peak HBM bandwidth.
    None where something is missing."""
    t, peaks, config = ctx["trace"], ctx["peaks"], ctx["config"]
    tc = ctx.get("trace_counters") or {}
    slot_steps = tc.get("batcher_decode_slot_steps", 0.0)
    row_steps = tc.get("ret_decode_row_steps", 0.0)
    if not t or not peaks or not slot_steps or not row_steps:
        return None
    rows_a_step = row_steps / (slot_steps / config["serve"]["slots"])
    steps = config["serve"]["chunk_steps"] * sum(
        n for name, n in t["module_count"].items()
        if name.startswith("jit_decode_chunk"))
    return (steps * rows_a_step * config["num_hidden_layers"] * 2
            * state_bytes_row_layer(config) / peaks["hbm_bytes_per_s"])


def admit_ops(config: dict, tokens: float, admissions: float) -> float:
    """Floating-point operations of the chunked form for ``tokens`` real
    tokens in ``admissions`` rows, every layer: among a chunk's tokens the
    causal half of its pairs (a score and a weighted value, 2 x 128 each,
    a query head); for the tokens behind a row's first chunk the query of
    the state (2 x 8,256 x 129 a query head); for every token the state's
    update (2 x 8,256 x 129 a key/value head).  At the symmetric size and
    the configuration's chunk length."""
    c = config
    d, chunk = c["head_dim"], c["ret_chunk"]
    state = 2 * products(c) * (d + 1)
    pairs = (chunk + 1) / 2 * 2 * (2 * d)
    behind = max(tokens - admissions * chunk, 0.0)
    return c["num_hidden_layers"] * (
        tokens * c["num_attention_heads"] * pairs
        + behind * c["num_attention_heads"] * state
        + tokens * c["num_key_value_heads"] * state)


def admit_least_s(ctx: dict):
    """The least time ``retention_prefill`` can take for the admissions the
    trace holds whole: :func:`admit_ops` of each admission that the trace
    pairs with its ``batcher.admit.row`` span (its own span's
    ``prompt_tokens - cached_tokens``, one row) over the peak bf16 rate
    (``trace_reduce.reduce``: ``admissions``; PR 52: tokens against device
    time; set it against ``trace_reduce.inside_s``, the kernel's seconds
    inside those same programs).  ``ret.admit.tokens`` of
    ``trace_counters`` is added when an admission is SETTLED: one begun
    before the trace gave tokens and no whole program.  None where
    something is missing."""
    t, peaks = ctx["trace"], ctx["peaks"]
    if not t or not peaks or not t.get("admissions"):
        return None
    return sum(admit_ops(ctx["config"], a["tokens"], 1)
               for a in t["admissions"]) / peaks["bf16_flops_per_s"]
