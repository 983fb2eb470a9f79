"""The expert kernel against its roofline for Qwen3-Next's share of the
routed experts: ``latent_experts_roofline``'s reckoning from this
configuration's keys (``kernel_bytes_qwen3next``: 128 held experts of 12
layers, three matrices of 2,048 x 512 an expert).  For each traced decode step
the tiles and scales of the held experts that had a token over peak HBM
bandwidth; for the traced admissions the larger of the same and the arithmetic
of the pairs that fell on a held expert over the peak bf16 rate.

Both shares are ratios of WHOLE-WINDOW counters, steady over a window: the
held experts touched (``moe.experts_touched`` over held x
``moe.layer_passes``) and the pairs held a routed pair (``moe.held_pairs``
over ``moe.routed_pairs``).  Every term is a lower bound (activations left
out; an expert many row tiles chose counted once; an admission above 2,048
tokens, which streams the stacks once a block of 2,048, counted once), so the
share reads low.  Nothing is clamped: a count that is wrong shows as a share
over 100%.  The steps are those of the decode programs that lie WHOLE inside
the trace, the admissions those the trace pairs with their span (PR 52:
tokens against device time; nothing from ``trace_counters``)."""
from benchmark import kernel_bytes_qwen3next as kb
from benchmark import trace_reduce

UNIT = "%"
KERNEL = "moe_experts"


def read(ctx):
    t, peaks = ctx["trace"], ctx["peaks"]
    c, config = ctx["counters"], ctx["config"]
    if (not t or not peaks or not t["op_s"].get(KERNEL)
            or config.get("model_type") != "qwen3_next"):
        return None
    passes, routed = c.get("moe_layer_passes", 0.0), c.get(
        "moe_routed_pairs", 0.0)
    if not passes or not routed:
        return None
    touched = c.get("moe_experts_touched", 0.0) / (
        config["num_experts"] * passes)
    per_pass_s = (kb.held_experts_bytes(config) * touched
                  / peaks["hbm_bytes_per_s"])
    # The pairs a prompt token gives the held experts, every layer.
    held_a_token = (config["num_experts_per_tok"] * kb.layers(config)
                    * c.get("moe_held_pairs", 0.0) / routed)
    return trace_reduce.paired_share(t, KERNEL, trace_reduce.least_s(
        t, config["serve"]["chunk_steps"], per_pass_s,
        lambda tokens: max(
            per_pass_s, kb.held_flops(config, tokens * held_a_token)
            / peaks["bf16_flops_per_s"])))
