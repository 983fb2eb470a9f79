"""The expert kernel against its roofline where a chip holds a share of the
routed experts: ``moe_experts_roofline``'s reckoning over the held experts
of the expert layers (``kernel_bytes_axk1``).  For each traced decode step
the bytes of the held experts that had a token over peak HBM bandwidth; for
the traced admissions the larger of the same and the arithmetic of the
pairs that fell on a held expert over the peak bf16 rate.

Both shares are ratios of WHOLE-WINDOW counters, steady over a window: the
held experts touched (``moe.experts_touched`` over held x
``moe.layer_passes``) and the pairs held a routed pair (``moe.held_pairs``
over ``moe.routed_pairs``).  Every term is a lower bound (activations
left out; an expert many row tiles chose counted once), so the share reads
low.  Nothing is clamped: a count that is wrong shows as a share over 100%.

**Tokens against device time** (PR 52): the steps are those of the decode
programs that lie WHOLE inside the trace, the admissions those the trace
pairs with their ``batcher.admit.row`` span (``trace_reduce.reduce``:
``decode``, ``admissions``), each with its own span's
``prompt_tokens - cached_tokens`` (real tokens, which every admission's
span carries with or without the prefix cache), and the kernel's time is
its seconds INSIDE those same programs.  Nothing comes from
``trace_counters``: a counter read on the host around the trace counts an
admission launched just before ``trace_stop`` whole, and the device gave it
no time."""
from benchmark import kernel_bytes_axk1 as kb
from benchmark import trace_reduce

UNIT = "%"
KERNEL = "moe_experts"


def read(ctx):
    t, peaks = ctx["trace"], ctx["peaks"]
    c, config = ctx["counters"], ctx["config"]
    if not t or not peaks or not t["op_s"].get(KERNEL):
        return None
    passes, routed = c.get("moe_layer_passes", 0.0), c.get(
        "moe_routed_pairs", 0.0)
    if not passes or not routed or "router_outputs" not in config:
        return None
    touched = c.get("moe_experts_touched", 0.0) / (
        config["n_routed_experts"] * passes)
    per_pass = kb.held_experts_bytes(config) * touched
    per_pass_s = per_pass / peaks["hbm_bytes_per_s"]
    # The pairs a prompt token gives the held experts, every expert layer.
    held_a_token = (config["num_experts_per_tok"]
                    * kb.expert_layers(config)
                    * c.get("moe_held_pairs", 0.0) / routed)
    return trace_reduce.paired_share(t, KERNEL, trace_reduce.least_s(
        t, config["serve"]["chunk_steps"], per_pass_s,
        lambda tokens: max(
            per_pass_s, kb.held_flops(config, tokens * held_a_token)
            / peaks["bf16_flops_per_s"])))
