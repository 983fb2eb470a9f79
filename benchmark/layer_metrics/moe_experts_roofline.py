"""The expert kernel against its roofline.  The least time the chip could
take for the traced window's passes: for each decode step, the bytes of the
experts that had a token (``kernel_bytes_moe.touched_bytes_per_pass``) over
peak HBM bandwidth; for the admissions the larger of the same and the
arithmetic of their prompt tokens (``kernel_bytes_moe.routed_flops``: each
token through its k experts in every expert layer) over the peak bf16 rate
(the kernel dequantizes to bf16 for the MXU), an admission at a time.  The
share is that least time over the kernel's device time.

**Tokens against device time** (PR 52): the steps are those of the decode
programs that lie WHOLE inside the trace, the admissions those the trace
pairs with their ``batcher.admit.row`` span (``trace_reduce.reduce``:
``decode``, ``admissions``), each with its own span's
``prompt_tokens - cached_tokens`` (real tokens, which every admission's
span carries with or without the prefix cache), and the kernel's time is
its seconds INSIDE those same programs.  Nothing comes from
``trace_counters``: a counter read on the host around the trace counts an
admission launched just before ``trace_stop`` whole, and the device gave it
no time.

The share of experts touched is a ratio of WHOLE-WINDOW counters
(``moe.experts_touched`` over experts x ``moe.layer_passes``): a property
of the routing, steady over a window.  Every term is a lower bound
(activations left out; an expert many row tiles chose counted once; the
decode steps' share, under 1, applied to the admissions, which touch nearly
every expert; padding rows of a bucket not counted), so the share reads
low.  Nothing is clamped: a count that is wrong shows as a share over 100%."""
from benchmark import kernel_bytes_moe, trace_reduce

UNIT = "%"
KERNEL = "moe_experts"


def read(ctx):
    t, peaks = ctx["trace"], ctx["peaks"]
    c, config = ctx["counters"], ctx["config"]
    if not t or not peaks or not t["op_s"].get(KERNEL):
        return None
    passes = c.get("moe_layer_passes", 0.0)
    if not passes or "num_experts" not in config:
        return None
    touched = c.get("moe_experts_touched", 0.0) / (
        config["num_experts"] * passes)
    per_pass = kernel_bytes_moe.touched_bytes_per_pass(config, touched)
    per_pass_s = per_pass / peaks["hbm_bytes_per_s"]
    return trace_reduce.paired_share(t, KERNEL, trace_reduce.least_s(
        t, config["serve"]["chunk_steps"], per_pass_s,
        lambda tokens: max(per_pass_s,
                           kernel_bytes_moe.routed_flops(config, tokens)
                           / peaks["bf16_flops_per_s"])))
