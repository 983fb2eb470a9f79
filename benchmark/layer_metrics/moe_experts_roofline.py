"""The expert kernel against its roofline.  The least time the chip could
take for the traced window's passes: for each decode step, the bytes of the
experts that had a token (``kernel_bytes_moe.touched_bytes_per_pass``) over
peak HBM bandwidth; for the admissions the larger of the same and the
arithmetic of their prompt tokens (``kernel_bytes_moe.routed_flops``: each
token through its k experts in every expert layer) over the peak bf16 rate
(the kernel dequantizes to bf16 for the MXU).  The prompt tokens are
``batcher.prefix_cache.miss_tokens`` of the counter window inside the
trace, as ``quant_matmul_roofline`` takes them (real tokens, which every
admission counts with or without the prefix cache), and never more than
the traced admissions can have held (``max_len`` each).  The share is that
least time over the kernel's device time.

The share of experts touched is a ratio of WHOLE-WINDOW counters
(``moe.experts_touched`` over experts x ``moe.layer_passes``): a property
of the routing, steady over a window, where a count from the 6-s counter
window would not be the trace's.  Every term is a lower bound (activations
left out; an expert many row tiles chose counted once; the decode steps'
share, under 1, applied to the admissions, which touch nearly every
expert; padding rows of a bucket not counted), so the share reads low.
Nothing is clamped: a count that is wrong shows as a share over 100%."""
from benchmark import kernel_bytes_moe

UNIT = "%"
KERNEL = "moe_experts"
ADMISSIONS = ("jit_admit_row",)
CHUNKS = ("jit_decode_chunk",)


def read(ctx):
    t, peaks, tc = ctx["trace"], ctx["peaks"], ctx.get("trace_counters")
    c, config = ctx["counters"], ctx["config"]
    if not t or not peaks or not tc or not t["op_s"].get(KERNEL):
        return None
    passes = c.get("moe_layer_passes", 0.0)
    if not passes or "num_experts" not in config:
        return None
    touched = c.get("moe_experts_touched", 0.0) / (
        config["num_experts"] * passes)
    per_pass = kernel_bytes_moe.touched_bytes_per_pass(config, touched)
    steps = config["serve"]["chunk_steps"]
    decode = sum(n * steps for name, n in t["module_count"].items()
                 if name.startswith(CHUNKS))
    admits = sum(n for name, n in t["module_count"].items()
                 if name.startswith(ADMISSIONS))
    tokens = min(tc.get("batcher_prefix_cache_miss_tokens", 0.0),
                 admits * config["serve"]["max_len"])
    least_s = decode * per_pass / peaks["hbm_bytes_per_s"] + max(
        admits * per_pass / peaks["hbm_bytes_per_s"],
        kernel_bytes_moe.routed_flops(config, tokens)
        / peaks["bf16_flops_per_s"])
    if not least_s:
        return None
    return 100.0 * least_s / t["op_s"][KERNEL]
