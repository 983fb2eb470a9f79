"""The quantized matmul kernel against its roofline.  The least time the
chip could take for the traced window's forward passes is, for each decode
step, the block weights' bytes (``kernel_bytes.quant_matmul_bytes_per_pass``)
over peak HBM bandwidth: 16 rows are far below the ridge, so bandwidth
bounds it.  For the admissions it is the larger of that and
``2 x tokens x weights`` over the peak bf16 rate (the kernel dequantizes to
bf16 for the MXU): past some 120 tokens a prefill is bound by compute.  The
share is that least time over the kernel's device time."""
from benchmark import kernel_bytes

UNIT = "%"
KERNEL = "_quant_matmul_2d"
ADMISSIONS = ("jit_admit_row", "jit_prefill_chunk_step")
CHUNKS = ("jit_decode_chunk", "jit_mixed_step")


def read(ctx):
    t, peaks, c = ctx["trace"], ctx["peaks"], ctx.get("trace_counters")
    if not t or not peaks or not c or not t["op_s"].get(KERNEL):
        return None
    steps = ctx["config"]["serve"]["chunk_steps"]
    per_pass = kernel_bytes.quant_matmul_bytes_per_pass(ctx["config"])
    weights = kernel_bytes.quant_matmul_weights(ctx["config"])
    decode = sum(n * steps for name, n in t["module_count"].items()
                 if name.startswith(CHUNKS))
    admits = sum(n for name, n in t["module_count"].items()
                 if name.startswith(ADMISSIONS))
    tokens = c.get("batcher_prefix_cache_miss_tokens", 0.0)
    least_s = decode * per_pass / peaks["hbm_bytes_per_s"] + max(
        admits * per_pass / peaks["hbm_bytes_per_s"],
        2.0 * tokens * weights / peaks["bf16_flops_per_s"])
    if not least_s:
        return None
    return 100.0 * least_s / t["op_s"][KERNEL]
