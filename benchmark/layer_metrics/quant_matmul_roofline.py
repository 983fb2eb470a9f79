"""The quantized matmul kernel against its roofline.  The least time the
chip could take for the traced forward passes is, for each decode step, the
block weights' bytes (``kernel_bytes.quant_matmul_bytes_per_pass``) over
peak HBM bandwidth: 16 rows are far below the ridge, so bandwidth bounds
it.  For an admission it is the larger of that and ``2 x tokens x weights``
over the peak bf16 rate (the kernel dequantizes to bf16 for the MXU): past
some 120 tokens a prefill is bound by compute.  The share is that least
time over the kernel's device time.

**Tokens against device time** (PR 52): the steps are those of the decode
programs that lie WHOLE inside the trace, the admissions those the trace
pairs with their ``batcher.admit.row`` span (``trace_reduce.reduce``:
``decode``, ``admissions``), each with its own span's
``prompt_tokens - cached_tokens`` (real tokens, not the bucket's rows: a
little low, never high), and the kernel's time is its seconds INSIDE those
same programs.  Nothing comes from ``trace_counters``: a counter read on
the host around the trace counts an admission launched just before
``trace_stop`` whole, and the device gave it no time (107% in
``brumby-14b-int8.long-rows``, ledger, PR 51).  Nothing is clamped: a count
that is wrong shows as a share over 100%."""
from benchmark import kernel_bytes, trace_reduce

UNIT = "%"
KERNEL = "_quant_matmul_2d"


def read(ctx):
    t, peaks = ctx["trace"], ctx["peaks"]
    if not t or not peaks:
        return None
    per_pass_s = (kernel_bytes.quant_matmul_bytes_per_pass(ctx["config"])
                  / peaks["hbm_bytes_per_s"])
    per_token_s = (2.0 * kernel_bytes.quant_matmul_weights(ctx["config"])
                   / peaks["bf16_flops_per_s"])
    return trace_reduce.paired_share(t, KERNEL, trace_reduce.least_s(
        t, ctx["config"]["serve"]["chunk_steps"], per_pass_s,
        lambda tokens: max(per_pass_s, tokens * per_token_s)))
