"""The admission scan's kernel (``gdn_prefill``) against its roofline: for
the real tokens of each admission that lies WHOLE inside the trace and that
the trace pairs with its ``batcher.admit.row`` span, the larger of the scan's
bytes over peak HBM bandwidth and the chunked form's operations at the
configuration's chunk length, the triangle's among them, over the peak bf16
rate (``kernel_bytes_qwen3next.admit_least_s``), over the kernel's time INSIDE
those same programs (PR 52: tokens against device time; nothing from
``trace_counters``).  A kernel that contracts in float32, inverts the triangle
by products, scores whole squares of pairs or walks chunks of padding reads
lower, never higher.  Nothing is clamped."""
from benchmark import kernel_bytes_qwen3next as kb
from benchmark import trace_reduce

UNIT = "%"
KERNEL = "gdn_prefill"


def read(ctx):
    t, config = ctx["trace"], ctx["config"]
    if (not t or not t["op_s"].get(KERNEL)
            or config.get("model_type") != "qwen3_next"):
        return None
    return trace_reduce.paired_share(t, KERNEL, kb.admit_least_s(ctx))
