"""The admission scan's kernel (``retention_prefill``) against its roofline:
the chunked form's arithmetic at the configuration's chunk length
(``kernel_bytes_brumby.admit_ops``: the causal half of a chunk's pairs, the
state's query for the tokens behind a row's first chunk, the state's update
for every token, at the symmetric size) for the real tokens of each
admission that lies WHOLE inside the trace and that the trace pairs with
its ``batcher.admit.row`` span, over the peak bf16 rate, over the kernel's
time INSIDE those same programs (``kernel_bytes_brumby.admit_least_s``; PR
52: tokens against device time; nothing from ``trace_counters``).  A kernel
that scores whole squares of pairs, or walks chunks of padding, reads
lower, never higher.  Nothing is clamped."""
from benchmark import kernel_bytes_brumby as kb
from benchmark import trace_reduce

UNIT = "%"
KERNEL = "retention_prefill"


def read(ctx):
    t, config = ctx["trace"], ctx["config"]
    if (not t or not t["op_s"].get(KERNEL)
            or config.get("model_type") != "brumby"):
        return None
    return trace_reduce.paired_share(t, KERNEL, kb.admit_least_s(ctx))
