"""The admission scan's kernel (``retention_prefill``) against its roofline:
the chunked form's arithmetic at the configuration's chunk length
(``kernel_bytes_brumby.admit_ops``: the causal half of a chunk's pairs, the
state's query for the tokens behind a row's first chunk, the state's update
for every token, at the symmetric size) for the real tokens scanned in the
counter window INSIDE the trace (``ret.admit.tokens``), over the peak bf16
rate, over the kernel's time.  A kernel that scores whole squares of pairs,
or walks chunks of padding, reads lower, never higher.  Nothing is
clamped."""
from benchmark import kernel_bytes_brumby as kb

UNIT = "%"
KERNEL = "retention_prefill"


def read(ctx):
    t, config = ctx["trace"], ctx["config"]
    if (not t or not t["op_s"].get(KERNEL)
            or config.get("model_type") != "brumby"):
        return None
    least_s = kb.admit_least_s(ctx)
    if not least_s:
        return None
    return 100.0 * least_s / t["op_s"][KERNEL]
