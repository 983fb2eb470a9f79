"""The recurrence step's kernel (``gdn_decode``) against its roofline: the
rows that took a step, counted a step INSIDE the trace
(``kernel_bytes_qwen3next.rows_a_step``), x the steps of the decode programs
that lie WHOLE inside the trace x the 9 delta-rule layers x the state read and
written once (2 x 2,097,152 bytes a row a layer, whatever layout the kernel
keeps) over peak HBM bandwidth, over the kernel's seconds inside those same
programs.  A state updated where it lies is bound by the chip's writes taking
turns with its reads (657 GB/s of the 819 in ``peaks.json``: PERF.md section
7, From PR 54 (a)), so the share cannot read much over 85.  Nothing is
clamped: a count that is wrong shows as a share over 100%."""
from benchmark import kernel_bytes_qwen3next as kb

UNIT = "%"
KERNEL = "gdn_decode"


def read(ctx):
    t, config = ctx["trace"], ctx["config"]
    if (not t or config.get("model_type") != "qwen3_next"
            or not t.get("decode")
            or not t["decode"]["op_s"].get(KERNEL)):
        return None
    least_s = kb.decode_least_s(ctx)
    if not least_s:
        return None
    return 100.0 * least_s / t["decode"]["op_s"][KERNEL]
