"""The recurrence step's kernel (``retention_decode``) against its roofline:
the rows that took a step (``ret.decode.row_steps``, counted a step INSIDE
the trace: ``kernel_bytes_brumby.decode_least_s``) x the 10 layers x the
state read and written once at its SYMMETRIC size (2 x 34,080,768 bytes a
row a layer, whatever layout the kernel keeps) over peak HBM bandwidth, over
the kernel's time.  The served layout holds 1.5% more than that, so the
kernel cannot read over 98.5; one that kept the full 128 x 128 square would
read under 50.  Nothing is clamped: a count that is wrong shows as a share
over 100%."""
from benchmark import kernel_bytes_brumby as kb

UNIT = "%"
KERNEL = "retention_decode"


def read(ctx):
    t, config = ctx["trace"], ctx["config"]
    if (not t or not t["op_s"].get(KERNEL)
            or config.get("model_type") != "brumby"):
        return None
    least_s = kb.decode_least_s(ctx)
    if not least_s:
        return None
    return 100.0 * least_s / t["op_s"][KERNEL]
