"""The expert kernel against its roofline at 64 experts of 3 x 2,560 x 768
a layer, 12 layers (SmallThinker): ``moe_experts_roofline``'s reckoning
under this configuration's keys
(``kernel_bytes_smallthinker.experts_least_s``): the touched experts' bytes
for each step of the WHOLE decode programs of the trace, the larger of that
and its own tokens' arithmetic for each admission the trace pairs with its
``batcher.admit.row`` span, over the kernel's seconds inside those same
programs (PR 52: tokens against device time; nothing from
``trace_counters``).  Every term is a lower bound, so the share reads low.
Nothing is clamped: a count that is wrong shows as a share over 100%."""
from benchmark import kernel_bytes_smallthinker as kb
from benchmark import trace_reduce

UNIT = "%"
KERNEL = "moe_experts"


def read(ctx):
    t, config = ctx["trace"], ctx["config"]
    if (not t or not t["op_s"].get(KERNEL)
            or "moe_num_primary_experts" not in config):
        return None
    return trace_reduce.paired_share(t, KERNEL, kb.experts_least_s(ctx))
