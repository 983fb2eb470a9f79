"""The expert kernel against its roofline at 64 experts of 3 x 2,560 x 768
a layer, 12 layers (SmallThinker): ``moe_experts_roofline``'s reckoning
under this configuration's keys
(``kernel_bytes_smallthinker.experts_least_s``): the touched experts' bytes
for each traced decode step, the larger of that and the pairs' arithmetic
for the traced admissions.  Every term is a lower bound, so the share reads
low.  Nothing is clamped: a count that is wrong shows as a share over
100%."""
from benchmark import kernel_bytes_smallthinker as kb

UNIT = "%"
KERNEL = "moe_experts"


def read(ctx):
    t, config = ctx["trace"], ctx["config"]
    if (not t or not t["op_s"].get(KERNEL)
            or "moe_num_primary_experts" not in config):
        return None
    least_s = kb.experts_least_s(ctx)
    if not least_s:
        return None
    return 100.0 * least_s / t["op_s"][KERNEL]
