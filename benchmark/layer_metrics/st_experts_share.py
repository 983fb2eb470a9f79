"""Share of the device's busy time spent in the expert kernel
(``moe_experts``) where every layer holds 64 ReLU-gated experts of 768
(SmallThinker).  Another configuration has nothing to read here."""
UNIT = "%"
KERNEL = "moe_experts"


def read(ctx):
    t = ctx["trace"]
    if (not t or not t["op_s"].get(KERNEL)
            or "moe_num_primary_experts" not in ctx["config"]):
        return None
    return 100.0 * t["op_s"][KERNEL] / t["busy_s"]
