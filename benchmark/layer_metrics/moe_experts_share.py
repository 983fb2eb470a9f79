"""Share of the device's busy time spent in the expert kernel
(``pl.pallas_call(..., name="moe_experts")``, ops/moe_experts.py).  A
program without the kernel has nothing to read."""
UNIT = "%"


def read(ctx):
    t = ctx["trace"]
    if not t or not t["op_s"].get("moe_experts"):
        return None
    return 100.0 * t["op_s"]["moe_experts"] / t["busy_s"]
