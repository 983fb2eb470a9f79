"""Share of the device's busy time spent in the expert kernel
(``moe_experts``) of a configuration that holds a chip's share of the
routed experts (``router_outputs`` beside ``n_routed_experts`` in its
file).  Any other configuration reads nothing here (``moe_experts_share``
is its metric)."""
UNIT = "%"


def read(ctx):
    t = ctx["trace"]
    if (not t or not t["op_s"].get("moe_experts")
            or "router_outputs" not in ctx["config"]):
        return None
    return 100.0 * t["op_s"]["moe_experts"] / t["busy_s"]
