"""Share of the device's busy time spent in the expert kernel
(``moe_experts``) of K-EXAONE's configuration, which holds 16 of 128 routed
experts a layer (``router_outputs`` beside ``num_experts`` in its file).
Any other configuration reads nothing here."""
UNIT = "%"


def read(ctx):
    t, config = ctx["trace"], ctx["config"]
    if (not t or not t["op_s"].get("moe_experts")
            or "router_outputs" not in config or "num_experts" not in config):
        return None
    return 100.0 * t["op_s"]["moe_experts"] / t["busy_s"]
