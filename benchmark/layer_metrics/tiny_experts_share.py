"""Share of the device's busy time spent in the expert kernel
(``moe_experts``) of Qwen3-Next's configuration, which holds 128 of 512
experts a layer, each three int8 matrices of 2,048 x 512: a sixth of the
smallest expert another cell serves.  Any other configuration reads nothing
here."""
UNIT = "%"


def read(ctx):
    t, config = ctx["trace"], ctx["config"]
    if (not t or not t["op_s"].get("moe_experts")
            or config.get("model_type") != "qwen3_next"):
        return None
    return 100.0 * t["op_s"]["moe_experts"] / t["busy_s"]
