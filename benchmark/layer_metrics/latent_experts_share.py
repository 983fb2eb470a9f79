"""Share of the device's busy time spent in the expert kernel
(``moe_experts``) of Nemotron-H's configuration, which holds 128 of 512
non-gated experts a layer, each two int8 matrices on a 1,024-wide latent.
Any other configuration reads nothing here."""
UNIT = "%"


def read(ctx):
    t, config = ctx["trace"], ctx["config"]
    if (not t or not t["op_s"].get("moe_experts")
            or config.get("model_type") != "nemotron_h"):
        return None
    return 100.0 * t["op_s"]["moe_experts"] / t["busy_s"]
