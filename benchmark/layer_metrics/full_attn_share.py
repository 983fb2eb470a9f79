"""Share of the device's busy time spent in the paged decode attention
kernel (``paged_decode_attn``) of a configuration whose full-attention
layers alone are paged (``layer_types`` beside ``sliding_windows`` in its
file): K-EXAONE's 3 of 12 layers.  Any other configuration reads nothing
here (``paged_attn_share`` is its metric)."""
UNIT = "%"
KERNEL = "paged_decode_attn"


def read(ctx):
    t = ctx["trace"]
    if (not t or not t["op_s"].get(KERNEL)
            or "sliding_windows" not in ctx["config"]):
        return None
    return 100.0 * t["op_s"][KERNEL] / t["busy_s"]
