"""Share of the device's busy time spent in the paged decode kernel
(``paged_decode_attn``) where only the 3 unrotated full layers of 12 are
paged and rows run to 14k tokens (SmallThinker).  Another configuration
has nothing to read here (its share is ``paged_attn_share``)."""
UNIT = "%"
KERNEL = "paged_decode_attn"


def read(ctx):
    t = ctx["trace"]
    if (not t or not t["op_s"].get(KERNEL)
            or "sliding_window_layout" not in ctx["config"]):
        return None
    return 100.0 * t["op_s"][KERNEL] / t["busy_s"]
