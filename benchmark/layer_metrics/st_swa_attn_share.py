"""Share of the device's busy time spent in the kernel that walks the
windowed layers' rings block by block (``swa_decode_attn``,
ops/decode_attn.py) where a ring is 4,096 tokens (SmallThinker: 9 of 12
layers).  A program without the kernel, or another configuration, has
nothing to read."""
UNIT = "%"
KERNEL = "swa_decode_attn"


def read(ctx):
    t = ctx["trace"]
    if (not t or not t["op_s"].get(KERNEL)
            or "sliding_window_layout" not in ctx["config"]):
        return None
    return 100.0 * t["op_s"][KERNEL] / t["busy_s"]
