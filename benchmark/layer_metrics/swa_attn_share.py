"""Share of the device's busy time spent in the kernel that reads the
windowed layers' rings (``pl.pallas_call(..., name="swa_decode_attn")``,
ops/decode_attn.py).  A program without the kernel has nothing to read."""
UNIT = "%"
KERNEL = "swa_decode_attn"


def read(ctx):
    t = ctx["trace"]
    if not t or not t["op_s"].get(KERNEL):
        return None
    return 100.0 * t["op_s"][KERNEL] / t["busy_s"]
