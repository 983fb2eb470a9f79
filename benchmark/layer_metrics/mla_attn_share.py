"""Share of the device's busy time spent in the latent decode attention
kernel (``pl.pallas_call(..., name="mla_paged_decode_attn")``,
ops/decode_attn.py).  A program without the kernel has nothing to read."""
UNIT = "%"
KERNEL = "mla_paged_decode_attn"


def read(ctx):
    t = ctx["trace"]
    if not t or not t["op_s"].get(KERNEL):
        return None
    return 100.0 * t["op_s"][KERNEL] / t["busy_s"]
