"""Share of the device's busy time spent in the paged decode attention
kernel.  The kernel has a name of its own in the trace only from the PR that
named it (``pl.pallas_call(..., name="paged_decode_attn")``); before that it
is a ``closed_call`` among others and there is nothing to read."""
UNIT = "%"


def read(ctx):
    t = ctx["trace"]
    if not t or not t["op_s"].get("paged_decode_attn"):
        return None
    return 100.0 * t["op_s"]["paged_decode_attn"] / t["busy_s"]
