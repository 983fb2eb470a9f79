"""Share of the device's busy time spent in the kernel that takes one
recurrence step of every row's state (``retention_decode``,
ops/retention.py) where every layer is a power-retention layer (Brumby).  A
program without the kernel, or another configuration, has nothing to
read."""
UNIT = "%"
KERNEL = "retention_decode"


def read(ctx):
    t = ctx["trace"]
    if (not t or not t["op_s"].get(KERNEL)
            or ctx["config"].get("model_type") != "brumby"):
        return None
    return 100.0 * t["op_s"][KERNEL] / t["busy_s"]
