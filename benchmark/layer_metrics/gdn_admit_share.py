"""Share of the device's busy time spent in the kernel that scans an
admission's tokens in chunks and solves a triangle a chunk (``gdn_prefill``,
ops/gdn.py), in Qwen3-Next's configuration.  A program without the kernel, or
another configuration, has nothing to read."""
UNIT = "%"
KERNEL = "gdn_prefill"


def read(ctx):
    t = ctx["trace"]
    if (not t or not t["op_s"].get(KERNEL)
            or ctx["config"].get("model_type") != "qwen3_next"):
        return None
    return 100.0 * t["op_s"][KERNEL] / t["busy_s"]
