"""Share of the device's busy time spent in the kernel that scans an
admission's tokens in chunks (``ssm_prefill``, ops/ssm.py), in Nemotron-H's
configuration.  A program without the kernel, or another configuration, has
nothing to read."""
UNIT = "%"
KERNEL = "ssm_prefill"


def read(ctx):
    t = ctx["trace"]
    if (not t or not t["op_s"].get(KERNEL)
            or ctx["config"].get("model_type") != "nemotron_h"):
        return None
    return 100.0 * t["op_s"][KERNEL] / t["busy_s"]
