"""Share of the device's busy time spent in the quantized matmul kernel."""
UNIT = "%"


def read(ctx):
    t = ctx["trace"]
    if not t or not t["op_s"].get("_quant_matmul_2d"):
        return None
    return 100.0 * t["op_s"]["_quant_matmul_2d"] / t["busy_s"]
