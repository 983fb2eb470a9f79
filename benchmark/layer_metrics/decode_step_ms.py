"""Device time of one decode step: the ``jit_decode_chunk`` programs of the
traced window over the steps they ran (each runs ``chunk_steps``)."""
UNIT = "ms"


def read(ctx):
    t = ctx["trace"]
    if not t or not t["module_count"].get("jit_decode_chunk"):
        return None
    steps = t["module_count"]["jit_decode_chunk"] * ctx["config"]["serve"]["chunk_steps"]
    return 1e3 * t["module_s"]["jit_decode_chunk"] / steps
