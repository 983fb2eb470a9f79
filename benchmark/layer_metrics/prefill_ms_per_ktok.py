"""Device time of the admission programs (``jit_admit_row*``, and the
chunked-prefill ones where the schedule uses them) in the traced window,
over the prompt tokens prefilled fresh in it (thousands)."""
UNIT = "ms/ktok"
PROGRAMS = ("jit_admit_row", "jit_prefill_chunk_step", "jit_finish_chunked")


def read(ctx):
    t, c = ctx["trace"], ctx.get("trace_counters")
    if not t or not c:
        return None
    tokens = c.get("batcher_prefix_cache_miss_tokens", 0)
    secs = sum(v for k, v in t["module_s"].items() if k.startswith(PROGRAMS))
    if not tokens or not secs:
        return None
    return 1e3 * secs / (tokens / 1e3)
