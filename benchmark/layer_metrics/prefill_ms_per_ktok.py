"""Device time of the admission programs (``jit_admit_row*``) over the
prompt tokens they prefilled fresh (thousands): of the admissions that lie
WHOLE inside the trace and that the trace pairs with their
``batcher.admit.row`` span (``trace_reduce.reduce``: ``admissions``), each
program's seconds over its own span's ``prompt_tokens - cached_tokens``.
Numerator and denominator are the same admissions' (PR 52): a host
counter's window is not the device's, and with three admissions of
4,096-16,384 tokens in the trace one at its edge moved this by a third."""
UNIT = "ms/ktok"


def read(ctx):
    t = ctx["trace"]
    if not t or not t.get("admissions"):
        return None
    tokens = sum(a["tokens"] for a in t["admissions"])
    secs = sum(a["seconds"] for a in t["admissions"])
    if not tokens or not secs:
        return None
    return 1e3 * secs / (tokens / 1e3)
