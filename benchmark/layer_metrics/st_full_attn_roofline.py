"""The paged decode kernel against its roofline where only the full layers
are paged (SmallThinker): the tokens resident
(``attn.decode.resident_tokens``, counted a step INSIDE the trace) x the 3
full layers x the 2,048 bytes of
a token's keys and values a layer over peak HBM bandwidth
(``kernel_bytes_smallthinker.decode_attn_least_s``), over the kernel's
time.  7 query heads a key/value head: 14 operations a byte, far under the
chip's 240.  Nothing is clamped."""
from benchmark import kernel_bytes_smallthinker as kb

UNIT = "%"
KERNEL = "paged_decode_attn"


def read(ctx):
    t, config = ctx["trace"], ctx["config"]
    if (not t or not t["op_s"].get(KERNEL)
            or "sliding_window_layout" not in config):
        return None
    least_s = kb.decode_attn_least_s(
        ctx, "attn_decode_resident_tokens", kb.full_layers(config))
    if not least_s:
        return None
    return 100.0 * least_s / t["op_s"][KERNEL]
