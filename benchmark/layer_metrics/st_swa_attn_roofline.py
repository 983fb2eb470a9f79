"""The rings' decode kernel (``swa_decode_attn``) against its roofline
where a ring is walked in blocks: the LIVE tokens inside the window
(``swa.decode.window_tokens``: each decoding row's min(tokens held, 4,096),
counted a step INSIDE the trace: ``kernel_bytes_smallthinker.
decode_attn_least_s``) x the 9 windowed layers x 2,048 bytes over peak HBM bandwidth, over the
kernel's time.  The kernel fetches a row's live blocks of 64 tokens, so at
most 63 tokens a row a layer more than what counts; a kernel that fetched
whole 4,096-token rings for short rows would read low by the rings' fill
(``st_ring_fill``).  Nothing is clamped: a count that is wrong shows as a
share over 100%."""
from benchmark import kernel_bytes_smallthinker as kb

UNIT = "%"
KERNEL = "swa_decode_attn"


def read(ctx):
    t, config = ctx["trace"], ctx["config"]
    if (not t or not t["op_s"].get(KERNEL)
            or "sliding_window_layout" not in config):
        return None
    least_s = kb.decode_attn_least_s(
        ctx, "swa_decode_window_tokens", kb.window_layers(config))
    if not least_s:
        return None
    return 100.0 * least_s / t["op_s"][KERNEL]
