"""The rings' decode kernel (``swa_decode_attn``) against its roofline:
``full_attn_roofline``'s reckoning over the tokens inside the window
(``swa.decode.window_tokens``: each decoding row's min(tokens held,
sliding_window)) x the windowed layers x 4,096 bytes.  The kernel fetches a
row's whole ring of 128 tokens however few of them count, so rows shorter
than the window read as a share below what the kernel moves: the share
reads low, never high.  Nothing is clamped."""
from benchmark import kernel_bytes_kexaone

UNIT = "%"
KERNEL = "swa_decode_attn"


def read(ctx):
    t, config = ctx["trace"], ctx["config"]
    if (not t or not t["op_s"].get(KERNEL)
            or "sliding_windows" not in config):
        return None
    least_s = kernel_bytes_kexaone.decode_attn_least_s(
        ctx, "swa_decode_window_tokens",
        kernel_bytes_kexaone.window_layers(config))
    if not least_s:
        return None
    return 100.0 * least_s / t["op_s"][KERNEL]
