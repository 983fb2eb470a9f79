"""The paged decode attention kernel against its roofline where only the
full-attention layers are paged.  At 8 operations a byte the kernel is far
under the chip's ridge, so the least time of a decode step is its rows'
bytes over peak HBM bandwidth: the tokens resident
(``attn.decode.resident_tokens``) x the full layers x the 4,096 bytes of a
token's keys and values a layer
(``kernel_bytes_kexaone.decode_attn_least_s``).  Nothing is clamped: a
count that is wrong shows as a share over 100%."""
from benchmark import kernel_bytes_kexaone

UNIT = "%"
KERNEL = "paged_decode_attn"


def read(ctx):
    t, config = ctx["trace"], ctx["config"]
    if (not t or not t["op_s"].get(KERNEL)
            or "sliding_windows" not in config):
        return None
    least_s = kernel_bytes_kexaone.decode_attn_least_s(
        ctx, "attn_decode_resident_tokens",
        kernel_bytes_kexaone.full_layers(config))
    if not least_s:
        return None
    return 100.0 * least_s / t["op_s"][KERNEL]
