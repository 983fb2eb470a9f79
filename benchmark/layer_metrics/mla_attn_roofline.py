"""The latent decode attention kernel against its roofline.  The kernel is
near the chip's ridge, not under it (139,264 operations for the 1,152 bytes
of a resident token's row a layer: 121 a byte against 240), so the least
time of a decode step is the LARGER of its rows' bytes over peak HBM
bandwidth and their operations over the peak bf16 rate
(``kernel_bytes_axk1``), in every layer.

The tokens resident a decode step are a ratio of WHOLE-WINDOW counters
(``mla.decode.resident_tokens`` over the steps dispatched,
``batcher.decode.slot_steps`` / slots): steady over a window, where a count
from the 6-s counter window would not be the trace's.  The steps are the
traced ``jit_decode_chunk`` programs x ``chunk_steps``.  Admissions do not
run the kernel.  Nothing is clamped: a count that is wrong shows as a share
over 100%."""
from benchmark import kernel_bytes_axk1

UNIT = "%"
KERNEL = "mla_paged_decode_attn"
CHUNKS = ("jit_decode_chunk",)


def read(ctx):
    t, peaks, c, config = (ctx["trace"], ctx["peaks"], ctx["counters"],
                           ctx["config"])
    if not t or not peaks or not t["op_s"].get(KERNEL):
        return None
    slot_steps = c.get("batcher_decode_slot_steps", 0.0)
    resident = c.get("mla_decode_resident_tokens", 0.0)
    if not slot_steps or not resident or "kv_lora_rank" not in config:
        return None
    per_step = resident / (slot_steps / config["serve"]["slots"])
    steps = config["serve"]["chunk_steps"] * sum(
        n for name, n in t["module_count"].items() if name.startswith(CHUNKS))
    rows = steps * per_step * config["num_hidden_layers"]
    least_s = max(
        rows * kernel_bytes_axk1.latent_bytes_per_token_layer(config)
        / peaks["hbm_bytes_per_s"],
        rows * kernel_bytes_axk1.latent_ops_per_token_layer(config)
        / peaks["bf16_flops_per_s"])
    if not least_s:
        return None
    return 100.0 * least_s / t["op_s"][KERNEL]
