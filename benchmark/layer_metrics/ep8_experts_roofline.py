"""The expert kernel against its roofline for K-EXAONE's share of the
routed experts: ``held_experts_roofline``'s reckoning from this
configuration's keys (``kernel_bytes_kexaone``: 16 held experts of 11
layers).  For each traced decode step the bytes of the held experts that
had a token over peak HBM bandwidth; for the traced admissions the larger
of the same and the arithmetic of the pairs that fell on a held expert over
the peak bf16 rate.

Both shares are ratios of WHOLE-WINDOW counters, steady over a window: the
held experts touched (``moe.experts_touched`` over held x
``moe.layer_passes``) and the pairs held a routed pair (``moe.held_pairs``
over ``moe.routed_pairs``); the admissions' prompt tokens are
``batcher.prefix_cache.miss_tokens`` of the counter window inside the
trace, never more than the traced admissions can have held.  Every term is
a lower bound (activations left out; an expert many row tiles chose counted
once; an admission above 2,048 tokens, which streams the stacks once a
block of 2,048, counted once), so the share reads low.  Nothing is clamped:
a count that is wrong shows as a share over 100%."""
from benchmark import kernel_bytes_kexaone

UNIT = "%"
KERNEL = "moe_experts"
ADMISSIONS = ("jit_admit_row",)
CHUNKS = ("jit_decode_chunk",)


def read(ctx):
    t, peaks, tc = ctx["trace"], ctx["peaks"], ctx.get("trace_counters")
    c, config = ctx["counters"], ctx["config"]
    if not t or not peaks or not tc or not t["op_s"].get(KERNEL):
        return None
    passes, routed = c.get("moe_layer_passes", 0.0), c.get(
        "moe_routed_pairs", 0.0)
    if (not passes or not routed or "router_outputs" not in config
            or "num_experts" not in config):
        return None
    touched = c.get("moe_experts_touched", 0.0) / (
        config["num_experts"] * passes)
    per_pass = kernel_bytes_kexaone.held_experts_bytes(config) * touched
    steps = config["serve"]["chunk_steps"]
    decode = sum(n * steps for name, n in t["module_count"].items()
                 if name.startswith(CHUNKS))
    admits = sum(n for name, n in t["module_count"].items()
                 if name.startswith(ADMISSIONS))
    tokens = min(tc.get("batcher_prefix_cache_miss_tokens", 0.0),
                 admits * config["serve"]["max_len"])
    held_pairs = (tokens * config["num_experts_per_tok"]
                  * kernel_bytes_kexaone.expert_layers(config)
                  * c.get("moe_held_pairs", 0.0) / routed)
    least_s = decode * per_pass / peaks["hbm_bytes_per_s"] + max(
        admits * per_pass / peaks["hbm_bytes_per_s"],
        kernel_bytes_kexaone.held_flops(config, held_pairs)
        / peaks["bf16_flops_per_s"])
    if not least_s:
        return None
    return 100.0 * least_s / t["op_s"][KERNEL]
