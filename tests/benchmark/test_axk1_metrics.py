"""A.X-K1's per-layer metrics (PR 32) on a made-up trace and counters, the
byte functions they stand on against the configuration's numbers written
out, and the configuration's file against the catalog's numbers and the
preset."""

import json
import os

import pytest

from benchmark import kernel_bytes, kernel_bytes_axk1 as kb, metrics, traffic

from declared_cell import check_declared
from paired_trace import paired

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "ax-k1-int8-ep16.long-answers"
DECLARED = ("ax-k1-int8-ep16", "long-answers", 1)   # config, traffic, chips
NEW = ["mla_attn_share", "mla_attn_roofline", "held_experts_share",
       "held_experts_roofline", "held_touched_share", "held_pair_share"]


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ax-k1-int8-ep16.json")) as f:
        return json.load(f)


# A window of 300 chunks of 8 steps at 64 slots, and 150 admissions: 2,550
# passes of 12 expert layers; 9 of the 12 held experts touched a layer
# pass; one routed pair in 16 held; 60 rows of 1,500 tokens a decode step.
PASSES = (300 * 8 + 150) * 12.0
COUNTERS = {
    "moe_layer_passes": PASSES,
    "moe_experts_touched": PASSES * 9,
    "moe_routed_pairs": 3.2e6,
    "moe_held_pairs": 2.0e5,
    "moe_max_load_tokens": 1.0e5,
    "batcher_decode_slot_steps": 300 * 8 * 64.0,
    "mla_decode_resident_tokens": 300 * 8 * 60 * 1500.0,
}
# 6 traced seconds: 30 decode chunks, 15 admissions of 600 tokens.
TRACE = {
    "busy_s": 5.5,
    "op_s": {"moe_experts": 2.2, "mla_paged_decode_attn": 1.1,
             "_quant_matmul_2d": 1.4},
    "module_count": {"jit_decode_chunk": 30.0, "jit_admit_row_paged": 15.0},
    "module_s": {},
}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def ctx(**over):
    return {"counters": COUNTERS, "trace": paired(TRACE, [600] * 15),
            "peaks": PEAKS, "config": config(),
            "trace_counters": {"batcher_prefix_cache_miss_tokens": 9000.0},
            **over}


def test_counter_readers():
    assert metrics.read_layer_metric("held_touched_share", ctx()) == \
        (pytest.approx(75.0), "%")
    assert metrics.read_layer_metric("held_pair_share", ctx()) == \
        (pytest.approx(6.25), "%")


def test_shares_of_busy_time():
    assert metrics.read_layer_metric("mla_attn_share", ctx()) == \
        (pytest.approx(20.0), "%")
    assert metrics.read_layer_metric("held_experts_share", ctx()) == \
        (pytest.approx(40.0), "%")


def test_latent_attention_roofline_takes_the_larger_bound():
    """240 traced steps x 90,000 resident tokens x 13 layers: their bytes
    take 0.395 s, their operations 0.1985 s; the kernel ran 1.1 s."""
    rows = 240 * 60 * 1500 * 13
    by_bytes, by_ops = rows * 1152 / 819e9, rows * 139264 / 197e12
    assert by_bytes > by_ops  # 121 operations a byte against the ridge's 240
    got = metrics.read_layer_metric("mla_attn_roofline", ctx())
    assert got == (pytest.approx(100 * by_bytes / 1.1), "%")
    # Were the chip's arithmetic the slower side, that bound would decide.
    slow = {**PEAKS, "bf16_flops_per_s": 197e12 / 4}
    assert metrics.read_layer_metric("mla_attn_roofline", ctx(peaks=slow)) \
        == (pytest.approx(100 * 4 * by_ops / 1.1), "%")


def test_held_experts_roofline():
    """Decode steps stream the touched share of 12 x 12 held experts;
    admissions take the larger of that and their held pairs' arithmetic."""
    c = config()
    per_pass = 12 * 12 * 44_040_192 * 1.03125 * 0.75
    held_pairs = 9000 * 8 * 12 / 16
    least = 240 * per_pass / 819e9 + max(
        15 * per_pass / 819e9, 2 * held_pairs * 44_040_192 / 197e12)
    got = metrics.read_layer_metric("held_experts_roofline", ctx())
    assert got == (pytest.approx(100 * least / 2.2), "%")
    assert kb.held_experts_bytes(c) * 0.75 == pytest.approx(per_pass)


def test_a_wrong_count_is_not_hidden():
    """Nothing is clamped: twice the resident tokens, or every expert
    counted touched four times, reads over 100%."""
    doubled = {**COUNTERS, "mla_decode_resident_tokens":
               8 * COUNTERS["mla_decode_resident_tokens"]}
    assert metrics.read_layer_metric(
        "mla_attn_roofline", ctx(counters=doubled))[0] > 100
    touched = {**COUNTERS, "moe_experts_touched": PASSES * 12 * 4}
    assert metrics.read_layer_metric(
        "held_experts_roofline", ctx(counters=touched))[0] > 100
    assert metrics.read_layer_metric(
        "held_touched_share", ctx(counters=touched))[0] > 100


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_kernels_and_counters_reads_nothing(name):
    """The parent commit, or another configuration: no such kernel in the
    trace, no such counter, no such key in the configuration's file."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen2-7b-int8.json")) as f:
        other = json.load(f)
    bare = ctx(
        counters={"batcher_decode_slot_steps": 1000.0},
        trace={**TRACE, "op_s": {"_quant_matmul_2d": 1.4,
                                 "paged_decode_attn": 0.5}},
        config=other)
    assert metrics.read_layer_metric(name, bare) is None
    assert metrics.read_layer_metric(name, {**bare, "trace": None}) is None
    if name.startswith("held_experts"):
        # lfm2's trace has the expert kernel; its file holds every expert.
        with open(os.path.join(ROOT, "benchmark", "configs",
                               "lfm2-8b-a1b-int8.json")) as f:
            lfm2 = json.load(f)
        assert metrics.read_layer_metric(name, ctx(config=lfm2)) is None


def test_bytes_against_the_configurations_numbers_written_out():
    c = config()
    assert kb.latent_bytes_per_token_layer(c) == 1152
    assert kb.latent_ops_per_token_layer(c) == 139_264
    assert kb.expert_weights(c) == 44_040_192
    assert kb.expert_bytes(c) == pytest.approx(45.4e6, rel=1e-3)
    assert kb.attention_weights_per_layer(c) == 101_122_048
    assert kb.expert_layers(c) == 12
    # ISSUE 32's reckoning, every block weight int8: 9.50 GB; as stored,
    # with W_kva and W_kvb in bfloat16: 9.66 GB.
    assert kb.weight_bytes(c, wkv_bf16=False) == pytest.approx(9.50e9, rel=2e-3)
    assert kb.weight_bytes(c) == pytest.approx(9.66e9, rel=2e-3)
    # What _quant_matmul_2d streams a pass: the mean layer times 13.
    attn = 13 * (7168 * 1536 + 1536 * 12288 + 8192 * 7168)
    dense, shared = 3 * 7168 * 18432, 12 * 3 * 7168 * 2048
    assert kernel_bytes.quant_matmul_weights(c) == \
        pytest.approx(attn + dense + shared, rel=1e-12)
    assert attn + dense + shared == 2_076_704_768


def test_the_program_agrees_with_the_byte_functions():
    """count_params and page_bytes of the preset against the file's."""
    import jax
    import jax.numpy as jnp

    from distributed_llms_tpu.models import kv_cache, model as model_lib
    from distributed_llms_tpu.models.presets import get_preset

    c, cfg = config(), get_preset(config()["preset"])
    shapes = jax.eval_shape(
        lambda k: model_lib.init_params_quantized(k, cfg, 8),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    stored = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert stored == pytest.approx(kb.weight_bytes(c), rel=1e-4)  # + norms
    # A token's row a layer: 1,280 bytes stored, 1,152 of them values.
    blk = c["serve"]["page_size"]
    assert kv_cache.page_bytes(cfg, blk) == 13 * blk * 1280
    assert cfg.latent_width * 2 - kb.latent_bytes_per_token_layer(c) == 128
    pool = c["serve"]["paged_pages"] * kv_cache.page_bytes(cfg, blk)
    assert pool == pytest.approx(2.317e9, rel=1e-3)
    assert (stored + pool) / 15.75e9 > 0.6


def test_the_configuration_carries_every_published_number():
    c = config()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry = [x for x in m["configs"] if x["name"] == c["name"]][0]
    assert entry["source"] == c["source"]
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings"]
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 7168,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "model_type": "axk1", "moe_intermediate_size": 2048,
        "moe_layer_freq": 1, "n_group": 8, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_key_value_heads": 64,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "seq_aux": True, "tie_word_embeddings": False, "topk_group": 4,
        "topk_method": "none", "v_head_dim": 128,
    }
    assert {k: c[k] for k in published} == published
    assert c["published"] == {
        "num_hidden_layers": 61, "n_routed_experts": 192,
        "vocab_size": 163840, "max_position_embeddings": 131072}
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"],
            c["router_outputs"]) == (13, 12, 20480, 192)
    assert c["max_position_embeddings"] == c["serve"]["max_len"] == 4096
    assert c["deployment"]["chips"] == 64 and "16" in c["deployment"]["layout"]
    assert "--prefix-cache" in c["serve"]["extra_argv"]
    assert set(c["assumed"]) >= {"group_score", "rope_layout", "weights",
                                 "wkv_a_wkv_b", "tokenizer"}
    assert set(c["reduced_why"]) == set(c["reduced"])


def test_the_preset_is_the_configuration():
    from distributed_llms_tpu.models.presets import get_preset

    c, p = config(), get_preset(config()["preset"])
    rs = c["rope_scaling"]
    assert (p.hidden_size, p.intermediate_size, p.expert_size, p.num_layers,
            p.num_dense_layers, p.num_heads, p.vocab_size, p.num_experts,
            p.held_experts, p.experts_offset, p.num_experts_per_token,
            p.n_shared_experts, p.moe_n_group, p.moe_topk_group) == (
        c["hidden_size"], c["intermediate_size"], c["moe_intermediate_size"],
        c["num_hidden_layers"], c["first_k_dense_replace"],
        c["num_attention_heads"], c["vocab_size"], c["router_outputs"],
        c["n_routed_experts"], 0, c["num_experts_per_tok"],
        c["n_shared_experts"], c["n_group"], c["topk_group"])
    assert (p.q_lora_rank, p.kv_lora_rank, p.qk_nope_head_dim,
            p.qk_rope_head_dim, p.v_head_dim) == (
        c["q_lora_rank"], c["kv_lora_rank"], c["qk_nope_head_dim"],
        c["qk_rope_head_dim"], c["v_head_dim"])
    assert (p.rope_scaling_type, p.rope_scaling_factor,
            p.rope_original_max_len, p.yarn_beta_fast, p.yarn_beta_slow,
            p.yarn_mscale, p.yarn_mscale_all_dim) == (
        rs["type"], rs["factor"], rs["original_max_position_embeddings"],
        rs["beta_fast"], rs["beta_slow"], rs["mscale"], rs["mscale_all_dim"])
    assert (p.norm_eps, p.rope_theta, p.moe_norm_topk, p.moe_routed_scale,
            p.moe_score_fn, p.moe_expert_bias, p.moe_capacity,
            p.tie_embeddings) == (
        c["rms_norm_eps"], c["rope_theta"], c["norm_topk_prob"],
        c["routed_scaling_factor"], c["scoring_func"], False, False,
        c["tie_word_embeddings"])
    assert set(p.layer_types) == {"mla"}
    assert p.max_seq_len == c["published"]["max_position_embeddings"]


def test_the_cell_and_its_metrics_are_declared():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    check_declared(m, CELL, DECLARED, NEW)


def test_the_mix_fits_the_pool_and_is_what_the_issue_gives():
    c, spec = config(), traffic.load("long-answers")
    need = traffic.worst_case_pages(spec, c["serve"]["page_size"])
    assert need == 2121
    assert c["serve"]["paged_pages"] == -(-(need + 1) // 64) * 64 == 2176
    assert (spec["clients"], len(spec["sessions"]), spec["preroll_s"],
            spec["rate_rps"]) == (64, 128, 20, None)
    turns = [t for s in spec["sessions"] for t in s["turns"]]
    assert len(turns) == 128 and not any(s["shared"] for s in spec["sessions"])
    prompts, answers = sorted(p for p, _ in turns), sorted(a for _, a in turns)
    assert (prompts[0], prompts[-1], answers[0], answers[-1]) == \
        (64, 2040, 256, 2048)
    assert 370 <= (prompts[63] + prompts[64]) / 2 <= 400
    assert 750 <= (answers[63] + answers[64]) / 2 <= 790
    assert max(p + a + 1 for p, a in turns) <= c["serve"]["max_len"]
