"""K-EXAONE's per-layer metrics (PR 34) on a made-up trace and counters,
the byte functions they stand on against the configuration's numbers
written out, and the configuration's file against the catalog's numbers,
the preset and the traffic ISSUE 34 gives."""

import json
import os

import pytest

from benchmark import kernel_bytes, kernel_bytes_kexaone as kb, metrics, traffic

from declared_cell import check_declared
from paired_trace import paired

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "k-exaone-int8-ep8.mixed-lengths"
DECLARED = ("k-exaone-int8-ep8", "mixed-lengths", 1)   # config, traffic, chips
NEW = ["full_attn_share", "full_attn_roofline", "swa_attn_share",
       "swa_attn_roofline", "ep8_experts_share", "ep8_experts_roofline",
       "ep8_touched_share"]


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "k-exaone-int8-ep8.json")) as f:
        return json.load(f)


# A window of 300 chunks of 8 steps at 64 slots, and 100 admissions of one
# pass: 2,500 passes of 11 expert layers; 12 of the 16 held experts touched
# a layer pass; one routed pair in 8 held; 60 rows a decode step that hold
# 2,000 tokens each, 125 of them inside the window.
PASSES = (300 * 8 + 100) * 11.0
COUNTERS = {
    "moe_layer_passes": PASSES,
    "moe_experts_touched": PASSES * 12,
    "moe_routed_pairs": 3.2e6,
    "moe_held_pairs": 4.0e5,
    "batcher_decode_slot_steps": 300 * 8 * 64.0,
    "attn_decode_resident_tokens": 300 * 8 * 60 * 2000.0,
    "swa_decode_window_tokens": 300 * 8 * 60 * 125.0,
}
# 6 traced seconds: 30 decode chunks, 10 admissions of 1,500 tokens.
TRACE = {
    "busy_s": 5.5,
    "op_s": {"moe_experts": 2.2, "paged_decode_attn": 0.55,
             "swa_decode_attn": 0.11, "_quant_matmul_2d": 1.4},
    "module_count": {"jit_decode_chunk": 30.0, "jit_admit_row_paged": 10.0},
    "module_s": {},
}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def ctx(**over):
    return {"counters": COUNTERS, "trace": paired(TRACE, [1500] * 10),
            "peaks": PEAKS, "config": config(),
            "trace_counters": {"batcher_prefix_cache_miss_tokens": 15000.0},
            **over}


def test_shares_of_busy_time_and_of_the_held_experts():
    assert metrics.read_layer_metric("full_attn_share", ctx()) == \
        (pytest.approx(10.0), "%")
    assert metrics.read_layer_metric("swa_attn_share", ctx()) == \
        (pytest.approx(2.0), "%")
    assert metrics.read_layer_metric("ep8_experts_share", ctx()) == \
        (pytest.approx(40.0), "%")
    assert metrics.read_layer_metric("ep8_touched_share", ctx()) == \
        (pytest.approx(75.0), "%")


def test_the_two_attention_rooflines():
    """240 traced steps x 60 rows: 2,000 resident tokens x 3 full layers
    and 125 window tokens x 9 windowed layers, 4,096 bytes each."""
    full = 240 * 60 * 2000 * 3 * 4096 / 819e9
    ring = 240 * 60 * 125 * 9 * 4096 / 819e9
    assert metrics.read_layer_metric("full_attn_roofline", ctx()) == \
        (pytest.approx(100 * full / 0.55), "%")
    assert metrics.read_layer_metric("swa_attn_roofline", ctx()) == \
        (pytest.approx(100 * ring / 0.11), "%")


def test_expert_roofline():
    """Decode steps stream the touched share of 11 x 16 held experts;
    admissions take the larger of that and their held pairs' arithmetic."""
    c = config()
    per_pass = 11 * 16 * 37_748_736 * 1.03125 * 0.75
    held_pairs = 15000 * 8 * 11 / 8
    least = 240 * per_pass / 819e9 + max(
        10 * per_pass / 819e9, 2 * held_pairs * 37_748_736 / 197e12)
    got = metrics.read_layer_metric("ep8_experts_roofline", ctx())
    assert got == (pytest.approx(100 * least / 2.2), "%")
    assert kb.held_experts_bytes(c) * 0.75 == pytest.approx(per_pass)


@pytest.mark.parametrize("name,counter,factor", [
    ("full_attn_roofline", "attn_decode_resident_tokens", 4),
    ("swa_attn_roofline", "swa_decode_window_tokens", 2),
    ("ep8_experts_roofline", "moe_experts_touched", 4),
    ("ep8_touched_share", "moe_experts_touched", 4),
])
def test_a_wrong_count_is_not_hidden(name, counter, factor):
    """Nothing is clamped: a count several times too high reads over
    100%."""
    wrong = {**COUNTERS, counter: factor * COUNTERS[counter]}
    assert metrics.read_layer_metric(name, ctx())[0] < 100
    assert metrics.read_layer_metric(name, ctx(counters=wrong))[0] > 100


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
    # The parent's program on THIS configuration's file (it cannot build
    # the preset, but a reader must not raise whatever it is handed): the
    # counters and the rings' kernel are missing.
    assert name == "full_attn_share" or metrics.read_layer_metric(
        name, {**bare, "config": config()}) is None
    if name.startswith("ep8_experts"):
        # A.X-K1's trace has the expert kernel and its file the router's
        # outputs, under other keys: held_* are its metrics.
        with open(os.path.join(ROOT, "benchmark", "configs",
                               "ax-k1-int8-ep16.json")) as f:
            axk1 = json.load(f)
        assert metrics.read_layer_metric(name, ctx(config=axk1)) is None


def test_bytes_against_the_configurations_numbers_written_out():
    c = config()
    assert kb.kv_bytes_per_token_layer(c) == 4096
    assert (kb.full_layers(c), kb.window_layers(c)) == (3, 9)
    assert kb.pool_bytes_per_token(c) == 12_288
    assert kb.ring_bytes(c) == 301_989_888  # 0.30 GB
    assert kb.attn_ops_per_token_layer(c) == 32_768
    assert kb.expert_weights(c) == 37_748_736
    assert kb.expert_bytes(c) == pytest.approx(38.93e6, rel=1e-3)
    assert kb.attention_weights_per_layer(c) == 113_246_208
    assert kb.expert_layers(c) == 11
    assert kb.weight_bytes(c) == pytest.approx(9.54e9, rel=1e-3)
    # What _quant_matmul_2d streams a pass: the mean layer times 12.
    assert kb.quant_matmul_weights(c) == 2_113_929_216
    assert kernel_bytes.quant_matmul_weights(c) == 2_113_929_216
    assert c["matmuls_per_layer"] == [
        [6144, 8192], [6144, 1024], [6144, 1024], [8192, 6144],
        [6144, 10240]]


def test_the_program_agrees_with_the_byte_functions():
    """count_params, page_bytes and the rings of the preset against the
    file's."""
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
    s = c["serve"]
    assert kv_cache.page_bytes(cfg, s["page_size"]) == 786_432 == \
        s["page_size"] * kb.pool_bytes_per_token(c)
    pool = jax.eval_shape(lambda: kv_cache.make_pool(
        cfg, s["paged_pages"], s["page_size"], slots=s["slots"]))
    assert pool.k.shape[0] == kb.full_layers(c)
    rings = 2 * pool.ring_k.size * pool.ring_k.dtype.itemsize
    assert rings == kb.ring_bytes(c)
    paged = s["paged_pages"] * 786_432
    assert paged == pytest.approx(2.92e9, rel=1e-3)
    assert (stored + paged + rings) / 15.75e9 > 0.8


def test_the_configuration_carries_every_published_number():
    c = config()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry = [x for x in m["configs"] if x["name"] == c["name"]][0]
    assert entry["source"] == c["source"]
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "max_position_embeddings"]
    published = {
        "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 6144, "intermediate_size": 18432,
        "model_type": "exaone_moe", "moe_intermediate_size": 2048,
        "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0],
        "n_group": 1, "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_key_value_heads": 8,
        "num_nextn_predict_layers": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "sliding_window": 128, "sliding_window_pattern": "LLLG",
        "tie_word_embeddings": False, "topk_group": 1,
    }
    assert {k: c[k] for k in published} == published
    # The three per-layer lists stay whole, as published (48 entries); the
    # 12 held layers are their first 12.
    period = ["sliding_attention"] * 3 + ["full_attention"]
    assert c["layer_types"] == period * 12
    assert c["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert c["sliding_windows"] == [128, 128, 128, 0] * 12
    assert kb.held_layer_types(c) == period * 3
    assert c["published"] == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 153600,
        "max_position_embeddings": 262144}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"],
            c["router_outputs"]) == (12, 16, 19200, 128)
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert c["max_position_embeddings"] == c["serve"]["max_len"] == 8192
    d = c["deployment"]
    assert d["chips"] == 32 and "4 pipeline stages of 8" in d["layout"]
    assert "multi-token-prediction" in d["not_modelled"]
    assert "--prefix-cache" not in c["serve"]["extra_argv"]
    assert set(c["assumed"]) >= {"qk_norm", "no_rope_on_full_layers",
                                 "norm_placement", "router", "weights",
                                 "tokenizer"}
    assert set(c["reduced_why"]) == set(c["reduced"])


def test_the_preset_is_the_configuration():
    from distributed_llms_tpu.models.presets import get_preset

    c, p = config(), get_preset(config()["preset"])
    assert (p.hidden_size, p.intermediate_size, p.expert_size, p.num_layers,
            p.num_dense_layers, p.num_heads, p.num_kv_heads, p.head_dim_,
            p.vocab_size, p.num_experts, p.held_experts, p.experts_offset,
            p.num_experts_per_token, p.n_shared_experts, p.moe_n_group,
            p.moe_topk_group, p.sliding_window) == (
        c["hidden_size"], c["intermediate_size"], c["moe_intermediate_size"],
        c["num_hidden_layers"], c["first_k_dense_replace"],
        c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
        c["vocab_size"], c["router_outputs"], c["num_experts"], 0,
        c["num_experts_per_tok"], c["num_shared_experts"], c["n_group"],
        c["topk_group"], c["sliding_window"])
    assert (p.norm_eps, p.rope_theta, p.rope_scaling_factor, p.moe_norm_topk,
            p.moe_routed_scale, p.moe_score_fn, p.moe_expert_bias,
            p.moe_capacity, p.tie_embeddings, p.qkv_bias) == (
        c["rms_norm_eps"], c["rope_parameters"]["rope_theta"], 1.0,
        c["norm_topk_prob"], c["routed_scaling_factor"], c["scoring_func"],
        False, False, c["tie_word_embeddings"], False)
    kinds = {"sliding_attention": "swa", "full_attention": "attn"}
    assert list(p.layer_types) == [kinds[t] for t in kb.held_layer_types(c)]
    # The three things the config has no key for (its ``assumed``).
    assert p.qk_norm and not p.attn_rope and p.moe_norm_eps == 1e-20
    assert p.max_seq_len == c["published"]["max_position_embeddings"]


def test_the_cell_and_its_metrics_are_declared():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    check_declared(m, CELL, DECLARED, NEW)
    # This cell's counter ratios are data files, the rest code.
    for x in (x for x in m["per_layer"] if x["name"] in NEW):
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", x["name"] + (
                ".json" if x["source"] == "program_counter" else ".py")))


def test_the_mix_fits_the_pool_and_is_what_the_issue_gives():
    c, spec = config(), traffic.load("mixed-lengths")
    need = traffic.worst_case_pages(spec, c["serve"]["page_size"])
    assert need == 3653
    assert c["serve"]["paged_pages"] == -(-(need + 1) // 64) * 64 == 3712
    assert (spec["clients"], len(spec["sessions"]), spec["preroll_s"],
            spec["rate_rps"]) == (64, 128, 24, None)
    turns = [s["turns"][0] for s in spec["sessions"]]
    assert all(len(s["turns"]) == 1 and not s["shared"]
               for s in spec["sessions"])
    long_at = [i for i, (p, _) in enumerate(turns) if p >= 3072]
    assert long_at == list(range(3, 128, 4))
    longs = sorted(turns[i][0] for i in long_at)
    shorts = sorted(p for i, (p, _) in enumerate(turns) if i not in long_at)
    answers = sorted(a for _, a in turns)
    assert (shorts[0], shorts[-1], longs[0], longs[-1], answers[0],
            answers[-1]) == (32, 1024, 3134, 6978, 128, 1024)
    assert 250 <= (shorts[47] + shorts[48]) / 2 <= 262
    assert 630 <= (answers[63] + answers[64]) / 2 <= 650
    assert max(p + a + 1 for p, a in turns) <= c["serve"]["max_len"]
    # A quarter of the scripts send only long requests: script j walks
    # sessions j, j + 64 of each cycle.
    long_scripts = [j for j in range(64)
                    if all(turns[i][0] >= 3072 for i in (j, j + 64))]
    mixed = [j for j in range(64)
             if (turns[j][0] >= 3072) != (turns[j + 64][0] >= 3072)]
    assert len(long_scripts) == 16 and not mixed
    # Long prompts meet short and long answers alike.
    long_answers = [turns[i][1] for i in long_at]
    assert min(long_answers) < 200 and max(long_answers) == 1024
    assert sorted({traffic.bucket(p + 1) for p, _ in turns}) == [
        64, 128, 256, 512, 1024, 2048, 4096, 8192]
