"""The expert layer's per-layer metrics (PR 28) on a made-up trace and
counters, the byte functions they stand on, and the configuration's file
against the catalog's numbers."""

import json
import os

import pytest

from benchmark import kernel_bytes, kernel_bytes_moe, metrics

from declared_cell import check_declared
from paired_trace import paired

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "lfm2-8b-a1b-int8.chat"
DECLARED = ("lfm2-8b-a1b-int8", "chat", 1)     # config, traffic, chips
NEW = ["moe_experts_share", "moe_experts_roofline", "moe_touched_share",
       "moe_load_imbalance"]


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-8b-a1b-int8.json")) as f:
        return json.load(f)


# A window of 70 chunks of 8 steps and 72 admissions: 632 passes of 22 expert
# layers, 0.875 of the experts touched a layer pass; the fullest expert held
# 1.5 times its even share.
COUNTERS = {
    "moe_layer_passes": 632.0 * 22,
    "moe_experts_touched": 632.0 * 22 * 28,
    "moe_routed_pairs": 4.0e6,
    "moe_max_load_tokens": 4.0e6 * 1.5 / 32,
}
# 6 traced seconds: 8 decode chunks, 9 admissions, the kernel busy 3 s of 5.
TRACE = {
    "busy_s": 5.0,
    "op_s": {"moe_experts": 3.0, "_quant_matmul_2d": 0.5},
    "module_count": {"jit_decode_chunk": 8.0, "jit_admit_row_paged": 9.0},
    "module_s": {},
}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def ctx(tokens=0.0, **over):
    """The nine admissions paired, ``tokens`` prompt tokens in all."""
    return {"counters": COUNTERS, "trace": paired(TRACE, [tokens / 9] * 9),
            "peaks": PEAKS, "config": config(),
            "trace_counters": {"batcher_prefix_cache_miss_tokens": 0.0},
            **over}


def test_counter_readers():
    assert metrics.read_layer_metric("moe_touched_share", ctx()) == \
        (pytest.approx(87.5), "%")
    assert metrics.read_layer_metric("moe_load_imbalance", ctx()) == \
        (pytest.approx(1.5), "ratio")


def test_share_of_busy_time():
    assert metrics.read_layer_metric("moe_experts_share", ctx()) == \
        (pytest.approx(60.0), "%")


def test_roofline_is_bytes_for_decode_and_the_larger_bound_for_admissions():
    touched = 0.875 * kernel_bytes_moe.all_experts_bytes(config())
    least = (8 * 8 + 9) * touched / 819e9
    assert metrics.read_layer_metric("moe_experts_roofline", ctx()) == \
        (pytest.approx(100 * least / 3.0), "%")
    # Nine admissions of 30,000 prompt tokens in all are bound by
    # arithmetic: each token through 4 experts of 11M weights in 22 layers.
    flops = 2 * 30e3 * 4 * 22 * 3 * 2048 * 1792 / 197e12
    assert flops == kernel_bytes_moe.routed_flops(config(), 30e3) / 197e12
    assert flops > 9 * touched / 819e9
    got = metrics.read_layer_metric("moe_experts_roofline", ctx(30e3))
    assert got[0] == pytest.approx(100 * (64 * touched / 819e9 + flops) / 3.0)
    assert got[0] < 100.0
    # A counter window wider than the trace cannot count more prompt
    # tokens than the traced admissions held: since PR 52 the tokens are
    # the paired admissions' own and no host counter is read for them.
    wide = metrics.read_layer_metric("moe_experts_roofline", ctx(
        30e3, trace_counters={"batcher_prefix_cache_miss_tokens": 1e9}))
    assert wide == got
    # The larger bound is taken an admission at a time: one of the nine
    # with every token leaves eight bound by their bytes.
    one = ctx()
    one["trace"]["admissions"][0]["tokens"] = 30e3
    assert metrics.read_layer_metric("moe_experts_roofline", one)[0] == \
        pytest.approx(100 * ((64 + 8) * touched / 819e9 + flops) / 3.0)
    # Admissions that could not be paired are not guessed.
    assert metrics.read_layer_metric("moe_experts_roofline", ctx(
        trace={**TRACE, "admissions": None, "decode": one["trace"]["decode"]}
    )) is None


def test_a_wrong_count_of_touched_experts_is_not_hidden():
    """Nothing clamps the touched share: counters that say more experts
    were touched than there are read over 100%, where the driver sees it."""
    wrong = dict(COUNTERS, moe_experts_touched=632.0 * 22 * 32 * 6)
    got = metrics.read_layer_metric("moe_experts_roofline",
                                    ctx(counters=wrong))
    assert got[0] > 100.0


@pytest.mark.parametrize("name", ["moe_experts_share", "moe_experts_roofline",
                                  "moe_touched_share", "moe_load_imbalance"])
def test_a_program_without_experts_reads_nothing(name):
    """The parent commit has neither the kernel nor the counters: the
    readers return nothing and do not raise."""
    bare = {"counters": {}, "trace": {"busy_s": 5.0, "op_s": {},
                                      "module_count": {}, "module_s": {}},
            "peaks": PEAKS, "config": config(), "trace_counters": {}}
    assert metrics.read_layer_metric(name, bare) is None
    assert metrics.read_layer_metric(name, {**bare, "trace": None}) is None


def test_bytes_of_the_experts_and_of_the_other_matmuls():
    c = config()
    assert kernel_bytes_moe.expert_layers(c) == 22
    assert kernel_bytes_moe.expert_weights(c) == 3 * 2048 * 1792
    assert kernel_bytes_moe.all_experts_bytes(c) == \
        pytest.approx(7.993e9, rel=1e-3)
    # What _quant_matmul_2d streams a pass: every block matmul BUT the
    # experts, the mean layer times 24.
    conv = 18 * (2048 * 6144 + 2048 * 2048)
    attn = 6 * (2048 * (2048 + 512 + 512) + 2048 * 2048)
    dense = 2 * 3 * 2048 * 7168
    assert kernel_bytes.quant_matmul_weights(c) == conv + attn + dense \
        == 452_984_832
    assert kernel_bytes.quant_matmul_bytes_per_pass(c) == \
        pytest.approx(0.467e9, rel=1e-3)


def test_the_configuration_carries_every_published_number():
    c = config()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry = [x for x in m["configs"] if x["name"] == c["name"]][0]
    assert entry["reduced"] == c["reduced"] == ["max_position_embeddings"]
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536,
    }
    assert {k: c[k] for k in published} == published
    assert [i for i, t in enumerate(c["layer_types"])
            if t == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert c["max_position_embeddings"] == c["serve"]["max_len"] == 4096
    assert "--prefix-cache" not in c["serve"]["extra_argv"]
    assert set(c["assumed"]) >= {"tie_word_embeddings", "weights",
                                 "expert_bias", "tokenizer"}


def test_the_preset_is_the_configuration():
    from distributed_llms_tpu.models.presets import get_preset

    c, p = config(), get_preset(config()["preset"])
    assert (p.hidden_size, p.intermediate_size, p.expert_size, p.num_layers,
            p.num_dense_layers, p.num_heads, p.num_kv_heads, p.vocab_size,
            p.num_experts, p.num_experts_per_token, p.conv_kernel) == (
        c["hidden_size"], c["intermediate_size"], c["moe_intermediate_size"],
        c["num_hidden_layers"], c["num_dense_layers"],
        c["num_attention_heads"], c["num_key_value_heads"], c["vocab_size"],
        c["num_experts"], c["num_experts_per_tok"], c["conv_L_cache"])
    assert [t == "attn" for t in p.layer_types] == \
        [t == "full_attention" for t in c["layer_types"]]
    assert (p.norm_eps, p.rope_theta, p.moe_norm_topk, p.moe_expert_bias,
            p.moe_routed_scale, p.moe_score_fn, p.moe_capacity) == (
        c["norm_eps"], c["rope_theta"], c["norm_topk_prob"],
        c["use_expert_bias"], c["routed_scaling_factor"], "sigmoid", False)


def test_the_cell_and_its_metrics_are_declared():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    check_declared(m, CELL, DECLARED, NEW)
