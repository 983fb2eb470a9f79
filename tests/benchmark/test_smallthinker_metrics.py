"""SmallThinker's per-layer metrics (PR 45) on a made-up trace and
counters, the byte functions they stand on against ISSUE 45's numbers
written out, and the configuration's file against the catalog's numbers,
the preset and the traffic the issue gives."""

import json
import math
import os
from statistics import NormalDist

import pytest

from benchmark import kernel_bytes, kernel_bytes_smallthinker as kb
from benchmark import metrics, traffic

from declared_cell import check_declared
from paired_trace import paired

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "smallthinker-21ba3b-int8.long-think"
DECLARED = ("smallthinker-21ba3b-int8", "long-think", 1)  # config, traffic, chips
NEW = ["st_swa_attn_share", "st_swa_attn_roofline", "st_full_attn_share",
       "st_full_attn_roofline", "st_experts_share", "st_experts_roofline",
       "st_touched_share", "st_ring_fill"]


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21ba3b-int8.json")) as f:
        return json.load(f)


# A window of 400 chunks of 8 steps at 32 slots, and 60 admissions of one
# pass: 3,260 passes of 12 expert layers; 60 of the 64 experts touched a
# layer pass; 30 rows a decode step that hold 5,000 tokens each, 3,000 of
# them inside the window (of the 4,096 their rings hold room for).
PASSES = (400 * 8 + 60) * 12.0
COUNTERS = {
    "moe_layer_passes": PASSES,
    "moe_experts_touched": PASSES * 60,
    "moe_routed_pairs": 9.0e6,
    "batcher_decode_slot_steps": 400 * 8 * 32.0,
    "attn_decode_resident_tokens": 400 * 8 * 30 * 5000.0,
    "swa_decode_window_tokens": 400 * 8 * 30 * 3000.0,
    "swa_decode_ring_tokens": 400 * 8 * 30 * 4096.0,
}
# 6 traced seconds: 45 decode chunks, 6 admissions of 4,000 tokens.
TRACE = {
    "busy_s": 5.6,
    "op_s": {"moe_experts": 2.8, "paged_decode_attn": 0.56,
             "swa_decode_attn": 1.12, "_quant_matmul_2d": 0.4},
    "module_count": {"jit_decode_chunk": 45.0, "jit_admit_row_paged": 6.0},
    "module_s": {},
}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


# The counter window inside the trace: 44 chunks dispatched, 28 rows a step
# that hold 4,000 tokens each, 2,500 of them inside the window (a trough:
# the whole window's rows hold 5,000 and 3,000).
TRACE_COUNTERS = {
    "batcher_prefix_cache_miss_tokens": 24000.0,
    "batcher_decode_slot_steps": 44 * 8 * 32.0,
    "attn_decode_resident_tokens": 44 * 8 * 28 * 4000.0,
    "swa_decode_window_tokens": 44 * 8 * 28 * 2500.0,
}


def ctx(**over):
    return {"counters": COUNTERS, "trace": paired(TRACE, [4000] * 6),
            "peaks": PEAKS, "config": config(),
            "trace_counters": TRACE_COUNTERS, **over}


def test_shares_of_busy_time_of_the_experts_and_of_the_rings():
    assert metrics.read_layer_metric("st_swa_attn_share", ctx()) == \
        (pytest.approx(20.0), "%")
    assert metrics.read_layer_metric("st_full_attn_share", ctx()) == \
        (pytest.approx(10.0), "%")
    assert metrics.read_layer_metric("st_experts_share", ctx()) == \
        (pytest.approx(50.0), "%")
    assert metrics.read_layer_metric("st_touched_share", ctx()) == \
        (pytest.approx(100 * 60 / 64), "%")
    assert metrics.read_layer_metric("st_ring_fill", ctx()) == \
        (pytest.approx(100 * 3000 / 4096), "%")


def test_the_two_attention_rooflines():
    """360 traced steps x the 28 rows a step of the counter window INSIDE
    the trace: 4,000 resident tokens x 3 full layers and 2,500 LIVE window
    tokens x 9 windowed layers, 2,048 bytes each; the whole window's mean
    (30 rows of 5,000 and 3,000) is not what the traced steps read."""
    full = 360 * 28 * 4000 * 3 * 2048 / 819e9
    ring = 360 * 28 * 2500 * 9 * 2048 / 819e9
    assert metrics.read_layer_metric("st_full_attn_roofline", ctx()) == \
        (pytest.approx(100 * full / 0.56), "%")
    assert metrics.read_layer_metric("st_swa_attn_roofline", ctx()) == \
        (pytest.approx(100 * ring / 1.12), "%")


def test_expert_roofline():
    """Decode steps stream the touched share of 12 x 64 experts;
    admissions take the larger of that and their pairs' arithmetic."""
    c = config()
    per_pass = 12 * 64 * 5_898_240 * 1.03125 * 60 / 64
    pairs = 24000 * 6 * 12
    least = 360 * per_pass / 819e9 + max(
        6 * per_pass / 819e9, 2 * pairs * 5_898_240 / 197e12)
    got = metrics.read_layer_metric("st_experts_roofline", ctx())
    assert got == (pytest.approx(100 * least / 2.8), "%")
    assert kb.all_experts_bytes(c) * 60 / 64 == pytest.approx(per_pass)


@pytest.mark.parametrize("name,counter,factor", [
    ("st_full_attn_roofline", "attn_decode_resident_tokens", 4),
    ("st_swa_attn_roofline", "swa_decode_window_tokens", 4),
    ("st_experts_roofline", "moe_experts_touched", 3),
    ("st_touched_share", "moe_experts_touched", 2),
    ("st_ring_fill", "swa_decode_window_tokens", 2),
])
def test_a_wrong_count_is_not_hidden(name, counter, factor):
    """Nothing is clamped: a count several times too high reads over
    100%."""
    wrong = {"counters": {**COUNTERS, counter: factor * COUNTERS[counter]}}
    if name.endswith("_attn_roofline"):  # (read inside the trace)
        wrong = {"trace_counters": {
            **TRACE_COUNTERS, counter: factor * TRACE_COUNTERS[counter]}}
    assert metrics.read_layer_metric(name, ctx())[0] < 100
    assert metrics.read_layer_metric(name, ctx(**wrong))[0] > 100


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_kernels_and_counters_reads_nothing(name):
    """The parent commit, or another configuration: no such kernel in the
    trace, no such counter, no such key in the configuration's file; and
    no reader raises, whatever it is handed."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "k-exaone-int8-ep8.json")) as f:
        other = json.load(f)
    # K-EXAONE's run has all three kernels and every counter but the new
    # one: this cell's readers still read nothing of it.
    assert metrics.read_layer_metric(name, ctx(
        config=other, counters={
            k: v for k, v in COUNTERS.items()
            if k != "swa_decode_ring_tokens"})) is None or name in (
                "st_touched_share",)  # (a ratio of counters: a data file)
    bare = ctx(
        counters={"batcher_decode_slot_steps": 1000.0},
        trace={**TRACE, "op_s": {"_quant_matmul_2d": 1.4}}, config=other)
    assert metrics.read_layer_metric(name, bare) is None
    assert metrics.read_layer_metric(name, {**bare, "trace": None}) is None
    # The parent's program given THIS configuration's file cannot build
    # the preset; a reader handed its file and no counters reads nothing.
    assert metrics.read_layer_metric(
        name, {**bare, "config": config()}) is None
    assert metrics.read_layer_metric(
        name, {**bare, "config": config(), "trace_counters": None}) is None


def test_bytes_against_the_issues_numbers_written_out():
    c = config()
    assert kb.kv_bytes_per_token_layer(c) == 2048
    assert (kb.full_layers(c), kb.window_layers(c)) == (3, 9)
    assert kb.pool_bytes_per_token(c) == 6_144
    assert kb.ring_bytes(c) == 2_415_919_104  # 2.42 GB
    assert kb.ring_bytes(c) // (9 * 32) == 8_388_608  # a slot a layer
    assert kb.expert_weights(c) == 5_898_240
    assert 64 * kb.expert_weights(c) == 377_487_360
    assert kb.attention_weights_per_layer(c) == 20_971_520
    assert c["hidden_size"] * 64 == 163_840  # the router, float32
    assert kb.layer_bytes(c) == pytest.approx(0.4116e9, rel=2e-4)
    assert 2 * c["vocab_size"] * c["hidden_size"] * 2 == pytest.approx(
        1.556e9, rel=2e-4)
    assert kb.weight_bytes(c) == pytest.approx(6.49e9, rel=1e-3)
    assert kb.all_experts_bytes(c) == pytest.approx(4.671e9, rel=1e-3)
    # What _quant_matmul_2d streams a pass: the attention of 12 layers.
    assert kernel_bytes.quant_matmul_weights(c) == 12 * 20_971_520
    assert c["matmuls_per_layer"] == [
        [2560, 3584], [2560, 512], [2560, 512], [3584, 2560]]
    # The head over all T positions that no admission may hold (float32).
    assert 16_384 * c["vocab_size"] * 4 == pytest.approx(9.96e9, rel=1e-3)
    # All 52 layers: 21.4 GB of blocks and 1.6 GB of embedding and head.
    assert 52 * kb.layer_bytes(c) == pytest.approx(21.4e9, rel=2e-3)


def test_the_program_agrees_with_the_byte_functions():
    """init_params_quantized, page_bytes and the rings of the preset
    against the file's."""
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
    assert kv_cache.page_bytes(cfg, s["page_size"]) == 393_216 == \
        s["page_size"] * kb.pool_bytes_per_token(c)
    pool = jax.eval_shape(lambda: kv_cache.make_pool(
        cfg, s["paged_pages"], s["page_size"], slots=s["slots"]))
    assert pool.k.shape[0] == kb.full_layers(c)
    rings = 2 * pool.ring_k.size * pool.ring_k.dtype.itemsize
    assert rings == kb.ring_bytes(c)
    paged = s["paged_pages"] * 393_216
    assert paged == pytest.approx(1.46e9, rel=1e-3)
    # 10.37 GB resident: over the driver's quarter of a chip's 16 GB.
    assert (stored + paged + rings) == pytest.approx(10.37e9, rel=1e-3)


def test_the_configuration_carries_every_published_number():
    c = config()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry = [x for x in m["configs"] if x["name"] == c["name"]][0]
    assert entry["source"] == c["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
        "blob/main/config.json")
    assert entry["file"] == "benchmark/configs/" + c["name"] + ".json"
    assert entry["reduced"] == c["reduced"] == ["num_hidden_layers"]
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_size": 4096, "tie_word_embeddings": False,
        "vocab_size": 151936,
    }
    assert {k: c[k] for k in published} == published
    # Both per-layer lists stay whole, as published (52 entries); the 12
    # held layers are their first 12.
    assert c["rope_layout"] == c["sliding_window_layout"] == [0, 1, 1, 1] * 13
    assert kb.held_layout(c) == [0, 1, 1, 1] * 3
    assert c["published"] == {"num_hidden_layers": 52}
    assert c["num_hidden_layers"] == 12
    assert c["max_position_embeddings"] == c["serve"]["max_len"] == 16384
    d = c["deployment"]
    assert d["chips"] == 4 and "12, 12, 12 and 16 layers" in d["layout"]
    assert "embedding AND the head" in d["this_chip"]
    assert "fewer than the 32 rows" in d["not_modelled"]
    assert "secondary experts" in d["not_modelled"]
    assert "--prefix-cache" not in c["serve"]["extra_argv"]
    assert (c["serve"]["slots"], c["serve"]["page_size"],
            c["serve"]["paged_pages"], c["serve"]["chunk_steps"]) == (
        32, 64, 3712, 8)
    assert set(c["assumed"]) >= {
        "router_input", "gate_act", "attention_bias", "qk_norm",
        "rope_layout", "window_edge", "weights", "tokenizer"}
    assert "llama.cpp" in c["assumed"]["router_input"]
    assert set(c["reduced_why"]) == set(c["reduced"])


def test_the_preset_is_the_configuration():
    from distributed_llms_tpu.models.presets import get_preset

    c, p = config(), get_preset(config()["preset"])
    assert (p.hidden_size, p.expert_size, p.num_layers, p.num_dense_layers,
            p.num_heads, p.num_kv_heads, p.head_dim_, p.vocab_size,
            p.num_experts, p.held_experts, p.num_experts_per_token,
            p.n_shared_experts, p.sliding_window, p.max_seq_len) == (
        c["hidden_size"], c["moe_ffn_hidden_size"], c["num_hidden_layers"],
        0, c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
        c["vocab_size"], c["moe_num_primary_experts"],
        c["moe_num_primary_experts"], c["moe_num_active_primary_experts"], 0,
        c["sliding_window_size"], c["max_position_embeddings"])
    assert (p.norm_eps, p.rope_theta, p.rope_scaling_factor, p.moe_score_fn,
            p.moe_expert_bias, p.moe_capacity, p.tie_embeddings, p.qkv_bias
            ) == (c["rms_norm_eps"], c["rope_theta"], 1.0, "softmax", False,
                  False, c["tie_word_embeddings"], False)
    assert list(p.layer_types) == [
        "swa" if w else "attn" for w in kb.held_layout(c)]
    # What the config has no key for (its ``assumed``).
    assert (p.gate_act, p.moe_router_input, p.qk_norm, p.attn_rope) == (
        "relu", "block_input", False, False)


def test_the_cell_and_its_metrics_are_declared():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    check_declared(m, CELL, DECLARED, NEW)
    # This cell's counter ratios are data files, the rest code.
    for x in (x for x in m["per_layer"] if x["name"] in NEW):
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", x["name"] + (
                ".json" if x["source"] == "program_counter" else ".py")))
    why = [w for w in m["workloads"] if w["name"] == CELL][0]["why"]
    assert "cross the 4096 window while decoding" in why


def test_the_mix_fits_the_pool_and_is_what_the_issue_gives():
    c, spec = config(), traffic.load("long-think")
    need = traffic.worst_case_pages(spec, c["serve"]["page_size"])
    assert need == 3697
    assert c["serve"]["paged_pages"] == -(-(need + 1) // 64) * 64 == 3712
    assert (spec["clients"], len(spec["sessions"]), spec["preroll_s"],
            spec["rate_rps"]) == (32, 64, 24, None)
    assert spec["clients"] == c["serve"]["slots"]
    assert all(len(s["turns"]) == 1 and not s["shared"]
               for s in spec["sessions"])
    turns = [tuple(s["turns"][0]) for s in spec["sessions"]]
    # The 64 stratified quantiles of the two lognormals, cut (the answers'
    # lower cut of 256 bites nothing: the lowest quantile is 306).
    z = [NormalDist().inv_cdf((i + 0.5) / 64) for i in range(64)]
    prompts = [min(12288, max(512, round(3072 * math.exp(0.8 * x))))
               for x in z]
    answers = [min(2048, max(256, round(1024 * math.exp(0.5 * x))))
               for x in z]
    assert (prompts[0], prompts[-1], answers[0], answers[-1]) == (
        512, 12288, 306, 2048)
    assert round(sum(prompts) / 64) == 4001
    assert round(sum(answers) / 64) == 1112
    assert 3000 <= (prompts[31] + prompts[32]) / 2 <= 3150
    assert 1000 <= (answers[31] + answers[32]) / 2 <= 1050
    assert (sum(p > 4096 for p in prompts),
            sum(p > 8192 for p in prompts)) == (23, 7)
    buckets = [traffic.bucket(p + 1) for p in prompts]
    assert [buckets.count(b) for b in (1024, 2048, 4096, 8192, 16384)] == [
        5, 15, 21, 16, 7]
    assert max(p + a + 1 for p, a in turns) <= c["serve"]["max_len"]
    # Prompt quantile i goes with answer quantile 37 i mod 64, and the file
    # holds pair 37 j mod 64 at position j: a caller's two sessions (script
    # j walks sessions j and j + 32) are a prompt of the lower half and one
    # of the upper.
    pairs = [(prompts[i], answers[37 * i % 64]) for i in range(64)]
    assert turns == [pairs[37 * j % 64] for j in range(64)]
    assert all((37 * j % 64 < 32) != (37 * (j + 32) % 64 < 32)
               for j in range(32))
    # Rows cross the window WHILE decoding: prompts under 4,096 whose
    # answers carry them past it.
    assert sum(p + 1 <= 4096 < p + 1 + a for p, a in turns) >= 8
