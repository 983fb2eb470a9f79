"""Nemotron-H's per-layer metrics (PR 55) on a made-up trace, counters and
gauges, the byte and operation functions they stand on against ISSUE 55's
numbers written out, and the configuration's file against the catalog's
numbers, the preset and the traffic the issue gives."""

import json
import math
import os
from statistics import NormalDist

import pytest

from benchmark import kernel_bytes, kernel_bytes_nemotron as kb
from benchmark import metrics, traffic

from declared_cell import check_declared
from paired_trace import paired

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "nemotron3-super-int8-ep4.agent-turns"
DECLARED = ("nemotron3-super-int8-ep4", "agent-turns", 1)
NEW = ["ssm_decode_share", "ssm_decode_roofline", "ssm_admit_share",
       "ssm_admit_roofline", "latent_experts_share",
       "latent_experts_roofline", "latent_touched_share",
       "ssm_state_vs_pages"]
PATTERN22 = "MEMEMEM*EMEMEMEM*EMEME"


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron3-super-int8-ep4.json")) as f:
        return json.load(f)


# A window of 300 chunks of 8 steps at 64 slots, 60 rows a step; 10 expert
# layers a pass, 120 of 128 held experts touched, a quarter of the pairs held.
PASSES = 300 * 8 * 10.0
COUNTERS = {
    "batcher_decode_slot_steps": 300 * 8 * 64.0,
    "ssm_decode_row_steps": 300 * 8 * 60.0,
    "moe_layer_passes": PASSES,
    "moe_experts_touched": PASSES * 120,
    "moe_routed_pairs": PASSES * 60 * 22,
    "moe_held_pairs": PASSES * 60 * 22 / 4,
}
# 6 traced seconds: 25 decode chunks, 3 admissions.
TRACE = {
    "busy_s": 5.0,
    "op_s": {"ssm_decode": 2.0, "ssm_prefill": 0.25, "moe_experts": 2.0,
             "_quant_matmul_2d": 0.5},
    "module_count": {"jit_decode_chunk": 25.0, "jit_admit_row_paged": 3.0},
    "module_s": {},
}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
# The counter window inside the trace: 24 chunks dispatched, 56 rows a step
# (a trough).
TRACE_COUNTERS = {
    "batcher_decode_slot_steps": 24 * 8 * 64.0,
    "ssm_decode_row_steps": 24 * 8 * 56.0,
}
# 64 slots' states and taps; the rows held 3,500 pages at their most, and
# none when the window's deadline had cut them all.
GAUGES = {"batcher_ssm_state_bytes": 2_723_676_160.0,
          "batcher_pool_peak_held": 3500.0, "batcher_pool_held_pages": 0.0}


def traced(tokens=3000):
    """The three admissions paired, ``tokens`` real tokens each; the scan's
    kernel runs inside them and nowhere else."""
    return paired(TRACE, [tokens] * 3, admit_ops=("ssm_prefill",))


def ctx(**over):
    return {"counters": COUNTERS, "trace": traced(), "peaks": PEAKS,
            "config": config(), "trace_counters": TRACE_COUNTERS,
            "gauges": GAUGES, **over}


def test_shares_of_busy_time_the_touched_experts_and_state_against_pages():
    read = lambda name: metrics.read_layer_metric(name, ctx())
    assert read("ssm_decode_share") == (pytest.approx(40.0), "%")
    assert read("ssm_admit_share") == (pytest.approx(5.0), "%")
    assert read("latent_experts_share") == (pytest.approx(40.0), "%")
    assert read("latent_touched_share") == (pytest.approx(100 * 120 / 128), "%")
    # 2.72 GB of state beside 3,500 pages of 131,072 B: the state is the
    # larger part of what the rows hold by a factor of 5.9
    assert read("ssm_state_vs_pages") == (
        pytest.approx(2_723_676_160 / (3500 * 131_072)), "x")


def test_the_decode_roofline():
    """200 steps of the 25 whole decode programs x the 56 rows a step of the
    counter window INSIDE the trace x 10 layers x the state read and
    written; the whole window's 60 rows are not what the traced steps
    moved, and are read only where the traced part has no counters."""
    least = 200 * 56 * 10 * 2 * 4_194_304 / 819e9
    assert metrics.read_layer_metric("ssm_decode_roofline", ctx()) == \
        (pytest.approx(100 * least / 2.0), "%")
    assert metrics.read_layer_metric(
        "ssm_decode_roofline", ctx(trace_counters=None)) == \
        (pytest.approx(100 * least * 60 / 56 / 2.0), "%")


def test_the_admission_roofline():
    """9,000 real tokens in 3 rows (the paired admissions' own): every
    token's half chunk of pairs (a group's C . B and a head's weighted
    dt x) and, a head, the state's readout and update."""
    ops = 10 * 9000 * (8 * 64.5 * 256 + 128 * (64.5 * 128 + 4 * 64 * 128))
    assert 3 * kb.admit_ops(config(), 3000) == pytest.approx(ops)
    # 5.4 MFLOP a token a layer, 27 ns at the peak bf16 rate; its 37 KB of
    # x, y, B, C and dt take 46 ns of the chip's 819 GB/s: the bytes are the
    # larger, an admission at a time
    assert ops / 9000 / 10 == pytest.approx(5.38e6, rel=0.01)
    per_token = 2 * 8192 * 2 + 2 * 1024 * 2 + 128 * 4
    assert kb.admit_bytes(config(), 1) / 10 == per_token == 37_376
    least = max(ops / 197e12, 10 * 9000 * per_token / 819e9)
    assert least == 10 * 9000 * per_token / 819e9
    assert metrics.read_layer_metric("ssm_admit_roofline", ctx()) == \
        (pytest.approx(100 * least / 0.25), "%")


def test_the_expert_roofline():
    """A decode step streams the touched 120 / 128 of the held experts' two
    matrices and scales; a 3,000-token admission gives the held experts
    3,000 x 22 x 10 / 4 pairs, whose arithmetic is the larger."""
    c = config()
    per_pass = 7_046_430_720 * 1.03125 * (120 / 128) / 819e9
    pairs = 3000 * 22 * 10 / 4
    admit = max(per_pass, 2 * pairs * 5_505_024 / 197e12)
    assert admit > per_pass
    least = 25 * 8 * per_pass + 3 * admit
    assert metrics.read_layer_metric("latent_experts_roofline", ctx()) == \
        (pytest.approx(100 * least / 2.0), "%")
    assert kb.held_experts_bytes(c) == pytest.approx(7.267e9, rel=1e-3)


@pytest.mark.parametrize("name,wrong", [
    ("ssm_decode_roofline", {"trace_counters": {
        **TRACE_COUNTERS, "ssm_decode_row_steps": 24 * 8 * 56.0 * 4}}),
    ("ssm_admit_roofline", {"trace": traced(100 * 3000)}),
    ("latent_experts_roofline", {"counters": {
        **COUNTERS, "moe_experts_touched": PASSES * 120 * 4}}),
])
def test_a_wrong_count_is_not_hidden(name, wrong):
    """Nothing is clamped: a count several times too high reads over
    100%."""
    assert metrics.read_layer_metric(name, ctx())[0] < 100
    assert metrics.read_layer_metric(name, ctx(**wrong))[0] > 100


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_kernels_and_counters_reads_nothing(name):
    """The parent commit, or another configuration: no such kernel in the
    trace, no such counter or gauge, no such key in the configuration's
    file; and no reader raises, whatever it is handed."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen2-7b-int8.json")) as f:
        other = json.load(f)
    bare = ctx(
        counters={"batcher_decode_slot_steps": 1000.0},
        trace=paired({**TRACE, "op_s": {"_quant_matmul_2d": 1.4}}, []),
        config=other, gauges={},
        trace_counters={"batcher_decode_slot_steps": 100.0})
    assert metrics.read_layer_metric(name, bare) is None
    assert metrics.read_layer_metric(name, {**bare, "trace": None}) is None
    # Another configuration's run that happened to have the kernels' names.
    if name != "latent_touched_share":  # (a ratio of counters: a data file)
        assert metrics.read_layer_metric(name, ctx(config=other)) is None
    # The parent's program given THIS configuration's file cannot build
    # the preset; a reader handed its file and no counters reads nothing.
    assert metrics.read_layer_metric(
        name, {**bare, "config": config()}) is None
    assert metrics.read_layer_metric(
        name, {**bare, "config": config(), "trace_counters": None}) is None
    # ... nor does a trace that could not pair its admissions
    unpaired = {**TRACE, "admissions": None}
    if name.endswith("roofline"):
        assert metrics.read_layer_metric(name, ctx(trace=unpaired)) is None


def test_bytes_against_the_issues_numbers_written_out():
    c = config()
    assert kb.held_pattern(c) == PATTERN22
    assert (kb.ssm_layers(c), kb.expert_layers(c), kb.attn_layers(c)) == \
        (10, 10, 2)
    assert kb.state_bytes_row_layer(c) == 4_194_304
    assert kb.taps_bytes_row_layer(c) == 61_440
    assert kb.served_state_bytes(c) == 64 * 10 * 4_255_744 == 2_723_676_160
    assert kb.expert_weights(c) == 5_505_024
    assert kb.held_expert_weights(c) == 7_046_430_720
    assert kb.quant_matmul_weights(c) == 1_691_353_088 == (
        10 * 109_576_192 + 2 * 35_651_584 + 10 * 52_428_800)
    assert kb.page_bytes(c) == 131_072
    # a row's state is worth 20,800 tokens of its keys
    assert 10 * 4_255_744 / 2048 == pytest.approx(20_800, rel=2e-3)
    assert kb.weight_bytes(c) == pytest.approx(9.63e9, rel=1e-3)
    assert 5184 * kb.page_bytes(c) == pytest.approx(0.679e9, rel=1e-3)
    # 13.03 GB resident of the chip's 16: 81%
    resident = kb.weight_bytes(c) + kb.served_state_bytes(c) + 5184 * 131_072
    assert resident == pytest.approx(13.03e9, rel=1e-3)
    # matmuls_per_layer x 22 at or under the leaves' count by less than 0.01%
    assert c["matmuls_per_layer"] == [[4096, 18769]]
    got = kernel_bytes.quant_matmul_weights(c)
    assert 0 <= 1 - got / kb.quant_matmul_weights(c) < 1e-4
    # A decode step of 64 rows, 120 of 128 experts touched, at 819 GB/s:
    # the states read and written, the touched experts, the other blocks
    # and the head's slice: 17.4 ms before the pages (the issue's 18.0 with
    # 0.46 GB of them)
    step = (2 * 64 * 10 * 4_194_304 + kb.held_experts_bytes(c) * 120 / 128
            + kb.quant_matmul_weights(c) * 1.03125
            + c["vocab_size"] * c["hidden_size"] * 2) / 819e9
    assert step == pytest.approx(17.4e-3, rel=0.01)


def test_the_program_agrees_with_the_byte_functions():
    """init_params_quantized, the pool and the slots' state of the preset
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
    assert stored == pytest.approx(kb.weight_bytes(c), rel=1e-3)  # + norms
    s = c["serve"]
    pool = jax.eval_shape(lambda: kv_cache.make_pool(
        cfg, s["paged_pages"], s["page_size"], slots=s["slots"]))
    held = sum(x.size * x.dtype.itemsize for x in (pool.ssm_h, pool.ssm_conv))
    assert held == kb.served_state_bytes(c)
    assert kv_cache.page_bytes(cfg, s["page_size"]) == kb.page_bytes(c)
    assert cfg.ssm_chunk == c["chunk_size"] == 128


def test_the_configuration_carries_every_published_number():
    c = config()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry = [x for x in m["configs"] if x["name"] == c["name"]][0]
    assert entry["source"] == c["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-"
        "BF16/blob/main/config.json")
    assert entry["file"] == "benchmark/configs/" + c["name"] + ".json"
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings"]
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 4096,
        "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_num_heads": 128,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 2688, "moe_latent_size": 1024,
        "moe_shared_expert_intermediate_size": 5376, "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 22,
        "num_key_value_heads": 2, "num_nextn_predict_layers": 1,
        "rope_theta": 10000, "routed_scaling_factor": 5,
        "ssm_state_size": 128, "tie_word_embeddings": False,
        "topk_group": 1, "use_conv_bias": True,
    }
    assert {k: c[k] for k in published} == published
    assert len(c["hybrid_override_pattern"]) == 88  # whole, as published
    assert c["hybrid_override_pattern"].startswith(PATTERN22)
    assert [c["hybrid_override_pattern"].count(x) for x in "ME*"] == \
        [40, 40, 8]
    assert c["published"] == {
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "vocab_size": 131072, "max_position_embeddings": 262144}
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"],
            c["router_outputs"]) == (22, 128, 32768, 512)
    assert c["max_position_embeddings"] == c["serve"]["max_len"] == 16384
    d = c["deployment"]
    assert d["chips"] == 16 and "4 pipeline stages of 4 chips" in d["layout"]
    for absent in ("other three stages", "all-to-all",
                   "multi-token-prediction", "runs 16"):
        assert absent in d["not_modelled"], absent
    s = c["serve"]
    assert "--prefix-cache" not in s["extra_argv"]
    assert (s["slots"], s["paged_pages"], s["page_size"],
            s["chunk_steps"]) == (64, 5184, 64, 8)
    assert s["must_dispatch"] == [
        "quant_matmul", "paged_decode", "moe_experts", "ssm_prefill",
        "ssm_decode"]
    assert s["probe_bytes"] == [32, 200, 700, 1500, 6000]
    # The last probe crosses 46 chunk boundaries of the scan.
    assert (s["probe_bytes"][-1] + 1 - 1) // c["chunk_size"] == 46
    assert set(c["assumed"]) >= {
        "no_rope", "latent_moe", "router", "in_proj_order",
        "gate_before_norm", "dt", "state_precision", "weights", "tokenizer"}
    assert set(c["reduced_why"]) == set(c["reduced"])


def test_the_preset_is_the_configuration():
    from distributed_llms_tpu.models.presets import (
        blocks_of_pattern, get_preset)

    c, p = config(), get_preset(config()["preset"])
    assert (p.hidden_size, p.num_heads, p.num_kv_heads, p.head_dim_,
            p.vocab_size, p.num_experts, p.held_experts,
            p.num_experts_per_token, p.expert_size, p.shared_size,
            p.moe_latent_size) == (
        c["hidden_size"], c["num_attention_heads"],
        c["num_key_value_heads"], c["head_dim"], c["vocab_size"],
        c["router_outputs"], c["n_routed_experts"],
        c["num_experts_per_tok"], c["moe_intermediate_size"],
        c["moe_shared_expert_intermediate_size"], c["moe_latent_size"])
    assert (p.ssm_heads, p.ssm_head_dim, p.ssm_groups, p.ssm_state,
            p.ssm_conv_kernel, p.ssm_chunk, p.ssm_inner) == (
        c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"],
        c["ssm_state_size"], c["conv_kernel"], c["chunk_size"],
        c["expand"] * c["hidden_size"])
    assert (p.norm_eps, p.tie_embeddings, p.gate_act, p.moe_routed_scale,
            p.moe_norm_topk, p.moe_n_group, p.moe_topk_group,
            p.moe_score_fn) == (
        c["layer_norm_epsilon"], c["tie_word_embeddings"],
        c["mlp_hidden_act"], c["routed_scaling_factor"],
        c["norm_topk_prob"], c["n_group"], c["topk_group"], "sigmoid")
    folded = blocks_of_pattern(kb.held_pattern(c))
    assert (p.num_layers, p.layer_types, p.no_ffn_layers) == (
        folded["num_layers"], folded["layer_types"], folded["no_ffn_layers"])
    # 22 sub-layers in 12 blocks: 10 M, 2 *, and 10 E behind an operator
    assert len(p.layer_types) + p.ffn_kinds.count("moe") == \
        c["num_hidden_layers"]
    assert not p.attn_rope and not p.qk_norm  # (assumed: no position)
    assert p.max_seq_len >= c["serve"]["max_len"]


def test_the_cell_and_its_metrics_are_declared():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    check_declared(m, CELL, DECLARED, NEW)
    why = [w for w in m["workloads"] if w["name"] == CELL][0]["why"]
    assert "64 x 10 states" in why and "latent experts" in why


def test_the_mix_fits_the_server_and_is_what_the_issue_gives():
    c, spec = config(), traffic.load("agent-turns")
    assert not traffic.pool_fits(
        spec, c["serve"], c["serve"]["must_dispatch"])
    assert (spec["clients"], len(spec["sessions"]), spec["preroll_s"],
            spec["rate_rps"]) == (64, 128, 24, None)
    assert spec["clients"] == c["serve"]["slots"]
    assert all(len(s["turns"]) == 1 and not s["shared"]
               for s in spec["sessions"])
    turns = [tuple(s["turns"][0]) for s in spec["sessions"]]
    # The 128 stratified quantiles of the two lognormals, cut.
    z = [NormalDist().inv_cdf((i + 0.5) / 128) for i in range(128)]
    prompts = [min(8128, max(256, round(2048 * math.exp(0.8 * x))))
               for x in z]
    answers = [min(2048, max(192, round(768 * math.exp(0.6 * x))))
               for x in z]
    assert (prompts[0], prompts[-1], answers[0], answers[-1]) == (
        256, 8128, 192, 2048)
    assert round(sum(prompts) / 128) == 2664
    assert round(sum(answers) / 128) == 886
    buckets = [traffic.bucket(p + 1) for p in prompts]  # (the BOS counted)
    assert [buckets.count(b) for b in (512, 1024, 2048, 4096, 8192)] == \
        [5, 20, 39, 39, 25]
    assert max(buckets) == 8192  # no 16,384 bucket is compiled
    pairs = [(prompts[i], answers[37 * i % 128]) for i in range(128)]
    assert turns == [pairs[37 * j % 128] for j in range(128)]
    # a caller's two sessions: a prompt of the lower half and one of the upper
    assert all((37 * j % 128 < 64) != (37 * (j + 64) % 128 < 64)
               for j in range(64))
    assert max(p + a + 1 for p, a in turns) == 9638 <= c["serve"]["max_len"]
    worst = traffic.worst_case_pages(spec, 64)
    assert worst == 5132
    # the least multiple of 64 that holds it and the scratch page
    assert c["serve"]["paged_pages"] == -(-(worst + 1) // 64) * 64 == 5184
