"""Qwen3-Next's per-layer metrics (PR 59) on a made-up trace, counters and
gauges, the byte and operation functions they stand on against ISSUE 59's
numbers written out, and the configuration's file against the catalog's
numbers, the preset and the traffic the issue gives."""

import json
import os

import pytest

from benchmark import kernel_bytes, kernel_bytes_qwen3next as kb
from benchmark import metrics, traffic

from declared_cell import check_declared
from paired_trace import paired

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "qwen3-next-int8-ep4.agent-turns"
DECLARED = ("qwen3-next-int8-ep4", "agent-turns", 1)
NEW = ["gdn_decode_share", "gdn_decode_roofline", "gdn_admit_share",
       "gdn_admit_roofline", "tiny_experts_share", "tiny_experts_roofline",
       "tiny_touched_share", "gdn_state_vs_pages"]


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-int8-ep4.json")) as f:
        return json.load(f)


# A window of 300 chunks of 8 steps at 64 slots, 60 rows a step; 12 expert
# layers a pass, 90 of 128 held experts touched, a quarter of the pairs held.
PASSES = 300 * 8 * 12.0
COUNTERS = {
    "batcher_decode_slot_steps": 300 * 8 * 64.0,
    "gdn_decode_row_steps": 300 * 8 * 60.0,
    "moe_layer_passes": PASSES,
    "moe_experts_touched": PASSES * 90,
    "moe_routed_pairs": PASSES * 60 * 10,
    "moe_held_pairs": PASSES * 60 * 10 / 4,
}
# 6 traced seconds: 25 decode chunks, 3 admissions.
TRACE = {
    "busy_s": 5.0,
    "op_s": {"gdn_decode": 1.5, "gdn_prefill": 0.5, "moe_experts": 2.0,
             "_quant_matmul_2d": 0.5},
    "module_count": {"jit_decode_chunk": 25.0, "jit_admit_row_paged": 3.0},
    "module_s": {},
}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
# The counter window inside the trace: 24 chunks dispatched, 56 rows a step
# (a trough).
TRACE_COUNTERS = {
    "batcher_decode_slot_steps": 24 * 8 * 64.0,
    "gdn_decode_row_steps": 24 * 8 * 56.0,
}
# 64 slots' states and taps; the rows held 3,500 pages at their most, and
# none when the window's deadline had cut them all.
GAUGES = {"batcher_gdn_state_bytes": 1_236_271_104.0,
          "batcher_pool_peak_held": 3500.0, "batcher_pool_held_pages": 0.0}


def traced(tokens=3000):
    """The three admissions paired, ``tokens`` real tokens each; the scan's
    kernel runs inside them and nowhere else."""
    return paired(TRACE, [tokens] * 3, admit_ops=("gdn_prefill",))


def ctx(**over):
    return {"counters": COUNTERS, "trace": traced(), "peaks": PEAKS,
            "config": config(), "trace_counters": TRACE_COUNTERS,
            "gauges": GAUGES, **over}


def test_shares_of_busy_time_the_touched_experts_and_state_against_pages():
    read = lambda name: metrics.read_layer_metric(name, ctx())
    assert read("gdn_decode_share") == (pytest.approx(30.0), "%")
    assert read("gdn_admit_share") == (pytest.approx(10.0), "%")
    assert read("tiny_experts_share") == (pytest.approx(40.0), "%")
    assert read("tiny_touched_share") == (pytest.approx(100 * 90 / 128), "%")
    # 1.24 GB of state beside 3,500 pages of 393,216 B: the PAGES are the
    # larger part of what the rows hold (0.9; nemotron's reads 5.9)
    assert read("gdn_state_vs_pages") == (
        pytest.approx(1_236_271_104 / (3500 * 393_216)), "x")


def test_the_decode_roofline():
    """200 steps of the 25 whole decode programs x the 56 rows a step of the
    counter window INSIDE the trace x 9 layers x the state read and written;
    the whole window's 60 rows are not what the traced steps moved, and are
    read only where the traced part has no counters."""
    least = 200 * 56 * 9 * 2 * 2_097_152 / 819e9
    assert metrics.read_layer_metric("gdn_decode_roofline", ctx()) == \
        (pytest.approx(100 * least / 1.5), "%")
    assert metrics.read_layer_metric(
        "gdn_decode_roofline", ctx(trace_counters=None)) == \
        (pytest.approx(100 * least * 60 / 56 / 1.5), "%")


def test_the_admission_roofline():
    """9,000 real tokens in 3 rows (the paired admissions' own): a token and
    a key head the causal half of K K^T and Q K^T; a token and a value head
    the triangle's forward substitution, W S, Q S, the state's update and
    the chunk's own pairs."""
    ops = 9 * 9000 * 2 * (
        16 * 2 * 32.5 * 128
        + 32 * (32.5 * 256 + 3 * 128 * 128 + 32.5 * 128))
    assert 3 * kb.admit_ops(config(), 3000) == pytest.approx(ops)
    # 4.2 MFLOP a token a layer, 21 ns at the peak bf16 rate; its 24.8 KB of
    # q, k, v, o, g and beta take 30 ns of the chip's 819 GB/s: the bytes are
    # the larger, an admission at a time
    assert ops / 9000 / 9 == pytest.approx(4.21e6, rel=0.01)
    per_token = 2 * (2048 + 4096) * 2 + 2 * 32 * 4
    assert kb.admit_bytes(config(), 1) / 9 == per_token == 24_832
    least = max(ops / 197e12, 9 * 9000 * per_token / 819e9)
    assert least == 9 * 9000 * per_token / 819e9
    assert metrics.read_layer_metric("gdn_admit_roofline", ctx()) == \
        (pytest.approx(100 * least / 0.5), "%")


def test_the_expert_roofline():
    """A decode step streams the touched 90 / 128 of the held experts' three
    matrices and scales; a 3,000-token admission gives the held experts
    3,000 x 10 x 12 / 4 pairs, whose arithmetic is the smaller: an expert of
    3.1 M weights is read faster than 700 pairs an expert are multiplied."""
    c = config()
    per_pass = 4_831_838_208 * 1.03125 * (90 / 128) / 819e9
    pairs = 3000 * 10 * 12 / 4
    admit = max(per_pass, 2 * pairs * 3_145_728 / 197e12)
    assert admit == per_pass
    least = 25 * 8 * per_pass + 3 * admit
    assert metrics.read_layer_metric("tiny_experts_roofline", ctx(
        trace=paired({**TRACE, "op_s": {**TRACE["op_s"], "moe_experts": 2.0}},
                     [3000] * 3, admit_ops=("gdn_prefill",)))) == \
        (pytest.approx(100 * least / 2.0), "%")
    assert kb.held_experts_bytes(c) == pytest.approx(4.983e9, rel=1e-3)


@pytest.mark.parametrize("name,wrong", [
    ("gdn_decode_roofline", {"trace_counters": {
        **TRACE_COUNTERS, "gdn_decode_row_steps": 24 * 8 * 56.0 * 8}}),
    ("gdn_admit_roofline", {"trace": traced(1000 * 3000)}),
    ("tiny_experts_roofline", {"counters": {
        **COUNTERS, "moe_experts_touched": PASSES * 90 * 4}}),
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
    if name != "tiny_touched_share":  # (a ratio of counters: a data file)
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
    assert (kb.layers(c), kb.gdn_layers(c), kb.attn_layers(c)) == (12, 9, 3)
    assert kb.state_bytes_row_layer(c) == 2_097_152
    assert kb.taps_bytes_row_layer(c) == 49_152
    assert kb.served_state_bytes(c) == 64 * 9 * 2_146_304 == 1_236_271_104
    assert kb.expert_weights(c) == 3_145_728
    assert kb.held_expert_weights(c) == 4_831_838_208
    assert kb.quant_matmul_weights(c) == 421_527_552 == (
        9 * 33_554_432 + 3 * 27_262_976 + 12 * 3_145_728)
    assert kb.page_bytes(c) == 393_216
    # a row's state is worth 3,144 tokens of its keys
    assert 9 * 2_146_304 // 6144 == 3_144
    assert kb.weight_bytes(c) == pytest.approx(5.78e9, rel=1e-3)
    assert 5184 * kb.page_bytes(c) == pytest.approx(2.038e9, rel=1e-3)
    # 9.05 GB resident of the chip's 16: 57%
    resident = kb.weight_bytes(c) + kb.served_state_bytes(c) + 5184 * 393_216
    assert resident == pytest.approx(9.05e9, rel=2e-3)
    # matmuls_per_layer x 12 IS the leaves' count
    assert c["matmuls_per_layer"] == [[2048, 17152]]
    assert kernel_bytes.quant_matmul_weights(c) == kb.quant_matmul_weights(c)
    # A decode step of 64 rows, 91 of 128 experts touched (71%), 3.1k
    # resident tokens a row, at 819 GB/s: the touched experts, the states
    # read and written, keys and values, the other blocks and the head's
    # slice: 7.8 GB, 9.5 ms
    step = (kb.held_experts_bytes(c) * 0.71 + 2 * 64 * 9 * 2_097_152
            + 64 * 3100 * 6144 + kb.quant_matmul_weights(c) * 1.03125
            + c["vocab_size"] * c["hidden_size"] * 2)
    assert step == pytest.approx(7.8e9, rel=0.01)
    assert step / 819e9 == pytest.approx(9.5e-3, rel=0.01)


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
    assert stored == pytest.approx(kb.weight_bytes(c), rel=2e-3)  # + norms
    s = c["serve"]
    pool = jax.eval_shape(lambda: kv_cache.make_pool(
        cfg, s["paged_pages"], s["page_size"], slots=s["slots"]))
    held = sum(x.size * x.dtype.itemsize for x in (pool.gdn_s, pool.gdn_conv))
    assert held == kb.served_state_bytes(c)
    assert kv_cache.page_bytes(cfg, s["page_size"]) == kb.page_bytes(c)
    assert cfg.gdn_chunk == c["gdn_chunk_size"] == 64


def test_the_configuration_carries_every_published_number():
    c = config()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry = [x for x in m["configs"] if x["name"] == c["name"]][0]
    assert entry["source"] == c["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
        "config.json")
    assert entry["file"] == "benchmark/configs/" + c["name"] + ".json"
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "max_position_embeddings"]
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "mlp_only_layers": [], "model_type": "qwen3_next",
        "moe_intermediate_size": 512, "norm_topk_prob": True,
        "num_attention_heads": 16, "num_experts_per_tok": 10,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
        "use_sliding_window": False,
    }
    assert {k: c[k] for k in published} == published
    assert c["published"] == {
        "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936,
        "max_position_embeddings": 262144}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"],
            c["router_outputs"]) == (12, 128, 37984, 512)
    assert 4 * c["vocab_size"] == 151936
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
        "quant_matmul", "paged_decode", "moe_experts", "gdn_prefill",
        "gdn_decode"]
    assert s["probe_bytes"] == [32, 200, 700, 1500, 6000]
    # The last probe (6,000 bytes and the BOS) crosses 93 chunk boundaries.
    assert (s["probe_bytes"][-1] + 1 - 1) // c["gdn_chunk_size"] == 93
    assert set(c["assumed"]) >= {
        "projection_order", "norm_then_gate", "l2_eps", "state_precision",
        "chunk", "decay_init", "attention", "router", "shared_expert",
        "norms", "weights", "tokenizer"}
    assert set(c["reduced_why"]) == set(c["reduced"])


def test_the_preset_is_the_configuration():
    from distributed_llms_tpu.models.presets import get_preset

    c, p = config(), get_preset(config()["preset"])
    assert (p.hidden_size, p.num_heads, p.num_kv_heads, p.head_dim_,
            p.vocab_size, p.num_experts, p.held_experts,
            p.num_experts_per_token, p.expert_size, p.shared_size,
            p.intermediate_size, p.num_layers) == (
        c["hidden_size"], c["num_attention_heads"],
        c["num_key_value_heads"], c["head_dim"], c["vocab_size"],
        c["router_outputs"], c["num_experts"], c["num_experts_per_tok"],
        c["moe_intermediate_size"], c["shared_expert_intermediate_size"],
        c["intermediate_size"], c["num_hidden_layers"])
    assert (p.gdn_key_heads, p.gdn_value_heads, p.gdn_key_dim,
            p.gdn_value_dim, p.gdn_conv_kernel, p.gdn_chunk) == (
        c["linear_num_key_heads"], c["linear_num_value_heads"],
        c["linear_key_head_dim"], c["linear_value_head_dim"],
        c["linear_conv_kernel_dim"], c["gdn_chunk_size"])
    assert (p.norm_eps, p.tie_embeddings, p.gate_act, p.rope_theta,
            p.rotary_pct, p.moe_norm_topk, p.moe_score_fn,
            p.sliding_window) == (
        c["rms_norm_eps"], c["tie_word_embeddings"], c["hidden_act"],
        c["rope_theta"], c["partial_rotary_factor"], c["norm_topk_prob"],
        "softmax", None)
    # layers 3, 7, 11 attend (full_attention_interval 4), every FFN is the
    # expert layer (decoder_sparse_step 1, mlp_only_layers [])
    every = c["full_attention_interval"]
    assert p.layer_types == tuple(
        "attn" if l % every == every - 1 else "gdn"
        for l in range(c["num_hidden_layers"]))
    assert p.ffn_kinds == ("moe",) * 12 and not p.no_ffn_layers
    assert p.qk_norm and p.attn_rope and p.attn_out_gate
    assert p.moe_shared_gate and p.n_shared_experts == 1
    assert p.max_seq_len >= c["serve"]["max_len"]


def test_the_cell_and_its_metrics_are_declared():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    check_declared(m, CELL, DECLARED, NEW)
    why = [w for w in m["workloads"] if w["name"] == CELL][0]["why"]
    assert "9 states of 2 MiB" in why and "triangle" in why


def test_the_mix_fits_the_server_and_is_the_one_that_is_there():
    """``agent-turns`` as PR 55 wrote it (its own test holds the lengths to
    the issue's quantiles): unchanged, and it fits this server."""
    c, spec = config(), traffic.load("agent-turns")
    assert not traffic.pool_fits(
        spec, c["serve"], c["serve"]["must_dispatch"])
    assert (spec["clients"], len(spec["sessions"]), spec["preroll_s"],
            spec["rate_rps"]) == (64, 128, 24, None)
    assert spec["clients"] == c["serve"]["slots"]
    turns = [tuple(s["turns"][0]) for s in spec["sessions"]]
    assert max(p + a + 1 for p, a in turns) == 9638 <= c["serve"]["max_len"]
    worst = traffic.worst_case_pages(spec, 64)
    assert worst == 5132
    # the least multiple of 64 that holds it and the scratch page
    assert c["serve"]["paged_pages"] == -(-(worst + 1) // 64) * 64 == 5184
