"""Brumby's per-layer metrics (PR 50) on a made-up trace and counters, the
byte and operation functions they stand on against ISSUE 50's numbers
written out, and the configuration's file against the catalog's numbers,
the preset and the traffic the issue gives."""

import json
import math
import os
from statistics import NormalDist

import pytest

from benchmark import kernel_bytes, kernel_bytes_brumby as kb
from benchmark import metrics, traffic

from declared_cell import check_declared
from paired_trace import paired

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "brumby-14b-int8.long-rows"
# (config, traffic, chips).  The mix is ISSUE 50's ``long-rows``; its FILE is
# ``long-rows-8k.json`` because tests/benchmark/test_manifest.py
# ::test_a_state_only_cell_appended_to_a_copy_of_the_tree (PR 49) plants a
# ``long-rows.json`` of its own as a NEW file in a copy of this directory,
# and no file that exists here may be edited by the PR that adds a cell.
DECLARED = ("brumby-14b-int8", "long-rows-8k", 1)
NEW = ["ret_decode_share", "ret_decode_roofline", "ret_admit_share",
       "ret_admit_roofline", "ret_state_vs_kv"]


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "brumby-14b-int8.json")) as f:
        return json.load(f)


# A window of 200 chunks of 8 steps at 16 slots, 15 rows a step that hold
# 9,000 tokens each.
COUNTERS = {
    "batcher_decode_slot_steps": 200 * 8 * 16.0,
    "ret_decode_row_steps": 200 * 8 * 15.0,
    "ret_decode_resident_tokens": 200 * 8 * 15 * 9000.0,
}
# 6 traced seconds: 20 decode chunks, 3 admissions.
TRACE = {
    "busy_s": 5.0,
    "op_s": {"retention_decode": 2.5, "retention_prefill": 0.5,
             "_quant_matmul_2d": 1.5},
    "module_count": {"jit_decode_chunk": 20.0, "jit_admit_row": 3.0},
    "module_s": {},
}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
# The counter window inside the trace: 19 chunks dispatched, 14 rows a step
# (a trough), 3 admissions of 8,000 real tokens.
TRACE_COUNTERS = {
    "batcher_prefix_cache_miss_tokens": 24000.0,
    "batcher_decode_slot_steps": 19 * 8 * 16.0,
    "ret_decode_row_steps": 19 * 8 * 14.0,
    "ret_decode_resident_tokens": 19 * 8 * 14 * 9000.0,
    "ret_admit_tokens": 24000.0,
    "ret_admit_chunks": 3 * 32.0,
}


def traced(tokens=8000):
    """The three admissions paired, ``tokens`` real tokens each; the scan's
    kernel runs inside them and nowhere else."""
    return paired(TRACE, [tokens] * 3, admit_ops=("retention_prefill",),
                  program="jit_admit_row")


def ctx(**over):
    return {"counters": COUNTERS, "trace": traced(), "peaks": PEAKS,
            "config": config(), "trace_counters": TRACE_COUNTERS, **over}


def test_shares_of_busy_time_and_the_state_against_keys():
    assert metrics.read_layer_metric("ret_decode_share", ctx()) == \
        (pytest.approx(50.0), "%")
    assert metrics.read_layer_metric("ret_admit_share", ctx()) == \
        (pytest.approx(10.0), "%")
    # 9,000 tokens a row x 4,096 B of keys and values a layer against the
    # 34,080,768 B of state: 108%, the regime the model is for.
    assert metrics.read_layer_metric("ret_state_vs_kv", ctx()) == \
        (pytest.approx(100 * 9000 * 4096 / 34_080_768), "%")


def test_the_decode_roofline():
    """160 traced steps x the 14 rows a step of the counter window INSIDE
    the trace x 10 layers x the symmetric state read and written; the
    whole window's 15 rows are not what the traced steps moved."""
    least = 160 * 14 * 10 * 2 * 34_080_768 / 819e9
    assert metrics.read_layer_metric("ret_decode_roofline", ctx()) == \
        (pytest.approx(100 * least / 2.5), "%")


def test_the_admission_roofline():
    """24,000 real tokens in 3 rows (the paired admissions' own, 8,000
    each): every token's half chunk of pairs and its update of the state,
    and the state's query for the tokens behind each row's first chunk of
    256."""
    state = 2 * 8256 * 129
    ops = 10 * (24000 * 40 * (257 / 2) * 512
                + (24000 - 3 * 256) * 40 * state + 24000 * 8 * state)
    assert kb.admit_ops(config(), 24000, 3) == pytest.approx(ops)
    assert metrics.read_layer_metric("ret_admit_roofline", ctx()) == \
        (pytest.approx(100 * ops / 197e12 / 0.5), "%")
    # about 0.1 GFLOP a token a layer beside the blocks' 0.66
    assert ops / 24000 / 10 == pytest.approx(103.5e6, rel=0.02)


@pytest.mark.parametrize("name,counter,factor", [
    ("ret_decode_roofline", "ret_decode_row_steps", 4),
    ("ret_admit_roofline", "ret_admit_tokens", 30),
])
def test_a_wrong_count_is_not_hidden(name, counter, factor):
    """Nothing is clamped: a count several times too high reads over
    100%."""
    wrong = {"trace_counters": {
        **TRACE_COUNTERS, counter: factor * TRACE_COUNTERS[counter]}}
    if name == "ret_admit_roofline":
        # (since PR 52 its tokens are the paired admissions' own, and the
        # counter, settled and not launched, is not read)
        assert metrics.read_layer_metric(name, ctx(**wrong)) == \
            metrics.read_layer_metric(name, ctx())
        wrong = {"trace": traced(factor * 8000)}
    assert metrics.read_layer_metric(name, ctx())[0] < 100
    assert metrics.read_layer_metric(name, ctx(**wrong))[0] > 100


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_kernels_and_counters_reads_nothing(name):
    """The parent commit, or another configuration: no such kernel in the
    trace, no such counter, no such key in the configuration's file; and
    no reader raises, whatever it is handed."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen2-7b-int8.json")) as f:
        other = json.load(f)
    bare = ctx(
        counters={"batcher_decode_slot_steps": 1000.0},
        trace={**TRACE, "op_s": {"_quant_matmul_2d": 1.4}}, config=other,
        trace_counters={"batcher_decode_slot_steps": 100.0})
    assert metrics.read_layer_metric(name, bare) is None
    assert metrics.read_layer_metric(name, {**bare, "trace": None}) is None
    # Another configuration's run that happened to have the kernels' names.
    if name != "ret_state_vs_kv":  # (a ratio of counters: a data file)
        assert metrics.read_layer_metric(name, ctx(config=other)) is None
    # The parent's program given THIS configuration's file cannot build
    # the preset; a reader handed its file and no counters reads nothing.
    assert metrics.read_layer_metric(
        name, {**bare, "config": config()}) is None
    assert metrics.read_layer_metric(
        name, {**bare, "config": config(), "trace_counters": None}) is None


def test_bytes_against_the_issues_numbers_written_out():
    c = config()
    assert kb.products(c) == 8256
    assert kb.state_bytes_row_layer(c) == 34_080_768
    assert 8 * 8256 * 128 * 4 == 33_816_576  # the state alone: 33.8 MB
    assert kb.kv_bytes_per_token_layer(c) == 4096
    assert 10 * kb.kv_bytes_per_token_layer(c) == 40_960  # a token, 10 layers
    assert kb.layer_weights(c) == 330_301_440
    assert kernel_bytes.quant_matmul_weights(c) == 10 * 330_301_440
    assert c["matmuls_per_layer"] == [
        [5120, 5120], [5120, 1024], [5120, 1024], [5120, 5120],
        [5120, 17408], [5120, 17408], [17408, 5120]]
    assert kb.layer_weights(c) * 1.03125 == pytest.approx(0.3406e9, rel=1e-3)
    assert 2 * c["vocab_size"] * c["hidden_size"] * 2 == pytest.approx(
        3.1116e9, rel=2e-4)
    assert kb.weight_bytes(c) == pytest.approx(6.52e9, rel=1e-3)
    # All 40 layers: 13.6 GB of blocks beside 3.11 GB of embedding and head.
    assert 40 * kb.layer_weights(c) * 1.03125 == pytest.approx(
        13.62e9, rel=1e-3)
    # The symmetric state of 16 rows x 10 layers (the issue's 5.45 GB) and
    # what the served layout holds (65 diagonals and a square normaliser).
    assert 16 * 10 * kb.state_bytes_row_layer(c) == 5_452_922_880
    assert kb.served_state_bytes(c) == 5_536_481_280
    assert kb.served_state_bytes(c) / 5_452_922_880 == pytest.approx(
        1.0153, abs=1e-4)
    # The state is the cheaper from some 8,300 tokens a row on.
    assert kb.state_bytes_row_layer(c) / 4096 == pytest.approx(8320.5)
    # A decode step of 16 rows: the state read and written, the blocks and
    # the head read: 19.4 ms at 819 GB/s at the symmetric size.
    step = (2 * 5_452_922_880 + kb.layer_weights(c) * 10 * 1.03125
            + c["vocab_size"] * c["hidden_size"] * 2) / 819e9
    assert step == pytest.approx(19.4e-3, rel=0.01)


def test_the_program_agrees_with_the_byte_functions():
    """init_params_quantized and the slots' state of the preset against the
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
    assert stored == pytest.approx(kb.weight_bytes(c), rel=1e-3)  # + norms
    assert shapes["blocks"]["ret"]["wg"].dtype == jnp.bfloat16  # not int8
    s = c["serve"]
    cache = jax.eval_shape(
        lambda: kv_cache.init_cache(cfg, s["slots"], s["max_len"]))
    assert cache.k.size == cache.v.size == 0  # no key, no value
    held = sum(x.size * x.dtype.itemsize for x in (cache.ret_s, cache.ret_z))
    assert held == kb.served_state_bytes(c)
    assert cfg.ret_chunk == c["ret_chunk"] == 256
    # 12.06 GB resident: over the driver's quarter of a chip's 16 GB.
    assert stored + kb.served_state_bytes(c) == pytest.approx(
        12.06e9, rel=1e-3)


def test_the_configuration_carries_every_published_number():
    c = config()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry = [x for x in m["configs"] if x["name"] == c["name"]][0]
    assert entry["source"] == c["source"] == (
        "https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/"
        "config.json")
    assert entry["file"] == "benchmark/configs/" + c["name"] + ".json"
    assert entry["reduced"] == c["reduced"] == ["num_hidden_layers"]
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 5120, "intermediate_size": 17408,
        "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "num_attention_heads": 40,
        "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936,
    }
    assert {k: c[k] for k in published} == published
    assert c["published"] == {"num_hidden_layers": 40}
    assert c["num_hidden_layers"] == 10
    assert c["max_position_embeddings"] == c["serve"]["max_len"] == 32768
    d = c["deployment"]
    assert d["chips"] == 4 and "4 pipeline stages of 10 whole layers" in \
        d["layout"]
    assert "embedding AND the head" in d["this_chip"]
    assert "fewer than the 16 rows" in d["not_modelled"]
    s = c["serve"]
    assert "--prefix-cache" not in s["extra_argv"]
    assert (s["slots"], s["paged_pages"], s["chunk_steps"]) == (16, 0, 8)
    assert s["must_dispatch"] == [
        "quant_matmul", "retention_prefill", "retention_decode"]
    assert s["probe_bytes"] == [32, 200, 700, 1500, 6000]
    # The last probe crosses at least two chunk boundaries.
    assert s["probe_bytes"][-1] > 2 * c["ret_chunk"]
    assert set(c["assumed"]) >= {
        "degree", "gate", "normaliser", "qk_norm", "state_precision",
        "chunk", "state_layout", "weights", "tokenizer"}
    assert set(c["reduced_why"]) == set(c["reduced"])


def test_the_preset_is_the_configuration():
    from distributed_llms_tpu.models.presets import get_preset

    c, p = config(), get_preset(config()["preset"])
    assert (p.hidden_size, p.intermediate_size, p.num_layers, p.num_heads,
            p.num_kv_heads, p.head_dim_, p.vocab_size, p.num_experts,
            p.sliding_window, p.max_seq_len) == (
        c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"],
        c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
        c["vocab_size"], 0, c["sliding_window"],
        c["max_position_embeddings"])
    assert (p.norm_eps, p.rope_theta, p.rope_scaling_factor,
            p.tie_embeddings, p.qkv_bias, p.gate_act) == (
        c["rms_norm_eps"], c["rope_theta"], 1.0, c["tie_word_embeddings"],
        c["attention_bias"], c["hidden_act"])
    assert list(p.layer_types) == ["ret"] * 10
    assert p.attn_layers == () and len(p.ret_layers) == 10
    assert p.qk_norm  # (assumed: the config has no key for it)


def test_the_cell_and_its_metrics_are_declared():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    check_declared(m, CELL, DECLARED, NEW)
    # This cell's counter ratio is a data file, the rest code.
    for x in (x for x in m["per_layer"] if x["name"] in NEW):
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", x["name"] + (
                ".json" if x["source"] == "program_counter" else ".py")))
    why = [w for w in m["workloads"] if w["name"] == CELL][0]["why"]
    assert "float32 state and no key" in why


def test_the_mix_fits_the_server_and_is_what_the_issue_gives():
    c, spec = config(), traffic.load("long-rows-8k")
    assert not traffic.pool_fits(
        spec, c["serve"], c["serve"]["must_dispatch"])
    assert (spec["clients"], len(spec["sessions"]), spec["preroll_s"],
            spec["rate_rps"]) == (16, 32, 24, None)
    assert spec["clients"] == c["serve"]["slots"]
    assert all(len(s["turns"]) == 1 and not s["shared"]
               for s in spec["sessions"])
    turns = [tuple(s["turns"][0]) for s in spec["sessions"]]
    # The 32 stratified quantiles of the two lognormals, cut; a prompt's
    # quantile counts the BOS the server adds, so the file holds a byte less.
    z = [NormalDist().inv_cdf((i + 0.5) / 32) for i in range(32)]
    prompts = [min(16384, max(4096, round(8192 * math.exp(0.45 * x))))
               for x in z]
    answers = [min(1536, max(320, round(768 * math.exp(0.45 * x))))
               for x in z]
    assert (prompts[0], prompts[-1], answers[0], answers[-1]) == (
        4096, 16384, 320, 1536)
    assert round(sum(prompts) / 32) == 8868
    assert round(sum(answers) / 32) == 829
    buckets = [traffic.bucket(p) for p in prompts]
    assert [buckets.count(b) for b in (4096, 8192, 16384)] == [2, 14, 16]
    assert max(buckets) == 16384  # no 32,768 bucket is compiled
    pairs = [(prompts[i] - 1, answers[19 * i % 32]) for i in range(32)]
    assert turns == [pairs[19 * j % 32] for j in range(32)]
    assert all((19 * j % 32 < 16) != (19 * (j + 16) % 32 < 16)
               for j in range(16))
    assert max(p + 1 + a for p, a in turns) == 17560 <= 17920
    assert max(p + a + 1 for p, a in turns) <= c["serve"]["max_len"]
