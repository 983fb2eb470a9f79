"""Named model presets covering the BASELINE.json ladder configs
(GPT-2-125M -> TinyLlama-1.1B -> Llama-2-7B -> Llama-2-13B -> Llama-3-70B)
plus tiny variants for tests.  Replaces the reference's single hard-coded
model id (run_master.py:17, "facebook/opt-125m")."""

from __future__ import annotations

from dataclasses import replace

from ..core.config import ModelConfig

def blocks_of_pattern(pattern: str) -> dict:
    """A ``hybrid_override_pattern`` of single sub-layers (``M`` Mamba-2,
    ``*`` attention, ``E`` experts; each ``x <- x + f(rms(x))``) folded into
    (operator, FFN) blocks: an ``E`` joins the operator in front of it, and
    an operator that no ``E`` follows is a block with no FFN.  -> the
    ``num_layers`` / ``layer_types`` / ``no_ffn_layers`` of a ModelConfig."""
    kinds, bare = [], []
    for i, c in enumerate(pattern):
        if c == "E":
            if i == 0 or pattern[i - 1] == "E":
                raise ValueError(
                    f"pattern {pattern!r}: an expert layer with no operator "
                    "in front of it folds into no block")
            continue
        if i + 1 == len(pattern) or pattern[i + 1] != "E":
            bare.append(len(kinds))
        kinds.append({"M": "ssm", "*": "attn"}[c])
    return {"num_layers": len(kinds), "layer_types": tuple(kinds),
            "no_ffn_layers": tuple(bare)}


PRESETS: dict[str, ModelConfig] = {
    "gpt2-125m": ModelConfig(
        family="gpt2", vocab_size=50257, hidden_size=768, intermediate_size=3072,
        num_layers=12, num_heads=12, num_kv_heads=12, max_seq_len=1024,
        norm_eps=1e-5, tie_embeddings=True,
    ),
    "gpt2-medium": ModelConfig(
        family="gpt2", vocab_size=50257, hidden_size=1024, intermediate_size=4096,
        num_layers=24, num_heads=16, num_kv_heads=16, max_seq_len=1024,
        norm_eps=1e-5, tie_embeddings=True,
    ),
    # The reference's default model (run_master.py:17).
    "opt-125m": ModelConfig(
        family="opt", vocab_size=50272, hidden_size=768, intermediate_size=3072,
        num_layers=12, num_heads=12, num_kv_heads=12, max_seq_len=2048,
        norm_eps=1e-5, tie_embeddings=True, activation="relu",
    ),
    "tinyllama-1.1b": ModelConfig(
        family="llama", vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_layers=22, num_heads=32, num_kv_heads=4, max_seq_len=2048,
        rope_theta=10000.0, norm_eps=1e-5, tie_embeddings=False,
    ),
    "llama-2-7b": ModelConfig(
        family="llama", vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_layers=32, num_heads=32, num_kv_heads=32, max_seq_len=4096,
        rope_theta=10000.0, norm_eps=1e-5, tie_embeddings=False,
    ),
    "llama-2-13b": ModelConfig(
        family="llama", vocab_size=32000, hidden_size=5120, intermediate_size=13824,
        num_layers=40, num_heads=40, num_kv_heads=40, max_seq_len=4096,
        rope_theta=10000.0, norm_eps=1e-5, tie_embeddings=False,
    ),
    "llama-3-8b": ModelConfig(
        family="llama", vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, max_seq_len=8192,
        rope_theta=500000.0, norm_eps=1e-5, tie_embeddings=False,
    ),
    "llama-3.1-8b": ModelConfig(
        family="llama", vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, max_seq_len=131072,
        rope_theta=500000.0, norm_eps=1e-5, tie_embeddings=False,
        rope_scaling_factor=8.0, rope_low_freq_factor=1.0,
        rope_high_freq_factor=4.0, rope_original_max_len=8192,
    ),
    "llama-3-70b": ModelConfig(
        family="llama", vocab_size=128256, hidden_size=8192, intermediate_size=28672,
        num_layers=80, num_heads=64, num_kv_heads=8, max_seq_len=8192,
        rope_theta=500000.0, norm_eps=1e-5, tie_embeddings=False,
    ),
    "qwen2-7b": ModelConfig(
        family="llama", qkv_bias=True, vocab_size=152064, hidden_size=3584,
        intermediate_size=18944, num_layers=28, num_heads=28, num_kv_heads=4,
        max_seq_len=32768, rope_theta=1e6, norm_eps=1e-6, tie_embeddings=False,
    ),
    "gemma-7b": ModelConfig(
        family="llama", gate_act="gelu_tanh", norm_plus_one=True,
        embed_scale=3072.0**0.5, vocab_size=256000, hidden_size=3072,
        intermediate_size=24576, num_layers=28, num_heads=16, num_kv_heads=16,
        head_dim=256, max_seq_len=8192, rope_theta=10000.0, norm_eps=1e-6,
        tie_embeddings=True,
    ),
    "phi-3-mini-4k": ModelConfig(
        family="llama", sliding_window=2047, vocab_size=32064,
        hidden_size=3072, intermediate_size=8192, num_layers=32,
        num_heads=32, num_kv_heads=32, max_seq_len=4096,
        rope_theta=10000.0, norm_eps=1e-5, tie_embeddings=False,
    ),
    "mistral-7b": ModelConfig(
        family="llama", sliding_window=4096, vocab_size=32000, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        max_seq_len=32768, rope_theta=10000.0, norm_eps=1e-5,
        tie_embeddings=False,
    ),
    "pythia-6.9b": ModelConfig(
        family="neox", vocab_size=50432, hidden_size=4096,
        intermediate_size=16384, num_layers=32, num_heads=32,
        num_kv_heads=32, max_seq_len=2048, rope_theta=10000.0,
        rotary_pct=0.25, parallel_residual=True, norm_eps=1e-5,
        tie_embeddings=False, activation="gelu_exact",
    ),
    "mixtral-8x7b": ModelConfig(
        family="llama", vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, max_seq_len=32768,
        rope_theta=1e6, norm_eps=1e-5, tie_embeddings=False,
        num_experts=8, num_experts_per_token=2,
    ),
    # LFM2-8B-A1B (LiquidAI, model_type lfm2_moe): 18 gated short
    # convolutions and 6 GQA attention layers (at 2, 6, 10, 14, 18, 21: no
    # fixed period), heads of 64 with QK-norm, two dense FFNs then 32 experts
    # of 1,792 routed 4 a token by biased sigmoid scores, no capacity rule.
    # The head is tied to the embedding, as published.
    "lfm2-8b-a1b": ModelConfig(
        family="hybrid", vocab_size=65536, hidden_size=2048,
        intermediate_size=7168, moe_intermediate_size=1792, num_layers=24,
        num_dense_layers=2, num_heads=32, num_kv_heads=8, max_seq_len=128000,
        rope_theta=1e6, norm_eps=1e-5, tie_embeddings=True, qk_norm=True,
        conv_kernel=3,
        layer_types=tuple(
            "attn" if l in (2, 6, 10, 14, 18, 21) else "conv"
            for l in range(24)
        ),
        num_experts=32, num_experts_per_token=4, moe_score_fn="sigmoid",
        moe_expert_bias=True, moe_norm_topk=True, moe_routed_scale=1.0,
        moe_capacity=False,
    ),
    # A.X-K1 (SKT, model_type axk1) as ONE CHIP'S SHARE of a 16-chip
    # pipeline stage: the published widths (latent attention at 64 heads,
    # q/kv ranks 1536/512, heads of 128 + 64 and 128, YaRN 32 x 4,096; a
    # dense SwiGLU of 18,432 then expert layers of 192 routed (8 a token,
    # sigmoid scores, 8 groups of which 4 are kept, normalised, x 2.5) and
    # one shared expert of 2,048), cut in depth to 1 + 12 of the 61 layers,
    # to experts 0-11 of every layer's 192 (the router keeps all 192
    # outputs) and to a 20,480-row slice of the vocabulary:
    # benchmark/configs/ax-k1-int8-ep16.json has the deployment.
    "ax-k1-ep16": ModelConfig(
        family="hybrid", vocab_size=20480, hidden_size=7168,
        intermediate_size=18432, moe_intermediate_size=2048, num_layers=13,
        num_dense_layers=1, num_heads=64, num_kv_heads=64, head_dim=192,
        max_seq_len=131072, rope_theta=10000.0, norm_eps=1e-6,
        tie_embeddings=False, layer_types=("mla",) * 13,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        rope_scaling_type="yarn", rope_scaling_factor=32.0,
        rope_original_max_len=4096, yarn_beta_fast=32.0, yarn_beta_slow=1.0,
        yarn_mscale=1.0, yarn_mscale_all_dim=1.0,
        num_experts=192, num_experts_per_token=8, moe_score_fn="sigmoid",
        moe_norm_topk=True, moe_norm_eps=1e-20, moe_routed_scale=2.5,
        moe_capacity=False, n_shared_experts=1, moe_n_group=8,
        moe_topk_group=4, experts_held=12, experts_offset=0,
    ),
    # K-EXAONE-236B-A23B (LG AI Research, model_type exaone_moe) as ONE
    # CHIP'S SHARE of an 8-chip pipeline stage: the published widths (64
    # query heads over 8 key/value heads of 128 with QK-norm; layers "LLLG",
    # three with a window of 128 and rotation, one over the whole prefix
    # with NO rotation; a dense SwiGLU of 18,432 then expert layers of 128
    # routed (8 a token, sigmoid scores, no groups, normalised, x 2.5) and
    # one shared expert of 2,048), cut in depth to stage 0's 12 of the 48
    # layers, to experts 0-15 of every layer's 128 (the router keeps all
    # 128 outputs) and to a 19,200-row slice of the vocabulary; the
    # multi-token-prediction layer is not served:
    # benchmark/configs/k-exaone-int8-ep8.json has the deployment.
    "k-exaone-ep8": ModelConfig(
        family="hybrid", vocab_size=19200, hidden_size=6144,
        intermediate_size=18432, moe_intermediate_size=2048, num_layers=12,
        num_dense_layers=1, num_heads=64, num_kv_heads=8, head_dim=128,
        max_seq_len=262144, rope_theta=1e6, norm_eps=1e-5,
        tie_embeddings=False, qk_norm=True, attn_rope=False,
        sliding_window=128, layer_types=("swa", "swa", "swa", "attn") * 3,
        num_experts=128, num_experts_per_token=8, moe_score_fn="sigmoid",
        moe_norm_topk=True, moe_norm_eps=1e-20, moe_routed_scale=2.5,
        moe_capacity=False, n_shared_experts=1, experts_held=16,
        experts_offset=0,
    ),
    # SmallThinker-21BA3B-Instruct (PowerInfer, model_name
    # smallthinker_21b_instruct) as STAGE 0 of a four-chip host's pipeline:
    # every published width (28 query heads over 4 key/value heads of 128,
    # no QK-norm, no biases; layers full / window / window / window, the
    # windowed ones rotated with a window of 4,096, the full ones NOT
    # rotated; every layer 64 ReLU-gated experts of 768 routed 6 a token by
    # a softmax over the chosen, the router reading the block's INPUT; no
    # dense layer, no shared expert; the whole vocabulary, head untied, the
    # whole 16,384 context), cut in depth to the stage's 12 of the 52
    # layers: benchmark/configs/smallthinker-21ba3b-int8.json has the
    # deployment.
    "smallthinker-pp4": ModelConfig(
        family="hybrid", vocab_size=151936, hidden_size=2560,
        intermediate_size=768, moe_intermediate_size=768, num_layers=12,
        num_dense_layers=0, num_heads=28, num_kv_heads=4, head_dim=128,
        max_seq_len=16384, rope_theta=1.5e6, norm_eps=1e-6,
        tie_embeddings=False, qk_norm=False, attn_rope=False,
        sliding_window=4096, layer_types=("attn", "swa", "swa", "swa") * 3,
        gate_act="relu", num_experts=64, num_experts_per_token=6,
        moe_score_fn="softmax", moe_router_input="block_input",
        moe_capacity=False, n_shared_experts=0,
    ),
    # Brumby-14B-Base (manifestai/Brumby-14B-Base), stage 0 of a four-chip
    # host cut into 4 pipeline stages of 10 whole layers: 10 of the 40
    # power-retention layers (40 query heads over 8 key/value heads of 128,
    # Qwen3's per-head norm on q and k, the rotation kept) in front of a
    # SwiGLU of 17,408, the whole vocabulary, embedding and head untied
    # (benchmark/configs/brumby-14b-int8.json has the cut's arithmetic).
    "brumby-pp4": ModelConfig(
        family="hybrid", vocab_size=151936, hidden_size=5120,
        intermediate_size=17408, num_layers=10, num_heads=40,
        num_kv_heads=8, head_dim=128, max_seq_len=32768, rope_theta=1e6,
        norm_eps=1e-6, tie_embeddings=False, qk_norm=True,
        layer_types=("ret",) * 10,
    ),
    # NVIDIA-Nemotron-3-Super-120B-A12B (model_type nemotron_h) as ONE
    # CHIP'S SHARE of a four-chip pipeline stage: the first 22 of the 88
    # published single sub-layers (`MEMEMEM*EMEMEMEM*EMEME`: 10 Mamba-2, 10
    # LatentMoE, 2 GQA) folded into 12 (operator, FFN) blocks, two of them
    # with no FFN (:func:`blocks_of_pattern`); every width; experts 0-127 of
    # the 512 (the router keeps 512 outputs and 22 a token), a 32,768-row
    # slice of the vocabulary; no rotation in the attention layers; the
    # multi-token-prediction layer is not served:
    # benchmark/configs/nemotron3-super-int8-ep4.json has the deployment.
    "nemotron3-super-ep4": ModelConfig(
        family="hybrid", vocab_size=32768, hidden_size=4096,
        intermediate_size=5376, moe_intermediate_size=2688, num_heads=32,
        num_kv_heads=2, head_dim=128, max_seq_len=262144, rope_theta=10000.0,
        norm_eps=1e-5, tie_embeddings=False, attn_rope=False,
        **blocks_of_pattern("MEMEMEM*EMEMEMEM*EMEME"),
        gate_act="relu2", num_experts=512, num_experts_per_token=22,
        moe_score_fn="sigmoid", moe_expert_bias=True, moe_norm_eps=1e-20,
        moe_routed_scale=5.0, moe_capacity=False, n_shared_experts=1,
        moe_shared_intermediate_size=5376, moe_latent_size=1024,
        experts_held=128, experts_offset=0, ssm_heads=128, ssm_head_dim=64,
        ssm_groups=8, ssm_state=128, ssm_conv_kernel=4, ssm_chunk=128,
    ),
    # Qwen3-Next-80B-A3B-Instruct (model_type qwen3_next) as ONE CHIP'S SHARE
    # of a four-chip pipeline stage: published layers 0-11, three periods of
    # (3 Gated DeltaNet, 1 gated attention), every one with the expert layer
    # behind it; every width; experts 0-127 of the 512 (the router keeps 512
    # outputs and 10 a token) and the gated shared expert; a quarter of the
    # vocabulary (37,984 rows); the multi-token-prediction layer is not
    # served: benchmark/configs/qwen3-next-int8-ep4.json has the deployment.
    "qwen3-next-ep4": ModelConfig(
        family="hybrid", vocab_size=37984, hidden_size=2048,
        intermediate_size=5120, moe_intermediate_size=512, num_layers=12,
        num_heads=16, num_kv_heads=2, head_dim=256, max_seq_len=262144,
        rope_theta=10000000.0, rotary_pct=0.25, norm_eps=1e-6,
        tie_embeddings=False, qk_norm=True, attn_out_gate=True,
        layer_types=("gdn", "gdn", "gdn", "attn") * 3,
        num_experts=512, num_experts_per_token=10, moe_score_fn="softmax",
        moe_capacity=False, n_shared_experts=1,
        moe_shared_intermediate_size=512, moe_shared_gate=True,
        experts_held=128, experts_offset=0, gdn_key_heads=16,
        gdn_value_heads=32, gdn_key_dim=128, gdn_value_dim=128,
        gdn_conv_kernel=4, gdn_chunk=64,
    ),
    # Tiny configs for unit tests / CPU fake-mesh integration tests.
    "qwen3-next-tiny": ModelConfig(
        family="hybrid", vocab_size=256, hidden_size=256,
        intermediate_size=128, moe_intermediate_size=128, num_layers=8,
        num_heads=4, num_kv_heads=2, head_dim=64, max_seq_len=1024,
        rope_theta=10000.0, rotary_pct=0.25, norm_eps=1e-6,
        tie_embeddings=False, dtype="float32", qk_norm=True,
        attn_out_gate=True, layer_types=("gdn", "gdn", "gdn", "attn") * 2,
        num_experts=32, num_experts_per_token=4, moe_score_fn="softmax",
        moe_capacity=False, n_shared_experts=1,
        moe_shared_intermediate_size=128, moe_shared_gate=True,
        experts_held=8, experts_offset=0, gdn_key_heads=2,
        gdn_value_heads=4, gdn_key_dim=128, gdn_value_dim=128,
        gdn_conv_kernel=4, gdn_chunk=64,
    ),
    "nemotron3-super-tiny": ModelConfig(
        family="hybrid", vocab_size=256, hidden_size=256,
        intermediate_size=128, moe_intermediate_size=96, num_heads=4,
        num_kv_heads=2, head_dim=32, max_seq_len=1024, norm_eps=1e-5,
        tie_embeddings=False, dtype="float32", attn_rope=False,
        **blocks_of_pattern("MEM*EME"),
        gate_act="relu2", num_experts=32, num_experts_per_token=6,
        moe_score_fn="sigmoid", moe_expert_bias=True, moe_norm_eps=1e-20,
        moe_routed_scale=5.0, moe_capacity=False, n_shared_experts=1,
        moe_shared_intermediate_size=128, moe_latent_size=64,
        experts_held=8, experts_offset=0, ssm_heads=16, ssm_head_dim=64,
        ssm_groups=2, ssm_state=128, ssm_conv_kernel=4, ssm_chunk=128,
    ),
    "brumby-tiny": ModelConfig(
        family="hybrid", vocab_size=256, hidden_size=64,
        intermediate_size=224, num_layers=2, num_heads=10, num_kv_heads=2,
        head_dim=128, max_seq_len=512, rope_theta=1e6, norm_eps=1e-6,
        tie_embeddings=False, dtype="float32", qk_norm=True,
        layer_types=("ret",) * 2, ret_chunk=64,
    ),
    "smallthinker-tiny": ModelConfig(
        family="hybrid", vocab_size=256, hidden_size=64,
        intermediate_size=32, moe_intermediate_size=32, num_layers=8,
        num_dense_layers=0, num_heads=4, num_kv_heads=2, head_dim=16,
        max_seq_len=256, rope_theta=1.5e6, norm_eps=1e-6,
        tie_embeddings=False, dtype="float32", qk_norm=False,
        attn_rope=False, sliding_window=8,
        layer_types=("attn", "swa", "swa", "swa") * 2,
        gate_act="relu", num_experts=16, num_experts_per_token=6,
        moe_score_fn="softmax", moe_router_input="block_input",
        moe_capacity=False, n_shared_experts=0,
    ),
    "k-exaone-tiny": ModelConfig(
        family="hybrid", vocab_size=256, hidden_size=64,
        intermediate_size=128, moe_intermediate_size=32, num_layers=8,
        num_dense_layers=1, num_heads=4, num_kv_heads=2, head_dim=16,
        max_seq_len=256, rope_theta=1e6, norm_eps=1e-5,
        tie_embeddings=False, dtype="float32", qk_norm=True,
        attn_rope=False, sliding_window=8,
        layer_types=("swa", "swa", "swa", "attn") * 2,
        num_experts=16, num_experts_per_token=4, moe_score_fn="sigmoid",
        moe_norm_eps=1e-20, moe_routed_scale=2.5, moe_capacity=False,
        n_shared_experts=1,
    ),
    "ax-k1-tiny": ModelConfig(
        family="hybrid", vocab_size=256, hidden_size=64,
        intermediate_size=128, moe_intermediate_size=32, num_layers=4,
        num_dense_layers=1, num_heads=4, num_kv_heads=4, head_dim=24,
        max_seq_len=256, rope_theta=10000.0, norm_eps=1e-6,
        tie_embeddings=False, dtype="float32", layer_types=("mla",) * 4,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=12,
        rope_scaling_type="yarn", rope_scaling_factor=4.0,
        rope_original_max_len=32, yarn_mscale_all_dim=1.0,
        num_experts=16, num_experts_per_token=4, moe_score_fn="sigmoid",
        moe_norm_eps=1e-20, moe_routed_scale=2.5, moe_capacity=False,
        n_shared_experts=1, moe_n_group=4, moe_topk_group=2,
    ),
    "lfm2-tiny": ModelConfig(
        family="hybrid", vocab_size=256, hidden_size=64,
        intermediate_size=128, moe_intermediate_size=32, num_layers=8,
        num_dense_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        max_seq_len=128, rope_theta=1e6, tie_embeddings=True, qk_norm=True,
        dtype="float32", conv_kernel=3,
        layer_types=("conv", "conv", "attn", "conv", "conv", "conv", "attn",
                     "conv"),
        num_experts=8, num_experts_per_token=2, moe_score_fn="sigmoid",
        moe_expert_bias=True, moe_capacity=False,
    ),
    "moe-tiny": ModelConfig(
        family="llama", vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
        tie_embeddings=False, dtype="float32",
        num_experts=4, num_experts_per_token=2,
    ),
    "gpt2-tiny": ModelConfig(
        family="gpt2", vocab_size=256, hidden_size=64, intermediate_size=256,
        num_layers=4, num_heads=4, num_kv_heads=4, max_seq_len=128,
        tie_embeddings=True, dtype="float32",
    ),
    "opt-tiny": ModelConfig(
        family="opt", vocab_size=256, hidden_size=64, intermediate_size=256,
        num_layers=4, num_heads=4, num_kv_heads=4, max_seq_len=128,
        tie_embeddings=True, dtype="float32", activation="relu",
    ),
    "neox-tiny": ModelConfig(
        family="neox", vocab_size=256, hidden_size=64, intermediate_size=176,
        num_layers=3, num_heads=4, num_kv_heads=4, max_seq_len=128,
        rotary_pct=0.25, parallel_residual=True, tie_embeddings=False,
        dtype="float32", activation="gelu_exact",
    ),
    "llama-tiny": ModelConfig(
        family="llama", vocab_size=256, hidden_size=64, intermediate_size=176,
        num_layers=4, num_heads=4, num_kv_heads=2, max_seq_len=128,
        tie_embeddings=False, dtype="float32",
    ),
}

# HF hub repo ids for the checkpoint converter.
HF_REPOS: dict[str, str] = {
    "gpt2-125m": "gpt2",
    "gpt2-medium": "gpt2-medium",
    "opt-125m": "facebook/opt-125m",
    "tinyllama-1.1b": "TinyLlama/TinyLlama-1.1B-Chat-v1.0",
    "llama-2-7b": "meta-llama/Llama-2-7b-hf",
    "llama-2-13b": "meta-llama/Llama-2-13b-hf",
    "llama-3-8b": "meta-llama/Meta-Llama-3-8B",
    "llama-3.1-8b": "meta-llama/Llama-3.1-8B",
    "llama-3-70b": "meta-llama/Meta-Llama-3-70B",
    "qwen2-7b": "Qwen/Qwen2-7B",
    "gemma-7b": "google/gemma-7b",
    "mistral-7b": "mistralai/Mistral-7B-v0.1",
    "phi-3-mini-4k": "microsoft/Phi-3-mini-4k-instruct",
    "pythia-6.9b": "EleutherAI/pythia-6.9b",
    "lfm2-8b-a1b": "LiquidAI/LFM2-8B-A1B",
}


def get_preset(name: str, **overrides) -> ModelConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown preset {name!r}; have {sorted(PRESETS)}")
    cfg = PRESETS[name]
    return replace(cfg, **overrides) if overrides else cfg
