"""Model forward tests: shapes, KV-cache consistency, and golden parity
against torch transformers (randomly-initialized tiny models — no downloads,
mirroring the reference's patched-hub test technique, SURVEY §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.models import kv_cache, model, presets
from distributed_llms_tpu.checkpoint import convert


@pytest.mark.parametrize("name", ["gpt2-tiny", "llama-tiny", "opt-tiny", "neox-tiny"])
def test_forward_shapes(name):
    cfg = presets.get_preset(name)
    params = model.init_params(jax.random.key(0), cfg)
    toks = jnp.array([[1, 2, 3, 4, 5], [5, 4, 3, 2, 1]], dtype=jnp.int32)
    logits, cache = model.forward(params, cfg, toks)
    assert logits.shape == (2, 5, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert cache is None


@pytest.mark.parametrize("name", ["gpt2-tiny", "llama-tiny", "opt-tiny", "neox-tiny"])
def test_kv_cache_matches_full_forward(name):
    cfg = presets.get_preset(name)
    params = model.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 9), 0, cfg.vocab_size, dtype=jnp.int32)

    full_logits, _ = model.forward(params, cfg, toks)

    # prefill 6 tokens, then decode 3 incrementally
    cache = kv_cache.init_cache(cfg, 2, 16)
    pre_logits, cache = model.forward(params, cfg, toks[:, :6], cache=cache, cache_index=jnp.int32(0))
    np.testing.assert_allclose(np.asarray(full_logits[:, :6]), np.asarray(pre_logits), rtol=1e-4, atol=1e-4)
    for t in range(6, 9):
        step_logits, cache = model.forward(
            params, cfg, toks[:, t : t + 1], cache=cache, cache_index=jnp.int32(t)
        )
        np.testing.assert_allclose(
            np.asarray(full_logits[:, t]), np.asarray(step_logits[:, 0]), rtol=1e-3, atol=1e-3
        )


def test_causality():
    """Changing a future token must not affect past logits."""
    cfg = presets.get_preset("llama-tiny")
    params = model.init_params(jax.random.key(0), cfg)
    a = jnp.array([[1, 2, 3, 4, 5, 6]], dtype=jnp.int32)
    b = a.at[0, 5].set(99)
    la, _ = model.forward(params, cfg, a)
    lb, _ = model.forward(params, cfg, b)
    np.testing.assert_allclose(np.asarray(la[:, :5]), np.asarray(lb[:, :5]), atol=1e-5)
    assert np.abs(np.asarray(la[:, 5]) - np.asarray(lb[:, 5])).max() > 1e-3


def _hf_gpt2_pair():
    import torch
    from transformers import GPT2Config, GPT2LMHeadModel

    hf_cfg = GPT2Config(
        vocab_size=97, n_positions=64, n_embd=32, n_layer=3, n_head=4,
        activation_function="gelu_new", resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
    )
    torch.manual_seed(0)
    hf_model = GPT2LMHeadModel(hf_cfg).eval()
    cfg = convert.config_from_hf(hf_cfg.to_dict())
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": "float32"})
    sd = convert.torch_state_dict_to_numpy(hf_model.state_dict())
    params = convert.convert_state_dict(sd, cfg)
    return hf_model, cfg, params


def _hf_llama_pair():
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    hf_cfg = LlamaConfig(
        vocab_size=97, hidden_size=32, intermediate_size=88, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
        rms_norm_eps=1e-5, tie_word_embeddings=False, attention_dropout=0.0,
    )
    torch.manual_seed(0)
    hf_model = LlamaForCausalLM(hf_cfg).eval()
    cfg = convert.config_from_hf(hf_cfg.to_dict())
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": "float32"})
    sd = convert.torch_state_dict_to_numpy(hf_model.state_dict())
    params = convert.convert_state_dict(sd, cfg)
    return hf_model, cfg, params


def _hf_opt_pair():
    import torch
    from transformers import OPTConfig, OPTForCausalLM

    hf_cfg = OPTConfig(
        vocab_size=97, hidden_size=32, ffn_dim=88, num_hidden_layers=3,
        num_attention_heads=4, max_position_embeddings=64,
        activation_function="relu", do_layer_norm_before=True,
        word_embed_proj_dim=32, dropout=0.0, attention_dropout=0.0,
    )
    torch.manual_seed(0)
    hf_model = OPTForCausalLM(hf_cfg).eval()
    cfg = convert.config_from_hf(hf_cfg.to_dict())
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": "float32"})
    sd = convert.torch_state_dict_to_numpy(hf_model.state_dict())
    params = convert.convert_state_dict(sd, cfg)
    return hf_model, cfg, params


def _hf_qwen2_pair():
    import torch
    from transformers import Qwen2Config, Qwen2ForCausalLM

    hf_cfg = Qwen2Config(
        vocab_size=97, hidden_size=32, intermediate_size=88,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-6,
        tie_word_embeddings=False, attention_dropout=0.0,
        use_sliding_window=False,
    )
    torch.manual_seed(0)
    hf_model = Qwen2ForCausalLM(hf_cfg).eval()
    cfg = convert.config_from_hf(hf_cfg.to_dict())
    assert cfg.qkv_bias  # Qwen2's delta from llama
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": "float32"})
    sd = convert.torch_state_dict_to_numpy(hf_model.state_dict())
    params = convert.convert_state_dict(sd, cfg)
    assert "bq" in params["blocks"]["attn"]
    return hf_model, cfg, params


def _hf_gemma_pair():
    import torch
    from transformers import GemmaConfig, GemmaForCausalLM

    hf_cfg = GemmaConfig(
        vocab_size=97, hidden_size=32, intermediate_size=88,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16,  # explicit: heads * head_dim != hidden is Gemma-legal
        max_position_embeddings=64, rms_norm_eps=1e-6,
        hidden_activation="gelu_pytorch_tanh", attention_dropout=0.0,
    )
    torch.manual_seed(0)
    hf_model = GemmaForCausalLM(hf_cfg).eval()
    cfg = convert.config_from_hf(hf_cfg.to_dict())
    assert cfg.gate_act == "gelu_tanh" and cfg.norm_plus_one
    assert cfg.head_dim_ == 16 and cfg.embed_scale == 32.0**0.5
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": "float32"})
    sd = convert.torch_state_dict_to_numpy(hf_model.state_dict())
    params = convert.convert_state_dict(sd, cfg)
    return hf_model, cfg, params


def _hf_llama31_pair():
    """Llama-3.1-style rope_scaling (rope_type llama3): the tiny
    original_max_position_embeddings forces several frequencies into the
    scaled and smoothed bands, so the piecewise rescale is live."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    hf_cfg = LlamaConfig(
        vocab_size=97, hidden_size=32, intermediate_size=88,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5,
        tie_word_embeddings=False, attention_dropout=0.0,
        rope_theta=10000.0,
        rope_scaling={"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 16},
    )
    torch.manual_seed(0)
    hf_model = LlamaForCausalLM(hf_cfg).eval()
    cfg = convert.config_from_hf(hf_cfg.to_dict())
    assert cfg.rope_scaling_factor == 8.0 and cfg.rope_original_max_len == 16
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": "float32"})
    sd = convert.torch_state_dict_to_numpy(hf_model.state_dict())
    params = convert.convert_state_dict(sd, cfg)
    return hf_model, cfg, params


def _hf_phi3_pair():
    import torch
    from transformers import Phi3Config, Phi3ForCausalLM

    hf_cfg = Phi3Config(
        vocab_size=97, hidden_size=32, intermediate_size=88,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5,
        tie_word_embeddings=False, attention_dropout=0.0, resid_pdrop=0.0,
        embd_pdrop=0.0, sliding_window=3, attn_implementation="eager",
        pad_token_id=0, bos_token_id=1, eos_token_id=2,
    )
    torch.manual_seed(0)
    hf_model = Phi3ForCausalLM(hf_cfg).eval()
    cfg = convert.config_from_hf(hf_cfg.to_dict())
    # Phi-3's deltas from llama: fused projections (split at convert) and
    # the sliding window.
    assert cfg.sliding_window == 3 and not cfg.qkv_bias
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": "float32"})
    sd = convert.torch_state_dict_to_numpy(hf_model.state_dict())
    params = convert.convert_state_dict(sd, cfg)
    return hf_model, cfg, params


def _hf_neox_pair(parallel=True):
    """GPT-NeoX/Pythia: interleaved fused qkv, PARTIAL rotary (pct 0.25 of
    head_dim 16 = 4 rotated dims), parallel residual (the NeoX default)."""
    import torch
    from transformers import GPTNeoXConfig, GPTNeoXForCausalLM

    hf_cfg = GPTNeoXConfig(
        vocab_size=97, hidden_size=64, intermediate_size=176,
        num_hidden_layers=3, num_attention_heads=4,
        max_position_embeddings=64, rotary_pct=0.25,
        use_parallel_residual=parallel, hidden_act="gelu",
        layer_norm_eps=1e-5, tie_word_embeddings=False,
        attention_dropout=0.0, hidden_dropout=0.0,
    )
    torch.manual_seed(0)
    hf_model = GPTNeoXForCausalLM(hf_cfg).eval()
    cfg = convert.config_from_hf(hf_cfg.to_dict())
    assert cfg.family == "neox" and cfg.rotary_pct == 0.25
    assert cfg.parallel_residual is parallel
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": "float32"})
    sd = convert.torch_state_dict_to_numpy(hf_model.state_dict())
    params = convert.convert_state_dict(sd, cfg)
    return hf_model, cfg, params


def _hf_neox_seq_pair():
    return _hf_neox_pair(parallel=False)


@pytest.mark.parametrize(
    "maker",
    [_hf_gpt2_pair, _hf_llama_pair, _hf_opt_pair, _hf_qwen2_pair,
     _hf_gemma_pair, _hf_phi3_pair, _hf_llama31_pair, _hf_neox_pair,
     _hf_neox_seq_pair],
    ids=["gpt2", "llama", "opt", "qwen2", "gemma", "phi3", "llama31",
         "neox", "neox-seq"],
)
def test_golden_parity_vs_transformers(maker):
    import torch

    hf_model, cfg, params = maker()
    toks = np.array([[3, 14, 15, 92, 65, 35], [8, 9, 79, 3, 2, 38]], dtype=np.int64)
    with torch.no_grad():
        ref = hf_model(torch.tensor(toks)).logits.float().numpy()
    ours, _ = model.forward(params, cfg, jnp.asarray(toks, dtype=jnp.int32))
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=2e-3, atol=2e-3)


def test_config_from_hf_rejects_unknown():
    with pytest.raises(ValueError):
        convert.config_from_hf({"model_type": "mamba"})


def test_config_from_hf_neox_rejects_tied_embeddings():
    with pytest.raises(ValueError, match="tied"):
        convert.config_from_hf(dict(
            model_type="gpt_neox", vocab_size=100, hidden_size=64,
            intermediate_size=176, num_hidden_layers=2,
            num_attention_heads=4, max_position_embeddings=64,
            tie_word_embeddings=True,
        ))


def test_config_from_hf_rejects_non_llama3_rope_scaling():
    base = dict(
        model_type="llama", vocab_size=100, hidden_size=32,
        intermediate_size=88, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=4096,
    )
    for rtype in ("linear", "dynamic", "yarn"):
        with pytest.raises(ValueError, match="rope_scaling"):
            convert.config_from_hf(
                {**base, "rope_scaling": {"rope_type": rtype, "factor": 2.0}}
            )
    for mt in ("mistral", "qwen2", "gemma"):
        with pytest.raises(ValueError, match="rope_scaling"):
            convert.config_from_hf(
                {**base, "model_type": mt,
                 "rope_scaling": {"rope_type": "yarn", "factor": 2.0}}
            )
    # Malformed llama3 blocks fail loudly too — a zero-width smooth band
    # would serve NaN frequencies, a missing factor a bare KeyError.
    with pytest.raises(ValueError, match="factor"):
        convert.config_from_hf(
            {**base, "rope_scaling": {"rope_type": "llama3"}}
        )
    with pytest.raises(ValueError, match="high_freq_factor"):
        convert.config_from_hf(
            {**base, "rope_scaling": {"rope_type": "llama3", "factor": 8.0,
                                      "low_freq_factor": 2.0,
                                      "high_freq_factor": 2.0}}
        )


def test_config_from_hf_phi3_rejects_longrope():
    base = dict(
        model_type="phi3", vocab_size=100, hidden_size=32,
        intermediate_size=88, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=4096,
        sliding_window=2047,
    )
    assert convert.config_from_hf(base).sliding_window == 2047
    with pytest.raises(ValueError, match="rope_scaling"):
        convert.config_from_hf(
            {**base, "rope_scaling": {"type": "longrope",
                                      "short_factor": [1.0]}}
        )
    with pytest.raises(ValueError, match="partial_rotary"):
        convert.config_from_hf({**base, "partial_rotary_factor": 0.5})
