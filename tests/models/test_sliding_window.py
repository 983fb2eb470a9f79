"""Sliding-window attention (Mistral family).

Reference surface: the upstream framework has no windowed-attention model at
all (its compute is a placeholder matmul, src/worker/node.py:24-32); this
covers the Mistral architecture the way SURVEY §4's golden-parity strategy
covers every family — randomly-initialized tiny HF models, no downloads.

Core invariants:
- a 1-layer windowed model's last-position logits over a long sequence equal
  a run over only the last `window` tokens (RoPE positions preserved) — the
  mask, not the cache size, bounds the span;
- cached decode matches the no-cache forward token-for-token past the window;
- golden parity vs torch transformers' MistralForCausalLM with the window
  active (seq > window);
- the continuous batcher serves windowed models via masks (ragged/paged
  kernels, which read the full prefix, are refused loudly).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.checkpoint import convert
from distributed_llms_tpu.core.config import ModelConfig
from distributed_llms_tpu.models import kv_cache, model, presets


def _windowed_tiny(window=4, num_layers=4):
    return presets.get_preset("llama-tiny", sliding_window=window,
                              num_layers=num_layers)


def test_window_bounds_attention_span_one_layer():
    """1 layer ⇒ the receptive field IS the window: last-position logits over
    the full sequence must equal a forward over only the last `window` tokens
    at their true RoPE positions."""
    cfg = _windowed_tiny(window=4, num_layers=1)
    params = model.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 10), 0, cfg.vocab_size,
                              dtype=jnp.int32)
    full, _ = model.forward(params, cfg, toks)
    tail = toks[:, 6:10]
    positions = jnp.broadcast_to(jnp.arange(6, 10, dtype=jnp.int32), (2, 4))
    tail_logits, _ = model.forward(params, cfg, tail, positions=positions)
    np.testing.assert_allclose(
        np.asarray(full[:, -1]), np.asarray(tail_logits[:, -1]),
        rtol=1e-4, atol=1e-4,
    )


def test_windowed_differs_from_global():
    cfg = _windowed_tiny(window=3)
    cfg_global = dataclasses.replace(cfg, sliding_window=None)
    params = model.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (1, 8), 0, cfg.vocab_size,
                              dtype=jnp.int32)
    lw, _ = model.forward(params, cfg, toks)
    lg, _ = model.forward(params, cfg_global, toks)
    # Positions inside the first window agree; past it they must diverge.
    np.testing.assert_allclose(np.asarray(lw[:, :3]), np.asarray(lg[:, :3]),
                               rtol=1e-4, atol=1e-4)
    assert np.abs(np.asarray(lw[:, -1]) - np.asarray(lg[:, -1])).max() > 1e-3


def test_kv_cache_matches_full_forward_windowed():
    """Prefill + incremental decode through the cache must reproduce the
    no-cache windowed forward even past the window boundary."""
    cfg = _windowed_tiny(window=4)
    params = model.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 9), 0, cfg.vocab_size,
                              dtype=jnp.int32)
    full_logits, _ = model.forward(params, cfg, toks)
    cache = kv_cache.init_cache(cfg, 2, 16)
    pre, cache = model.forward(params, cfg, toks[:, :6], cache=cache,
                               cache_index=jnp.int32(0))
    np.testing.assert_allclose(np.asarray(full_logits[:, :6]), np.asarray(pre),
                               rtol=1e-4, atol=1e-4)
    for t in range(6, 9):
        step, cache = model.forward(params, cfg, toks[:, t:t + 1], cache=cache,
                                    cache_index=jnp.int32(t))
        np.testing.assert_allclose(np.asarray(full_logits[:, t]),
                                   np.asarray(step[:, 0]), rtol=1e-3, atol=1e-3)


@pytest.mark.fragile_xla_cpu
def test_flash_impl_matches_windowed_dot(monkeypatch):
    """attn_impl='flash' on a windowed model rides the kernel's window
    band (ops/flash.py window=) for no-cache forwards, and a cached prefill
    rides it on the kernel's legs whatever ``attn_impl`` says (PR 47:
    models.model._continuation_attention; here the interpreter's), matching
    the masked dot path exactly; the windowed generate loop stays
    token-identical too (decode steps keep the dense path)."""
    from distributed_llms_tpu.runtime import generate as gen_lib

    cfg = _windowed_tiny(window=3)
    cfg_flash = dataclasses.replace(cfg, attn_impl="flash")
    params = model.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (1, 8), 0, cfg.vocab_size,
                              dtype=jnp.int32)
    a, _ = model.forward(params, cfg, toks)
    b, _ = model.forward(params, cfg_flash, toks)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)
    # Ragged generate: windowed flash prefill into the padded cache must
    # emit the same tokens as the dot path (window crossed mid-decode).
    prompt = jnp.asarray([[7, 1, 9, 0, 0, 0], [4] * 6], jnp.int32)
    lens = jnp.asarray([3, 6], jnp.int32)
    ref = gen_lib.generate_tokens(
        params, cfg, prompt, lens, jax.random.key(2), max_new_tokens=8,
    )
    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    out = gen_lib.generate_tokens(
        params, cfg_flash, prompt, lens, jax.random.key(2), max_new_tokens=8,
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_golden_parity_vs_transformers_mistral():
    import torch
    from transformers import MistralConfig, MistralForCausalLM

    hf_cfg = MistralConfig(
        vocab_size=97, hidden_size=32, intermediate_size=88,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5,
        tie_word_embeddings=False, attention_dropout=0.0,
        sliding_window=3, attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = MistralForCausalLM(hf_cfg).eval()
    cfg = convert.config_from_hf(hf_cfg.to_dict())
    assert cfg.sliding_window == 3  # the Mistral delta from llama
    cfg = dataclasses.replace(cfg, dtype="float32")
    sd = convert.torch_state_dict_to_numpy(hf_model.state_dict())
    params = convert.convert_state_dict(sd, cfg)
    toks = np.array([[3, 14, 15, 92, 65, 35], [8, 9, 79, 3, 2, 38]],
                    dtype=np.int64)
    with torch.no_grad():
        ref = hf_model(torch.tensor(toks)).logits.float().numpy()
    ours, _ = model.forward(params, cfg, jnp.asarray(toks, dtype=jnp.int32))
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=2e-3, atol=2e-3)


def test_config_from_hf_mistral_window_mapping():
    base = dict(
        model_type="mistral", vocab_size=32000, hidden_size=64,
        intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=1024,
    )
    cfg = convert.config_from_hf({**base, "sliding_window": 256})
    assert cfg.family == "llama" and cfg.sliding_window == 256
    # v0.2+ style: null window -> global attention.
    assert convert.config_from_hf({**base, "sliding_window": None}).sliding_window is None
    # window >= max_len degenerates to global; keep the cheap mask.
    assert convert.config_from_hf({**base, "sliding_window": 4096}).sliding_window is None


def test_invalid_window_combos_rejected():
    with pytest.raises(ValueError, match="ring"):
        presets.get_preset("llama-tiny", sliding_window=4, attn_impl="ring")
    # ragged_decode + window COMPOSES since the kernel carries the window
    # band (ops/decode_attn.py) — only seq-parallel impls still reject.
    cfg = presets.get_preset("llama-tiny", sliding_window=4,
                             ragged_decode=True)
    assert cfg.sliding_window == 4 and cfg.ragged_decode
    with pytest.raises(ValueError, match="sliding_window must be"):
        ModelConfig(family="llama", sliding_window=0)


def test_batcher_serves_windowed_model_exactly():
    """Mixed budgets through the batcher on a windowed model must match solo
    decodes token-for-token (the window rides the batcher's per-row masks)."""
    from distributed_llms_tpu.runtime import generate as gen_lib
    from distributed_llms_tpu.runtime.batcher import ContinuousBatcher

    cfg = presets.get_preset("llama-tiny", vocab_size=512, sliding_window=5)
    params = model.init_params(jax.random.key(0), cfg)
    b = ContinuousBatcher(cfg, params, batch_slots=2, max_len=64, chunk_steps=4)
    # Off-TPU default is the dense fallback; under kernel/interpret modes
    # windowed models now ride the ragged kernel's window band (exactness
    # under interpret is pinned by tests/ops/test_decode_attn.py).
    reqs = [([7, 1, 9, 4, 2, 8, 3], 8), ([4, 4, 4], 6), ([11, 12], 10)]
    rids = [b.submit(ids, max_new_tokens=n) for ids, n in reqs]
    res = b.run()
    for rid, (ids, n) in zip(rids, reqs):
        out = gen_lib.generate_tokens(
            params, cfg, jnp.asarray([ids], jnp.int32),
            jnp.asarray([len(ids)], jnp.int32), jax.random.key(9),
            max_new_tokens=n, eos_id=-1, pad_id=0,
        )
        assert res[rid] == np.asarray(out)[0].tolist()


def test_ragged_batch_windowed_decode_matches_solo():
    """REGRESSION (r4 review): the right-padded generate layout puts
    generated slot T+j at position len+j; the window mask must compare
    POSITIONS, not slots, or short rows in a ragged batch attend (T - len)
    positions past the window.  Each padded row must match its own solo
    (pad-free) run exactly."""
    from distributed_llms_tpu.runtime import generate as gen_lib

    cfg = presets.get_preset("llama-tiny", vocab_size=512, sliding_window=3)
    params = model.init_params(jax.random.key(0), cfg)
    prompts = [[7, 1, 9], [4, 4, 4, 4, 4, 4, 4, 4]]
    t = max(len(p) for p in prompts)
    padded = jnp.asarray([p + [0] * (t - len(p)) for p in prompts], jnp.int32)
    lens = jnp.asarray([len(p) for p in prompts], jnp.int32)
    batch = np.asarray(gen_lib.generate_tokens(
        params, cfg, padded, lens, jax.random.key(1), max_new_tokens=12,
    ))
    for i, p in enumerate(prompts):
        solo = np.asarray(gen_lib.generate_tokens(
            params, cfg, jnp.asarray([p], jnp.int32),
            jnp.asarray([len(p)], jnp.int32), jax.random.key(1),
            max_new_tokens=12,
        ))
        np.testing.assert_array_equal(batch[i], solo[0])


@pytest.mark.fragile_xla_cpu
def test_ragged_windowed_speculative_matches_generate():
    """Same regression through the speculative loop (shares the layout)."""
    from distributed_llms_tpu.runtime import generate as gen_lib
    from distributed_llms_tpu.runtime.speculative import (
        speculative_generate_tokens,
    )

    cfg = presets.get_preset("llama-tiny", vocab_size=512, sliding_window=3)
    params = model.init_params(jax.random.key(0), cfg)
    dcfg = presets.get_preset("llama-tiny", vocab_size=512, num_layers=2)
    dparams = model.init_params(jax.random.key(5), dcfg)
    prompt = jnp.asarray([[7, 1, 9, 0, 0, 0, 0, 0], [4] * 8], jnp.int32)
    lens = jnp.asarray([3, 8], jnp.int32)
    want = np.asarray(gen_lib.generate_tokens(
        params, cfg, prompt, lens, jax.random.key(1), max_new_tokens=12,
    ))
    got = speculative_generate_tokens(
        params, cfg, dparams, dcfg, prompt, lens, k=3, max_new_tokens=12,
    )
    np.testing.assert_array_equal(np.asarray(got), want)


def test_windowed_ragged_session_matches_solo():
    """Multi-turn sessions use the same padded (gapped) layout as generate —
    the per-turn slot->position map is session STATE (slot_positions).  A
    ragged 2-row session must match per-row solo sessions exactly (solo B=1
    has no pad gap, so it is layout-independent ground truth)."""
    from distributed_llms_tpu.core.config import RuntimeConfig
    from distributed_llms_tpu.runtime.engine import InferenceEngine

    rt = RuntimeConfig(max_decode_steps=6, max_seq_len=128)
    eng = InferenceEngine.from_preset(
        "llama-tiny", rt, vocab_size=512, sliding_window=5
    )
    turn1 = ["hello world", "hi"]
    turn2 = ["more text", "y"]
    sid, r1 = eng.start_session(turn1, max_new_tokens=6)
    r2 = eng.continue_session(sid, turn2, max_new_tokens=6)
    solo = InferenceEngine(eng.cfg, eng.rt, eng.params)
    for i in range(2):
        ssid, s1 = solo.start_session([turn1[i]], max_new_tokens=6)
        s2 = solo.continue_session(ssid, [turn2[i]], max_new_tokens=6)
        np.testing.assert_array_equal(r1.tokens[i], s1.tokens[0])
        np.testing.assert_array_equal(r2.tokens[i], s2.tokens[0])
        solo.end_session(ssid)


# The two mesh-decode tests below compile big pipelined/GSPMD programs —
# fresh-process via tests/runtime/test_isolated.py (shared marker).
@pytest.mark.fragile_xla_cpu
def test_mesh_windowed_decode_matches_single_device():
    """Mesh decode of sliding-window models threads key_positions through
    the adapters (parallel/api.py), so a ragged batch on a dp x tp mesh
    must match single-device tokens exactly — the window must NOT widen by
    each row's pad amount.  Mesh training stays fine too (cache=None
    forward windows in position space)."""
    from distributed_llms_tpu.core.config import MeshConfig
    from distributed_llms_tpu.parallel.api import make_parallel_model
    from distributed_llms_tpu.runtime import generate as gen_lib
    from distributed_llms_tpu.runtime import train

    cfg = presets.get_preset(
        "llama-tiny", vocab_size=512, sliding_window=3, dtype="float32"
    )
    params = model.init_params(jax.random.key(0), cfg)
    # Ragged lengths: row pads differ, so a slot-space window would widen
    # differently per row; 10 new tokens cross the window boundary.
    prompt = jnp.asarray([[7, 1, 9, 0, 0, 0, 0, 0], [4] * 8], jnp.int32)
    lens = jnp.asarray([3, 8], jnp.int32)
    ref = np.asarray(gen_lib.generate_tokens(
        params, cfg, prompt, lens, jax.random.key(1), max_new_tokens=10,
    ))
    pm = make_parallel_model(cfg, MeshConfig(data=2, model=2),
                             devices=jax.devices()[:4])
    out = gen_lib.generate_tokens(
        pm.shard_params(params), cfg, prompt, lens, jax.random.key(1),
        max_new_tokens=10, forward_fn=pm.as_forward_fn(),
        make_cache=pm.as_make_cache(),
    )
    np.testing.assert_array_equal(np.asarray(out), ref)

    trainer = train.Trainer(cfg, train.default_optimizer(1e-3), parallel=pm)
    step = trainer.make_step()
    toks = jax.random.randint(jax.random.key(1), (2, 9), 0, cfg.vocab_size,
                              dtype=jnp.int32)
    _, _, loss = step(pm.shard_params(params), trainer.init(params), toks,
                      None)
    assert jnp.isfinite(loss)


@pytest.mark.fragile_xla_cpu
def test_pipelined_windowed_decode_matches_single_device():
    """The pipelined paths derive the slot->position map too: per-token
    schedule (pipeline_blocks) and the fused wavefront (pipeline_decode)
    both match single-device windowed decode exactly."""
    from distributed_llms_tpu.core.config import MeshConfig
    from distributed_llms_tpu.parallel.api import make_parallel_model
    from distributed_llms_tpu.runtime import generate as gen_lib

    cfg = presets.get_preset(
        "llama-tiny", vocab_size=512, sliding_window=3, num_layers=4,
        dtype="float32",
    )
    params = model.init_params(jax.random.key(0), cfg)
    prompt = jnp.asarray([[7, 1, 9, 0, 0, 0, 0, 0], [4] * 8], jnp.int32)
    lens = jnp.asarray([3, 8], jnp.int32)
    ref = np.asarray(gen_lib.generate_tokens(
        params, cfg, prompt, lens, jax.random.key(1), max_new_tokens=8,
    ))
    pm = make_parallel_model(cfg, MeshConfig(pipe=2), num_microbatches=2,
                             devices=jax.devices()[:2])
    sharded = pm.shard_params(params)
    out = gen_lib.generate_tokens(
        sharded, cfg, prompt, lens, jax.random.key(1), max_new_tokens=8,
        forward_fn=pm.as_forward_fn(), make_cache=pm.as_make_cache(),
    )
    np.testing.assert_array_equal(np.asarray(out), ref)
    fused = gen_lib.generate_tokens(
        sharded, cfg, prompt, lens, jax.random.key(1), max_new_tokens=8,
        forward_fn=pm.as_forward_fn(), make_cache=pm.as_make_cache(),
        decode_fn=pm.as_decode_fn(),
    )
    np.testing.assert_array_equal(np.asarray(fused), ref)


def test_seq_parallel_windowed_decode_refuses():
    """Ring/Ulysses seq-parallel decode is causal-only (no window bound) —
    the adapters must refuse windowed models loudly."""
    from distributed_llms_tpu.core.config import MeshConfig
    from distributed_llms_tpu.parallel.api import make_parallel_model

    cfg = presets.get_preset("llama-tiny", sliding_window=4)
    pm = make_parallel_model(cfg, MeshConfig(seq=2), devices=jax.devices()[:2])
    for entry in (pm.as_forward_fn, pm.as_make_cache, pm.as_decode_fn):
        with pytest.raises(ValueError, match="sequence-parallel"):
            entry()


def test_paged_batcher_refuses_windowed_model():
    from distributed_llms_tpu.runtime.batcher import ContinuousBatcher

    cfg = presets.get_preset("llama-tiny", sliding_window=4)
    params = model.init_params(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="sliding-window"):
        ContinuousBatcher(cfg, params, batch_slots=2, max_len=64,
                          paged_pages=5, page_size=16)
