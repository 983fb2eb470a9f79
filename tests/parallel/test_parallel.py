"""Fake-mesh (8 CPU devices) integration tests: TP/DP GSPMD forward,
pipeline equivalence + gradients, combined dp*pp*tp generation, train step.
This is the multi-device test strategy the reference lacked entirely
(SURVEY §4: "How multi-node is tested without a cluster: it isn't")."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.core.config import MeshConfig
from distributed_llms_tpu.core.mesh import build_mesh
from distributed_llms_tpu.models import model, presets
from distributed_llms_tpu.parallel import pipeline as pl
from distributed_llms_tpu.parallel import specs as specs_lib
from distributed_llms_tpu.parallel import stages
from distributed_llms_tpu.parallel.api import make_parallel_model
from distributed_llms_tpu.runtime import generate as gen_lib
from distributed_llms_tpu.runtime.tokenizer import pad_batch


@pytest.fixture(scope="module")
def gpt2():
    cfg = presets.get_preset("gpt2-tiny")
    params = model.init_params(jax.random.key(0), cfg)
    return cfg, params


def test_stage_partition_contiguous():
    sizes = [10, 1, 1, 10, 1, 1, 10, 2]
    a = stages.partition_contiguous(sizes, 3)
    assert a.num_stages == 3
    assert a.boundaries[0] == 0 and a.boundaries[-1] == len(sizes)
    costs = [sum(sizes[a.boundaries[i]:a.boundaries[i + 1]]) for i in range(3)]
    assert max(costs) == 12  # optimal: [10,1,1] [10,1,1] [10,2]
    assert a.stage_of(0) == 0 and a.stage_of(7) == 2


def test_pack_greedy_balances():
    packing = stages.pack_greedy({"a": 8, "b": 7, "c": 4, "d": 3}, 2)
    bins = {}
    for k, b in packing.items():
        bins.setdefault(b, 0)
        bins[b] += {"a": 8, "b": 7, "c": 4, "d": 3}[k]
    assert sorted(bins.values()) == [11, 11]


def test_tp_dp_forward_matches_single_device(gpt2, devices8):
    cfg, params = gpt2
    toks = jax.random.randint(jax.random.key(1), (4, 6), 0, cfg.vocab_size, dtype=jnp.int32)
    ref, _ = model.forward(params, cfg, toks)

    mesh = build_mesh(MeshConfig(data=2, model=4))
    sharded = specs_lib.shard_params(params, cfg, mesh)
    out, _ = jax.jit(lambda p, t: model.forward(p, cfg, t))(sharded, toks)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=1e-4, atol=1e-4)


def test_pipeline_matches_plain_blocks(gpt2, devices8):
    cfg, params = gpt2
    mesh = build_mesh(MeshConfig(data=1, pipe=4, model=2))
    B, T = 4, 6
    toks = jax.random.randint(jax.random.key(1), (B, T), 0, cfg.vocab_size, dtype=jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x = model.embed(params, cfg, toks, positions)
    y_ref, _, _ = model.run_blocks(
        x, params["blocks"], cfg, model.call_of((B, T), positions), None)
    staged = pl.split_stages(params["blocks"], 4)
    y_pipe, _ = pl.pipeline_blocks(mesh, cfg, staged, x, positions, num_microbatches=2)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pipe), rtol=1e-5, atol=1e-5)


def test_pipeline_gradients_match(gpt2, devices8):
    cfg, params = gpt2
    mesh = build_mesh(MeshConfig(data=1, pipe=2, model=1, seq=4))
    B, T = 4, 6
    toks = jax.random.randint(jax.random.key(1), (B, T), 0, cfg.vocab_size, dtype=jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x = model.embed(params, cfg, toks, positions)

    def loss_plain(blocks):
        y, _, _ = model.run_blocks(
            x, blocks, cfg, model.call_of((B, T), positions), None)
        return jnp.mean(y.astype(jnp.float32) ** 2)

    def loss_pipe(staged):
        y, _ = pl.pipeline_blocks(mesh, cfg, staged, x, positions, num_microbatches=2)
        return jnp.mean(y.astype(jnp.float32) ** 2)

    g_plain = jax.grad(loss_plain)(params["blocks"])
    g_pipe = pl.merge_stages(jax.grad(loss_pipe)(pl.split_stages(params["blocks"], 2)))
    for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_pipe)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=1e-5)


def test_dp_pp_tp_generation_matches_single_device(gpt2, devices8):
    cfg, params = gpt2
    rows = [[7, 1, 9], [4, 4, 4, 4, 4, 4], [100, 3, 5, 2], [9, 8, 7, 6, 5]]
    arr, lens = pad_batch(rows, pad_id=0)
    ref = gen_lib.generate_tokens(
        params, cfg, jnp.asarray(arr), jnp.asarray(lens), jax.random.key(0),
        max_new_tokens=4,
    )
    pm = make_parallel_model(cfg, MeshConfig(data=2, pipe=2, model=2), num_microbatches=2)
    sharded = pm.shard_params(params)
    out = gen_lib.generate_tokens(
        sharded, cfg, jnp.asarray(arr), jnp.asarray(lens), jax.random.key(0),
        max_new_tokens=4, forward_fn=pm.as_forward_fn(), make_cache=pm.as_make_cache(),
    )
    assert np.asarray(ref).tolist() == np.asarray(out).tolist()


def test_neox_blocks_shard_and_generate(devices8):
    """GPT-NeoX layout (parallel residual + partial rotary + biasful
    LayerNorm blocks, no position table): dp x pp x tp generation matches
    the single device exactly."""
    from distributed_llms_tpu.models import presets

    cfg = presets.get_preset("neox-tiny", vocab_size=512, num_layers=4)
    params = model.init_params(jax.random.key(5), cfg)
    assert "wpe" not in params["embed"]
    rows = [[7, 1, 9], [4, 4, 4, 4], [100, 3, 5, 2], [9, 8]]
    arr, lens = pad_batch(rows, pad_id=0)
    ref = gen_lib.generate_tokens(
        params, cfg, jnp.asarray(arr), jnp.asarray(lens), jax.random.key(0),
        max_new_tokens=4,
    )
    pm = make_parallel_model(cfg, MeshConfig(data=2, pipe=2, model=2), num_microbatches=2)
    sharded = pm.shard_params(params)
    out = gen_lib.generate_tokens(
        sharded, cfg, jnp.asarray(arr), jnp.asarray(lens), jax.random.key(0),
        max_new_tokens=4, forward_fn=pm.as_forward_fn(), make_cache=pm.as_make_cache(),
    )
    assert np.asarray(ref).tolist() == np.asarray(out).tolist()


def test_qkv_bias_blocks_shard_and_generate(devices8):
    """Qwen2-style llama blocks (cfg.qkv_bias): the bias leaves shard with
    their head axes over 'model' and dp x pp x tp generation matches the
    single device exactly."""
    import dataclasses

    from distributed_llms_tpu.models import presets

    cfg = dataclasses.replace(
        presets.get_preset("llama-tiny", vocab_size=512), qkv_bias=True
    )
    params = model.init_params(jax.random.key(3), cfg)
    assert "bq" in params["blocks"]["attn"]
    rows = [[7, 1, 9], [4, 4, 4, 4], [100, 3, 5, 2], [9, 8]]
    arr, lens = pad_batch(rows, pad_id=0)
    ref = gen_lib.generate_tokens(
        params, cfg, jnp.asarray(arr), jnp.asarray(lens), jax.random.key(0),
        max_new_tokens=4,
    )
    pm = make_parallel_model(cfg, MeshConfig(data=2, pipe=2, model=2), num_microbatches=2)
    sharded = pm.shard_params(params)
    out = gen_lib.generate_tokens(
        sharded, cfg, jnp.asarray(arr), jnp.asarray(lens), jax.random.key(0),
        max_new_tokens=4, forward_fn=pm.as_forward_fn(), make_cache=pm.as_make_cache(),
    )
    assert np.asarray(ref).tolist() == np.asarray(out).tolist()


@pytest.mark.parametrize("microbatches", [1, 2, 4])
def test_fused_wavefront_decode_matches_single_device(gpt2, devices8, microbatches):
    """The fused decode schedule (pipeline never drains between tokens,
    max(M,P) ticks per token round vs M+P-1) is numerically identical to the
    single-device loop, for M below/at/above P."""
    cfg, params = gpt2
    rows = [[7, 1, 9], [4, 4, 4, 4, 4, 4], [100, 3, 5, 2], [9, 8, 7, 6, 5]]
    arr, lens = pad_batch(rows, pad_id=0)
    ref = gen_lib.generate_tokens(
        params, cfg, jnp.asarray(arr), jnp.asarray(lens), jax.random.key(0),
        max_new_tokens=5,
    )
    pm = make_parallel_model(
        cfg, MeshConfig(data=2, pipe=2, model=2), num_microbatches=microbatches
    )
    sharded = pm.shard_params(params)
    out = gen_lib.generate_tokens(
        sharded, cfg, jnp.asarray(arr), jnp.asarray(lens), jax.random.key(0),
        max_new_tokens=5, forward_fn=pm.as_forward_fn(),
        make_cache=pm.as_make_cache(), decode_fn=pm.as_decode_fn(),
    )
    assert np.asarray(ref).tolist() == np.asarray(out).tolist()


def test_fused_decode_eos_freezing_matches(gpt2, devices8):
    """EOS-aware freezing (rows stop and pad-fill) through the wavefront."""
    cfg, params = gpt2
    rows = [[7, 1, 9], [4, 4, 4, 4], [100, 3, 5, 2], [9, 8, 7, 6, 5]]
    arr, lens = pad_batch(rows, pad_id=0)
    ref = gen_lib.generate_tokens(
        params, cfg, jnp.asarray(arr), jnp.asarray(lens), jax.random.key(0),
        max_new_tokens=6,
    )
    eos = int(np.asarray(ref)[0, 1])  # a token greedy decoding actually emits
    kw = dict(max_new_tokens=6, eos_id=eos, pad_id=0)
    ref_e = gen_lib.generate_tokens(
        params, cfg, jnp.asarray(arr), jnp.asarray(lens), jax.random.key(0), **kw
    )
    assert (np.asarray(ref_e) == eos).any()
    pm = make_parallel_model(cfg, MeshConfig(data=2, pipe=2, model=2), num_microbatches=2)
    out_e = gen_lib.generate_tokens(
        pm.shard_params(params), cfg, jnp.asarray(arr), jnp.asarray(lens),
        jax.random.key(0), forward_fn=pm.as_forward_fn(),
        make_cache=pm.as_make_cache(), decode_fn=pm.as_decode_fn(), **kw
    )
    assert np.asarray(ref_e).tolist() == np.asarray(out_e).tolist()


def test_train_step_decreases_loss(devices8):
    from distributed_llms_tpu.runtime import train

    cfg = presets.get_preset("gpt2-tiny", num_layers=2)
    params = model.init_params(jax.random.key(0), cfg)
    pm = make_parallel_model(cfg, MeshConfig(data=2, pipe=2, model=2), num_microbatches=2)
    params = pm.shard_params(params)
    trainer = train.Trainer(cfg, train.default_optimizer(1e-2), parallel=pm)
    opt_state = trainer.init(params)
    step = trainer.make_step()
    tokens = jax.random.randint(jax.random.key(2), (4, 9), 0, cfg.vocab_size, dtype=jnp.int32)
    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, tokens, None)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_seq_parallel_forward_matches_single_device(gpt2, devices8):
    """Sequence parallelism: ring attention over 'seq' == dense attention."""
    cfg, params = gpt2
    B, T = 2, 16
    toks = jax.random.randint(jax.random.key(3), (B, T), 0, cfg.vocab_size, dtype=jnp.int32)
    ref, _ = model.forward(params, cfg, toks)

    pm = make_parallel_model(cfg, MeshConfig(data=2, seq=4))
    sharded = pm.shard_params(params)
    out, cache = pm.forward(sharded, toks)
    assert cache is None
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=1e-4, atol=1e-4)


def test_seq_parallel_train_step(devices8):
    """Training differentiates through the ppermute ring."""
    from distributed_llms_tpu.runtime import train

    cfg = presets.get_preset("gpt2-tiny", num_layers=2)
    params = model.init_params(jax.random.key(0), cfg)
    pm = make_parallel_model(cfg, MeshConfig(data=2, seq=4))
    params = pm.shard_params(params)
    trainer = train.Trainer(cfg, train.default_optimizer(1e-2), parallel=pm)
    opt_state = trainer.init(params)
    step = trainer.make_step()
    tokens = jax.random.randint(jax.random.key(2), (4, 17), 0, cfg.vocab_size, dtype=jnp.int32)
    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, tokens, None)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_seq_parallel_cached_generation_matches(gpt2, devices8):
    """Long-context decode (SURVEY §5.7): prompt KV sharded over 'seq' (two-
    region cache), decode merges partial softmax stats with one psum — tokens
    must match the single-device loop exactly."""
    cfg, params = gpt2
    B, T, N = 2, 16, 6
    prompt = jax.random.randint(jax.random.key(5), (B, T), 0, cfg.vocab_size, dtype=jnp.int32)
    lens = jnp.array([16, 11], jnp.int32)
    ref = gen_lib.generate_tokens(
        params, cfg, prompt, lens, jax.random.key(0), max_new_tokens=N
    )
    pm = make_parallel_model(cfg, MeshConfig(data=2, seq=4))
    out = gen_lib.generate_tokens(
        pm.shard_params(params), cfg, prompt, lens, jax.random.key(0),
        max_new_tokens=N, forward_fn=pm.as_forward_fn(),
        make_cache=pm.as_make_cache(),
    )
    assert np.asarray(ref).tolist() == np.asarray(out).tolist()


def test_seq_parallel_ulysses_cached_generation_matches(gpt2, devices8):
    """Same decode path behind a Ulysses prefill, composed with TP."""
    import dataclasses

    cfg, params = gpt2
    cfg_u = dataclasses.replace(cfg, attn_impl="ulysses")
    B, T, N = 2, 16, 5
    prompt = jax.random.randint(jax.random.key(6), (B, T), 0, cfg.vocab_size, dtype=jnp.int32)
    lens = jnp.array([16, 9], jnp.int32)
    ref = gen_lib.generate_tokens(
        params, cfg, prompt, lens, jax.random.key(0), max_new_tokens=N
    )
    pm = make_parallel_model(cfg_u, MeshConfig(data=2, seq=2, model=2))
    out = gen_lib.generate_tokens(
        pm.shard_params(params), cfg_u, prompt, lens, jax.random.key(0),
        max_new_tokens=N, forward_fn=pm.as_forward_fn(),
        make_cache=pm.as_make_cache(),
    )
    assert np.asarray(ref).tolist() == np.asarray(out).tolist()


def test_seq_parallel_cache_requires_prompt_len(gpt2, devices8):
    """The session path (no prompt_len) fails loudly, not silently densely."""
    cfg, _ = gpt2
    pm = make_parallel_model(cfg, MeshConfig(data=2, seq=4))
    with pytest.raises(ValueError, match="prompt_len"):
        pm.init_cache(batch=2, max_len=32)


def test_seq_plus_pipe_rejected(devices8):
    cfg = presets.get_preset("gpt2-tiny")
    with pytest.raises(ValueError, match="seq"):
        make_parallel_model(cfg, MeshConfig(pipe=2, seq=2, data=2))


def test_seq_parallel_falls_back_on_custom_mask(gpt2, devices8):
    """A caller-supplied attn_mask must not be dropped by the ring path."""
    cfg, params = gpt2
    B, T = 2, 16
    toks = jax.random.randint(jax.random.key(3), (B, T), 0, cfg.vocab_size, dtype=jnp.int32)
    # Mask out the first 4 keys entirely (plus causal).
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    from distributed_llms_tpu.models import layers
    k_valid = jnp.broadcast_to(jnp.arange(T) >= 4, (B, T))
    mask = layers.causal_mask(positions, positions, k_valid)
    ref, _ = model.forward(params, cfg, toks, attn_mask=mask)
    pm = make_parallel_model(cfg, MeshConfig(data=2, seq=4))
    out, _ = pm.forward(pm.shard_params(params), toks, attn_mask=mask)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=1e-4, atol=1e-4)
