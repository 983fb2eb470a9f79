"""Autoregressive generation: jitted prefill + lax.scan decode loop.

This is the real replacement for the reference's RUN_INFERENCE path
(src/master/node.py:227-277 -> src/worker/node.py:218-238), which did one
placeholder matmul per worker and returned the first worker's raw partial
(defect D9).  Here: prefill fills the KV cache for the whole (right-padded)
prompt in one pass, then a ``lax.scan`` emits one token per step with
EOS-aware freezing — all inside a single jit, static shapes throughout.

Ragged batches: prompts are right-padded to T.  Every decode step writes all
rows' K/V at the *same* cache slot (T + step) so the update is a single
``dynamic_update_slice``; per-row token positions (``prompt_lens + step``)
feed RoPE / learned position embeddings, and an explicit attention mask keeps
each row attending only its own real prompt slots plus generated slots.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ..core.config import ModelConfig, RuntimeConfig
from ..models import kv_cache, model as model_lib
from . import sampling


def _default_forward(params, cfg, tokens, positions=None, cache=None, cache_index=None, attn_mask=None, key_positions=None):
    return model_lib.forward(
        params, cfg, tokens, positions=positions, cache=cache,
        cache_index=cache_index, attn_mask=attn_mask,
        key_positions=key_positions,
    )


def window_key_positions(t: int, prompt_lens: jax.Array, max_len: int) -> jax.Array:
    """[B, S] true RoPE position of every cache slot under THE right-padded
    generate layout (prompt slots 0..t-1, generated token j at slot t+j,
    position len+j) — the single definition of the slot->position map the
    sliding-window mask needs (models.model._attention key_positions).
    Shared by generate_tokens and runtime.speculative."""
    slots = jnp.arange(max_len, dtype=jnp.int32)
    return jnp.where(
        slots[None, :] < t, slots[None, :],
        prompt_lens[:, None] + (slots[None, :] - t),
    )


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "max_new_tokens", "temperature", "top_k", "top_p", "eos_id",
        "pad_id", "forward_fn", "make_cache", "decode_fn",
    ),
)
def generate_tokens(
    params: Any,
    cfg: ModelConfig,
    prompt: jax.Array,  # [B, T] int32, right-padded with pad_id
    prompt_lens: jax.Array,  # [B] int32 true lengths
    rng: jax.Array,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    eos_id: int = -1,  # -1 => never stops early
    pad_id: int = 0,
    forward_fn: Any = None,  # (params, cfg, tokens, positions=, cache=, cache_index=, attn_mask=) -> (logits, cache)
    make_cache: Any = None,  # (cfg, batch, max_len) -> KVCache
    decode_fn: Any = None,  # fused decode loop (ParallelModel.as_decode_fn())
) -> jax.Array:
    """Generate.  Returns new tokens [B, max_new_tokens] int32; positions
    after a sequence's EOS are filled with pad_id.

    ``forward_fn``/``make_cache`` default to the single-device model; a
    mesh-parallel model (parallel.api.ParallelModel) plugs in its own.

    runtime.session.session_step is the multi-turn generalization of this
    loop; the pair is deliberately unmerged (this prefill's attn_mask=None
    unlocks the flash kernel) and pinned equivalent by
    tests/runtime/test_session.py — decode-loop changes must land in both.

    The KV cache is sized T + max_new_tokens exactly, so the
    ``cache_index + T <= max_len`` contract of models.model.forward holds by
    construction.
    """
    if forward_fn is None:
        forward_fn = _default_forward
    if make_cache is None:
        make_cache = kv_cache.init_cache
    b, t = prompt.shape
    max_len = t + max_new_tokens
    cache = make_cache(cfg, b, max_len, prompt_len=t)

    # --- prefill: causal attention over prompt slots (pad queries produce
    # garbage but nothing reads their logits; pad K/V slots are masked during
    # decode via the explicit mask below).
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    logits, cache = forward_fn(
        params, cfg, prompt, positions=positions, cache=cache, cache_index=jnp.int32(0)
    )
    last_idx = jnp.maximum(prompt_lens - 1, 0)
    next_logits = jnp.take_along_axis(logits, last_idx[:, None, None], axis=1)[:, 0]

    if decode_fn is not None:
        # Fused wavefront decode (pipelined models): the whole loop runs as
        # one schedule that never drains the pipeline between tokens —
        # max(M, P) ticks per token round instead of M + P - 1.
        rng0, rng_loop = jax.random.split(rng)
        tok0 = sampling.sample(rng0, next_logits, temperature, top_k, top_p)
        return decode_fn(
            params, tok0, prompt_lens, t, cache, rng_loop, max_new_tokens,
            temperature, top_k, top_p, eos_id, pad_id,
        )

    slots = jnp.arange(max_len, dtype=jnp.int32)  # [S]
    prompt_valid = slots[None, :] < prompt_lens[:, None]  # [B, S]
    # Sliding-window models: the decode mask below carries causality and
    # validity in SLOT space, but the window bound compares RoPE POSITIONS —
    # and in this right-padded layout generated slot T+j sits at position
    # len+j.  Hand the true slot->position map to the forward or the window
    # silently widens by the pad amount (models.model._attention).
    win_kwargs = {}
    if cfg.model_window is not None:
        win_kwargs["key_positions"] = window_key_positions(t, prompt_lens, max_len)

    def step(carry, inputs):
        cache, cur_logits, done = carry
        j, rng_step = inputs
        tok = sampling.sample(rng_step, cur_logits, temperature, top_k, top_p)
        tok = jnp.where(done, jnp.int32(pad_id), tok)
        if eos_id >= 0:
            done = jnp.logical_or(done, tok == eos_id)
        # Valid keys: real prompt slots + generated slots up to and including
        # this step's write slot (t + j).
        gen_valid = jnp.logical_and(slots[None, :] >= t, slots[None, :] <= t + j)
        mask = jnp.logical_or(prompt_valid, gen_valid)[:, None, None, :]  # [B,1,1,S]
        positions = (prompt_lens + j)[:, None]  # [B, 1]
        logits, new_cache = forward_fn(
            params, cfg, tok[:, None],
            positions=positions, cache=cache, cache_index=t + j, attn_mask=mask,
            **win_kwargs,
        )
        return (new_cache, logits[:, 0], done), tok

    rngs = jax.random.split(rng, max_new_tokens)
    steps = jnp.arange(max_new_tokens, dtype=jnp.int32)
    done0 = jnp.zeros((b,), dtype=bool)
    _, toks = jax.lax.scan(step, (cache, next_logits, done0), (steps, rngs))
    return toks.T  # [B, N]


def check_sequence_budget(
    prompt_len: int, max_new_tokens: int, rt: RuntimeConfig, cfg: ModelConfig
) -> None:
    """Shared guard: prompt + decode budget must fit both the runtime limit
    and the model's position table (GPT-2 wpe indexes OOB -> NaN fill)."""
    limit = min(rt.max_seq_len, cfg.max_seq_len)
    if prompt_len + max_new_tokens > limit:
        raise ValueError(
            f"prompt len {prompt_len} + {max_new_tokens} new tokens exceeds "
            f"sequence limit {limit} (min of runtime {rt.max_seq_len} and "
            f"model {cfg.max_seq_len})"
        )


def generate(
    params: Any,
    cfg: ModelConfig,
    rt: RuntimeConfig,
    prompt: jax.Array,
    prompt_lens: jax.Array | None = None,
    rng: jax.Array | None = None,
    eos_id: int = -1,
    pad_id: int = 0,
) -> jax.Array:
    """Convenience wrapper binding knobs from a RuntimeConfig."""
    b, t = prompt.shape
    if prompt_lens is None:
        prompt_lens = jnp.full((b,), t, dtype=jnp.int32)
    if rng is None:
        rng = jax.random.key(rt.seed)
    check_sequence_budget(t, rt.max_decode_steps, rt, cfg)
    return generate_tokens(
        params, cfg, prompt, prompt_lens, rng,
        max_new_tokens=rt.max_decode_steps,
        temperature=rt.temperature, top_k=rt.top_k, top_p=rt.top_p,
        eos_id=eos_id, pad_id=pad_id,
    )
