"""True pipeline parallelism over a `pipe` mesh axis.

The reference's "pipeline" never pipelined: every worker received the same
input and the master collected partials (fan-out/fan-in star,
src/master/node.py:256-269) — activations never flowed worker->worker
(SURVEY §2.3).  Here activations hop stage->stage over ICI via
``lax.ppermute`` inside ``shard_map``:

- stacked block params [L, ...] are reshaped to [P, L/P, ...] and sharded
  over 'pipe' — each device owns a contiguous layer block (stage);
- a GPipe microbatch schedule runs as a ``lax.scan`` over
  ``num_microbatches + P - 1`` ticks; at each tick every stage processes one
  microbatch and the results rotate one stage forward;
- the schedule is a pure scan over ppermute/dynamic-slice ops, so
  ``jax.grad`` differentiates straight through it — the backward pipeline
  schedule falls out of autodiff, no hand-written 1F1B needed;
- the 'model' (tensor-parallel) and 'data' axes stay GSPMD-auto inside the
  body (``axis_names={'pipe'}``), so TP composes with PP without manual
  collectives.

KV-cache decoding: each stage owns the cache slice for its layers
([P, L/P, B, S, KVH, HD] sharded over 'pipe'); at tick t stage s updates the
batch rows of microbatch (t - s), predicated so bubble ticks write no-ops.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.config import ModelConfig
from ..models import model as model_lib
from ..models.kv_cache import KVCache
from ..runtime import sampling

Params = Any


def split_stages(blocks: Params, num_stages: int) -> Params:
    """[L, ...] stacked block params -> [P, L/P, ...]."""
    def r(a):
        l = a.shape[0]
        if l % num_stages:
            raise ValueError(f"layers {l} not divisible by stages {num_stages}")
        return a.reshape(num_stages, l // num_stages, *a.shape[1:])

    return jax.tree.map(r, blocks)


def merge_stages(blocks: Params) -> Params:
    """[P, L/P, ...] -> [L, ...]."""
    return jax.tree.map(lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), blocks)


def _split_mb(x: jax.Array, m: int) -> jax.Array:
    b = x.shape[0]
    if b % m:
        raise ValueError(f"batch {b} not divisible by microbatches {m}")
    return x.reshape(m, b // m, *x.shape[1:])


def pipeline_blocks(
    mesh: Mesh,
    cfg: ModelConfig,
    staged_blocks: Params,  # [P, L/P, ...] sharded over 'pipe'
    x: jax.Array,  # [B, T, D] activations after embed
    positions: jax.Array,  # [B, T]
    num_microbatches: int,
    cache_k: jax.Array | None = None,  # [P, L/P, B, S, KVH, HD]
    cache_v: jax.Array | None = None,
    cache_index: jax.Array | None = None,  # scalar int32
    attn_mask: jax.Array | None = None,  # [B, 1, Tq, S]
    remat: bool = False,
    key_positions: jax.Array | None = None,  # [B, S] slot->position map for
    #   sliding-window models under the right-padded decode layout
) -> tuple[jax.Array, tuple[jax.Array, jax.Array] | None]:
    """Run the decoder blocks through the pipeline.  Returns ([B, T, D],
    updated staged caches or None)."""
    num_stages = mesh.shape["pipe"]
    m = num_microbatches
    use_cache = cache_k is not None

    x_mb = _split_mb(x, m)  # [M, mb, T, D]
    pos_mb = _split_mb(positions, m)
    use_mask = attn_mask is not None
    # shard_map wants arrays, not None: dummy when unused (never read).
    mask_mb = (
        _split_mb(attn_mask, m) if use_mask else jnp.zeros((m, 1, 1, 1, 1), dtype=bool)
    )
    use_kpos = key_positions is not None
    kpos_mb = (
        _split_mb(key_positions, m) if use_kpos
        else jnp.zeros((m, 1, 1), dtype=jnp.int32)
    )
    mb_size = x_mb.shape[1]

    def body(staged_blocks, x_mb, pos_mb, cache_k, cache_v, mask_mb, kpos_mb):
        # Per-device views: leading 'pipe' axis has local size 1 -> squeeze.
        blocks = jax.tree.map(lambda a: a[0], staged_blocks)
        stage = jax.lax.axis_index("pipe")
        ck = cache_k[0] if use_cache else None  # [L/P, B, S, KVH, HD]
        cv = cache_v[0] if use_cache else None

        # Mark per-stage buffers as varying over 'pipe' for vma tracking.
        out_mb = jax.lax.pcast(jnp.zeros_like(x_mb), ("pipe",), to="varying")

        def tick(carry, t):
            state, out_mb, ck, cv = carry
            mb_idx = jnp.clip(t - stage, 0, m - 1)
            valid = jnp.logical_and(t - stage >= 0, t - stage < m)

            x_in = jnp.where(
                stage == 0,
                jax.lax.dynamic_index_in_dim(x_mb, mb_idx, keepdims=False),
                state,
            )
            pos = jax.lax.dynamic_index_in_dim(pos_mb, mb_idx, keepdims=False)
            amask = (
                jax.lax.dynamic_index_in_dim(mask_mb, mb_idx, keepdims=False)
                if use_mask
                else None
            )
            kpos = (
                jax.lax.dynamic_index_in_dim(kpos_mb, mb_idx, keepdims=False)
                if use_kpos
                else None
            )

            if use_cache:
                row0 = mb_idx * mb_size
                ck_mb = jax.lax.dynamic_slice_in_dim(ck, row0, mb_size, axis=1)
                cv_mb = jax.lax.dynamic_slice_in_dim(cv, row0, mb_size, axis=1)
                y, new, _ = model_lib.run_blocks(
                    x_in, blocks, cfg, model_lib.call_of(
                        x_in.shape[:2], pos, cache_index, amask, kpos,
                        cached=True),
                    KVCache(k=ck_mb, v=cv_mb), remat,
                )
                nk = jnp.where(valid, new.k, ck_mb)
                nv = jnp.where(valid, new.v, cv_mb)
                ck = jax.lax.dynamic_update_slice_in_dim(ck, nk, row0, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(cv, nv, row0, axis=1)
            else:
                # MoE aux loss is not threaded through the pipeline schedule
                # (train MoE with data/tensor/expert axes, not 'pipe').
                y, _, _ = model_lib.run_blocks(
                    x_in, blocks, cfg,
                    model_lib.call_of(x_in.shape[:2], pos, attn_mask=amask),
                    None, remat,
                )

            # Last stage banks its finished microbatch.
            out_idx = jnp.clip(t - (num_stages - 1), 0, m - 1)
            bank = jnp.logical_and(stage == num_stages - 1, t >= num_stages - 1)
            cur = jax.lax.dynamic_index_in_dim(out_mb, out_idx, keepdims=False)
            out_mb = jax.lax.dynamic_update_index_in_dim(
                out_mb, jnp.where(bank, y, cur), out_idx, axis=0
            )

            # Rotate activations one stage forward (circular; stage 0 ignores
            # what it receives and reads the next fresh microbatch instead).
            state = jax.lax.ppermute(
                y, "pipe", [(i, (i + 1) % num_stages) for i in range(num_stages)]
            )
            return (state, out_mb, ck, cv), None

        state0 = jax.lax.pcast(jnp.zeros_like(x_mb[0]), ("pipe",), to="varying")
        carry = (state0, out_mb, ck, cv)
        (state, out_mb, ck, cv), _ = jax.lax.scan(
            tick, carry, jnp.arange(m + num_stages - 1)
        )
        if use_cache:
            return out_mb[None], ck[None], cv[None]
        return (out_mb[None],)

    in_specs = (
        P("pipe"),  # staged blocks
        P(),        # x_mb (replicated over pipe; data/model axes stay auto)
        P(),        # pos_mb
        P("pipe") if use_cache else P(),
        P("pipe") if use_cache else P(),
        P(),        # mask_mb
        P(),        # kpos_mb
    )
    out_specs = (P("pipe"), P("pipe"), P("pipe")) if use_cache else (P("pipe"),)

    result = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names={"pipe"},
        check_vma=True,
    )(
        staged_blocks, x_mb, pos_mb,
        cache_k if use_cache else jnp.zeros((num_stages, 1)),
        cache_v if use_cache else jnp.zeros((num_stages, 1)),
        mask_mb, kpos_mb,
    )

    if use_cache:
        out_all, new_ck, new_cv = result
    else:
        (out_all,) = result
        new_ck = new_cv = None

    # out_all: [P, M, mb, T, D]; only the last stage's bank is meaningful.
    y = out_all[-1].reshape(x.shape)
    return y, ((new_ck, new_cv) if use_cache else None)


def pipeline_decode(
    mesh: Mesh,
    cfg: ModelConfig,
    params: Params,  # staged tree: params["blocks"] is [P, L/P, ...] over 'pipe'
    tok0: jax.Array,  # [B] int32: first token, sampled from the prefill logits
    prompt_lens: jax.Array,  # [B] int32 true prompt lengths
    prompt_pad_len: int,  # T: padded prompt length = cache write base
    cache_k: jax.Array,  # [P, L/P, B, S, KVH, HD] (prefilled)
    cache_v: jax.Array,
    num_new_tokens: int,
    num_microbatches: int,
    rng: jax.Array,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    eos_id: int = -1,
    pad_id: int = 0,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused wavefront decode: the whole autoregressive loop as ONE scan, the
    pipeline never drains between tokens (SURVEY §7 hard part 1).

    Running the per-token GPipe schedule once per decode step costs
    ``M + P - 1`` ticks per token and drains the pipeline every step.  Here
    stage 0 starts microbatch ``m``'s token ``j`` at tick ``j*Q + m`` with
    ``Q = max(M, P)``; the last stage's block output rotates (the existing
    circular ppermute) back to stage 0, which applies the final norm +
    unembed, samples token ``j+1``, embeds it, and parks it in a per-
    microbatch buffer until its start tick.  Steady-state cost: ``Q`` ticks
    per token round — with M >= P microbatches in flight every stage is busy
    every tick (zero steady-state bubbles); the per-token schedule can never
    do better than ``M + P - 1``.

    Exactness: identical math to the per-token path under greedy decoding
    (same masks, cache slots, and per-stage block partitioning); under
    sampling the RNG stream differs (keys are ``fold_in(fold_in(rng, j), m)``
    rather than a pre-split array), which is a draw from the same
    distribution.

    Cost note: every stage traces the stage-0 duties (unembed + sample +
    embed) and discards them via ``where`` — SPMD branchless gating.  The
    wasted unembed read per tick is the price of keeping the scan free of
    cross-stage control flow.

    Returns (tokens [B, N] int32 — EOS-frozen rows pad-filled, matching
    runtime.generate semantics — plus the updated staged KV cache halves).
    """
    num_stages = mesh.shape["pipe"]
    p_, m_, n_ = num_stages, num_microbatches, num_new_tokens
    q = max(m_, p_)
    b = tok0.shape[0]
    if b % m_:
        raise ValueError(f"batch {b} not divisible by microbatches {m_}")
    mb = b // m_
    t_base = prompt_pad_len
    s_len = cache_k.shape[3]
    ticks = (n_ - 1) * q + m_ + p_ - 1
    head = {k: v for k, v in params.items() if k != "blocks"}
    head_specs = jax.tree.map(lambda _: P(), head)
    key_data = jax.random.key_data(rng)

    def body(staged_blocks, head, tok0_mb, plens_mb, key_data, cache_k, cache_v):
        blocks = jax.tree.map(lambda a: a[0], staged_blocks)
        ck, cv = cache_k[0], cache_v[0]  # [L/P, B, S, KVH, HD]
        stage = jax.lax.axis_index("pipe")
        base_key = jax.random.wrap_key_data(key_data)
        slots = jnp.arange(s_len, dtype=jnp.int32)
        dtype = jnp.dtype(cfg.dtype)

        def emb(tok, pos):  # [mb] int32, [mb] int32 -> [mb, 1, D]
            return model_lib.embed(head, cfg, tok[:, None], pos[:, None])

        # Stage-0 state (vma-varying; other stages carry discarded copies).
        var = lambda a: jax.lax.pcast(a, ("pipe",), to="varying")
        buf0 = jnp.stack([emb(tok0_mb[m], plens_mb[m]) for m in range(m_)])
        buf = var(buf0.astype(dtype))  # [M, mb, 1, D] next-token embeds
        done0 = (tok0_mb == eos_id) if eos_id >= 0 else jnp.zeros((m_, mb), bool)
        done = var(done0)
        out = var(jnp.zeros((n_, m_, mb), jnp.int32).at[0].set(tok0_mb))
        state = var(jnp.zeros((mb, 1, buf0.shape[-1]), dtype))

        def tick(carry, t):
            state, buf, done, out, ck, cv = carry

            # -- stage-0 arrival: `state` is what stage P-1 rotated out at
            # the end of tick t-1, i.e. the block output for (m', j') with
            # u' = t - P.  Turn it into token j'+1.
            up = t - p_
            mp = jnp.clip(up % q, 0, m_ - 1)
            jp = up // q
            arr_valid = jnp.logical_and(
                jnp.logical_and(up >= 0, (up % q) < m_), jp + 1 < n_
            )
            logits = model_lib.unembed(head, cfg, state)[:, 0]  # [mb, V] f32
            key = jax.random.fold_in(jax.random.fold_in(base_key, jp + 1), mp)
            tok = sampling.sample(key, logits, temperature, top_k, top_p)
            dmb = jax.lax.dynamic_index_in_dim(done, mp, keepdims=False)
            tok = jnp.where(dmb, jnp.int32(pad_id), tok)
            dnew = jnp.logical_or(dmb, tok == eos_id) if eos_id >= 0 else dmb
            apply = jnp.logical_and(arr_valid, stage == 0)
            done = jax.lax.dynamic_update_index_in_dim(
                done, jnp.where(apply, dnew, dmb), mp, axis=0
            )
            jpc = jnp.clip(jp + 1, 0, n_ - 1)
            cur_out = jax.lax.dynamic_index_in_dim(out, jpc, keepdims=False)
            cur_row = jax.lax.dynamic_index_in_dim(cur_out, mp, keepdims=False)
            new_row = jnp.where(apply, tok, cur_row)
            out = jax.lax.dynamic_update_index_in_dim(
                out,
                jax.lax.dynamic_update_index_in_dim(cur_out, new_row, mp, axis=0),
                jpc, axis=0,
            )
            plens_arr = jax.lax.dynamic_index_in_dim(plens_mb, mp, keepdims=False)
            x_next = emb(tok, plens_arr + jp + 1).astype(dtype)
            cur_buf = jax.lax.dynamic_index_in_dim(buf, mp, keepdims=False)
            buf = jax.lax.dynamic_update_index_in_dim(
                buf, jnp.where(apply, x_next, cur_buf), mp, axis=0
            )

            # -- this tick's stage compute: (m, j) with u = t - stage.
            u = t - stage
            m_idx = jnp.clip(u % q, 0, m_ - 1)
            j = jnp.clip(u // q, 0, n_ - 1)
            valid = jnp.logical_and(
                jnp.logical_and(u >= 0, (u % q) < m_), u // q < n_
            )
            x_in = jnp.where(
                stage == 0,
                jax.lax.dynamic_index_in_dim(buf, m_idx, keepdims=False),
                state,
            )
            plens_m = jax.lax.dynamic_index_in_dim(plens_mb, m_idx, keepdims=False)
            pos = (plens_m + j)[:, None]  # [mb, 1]
            prompt_valid = slots[None, :] < plens_m[:, None]
            gen_valid = jnp.logical_and(
                slots[None, :] >= t_base, slots[None, :] <= t_base + j
            )
            mask = jnp.logical_or(prompt_valid, gen_valid)[:, None, None, :]
            row0 = m_idx * mb
            ck_mb = jax.lax.dynamic_slice_in_dim(ck, row0, mb, axis=1)
            cv_mb = jax.lax.dynamic_slice_in_dim(cv, row0, mb, axis=1)
            # Sliding-window models: slot->position map under this layout
            # (prompt slot s holds position s; generated slot t_base + i
            # holds position len + i) — same formula as
            # runtime.generate.window_key_positions, per microbatch.
            kpos = None
            if cfg.model_window is not None:
                kpos = jnp.where(
                    slots[None, :] < t_base, slots[None, :],
                    plens_m[:, None] + (slots[None, :] - t_base),
                )
            y, new, _ = model_lib.run_blocks(
                x_in, blocks, cfg, model_lib.call_of(
                    x_in.shape[:2], pos, t_base + j, mask, kpos, cached=True),
                KVCache(k=ck_mb, v=cv_mb),
            )
            nk = jnp.where(valid, new.k, ck_mb)
            nv = jnp.where(valid, new.v, cv_mb)
            ck = jax.lax.dynamic_update_slice_in_dim(ck, nk, row0, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, nv, row0, axis=1)

            state = jax.lax.ppermute(
                y, "pipe", [(i, (i + 1) % p_) for i in range(p_)]
            )
            return (state, buf, done, out, ck, cv), None

        carry = (state, buf, done, out, ck, cv)
        (state, buf, done, out, ck, cv), _ = jax.lax.scan(
            tick, carry, jnp.arange(ticks)
        )
        return out[None], ck[None], cv[None]

    tok0_mb = tok0.reshape(m_, mb)
    plens_mb = prompt_lens.reshape(m_, mb)
    out_all, new_ck, new_cv = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P("pipe"), head_specs, P(), P(), P(), P("pipe"), P("pipe")),
        out_specs=(P("pipe"), P("pipe"), P("pipe")),
        axis_names={"pipe"},
        check_vma=True,
    )(params["blocks"], head, tok0_mb, plens_mb, key_data, cache_k, cache_v)

    # out_all: [P, N, M, mb]; stage 0 holds the real bank.
    toks = out_all[0].reshape(num_new_tokens, b).T  # [B, N]
    return toks, new_ck, new_cv
