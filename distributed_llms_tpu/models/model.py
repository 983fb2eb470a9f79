"""Decoder-only transformer forward pass (GPT-2 and Llama families).

Pure functions over stacked-layer param pytrees; covers the role of the
reference's model layer (src/model/loader.py, src/worker/node.py:13-32) with a
*real* transformer forward — the reference's compute was a placeholder matmul
(src/worker/node.py:24-32) and no decode loop existed anywhere (SURVEY §2.5).

Layout conventions:
- params["blocks"][...] arrays have a leading layer axis L; blocks execute
  under ``lax.scan`` so XLA traces one block and reuses it L times.
- KV cache is a preallocated [L, B, S, KVH, HD] pair living in HBM, updated
  with ``dynamic_update_slice`` at jit-static shapes.
- Paged serving keeps the KV as a page pool instead, one stack
  [L, NB, BLK, KVH, HD] for all layers, which the layer scan CARRIES and
  scatters into where it lies (``run_blocks``, ``_paged_attention``).
"""

from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Any

import jax
import jax.numpy as jnp

from ..core.config import ModelConfig
from . import kv_cache, layers
from .layers import Params


# ---------------------------------------------------------------------------
# The call
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)  # (fields are arrays: identity)
class Call:
    """What one call of :func:`forward` is, the same for every layer of it:
    what the caller handed over and what follows from that, worked out once
    (:func:`call_of`) and nowhere else.  No pytree: the layer scans' bodies
    close over it.  ``kind`` is a Python value while tracing, one of

    - "plain": no cache (training, evaluation, a reference's comparison);
    - "start": a fresh row's cache to fill: no mask and no map of the
      caller's own, and a write offset KNOWN WHILE TRACING to be 0
      (runtime.batcher._prefill_row passes the Python 0; ``jnp.int32(0)``
      under ``jit`` is a tracer and no start).  The T new tokens can see
      nothing but each other, whatever the cache's length;
    - "continuation": a cache, a scalar offset that is no start and no mask
      of the caller's: every row holds its keys in slots
      [0, cache_index + T) (a suffix behind a cached prefix, a chunk of a
      chunked prefill, a one-shot prefill into a longer cache);
    - "masked": a cache, a scalar offset and the caller's ``attn_mask``
      (right-padded generate, sessions);
    - "decode": a cache and one write slot a row (``cache_index`` [B]):
      against the page pool where ``kv_tables`` is given, else a contiguous
      cache under the caller's mask.  The speculative window's T > 1 is
      read off the input."""

    positions: jax.Array  # [B, T] int32
    cache_index: Any  # None, a scalar write offset, or [B] per-row offsets
    attn_mask: Any  # None or broadcastable to [B, H, Tq, S]; True = attend
    key_positions: Any  # None or [B, S]: the true RoPE position of each
    #   cache slot, consulted by the sliding-window mask ALONE.  Contiguous
    #   layouts (slot == position: the continuous batcher) leave it None;
    #   gapped ones MUST give it or the window silently widens by the pad on
    #   generated keys: right-padded generate/speculative (prompt slots
    #   0..T-1, generated token j at slot T+j but position len+j) and
    #   multi-turn sessions (Session.slot_positions)
    kv_tables: Any  # None or [B, P] int32 page table: the cache is the PAGE
    #   POOL, every layer's pages in one stack (:func:`_paged_attention`),
    #   row b's slot s at (layer, tables[b, s // BLK], s % BLK); "decode"
    #   only, the mask implicitly the prefix [0, cache_index[b]]
    seq_lens: Any  # None or [B] int32: the real tokens of each row's T
    cached: bool  # whether a cache was given
    kind: str
    std_layout: bool  # positions are the standard arange forward made
    #   itself: unlocks the flash kernel's static-causal fast path
    rows: Any  # None or [1] int32: how many leading rows of the [B * T, K]
    #   activations are real, where that is known: ONE right-padded
    #   sequence (an admission) whose ``seq_lens`` the caller gave.  A
    #   batch's real rows are no run from the top
    token_mask: Any  # None or [B, T] bool: arange(T) < seq_lens


def call_of(shape: tuple[int, int], positions=None, cache_index=None,
            attn_mask=None, key_positions=None, kv_tables=None,
            seq_lens=None, cached: bool = False) -> Call:
    """The :class:`Call` of a [B, T] = ``shape`` input: the one place the
    kind of a call and what else follows from its facts are decided."""
    b, t = shape
    std_layout = positions is None and (cache_index is None or not cached)
    if positions is None:
        base = cache_index if cache_index is not None else 0
        positions = jnp.broadcast_to(
            jnp.arange(t, dtype=jnp.int32) + base, (b, t))
    if not cached:
        kind = "plain"
    elif getattr(cache_index, "ndim", 0) == 1:
        kind = "decode"
    elif (attn_mask is None and key_positions is None
          and not isinstance(cache_index, jax.core.Tracer)
          and int(cache_index) == 0):
        kind = "start"
    else:
        kind = "continuation" if attn_mask is None else "masked"
    rows = token_mask = None
    if seq_lens is not None:
        token_mask = (jnp.arange(t, dtype=jnp.int32)[None, :]
                      < seq_lens[:, None])
        if b == 1:
            rows = seq_lens.astype(jnp.int32).reshape(1)
    return Call(positions, cache_index, attn_mask, key_positions, kv_tables,
                seq_lens, cached, kind, std_layout, rows, token_mask)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _paged_attention(q, k, v, p, pool, layer, cache_index, kv_tables,
                     gate=None):
    """Attention of T new tokens a row (T static: 1 in a decode step,
    spec_k + 1 in the speculative draft/verify pass) against the page
    pool.  ``pool`` is the WHOLE stack, every layer's pages (a page-pool
    pytree of models/kv_cache.py, whatever its format), and ``layer`` the
    traced index of this layer: the stack is the layer scan's carry,
    written where it lies and read by the kernel through (layer, page), so
    no program ever holds a layer's slice of it as a buffer of its own.

    K/V for all T tokens scatter through the page table first (row b's
    slots cache_index[b]..+T-1 — the caller's growth loop guaranteed pages
    cover them), then query j reads its row's prefix through slot
    cache_index[b]+j: per-offset lengths give exact causality inside the
    window while the paged kernel's prefix contract covers everything
    before it.  The reads unroll into T kernel calls inside ONE compiled
    program.  Rollback is free: slots past the committed frontier hold
    junk no read ever admits (lengths cap every read), awaiting overwrite.
    How a token is stored (an int8 pool quantizes it once, at this write)
    and what the kernel is handed are the format's:
    kv_cache.write_tokens, kv_cache.kernel_operands."""
    from ..ops import decode_attn

    t_w = q.shape[1]
    rows = jnp.arange(q.shape[0], dtype=jnp.int32)
    blk = pool.k.shape[2]
    idx = cache_index[:, None] + jnp.arange(t_w, dtype=jnp.int32)[None, :]
    page = kv_tables[rows[:, None], idx // blk]  # [B, T]
    off = idx % blk
    pool = kv_cache.write_tokens(pool, layer, page, off, k, v)
    k_pages, v_pages, scales = kv_cache.kernel_operands(pool)
    out = jnp.concatenate(
        [
            decode_attn.paged_decode_attention(
                q[:, j: j + 1], k_pages, v_pages, cache_index + 1 + j,
                kv_tables, layer=layer, **scales,
            )
            for j in range(t_w)
        ],
        axis=1,
    )
    return layers.out_project(out, p, gate), pool


@jax.named_scope("attn")  # profiler scope; HLO metadata only
def _attention(
    x: jax.Array,
    p: Params,
    cfg: ModelConfig,
    use_rope: bool,
    call: Call,
    layer_cache: Any,  # None; this layer's (k, v) rows [B, S, KVH, HD]; or
    #   with call.kv_tables the whole page pool
    layer: jax.Array | None = None,  # this layer's index into the pool
    #   stack (with call.kv_tables only)
) -> tuple[jax.Array, tuple[jax.Array, jax.Array] | None]:
    positions, cache_index, kind = call.positions, call.cache_index, call.kind
    attn_mask, key_positions = call.attn_mask, call.key_positions
    q, k, v = layers.qkv_project(x, p, cfg)
    gate = None
    if cfg.attn_out_gate:  # a head's outputs of W_q are [query | gate]; the
        # attention's output times sigmoid(gate), in layers.out_project
        q, gate = q[..., :cfg.head_dim_], q[..., cfg.head_dim_:]
    if cfg.qk_norm:  # per head, over the head dim, before the rotation
        q = layers.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = layers.rms_norm(k, p["k_norm"], cfg.norm_eps)
    if use_rope:
        rope_scale = (
            (cfg.rope_scaling_factor, cfg.rope_low_freq_factor,
             cfg.rope_high_freq_factor, cfg.rope_original_max_len)
            if cfg.rope_scaling_factor != 1.0 else None  # Llama-3.1 rescale
        )
        if cfg.rotary_pct < 1.0:
            # Partial rotary (GPT-NeoX/Pythia): only the first rotary_pct
            # of each head's dims rotate; the rest are position-free.
            rot = int(cfg.head_dim_ * cfg.rotary_pct)

            def _rope(t):
                return jnp.concatenate(
                    [layers.apply_rope(t[..., :rot], positions,
                                       cfg.rope_theta, rope_scale),
                     t[..., rot:]], axis=-1,
                )

            q, k = _rope(q), _rope(k)
        else:
            q = layers.apply_rope(q, positions, cfg.rope_theta, rope_scale)
            k = layers.apply_rope(k, positions, cfg.rope_theta, rope_scale)

    if call.kv_tables is not None:
        if kind != "decode":
            raise ValueError(
                "paged attention is per-row decode (a per-row cache_index "
                "over a page-pool cache)"
            )
        if cfg.model_window is not None:
            raise ValueError(
                "paged decode attends each row's full cache prefix; it "
                "cannot honor sliding_window"
            )
        return _paged_attention(
            q, k, v, p, layer_cache, layer, cache_index, call.kv_tables, gate
        )
    if kind == "plain":
        return _plain_attention(q, k, v, p, cfg, call, gate), None
    if cfg.attn_impl in ("ring", "ulysses"):
        # Sequence-parallel cached generation (SURVEY §5.7): the KV cache is
        # split into a seq-sharded prefill region and a small replicated
        # decode region (parallel.api builds it; see ParallelModel.init_cache).
        return _seq_cached_attention(q, k, v, p, cfg, call, layer_cache)

    ck, cv = layer_cache  # [B, S, KVH, HD]
    if kind == "start":
        # An admission's fresh row: the T tokens attend among themselves
        # and take the row cache's first T slots; no slot past T is read,
        # repeated to the query heads or scored.
        t = x.shape[1]
        out = _self_attention(q, k, v, positions, cfg.model_window)
        return layers.out_project(out, p, gate), (
            ck.at[:, :t].set(k.astype(ck.dtype)),
            cv.at[:, :t].set(v.astype(cv.dtype)))
    if kind == "decode":
        # Per-ROW write slots (continuous batching: rows admitted at
        # different times sit at different depths).  Only the KV write
        # scatters; everything else stays batched.  Callers must supply
        # attn_mask: nothing below derives one from B frontiers.
        if attn_mask is None:
            raise ValueError(
                "per-row cache_index requires an explicit attn_mask"
            )
        row_upd = jax.vmap(
            lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i, 0, 0))
        )
        ck = row_upd(ck, k.astype(ck.dtype), cache_index)
        cv = row_upd(cv, v.astype(cv.dtype), cache_index)
        if cfg.ragged_decode and x.shape[1] == 1:
            # Ragged read: row b touches only [0, cache_index[b]] of the
            # cache (lengths = cache_index + 1 includes the slot just
            # written above).  cfg.ragged_decode is the caller's promise
            # that attn_mask IS that prefix mask (core/config.py).
            # Sliding-window models pass the window through: the kernel
            # reads only [length - window, length) per row — exact
            # because the ragged contract layout is slot == position.
            from ..ops import decode_attn

            # ck/cv go in at the CACHE's dtype — the kernel casts per
            # block in VMEM, so a kv_dtype != compute dtype never costs
            # a full-width HBM copy of the cache.
            out = decode_attn.ragged_decode_attention(
                q, ck, cv, cache_index + 1, window=cfg.model_window,
            )
            return layers.out_project(out, p, gate), (ck, cv)
    else:
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, cache_index, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, cache_index, 0, 0))
    if kind == "continuation":
        out = _continuation_attention(
            q, ck, cv, positions, cache_index, cfg.model_window,
            key_positions)
        return layers.out_project(out, p, gate), (ck, cv)
    # "masked", and a decode step the ragged kernel does not take.
    if cfg.model_window is not None:
        # Caller-supplied masks (continuous batching's per-row prefix
        # masks, padded prefill) carry causality/validity but not the
        # window — AND it in here so no dense cached path can silently
        # attend past the window.
        if key_positions is None:
            s = ck.shape[1]
            key_positions = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32), (x.shape[0], s)
            )
        attn_mask = layers.and_window(
            attn_mask, positions, key_positions, cfg.model_window
        )
    k_full = layers.repeat_kv(ck.astype(q.dtype), cfg.q_per_kv)
    v_full = layers.repeat_kv(cv.astype(q.dtype), cfg.q_per_kv)
    out = layers.dot_product_attention(q, k_full, v_full, attn_mask)
    return layers.out_project(out, p, gate), (ck, cv)


def _plain_attention(q, k, v, p, cfg: ModelConfig, call: Call,
                     gate=None) -> jax.Array:
    """Attention of a call without a cache, the input block over itself
    (q, k, v rotated; -> the projected output)."""
    positions, attn_mask = call.positions, call.attn_mask
    if cfg.attn_impl == "flash" and attn_mask is None:
        # Sliding-window models ride the kernel's window band (positions
        # space, layers.and_window semantics): out-of-window tiles are
        # skipped without even a DMA, so windowed prefill work scales with
        # the window instead of the sequence.
        from ..ops import flash

        out = flash.flash_attention(
            q, k, v,
            q_positions=None if call.std_layout else positions,
            k_positions=None if call.std_layout else positions,
            causal=True, window=cfg.model_window,
        )
    elif cfg.attn_impl in ("ring", "ulysses"):
        # Sequence-parallel paths: we are inside a shard_map over the 'seq'
        # mesh axis (ParallelModel handles the wrapping); positions carry
        # *global* indices so causality holds across blocks.
        if attn_mask is not None:
            raise NotImplementedError(
                f"{cfg.attn_impl} attention supports causal masking only"
            )
        if cfg.attn_impl == "ring":
            from ..ops import ring

            out = ring.ring_attention(q, k, v, positions, positions, axis_name="seq")
        else:
            from ..ops import ulysses

            out = ulysses.ulysses_attention(q, k, v, positions, axis_name="seq")
    else:
        if attn_mask is None:
            mask = layers.causal_mask(positions, positions, window=cfg.model_window)
        else:
            mask = attn_mask
            if cfg.model_window is not None:
                mask = layers.and_window(
                    mask, positions, positions, cfg.model_window
                )
        k_full = layers.repeat_kv(k, cfg.q_per_kv)
        v_full = layers.repeat_kv(v, cfg.q_per_kv)
        out = layers.dot_product_attention(q, k_full, v_full, mask)
    return layers.out_project(out, p, gate)


def _seq_cached_attention(
    q: jax.Array,  # [B, Tq, H, HD] (post-RoPE)
    k: jax.Array,  # [B, Tq, KVH, HD]
    v: jax.Array,
    p: Params,
    cfg: ModelConfig,
    call: Call,
    layer_cache: tuple,  # ((ck_pref, ck_dec), (cv_pref, cv_dec))
) -> tuple[jax.Array, tuple]:
    """Cached attention under sequence parallelism — runs inside a shard_map
    over the 'seq' axis (parallel.api wraps it).

    Two-region cache layout: the prefill region holds the long prompt's KV
    sharded over 'seq' (each device keeps its own block — written locally,
    never moved); the decode region holds generated tokens' KV replicated
    (bounded by max_new_tokens, a sliver next to a long-context prompt).

    Prefill (Tq > 1): this device's block fills its prefill slice wholesale
    and attention is the ring / Ulysses pass.  Decode (Tq == 1): the token's
    KV appends to the decode region on every device, and attention merges
    flash-style partial stats across the seq axis (one psum) — the KV stays
    put instead of rotating to meet a single query (ops/ring.py,
    seq_cached_decode_attention)."""
    from ..ops import ring

    positions, attn_mask = call.positions, call.attn_mask
    (ck_pref, ck_dec), (cv_pref, cv_dec) = layer_cache
    tq = q.shape[1]
    if tq > 1:
        # -- prefill: whole (sharded) prompt in one pass at cache_index 0.
        if attn_mask is not None:
            raise NotImplementedError(
                "sequence-parallel prefill supports causal masking only"
            )
        if tq != ck_pref.shape[1]:
            raise ValueError(
                f"seq-parallel prefill expects the full prompt at once: got "
                f"{tq} local tokens for a {ck_pref.shape[1]}-slot local "
                "prefill region (chunked prefill is unsupported here)"
            )
        ck_pref = k.astype(ck_pref.dtype)
        cv_pref = v.astype(cv_pref.dtype)
        if cfg.attn_impl == "ring":
            out = ring.ring_attention(q, k, v, positions, positions, axis_name="seq")
        else:
            from ..ops import ulysses

            out = ulysses.ulysses_attention(q, k, v, positions, axis_name="seq")
        return layers.out_project(out, p), ((ck_pref, ck_dec), (cv_pref, cv_dec))

    # -- decode: append this token's KV to the replicated decode region.
    if not isinstance(attn_mask, tuple):
        raise ValueError(
            "seq-parallel cached decode needs attn_mask=(prefill_mask, "
            "decode_mask) — ParallelModel.forward splits the global mask"
        )
    t_pref_global = ck_pref.shape[1] * jax.lax.axis_size("seq")
    di = call.cache_index - t_pref_global
    ck_dec = jax.lax.dynamic_update_slice(ck_dec, k.astype(ck_dec.dtype), (0, di, 0, 0))
    cv_dec = jax.lax.dynamic_update_slice(cv_dec, v.astype(cv_dec.dtype), (0, di, 0, 0))
    m_pref, m_dec = attn_mask
    out = ring.seq_cached_decode_attention(
        q, ck_pref.astype(q.dtype), cv_pref.astype(q.dtype),
        ck_dec.astype(q.dtype), cv_dec.astype(q.dtype),
        m_pref, m_dec, axis_name="seq",
    )
    return layers.out_project(out, p), ((ck_pref, ck_dec), (cv_pref, cv_dec))


def mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale of a latent-attention head: width ** -0.5, times
    YaRN's mscale(factor, mscale_all_dim) squared."""
    m = (layers.yarn_mscale(cfg.rope_scaling_factor, cfg.yarn_mscale_all_dim)
         if cfg.rope_scaling_type == "yarn" else 1.0)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def mla_rope(x: jax.Array, positions: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Rotate the rope part of latent attention's queries or shared key
    (pairs (2i, 2i + 1) together), with YaRN where the config has it."""
    if cfg.rope_scaling_type == "yarn" and cfg.rope_scaling_factor != 1.0:
        f = cfg.rope_scaling_factor
        inv_freq = layers.yarn_frequencies(
            x.shape[-1], cfg.rope_theta, f, cfg.rope_original_max_len,
            cfg.yarn_beta_fast, cfg.yarn_beta_slow)
        m = (layers.yarn_mscale(f, cfg.yarn_mscale)
             / layers.yarn_mscale(f, cfg.yarn_mscale_all_dim))
    else:
        inv_freq, m = layers.rope_frequencies(x.shape[-1], cfg.rope_theta), 1.0
    return layers.apply_rope_pairs(x, positions, inv_freq, m)


_MLA_QUERY_BLOCK = 256


def _expanded_attention(q, k, v, mask, scale=None):
    """Dense attention of an admission, every query head with a key and
    value head of its own, the queries a block at a time where there are
    many: 64 heads x 2,048 queries x 4,096 slots of float32 scores would
    be 2 GiB at once."""
    b, t, h, _ = q.shape
    n = t // _MLA_QUERY_BLOCK
    if n < 2 or t % _MLA_QUERY_BLOCK:
        return layers.dot_product_attention(q, k, v, mask, scale)
    mask = jnp.broadcast_to(mask, (b, mask.shape[1], t, k.shape[1]))

    def block(args):
        qb, mb = args
        return layers.dot_product_attention(qb, k, v, mb, scale)

    out = jax.lax.map(block, (
        jnp.moveaxis(q.reshape(b, n, _MLA_QUERY_BLOCK, h, -1), 1, 0),
        jnp.moveaxis(mask.reshape(b, -1, n, _MLA_QUERY_BLOCK, k.shape[1]),
                     2, 0),
    ))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, -1)


@jax.named_scope("attn")  # profiler scope; HLO metadata only
def mla_attention(
    x: jax.Array,  # [B, T, D], normed
    p: Params,  # wq_a, q_norm, wq_b, wkv_a, kv_norm, wkv_b, wo
    cfg: ModelConfig,
    call: Call,
    layer_cache: Any,  # None; this layer's latent rows [B, S, W]; or with
    #   call.kv_tables the whole latent page pool (kv_cache.LatentCache)
    layer: jax.Array | None = None,
) -> tuple[jax.Array, Any]:
    """Multi-head latent attention.  ``c_q = rms(x W_qa)``, a head's query
    ``[q_nope | rope(q_rope)] = c_q W_qb``; ``[c | k_r] = x W_kva``, the
    token's cached row ``[rms(c) | rope(k_r)]`` (one rotated key for all
    heads); a head's key ``[c_kv W_uk | k_rope]`` and value ``c_kv W_uv``
    with ``W_kvb = [W_uk | W_uv]`` a head.

    A decode step against the page pool (``kv_tables``) ABSORBS the
    up-projection: ``q_lat = q_nope W_uk^T`` attends to the rows as they
    lie and the weighted sum of latents goes through ``W_uv`` afterwards
    (ops.decode_attn.mla_paged_decode_attention), so a page is read once
    and no head's key or value is ever formed.  Every other call EXPANDS
    keys and values from the rows and attends as the other families'
    admissions do: a fresh row ("start") its own T rows, among themselves
    (:func:`_self_attention`); a suffix behind a prefix hit the row cache's
    every slot, the cached run's too, densely.  All read the same stored
    rows and the same W_kvb."""
    from ..ops import decode_attn

    positions, cache_index, kind = call.positions, call.cache_index, call.kind
    attn_mask, kv_tables = call.attn_mask, call.kv_tables
    if call.key_positions is not None:
        raise ValueError(
            "latent attention has no window: a map of the slots' positions "
            "(key_positions) has nothing to say to it"
        )
    b, t, _ = x.shape
    h, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    w = cfg.latent_width
    scale = mla_scale(cfg)
    with jax.named_scope("mla_q"):
        cq = layers.rms_norm(
            layers._contract(x, p["wq_a"], "btd,dn->btn", 1, "n"),
            p["q_norm"], cfg.norm_eps)
        q = layers._contract(cq, p["wq_b"], "btd,dn->btn", 1, "n").reshape(
            b, t, h, dn + dr)
        q_nope, q_rope = q[..., :dn], mla_rope(q[..., dn:], positions, cfg)
    with jax.named_scope("mla_kv"):
        ckr = jnp.einsum("btd,dn->btn", x, p["wkv_a"].astype(x.dtype))
        row = jnp.concatenate([
            layers.rms_norm(ckr[..., :r], p["kv_norm"], cfg.norm_eps),
            mla_rope(ckr[..., r:], positions, cfg),
            jnp.zeros((b, t, w - r - dr), x.dtype),
        ], axis=-1)  # [B, T, W]: what is cached
    wkv_b = p["wkv_b"].astype(x.dtype).reshape(r, h, dn + dv)

    def project(o):  # [B, T, H, dv] -> [B, T, D]
        return layers._contract(
            o.reshape(b, t, h * dv), p["wo"], "btn,nd->btd", 1, "k")

    if kv_tables is not None:
        if kind != "decode":
            raise ValueError(
                "paged attention is per-row decode (a per-row cache_index "
                "over a page-pool cache)"
            )
        pool = layer_cache
        rows = jnp.arange(b, dtype=jnp.int32)
        blk = pool.k.shape[2]
        idx = cache_index[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        pool = kv_cache.write_tokens(
            pool, layer, kv_tables[rows[:, None], idx // blk], idx % blk, row)
        with jax.named_scope("mla_absorb"):
            q_abs = jnp.concatenate([
                jnp.einsum("bthn,rhn->bthr", q_nope, wkv_b[..., :dn]),
                q_rope, jnp.zeros((b, t, h, w - r - dr), x.dtype),
            ], axis=-1).astype(pool.k.dtype)
        # T > 1 (no caller today): a call a token, as _paged_attention.
        o_lat = jnp.concatenate([
            decode_attn.mla_paged_decode_attention(
                q_abs[:, j: j + 1], pool.k, cache_index + 1 + j, kv_tables,
                latent=r, scale=scale, layer=layer)
            for j in range(t)
        ], axis=1)
        with jax.named_scope("mla_absorb"):
            o = jnp.einsum("bthr,rhv->bthv", o_lat.astype(x.dtype),
                           wkv_b[..., dn:])
        return project(o), pool

    def expand(rows):  # latent rows [B, S, W] -> a head's keys and values
        kv = jnp.einsum("bsr,rhn->bshn", rows[..., :r], wkv_b)
        return jnp.concatenate([
            kv[..., :dn],
            jnp.broadcast_to(rows[:, :, None, r: r + dr],
                             (*kv.shape[:3], dr)),
        ], axis=-1), kv[..., dn:]

    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    if kind == "start":
        # An admission's fresh row: W_kvb expands the T new rows only, they
        # attend among themselves and take the row cache's first T slots.
        ck = row.astype(layer_cache.dtype)
        out = _self_attention(
            q, *expand(ck.astype(x.dtype)), positions, scale=scale)
        return project(out), layer_cache.at[:, :t].set(ck)

    if kind == "plain":
        keys, new_cache = row, None
        mask = (layers.causal_mask(positions, positions)
                if attn_mask is None else attn_mask)
    else:
        ck = layer_cache  # [B, S, W]
        if kind == "decode":
            if attn_mask is None:
                raise ValueError(
                    "per-row cache_index requires an explicit attn_mask")
            ck = jax.vmap(
                lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i, 0))
            )(ck, row.astype(ck.dtype), cache_index)
        else:
            ck = jax.lax.dynamic_update_slice(
                ck, row.astype(ck.dtype), (0, cache_index, 0))
        new_cache, keys, mask = ck, ck.astype(x.dtype), attn_mask
        if kind == "continuation":
            s = keys.shape[1]
            k_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
            mask = layers.causal_mask(positions, k_pos,
                                      k_pos < cache_index + t)
    out = _expanded_attention(q, *expand(keys), mask, scale)
    return project(out), new_cache


_TOKEN_BLOCK = 2048  # tokens a long admission's FFNs take at a time


_LANES = 128  # a register's lanes: the flash kernel's tiles are whole ones


def _self_attention(q, k, v, positions, window: int | None = None,
                    scale: float | None = None,
                    rows: jax.Array | None = None):
    """Causal attention of T tokens over themselves, a row's start: the one
    place an admission's fresh row is scored (q [B, T, H, hd], k [B, T,
    KVH, hd], v [B, T, KVH, hv]; ``positions`` [B, T] rise by one along the
    block; ``scale`` None: hd ** -0.5; ``rows`` what ``Call.rows``
    holds: the kernel ends its grid at the last tile of queries that
    holds a real token and leaves the tiles past it as zeros, the real
    tokens' outputs bit for bit what they are without it; the dense bodies
    score the bucket).  The body is chosen by what the call can see:

    - on the chip, one shard, heads a whole number of registers wide (128:
      qwen2, pythia, k-exaone), and on the interpreter whatever the heads:
      the flash kernel (ops/flash.py, its static-causal path: no [T, T]
      score matrix exists, tiles above the diagonal and, with ``window``,
      below the band are skipped and never fetched, so a windowed layer's
      work grows with T x window and an 8,192-token admission fits);
    - on the chip, heads that fill a register in part (lfm2's 64; latent
      attention's expanded 192 for q and k beside 128 for v):
      layers.dot_product_attention over the T keys under
      layers.causal_mask, in query blocks where T is long.  Against scoring
      a row cache's every slot it drops only terms that are exactly zero,
      which is what these two configurations' goldens (the served path's
      own first-token logprobs, held to 0.05) ask for: the kernel sums in
      another order and put A.X-K1's 700-byte probe 0.060 off, lfm2's four
      0.10-0.23 (PERF.md, PR 35; the kernel takes 192 / 128 and a
      ``scale`` and was the faster body there, so the rule is the
      goldens', not the tiles');
    - under a mesh (the kernel has no per-shard wrapper) and with
      ``DLT_RAGGED_DECODE=fallback`` (the CPU's default; counted as the
      kernel's fallback), the same dense body: the numbers the kernel is
      parity-tested against.

    (As one XLA softmax over 8,192 keys the row maximum compiles to a
    reduce-window that takes 24 ms a block of 128 queries: PERF.md, PR 34.)"""
    from ..ops import dispatch, flash

    mode = _flash_mode(q.shape[-1])
    if mode is None or mode == "fallback":
        if mode == "fallback":  # (a body chosen by shape or mesh is no
            # fallback: the record would read as a kernel that failed)
            dispatch.record("flash", "fallback", (*q.shape, k.shape[2]))
        g = q.shape[2] // k.shape[2]
        return _expanded_attention(
            q, layers.repeat_kv(k, g), layers.repeat_kv(v, g),
            layers.causal_mask(positions, positions, window=window), scale)
    block = _flash_block(window, q.shape[-1] * q.dtype.itemsize)
    return flash.flash_attention(
        q, k, v, causal=True, window=window, block_q=block, block_k=block,
        interpret=mode == "interpret", scale=scale, rows=rows)


def _continuation_attention(q, ck, cv, positions, cache_index,
                            window: int | None = None, key_positions=None):
    """Causal attention of T new tokens over a row cache that holds them
    behind what came before, a row's CONTINUATION: the one place it is
    scored (q [B, T, H, hd]; ck, cv [B, S, KVH, hd], the new keys and
    values already written at slots [cache_index, cache_index + T);
    ``positions`` [B, T] the new tokens'; ``cache_index`` a scalar, so every
    row of the batch has its keys in slots [0, cache_index + T) and none
    past them: a suffix behind a cached prefix, a chunk of a chunked
    prefill, a one-shot prefill into a cache longer than the prompt).  A
    query sees the slots at or below its position, with ``window`` those
    above position - window.  The body is chosen by what the call can see,
    as :func:`_self_attention`'s is:

    - on the chip, one shard, heads a whole number of registers wide, and on
      the interpreter whatever the heads: the flash kernel's static-causal
      path with its diagonal ``cache_index`` down (ops/flash.py,
      ``start``): no [T, S] score matrix and no copy of the keys at the
      query heads exists, the cached run's tiles are scored without a
      mask, and no tile of keys past the new tokens is fetched, so the
      work grows with the keys the row holds and not with ``max_len``.  The
      kernel takes slot == position for causality AND the window: exact in
      the ungapped layout, where ``positions`` are cache_index + 0 .. T - 1;
      a windowed model whose caller brings a map of the slots' positions
      (``key_positions``: the right-padded generate layout) and a single
      token (a decode step: the kernel's tiles are blocks of queries) take
      the dense body;
    - heads that fill a register in part, a mesh, and
      ``DLT_RAGGED_DECODE=fallback`` (the CPU's default; counted as the
      kernel's fallback): layers.dot_product_attention over all S slots
      under layers.causal_mask, the numbers the kernel is parity-tested
      against."""
    from ..ops import dispatch, flash

    b, t = q.shape[:2]
    s = ck.shape[1]
    mode = _flash_mode(q.shape[-1])
    suited = t > 1 and (window is None or key_positions is None)
    if suited and mode in ("kernel", "interpret"):
        return flash.flash_attention(
            q, ck.astype(q.dtype), cv.astype(q.dtype), causal=True,
            window=window, block_q=_CONTINUATION_BLOCKS[0],
            block_k=_CONTINUATION_BLOCKS[1], interpret=mode == "interpret",
            start=jnp.reshape(cache_index, (1,)))
    if suited and mode == "fallback":
        dispatch.record("flash", "fallback", (*q.shape, ck.shape[2]))
    k_positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    # Causality/validity compare SLOT indices (the write frontier); the
    # window compares POSITIONS: for gapped layouts the caller supplies
    # key_positions (see _attention's parameter comment).
    mask = layers.causal_mask(
        positions, k_positions, k_positions < (cache_index + t))
    if window is not None:
        mask = layers.and_window(
            mask, positions,
            k_positions if key_positions is None else key_positions, window)
    g = q.shape[2] // ck.shape[2]
    return layers.dot_product_attention(
        q, layers.repeat_kv(ck.astype(q.dtype), g),
        layers.repeat_kv(cv.astype(q.dtype), g), mask)


def _flash_mode(head_dim: int) -> str | None:
    """How :func:`_self_attention` and :func:`_continuation_attention` score
    heads ``head_dim`` wide: "kernel" or "interpret" (the flash kernel's two
    legs), "fallback" (the dense body, asked for by ``DLT_RAGGED_DECODE``),
    None (the dense body, chosen by the mesh or by heads that fill a
    register in part)."""
    from ..ops import dispatch

    mode = dispatch.attention_mode()
    if mode == "fallback":
        return mode
    # (the interpreter, the tests' leg of the kernel's program, has no lanes)
    partial = mode == "kernel" and head_dim % _LANES != 0
    return None if dispatch.mesh() is not None or partial else mode


# The flash kernel's tile (queries, keys) for a row's continuation: the
# 7 x 128 rows of a KV group's query heads over a 128-token suffix are one
# tile of queries, and a cell's rows hold 800-1,900 keys, which tiles of 512
# follow twice as closely as tiles of 1,024.
_CONTINUATION_BLOCKS = (1024, 512)


def _flash_block(window: int | None, row_bytes: int = 512) -> int:
    """The flash kernel's tile for a row's start.  A band of 128 inside
    tiles of 1,024 would score eight times the keys it needs: tiles of 512
    for a windowed layer.  And for a head whose row is over 512 bytes (256
    wide in float32, a reference check's leg: tiles of 1,024 of q, k, v and
    the scores are 22.9 MB of the kernel's 16; in bfloat16, as served, a
    256-wide head's row is 512 bytes and keeps 1,024)."""
    return 1024 if window is None and row_bytes <= 512 else 512


def self_attention_pairs(cfg: ModelConfig, t: int, rows: int
                         ) -> tuple[int, int]:
    """((query, key) pairs the flash kernel's live tiles hold over one
    fresh admission of a bucket of ``t`` tokens, summed over ``cfg``'s
    attention layers by kind; of them those it scores for a prompt of
    ``rows`` tokens).  Equal where no count reaches the kernel (the
    families whose layers are all alike); (0, 0) where the admission does
    not take the kernel.  Host arithmetic, for the batcher's counters."""
    from ..ops.flash import live_tiles

    if cfg.kv_lora_rank:
        head = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    else:
        head = cfg.head_dim_
    if _flash_mode(head) not in ("kernel", "interpret"):
        return 0, 0
    kinds = [(len(cfg.attn_layers), cfg.model_window)]
    if cfg.swa_layers:
        kinds.append((len(cfg.swa_layers), cfg.sliding_window))
    # (mixed_attention alone hands the kernel the count)
    real = rows if cfg.swa_layers else t
    block = lambda w: _flash_block(w, head * jnp.dtype(cfg.dtype).itemsize)
    pairs = [(n, live_tiles(t, real, block(w), w)) for n, w in kinds]
    return (sum(n * p[0] for n, p in pairs), sum(n * p[1] for n, p in pairs))


def continuation_keys(cfg: ModelConfig, t: int, s: int, keys: int) -> int:
    """Slots of a row cache of ``s`` that one layer scores for a bucket of
    ``t`` tokens continuing a row that then holds ``keys``: the key tiles
    the flash kernel fetches where :func:`_continuation_attention` takes it
    (under a mesh the caller says so: the count is then ``s``), all ``s``
    for the dense body and for the families whose continuations are not
    scored there (latent attention's).  Host arithmetic, for the batcher's
    counters."""
    from ..ops.flash import live_keys

    if (cfg.family not in BLOCK_FNS or t == 1
            or _flash_mode(cfg.head_dim_) not in ("kernel", "interpret")):
        return s
    return live_keys(s, keys, _CONTINUATION_BLOCKS[1])


def mixed_attention(
    x: jax.Array,  # [B, T, D], normed
    p: Params,  # wq, wk, wv, wo (+ q_norm, k_norm)
    cfg: ModelConfig,
    op: str,  # "attn": the whole prefix; "swa": the last sliding_window
    call: Call,
    cache: kv_cache.HybridCache | None,  # the whole cache: the page pool
    #   (``call.kv_tables``) or a fresh row's contiguous cache, and the rings
    layer: jax.Array | int,  # index among the layers of its kind
) -> tuple[jax.Array, kv_cache.HybridCache | None]:
    """GQA attention of a model whose layers mix full and windowed
    attention (``cfg.swa_layers``: K-EXAONE's pattern of three windowed
    layers and a full one).  Both kinds: q, k RMS-normalised per head
    (``cfg.qk_norm``); a windowed layer rotates them, a full one only if
    ``cfg.attn_rope``; query head g reads key/value head g // q_per_kv.

    A decode step ("decode", one token a row): a full layer writes and
    reads its pages (:func:`_paged_attention`); a windowed one writes the
    new key and value into the row's ring at position mod W and attends to
    the ring's min(length, W) valid entries
    (ops.decode_attn.swa_decode_attention), so it reads at most W tokens a
    row however long the row is.  Otherwise the T tokens are a row's start
    ("plain", or "start": an admission) and attend among themselves
    (:func:`_self_attention`, a windowed layer through its band); a full
    layer leaves its keys and values in the row cache's first T slots, a
    windowed one leaves in the ring the last min(n, W) tokens of the
    ``call.seq_lens`` REAL ones (None: all T), at the prompt's true length
    and not at the padded bucket's end."""
    from ..ops import decode_attn

    positions, cache_index, seq_lens = (
        call.positions, call.cache_index, call.seq_lens)
    w = cfg.sliding_window if op == "swa" else None
    q, k, v = layers.qkv_project(x, p, cfg)
    if cfg.qk_norm:
        q = layers.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = layers.rms_norm(k, p["k_norm"], cfg.norm_eps)
    if op == "swa" or cfg.attn_rope:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    b, t = x.shape[:2]
    if call.kind == "decode":
        if t != 1 or (op == "attn" and call.kv_tables is None):
            raise ValueError(
                "a model of windowed and full attention layers decodes one "
                "token a row against the page pool and the rings"
            )
        if op == "attn":
            return _paged_attention(
                q, k, v, p, cache, layer, cache_index, call.kv_tables)
        cache = kv_cache.write_ring(
            cache, layer, cache_index % w, k[:, 0], v[:, 0])
        out = decode_attn.swa_decode_attention(
            q, cache.ring_k, cache.ring_v, jnp.minimum(cache_index + 1, w),
            layer)
        return layers.out_project(out, p), cache
    if call.kind in ("continuation", "masked"):
        raise ValueError(
            "a model of windowed and full attention layers prefills a row "
            "from its start (cache_index 0, no mask and no map of the "
            "caller's): the rings hold no prefix to continue from"
        )
    out = layers.out_project(
        _self_attention(q, k, v, positions, w, rows=call.rows), p)
    if cache is None:
        return out, None
    if op == "attn":
        return out, dataclasses.replace(
            cache,
            k=cache.k.at[layer, :, :t].set(k.astype(cache.k.dtype)),
            v=cache.v.at[layer, :, :t].set(v.astype(cache.v.dtype)))
    # Ring entry j holds the last real position that is j mod W (before
    # position 0: whatever, past the count a reader masks by).
    last = (jnp.full((b,), t, jnp.int32) if seq_lens is None
            else seq_lens) - 1
    j = jnp.arange(w, dtype=jnp.int32)
    src = jnp.clip(last[:, None] - (last[:, None] - j[None, :]) % w, 0, t - 1)
    take = src[:, :, None, None]
    return out, dataclasses.replace(
        cache,
        ring_k=cache.ring_k.at[layer].set(
            jnp.take_along_axis(k, take, axis=1).astype(cache.ring_k.dtype)),
        ring_v=cache.ring_v.at[layer].set(
            jnp.take_along_axis(v, take, axis=1).astype(cache.ring_v.dtype)))


def retention_layer(
    x: jax.Array,  # [B, T, D], normed
    p: Params,  # wq, wk, wv, wo, q_norm, k_norm, wg
    cfg: ModelConfig,
    call: Call,
    cache: kv_cache.HybridCache | None,  # the slots' states (a decode
    #   step) or a fresh row's (an admission); None: nothing is kept
    layer: jax.Array | int,  # index among the retention layers
) -> tuple[jax.Array, kv_cache.HybridCache | None]:
    """Power retention of degree 2 (layer kind "ret"; ops/retention.py has
    the equations and the state's layout).  GQA's projections, q and k
    RMS-normalised per head and rotated, and a scalar gate a token and a
    key/value head, ``log g = logsigmoid(x w_g)`` in float32.  A decode
    step ("decode", one token a row) is one recurrence step against the
    slots' states, which are updated where they lie; a row that does not
    decode (``call.seq_lens`` 0) keeps its state.  Otherwise the T tokens
    are a row's start ("plain", or "start": an admission) and run the
    chunked scan from an empty state, ``cfg.ret_chunk`` tokens a chunk,
    leaving in ``cache`` the state at the ``call.seq_lens`` REAL tokens
    (None: all T): a padded position adds nothing and gates nothing.  A
    state keeps no past, so there is nothing to continue from."""
    from ..ops import retention

    with jax.named_scope("ret_qkvg"):
        q, k, v = layers.qkv_project(x, p, cfg)
        q = layers.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = layers.rms_norm(k, p["k_norm"], cfg.norm_eps)
        q = layers.apply_rope(q, call.positions, cfg.rope_theta)
        k = layers.apply_rope(k, call.positions, cfg.rope_theta)
        log_g = jax.nn.log_sigmoid(jnp.einsum(
            "btd,dh->bth", x, p["wg"].astype(x.dtype),
            preferred_element_type=jnp.float32))
    b, t = x.shape[:2]
    if call.kind == "decode":
        if t != 1 or cache is None:
            raise ValueError(
                "a retention layer decodes one token a row against the "
                "slots' states"
            )
        live = None if call.seq_lens is None else call.seq_lens > 0
        with jax.named_scope("retention"):
            o, ret_s, ret_z = retention.retention_decode(
                q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], cache.ret_s,
                cache.ret_z, layer, live)
        return layers.out_project(o[:, None], p), dataclasses.replace(
            cache, ret_s=ret_s, ret_z=ret_z)
    if call.kind in ("continuation", "masked"):
        raise ValueError(
            "a retention layer prefills a row from its start (cache_index "
            "0, no mask and no map of the caller's): the state holds no "
            "prefix to continue from"
        )
    with jax.named_scope("retention"):
        rows = [retention.retention_prefill(
            q[i], k[i], v[i], log_g[i],
            None if call.seq_lens is None else call.seq_lens[i],
            cfg.ret_chunk) for i in range(b)]
    out = layers.out_project(jnp.stack([r[0] for r in rows]), p)
    if cache is None:
        return out, None
    return out, dataclasses.replace(
        cache,
        ret_s=cache.ret_s.at[layer].set(jnp.stack([r[1] for r in rows])),
        ret_z=cache.ret_z.at[layer].set(jnp.stack([r[2] for r in rows])))


def retention_counts(cfg: ModelConfig, call: Call, shape: tuple) -> jax.Array:
    """What a pass of a retention model did, int32 [4], a by-product like
    the expert layers' counts (:func:`run_layers`' third value): the real
    tokens an admission scanned and the chunks it walked (those that hold
    a real token), the rows a decode step advanced and the tokens those
    rows then held, the new one included.  A layer, not summed over them."""
    b, t = shape
    lens = (jnp.full((b,), t, jnp.int32) if call.seq_lens is None
            else call.seq_lens.astype(jnp.int32))
    zero = jnp.zeros((), jnp.int32)
    if call.kind == "decode":
        held = jnp.where(lens > 0, call.cache_index + 1, 0)
        return jnp.stack([zero, zero, jnp.sum((lens > 0).astype(jnp.int32)),
                          jnp.sum(held, dtype=jnp.int32)])
    return jnp.stack([jnp.sum(lens), jnp.sum(-(-lens // cfg.scan_chunk)),
                      zero, zero])


def ssm_layer(
    x: jax.Array,  # [B, T, D], normed
    p: Params,  # in_proj, taps, conv_bias, dt_bias, A_log, D, norm_w, out_proj
    cfg: ModelConfig,
    call: Call,
    cache: kv_cache.HybridCache | None,  # the slots' states and taps (a
    #   decode step) or a fresh row's (an admission); None: nothing is kept
    layer: jax.Array | int,  # index among the state-space layers
) -> tuple[jax.Array, kv_cache.HybridCache | None]:
    """Mamba-2 (layer kind "ssm"; ops/ssm.py has the scan's equations and
    the state's layout).  ``[z | xBC | dt] = x W_in``; ``xBC <- silu(causal
    depthwise conv_K(xBC) + b)``, split ``[x (heads x P) | B (groups x N) |
    C (groups x N)]``; ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``
    in float32; the scan; ``y <- y + D x``; the gate FIRST, ``y silu(z)``,
    then an RMS norm over each group's channels times ``norm_w``; ``y
    W_out``.  A decode step ("decode", one token a row) is one recurrence
    step against the slots' states, updated where they lie, and moves the
    convolution's taps by one; a row that does not decode (``call.seq_lens``
    0) keeps both.  Otherwise the T tokens are a row's start ("plain", or
    "start": an admission) and run the chunked scan from an empty state,
    ``cfg.ssm_chunk`` tokens a chunk, leaving in ``cache`` the state and the
    taps at the ``call.seq_lens`` REAL tokens (None: all T).  A state keeps
    no past, so there is nothing to continue from."""
    from ..ops import ssm

    if call.kind in ("continuation", "masked"):
        raise ValueError(
            "a state-space layer prefills a row from its start (cache_index "
            "0, no mask and no map of the caller's): the state holds no "
            "prefix to continue from"
        )
    decode = call.kind == "decode"
    b, t, _ = x.shape
    if decode and (t != 1 or cache is None):
        raise ValueError(
            "a state-space layer decodes one token a row against the "
            "slots' states"
        )
    nh, hd, ng, ns = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                      cfg.ssm_state)
    inner, width = cfg.ssm_inner, cfg.ssm_conv_width
    with jax.named_scope("ssm_proj"):
        zxd = layers._contract(x, p["in_proj"], "btd,df->btf", 1, "n")
        z, xbc = zxd[..., :inner], zxd[..., inner:inner + width]
        dt = jax.nn.softplus(zxd[..., inner + width:].astype(jnp.float32)
                             + p["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(p["A_log"].astype(jnp.float32))
    with jax.named_scope("ssm_conv"):
        xbc, new_taps = layers.causal_conv(
            xbc, p["taps"], cache.ssm_conv[layer] if decode else None,
            call.seq_lens)
        xbc = jax.nn.silu(
            xbc + p["conv_bias"].astype(jnp.float32)).astype(x.dtype)
    xs = xbc[..., :inner].reshape(b, t, nh, hd)
    bm = xbc[..., inner:inner + ng * ns].reshape(b, t, ng, ns)
    cm = xbc[..., inner + ng * ns:].reshape(b, t, ng, ns)
    with jax.named_scope("ssm_scan"):
        if decode:
            live = None if call.seq_lens is None else call.seq_lens > 0
            y, ssm_h = ssm.ssm_decode(
                xs[:, 0], bm[:, 0], cm[:, 0], dt[:, 0], a, cache.ssm_h,
                layer, live)
            y = y[:, None]
            cache = dataclasses.replace(
                cache, ssm_h=ssm_h, ssm_conv=cache.ssm_conv.at[layer].set(
                    new_taps.astype(cache.ssm_conv.dtype)))
        else:
            rows = [ssm.ssm_prefill(
                xs[i], bm[i], cm[i], dt[i], a,
                None if call.seq_lens is None else call.seq_lens[i],
                cfg.ssm_chunk) for i in range(b)]
            y = jnp.stack([r[0] for r in rows])
            if cache is not None:
                cache = dataclasses.replace(
                    cache,
                    ssm_h=cache.ssm_h.at[layer].set(
                        jnp.stack([r[1] for r in rows])),
                    ssm_conv=cache.ssm_conv.at[layer].set(
                        new_taps.astype(cache.ssm_conv.dtype)))
    with jax.named_scope("ssm_gate"):
        y = (y.astype(jnp.float32)
             + p["D"].astype(jnp.float32)[:, None] * xs.astype(jnp.float32))
        y = y.reshape(b, t, inner) * jax.nn.silu(z.astype(jnp.float32))
        y = y.reshape(b, t, ng, inner // ng)
        y = y * jax.lax.rsqrt(
            jnp.mean(jnp.square(y), axis=-1, keepdims=True) + cfg.norm_eps)
        y = (y.reshape(b, t, inner)
             * p["norm_w"].astype(jnp.float32)).astype(x.dtype)
    return layers._contract(y, p["out_proj"], "btf,fd->btd", 1, "k"), cache


def gdn_layer(
    x: jax.Array,  # [B, T, D], normed
    p: Params,  # w_qkvz, w_ba, taps, dt_bias, A_log, norm_w, out_proj
    cfg: ModelConfig,
    call: Call,
    cache: kv_cache.HybridCache | None,  # the slots' states and taps (a
    #   decode step) or a fresh row's (an admission); None: nothing is kept
    layer: jax.Array | int,  # index among the delta-rule layers
) -> tuple[jax.Array, kv_cache.HybridCache | None]:
    """Gated DeltaNet (layer kind "gdn"; ops/gdn.py has the scan's
    equations).  ``[q | k | v | z] = x W_qkvz`` and ``[b | a] = x W_ba``, in
    that order; ``[q | k | v] <- silu(causal depthwise conv_K([q | k | v]))``,
    no bias; ``q`` and ``k`` L2-normalised a head (eps 1e-6), ``q`` times
    ``key_dim^-0.5`` (inside the scan's operators); ``beta = sigmoid(b)`` and ``g = -exp(A_log) softplus(a +
    dt_bias)`` in float32; the scan; then an RMS norm over each head's values
    times ``norm_w`` FIRST and the gate ``silu(z)`` after it (the other way
    round from :func:`ssm_layer`); ``o W_out``.  The kinds of call are
    :func:`ssm_layer`'s: a decode step is one recurrence step against the
    slots' states, updated where they lie, and moves the taps by one (a row
    that does not decode keeps both); any other call is a row's start and
    runs the chunked scan from an empty state, ``cfg.gdn_chunk`` tokens a
    chunk, leaving state and taps at the ``call.seq_lens`` REAL tokens."""
    from ..ops import gdn

    if call.kind in ("continuation", "masked"):
        raise ValueError(
            "a delta-rule layer prefills a row from its start (cache_index "
            "0, no mask and no map of the caller's): the state holds no "
            "prefix to continue from"
        )
    decode = call.kind == "decode"
    b, t, _ = x.shape
    if decode and (t != 1 or cache is None):
        raise ValueError(
            "a delta-rule layer decodes one token a row against the slots' "
            "states"
        )
    hk, hv, dk, dv = (cfg.gdn_key_heads, cfg.gdn_value_heads,
                      cfg.gdn_key_dim, cfg.gdn_value_dim)
    kw, width = cfg.gdn_key_width, cfg.gdn_conv_width
    f32 = jnp.float32
    with jax.named_scope("gdn_proj"):
        qkvz = layers._contract(x, p["w_qkvz"], "btd,df->btf", 1, "n")
        qkv, z = qkvz[..., :width], qkvz[..., width:]
        ba = jnp.einsum("btd,dh->bth", x, p["w_ba"].astype(x.dtype),
                        preferred_element_type=f32)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
            ba[..., hv:] + p["dt_bias"].astype(f32))
    with jax.named_scope("gdn_conv"):
        qkv, new_taps = layers.causal_conv(
            qkv, p["taps"], cache.gdn_conv[layer] if decode else None,
            call.seq_lens)
        qkv = jax.nn.silu(qkv).astype(x.dtype)
        # (q and k go to the scan as they are: ops/gdn.py normalises them)
        q = qkv[..., :kw].reshape(b, t, hk, dk)
        k = qkv[..., kw:2 * kw].reshape(b, t, hk, dk)
        v = qkv[..., 2 * kw:].reshape(b, t, hv, dv)
    with jax.named_scope("gdn_scan"):
        if decode:
            live = None if call.seq_lens is None else call.seq_lens > 0
            o, gdn_s = gdn.gdn_decode(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], cache.gdn_s,
                layer, live)
            o = o[:, None]
            cache = dataclasses.replace(
                cache, gdn_s=gdn_s, gdn_conv=cache.gdn_conv.at[layer].set(
                    new_taps.astype(cache.gdn_conv.dtype)))
        else:
            rows = [gdn.gdn_prefill(
                q[i], k[i], v[i], g[i], beta[i],
                None if call.seq_lens is None else call.seq_lens[i],
                cfg.gdn_chunk) for i in range(b)]
            o = jnp.stack([r[0] for r in rows])
            if cache is not None:
                cache = dataclasses.replace(
                    cache,
                    gdn_s=cache.gdn_s.at[layer].set(
                        jnp.stack([r[1] for r in rows])),
                    gdn_conv=cache.gdn_conv.at[layer].set(
                        new_taps.astype(cache.gdn_conv.dtype)))
    with jax.named_scope("gdn_gate"):
        o = o.astype(f32)
        o = o * jax.lax.rsqrt(
            jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.norm_eps)
        o = (o * p["norm_w"].astype(f32)
             * jax.nn.silu(z.astype(f32).reshape(b, t, hv, dv)))
        o = o.reshape(b, t, hv * dv).astype(x.dtype)
    return layers._contract(o, p["out_proj"], "btf,fd->btd", 1, "k"), cache


def ssm_counts(cfg: ModelConfig, call: Call, shape: tuple) -> jax.Array:
    """What a pass did in ONE state-space layer (or ONE delta-rule layer),
    int32 [3], a by-product beside the expert layers' counts
    (:func:`run_layers`' third value): the real tokens an admission scanned
    and the chunks it walked (those that hold a real token), the rows a
    decode step advanced."""
    b, t = shape
    lens = (jnp.full((b,), t, jnp.int32) if call.seq_lens is None
            else call.seq_lens.astype(jnp.int32))
    zero = jnp.zeros((), jnp.int32)
    if call.kind == "decode":
        return jnp.stack([zero, zero, jnp.sum((lens > 0).astype(jnp.int32))])
    return jnp.stack([jnp.sum(lens), jnp.sum(-(-lens // cfg.scan_chunk)),
                      zero])


def gpt2_block(x, p, cfg, call, layer_cache, layer=None):
    """-> (x, new_cache, aux): aux is the MoE load-balance term (0 here).
    Shared by the gpt2 and opt families (pre-LN + learned positions);
    cfg.activation picks the MLP nonlinearity (gelu vs relu)."""
    h = layers.layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"], cfg.norm_eps)
    attn_out, new_cache = _attention(h, p["attn"], cfg, False, call, layer_cache, layer)
    x = x + attn_out
    h = layers.layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"], cfg.norm_eps)
    x = x + layers.mlp_gelu(h, p["mlp"], cfg.activation)
    return x, new_cache, jnp.float32(0.0)


def llama_block(x, p, cfg, call, layer_cache, layer=None):
    """-> (x, new_cache, aux): aux is the MoE load-balance term."""
    h = layers.rms_norm(x, p["ln1"]["scale"], cfg.norm_eps)
    attn_out, new_cache = _attention(h, p["attn"], cfg, True, call, layer_cache, layer)
    x = x + attn_out
    h = layers.rms_norm(x, p["ln2"]["scale"], cfg.norm_eps)
    if "router" in p["mlp"]:  # MoE block (cfg.num_experts > 0)
        if cfg.moe_capacity:
            mlp_out, aux = layers.moe_swiglu(h, p["mlp"], cfg)
        else:  # no drops, and no load to balance: nothing is dropped
            mlp_out, aux = layers.moe_dropless_layer(h, p["mlp"], cfg), 0.0
        return x + mlp_out, new_cache, jnp.float32(aux)
    x = x + layers.mlp_swiglu(h, p["mlp"], cfg.gate_act)
    return x, new_cache, jnp.float32(0.0)


def neox_block(x, p, cfg, call, layer_cache, layer=None):
    """GPT-NeoX/Pythia: LayerNorm + (partial) rotary + optionally PARALLEL
    residual — out = x + attn(ln1 x) + mlp(ln2 x), both norms reading the
    SAME input (HF use_parallel_residual, the NeoX default); sequential
    pre-LN otherwise.  -> (x, new_cache, aux)."""
    h = layers.layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"], cfg.norm_eps)
    attn_out, new_cache = _attention(h, p["attn"], cfg, True, call, layer_cache, layer)
    if cfg.parallel_residual:
        h2 = layers.layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"], cfg.norm_eps)
        return x + attn_out + layers.mlp_gelu(h2, p["mlp"], cfg.activation), new_cache, jnp.float32(0.0)
    x = x + attn_out
    h2 = layers.layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"], cfg.norm_eps)
    return x + layers.mlp_gelu(h2, p["mlp"], cfg.activation), new_cache, jnp.float32(0.0)


def layer_of(blocks: Params, layer: jax.Array,
             rows: jax.Array | None = None) -> Params:
    """Layer ``layer`` of stacked block leaves, for the body of a layer
    scan.  A quantized stack of matrices [L, N, K] stays whole and carries
    the index (``QuantizedTensor.at``): layers._contract hands both to the
    kernel, which reads the layer's tiles where they lie, where a slice
    would be a copy of the layer's weights in every step (a Pallas call
    takes each operand as a buffer of its own).  It carries ``rows`` too
    (``Call.rows``), for the kernel to skip an admission's padding.
    Every other leaf, norms and biases, float weights, expert stacks of the
    capacity path, is sliced."""
    from ..checkpoint.quantize import QuantizedTensor

    def is_q(a):
        return isinstance(a, QuantizedTensor)

    def take(a):
        if is_q(a) and a.data.ndim == 3:
            return a.at(layer, rows)
        return jax.tree.map(lambda v: v[layer], a)

    return jax.tree.map(take, blocks, is_leaf=is_q)


BLOCK_FNS = {"gpt2": gpt2_block, "opt": gpt2_block, "llama": llama_block,
             "neox": neox_block}


def run_blocks(
    x: jax.Array,
    blocks: Params,
    cfg: ModelConfig,
    call: Call,
    cache: Any,  # these blocks' cache (models/kv_cache.py) or None: leaves
    #   [L, B, S, KVH, HD] contiguous, or with call.kv_tables the page pool
    remat: bool = False,
) -> tuple[jax.Array, Any, jax.Array]:
    """Scan the stacked blocks over x.  Used both for the whole model and for
    a single pipeline stage (blocks then hold only the stage's layer slice).
    Returns (x, cache', aux) — aux sums the MoE load-balance terms.

    A contiguous cache enters the scan as scanned inputs and leaves it as
    stacked outputs, one layer's [B, S, KVH, HD] at a time.  A page pool
    (``call.kv_tables``) is the scan's CARRY instead, beside x: each layer
    scatters its new K/V into the stack at (layer, page, off) and the
    paged kernel reads its pages out of the stack, so the pool is updated
    where it lies.  Sliced per layer it would be copied whole four times
    a decode step (a Pallas call takes each operand as a buffer of its
    own, and the stacked outputs are a second pool).

    Blocks may carry ``QuantizedTensor`` leaves (weight-only quantized
    serving): weights live in HBM at int8/int4.  With a cache the scan
    runs over the layer's index alone and the blocks are closed over:
    :func:`layer_of` slices the small leaves and hands each matmul site
    its whole stack and the index, and layers._contract runs the fused
    dequant-matmul Pallas kernel (ops/quant_matmul.py) on the layer's
    tiles where they lie — the weights are read once, at their quantized
    width, never copied and never materialized full-dtype in HBM."""
    block_fn = BLOCK_FNS[cfg.family]
    layer_index = jnp.arange(
        jax.tree.leaves(blocks)[0].shape[0], dtype=jnp.int32)

    paged = call.kv_tables is not None
    if cache is None:
        def body(carry, layer_params):
            y, _, aux = block_fn(carry, layer_params, cfg, call, None)
            return y, aux

        init, xs = x, blocks
    elif paged:
        def body(carry, layer):
            y, pool = carry
            y, pool, aux = block_fn(y, layer_of(blocks, layer, call.rows), cfg, call, pool, layer)
            return (y, pool), aux

        init = (x, cache)
        xs = layer_index
    else:
        def body(carry, xs):
            layer, ck, cv = xs
            y, new_cache, aux = block_fn(carry, layer_of(blocks, layer, call.rows), cfg, call, (ck, cv))
            return y, (new_cache, aux)

        init, xs = x, (layer_index, cache.k, cache.v)

    if remat:
        body = jax.checkpoint(body)
    out, ys = jax.lax.scan(body, init, xs)
    if cache is None:
        return out, None, jnp.sum(ys)
    if paged:
        return *out, jnp.sum(ys)
    new_k, new_v = ys[0]
    return out, dataclasses.replace(cache, k=new_k, v=new_v), jnp.sum(ys[1])


def layer_runs(cfg: ModelConfig) -> tuple:
    """The hybrid family's layers as runs: ((unit, repeats), ...), a unit a
    tuple of layers (operator kind, FFN kind; None: a block that is its
    operator alone) that repeats ``repeats`` times in the published order.  LFM2-8B-A1B: a convolution layer with a
    dense FFN twice, (attention, conv, conv, conv) with experts four times,
    (attention, conv, conv) with experts twice.  A run that repeats is one
    ``lax.scan`` (:func:`run_layers`), so a program holds each unit's
    kernels once: 44 Pallas calls where 24 unrolled layers hold 116, and
    compiles in a third of the time (PERF.md, PR 28)."""
    seq = list(zip(cfg.layer_types, cfg.ffn_kinds))  # (ffn None: no FFN)
    runs, i = [], 0
    while i < len(seq):
        best = (1, 1)  # (period, repeats) covering the most layers
        for period in range(1, (len(seq) - i) // 2 + 1):
            unit, reps = seq[i: i + period], 1
            while seq[i + reps * period: i + (reps + 1) * period] == unit:
                reps += 1
            if reps > 1 and period * reps > best[0] * best[1]:
                best = (period, reps)
        runs.append((tuple(seq[i: i + best[0]]), best[1]))
        i += best[0] * best[1]
    return tuple(runs)


def run_layers(
    x: jax.Array,
    blocks: Params,  # params["blocks"]: one stack a KIND of layer
    cfg: ModelConfig,
    call: Call,
    cache: kv_cache.HybridCache | None,
) -> tuple[jax.Array, kv_cache.HybridCache | None, jax.Array]:
    """The "hybrid" family's layers (LFM2-MoE), which differ: layer l is
    ``x + op_l(rms(x))`` then ``+ ffn_l(rms(.))`` with op_l a gated short
    convolution or GQA attention (``cfg.layer_types``) and ffn_l a dense
    SwiGLU for the first ``cfg.num_dense_layers`` layers, an expert layer
    after.

    The weights are stacked by KIND (``blocks["conv"]`` [18, ...],
    ``["attn"]`` [6, ...], ``["dense"]`` [2, ...], ``["moe"]`` [22, ...];
    each layer's two norms lie with its operator) and walked in the
    published order as :func:`layer_runs` has it, a repeating run under
    one ``lax.scan`` with the layer's index into each stack computed from
    the iteration.  The expert stacks go into their kernel whole, indexed
    by (layer, expert) (ops/moe_experts.py), and the other quantized
    weights go into theirs the same way (:func:`layer_of`), so no program
    copies or dequantizes a weight; norms and taps are sliced a layer.

    The cache is the scans' carry beside x.  An attention layer reads and
    writes it at its own index among the attention layers: the page pool
    whole (``call.kv_tables``; see :func:`_paged_attention`), a contiguous
    cache by its layer slice.  A convolution layer reads and writes its
    [B, K-1, D] slice of ``cache.conv``.  In a model that mixes windowed
    ("swa") and full ("attn") attention layers both kinds go through
    :func:`mixed_attention`: the full ones hold pages, the windowed ones a
    ring a row in ``cache.ring_k`` / ``ring_v``.  The position-wise half
    of a layer runs ``_TOKEN_BLOCK`` tokens at a time where an admission
    is longer than that.

    Returns (x, cache', stats): stats int32 [4] adds up what the expert
    layers routed for the real tokens of this pass (layers.moe_dropless:
    pairs, layer passes, experts touched, fullest expert's tokens), a
    by-product like the dense families' aux loss and no part of the
    state."""
    moe = jnp.zeros((4 if cfg.experts_held is None else 6,), jnp.int32)
    if cfg.ret_layers:  # the retention layers' counts ride there instead
        moe = retention_counts(cfg, call, x.shape[:2])
    shape = x.shape[:2]
    rows, token_mask, paged = (
        call.rows, call.token_mask, call.kv_tables is not None)

    def layer(carry, op, ffn, at):
        """One layer; ``at`` its index into each kind's stack."""
        x, cache, moe = carry
        p = layer_of(blocks[op], at[op], rows)
        route = None
        if ffn == "moe" and cfg.moe_router_input == "block_input":
            # The router reads what the block read, before its norm and
            # its operator: the layer's routing is known before attention
            # runs, and the logits ride to the expert layer below.
            with jax.named_scope("moe_route"):
                route = layers.router_logits(
                    x, blocks["moe"]["router"][at[ffn]])
        h = layers.rms_norm(x, p["ln1"]["scale"], cfg.norm_eps)
        if op == "conv":
            out, new = layers.short_conv(
                h, p, None if cache is None else cache.conv[at[op]],
                call.seq_lens)
            if cache is not None:
                cache = dataclasses.replace(cache, conv=cache.conv.at[
                    at[op]].set(new.astype(cache.conv.dtype)))
        elif op == "ret":
            out, cache = retention_layer(h, p, cfg, call, cache, at[op])
        elif op == "ssm":
            out, cache = ssm_layer(h, p, cfg, call, cache, at[op])
        elif op == "gdn":
            out, cache = gdn_layer(h, p, cfg, call, cache, at[op])
        elif cfg.swa_layers:  # windowed and full attention layers mixed
            with jax.named_scope("swa_attn" if op == "swa" else "full_attn"):
                out, cache = mixed_attention(
                    h, p, cfg, op, call, cache, at[op])
        elif op == "mla":
            ai = at[op]
            if cache is None or paged:
                layer_cache = cache
            else:
                layer_cache = cache.k[ai]
            out, new = mla_attention(h, p, cfg, call, layer_cache, ai)
            if paged:
                cache = new
            elif cache is not None:
                cache = dataclasses.replace(cache, k=cache.k.at[ai].set(new))
        else:
            ai = at[op]
            if cache is None:
                layer_cache = None
            elif paged:
                layer_cache = cache
            else:
                layer_cache = (cache.k[ai], cache.v[ai])
            out, new = _attention(
                h, p, cfg, cfg.attn_rope, call, layer_cache, ai)
            if paged:
                cache = new
            elif cache is not None:
                cache = dataclasses.replace(
                    cache, k=cache.k.at[ai].set(new[0]),
                    v=cache.v.at[ai].set(new[1]))
        x = x + out
        if ffn is None:  # a block that is its operator alone
            return x, cache, moe
        h = layers.rms_norm(x, p["ln2"]["scale"], cfg.norm_eps)

        def add_ffn(x, h, mask, rows, route=None):
            """x + ffn(h) and the expert layer's counts (zeros: dense);
            ``route``: the router's logits where it read the block's
            input."""
            if ffn != "moe":
                return x + layers.mlp_swiglu(
                    h, layer_of(blocks["dense"], at[ffn], rows), cfg.gate_act
                ), jnp.zeros_like(moe)
            y, stats = layers.moe_dropless(
                h, blocks["moe"], cfg, mask, layer=at[ffn], logits=route)
            x = x + y
            if cfg.n_shared_experts:
                shared = layer_of(blocks["moe"]["shared"], at[ffn], rows)
                with jax.named_scope("shared_expert"):
                    y = (layers.mlp_plain(h, shared, cfg.gate_act)
                         if cfg.moe_latent_size
                         else layers.mlp_swiglu(h, shared, cfg.gate_act))
                    if cfg.moe_shared_gate:  # one scalar a token
                        y = (y * jax.nn.sigmoid(jnp.einsum(
                            "btd,d->bt", h,
                            blocks["moe"]["shared_gate"][at[ffn]].astype(
                                h.dtype), preferred_element_type=jnp.float32
                        ))[..., None]).astype(y.dtype)
                    x = x + y
            return x, stats

        b, t, d = x.shape
        n = t // _TOKEN_BLOCK
        if n < 2 or t % _TOKEN_BLOCK:
            x, stats = add_ffn(x, h, token_mask, rows, route)
            return x, cache, moe + stats
        # A long admission: the position-wise part a block of tokens at a
        # time, so that its temporaries (the dense layer's 18,432 columns,
        # the grouped list of token x k pairs) do not grow with the
        # bucket.  A token sees the same arithmetic; an expert layer's
        # counts add up over the blocks, each a pass of its own, and block
        # i holds what is left of the real rows after i blocks.
        mask = (jnp.ones((b, t), bool) if token_mask is None else token_mask)
        left = None if rows is None else jnp.clip(
            rows - _TOKEN_BLOCK * jnp.arange(n, dtype=jnp.int32)[:, None],
            0, _TOKEN_BLOCK)  # [n, 1]
        def cut(a):  # [b, t, ..] -> [n, b, _TOKEN_BLOCK, ..]; None: None
            return None if a is None else jnp.moveaxis(
                a.reshape(b, n, _TOKEN_BLOCK, *a.shape[2:]), 1, 0)
        x, stats = jax.lax.map(lambda a: add_ffn(*a), (
            cut(x), cut(h), cut(mask), left, cut(route)))
        return (jnp.moveaxis(x, 0, 1).reshape(b, t, d), cache,
                moe + jnp.sum(stats, axis=0))

    carry = (x, cache, moe)
    base = dict(conv=0, attn=0, swa=0, mla=0, ret=0, ssm=0, gdn=0, dense=0,
                moe=0)
    for unit, reps in layer_runs(cfg):
        kinds = [kind for pair in unit for kind in pair if kind]
        per_unit = {kind: kinds.count(kind) for kind in base}

        def run(carry, rep, unit=unit, per_unit=per_unit, base=dict(base)):
            seen = dict.fromkeys(base, 0)
            for op, ffn in unit:
                at = {kind: base[kind] + rep * per_unit[kind] + seen[kind]
                      for kind in (op, ffn) if kind}
                carry = layer(carry, op, ffn, at)
                for kind in at:
                    seen[kind] += 1
            return carry, None

        if reps == 1:
            carry, _ = run(carry, 0)
        else:
            carry, _ = jax.lax.scan(
                run, carry, jnp.arange(reps, dtype=jnp.int32))
        for kind in base:
            base[kind] += reps * per_unit[kind]
    if cfg.ssm_layers or cfg.gdn_layers:  # (a scan's counts ride behind
        # the experts')
        x, cache, moe = carry
        carry = (x, cache, jnp.concatenate(
            [moe, ssm_counts(cfg, call, shape)]))
    return carry


def hybrid_layers(params: Params, cfg: ModelConfig):
    """``params`` of a hybrid model, a layer at a time in the published
    order: dicts {"ln1", "ln2": {"scale"}, "conv" | "attn": {...}, "mlp":
    {...}} as models/reference/lfm2_moe.py reads them.  A generator, so a
    caller that dequantizes what it is handed holds one layer in float32."""
    at = dict(conv=0, attn=0, swa=0, mla=0, ret=0, ssm=0, gdn=0, dense=0,
              moe=0)
    blocks = params["blocks"]

    def take(kind):
        out = jax.tree.map(lambda a: a[at[kind]], blocks[kind])
        at[kind] += 1
        return out

    for op, ffn in zip(cfg.layer_types, cfg.ffn_kinds):
        p = take(op)
        yield {"ln1": p.pop("ln1"), "ln2": p.pop("ln2"), op: p,
               "mlp": take(ffn) if ffn else None}


# ---------------------------------------------------------------------------
# Full model forward
# ---------------------------------------------------------------------------

def embed(params: Params, cfg: ModelConfig, tokens: jax.Array, positions: jax.Array) -> jax.Array:
    x = jnp.take(params["embed"]["wte"], tokens, axis=0)
    if cfg.family in ("gpt2", "opt"):
        # OPT's learned position table carries HF's historical offset of 2
        # (OPTLearnedPositionalEmbedding); the converted table keeps it.
        off = 2 if cfg.family == "opt" else 0
        x = x + jnp.take(params["embed"]["wpe"], positions + off, axis=0)
    x = x.astype(jnp.dtype(cfg.dtype))
    if cfg.embed_scale != 1.0:
        # Gemma scales embeddings by sqrt(hidden) in the compute dtype
        # (HF casts the normalizer to hidden_states.dtype before the mul).
        x = x * jnp.asarray(cfg.embed_scale, x.dtype)
    return x


def hidden_at(x: jax.Array, at: jax.Array | None) -> jax.Array:
    """Row b's hidden state at position ``at[b]`` alone, [B, 1, D], before
    the final norm and the head (forward's ``logits_at``); None: all of
    x."""
    if at is None:
        return x
    return jnp.take_along_axis(
        x, at.astype(jnp.int32)[:, None, None], axis=1)


@jax.named_scope("head")  # final norm + lm head
def unembed(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.family in ("gpt2", "opt", "neox"):
        x = layers.layer_norm(x, params["final_norm"]["scale"], params["final_norm"]["bias"], cfg.norm_eps)
    else:
        x = layers.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    if cfg.tie_embeddings:
        w = params["embed"]["wte"].T  # [D, V]
    else:
        w = params["lm_head"]["w"]
    return jnp.einsum(
        "btd,dv->btv", x, w.astype(x.dtype), preferred_element_type=jnp.float32
    )


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # [B, T] int32
    positions: jax.Array | None = None,  # [B, T] int32; None: the arange
    #   behind ``cache_index``.  This and the five marked (*) are the call's
    #   facts: :class:`Call` says what each is and what kind they make
    cache: kv_cache.KVCache | None = None,
    cache_index: jax.Array | None = None,  # (*) scalar or [B] write offset
    remat: bool = False,
    attn_mask: jax.Array | None = None,  # (*)
    return_aux: bool = False,  # also return the expert layers' by-product:
    #   the load-balance aux loss; for the hybrid family, which is not
    #   trained, its routing counts int32 [4] (run_layers)
    kv_tables: jax.Array | None = None,  # (*) the cache is a page pool
    key_positions: jax.Array | None = None,  # (*)
    seq_lens: jax.Array | None = None,  # (*) [B] int32: how many of the T
    #   tokens of each row are real (right-padded input; 0 for a batch row
    #   that is not decoding).  Only a model with state that is not keys and
    #   values needs it (family "hybrid": layers.short_conv,
    #   layers.moe_dropless); every family hands a lone row's count to the
    #   quantized matmuls (``Call.rows``), and nothing else of it.  None
    #   means all T
    logits_at: jax.Array | None = None,  # [B] int32: the ONE position of
    #   each row whose logits the caller wants (an admission samples its
    #   first token from the last real position and nothing else): the
    #   final norm and the head then read that position's hidden state
    #   alone and the logits are [B, 1, V], so the head's float32
    #   temporaries do not grow with T (16,384 x 151,936 x 4 B otherwise)
) -> tuple[jax.Array, Any] | tuple[jax.Array, Any, jax.Array]:
    """Full forward.  Returns (logits [B, T, V] float32, updated cache), plus
    the summed MoE aux loss when ``return_aux`` (scale by
    cfg.moe_aux_loss_weight and add to the task loss when training MoE;
    the hybrid family hands out its routing counts there instead).

    Contract: ``cache_index + T`` must not exceed ``cache.max_len`` — XLA's
    ``dynamic_update_slice`` clamps out-of-range starts, which would silently
    overwrite the last cache slot.  The decode loop in runtime/ enforces this
    statically (max_decode_steps + prompt_len <= max_seq_len)."""
    call = call_of(tokens.shape, positions, cache_index, attn_mask,
                   key_positions, kv_tables, seq_lens, cache is not None)
    x = embed(params, cfg, tokens, call.positions)
    if cfg.family == "hybrid":
        if isinstance(cache, kv_cache.QuantKVCache) or remat:
            raise ValueError(
                "the hybrid family serves a full-width HybridCache and is "
                "not trained: no int8 pool, no remat"
            )
        x, cache, stats = run_layers(x, params["blocks"], cfg, call, cache)
        out = (unembed(params, cfg, hidden_at(x, logits_at)), cache)
        return (*out, stats) if return_aux else out
    if isinstance(cache, kv_cache.QuantKVCache) and kv_tables is None:
        # Int8 page pool: decode-only (the per-step quantized write and the
        # scale-fused attention read both live on the kv_tables path).
        raise ValueError(
            "QuantKVCache serves paged decode only (pass kv_tables); "
            "prefill runs against full-width transient rows"
        )
    x, cache, aux = run_blocks(x, params["blocks"], cfg, call, cache, remat)
    out = (unembed(params, cfg, hidden_at(x, logits_at)), cache)
    return (*out, aux) if return_aux else out


# ---------------------------------------------------------------------------
# Random init (tests, benchmarks; real weights come from checkpoint/)
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: ModelConfig, dtype: Any = None) -> Params:
    dtype = dtype or jnp.dtype(cfg.dtype)
    L, D, F = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    H, KVH, HD = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    keys = iter(jax.random.split(rng, 32))

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * (fan_in**-0.5)).astype(dtype)

    params: Params = {
        "embed": {"wte": dense(next(keys), (cfg.vocab_size, D), D)},
        "final_norm": {"scale": jnp.ones((D,), dtype)},
    }
    if cfg.family in ("gpt2", "opt", "neox"):
        if cfg.family != "neox":  # neox uses rotary, not a position table
            pos_rows = cfg.max_seq_len + (2 if cfg.family == "opt" else 0)
            params["embed"]["wpe"] = dense(next(keys), (pos_rows, D), D)
        params["final_norm"]["bias"] = jnp.zeros((D,), dtype)
        params["blocks"] = {
            "ln1": {"scale": jnp.ones((L, D), dtype), "bias": jnp.zeros((L, D), dtype)},
            "ln2": {"scale": jnp.ones((L, D), dtype), "bias": jnp.zeros((L, D), dtype)},
            "attn": {
                "wq": dense(next(keys), (L, D, H, HD), D),
                "wk": dense(next(keys), (L, D, KVH, HD), D),
                "wv": dense(next(keys), (L, D, KVH, HD), D),
                "wo": dense(next(keys), (L, H, HD, D), H * HD),
                "bq": jnp.zeros((L, H, HD), dtype),
                "bk": jnp.zeros((L, KVH, HD), dtype),
                "bv": jnp.zeros((L, KVH, HD), dtype),
                "bo": jnp.zeros((L, D), dtype),
            },
            "mlp": {
                "w_in": dense(next(keys), (L, D, F), D),
                "b_in": jnp.zeros((L, F), dtype),
                "w_out": dense(next(keys), (L, F, D), F),
                "b_out": jnp.zeros((L, D), dtype),
            },
        }
    elif cfg.family == "llama":
        if cfg.num_experts > 0:
            E = cfg.num_experts
            mlp = {
                "router": dense(next(keys), (L, D, E), D),
                "w_gate": dense(next(keys), (L, E, D, F), D),
                "w_up": dense(next(keys), (L, E, D, F), D),
                "w_down": dense(next(keys), (L, E, F, D), F),
            }
        else:
            mlp = {
                "w_gate": dense(next(keys), (L, D, F), D),
                "w_up": dense(next(keys), (L, D, F), D),
                "w_down": dense(next(keys), (L, F, D), F),
            }
        attn = {
            "wq": dense(next(keys), (L, D, H, HD), D),
            "wk": dense(next(keys), (L, D, KVH, HD), D),
            "wv": dense(next(keys), (L, D, KVH, HD), D),
            "wo": dense(next(keys), (L, H, HD, D), H * HD),
        }
        if cfg.qkv_bias:  # Qwen2-style llama blocks
            attn["bq"] = jnp.zeros((L, H, HD), dtype)
            attn["bk"] = jnp.zeros((L, KVH, HD), dtype)
            attn["bv"] = jnp.zeros((L, KVH, HD), dtype)
        params["blocks"] = {
            "ln1": {"scale": jnp.ones((L, D), dtype)},
            "ln2": {"scale": jnp.ones((L, D), dtype)},
            "attn": attn,
            "mlp": mlp,
        }
    elif cfg.family == "hybrid":
        params["blocks"] = _init_hybrid_blocks(rng, cfg, dtype)
    else:
        raise ValueError(f"unknown family {cfg.family!r}")
    if cfg.num_experts > 0 and cfg.family not in ("llama", "hybrid"):
        raise ValueError("MoE (num_experts > 0) is supported for the llama family")
    if cfg.family == "neox" and cfg.tie_embeddings:
        raise ValueError("neox checkpoints untie embeddings (embed_out)")
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": dense(next(keys), (D, cfg.vocab_size), D)}
    return params


def hybrid_fan_in(name: str, shape: tuple) -> int:
    """Fan-in of a hybrid-family weight leaf [L, ...], by its path: what
    the random init scales by (shared by :func:`init_params` and
    :func:`init_params_quantized`, so that both draw at one scale)."""
    parts = name.split("/")
    if parts[-1] == "wo" and len(shape) == 4:  # [L, H, hd, D]
        return shape[1] * shape[2]
    if parts[-1] == "taps":  # [L, D, K]
        return shape[2]
    if "experts" in parts:  # [L, E, K, N]
        return shape[2]
    return shape[1]


# A state-space layer's leaves that are drawn by a rule of their own.
SSM_LEAVES = ("A_log", "dt_bias", "D", "conv_bias")


def ssm_leaf(key: jax.Array, leaf: str, shape: tuple, dtype: Any) -> jax.Array:
    """A state-space layer's small leaves as the published initialiser draws
    them: ``A`` uniform in [1, 16] a head (``A_log`` its log), ``dt`` log-
    uniform in [0.001, 0.1] and ``dt_bias`` its inverse softplus, so that a
    head forgets over tens to hundreds of tokens and a state kept at less
    than float32 shows; ``D`` ones; the convolution's bias N(0, 0.1) (a
    trained model's is learned; zeros would leave it unread)."""
    if leaf == "D":
        return jnp.ones(shape, dtype)
    if leaf == "conv_bias":
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    u = jax.random.uniform(key, shape, jnp.float32)
    if leaf == "A_log":
        return jnp.log(1.0 + 15.0 * u).astype(dtype)
    dt = jnp.exp(u * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _init_hybrid_blocks(rng: jax.Array, cfg: ModelConfig, dtype: Any) -> Params:
    """One stack a kind of layer (models.model.run_layers): ``conv``,
    ``attn`` and ``swa`` (each with its layers' two norms; the windowed
    attention layers' weights have the full ones' shapes), ``dense`` for
    the first
    ``cfg.num_dense_layers`` FFNs and ``moe`` (router + experts) for the
    rest.  The router and the selection bias are float32 whatever the
    model's dtype (the scores pick the experts); ``expert_bias`` is drawn
    N(0, 0.1) so that it changes the chosen set (a trained model's is
    learned)."""
    D, F, FE = cfg.hidden_size, cfg.intermediate_size, cfg.expert_size
    H, KVH, HD = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    E = cfg.num_experts
    NC, NA = len(cfg.conv_layers), len(cfg.attn_layers)
    ND, NM = cfg.ffn_kinds.count("dense"), cfg.ffn_kinds.count("moe")

    def dense(name, shape, dt=dtype):
        full = f"blocks/{name}"
        key = jax.random.fold_in(rng, zlib.crc32(full.encode()))
        scale = hybrid_fan_in(full, shape) ** -0.5
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dt)

    def norms(n):
        return {"ln1": {"scale": jnp.ones((n, D), dtype)},
                "ln2": {"scale": jnp.ones((n, D), dtype)}}

    blocks: Params = {
        "dense": {
            "w_gate": dense("dense/w_gate", (ND, D, F)),
            "w_up": dense("dense/w_up", (ND, D, F)),
            "w_down": dense("dense/w_down", (ND, F, D)),
        },
    }
    if NC:
        blocks["conv"] = {
            **norms(NC),
            "in_proj": dense("conv/in_proj", (NC, D, 3 * D)),
            "taps": dense("conv/taps", (NC, D, cfg.conv_kernel)),
            "out_proj": dense("conv/out_proj", (NC, D, D)),
        }
    if cfg.kv_lora_rank:
        # Latent attention (models.model.mla_attention).  Every leaf a
        # matrix, the head axes flat.  W_kva (its 576 columns are not whole
        # 128-wide blocks) and W_kvb (read in two orientations: expanded by
        # an admission, absorbed by a decode step) stay in the model's
        # dtype (checkpoint.quantize), 25 MB a layer at A.X-K1's widths.
        QR, R = cfg.q_lora_rank, cfg.kv_lora_rank
        DN, DR, DV = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        blocks["mla"] = {
            **norms(NA),
            "wq_a": dense("mla/wq_a", (NA, D, QR)),
            "q_norm": jnp.ones((NA, QR), dtype),
            "wq_b": dense("mla/wq_b", (NA, QR, H * (DN + DR))),
            "wkv_a": dense("mla/wkv_a", (NA, D, R + DR)),
            "kv_norm": jnp.ones((NA, R), dtype),
            "wkv_b": dense("mla/wkv_b", (NA, R, H * (DN + DV))),
            "wo": dense("mla/wo", (NA, H * DV, D)),
        }
    for kind, n in (("attn", 0 if cfg.kv_lora_rank else NA),
                    ("swa", len(cfg.swa_layers)),
                    ("ret", len(cfg.ret_layers))):
        if not n:
            continue
        blocks[kind] = {
            **norms(n),
            # [D, H * hd], the head axes flat: quantized with their own
            # axis last, heads of 64 would get absmax blocks of 64, which
            # the fused kernel cannot tile (layers.qkv_project unflattens).
            # (a gated attention layer's: [query | gate] a head)
            "wq": dense(f"{kind}/wq", (n, D, H * HD * (
                2 if kind == "attn" and cfg.attn_out_gate else 1))),
            "wk": dense(f"{kind}/wk", (n, D, KVH * HD)),
            "wv": dense(f"{kind}/wv", (n, D, KVH * HD)),
            "wo": dense(f"{kind}/wo", (n, H, HD, D)),
        }
        if cfg.qk_norm:
            blocks[kind]["q_norm"] = jnp.ones((n, HD), dtype)
            blocks[kind]["k_norm"] = jnp.ones((n, HD), dtype)
        if kind == "ret":  # the gate: a scalar a token a key/value head,
            # in the model's dtype (checkpoint.quantize leaves it float)
            blocks[kind]["wg"] = dense("ret/wg", (n, D, KVH))
    if cfg.ssm_layers:
        NS, W = len(cfg.ssm_layers), cfg.ssm_conv_width
        NH, IN = cfg.ssm_heads, cfg.ssm_inner
        blocks["ssm"] = {
            **norms(NS),
            # [z | x B C | dt], in that order
            "in_proj": dense("ssm/in_proj", (NS, D, IN + W + NH)),
            "taps": dense("ssm/taps", (NS, W, cfg.ssm_conv_kernel)),
            "out_proj": dense("ssm/out_proj", (NS, IN, D)),
            "norm_w": jnp.ones((NS, IN), dtype),
            **{leaf: ssm_leaf(
                jax.random.fold_in(rng, zlib.crc32(f"blocks/ssm/{leaf}".encode())),
                leaf, (NS, W if leaf == "conv_bias" else NH),
                dtype if leaf == "conv_bias" else jnp.float32)
               for leaf in SSM_LEAVES},
        }
    if cfg.gdn_layers:
        NG, W = len(cfg.gdn_layers), cfg.gdn_conv_width
        VW, HV = cfg.gdn_value_width, cfg.gdn_value_heads
        blocks["gdn"] = {
            **norms(NG),
            # [q | k | v | z] and [b | a], in that order, each flat by head
            "w_qkvz": dense("gdn/w_qkvz", (NG, D, W + VW)),
            "w_ba": dense("gdn/w_ba", (NG, D, 2 * HV)),
            "taps": dense("gdn/taps", (NG, W, cfg.gdn_conv_kernel)),
            "out_proj": dense("gdn/out_proj", (NG, VW, D)),
            "norm_w": jnp.ones((NG, cfg.gdn_value_dim), dtype),
            **{leaf: ssm_leaf(
                jax.random.fold_in(rng, zlib.crc32(f"blocks/gdn/{leaf}".encode())),
                leaf, (NG, HV), jnp.float32)
               for leaf in ("A_log", "dt_bias")},
        }
    if NM:
        EH = cfg.held_experts  # (a chip's share; the router scores all E)
        LAT = cfg.moe_latent_size
        blocks["moe"] = {
            "router": dense("moe/router", (NM, D, E), jnp.float32),
            "experts": {
                "w_gate_up": dense("moe/experts/w_gate_up", (NM, EH, D, 2 * FE)),
                "w_down": dense("moe/experts/w_down", (NM, EH, FE, D)),
            } if not LAT else {  # two matrices an expert, on the latent
                "w_up": dense("moe/experts/w_up", (NM, EH, LAT, FE)),
                "w_down": dense("moe/experts/w_down", (NM, EH, FE, LAT)),
            },
        }
        if LAT:
            blocks["moe"]["latent"] = {
                "w_dn": dense("moe/latent/w_dn", (NM, D, LAT)),
                "w_up": dense("moe/latent/w_up", (NM, LAT, D)),
            }
        if cfg.n_shared_experts:
            FS = cfg.shared_size
            blocks["moe"]["shared"] = {
                "w_gate": dense("moe/shared/w_gate", (NM, D, FS)),
                "w_up": dense("moe/shared/w_up", (NM, D, FS)),
                "w_down": dense("moe/shared/w_down", (NM, FS, D)),
            } if not LAT else {
                "w_up": dense("moe/shared/w_up", (NM, D, FS)),
                "w_down": dense("moe/shared/w_down", (NM, FS, D)),
            }
        if cfg.moe_shared_gate:  # w_s: a scalar a token, in the model's dtype
            blocks["moe"]["shared_gate"] = dense("moe/shared_gate", (NM, D))
        if cfg.moe_expert_bias:
            key = jax.random.fold_in(
                rng, zlib.crc32(b"blocks/moe/expert_bias"))
            blocks["moe"]["expert_bias"] = 0.1 * jax.random.normal(
                key, (NM, E), jnp.float32)
    return blocks


def init_params_quantized(
    rng: jax.Array, cfg: ModelConfig, bits: int, mesh: Any = None
) -> Params:
    """Seeded random params with the decoder-block matmul weights born
    int8/int4 (``QuantizedTensor`` leaves), built on the device leaf by
    leaf: what a full-width preset needs to reach one chip.

    :func:`init_params` draws each stacked leaf whole in float32 before
    casting (a 7.6 GB transient for one 7B MLP leaf) and the finished bf16
    tree may not fit at all.  Here every leaf is one jitted program that
    walks the layer axis with ``lax.map``, so the float32 transient is a
    single layer, and with ``mesh`` the leaf is born under
    parallel.specs.param_specs' sharding — no leaf ever sits whole on
    device 0.  Same tree, shapes, dtypes, fan-in scaling and
    quantization plan (checkpoint.quantize.leaf_plan) as
    ``quantize_tree(init_params(...)["blocks"])``; the values differ (one
    folded key per leaf and layer) and, the threefry generator being
    partitionable, do not depend on the mesh."""
    from ..checkpoint import quantize as quant_lib

    shapes = jax.eval_shape(lambda k: init_params(k, cfg), rng)
    specs = None
    if mesh is not None:
        from jax.sharding import NamedSharding

        from ..parallel import api as parallel_api
        from ..parallel import specs as specs_lib

        specs = specs_lib.param_specs(cfg, mesh)
    fan_ins = {
        "wo": cfg.num_heads * cfg.head_dim_,
        "w_out": cfg.intermediate_size, "w_down": cfg.intermediate_size,
    }

    def build(path, sd, spec=None):
        name = "/".join(str(p.key) for p in path)
        leaf = path[-1].key
        key = jax.random.fold_in(rng, zlib.crc32(name.encode()))
        hybrid = cfg.family == "hybrid" and name.startswith("blocks/")
        scale = (hybrid_fan_in(name, sd.shape) if hybrid and sd.ndim > 2
                 else fan_ins.get(leaf, cfg.hidden_size)) ** -0.5
        should, k_axes, n_axes = quant_lib.leaf_plan(name, sd)
        quant = should and name.startswith("blocks/")
        quantize = functools.partial(
            quant_lib.quantize, bits=bits, k_axes=k_axes, n_axes=n_axes,
            block_axis=quant_lib.block_axis_of(name))
        stacked = jax.eval_shape(quantize, sd) if quant else None
        repeat = 1
        sharding = None
        if spec is not None:
            sharding = NamedSharding(mesh, spec)
            if quant:
                data_spec, scale_spec, repeat = parallel_api.quantized_layout(
                    stacked, spec, mesh, name)
                sharding = (NamedSharding(mesh, data_spec),
                            NamedSharding(mesh, scale_spec))

        def dense(k, shape):
            return jax.random.normal(k, shape, jnp.float32) * scale

        def one_layer(i):
            x = dense(jax.random.fold_in(key, i), sd.shape[1:])
            if not quant:
                return x.astype(sd.dtype)
            qt = quantize(x)
            return qt.data, jnp.repeat(qt.scale, repeat, axis=-2)

        def gen():
            if leaf in ("scale", "q_norm", "k_norm", "kv_norm", "norm_w"):
                return jnp.ones(sd.shape, sd.dtype)
            if leaf in SSM_LEAVES and name.startswith(
                    ("blocks/ssm/", "blocks/gdn/")):
                return ssm_leaf(key, leaf, sd.shape, sd.dtype)
            if leaf == "expert_bias":  # drawn, so that it changes the choice
                return 0.1 * jax.random.normal(key, sd.shape, sd.dtype)
            if leaf.startswith("b"):  # bias, bq/bk/bv/bo, b_in/b_out
                return jnp.zeros(sd.shape, sd.dtype)
            if name.startswith("blocks/"):
                return jax.lax.map(one_layer, jnp.arange(sd.shape[0]))
            return dense(key, sd.shape).astype(sd.dtype)

        out = jax.jit(gen, out_shardings=sharding)()
        if not quant:
            return out
        return dataclasses.replace(stacked, data=out[0], scale=out[1])

    if specs is None:
        return jax.tree_util.tree_map_with_path(build, shapes)
    return jax.tree_util.tree_map_with_path(build, shapes, specs)


def count_params(params: Params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))
