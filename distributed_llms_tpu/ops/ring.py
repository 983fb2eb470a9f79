"""Ring attention: sequence/context parallelism over a ``seq`` mesh axis.

Net-new capability relative to the reference, which has no sequence
parallelism of any kind (SURVEY §5.7: grep for ring/context/sequence over the
tree finds nothing; sequence length is never even a parameter —
snippets.md:633's dummy ``(1, 768)`` input is the only sequence notion).

Design (blockwise attention with rotating KV, scaling-book style):

- the sequence axis of Q/K/V is sharded over the ``seq`` mesh axis inside
  ``shard_map``; each device owns one contiguous sequence block;
- K/V (plus their global positions) rotate one hop around the ring per step
  via ``lax.ppermute`` over ICI, for ``seq`` steps total;
- each device accumulates attention over the visiting KV blocks with a
  numerically-stable *online softmax* (running max / numerator / denominator,
  exactly the flash-attention recurrence), so the full [Tq, Tk] score matrix
  never materializes;
- causality falls out of masking on *global positions* carried with the
  rotating KV block — no per-step index arithmetic, and fully-masked blocks
  contribute exp(-inf)=0 without NaNs;
- the ppermute is issued before the block compute consumes it on the next
  scan iteration, letting XLA overlap the hop with local attention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _block_scores(
    q: jax.Array,  # [B, Tq, H, D]
    k: jax.Array,  # [B, Tk, KVH, D]
    q_pos: jax.Array,  # [B, Tq]
    k_pos: jax.Array,  # [B, Tk]
    k_valid: jax.Array,  # [B, Tk] bool
    causal: bool,
    q_per_kv: int,
) -> jax.Array:
    """Masked f32 logits [B, H, Tq, Tk] for one KV block (GQA-aware)."""
    scale = q.shape[-1] ** -0.5
    if q_per_kv > 1:
        b, tq, h, d = q.shape
        qg = q.reshape(b, tq, h // q_per_kv, q_per_kv, d)
        logits = jnp.einsum(
            "bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32
        ).reshape(b, h, tq, k.shape[1])
    else:
        logits = jnp.einsum("bqhd,bshd->bhqs", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    mask = k_valid[:, None, None, :]
    if causal:
        mask = jnp.logical_and(mask, k_pos[:, None, None, :] <= q_pos[:, None, :, None])
    return jnp.where(mask, logits, _NEG_INF)


def _block_pv(probs: jax.Array, v: jax.Array, q_per_kv: int) -> jax.Array:
    """probs [B, H, Tq, Tk] @ v [B, Tk, KVH, D] -> [B, Tq, H, D] (GQA-aware)."""
    if q_per_kv > 1:
        b, h, tq, tk = probs.shape
        pg = probs.reshape(b, h // q_per_kv, q_per_kv, tq, tk)
        out = jnp.einsum("bkgqs,bskd->bqkgd", pg.astype(v.dtype), v)
        return out.reshape(b, tq, h, v.shape[-1])
    return jnp.einsum("bhqs,bshd->bqhd", probs.astype(v.dtype), v)


def ring_attention(
    q: jax.Array,  # [B, Tq_local, H, D]   — local sequence block
    k: jax.Array,  # [B, Tk_local, KVH, D]
    v: jax.Array,  # [B, Tk_local, KVH, D]
    q_positions: jax.Array,  # [B, Tq_local] global positions
    k_positions: jax.Array,  # [B, Tk_local] global positions
    axis_name: str = "seq",
    causal: bool = True,
    k_valid: jax.Array | None = None,  # [B, Tk_local] bool
) -> jax.Array:
    """Ring attention body — call *inside* ``shard_map`` with the sequence
    axis sharded over ``axis_name``.  Returns [B, Tq_local, H, D].

    Works for any KVH dividing H (grouped-query attention); the score matrix
    per step is only [B, H, Tq/S, Tk/S].
    """
    try:
        num_blocks = jax.lax.axis_size(axis_name)
    except NameError as e:
        raise RuntimeError(
            f"ring attention needs a bound {axis_name!r} mesh axis — call it "
            "inside shard_map (e.g. via ParallelModel with MeshConfig(seq=N)); "
            "attn_impl='ring' is set internally by that path, not by user config"
        ) from e
    q_per_kv = q.shape[2] // k.shape[2]
    b, tq, h, d = q.shape
    if k_valid is None:
        # Freshly created => not device-varying over the ring axis yet; mark
        # it so the rotating scan carry has consistent vma types.
        k_valid = jax.lax.pcast(
            jnp.ones(k_positions.shape, dtype=bool), (axis_name,), to="varying"
        )

    perm = [(i, (i + 1) % num_blocks) for i in range(num_blocks)]

    def accumulate(acc, k_blk, v_blk, kpos_blk, kvalid_blk):
        num, den, mx = acc
        logits = _block_scores(q, k_blk, q_positions, kpos_blk, kvalid_blk, causal, q_per_kv)
        blk_max = jnp.max(logits, axis=-1)  # [B, H, Tq]
        new_max = jnp.maximum(mx, blk_max)
        # Rows where every block so far is masked have new_max == _NEG_INF
        # (finite finfo.min, not -inf): subtracting it verbatim would give
        # exp(0)=1 on masked entries.  Substitute 0 so those rows underflow
        # to exp(_NEG_INF) = 0 and contribute nothing.
        safe_max = jnp.where(new_max <= _NEG_INF * 0.5, 0.0, new_max)
        probs = jnp.exp(logits - safe_max[..., None])
        alpha = jnp.exp(mx - safe_max)  # rescale old accumulators (0 while mx unseeded)
        num = num * alpha[..., None].transpose(0, 2, 1, 3) + _block_pv(
            probs, v_blk, q_per_kv
        ).astype(jnp.float32)
        den = den * alpha + jnp.sum(probs, axis=-1)
        return num, den, new_max

    def step(carry, _):
        # Rotate first, then accumulate: the local block's contribution is
        # peeled off before the scan, so only num_blocks-1 hops are issued —
        # no discarded final ppermute.  XLA overlaps the hop with compute.
        k_blk, v_blk, kpos_blk, kvalid_blk, *acc = carry
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        kpos_blk = jax.lax.ppermute(kpos_blk, axis_name, perm)
        kvalid_blk = jax.lax.ppermute(kvalid_blk, axis_name, perm)
        acc = accumulate(tuple(acc), k_blk, v_blk, kpos_blk, kvalid_blk)
        return (k_blk, v_blk, kpos_blk, kvalid_blk, *acc), None

    # Accumulators are device-varying over the ring axis (vma tracking).
    varying = lambda x: jax.lax.pcast(x, (axis_name,), to="varying")
    num0 = varying(jnp.zeros((b, tq, h, d), jnp.float32))
    den0 = varying(jnp.zeros((b, h, tq), jnp.float32))
    max0 = varying(jnp.full((b, h, tq), _NEG_INF, jnp.float32))
    acc = accumulate((num0, den0, max0), k, v, k_positions, k_valid)
    carry = (k, v, k_positions, k_valid, *acc)
    (_, _, _, _, num, den, _), _ = jax.lax.scan(
        step, carry, None, length=num_blocks - 1
    )
    den = den.transpose(0, 2, 1)[..., None]  # [B, Tq, H, 1]
    out = num / jnp.maximum(den, 1e-37)
    return out.astype(q.dtype)


def seq_cached_decode_attention(
    q: jax.Array,  # [B, 1, H, D] — replicated over the seq axis
    ck_local: jax.Array,  # [B, S_loc, KVH, D] — this device's prefill KV block
    cv_local: jax.Array,
    dk: jax.Array,  # [B, N, KVH, D] — decode-region KV, replicated
    dv: jax.Array,
    mask_local: jax.Array,  # [B, S_loc] bool — this device's slice of the key mask
    mask_dec: jax.Array,  # [B, N] bool
    axis_name: str = "seq",
) -> jax.Array:
    """Single-token decode over a sequence-sharded KV cache (long-context
    generation, SURVEY §5.7 — the part ring prefill alone leaves open).

    Decode inverts ring attention's economics: the query is one token, so
    rotating KV blocks would move O(S) bytes to meet O(1) queries.  Instead
    the KV stays put: every device computes flash-style partial softmax stats
    (max / numerator / denominator) over its resident block, and one psum
    over ``axis_name`` merges them — the only collective in the step.  The
    decode region (tokens generated after prefill) is replicated on every
    device — it is bounded by max_new_tokens, a sliver next to a long
    prompt — so its stats merge locally with no ownership bookkeeping.

    Returns [B, 1, H, D], identical on every device of the seq axis.
    """
    q_per_kv = q.shape[2] // ck_local.shape[2]

    def stats(k_blk, v_blk, valid):
        logits = _block_scores(
            q, k_blk, q, k_blk, valid, causal=False, q_per_kv=q_per_kv
        )  # positions unused with causal=False
        mx = jnp.max(logits, axis=-1)  # [B, H, 1]
        safe = jnp.where(mx <= _NEG_INF * 0.5, 0.0, mx)
        probs = jnp.exp(logits - safe[..., None])
        num = _block_pv(probs, v_blk, q_per_kv).astype(jnp.float32)  # [B,1,H,D]
        den = jnp.sum(probs, axis=-1)  # [B, H, 1]
        return num, den, mx

    # Local prefill block -> psum-merged global prefill stats.
    num_l, den_l, mx_l = stats(ck_local, cv_local, mask_local)
    mx_p = jax.lax.pmax(mx_l, axis_name)
    safe_p = jnp.where(mx_p <= _NEG_INF * 0.5, 0.0, mx_p)
    scale_l = jnp.exp(mx_l - safe_p)  # 0 for fully-masked local blocks
    num_p = jax.lax.psum(num_l * scale_l[..., None].transpose(0, 2, 1, 3), axis_name)
    den_p = jax.lax.psum(den_l * scale_l, axis_name)

    # Decode region (replicated, computed identically everywhere).
    num_d, den_d, mx_d = stats(dk, dv, mask_dec)

    # Final merge of the two partial softmaxes.
    mx = jnp.maximum(mx_p, mx_d)
    safe = jnp.where(mx <= _NEG_INF * 0.5, 0.0, mx)
    a_p = jnp.exp(mx_p - safe)[..., None].transpose(0, 2, 1, 3)
    a_d = jnp.exp(mx_d - safe)[..., None].transpose(0, 2, 1, 3)
    num = num_p * a_p + num_d * a_d
    den = (den_p * jnp.exp(mx_p - safe) + den_d * jnp.exp(mx_d - safe))
    den = den.transpose(0, 2, 1)[..., None]  # [B, 1, H, 1]
    return (num / jnp.maximum(den, 1e-37)).astype(q.dtype)


def ring_self_attention(
    mesh: Mesh,
    q: jax.Array,  # [B, T, H, D] global
    k: jax.Array,  # [B, T, KVH, D]
    v: jax.Array,
    positions: jax.Array,  # [B, T]
    causal: bool = True,
    seq_axis: str = "seq",
) -> jax.Array:
    """Host-level wrapper: shards the sequence axis over ``seq_axis`` and runs
    :func:`ring_attention`.  Batch stays on 'data'; heads stay on 'model'
    (GSPMD-auto inside the body)."""
    fn = functools.partial(ring_attention, axis_name=seq_axis, causal=causal)
    seq_sharded = P(None, seq_axis, None, None)
    pos_sharded = P(None, seq_axis)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(seq_sharded, seq_sharded, seq_sharded, pos_sharded, pos_sharded),
        out_specs=seq_sharded,
        axis_names={seq_axis},
    )(q, k, v, positions, positions)
