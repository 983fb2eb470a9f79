"""Ulysses sequence parallelism: all-to-all head-scatter attention.

The second sequence-parallel strategy (SURVEY §2.3: "Ulysses ... all-to-all
on heads<->sequence ... optional, after ring attention").  Where ring
attention keeps the sequence sharded and rotates KV blocks around the ring,
Ulysses re-shards *once* per attention call:

    [B, T/S, H, D]  --all_to_all-->  [B, T, H/S, D]
      (seq sharded)                   (heads sharded)

so each device runs *full* attention over the whole sequence for its subset
of heads, then the inverse all-to-all restores sequence sharding for the
(position-wise) MLP.  Two collectives per layer instead of S-1 ppermute
hops — cheaper when the per-hop latency dominates, but requires
``num_heads % S == 0`` and ``num_kv_heads % S == 0`` (use ring attention
when the KV-head count is smaller than the seq axis).

The local attention is the Pallas flash kernel (ops/flash.py) with explicit
global positions, so causality holds for any contiguous block sharding and
long gathered sequences never materialize dense score matrices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import flash


def ulysses_attention(
    q: jax.Array,  # [B, T_local, H, D] — sequence sharded over axis_name
    k: jax.Array,  # [B, T_local, KVH, D]
    v: jax.Array,  # [B, T_local, KVH, D]
    q_positions: jax.Array,  # [B, T_local] global positions
    axis_name: str = "seq",
    causal: bool = True,
    k_valid: jax.Array | None = None,  # [B, T_local] bool
) -> jax.Array:
    """Ulysses attention body — call *inside* ``shard_map`` with the sequence
    axis sharded over ``axis_name``.  Returns [B, T_local, H, D]."""
    try:
        s = jax.lax.axis_size(axis_name)
    except NameError as e:
        raise RuntimeError(
            f"ulysses attention needs a bound {axis_name!r} mesh axis — call "
            "it inside shard_map (e.g. via ParallelModel with "
            "MeshConfig(seq=N) and attn_impl='ulysses')"
        ) from e
    h, kvh = q.shape[2], k.shape[2]
    if h % s or kvh % s:
        raise ValueError(
            f"ulysses needs num_heads ({h}) and num_kv_heads ({kvh}) divisible "
            f"by the seq axis ({s}); use attn_impl='ring' for small-KV GQA"
        )

    # Head-scatter / sequence-gather: [B, T/S, H, D] -> [B, T, H/S, D].
    a2a = lambda x: jax.lax.all_to_all(
        x, axis_name, split_axis=2, concat_axis=1, tiled=True
    )
    qg, kg, vg = a2a(q), a2a(k), a2a(v)
    pos = jax.lax.all_gather(q_positions, axis_name, axis=1, tiled=True)
    kv_full = (
        None
        if k_valid is None
        else jax.lax.all_gather(k_valid, axis_name, axis=1, tiled=True)
    )

    out = flash.flash_attention(
        qg, kg, vg,
        q_positions=pos, k_positions=pos, k_valid=kv_full, causal=causal,
    )  # [B, T, H/S, D]

    # Inverse: sequence-scatter / head-gather back to [B, T/S, H, D].
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)
