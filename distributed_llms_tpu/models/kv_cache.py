"""How keys and values are stored: the cache pytrees and everything that
knows their layout.  Nothing outside this module tells one format from
another (tests/models/test_kv_cache.py): ``models.model.forward`` writes a
step's tokens (:func:`write_tokens`) and hands the kernel its operands,
the batcher's programs move whole pages (:func:`write_row`,
:func:`gather_row`, :func:`export_raw`, :func:`import_raw`,
:func:`import_full`), the mesh places the pool by :func:`pool_specs`, and
what a format cannot do yet is refused in :func:`refuse_unpaged_state`.
A new format is a case in :func:`_paged_fields` and :func:`_encode` first.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.config import ModelConfig


@jax.tree_util.register_dataclass
@dataclass
class KVCache:
    """Preallocated per-layer KV cache, [L, B, S, KVH, HD].

    Under sequence parallelism ``k``/``v`` are two-region tuples
    ``(prefill, decode)`` instead (see models.model._seq_cached_attention);
    every consumer treats the fields as opaque pytrees."""

    k: Any
    v: Any

    @property
    def max_len(self) -> int:
        if isinstance(self.k, tuple):  # seq-parallel two-region layout
            return self.k[0].shape[2] + self.k[1].shape[2]
        return self.k.shape[2]


@dataclass
class QuantKVCache:
    """Int8-quantized KV page pool (``--kv-bits 8`` tiering): ``k``/``v``
    hold the pool pages at int8 ([L, NB, BLK, KVH, HD]) and
    ``k_scale``/``v_scale`` one float32 absmax scale per head-dim vector
    ([L, NB, BLK, KVH] — checkpoint.quantize.kv_quantize's layout).  Pages
    are quantized ONCE at the write (admission splice / decode-step
    scatter) and dequantized inside the attention read (the decode
    kernel's int8 leg folds the scales into the contraction), so pool
    storage is never materialized full-width.  ``row_dtype`` names the
    dequantized dtype transient row caches (and gathers) restore to —
    static metadata, so jit keys stay stable.

    Decode-only through ``models.model.forward`` (requires ``kv_tables``):
    the contiguous per-row and prefill paths keep full-width caches."""

    k: Any
    v: Any
    k_scale: Any
    v_scale: Any
    row_dtype: str = "bfloat16"


# data/scales are pytree children; row_dtype is static metadata (hashable,
# part of the jit key — exactly how QuantizedTensor registers its bits).
jax.tree_util.register_dataclass(
    QuantKVCache,
    data_fields=["k", "v", "k_scale", "v_scale"],
    meta_fields=["row_dtype"],
)


@jax.tree_util.register_dataclass
@dataclass
class HybridCache(KVCache):
    """Cache of a model whose layers differ (family "hybrid"): two kinds of
    state a row.  ``k``/``v`` as in :class:`KVCache` (contiguous or the page
    pool), their layer axis counting the layers that attend a row's WHOLE
    prefix only (``cfg.attn_layers``); beside them what a row keeps at a
    fixed size, one entry a batch slot of the batcher (not paged), None
    where the model has no such layer: ``conv`` [conv layers, B, K-1, D],
    each short-convolution layer's last K-1 gated inputs a row, in the
    activations' dtype; ``ring_k``/``ring_v`` [swa layers, B, W, KVH, HD],
    each windowed attention layer's last W = ``cfg.sliding_window`` keys
    and values a row.  The key of position p lies at p mod W, already
    rotated, so order inside the ring does not matter, a row longer than
    the window overwrites what fell out of it, and a reader masks by count
    (min(length, W)): a slot's ring may hold a finished row's leftovers.
    ``ret_s`` [ret layers, B, KVH, 65, 128, 128] and ``ret_z`` [ret layers,
    B, KVH, 128, 128], both float32 whatever the activations' dtype: each
    power-retention layer's state a row and a key/value head and its
    normaliser (ops/retention.py says how the products of a key lie in
    them).  They are a row's WHOLE memory in such a layer: a model of
    retention layers alone holds no key, ``k``/``v`` count zero layers, and
    it is served without a page pool (:func:`refuse_unpaged_state`).
    ``ssm_h`` [ssm layers, B, R, N, 128] float32 whatever the activations'
    dtype: each Mamba-2 layer's state a row, heads x head size x state size
    values as ops/ssm.py lays them (R = heads x head size / 128 rows of N x
    128); ``ssm_conv`` [ssm layers, B, K-1, x + B + C channels], the last
    K-1 inputs of its convolution, in the activations' dtype.  They lie
    BESIDE the pool: such a model's attention layers page their keys as any
    other's do, ``k``/``v`` count those layers, and it is served from the
    pool.
    ``gdn_s`` [gdn layers, B, value heads, key_dim, value_dim] float32: each
    gated delta-rule layer's state a row, a value head's [keys x values]
    whole 128-lane tiles as the recurrence writes them (ops/gdn.py);
    ``gdn_conv`` [gdn layers, B, K-1, q + k + v channels], the last K-1
    inputs of its convolution, in the activations' dtype.  BESIDE the pool
    as ``ssm_h`` is."""

    conv: Any = None
    ring_k: Any = None
    ring_v: Any = None
    ret_s: Any = None
    ret_z: Any = None
    ssm_h: Any = None
    ssm_conv: Any = None
    gdn_s: Any = None
    gdn_conv: Any = None


@jax.tree_util.register_dataclass
@dataclass
class LatentCache:
    """Cache of a model with multi-head latent attention: ONE leaf, and no
    KV-head axis.  ``k`` [L, B, S, W] (contiguous) or [L, NB, BLK, W] (the
    page pool) holds a token's row a layer, W = ``cfg.latent_width``
    lanes: the normalised latent c_kv (``kv_lora_rank``), the rotated key
    every head shares (``qk_rope_head_dim``), zeros up to whole 128-lane
    rows.  All heads' keys AND values are functions of that row: the
    decode step attends to it as it lies (ops.decode_attn, the absorbed
    form), an admission expands it (models.model.mla_attention).  A row's
    whole state is its pages, so the prefix cache works on them as on
    key/value pages."""

    k: Any


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype: Any = None,
    prompt_len: int | None = None,
) -> KVCache:
    """``prompt_len`` is part of the shared make_cache protocol (the
    seq-parallel cache splits regions there); the dense layout ignores it."""
    del prompt_len
    dtype = dtype or jnp.dtype(cfg.dtype)
    if cfg.kv_lora_rank:
        return LatentCache(k=jnp.zeros(
            (len(cfg.attn_layers), batch, max_len, cfg.latent_width), dtype))
    shape = (len(cfg.attn_layers), batch, max_len, cfg.num_kv_heads,
             cfg.head_dim_)
    k, v = jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
    if not pages_are_private(cfg):
        return KVCache(k=k, v=v)
    return HybridCache(k=k, v=v, **slot_state(cfg, batch, dtype))


def conv_state(cfg: ModelConfig, rows: int) -> jax.Array:
    """What a :class:`HybridCache` holds beside k and v, zeroed: the
    convolution state of ``rows`` rows."""
    return jnp.zeros(
        (len(cfg.conv_layers), rows, cfg.conv_kernel - 1, cfg.hidden_size),
        jnp.dtype(cfg.dtype))


def slot_state(cfg: ModelConfig, rows: int, dtype) -> dict:
    """What a :class:`HybridCache` holds beside k and v for ``rows`` rows,
    zeroed, by field: the convolution layers' state, the windowed layers'
    rings (in the keys' dtype), the retention layers' state and normaliser
    (float32: ``ops.retention.state_shapes``), the Mamba-2 layers' state
    (float32: ``ops.ssm.state_shape``) and their convolutions' last inputs,
    the gated delta-rule layers' the same (``ops.gdn.state_shape``)."""
    out = {}
    if cfg.gdn_layers:
        from ..ops.gdn import state_shape

        lead = (len(cfg.gdn_layers), rows)
        out["gdn_s"] = jnp.zeros(
            lead + state_shape(cfg.gdn_value_heads, cfg.gdn_key_dim,
                               cfg.gdn_value_dim), jnp.float32)
        out["gdn_conv"] = jnp.zeros(
            lead + (cfg.gdn_conv_kernel - 1, cfg.gdn_conv_width),
            jnp.dtype(cfg.dtype))
    if cfg.ssm_layers:
        from ..ops.ssm import state_shape

        lead = (len(cfg.ssm_layers), rows)
        out["ssm_h"] = jnp.zeros(
            lead + state_shape(cfg.ssm_heads, cfg.ssm_head_dim,
                               cfg.ssm_state), jnp.float32)
        out["ssm_conv"] = jnp.zeros(
            lead + (cfg.ssm_conv_kernel - 1, cfg.ssm_conv_width),
            jnp.dtype(cfg.dtype))
    if cfg.ret_layers:
        from ..ops.retention import state_shapes

        s, z = state_shapes(cfg.num_kv_heads)
        lead = (len(cfg.ret_layers), rows)
        out["ret_s"] = jnp.zeros(lead + s, jnp.float32)
        out["ret_z"] = jnp.zeros(lead + z, jnp.float32)
    if cfg.conv_layers:
        out["conv"] = conv_state(cfg, rows)
    if cfg.swa_layers:
        shape = (len(cfg.swa_layers), rows, cfg.sliding_window,
                 cfg.num_kv_heads, cfg.head_dim_)
        out["ring_k"] = jnp.zeros(shape, dtype)
        out["ring_v"] = jnp.zeros(shape, dtype)
    return out


# ---------------------------------------------------------------------------
# The page pool: construction, size, placement
# ---------------------------------------------------------------------------

def make_pool(cfg: ModelConfig, num_pages: int, page_size: int,
              kv_bits: int = 16, dtype=None, slots: int = 0):
    """KV page pools [L, NB, BLK, KVH, HD] (distinct k/v buffers — the
    chunk fns donate the cache).  Each is ONE stack of every layer's
    pages and stays one: the decode programs carry it through the layer
    scan and update it in place (models.model.run_blocks), the paged
    kernel reads (layer, page) out of it, and admissions write whole
    pages into it (:func:`write_row`); nothing holds a layer's slice
    or a second stack.  ``kv_bits=8`` builds an int8 :class:`QuantKVCache`
    pool (data int8 + one f32 absmax scale per head-dim vector) at roughly
    half the bytes per token; the full-width dtype survives as
    ``row_dtype`` so gathers/transient rows restore to it.  A hybrid
    model's pool counts the layers that attend the whole prefix only and
    comes with the state that is not paged: each convolution layer's and
    each windowed attention layer's ring, one entry a batch slot
    (``slots``), in a :class:`HybridCache`.  A model with latent
    attention gets the one leaf of a :class:`LatentCache`,
    [L, NB, BLK, latent_width]."""
    from ..ops.decode_attn import pool_head_shape

    l = len(cfg.attn_layers)
    if cfg.kv_lora_rank:
        if kv_bits != 16:
            refuse_unpaged_state(cfg, kv_bits=True)
        return LatentCache(k=jnp.zeros(
            (l, num_pages, page_size, cfg.latent_width),
            jnp.dtype(dtype) if dtype else jnp.dtype(cfg.dtype)))
    kvh, hd = pool_head_shape(cfg.num_kv_heads, cfg.head_dim_,
                              fold_narrow=pages_are_private(cfg))
    dt = jnp.dtype(dtype) if dtype else jnp.dtype(cfg.dtype)
    shape = (l, num_pages, page_size, kvh, hd)
    if pages_are_private(cfg):
        return HybridCache(k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt),
                           **slot_state(cfg, slots, dt))
    if kv_bits == 8:
        sshape = (l, num_pages, page_size, kvh)
        return QuantKVCache(
            k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.ones(sshape, jnp.float32),
            v_scale=jnp.ones(sshape, jnp.float32),
            row_dtype=dt.name,
        )
    return KVCache(k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt))


def page_bytes(cfg: ModelConfig, page_size: int, kv_bits: int = 16,
               dtype=None) -> int:
    """Bytes one pool page costs (k + v + scales, every layer): those of
    :func:`make_pool`'s paged leaves at one page."""
    pool = jax.eval_shape(
        lambda: make_pool(cfg, 1, page_size, kv_bits, dtype))
    return sum(x.size * x.dtype.itemsize
               for x in (getattr(pool, f) for f in _paged_fields(pool)))


def format_bytes(pool, cfg: ModelConfig) -> dict[str, float]:
    """Sizes that only one format has, for its gauges: the bytes of the
    state a :class:`HybridCache` keeps beside its pages (``conv_state``,
    ``window_state``: the windowed layers' rings, ``ret_state``: the
    retention layers' state and normaliser, ``ssm_state``: the Mamba-2
    layers' state and their convolutions' last inputs, ``gdn_state``: the
    gated delta-rule layers' the same), the bytes of one page of a
    :class:`LatentCache` (``latent_page``)."""
    match pool:
        case HybridCache():
            sizes = {}
            if pool.conv is not None:
                sizes["conv_state"] = float(pool.conv.nbytes)
            if pool.ring_k is not None:
                sizes["window_state"] = float(
                    pool.ring_k.nbytes + pool.ring_v.nbytes)
            if pool.ret_s is not None:
                sizes["ret_state"] = float(
                    pool.ret_s.nbytes + pool.ret_z.nbytes)
            if pool.ssm_h is not None:
                sizes["ssm_state"] = float(
                    pool.ssm_h.nbytes + pool.ssm_conv.nbytes)
            if pool.gdn_s is not None:
                sizes["gdn_state"] = float(
                    pool.gdn_s.nbytes + pool.gdn_conv.nbytes)
            return sizes
        case LatentCache():
            return {"latent_page": float(
                page_bytes(cfg, pool.k.shape[2], dtype=pool.k.dtype))}
        case _:
            return {}


# The leaves of a :class:`HybridCache` that hold one entry a batch slot
# ([layers of the kind, B, ...]) and no page.
_SLOT_FIELDS = ("conv", "ring_k", "ring_v", "ret_s", "ret_z", "ssm_h",
                "ssm_conv", "gdn_s", "gdn_conv")


def splice_slot(cache: "HybridCache", slot, row_cache: "HybridCache"):
    """``cache`` with batch slot ``slot`` of every leaf that holds one
    entry a slot taken from the one-row ``row_cache`` (all of it: whatever
    the slot's last row left is overwritten), where the leaves lie."""
    return dataclasses.replace(cache, **{
        f: jax.lax.dynamic_update_slice_in_dim(
            state, getattr(row_cache, f).astype(state.dtype), slot, axis=1)
        for f in _SLOT_FIELDS if (state := getattr(cache, f)) is not None})


def splice_row(cache, slot, row_cache):
    """Overwrite batch row ``slot`` of a CONTIGUOUS cache (the batcher
    without a pool) with a prefilled single-row cache: keys and values
    (leaves end in [..., B, S, KVH, HD]: the batch axis is the 4th from the
    right), or, for a model of retention layers, whose stacks of keys count
    zero layers, the row's state (:func:`splice_slot`)."""
    def splice(full, row):
        start = [0] * full.ndim
        start[full.ndim - 4] = slot
        return jax.lax.dynamic_update_slice(
            full, row.astype(full.dtype), tuple(start)
        )

    if isinstance(cache, HybridCache):
        return splice_slot(cache, slot, row_cache)
    return KVCache(k=splice(cache.k, row_cache.k),
                   v=splice(cache.v, row_cache.v))


def _paged_fields(pool) -> tuple[str, ...]:
    """The pool's leaves that have a page axis ([L, NB, BLK, ...]), in the
    tree's order.  What else a format holds (a hybrid model's state a
    batch slot) is not paged and moves with no page."""
    match pool:
        case QuantKVCache():
            return ("k", "v", "k_scale", "v_scale")
        case LatentCache():
            return ("k",)
        case _:
            return ("k", "v")


def _row_fields(pool) -> tuple[str, ...]:
    """The leaves of a transient contiguous row cache of the pool's kind
    (:func:`init_cache`): full-width keys and values, or latent rows."""
    return ("k",) if isinstance(pool, LatentCache) else ("k", "v")


def row_cache_of(pool, *rows: jax.Array):
    """The contiguous row cache that :func:`gather_row` gathered out of
    ``pool``, as the pytree models.model.forward takes."""
    if isinstance(pool, LatentCache):
        return LatentCache(*rows)
    return KVCache(*rows)


def _encode(pool, k: jax.Array, v: jax.Array | None = None) -> tuple:
    """Keys and values [..., KVH, HD] as the pool stores them, one array a
    paged field.  An int8 pool quantizes each head-dim vector once, here,
    at the write (checkpoint.quantize.kv_quantize: int8 data + one f32
    absmax scale); a full-width pool casts, and lays the last two axes out
    as its own (narrow heads lie folded there,
    ops.decode_attn.pool_head_shape; the same bytes)."""
    match pool:
        case QuantKVCache():
            from ..checkpoint.quantize import kv_quantize

            (k, ks), (v, vs) = kv_quantize(k), kv_quantize(v)
            return (k, v, ks, vs)
        case LatentCache():  # rows [..., W] as the model made them
            return (k.astype(pool.k.dtype),)
        case _:
            return tuple(
                x.astype(leaf.dtype).reshape(*x.shape[:-2], *leaf.shape[3:])
                for x, leaf in zip((k, v), (pool.k, pool.v)))


def row_dtype(pool) -> Any:
    """Dtype transient single-row caches (and pool gathers) use: the
    pool's own dtype, or the declared full-width dtype of an int8 pool.
    Safe inside jit — the pytree TYPE of ``pool`` is static."""
    match pool:
        case QuantKVCache():
            return jnp.dtype(pool.row_dtype)
        case _:
            return pool.k.dtype


def pool_specs(cfg: ModelConfig, mesh: Mesh, pool) -> Any:
    """PartitionSpec pytree of ``pool``'s own structure (the pool or its
    ``jax.eval_shape``): paged data leaves [L, NB, BLK, KVH, HD] shard the
    KV-head axis over 'model' (Megatron-style tensor parallelism — each
    chip holds its heads' slice of every page, so per-chip pool bytes
    divide by tp); int8 absmax scales [L, NB, BLK, KVH] shard the same
    axis; what is not paged replicates.  Pages are shared across rows
    (prefix cache, handoff imports), so the page axis never shards over
    'data' — scheduling state replicates instead.  Non-divisible KV heads
    replicate (the batcher REJECTS that combination up front; the spec
    mirrors param_specs' degrade convention so the graftcheck GC2 audit
    stays total over the mesh ladder)."""
    tp = mesh.shape.get("model", 1)
    kv_ax = "model" if cfg.num_kv_heads % max(tp, 1) == 0 else None
    specs = jax.tree.map(lambda _: P(), pool)
    if isinstance(pool, LatentCache):
        # No head axis to split: replicated (and a mesh is refused for the
        # format, refuse_unpaged_state).
        return dataclasses.replace(specs, k=P(*(None,) * pool.k.ndim))
    return dataclasses.replace(specs, **{
        f: P(None, None, None, kv_ax, *(None,) * (getattr(pool, f).ndim - 4))
        for f in _paged_fields(pool)
    })


def constrain(pm, pool):
    """Pin a page pool's leaves to their mesh sharding (:func:`pool_specs`),
    the layout every paged program produces and consumes on a mesh
    batcher.  Applied to every program output that carries the pool
    (splice, decode chunk, import scatters) so XLA can never hand back a
    differently-placed pool and force a resharding copy (or a fresh
    compile key) on the next call.  No-op single-device (``pm`` None)."""
    if pm is None:
        return pool
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(
            x, NamedSharding(pm.mesh, s)
        ),
        pool, pool_specs(pm.cfg, pm.mesh, pool),
    )


# ---------------------------------------------------------------------------
# The movements of pages
# ---------------------------------------------------------------------------

def write_tokens(pool, layer, page, off, k: jax.Array,
                 v: jax.Array | None = None):
    """Scatter the new tokens' keys and values ([B, T, KVH, HD]; a latent
    pool's rows [B, T, W] as ``k`` alone) into
    layer ``layer`` of the pool at (``page``, ``off``) [B, T], where the
    stacks lie (they are the layer scan's carry).

    LIVE rows own distinct pages, but FREED rows' tables are zeroed to the
    shared scratch page, so two inactive rows CAN produce identical
    (page, off) indices — the scatter must tolerate duplicates (XLA picks
    a winner; the scratch page is never read by a live row).  Do NOT add
    unique_indices=True here."""
    return dataclasses.replace(pool, **{
        f: getattr(pool, f).at[layer, page, off].set(x)
        for f, x in zip(_paged_fields(pool), _encode(pool, k, v))
    })


def write_ring(pool: HybridCache, layer, at: jax.Array, k: jax.Array,
               v: jax.Array) -> HybridCache:
    """Put each row's new key and value ([B, KVH, HD]) into windowed layer
    ``layer``'s ring at index ``at`` [B] (the token's position mod the
    window), where the rings lie: they are the layer scans' carry."""
    rows = jnp.arange(at.shape[0], dtype=jnp.int32)
    return dataclasses.replace(
        pool,
        ring_k=pool.ring_k.at[layer, rows, at].set(
            k.astype(pool.ring_k.dtype)),
        ring_v=pool.ring_v.at[layer, rows, at].set(
            v.astype(pool.ring_v.dtype)))


def kernel_operands(pool) -> tuple[jax.Array, jax.Array, dict]:
    """(k pages, v pages, scales) as ops.decode_attn.paged_decode_attention
    takes them: ``scales`` is its ``k_scale``/``v_scale`` keywords on an
    int8 pool (the kernel's int8 leg folds them into the attention
    contraction, so the pool is read at 1 byte/elem and never dequantized
    in HBM), empty otherwise."""
    return pool.k, pool.v, {
        f: getattr(pool, f) for f in _paged_fields(pool)[2:]}


def _write_pages(pools: tuple, page_list: jax.Array, pages: tuple) -> tuple:
    """Write ``pages`` ([L, P, ...] a leaf) into the donated pool stacks
    ([L, NB, ...]) at ``page_list`` [P], where the stacks lie: one
    ``dynamic_update_slice`` a page, the stacks the loop's carry.  As one
    scatter on the page axis (``pool.at[:, page_list].set``) the compiler
    moves a pool of few KV heads (qwen2's 4) into a layout of its own,
    scatters there and moves it back: four pool-sized copies an admission
    (AOT compile for the v5e, PR 26).  A page listed twice (the scratch
    page pads the list) keeps the last write."""

    def write(i, pools):
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(
                pool, jax.lax.dynamic_slice_in_dim(new, i, 1, axis=1),
                page_list[i], axis=1,
            )
            for pool, new in zip(pools, pages)
        )

    return jax.lax.fori_loop(0, page_list.shape[0], write, pools)


def write_row(pool, page_list: jax.Array, row_cache, slot=None):
    """Write a contiguous transient row cache ([L, 1, P*BLK, KVH, HD]
    leaves) into the row's pages.  ``page_list`` [P] is padded with the
    reserved scratch page 0 past the allocation, so the fixed-shape write
    stays compiled once — the extra writes land in the scratch page, whose
    contents no LIVE row ever reads (freed rows' clamped decode reads do
    touch it, but their outputs are masked to pad).  Prefix-cache-hit
    admissions also route their CACHED positions to the scratch page: the
    shared pages already hold exactly that KV and must never be rewritten
    while other rows read them.  A :class:`HybridCache` also takes the
    row's state that is not paged (convolution state, rings) into batch
    slot ``slot`` (all of it: whatever the slot's last row left is
    overwritten)."""
    p = page_list.shape[0]
    blk = pool.k.shape[2]

    def as_pages(row):  # [L, 1, P*BLK, KVH, HD] -> [L, P, BLK, KVH, HD]
        # (the pool's own last two axes: narrow heads may lie folded there;
        # a latent row's one)
        return row[:, 0].reshape(row.shape[0], p, blk, *pool.k.shape[3:])

    fields = _paged_fields(pool)
    leaves = _write_pages(
        tuple(getattr(pool, f) for f in fields), page_list,
        _encode(pool, *(as_pages(getattr(row_cache, f))
                        for f in _row_fields(pool))),
    )
    new = dict(zip(fields, leaves))
    pool = dataclasses.replace(pool, **new)
    if isinstance(pool, HybridCache):
        pool = splice_slot(pool, slot, row_cache)
    return pool


@jax.jit
def gather_row(pool, read_list: jax.Array) -> tuple[jax.Array, ...]:
    """Gather a row's pages out of the pool into a transient contiguous
    row cache ([L, 1, P*BLK, KVH, HD] k/v pair, or a latent pool's one
    leaf; :func:`row_cache_of` makes the pytree) — the chunked-prefill
    analogue of admit_row_auto_paged's in-program gather.  A cache-hit
    chunked admission seeds its transient row from the shared pages ONCE
    (the "prefix" is then already resident, exactly as if those chunks had
    run), and only the un-cached suffix chunks through the model.  The
    outputs are fresh buffers, so every later prefill_chunk_step may
    donate them.  An int8 pool dequantizes the gathered pages to its
    ``row_dtype``: transient rows always run full-width; only POOL storage
    is quantized."""
    p = read_list.shape[0]
    if isinstance(pool, LatentCache):  # [L, 1, P*BLK, W], one leaf
        l, _, blk, w = pool.k.shape
        return (pool.k[:, read_list].reshape(l, 1, p * blk, w),)
    l, _, blk, kvh, hd = pool.k.shape
    match pool:
        case QuantKVCache():
            from ..checkpoint.quantize import kv_dequantize

            dt = jnp.dtype(pool.row_dtype)

            def gather(pages, scale):
                full = kv_dequantize(
                    pages[:, read_list], scale[:, read_list], dt)
                return full.reshape(l, 1, p * blk, kvh, hd)

            return gather(pool.k, pool.k_scale), gather(pool.v, pool.v_scale)
        case _:
            return tuple(x[:, read_list].reshape(l, 1, p * blk, kvh, hd)
                         for x in (pool.k, pool.v))


@jax.jit
def export_raw(pool, page_list: jax.Array) -> tuple:
    """Gather pages VERBATIM in pool layout and pool dtype — one page
    stack a paged field (k, v, and the scale stacks on an int8 pool).
    This is the host-tier parcel format (swap-preemption, prefix-cache
    spill): re-importing the exact bytes via :func:`import_raw` restores
    the pool state bit-for-bit, which is what makes a swap-restored row's
    stream byte-exact against its never-preempted run at EITHER kv
    width."""
    return tuple(getattr(pool, f)[:, page_list] for f in _paged_fields(pool))


@partial(jax.jit, static_argnames=("pm",))
def import_raw(pool, page_list: jax.Array, *pages: jax.Array, pm: Any = None):
    """Scatter a raw host-tier parcel (:func:`export_raw`'s layout) back
    into freshly allocated pool pages, verbatim — no quantize/dequantize
    hop, so restore is exact by construction."""
    return constrain(pm, dataclasses.replace(pool, **{
        f: getattr(pool, f).at[:, page_list].set(x)
        for f, x in zip(_paged_fields(pool), pages, strict=True)
    }))


@partial(jax.jit, static_argnames=("pm",))
def import_full(pool, page_list: jax.Array, k_pages: jax.Array,
                v_pages: jax.Array, pm: Any = None):
    """Scatter HANDED-OFF KV pages into the pool (disaggregated serving:
    a prefill-role engine shipped a finished row's pages over
    cluster/kv_transfer.py and this decode-role engine adopts them).
    ``k_pages``/``v_pages`` are full-width [L, P, BLK, KVH, HD] page
    stacks; ``page_list`` [P] names the freshly allocated destination
    pages.  An int8 pool re-quantizes the payload on the way in —
    byte-stable when the payload was itself dequantized from int8 pages
    (kv_quantize's exact round-trip property), which is how a kv-bits-8
    fleet ships pages without a second lossy step.  The pool is NOT
    donated: import is a rare, off-hot-path event and the caller reuses
    the returned pool exactly like the admission splices do."""
    return constrain(pm, dataclasses.replace(pool, **{
        f: getattr(pool, f).at[:, page_list].set(x)
        for f, x in zip(_paged_fields(pool), _encode(pool, k_pages, v_pages))
    }))


# ---------------------------------------------------------------------------
# What a format cannot do yet
# ---------------------------------------------------------------------------

def pages_are_private(cfg: ModelConfig) -> bool:
    """True where nothing but the admission's splice and the decode step
    ever touches a page: :func:`refuse_unpaged_state` has refused every
    feature that reads [.., KVH, HD] rows out of the pool (prefix cache,
    named prefixes, tiering, import/export, chunked prefill, speculation,
    the int8 pool, a mesh), which it does for a model that keeps state
    beside its pages (convolution state, the windowed layers' rings, a
    state-space layer's state: :class:`HybridCache`).  Only then may heads narrower than
    a 128-lane row lie folded in the pool
    (ops.decode_attn.pool_head_shape; heads of 128 never fold)."""
    return bool(cfg.conv_layers or cfg.swa_layers or cfg.ret_layers
                or cfg.ssm_layers or cfg.gdn_layers)


_LATENT_REFUSALS = {
    "kv_bits": "the int8 pool quantizes a head's vector, and a latent row "
               "is no head's (ask for kv_bits 16)",
    "host_pages": "the host tier's parcels and its swap-in are written "
                  "for key/value page pairs",
    "speculative": "the verify pass reads several query tokens a row, "
                   "and no draft model of the family exists",
    "prefill_chunk": "a chunked prefill hands key/value rows from bite to "
                     "bite, not latent rows",
    "token_budget": "it chunks prefills, which hand key/value rows from "
                    "bite to bite, not latent rows",
    "mesh": "a latent page has no head axis to split (pool_specs), and "
            "the expert stacks have no sharding rule yet",
    "named_prefix": "a registered prefix keeps key/value rows, not latent "
                    "rows (the automatic prefix cache serves them)",
    "kv_import": "KV import/export ships key/value page pairs",
    "kv_export": "KV import/export ships key/value page pairs",
    "sessions": "a session keeps key/value rows between turns, not latent "
                "rows",
    "padded_generate": "generate_text's padded batch decodes against a "
                       "contiguous key/value cache; serve through "
                       "continuous_batcher",
}


_RING_REFUSALS = {
    "prefix_cache": "a cached page run restores the full layers' keys and "
                    "values, not the last keys of the windowed layers",
    "kv_bits": "the int8 pool's write path knows no ring (ask for kv_bits "
               "16)",
    "host_pages": "the host tier and swap-out park pages, not the rings "
                  "that belong to them",
    "speculative": "a rejected draft would have to take its keys back out "
                   "of the rings",
    "prefill_chunk": "a chunked prefill would have to hand the rings from "
                     "bite to bite",
    "token_budget": "it chunks prefills, which would have to hand the "
                    "rings from bite to bite",
    "mesh": "the rings and the expert stacks have no sharding rule yet "
            "(mesh.model > 1 included)",
    "named_prefix": "a registered prefix keeps the full layers' keys and "
                    "values, not the windowed layers' last keys at its end",
    "kv_import": "KV import/export ships pages, not rings",
    "kv_export": "KV import/export ships pages, not rings",
    "sessions": "a session keeps contiguous keys and values between turns, "
                "not rings",
    "padded_generate": "generate_text pads rows of unlike length, and the "
                       "rings would be taken at the padded end; serve "
                       "through continuous_batcher",
}


_STATE_REFUSALS = {
    "paged_pages": "its rows hold no key and no value to page: serve it "
                   "without a pool (--paged-pages 0)",
    "prefix_cache": "it runs over the page pool, and a prefix's state is "
                    "no page: nothing snapshots the state at a prefix's end",
    "kv_bits": "the int8 pool quantizes keys and values; the state is "
               "float32 and is never quantized (ask for kv_bits 16)",
    "host_pages": "the host tier parks pages; nothing parks a row's "
                  "megabytes a layer of state",
    "speculative": "a rejected draft would have to roll the state back, "
                   "and a state keeps no past",
    "prefill_chunk": "a chunked prefill would have to hand the state from "
                     "bite to bite",
    "token_budget": "it chunks prefills, which would have to hand the "
                    "state from bite to bite",
    "mesh": "the state has no sharding rule yet (mesh.model > 1 included)",
    "named_prefix": "a registered prefix keeps keys and values; nothing "
                    "snapshots the state at its end",
    "kv_import": "KV import/export ships pages, and the state is none",
    "kv_export": "KV import/export ships pages, and the state is none",
    "sessions": "a session keeps keys and values between turns, not the "
                "state",
    "padded_generate": "generate_text pads rows of unlike length, and the "
                       "state would be taken at the padded end; serve "
                       "through continuous_batcher",
}


def refuse_unpaged_state(cfg: ModelConfig, **asked) -> None:
    """Refuse, by name and with the reason, every feature that moves or
    keeps keys and values and does not yet carry the state a hybrid model
    holds beside them (each convolution layer's last gated inputs a row:
    :class:`HybridCache`).  Served anyway, such a feature
    would hand a row its pages without its state.  A model whose windowed
    attention layers keep a ring a row refuses the same list for the
    rings' sake (``_RING_REFUSALS``).  ``asked`` maps a
    feature's name to whether it was asked for; ``paged_pages`` is the one
    that must be set.

    A model of power-retention layers (``cfg.ret_layers``) holds a state
    and no key: it is served WITHOUT a pool (``paged_pages`` is refused),
    and ``_STATE_REFUSALS`` names what cannot snapshot, ship or roll back
    the state.

    A model of Mamba-2 or gated delta-rule layers beside attention layers
    (``cfg.ssm_layers``, ``cfg.gdn_layers``) holds such a state AND keys: it is served FROM the pool (``paged_pages``
    is required, as for convolution state), and refuses for the state's sake
    what ``_STATE_REFUSALS`` names, all but its ``paged_pages`` entry.

    The latent format (:class:`LatentCache`) refuses here too, with its own
    reasons: what is written for key/value page pairs and has no latent
    case yet.  Its pages are whole rows' states, so ``prefix_cache`` is
    served."""
    if cfg.kv_lora_rank:
        if asked.pop("paged_pages", 1) is None:
            raise ValueError(
                "a model with latent attention is served from the page "
                "pool only; pass paged_pages"
            )
        asked.pop("prefix_cache", None)
        for name, value in asked.items():
            if value:
                raise ValueError(
                    f"{name} is not supported for latent (MLA) pages: "
                    f"{_LATENT_REFUSALS[name]}"
                )
        return
    if cfg.ret_layers:
        # A model of power-retention layers: a row's whole memory is its
        # float32 state, one entry a batch slot.  Served WITHOUT a pool,
        # and whatever would snapshot, ship or roll back the state is
        # refused until something can.
        for name, value in asked.items():
            if value:
                raise ValueError(
                    f"{name} is not supported for a model whose rows hold "
                    f"a recurrent state and no key (family {cfg.family!r}"
                    f", power retention): {_STATE_REFUSALS[name]}"
                )
        return
    if cfg.ssm_layers or cfg.gdn_layers:
        # A recurrent state BESIDE a pool: the attention layers' keys are
        # paged, the state is one entry a batch slot, and whatever would
        # snapshot, ship or roll back the state is refused until something
        # can.
        if asked.pop("paged_pages", 1) is None:
            raise ValueError(
                f"{cfg.family} model: the batcher serves its attention "
                "layers' keys and values from the page pool only, the "
                f"{'state-space' if cfg.ssm_layers else 'delta-rule'} "
                "layers' state beside it; pass paged_pages"
            )
        for name, value in asked.items():
            if value:
                raise ValueError(
                    f"{name} is not supported for a model whose rows hold "
                    f"a recurrent state beside their pages (family "
                    f"{cfg.family!r}, "
                    f"{'Mamba-2' if cfg.ssm_layers else 'Gated DeltaNet'}): "
                    f"{_STATE_REFUSALS[name]}"
                )
        return
    if not pages_are_private(cfg):
        return
    why = {
        "prefix_cache": "a cached page run restores keys and values, not "
                        "the convolution state at its end",
        "kv_bits": "the int8 pool's write path knows no convolution state "
                   "(ask for kv_bits 16)",
        "host_pages": "the host tier and swap-out park pages, not the "
                      "convolution state that belongs to them",
        "speculative": "a rejected draft would have to roll the "
                       "convolution state back",
        "prefill_chunk": "a chunked prefill would have to hand the "
                         "convolution state from bite to bite",
        "token_budget": "it chunks prefills, which would have to hand the "
                        "convolution state from bite to bite",
        "mesh": "the convolution state and the expert stacks have no "
                "sharding rule yet (mesh.model > 1 included)",
        "named_prefix": "a registered prefix keeps keys and values, not "
                        "the convolution state at its end",
        "kv_import": "KV import/export ships pages, not convolution state",
        "kv_export": "KV import/export ships pages, not convolution state",
        "sessions": "a session keeps keys and values between turns, not "
                    "the convolution state",
        "padded_generate": "generate_text pads rows of unlike length, and "
                           "the convolution state would be taken at the "
                           "padded end; serve through continuous_batcher",
    }
    if asked.pop("paged_pages", 1) is None:
        raise ValueError(
            f"{cfg.family} model: the batcher serves its keys and values "
            "from the page pool only; pass paged_pages"
        )
    what = "convolution state"
    if not cfg.conv_layers:  # window and full attention layers mixed
        why, what = _RING_REFUSALS, "windowed layers' rings"
    for name, value in asked.items():
        if value:
            raise ValueError(
                f"{name} is not supported for a model with {what} "
                f"(family {cfg.family!r}): {why[name]}"
            )
