"""Weight quantization: int8 and packed-int4, blockwise absmax scales.

Covers the reference's designed-but-unlanded quantization module
(snippets.md:675-833, plan.md:438-456): its scheme was per-tensor absmax
int8 (scale = absmax/127) with a 4-bit packed variant.  Here the same absmax
scheme is *blockwise* along the LAST axis of each weight — for most weights
that is the reduction axis, but for wq/wk/wv ([D, H, hd]) it is the output
head dim (finer-grained scales lose less precision, and blocks align with TP
shards so scales never straddle a shard boundary — SURVEY §7 hard part 6),
implemented as pure jnp ops.

Policy: only matmul weights (ndim >= 2) quantize; norms/biases stay in the
model dtype.  A quantized tree stores ``QuantizedTensor`` leaves that
``dequantize_tree`` restores — or, on TPU, that the fused dequant-matmul
kernel (ops/quant_matmul.py) consumes directly without ever writing the
full-precision weights back to HBM.

int4 pack layout: two values per byte along ``pack_axis`` — the weight's
*reduction* axis (adjacent rows k, k+1 share a byte; low nibble = even row).
Row-packing (rather than packing along the last axis) is what lets the TPU
kernel unpack with a sublane interleave, which Mosaic supports for any
width; scales always run along the LAST axis regardless.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class QuantizedTensor:
    """Blockwise-quantized array.

    data: int8; for int4, two values packed per byte along ``pack_axis``
    (low nibble = even index, high nibble = odd index along that axis).
    scale: float32, shape = unpacked shape with the last axis divided into
    blocks.
    pack_axis: negative axis index the int4 pairs run along — negative so a
    leading stacked-layer axis can be sliced off (lax.scan) without
    invalidating it.  Unused for int8.
    """

    data: jax.Array
    scale: jax.Array
    bits: int
    orig_shape: tuple[int, ...]
    pack_axis: int = -2
    # The axis the absmax blocks run along: -1 (the last one) for every 2-D
    # weight; -2 for the expert stacks [E, K, N], whose scales [E, K/128, N]
    # are then lane-dense for ops/moe_experts.py.  int8 only.
    block_axis: int = -1

    @property
    def unpacked_shape(self) -> tuple[int, ...]:
        """Shape of the dequantized array — derived from data (NOT
        orig_shape, which goes stale on stacked-layer slices)."""
        shape = list(self.data.shape)
        if self.bits == 4:
            shape[self.pack_axis] *= 2
        return tuple(shape)


# data/scale are pytree children; the rest is static metadata.
jax.tree_util.register_dataclass(
    QuantizedTensor,
    data_fields=["data", "scale"],
    meta_fields=["bits", "orig_shape", "pack_axis", "block_axis"],
)


def quantize(
    x: jax.Array, bits: int = 8, block: int = 128, pack_axis: int = -2,
    block_axis: int = -1,
) -> QuantizedTensor:
    if bits not in (8, 4):
        raise ValueError(f"bits must be 8 or 4, got {bits}")
    if block_axis == -2:
        if bits != 8:
            raise ValueError("blocks along the contracted axis are int8 only")
        qt = quantize(jnp.swapaxes(x, -1, -2), bits, block)
        return QuantizedTensor(
            data=jnp.swapaxes(qt.data, -1, -2),
            scale=jnp.swapaxes(qt.scale, -1, -2), bits=bits,
            orig_shape=tuple(x.shape), pack_axis=pack_axis, block_axis=-2,
        )
    orig_shape = tuple(x.shape)
    block = min(block, x.shape[-1])
    if x.shape[-1] % block:
        # shrink to the largest common divisor so any width quantizes
        import math

        block = math.gcd(x.shape[-1], block)
    n = x.shape[-1]
    xb = jnp.asarray(x, jnp.float32).reshape(*x.shape[:-1], n // block, block)
    qmax = 127.0 if bits == 8 else 7.0
    absmax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0)
    q = jnp.clip(jnp.round(xb / scale), -qmax, qmax).astype(jnp.int8)
    q = q.reshape(orig_shape)
    scale = scale[..., 0]  # [..., n_blocks]
    if bits == 4:
        if not -x.ndim <= pack_axis < 0:
            raise ValueError(f"pack_axis must be negative, got {pack_axis}")
        a = x.ndim + pack_axis
        if x.shape[a] % 2:
            raise ValueError(
                f"int4 packing requires even size along pack_axis {pack_axis} "
                f"(shape {orig_shape})"
            )
        idx_lo = [slice(None)] * x.ndim
        idx_hi = [slice(None)] * x.ndim
        idx_lo[a] = slice(0, None, 2)
        idx_hi[a] = slice(1, None, 2)
        lo = q[tuple(idx_lo)] & 0x0F
        hi = (q[tuple(idx_hi)] & 0x0F) << 4
        q = (lo | hi).astype(jnp.int8)
    return QuantizedTensor(
        data=q, scale=scale, bits=bits, orig_shape=orig_shape, pack_axis=pack_axis
    )


def dequantize(qt: QuantizedTensor, dtype: Any = jnp.float32) -> jax.Array:
    """Shapes derive from data/scale, NOT orig_shape: a per-layer slice of a
    stacked [L, ...] QuantizedTensor (what lax.scan hands the decoder-block
    body when serving quantized weights) carries stale orig_shape metadata
    but self-consistent data/scale."""
    if qt.block_axis == -2:
        t = QuantizedTensor(
            data=jnp.swapaxes(qt.data, -1, -2),
            scale=jnp.swapaxes(qt.scale, -1, -2), bits=qt.bits,
            orig_shape=qt.orig_shape, pack_axis=qt.pack_axis,
        )
        return jnp.swapaxes(dequantize(t, dtype), -1, -2)
    q = qt.data
    if qt.bits == 4:
        a = q.ndim + qt.pack_axis
        lo = (q << 4).astype(jnp.int8) >> 4  # sign-extend low nibble
        hi = q >> 4  # arithmetic shift sign-extends high nibble
        shape = list(q.shape)
        shape[a] *= 2
        q = jnp.stack([lo, hi], axis=a + 1).reshape(shape)
    qf = q.astype(jnp.float32)
    n = q.shape[-1]
    n_blocks = qt.scale.shape[-1]
    block = n // n_blocks
    qb = qf.reshape(*q.shape[:-1], n_blocks, block)
    out = qb * qt.scale[..., None]
    return out.reshape(q.shape).astype(dtype)


# Weights whose trailing TWO axes are output axes ([D, H, hd]): their
# reduction axis sits at -3, everything else contracts at -2.
_PACK_AXIS_BY_NAME = {"wq": -3, "wk": -3, "wv": -3}


# Bias leaves by exact name — matched explicitly (not by "b" prefix) so a
# future weight whose name starts with "b" is not silently left unquantized.
_BIAS_NAMES = frozenset({"bq", "bk", "bv", "bo", "b_in", "b_out", "b_gate", "b_up", "b_down"})


# Hybrid-family leaves (blocks/conv/..., blocks/moe/...) that stay float
# although they have two axes or more: the convolution's taps [L, D, K], and
# the router [L, D, E] and the selection bias [L, E], which decide the
# chosen set in float32.
_HYBRID_FLOAT = frozenset({"taps", "router", "expert_bias"})


def block_axis_of(path: str) -> int:
    """The axis a named leaf's absmax blocks run along: the contracted one
    for the expert stacks [E, K, N] under ``.../experts/`` (consumed by
    ops/moe_experts.py), the last one for every other weight."""
    return -2 if "experts" in path.split("/") else -1


def _should_quantize(path: str, x: Any) -> bool:
    if not hasattr(x, "ndim") or x.ndim < 2:
        return False
    leaf = path.split("/")[-1]
    if path.startswith(("blocks/conv/", "blocks/moe/")) and leaf in _HYBRID_FLOAT:
        return False
    if "norm" in path or "ln" in path.split("/")[-2:][0]:
        return False
    if leaf in _BIAS_NAMES:
        return False
    return True


def leaf_plan(path: str, x: Any) -> tuple[bool, int]:
    """(quantize?, pack_axis) for a named leaf — the single source of truth
    for which leaves quantize and how they pack, shared by quantize_tree
    and streaming builders (bench.py generates-and-quantizes on device leaf
    by leaf and must make the exact decisions the serving path makes)."""
    if not _should_quantize(path, x):
        return False, -2
    return True, _PACK_AXIS_BY_NAME.get(path.split("/")[-1], -2)


def quantize_tree(params: Any, bits: int = 8, block: int = 128) -> Any:
    """Quantize matmul weights in a param tree; other leaves pass through."""

    def visit(path, x):
        key = "/".join(str(getattr(p, "key", p)) for p in path)
        should, pack_axis = leaf_plan(key, x)
        if should:
            return quantize(x, bits=bits, block=block, pack_axis=pack_axis,
                            block_axis=block_axis_of(key))
        return x

    return jax.tree_util.tree_map_with_path(visit, params)


def dequantize_tree(params: Any, dtype: Any = None) -> Any:
    def visit(x):
        if isinstance(x, QuantizedTensor):
            return dequantize(x, dtype or jnp.float32)
        return x

    return jax.tree.map(
        visit, params, is_leaf=lambda x: isinstance(x, QuantizedTensor)
    )


# ---------------------------------------------------------------------------
# KV-cache quantization (int8 KV pages, runtime/batcher.py PagePool tiering)
#
# The same absmax scheme as quantize()/dequantize() above, specialised to the
# KV layout: one float32 scale per head-dim VECTOR (block == head_dim along
# the last axis — the finest block the weight path supports), so the decode
# kernel can fold the scale into the attention contraction itself:
# score = (q . k_int8) * k_scale and out = sum((p * v_scale) . v_int8) —
# per-(slot, head) scales sit OUTSIDE the head-dim dot product, which is what
# lets ops/decode_attn.py read the pool at 1 byte/elem and never materialize
# a dequantized page in HBM.
# ---------------------------------------------------------------------------

KV_QMAX = 127.0  # int8 absmax grid, the quantize() scheme's 8-bit constant


def kv_quantize(x: "jax.Array") -> tuple["jax.Array", "jax.Array"]:
    """Quantize KV vectors to int8 with one absmax scale per trailing
    head-dim vector.  ``x`` is [..., HD]; returns (data int8 [..., HD],
    scale float32 [...]).  Exact round-trip property: quantizing the
    output of :func:`kv_dequantize` reproduces the identical int8 data and
    scales (the dequantized absmax IS qmax * scale), which is what makes
    re-quantizing a dequantized handoff payload byte-stable."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(absmax > 0, absmax / KV_QMAX, 1.0)
    data = jnp.clip(
        jnp.round(xf / scale[..., None]), -KV_QMAX, KV_QMAX
    ).astype(jnp.int8)
    return data, scale


def kv_dequantize(data: "jax.Array", scale: "jax.Array", dtype: Any) -> "jax.Array":
    """Restore int8 KV vectors: ``f32(data) * scale`` cast to ``dtype`` —
    the exact numerics :func:`dequantize` uses, and the reference the
    fused decode-attention int8 leg must match."""
    return (data.astype(jnp.float32) * scale[..., None]).astype(dtype)


def tree_bytes(params: Any) -> int:
    total = 0
    for leaf in jax.tree.leaves(params, is_leaf=lambda x: isinstance(x, QuantizedTensor)):
        if isinstance(leaf, QuantizedTensor):
            total += leaf.data.size * leaf.data.dtype.itemsize
            total += leaf.scale.size * leaf.scale.dtype.itemsize
        else:
            total += leaf.size * np.dtype(leaf.dtype).itemsize
    return total
