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

A quantized weight is stored as the MATRIX the kernel reads, whatever axes
the model gives it: the contracted axes flattened to K, the output axes to
N, and both the weight ([N, K]) and its scales ([N/block, K]: the block
index second-minor) with K on the lanes.  A block's scales are then a ROW
over the block's 128 weight rows: the kernel multiplies a block by its row
as it lies (a sublane broadcast), where a weight with N on its lanes wants
a scale column broadcast over the lanes for every register of weights
(PERF.md, PR 33).  The kernel takes a layer's tiles out of the stacked
leaves as they lie; no served program re-lays out a weight or a scale.

int4 pack layout: two values per byte along ``pack_axis`` — the stored
matrix's rows, which are N (adjacent output columns n, n+1 of the model's
weight share a byte; low nibble = even row).  Row-packing (rather than
packing along the lanes) is what lets the TPU kernel unpack with a sublane
interleave, which Mosaic supports for any width; scale blocks always run
along N regardless.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class QuantizedTensor:
    """Blockwise-quantized weight, stored as a (stack of) matrix.

    data: int8 [*lead, N, K] (``block_axis`` -1): the weight's ``n_axes``
    output axes flattened to N and its ``k_axes`` contracted axes to K, K
    on the lanes.  For int4, two values packed per byte along ``pack_axis``
    (low nibble = even index, high nibble = odd index along that axis).
    scale: float32 [*lead, N/block, K]: the block index second-minor, K on
    the lanes, like the weight it belongs to.
    pack_axis: negative axis index of ``data`` the int4 pairs run along: -2
    (the stored rows, N: what the kernel unpacks) or -1 (the lanes, K:
    dequantized, never fed to the kernel) — negative so a leading
    stacked-layer axis can be sliced off (lax.scan) without invalidating
    it.  Unused for int8.
    orig_shape: the weight's shape when it was quantized.  Its last
    ``k_axes + n_axes`` entries say how K and N unflatten (wq [D, H, hd]:
    1 and 2; wo [H, hd, D]: 2 and 1); the leading ones go stale on
    stacked-layer slices and are never read.
    layer: None, or the int32 index of the ONE layer of a stack [L, N, K]
    this leaf stands for (:meth:`at`): what a layer scan hands a matmul
    site in place of a slice, which would be a copy.
    rows: None, or [1] int32: how many leading rows of the activations this
    leaf is about to meet are real (one right-padded sequence, :meth:`at`),
    which ops/quant_matmul.py uses to skip the row tiles of padding.
    """

    data: jax.Array
    scale: jax.Array
    bits: int
    orig_shape: tuple[int, ...]
    pack_axis: int = -2
    # The axis OF THE MODEL'S WEIGHT [.., K, N] the absmax blocks run along:
    # -1 (N) for every 2-D weight, stored turned as above; -2 (K) for the
    # expert stacks, stored as they are ([E, K, N], scales [E, K/128, N]:
    # lane-dense for ops/moe_experts.py).  int8 only.
    block_axis: int = -1
    k_axes: int = 1
    n_axes: int = 1
    layer: jax.Array | None = None
    rows: jax.Array | None = None

    def at(self, layer: jax.Array, rows: jax.Array | None = None
           ) -> "QuantizedTensor":
        """This stack [L, ...] read at ``layer`` (traced inside a scan), for
        activations of which the first ``rows`` rows are real."""
        return dataclasses.replace(self, layer=layer, rows=rows)

    def layer_slice(self) -> "QuantizedTensor":
        """The leaf of :meth:`at` as a leaf of its own: a copy of one
        layer (the paths without the kernel)."""
        if self.layer is None:
            return self
        return dataclasses.replace(
            self, data=self.data[self.layer], scale=self.scale[self.layer],
            layer=None)

    @property
    def tail_shape(self) -> tuple[tuple[int, ...], tuple[int, ...]]:
        """(K axes, N axes) of the weight as the model has it."""
        if self.k_axes == self.n_axes == 1:
            if self.block_axis == -2:
                return tuple((d,) for d in self.data.shape[-2:])
            n, k = self.data.shape[-2:]
            if self.bits == 4:
                n, k = ((n, 2 * k) if self.pack_axis == -1 else (2 * n, k))
            return (k,), (n,)
        tail = tuple(self.orig_shape[-(self.k_axes + self.n_axes):])
        return tail[: self.k_axes], tail[self.k_axes:]

    @property
    def unpacked_shape(self) -> tuple[int, ...]:
        """Shape of the dequantized array — the leading axes from data (NOT
        orig_shape, which goes stale on stacked-layer slices)."""
        k_shape, n_shape = self.tail_shape
        return (*self.data.shape[:-2], *k_shape, *n_shape)


# data/scale (and the layer index and the row count) are pytree children;
# the rest is static metadata.
jax.tree_util.register_dataclass(
    QuantizedTensor,
    data_fields=["data", "scale", "layer", "rows"],
    meta_fields=["bits", "orig_shape", "pack_axis", "block_axis", "k_axes",
                 "n_axes"],
)


def quantize(
    x: jax.Array, bits: int = 8, block: int = 128, pack_axis: int = -2,
    block_axis: int = -1, k_axes: int = 1, n_axes: int = 1,
) -> QuantizedTensor:
    """Quantize ``x`` [*lead, *K axes, *N axes] (``k_axes`` contracted axes,
    then ``n_axes`` output axes) to its matrix form.  Blocks run along the
    flattened N and never straddle the last axis of ``x``."""
    if bits not in (8, 4):
        raise ValueError(f"bits must be 8 or 4, got {bits}")
    orig_shape = tuple(x.shape)
    if block_axis == -2:
        if bits != 8:
            raise ValueError("blocks along the contracted axis are int8 only")
        # The turned weight [.., N, K] quantized along ITS last axis is
        # stored turned again: as x lies.
        qt = quantize(jnp.swapaxes(x, -1, -2), bits, block)
        return dataclasses.replace(
            qt, orig_shape=orig_shape, pack_axis=pack_axis, block_axis=-2)
    if pack_axis not in (-1, -2):
        raise ValueError(f"pack_axis must be -1 or -2, got {pack_axis}")
    block = min(block, x.shape[-1])
    if x.shape[-1] % block:
        # shrink to the largest common divisor so any width quantizes
        block = math.gcd(x.shape[-1], block)
    tail = k_axes + n_axes
    lead = orig_shape[: x.ndim - tail]
    k = math.prod(orig_shape[x.ndim - tail: x.ndim - n_axes])
    n = math.prod(orig_shape[x.ndim - n_axes:])
    xb = jnp.asarray(x, jnp.float32).reshape(*lead, k, n // block, block)
    qmax = 127.0 if bits == 8 else 7.0
    absmax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0)
    q = jnp.clip(jnp.round(xb / scale), -qmax, qmax).astype(jnp.int8)
    # Laid down turned, K on the lanes: the values are the [K, N] matrix's.
    q = jnp.swapaxes(q.reshape(*lead, k, n), -1, -2)  # [*lead, n, k]
    scale = jnp.swapaxes(scale[..., 0], -1, -2)  # [*lead, n_blocks, k]
    if bits == 4:
        a = q.ndim + pack_axis
        if q.shape[a] % 2:
            raise ValueError(
                f"int4 packing requires even size along pack_axis {pack_axis} "
                f"(shape {orig_shape} as a matrix {q.shape})"
            )
        idx_lo = [slice(None)] * q.ndim
        idx_hi = [slice(None)] * q.ndim
        idx_lo[a] = slice(0, None, 2)
        idx_hi[a] = slice(1, None, 2)
        lo = q[tuple(idx_lo)] & 0x0F
        hi = (q[tuple(idx_hi)] & 0x0F) << 4
        q = (lo | hi).astype(jnp.int8)
    return QuantizedTensor(
        data=q, scale=scale, bits=bits, orig_shape=orig_shape,
        pack_axis=pack_axis, k_axes=k_axes, n_axes=n_axes,
    )


def dequantize(qt: QuantizedTensor, dtype: Any = jnp.float32) -> jax.Array:
    """The weight as the model has it, [*lead, *K axes, *N axes].  Shapes
    derive from data/scale and the TRAILING entries of orig_shape: a
    per-layer slice of a stacked [L, ...] QuantizedTensor carries stale
    leading entries but self-consistent data/scale."""
    qt = qt.layer_slice()
    if qt.block_axis == -2:  # the turned weight's stored form: see quantize
        turned = dataclasses.replace(qt, block_axis=-1)
        return jnp.swapaxes(dequantize(turned, dtype), -1, -2)
    q = qt.data
    if qt.bits == 4:
        a = q.ndim + qt.pack_axis
        lo = (q << 4).astype(jnp.int8) >> 4  # sign-extend low nibble
        hi = q >> 4  # arithmetic shift sign-extends high nibble
        shape = list(q.shape)
        shape[a] *= 2
        q = jnp.stack([lo, hi], axis=a + 1).reshape(shape)
    qf = q.astype(jnp.float32)
    n, k = q.shape[-2:]
    n_blocks = qt.scale.shape[-2]
    qb = qf.reshape(*q.shape[:-2], n_blocks, n // n_blocks, k)
    out = (qb * qt.scale[..., :, None, :]).reshape(q.shape)
    return jnp.swapaxes(out, -1, -2).reshape(qt.unpacked_shape).astype(dtype)


# (contracted axes, output axes) of the block leaves that are no plain
# matrix: wq/wk/wv [D, H, hd] put out two axes, wo [H, hd, D] contracts two.
_AXES_BY_NAME = {"wq": (1, 2), "wk": (1, 2), "wv": (1, 2), "wo": (2, 1)}


# Bias leaves by exact name — matched explicitly (not by "b" prefix) so a
# future weight whose name starts with "b" is not silently left unquantized.
_BIAS_NAMES = frozenset({"bq", "bk", "bv", "bo", "b_in", "b_out", "b_gate", "b_up", "b_down"})


# Hybrid-family leaves (blocks/conv/..., blocks/moe/...) that stay float
# although they have two axes or more: the convolution's taps [L, D, K], and
# the router [L, D, E] and the selection bias [L, E], which decide the
# chosen set in float32; of a latent-attention layer (blocks/mla/...) the
# down-projection W_kva [L, D, r + rope], whose 576 columns at A.X-K1's
# widths are not whole 128-wide blocks, and the up-projection W_kvb
# [L, r, H * (nope + v)], which an admission reads as it lies and a decode
# step transposed (the absorbed form): both in the model's dtype, so the two
# paths read the same stored values; of a retention layer (blocks/ret/...)
# the gate's projection ``wg`` [L, D, KVH], 8 columns wide; of a Mamba-2
# layer (blocks/ssm/...) the convolution's taps and bias and a head's
# ``A_log``, ``dt_bias`` and ``D`` [L, heads], which set what a state forgets;
# of a gated delta-rule layer (blocks/gdn/...) the taps, ``A_log`` and
# ``dt_bias`` the same and ``w_ba`` [L, D, 2 heads], which gives a token's
# ``beta`` and decay; of an expert layer the shared expert's scalar gate
# ``shared_gate`` [L, D].
_HYBRID_FLOAT = frozenset({"taps", "router", "expert_bias", "wkv_a", "wkv_b",
                           "wg", "A_log", "dt_bias", "D", "conv_bias",
                           "w_ba", "shared_gate"})


def block_axis_of(path: str) -> int:
    """The axis a named leaf's absmax blocks run along: the contracted one
    for the expert stacks [E, K, N] under ``.../experts/`` (consumed by
    ops/moe_experts.py), the last one for every other weight."""
    return -2 if "experts" in path.split("/") else -1


def _should_quantize(path: str, x: Any) -> bool:
    if not hasattr(x, "ndim") or x.ndim < 2:
        return False
    leaf = path.split("/")[-1]
    if path.startswith(("blocks/conv/", "blocks/moe/", "blocks/mla/",
                        "blocks/ret/", "blocks/ssm/", "blocks/gdn/")) \
            and leaf in _HYBRID_FLOAT:
        return False
    if "norm" in path or "ln" in path.split("/")[-2:][0]:
        return False
    if leaf in _BIAS_NAMES:
        return False
    return True


def leaf_plan(path: str, x: Any) -> tuple[bool, int, int]:
    """(quantize?, k_axes, n_axes) for a named leaf — the single source of
    truth for which leaves quantize and which of their axes contract,
    shared by quantize_tree and streaming builders
    (models.model.init_params_quantized generates and quantizes on the
    device leaf by leaf and must make the exact decisions the serving path
    makes).  A leaf with heads that some family
    stores flat already (the hybrid's wq [D, H * hd]) is a plain matrix."""
    if not _should_quantize(path, x):
        return False, 1, 1
    k_axes, n_axes = _AXES_BY_NAME.get(path.split("/")[-1], (1, 1))
    # params["blocks"] leaves carry the stacked layer axis in front.
    if x.ndim - path.startswith("blocks/") < k_axes + n_axes:
        return True, 1, 1
    return True, k_axes, n_axes


def quantize_tree(params: Any, bits: int = 8, block: int = 128) -> Any:
    """Quantize matmul weights in a param tree; other leaves pass through."""

    def visit(path, x):
        key = "/".join(str(getattr(p, "key", p)) for p in path)
        should, k_axes, n_axes = leaf_plan(key, x)
        if should:
            return quantize(x, bits=bits, block=block, k_axes=k_axes,
                            n_axes=n_axes, block_axis=block_axis_of(key))
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
# KV-cache quantization (int8 KV pages: models/kv_cache.py QuantKVCache)
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
