"""Fused dequantize-matmul Pallas kernel for weight-only quantized serving.

Replaces the dequantize-then-einsum path (models/model.py run_blocks) for
int8 / packed-int4 blockwise-quantized weights (checkpoint/quantize.py).
On the dequantize path XLA materializes a full-precision copy of every
weight in HBM each layer — measured ~9 bytes/param of HBM traffic per
decode step on a v5e (BASELINE.md config 3-int8: 52.8 tok/s, ~12% of HBM
bandwidth).  Decode is weight-bandwidth-bound, so the ceiling is set by
bytes-read-per-param: this kernel streams the int8/int4 weights HBM→VMEM,
dequantizes tiles in VMEM (VPU), and feeds the MXU directly — ~1.1 (int8)
or ~0.6 (int4) bytes/param, never writing a dequantized copy back to HBM.

The reference's quantization design (snippets.md:675-833) dequantized to
full precision before each use; there is no fused-kernel counterpart to
cite — this is the TPU-native replacement for that whole mechanism.

Numerics match checkpoint.quantize.dequantize: q is dequantized as
``f32(q) * scale`` then cast to the compute dtype before the matmul, with
f32 accumulation.  The kernel is inference-only (no VJP; training always
runs full-dtype weights).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from . import dispatch

# Candidate tile sizes, largest first; a dimension uses the first candidate
# that divides it (grids must tile exactly — no masking on the K/N axes).
_BK_CANDIDATES = (512, 256, 128)
_BN_CANDIDATES = (512, 256, 128)
_BM_MAX = 256


def _pick(n: int, candidates: tuple[int, ...]) -> int | None:
    for c in candidates:
        if n % c == 0:
            return c
    return None


def _unpack_int4_rows(q: jax.Array) -> jax.Array:
    """[Kp, N] int32 packed nibbles -> [2*Kp, N] int32 values.  Low nibble =
    even K-row, high = odd (quantize() packs along the reduction axis):
    sign-extend via int32 shifts, then a sublane interleave, which Mosaic
    supports at any lane width.  Shared by the kernel and its flat-dequant
    fallback so the two layouts cannot diverge."""
    lo = (q << 28) >> 28
    hi = (q << 24) >> 28
    return jnp.stack([lo, hi], axis=1).reshape(q.shape[0] * 2, q.shape[1])


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, bits, block, nk, out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[:].astype(jnp.int32)  # [bk, bn] int8, or [bk//2, bn] packed int4
    if bits == 4:
        q = _unpack_int4_rows(q)

    s = s_ref[0]  # [bk, bn // block] float32 (j-tile's slice of [nj, K, nb])
    bk, bn = q.shape
    wf = q.astype(jnp.float32).reshape(bk, bn // block, block) * s[:, :, None]
    w = wf.reshape(bk, bn).astype(x_ref.dtype)
    acc_ref[:] += jnp.dot(x_ref[:], w, preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        o_ref[:] = acc_ref[:].astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "block", "bm", "bk", "bn", "interpret", "vma"),
)
def _quant_matmul_2d(
    x: jax.Array,  # [M, K] float (M padded to a multiple of bm by caller)
    q: jax.Array,  # [K, N] int8, or [K//2, N] packed int4 (row-packed)
    s: jax.Array,  # [nj, K, bn // block] float32 — scales regrouped per
    #               N-tile so each grid step reads a full-last-dim block
    #               (Mosaic requires last-dim tiles of 128 or the whole axis)
    *,
    bits: int,
    block: int,
    bm: int,
    bk: int,
    bn: int,
    interpret: bool = False,
    vma: frozenset = frozenset(),  # varying manual axes inside shard_map
) -> jax.Array:
    m, k_dim = x.shape
    n = q.shape[1]
    grid = (m // bm, n // bn, k_dim // bk)
    bkp = bk // 2 if bits == 4 else bk
    kernel = functools.partial(
        _kernel, bits=bits, block=block, nk=grid[2], out_dtype=x.dtype
    )
    flops = 2 * m * k_dim * n
    out_shape = jax.ShapeDtypeStruct((m, n), x.dtype, vma=vma)
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, j, k: (mi, k), memory_space=pltpu.VMEM),
            pl.BlockSpec((bkp, bn), lambda mi, j, k: (k, j), memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (1, bk, bn // block),
                lambda mi, j, k: (j, k, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (bm, bn), lambda mi, j, k: (mi, j), memory_space=pltpu.VMEM
        ),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=q.size + s.size * 4 + x.size * x.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(x, q, s)


def flatten_qt(qt, k_lead: int):
    """Reshape qt.data/scale to 2D for a [K, N] contraction over the first
    ``k_lead`` axes of the (logical, unpacked) weight.  Quant blocks run
    along the LAST axis only, so flattening trailing axes keeps blocks
    contiguous (block divides the last axis by quantize()'s construction).
    For int4 the data rows are packed pairs (K//2 of them); scale rows stay
    per-unpacked-row."""
    data, scale = qt.data, qt.scale
    kq = 1
    for d in data.shape[:k_lead]:
        kq *= d
    ks = 1
    for d in scale.shape[:k_lead]:
        ks *= d
    q2 = data.reshape(kq, -1)
    s2 = scale.reshape(ks, -1)
    n = q2.shape[1]
    block = n // s2.shape[1]
    return q2, s2, n, block


def _dequant_flat(q2: jax.Array, s2: jax.Array, bits: int, dtype) -> jax.Array:
    """Dequantize flat row-packed operands (the kernel's own layout) without
    the kernel — the local fallback when a (shard's) shape is untileable.
    Same math as checkpoint.quantize.dequantize for this layout."""
    q = q2.astype(jnp.int32)
    if bits == 4:
        q = _unpack_int4_rows(q)
    n = q.shape[1]
    nb = s2.shape[1]
    block = n // nb
    w = (
        q.astype(jnp.float32).reshape(q.shape[0], nb, block) * s2[:, :, None]
    ).reshape(q.shape[0], n)
    return w.astype(dtype)


def _qmm_flat(x2: jax.Array, q2: jax.Array, s2: jax.Array, *, bits: int,
              interpret: bool) -> jax.Array:
    """[M, K] @ dequant([K(-packed), N]) from flat operands.  Shapes are the
    LOCAL ones (per shard, inside shard_map): tile sizes, M padding and the
    scale regroup all derive from them; untileable shapes take the
    dequant+matmul fallback, so this is total over any shard."""
    m, k = x2.shape
    n = q2.shape[1]
    nb = s2.shape[1]
    block = n // nb
    bk = _pick(k, _BK_CANDIDATES)
    bn = _pick(n, _BN_CANDIDATES)
    tileable = (
        bk is not None and bn is not None
        and block % 128 == 0 and bn % block == 0
        and (bits == 8 or bk // 2 >= 8)
    )
    # Inside a vma-checked shard_map (the pipeline stage body) operands
    # carry varying manual axes; the kernel's out_shape must declare the
    # same set.  The Pallas HLO *interpreter* (off-TPU test path) loses vma
    # on its internal dynamic_slices (same limitation as ops/flash.py), so
    # it runs the numerically-identical flat dequant there.
    vma = frozenset().union(*(jax.typeof(a).vma for a in (x2, q2, s2)))
    if not tileable or (vma and interpret):
        dispatch.record("quant_matmul", "fallback", (m, k, n, bits))
        return x2 @ _dequant_flat(q2, s2, bits, x2.dtype)
    dispatch.record(
        "quant_matmul", "interpret" if interpret else "kernel",
        (m, k, n, bits),
    )
    bm = min(_BM_MAX, max(16, -(-m // 16) * 16))
    m_pad = -(-m // bm) * bm
    if m_pad != m:
        x2 = jnp.pad(x2, ((0, m_pad - m), (0, 0)))
    # Regroup scales per N-tile: [K, NB] -> [nj, K, nb].  Tiny arrays
    # (params/block floats); the transpose is a few % of the int8 bytes.
    nj, nbt = n // bn, bn // block
    s3 = s2.reshape(k, nj, nbt).transpose(1, 0, 2)
    return _quant_matmul_2d(
        x2, q2, s3, bits=bits, block=block, bm=bm, bk=bk, bn=bn,
        interpret=interpret, vma=vma,
    )[:m]


def _qmm_sharded(mesh, x2, q2, s2, *, bits: int, interpret: bool,
                 shard: str | None, whole: int, batch: int) -> jax.Array:
    """:func:`_qmm_flat` per shard of a tensor-parallel mesh
    (:func:`dispatch.per_shard`).  ``shard`` is the
    weight's Megatron role: "n" splits the output axis over 'model'
    (wq/wk/wv, w_in/w_gate/w_up — embarrassingly parallel), "k" splits the
    contracted axis (wo, w_out/w_down — partial products, psum over
    'model').  The specs mirror parallel.specs.param_specs, so placed
    weights enter without a reshard: the split axis must divide into
    ``whole`` slices of the weight's first such axis (heads, for the
    attention weights), whole rows or columns and whole scale blocks, or
    it stays replicated (redundant compute, same numerics).  ``batch`` is
    x's leading axis, which shards over 'data' when it divides."""
    m_ax = dispatch.axis(mesh, "data", batch)
    n_ax = k_ax = None
    if shard == "n":
        n_ax = dispatch.axis(mesh, "model", whole, q2.shape[1], s2.shape[1])
    elif shard == "k":
        k_ax = dispatch.axis(mesh, "model", whole, q2.shape[0], s2.shape[0])

    def body(x2, q2, s2):
        y = _qmm_flat(x2, q2, s2, bits=bits, interpret=interpret)
        return jax.lax.psum(y, k_ax) if k_ax else y

    return dispatch.per_shard(
        body, mesh, (P(m_ax, k_ax), P(k_ax, n_ax), P(k_ax, n_ax)),
        P(m_ax, n_ax),
    )(x2, q2, s2)


def quant_contract(
    x: jax.Array, qt, k_lead: int, eq: str | None = None, *,
    shard: str | None = None, interpret: bool = False,
):
    """x[..., K-axes] @ dequant(W)[K-axes, N-axes] with W blockwise-quantized.

    ``k_lead``: how many leading axes of the weight contract (1 for
    wq/wk/wv/w_in/w_gate/w_up/w_down, 2 for wo [H, hd, D]).  The matching
    trailing axes of ``x`` flatten to K; the weight's remaining axes are
    restored on the output.  Dispatches to the Pallas kernel on TPU (or when
    DLT_QUANT_MATMUL=kernel|interpret), per shard under a tensor-parallel
    mesh (``shard``: see :func:`_qmm_sharded`); otherwise dequantize +
    einsum over ``eq``, which XLA partitions itself.
    """
    out_tail = list(qt.data.shape[k_lead:])  # N axes are never packed
    lead = x.shape[: x.ndim - k_lead]
    k = math.prod(x.shape[x.ndim - k_lead:])
    x2 = x.reshape(-1, k)

    mode = "interpret" if interpret else dispatch.kernel_mode("DLT_QUANT_MATMUL")
    # int4: the kernel's sublane unpack (and _dequant_flat) assume the pack
    # pairs run along the LAST K axis (quantize_tree's convention).
    pack_ok = qt.bits == 8 or qt.data.ndim + qt.pack_axis == k_lead - 1
    if mode != "fallback" and pack_ok:
        q2, s2, _, _ = flatten_qt(qt, k_lead)
        kw = dict(bits=qt.bits, interpret=mode == "interpret")
        mesh = dispatch.mesh()
        if mesh is None:
            y2 = _qmm_flat(x2, q2, s2, **kw)
        else:
            y2 = _qmm_sharded(
                mesh, x2, q2, s2, shard=shard,
                whole=qt.data.shape[0] if shard == "k" else out_tail[0],
                batch=lead[0] if lead else 1, **kw,
            )
        return y2.reshape(*lead, *out_tail)

    # Fallback: dequantize then contract (XLA fuses what it can).  Matches
    # models/model.py's historical dequant-at-use numerics exactly.
    from ..checkpoint.quantize import dequantize

    dispatch.record(
        "quant_matmul", "fallback",
        (x2.shape[0], k, math.prod(out_tail), qt.bits),
    )
    w = dequantize(qt, x.dtype)
    if eq is not None:
        return jnp.einsum(eq, x, w)
    return (x2 @ w.reshape(k, -1)).reshape(*lead, *out_tail)
