"""Fused dequantize-matmul Pallas kernel for weight-only quantized serving.

Replaces the dequantize-then-einsum path (models/model.py run_blocks) for
int8 / packed-int4 blockwise-quantized weights (checkpoint/quantize.py).
On the dequantize path XLA materializes a full-precision copy of every
weight in HBM each layer, several bytes of HBM traffic a parameter per
decode step.  Decode is weight-bandwidth-bound (PERF.md, section 5:
``quant_matmul_roofline``), so the ceiling is set by
bytes-read-per-param: this kernel streams the int8/int4 weights HBM→VMEM,
dequantizes tiles in VMEM (VPU), and feeds the MXU directly, never writing
a dequantized copy back to HBM: 1 (int8) or 0.5 (int4) bytes a parameter
and 4/block more for the scales (1.03 at blocks of 128).

The operands are the STACKED leaves, every layer's weight [L, K, N] and
scales [L, N/block, K] as checkpoint/quantize.py stores them, and the
index of the layer to read, a scalar-prefetch operand the BlockSpecs'
index maps take (as ops/moe_experts.py and the paged decode kernel do).
A Pallas call takes each operand as a buffer of its own, so a layer sliced
out of its stack is a copy: 11.4 GB read and written a decode step in
qwen2-7b, a third of the step (PERF.md, PR 29).  A weight that is no stack
goes in as a stack of one.  The scales are stored the way the kernel reads
them, K on the lanes: a [K, N/block] array is padded to 128 lanes on the
device, as many bytes as the weight it belongs to.  The kernel turns a
scale tile's [rows, 512] pieces as it goes.  A weight tile is several
[512, 512] pieces (:func:`_tiles`), dequantized and multiplied one after
another in the order a grid of such tiles would take them: the result does
not depend on the tile, only the number of grid steps does.

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

from ..core.observability import METRICS
from . import dispatch

# One pass of the MXU's accumulation: the kernel adds up K in runs of this
# many rows (the first candidate that divides K; grids must tile exactly —
# no masking on the K/N axes), and a row of columns this wide at a time.
_BK_CANDIDATES = (512, 256, 128)
_BN_CANDIDATES = (512, 256, 128)
_BM_MAX = 256
# One int8 weight tile, double-buffered: several runs of rows or several
# rows of columns, so that a decode step's matmul is tens of grid steps and
# not hundreds (0.5 us a step is what a [512, 512] tile's bytes take).
_TILE_BYTES = 2 * 1024 * 1024


def _pick(n: int, candidates: tuple[int, ...]) -> int | None:
    for c in candidates:
        if n % c == 0:
            return c
    return None


def _tiles(k: int, n: int, block: int, bits: int = 8
           ) -> tuple[int, int, int, int] | None:
    """(bk, bn, ck, cn) of the weight tile [bk, bn] and of the [ck, cn]
    pieces the kernel dequantizes and multiplies one after another, or
    None if the shape cannot be tiled.  The pieces are what the candidates
    give (K is summed in runs of ck whatever the tile, so a result does
    not depend on the tile); the tile is the largest of whole pieces
    within ``_TILE_BYTES`` whose scale rows the kernel can address: bn
    either the whole of N, or a multiple of 8 blocks, or a piece that
    divides 8 blocks (the float32 sublane tile of the stored
    [N/block, K] scales).  A packed int4 tile is one piece: its unpacking
    unrolled sixteen times takes the compiler a minute a kernel."""
    ck, cn = _pick(k, _BK_CANDIDATES), _pick(n, _BN_CANDIDATES)
    if ck is None or cn is None or block % 128 or cn % block:
        return None
    best = (ck, cn)
    if bits == 4:
        return (ck, cn, ck, cn)
    for bk in range(ck, k + 1, ck):
        for bn in range(cn, n + 1, cn):
            rows = bn // block
            if (k % bk or n % bn or bk * bn > _TILE_BYTES
                    or not (bn == n or rows % 8 == 0 or bn == cn)):
                continue
            if (bk * bn, bn) > (best[0] * best[1], best[1]):
                best = (bk, bn)
    return (*best, ck, cn)


def _unpack_int4_rows(q: jax.Array) -> jax.Array:
    """[Kp, N] int32 packed nibbles -> [2*Kp, N] int32 values.  Low nibble =
    even K-row, high = odd (quantize() packs along the reduction axis):
    sign-extend via int32 shifts, then a sublane interleave, which Mosaic
    supports at any lane width.  Shared by the kernel and its flat-dequant
    fallback so the two layouts cannot diverge."""
    lo = (q << 28) >> 28
    hi = (q << 24) >> 28
    return jnp.stack([lo, hi], axis=1).reshape(q.shape[0] * 2, q.shape[1])


def _kernel(ly_ref, x_ref, q_ref, s_ref, o_ref, acc_ref, *, bits, block, ck,
            cn, nk, out_dtype):
    del ly_ref  # read by the index maps only
    j, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    bk, bn = x_ref.shape[1], o_ref.shape[1]
    ckp = ck // 2 if bits == 4 else ck
    rows, per = s_ref.shape[0], cn // block  # scale rows: held, a piece's
    for r in range(bk // ck):
        # [rows, ck] of the stored [N/block, K] scales, K on the lanes.  A
        # block shorter than its 8 rows holds 8 // per j-tiles' scales:
        # bring this tile's to the front.  Then turned, so that a block's
        # scales run down the weight piece's rows.
        sr = s_ref[:, r * ck:(r + 1) * ck]
        if bn // block < rows:
            held = sr
            for g in range(1, rows // per):
                sr = jnp.where(j % (rows // per) == g,
                               pltpu.roll(held, rows - g * per, 0), sr)
        st = sr.T  # [ck, rows]
        for c in range(bn // cn):
            q = q_ref[r * ckp:(r + 1) * ckp, c * cn:(c + 1) * cn]
            q = q.astype(jnp.int32)  # [ck, cn] int8, or [ck//2, cn] int4
            if bits == 4:
                q = _unpack_int4_rows(q)
            qf = q.astype(jnp.float32)
            w = jnp.concatenate(
                [qf[:, b * block:(b + 1) * block]
                 * st[:, c * per + b:c * per + b + 1] for b in range(per)],
                axis=1,
            ).astype(x_ref.dtype)
            acc_ref[:, c * cn:(c + 1) * cn] += jnp.dot(
                x_ref[:, r * ck:(r + 1) * ck], w,
                preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        o_ref[:] = acc_ref[:].astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "bm", "tiles", "interpret", "vma"),
)
def _quant_matmul_2d(
    x: jax.Array,  # [M, K] float (M padded to a multiple of bm by caller)
    q: jax.Array,  # [L, K, N] int8, or [L, K//2, N] packed int4 (row-packed)
    s: jax.Array,  # [L, N // block, K] float32, as stored
    layer: jax.Array,  # [1] int32: the layer of the stack to read
    *,
    bits: int,
    bm: int,
    tiles: tuple[int, int, int, int],  # :func:`_tiles`
    interpret: bool = False,
    vma: frozenset = frozenset(),  # varying manual axes inside shard_map
) -> jax.Array:
    m, k_dim = x.shape
    n, nb = q.shape[2], s.shape[1]
    block = n // nb
    bk, bn, ck, cn = tiles
    grid = (m // bm, n // bn, k_dim // bk)
    bkp = bk // 2 if bits == 4 else bk
    # Scale rows a block: the tile's own, or 8 that 8 // (its own) j-tiles
    # share (a tile of one piece whose blocks do not fill the sublanes).
    rows = bn // block
    if bn != n and rows % 8:
        rows = 8
    share = rows // (bn // block)  # j-tiles a block of scale rows serves
    kernel = functools.partial(
        _kernel, bits=bits, block=block, ck=ck, cn=cn, nk=grid[2],
        out_dtype=x.dtype,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda mi, j, k, ly: (mi, k)),
                pl.BlockSpec((None, bkp, bn),
                             lambda mi, j, k, ly: (ly[0], k, j)),
                pl.BlockSpec((None, rows, bk),
                             lambda mi, j, k, ly: (ly[0], j // share, k)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda mi, j, k, ly: (mi, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype, vma=vma),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret,
        name="_quant_matmul_2d",  # the operation's name in a trace
    )(layer, x, q, s)


def _dequant_flat(q2: jax.Array, s2: jax.Array, bits: int, dtype) -> jax.Array:
    """Dequantize one layer's stored operands ([K(-packed), N] and
    [N/block, K]) without the kernel — the local fallback when a (shard's)
    shape is untileable.  Same math as checkpoint.quantize.dequantize."""
    q = q2.astype(jnp.int32)
    if bits == 4:
        q = _unpack_int4_rows(q)
    n = q.shape[1]
    nb = s2.shape[0]
    w = (
        q.astype(jnp.float32).reshape(q.shape[0], nb, n // nb)
        * s2.T[:, :, None]
    ).reshape(q.shape[0], n)
    return w.astype(dtype)


def _qmm_flat(x2: jax.Array, q3: jax.Array, s3: jax.Array, layer: jax.Array,
              *, bits: int, interpret: bool) -> jax.Array:
    """[M, K] @ dequant(layer ``layer`` [1] of [L, K(-packed), N]) with the
    scales [L, N/block, K].  Shapes are the LOCAL ones (per shard, inside
    shard_map): tile sizes and M padding derive from them; untileable
    shapes take the dequant+matmul fallback on the layer's slice, so this
    is total over any shard."""
    m, k = x2.shape
    n, nb = q3.shape[2], s3.shape[1]
    tiles = _tiles(k, n, n // nb, bits)
    tileable = tiles is not None and (bits == 8 or tiles[2] // 2 >= 8)
    # Inside a vma-checked shard_map (the pipeline stage body) operands
    # carry varying manual axes; the kernel's out_shape must declare the
    # same set.  The Pallas HLO *interpreter* (off-TPU test path) loses vma
    # on its internal dynamic_slices (same limitation as ops/flash.py), so
    # it runs the numerically-identical flat dequant there.
    vma = frozenset().union(*(jax.typeof(a).vma for a in (x2, q3, s3)))
    if not tileable or (vma and interpret):
        dispatch.record("quant_matmul", "fallback", (m, k, n, bits))
        return x2 @ _dequant_flat(q3[layer[0]], s3[layer[0]], bits, x2.dtype)
    dispatch.record(
        "quant_matmul", "interpret" if interpret else "kernel",
        (m, k, n, bits),
    )
    if q3.shape[0] > 1:  # a stack read by index, not a layer's slice
        METRICS.inc("ops.dispatch.quant_matmul.stacked")
    bm = min(_BM_MAX, max(16, -(-m // 16) * 16))
    m_pad = -(-m // bm) * bm
    if m_pad != m:
        x2 = jnp.pad(x2, ((0, m_pad - m), (0, 0)))
    return _quant_matmul_2d(
        x2, q3, s3, layer, bits=bits, bm=bm, tiles=tiles,
        interpret=interpret, vma=vma,
    )[:m]


def _qmm_sharded(mesh, x2, q3, s3, layer, *, bits: int, interpret: bool,
                 shard: str | None, whole: int, batch: int) -> jax.Array:
    """:func:`_qmm_flat` per shard of a tensor-parallel mesh
    (:func:`dispatch.per_shard`).  ``shard`` is the
    weight's Megatron role: "n" splits the output axis over 'model'
    (wq/wk/wv, w_in/w_gate/w_up — embarrassingly parallel), "k" splits the
    contracted axis (wo, w_out/w_down — partial products, psum over
    'model').  The specs mirror parallel.specs.param_specs (the layer axis
    of the stacks and the layer index unsharded), so placed weights enter
    without a reshard: the split axis must divide into ``whole`` slices of
    the weight's first such axis (heads, for the attention weights), whole
    rows or columns and whole scale blocks, or it stays replicated
    (redundant compute, same numerics).  ``batch`` is x's leading axis,
    which shards over 'data' when it divides."""
    m_ax = dispatch.axis(mesh, "data", batch)
    n_ax = k_ax = None
    if shard == "n":
        n_ax = dispatch.axis(mesh, "model", whole, q3.shape[2], s3.shape[1])
    elif shard == "k":
        k_ax = dispatch.axis(mesh, "model", whole, q3.shape[1], s3.shape[2])

    def body(x2, q3, s3, layer):
        y = _qmm_flat(x2, q3, s3, layer, bits=bits, interpret=interpret)
        return jax.lax.psum(y, k_ax) if k_ax else y

    return dispatch.per_shard(
        body, mesh,
        (P(m_ax, k_ax), P(None, k_ax, n_ax), P(None, n_ax, k_ax), P(None)),
        P(m_ax, n_ax),
    )(x2, q3, s3, layer)


def quant_contract(
    x: jax.Array, qt, k_lead: int, eq: str | None = None, *,
    shard: str | None = None, interpret: bool = False,
):
    """x[..., K-axes] @ dequant(W)[K-axes, N-axes] with W blockwise-quantized.

    ``qt``: a ``QuantizedTensor`` of one weight, or a stack of them read at
    ``qt.layer`` (``QuantizedTensor.at``, what a layer scan hands over).
    ``k_lead``: how many leading axes of the weight contract (1 for
    wq/wk/wv/w_in/w_gate/w_up/w_down, 2 for wo [H, hd, D]).  The matching
    trailing axes of ``x`` flatten to K; the weight's output axes are
    restored on the output.  Dispatches to the Pallas kernel on TPU (or when
    DLT_QUANT_MATMUL=kernel|interpret), per shard under a tensor-parallel
    mesh (``shard``: see :func:`_qmm_sharded`); otherwise dequantize +
    einsum over ``eq`` on the layer's slice, which XLA partitions itself.
    """
    k_shape, out_tail = qt.tail_shape
    lead = x.shape[: x.ndim - k_lead]
    k = math.prod(k_shape)
    x2 = x.reshape(-1, k)

    mode = "interpret" if interpret else dispatch.kernel_mode("DLT_QUANT_MATMUL")
    # The kernel reads matrices [K, N] with blocks along N, int4 pairs down
    # the rows, one at a time or out of a stack [L, K, N] by index.
    stack = qt.data.ndim == (2 if qt.layer is None else 3)
    if (mode != "fallback" and stack and qt.block_axis == -1
            and k_lead == len(k_shape) and (qt.bits == 8 or qt.pack_axis == -2)):
        if qt.layer is None:
            q3, s3, layer = qt.data[None], qt.scale[None], 0
        else:
            q3, s3, layer = qt.data, qt.scale, qt.layer
        layer = jnp.asarray(layer, jnp.int32).reshape(1)
        kw = dict(bits=qt.bits, interpret=mode == "interpret")
        mesh = dispatch.mesh()
        if mesh is None:
            y2 = _qmm_flat(x2, q3, s3, layer, **kw)
        else:
            y2 = _qmm_sharded(
                mesh, x2, q3, s3, layer, shard=shard,
                whole=k_shape[0] if shard == "k" else out_tail[0],
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
