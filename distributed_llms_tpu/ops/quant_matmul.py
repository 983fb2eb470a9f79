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

The operands are the STACKED leaves, every layer's weight [L, N, K] and
scales [L, N/block, K] as checkpoint/quantize.py stores them, both with K
on the lanes, and the index of the layer to read, a scalar-prefetch operand
the BlockSpecs' index maps take (as ops/moe_experts.py and the paged decode
kernel do).  A Pallas call takes each operand as a buffer of its own, so a
layer sliced out of its stack is a copy: 11.4 GB read and written a decode
step in qwen2-7b, a third of the step (PERF.md, PR 29).  A weight that is no
stack goes in as a stack of one.

The dequantization is lane-dense (PERF.md, PR 33): a block of 128 weight
rows (output columns) shares ONE row of scales, so a block is dequantized
by one multiply with that row broadcast down the sublanes, and contracted
with the activations on the last axis of both (``x . w^T``, the form
ops/decode_attn.py runs against its key rows).  A weight with N on its
lanes wants a column of scales broadcast over the lanes for every register
of weights, and takes twice what its bytes take.  K is summed in runs of
512 (:func:`_tiles`) whatever the tile, in float32, so a result does not
depend on the tile.

An admission pads its prompt to a bucket, and every row tile of 256 rows
streams and dequantizes the whole weight again.  Where the caller knows how
many of the M rows are real (``rows``) and M is more than two row tiles, the
kernel's grid ends at the last row tile that holds a real row (a traced
bound: the same kernel, the same index maps), so the tiles past it fetch
nothing and compute nothing, and a second, trivial kernel writes them as
zeros where they lie (:func:`_zero_kernel`).  The tile that holds the last
real row is computed whole, so a real row's result does not depend on the
count.  A call of one or two row tiles, or with no count, is the call
without it: a caller that pads to powers of two never leaves the second of
two tiles empty, and every program that holds a counted call costs set-up a
half second more.  (A branch around the kernel's body in a grid over every tile does the same
on the chip and costs five times the body's trace on the server's host, a
fifth of a second a kernel, every boot: PERF.md, PR 39.)

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

# The kernel adds up K in runs of this many lanes, in float32 (the first
# candidate that divides K; grids tile exactly, nothing is masked).
_BK_CANDIDATES = (512, 256, 128)
_BM_MAX = 256
# One int8 weight tile, double-buffered: whole rows of K where a block of
# 128 of them fits, so that a decode step's matmul is tens of grid steps of
# one run of bytes each (0.5 us a step is what 512 KB take).
_TILE_BYTES = 2 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))  # x [M, K] . w [N, K]^T


def _pick(n: int, candidates: tuple[int, ...]) -> int | None:
    for c in candidates:
        if n % c == 0:
            return c
    return None


def _tiles(k: int, n: int, block: int, bits: int = 8
           ) -> tuple[int, int, int] | None:
    """(bn, bk, ck) of the weight tile [bn, bk] of the stored [N, K] and of
    the run of K the kernel sums at a time, or None if the shape cannot be
    tiled.  K is summed in runs of ck whatever the tile, so a result does
    not depend on the tile.  A tile is whole blocks of rows, dividing N,
    whose scale rows the kernel can address: the whole of N/block, or a
    multiple of 8 of them, or a divisor of 8 (the float32 sublane tile of
    the stored [N/block, K] scales, which 8 // rows tiles then share).
    Whole rows of K come first, as many blocks as fit ``_TILE_BYTES``: a
    tile is then one run of bytes and the activations stay where they are
    from one tile to the next.  Where not one block of whole rows fits
    (K of 18,432 and more), the most rows that do, so that the activations
    pass as few times as can be: every row's runs of 512 for qwen2's w_down
    ([3584, 512] of [3584, 18944]), [1024, 2048] for A.X-K1's ([7168,
    18432]); a few rows of half a K each ([128, 9216]) took 1.4 to 2.5
    times as long (PERF.md, PR 33).  A packed int4 tile is one run of at
    most four blocks: its unpacking unrolled over a whole tile takes the
    compiler 5 s a kernel, not half of one."""
    ck = _pick(k, _BK_CANDIDATES)
    if ck is None or block % 128 or n % block:
        return None
    nb = n // block
    if bits == 4:
        return (max(per for per in (4, 2, 1) if nb % per == 0) * block, ck, ck)
    pers = [per for per in range(nb, 0, -1) if nb % per == 0
            and (per == nb or per % 8 == 0 or 8 % per == 0)]
    part = [bk for bk in range(k - ck, 0, -ck) if k % bk == 0]
    for per, bk in ([(per, k) for per in pers]
                    + [(per, bk) for per in pers for bk in part]):
        if per * block * bk <= _TILE_BYTES:
            return (per * block, bk, ck)
    return None


def _row_tile(m: int) -> tuple[int, int]:
    """(bm, m_pad): the rows of a row tile and M padded to whole tiles."""
    bm = min(_BM_MAX, max(16, -(-m // 16) * 16))
    return bm, -(-m // bm) * bm


def live_rows(m: int, rows: int) -> int:
    """Of a call's ``m`` rows, those the kernel computes when told that the
    first ``rows`` are real: the row tiles that hold one, and all of ``m``
    where it is one tile or two (:func:`_qmm_flat`).  Host arithmetic, for
    the batcher's counters."""
    bm, m_pad = _row_tile(m)
    return m if m_pad <= 2 * bm else min(m, -(-rows // bm) * bm)


def _unpack_int4_rows(q: jax.Array) -> jax.Array:
    """[Np, K] int32 packed nibbles -> [2*Np, K] int32 values.  Low nibble =
    even stored row, high = odd (quantize() packs down the stored rows, N):
    sign-extend via int32 shifts, then a sublane interleave, which Mosaic
    supports at any lane width.  Shared by the kernel and its flat-dequant
    fallback so the two layouts cannot diverge."""
    lo = (q << 28) >> 28
    hi = (q << 24) >> 28
    return jnp.stack([lo, hi], axis=1).reshape(q.shape[0] * 2, q.shape[1])


def _kernel(ly_ref, x_ref, q_ref, s_ref, o_ref, acc_ref, *, bits, block, ck,
            share, nk, out_dtype):
    del ly_ref  # read by the index maps only
    j, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    bn, bk = o_ref.shape[1], x_ref.shape[1]
    per, packed = bn // block, block // 2 if bits == 4 else block
    # A block of scale rows longer than the tile's own serves ``share``
    # j-tiles: this one's rows start at ``base``.
    base = (j % share) * per if share > 1 else 0
    for b in range(per):
        q = q_ref[b * packed:(b + 1) * packed, :].astype(jnp.int32)
        if bits == 4:
            q = _unpack_int4_rows(q)
        # [block, bk] weights times their [1, bk] row of scales, in float32
        w = (q.astype(jnp.float32) * s_ref[pl.ds(base + b, 1), :]
             ).astype(x_ref.dtype)
        for r in range(bk // ck):
            acc_ref[:, b * block:(b + 1) * block] += jax.lax.dot_general(
                x_ref[:, r * ck:(r + 1) * ck], w[:, r * ck:(r + 1) * ck],
                _NT, preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        o_ref[:] = acc_ref[:].astype(out_dtype)


def _zero_kernel(first_ref, y_ref, o_ref):
    """Zeros into the row tiles the matmul's grid did not reach (the index
    map starts at ``first_ref[0]``).  Zeros and not what lay there: later
    layers mask padding by position, and 0 times a NaN is a NaN."""
    del first_ref, y_ref  # the output is y itself: the other tiles stay
    o_ref[:] = jnp.zeros_like(o_ref)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "bm", "tiles", "interpret", "vma"),
)
def _quant_matmul_2d(
    x: jax.Array,  # [M, K] float (M padded to a multiple of bm by caller)
    q: jax.Array,  # [L, N, K] int8, or [L, N//2, K] packed int4 (row-packed)
    s: jax.Array,  # [L, N // block, K] float32, as stored
    layer: jax.Array,  # [1] int32: the layer of the stack to read
    real: jax.Array | None = None,  # [1] int32: the first ``real`` of the M
    #   rows are real (a call of more than two row tiles); None: all of them
    *,
    bits: int,
    bm: int,
    tiles: tuple[int, int, int],  # :func:`_tiles`
    interpret: bool = False,
    vma: frozenset = frozenset(),  # varying manual axes inside shard_map
) -> jax.Array:
    m, k_dim = x.shape
    nb = s.shape[1]
    n = q.shape[1] * (2 if bits == 4 else 1)
    block = n // nb
    bn, bk, ck = tiles
    # The row tiles that hold a real row: the grid stops there (a traced
    # bound), so the tiles past them are not fetched, dequantized or
    # multiplied, and the kernel and its index maps are the same either way.
    live = m // bm if real is None else jnp.clip(
        pl.cdiv(real[0], bm), 0, m // bm)
    grid = (live, n // bn, k_dim // bk)
    # Scale rows a block: the tile's own, or 8 that 8 // (its own) j-tiles
    # share (a tile whose blocks do not fill the sublanes).
    rows = bn // block
    if bn != n and rows % 8:
        rows = 8
    share = rows // (bn // block)  # j-tiles a block of scale rows serves
    kernel = functools.partial(
        _kernel, bits=bits, block=block, ck=ck, share=share, nk=grid[2],
        out_dtype=x.dtype,
    )
    y = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda mi, j, k, ly: (mi, k)),
                pl.BlockSpec((None, bn // 2 if bits == 4 else bn, bk),
                             lambda mi, j, k, ly: (ly[0], j, k)),
                pl.BlockSpec((None, rows, bk),
                             lambda mi, j, k, ly: (ly[0], j // share, k)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda mi, j, k, ly: (mi, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype, vma=vma),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="_quant_matmul_2d",  # the operation's name in a trace
    )(layer, x, q, s)
    if real is None:
        return y
    # ... and are written as zeros, where they lie in y (:func:`_zero_kernel`).
    return pl.pallas_call(
        _zero_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m // bm - live, n // bn),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(
                (bm, bn), lambda mi, j, first: (first[0] + mi, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype, vma=vma),
        input_output_aliases={1: 0},
        interpret=interpret,
        name="_quant_matmul_2d_padding",
    )(live.reshape(1), y)


def _dequant_flat(q2: jax.Array, s2: jax.Array, bits: int, dtype) -> jax.Array:
    """Dequantize one layer's stored operands ([N(-packed), K] and
    [N/block, K]) to [N, K] without the kernel — the local fallback when a
    (shard's) shape is untileable.  Same math as
    checkpoint.quantize.dequantize."""
    q = q2.astype(jnp.int32)
    if bits == 4:
        q = _unpack_int4_rows(q)
    n, k = q.shape
    nb = s2.shape[0]
    w = q.astype(jnp.float32).reshape(nb, n // nb, k) * s2[:, None, :]
    return w.reshape(n, k).astype(dtype)


def _qmm_flat(x2: jax.Array, q3: jax.Array, s3: jax.Array, layer: jax.Array,
              rows: jax.Array | None = None, *, bits: int, interpret: bool
              ) -> jax.Array:
    """[M, K] @ dequant(layer ``layer`` [1] of [L, N(-packed), K])^T with
    the scales [L, N/block, K].  Shapes are the LOCAL ones (per shard,
    inside shard_map): tile sizes and M padding derive from them;
    untileable shapes take the dequant+matmul fallback on the layer's
    slice, so this is total over any shard.  ``rows`` [1] (None: M) says
    how many leading rows are real: the kernel is handed it where M is more
    than two row tiles and leaves the tiles past it as zeros; every other
    call is the call without it."""
    m, k = x2.shape
    nb = s3.shape[1]
    n = q3.shape[1] * (2 if bits == 4 else 1)
    tiles = _tiles(k, n, n // nb, bits)
    # Inside a vma-checked shard_map (the pipeline stage body) operands
    # carry varying manual axes; the kernel's out_shape must declare the
    # same set.  The Pallas HLO *interpreter* (off-TPU test path) loses vma
    # on its internal dynamic_slices (same limitation as ops/flash.py), so
    # it runs the numerically-identical flat dequant there.
    vma = frozenset().union(*(jax.typeof(a).vma for a in (x2, q3, s3)))
    if tiles is None or (vma and interpret):
        dispatch.record("quant_matmul", "fallback", (m, k, n, bits))
        w = _dequant_flat(q3[layer[0]], s3[layer[0]], bits, x2.dtype)
        return jax.lax.dot_general(x2, w, _NT)
    dispatch.record(
        "quant_matmul", "interpret" if interpret else "kernel",
        (m, k, n, bits),
    )
    METRICS.inc("ops.dispatch.quant_matmul.k_minor")
    if q3.shape[0] > 1:  # a stack read by index, not a layer's slice
        METRICS.inc("ops.dispatch.quant_matmul.stacked")
    bm, m_pad = _row_tile(m)
    if m_pad != m:
        x2 = jnp.pad(x2, ((0, m_pad - m), (0, 0)))
    if m_pad <= 2 * bm:  # one row tile, or two of which a bucket fills both
        rows = None
    return _quant_matmul_2d(
        x2, q3, s3, layer, rows, bits=bits, bm=bm, tiles=tiles,
        interpret=interpret, vma=vma,
    )[:m]


def _qmm_sharded(mesh, x2, q3, s3, layer, rows=None, *, bits: int,
                 interpret: bool, shard: str | None, whole: int, batch: int
                 ) -> jax.Array:
    """:func:`_qmm_flat` per shard of a tensor-parallel mesh
    (:func:`dispatch.per_shard`).  ``shard`` is the
    weight's Megatron role: "n" splits the output axis over 'model'
    (wq/wk/wv, w_in/w_gate/w_up — embarrassingly parallel), "k" splits the
    contracted axis (wo, w_out/w_down — partial products, psum over
    'model').  The specs mirror parallel.specs.param_specs (the layer axis
    of the stacks and the layer index unsharded; the weight [L, N, K] and
    its scales [L, N/block, K] split alike), so placed weights enter
    without a reshard: the split axis must divide into ``whole`` slices of
    the weight's first such axis (heads, for the attention weights), whole
    rows or columns and whole scale blocks, or it stays replicated
    (redundant compute, same numerics).  ``batch`` is x's leading axis,
    which shards over 'data' when it divides; the count of real rows
    (``rows``, see :func:`_qmm_flat`) rides in replicated, like the layer
    index, and is dropped where the rows are split."""
    m_ax = dispatch.axis(mesh, "data", batch)
    if m_ax:
        rows = None
    n_ax = k_ax = None
    if shard == "n":
        n_ax = dispatch.axis(mesh, "model", whole, q3.shape[1], s3.shape[1])
    elif shard == "k":
        k_ax = dispatch.axis(mesh, "model", whole, q3.shape[2], s3.shape[2])

    def body(x2, q3, s3, layer, rows):
        y = _qmm_flat(x2, q3, s3, layer, rows, bits=bits, interpret=interpret)
        return jax.lax.psum(y, k_ax) if k_ax else y

    w_spec = P(None, n_ax, k_ax)
    return dispatch.per_shard(
        body, mesh, (P(m_ax, k_ax), w_spec, w_spec, P(None),
                     None if rows is None else P(None)), P(m_ax, n_ax),
    )(x2, q3, s3, layer, rows)


def quant_contract(
    x: jax.Array, qt, k_lead: int, eq: str | None = None, *,
    shard: str | None = None, interpret: bool = False,
    rows: jax.Array | None = None,
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
    ``rows`` ([1] int32, None: all): of x flattened to [M, K] only the
    first ``rows`` rows are real (one padded sequence); the kernel may
    leave the others as zeros (:func:`_qmm_flat`).
    """
    k_shape, out_tail = qt.tail_shape
    lead = x.shape[: x.ndim - k_lead]
    k = math.prod(k_shape)
    x2 = x.reshape(-1, k)

    mode = "interpret" if interpret else dispatch.kernel_mode("DLT_QUANT_MATMUL")
    # The kernel reads matrices [N, K] with blocks of rows, int4 pairs down
    # the rows, one at a time or out of a stack [L, N, K] by index.
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
            y2 = _qmm_flat(x2, q3, s3, layer, rows, **kw)
        else:
            y2 = _qmm_sharded(
                mesh, x2, q3, s3, layer, rows, shard=shard,
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
