"""Expert FFNs without drops: (token, choice) pairs grouped by expert and
multiplied against the expert stacks, the int8 stacks never dequantized
whole.

``layers.moe_swiglu`` with the capacity rule (GShard buffers) computes a
fixed number of slots an expert and drops what overflows, so a row's result
depends on its batch-mates; a served model may not.  Here every pair is
computed: pairs are grouped by expert, each group is padded to whole row
tiles, and one grouped matmul a projection walks the tiles.  A tile belongs
to ONE expert, named by a scalar-prefetched table that the weight
BlockSpecs' index maps read, so an expert no token chose is never fetched,
an expert many tokens chose is fetched once a tile of them, and the work
grows with tokens x k, not tokens x experts.

Weights: ``w_gate_up`` [L, E, D, 2F] (gate and up side by side, one matmul)
and ``w_down`` [L, E, F, D], every expert layer's in one stack that the
kernel indexes by (layer, expert).  Quantized they are ``QuantizedTensor`` with
``block_axis=-2``: int8 data and one float32 absmax scale per 128 ROWS of the
contracted axis and output column, scale [L, E, K/128, N].  That axis, and not
the last one as for the 2-D weights of ops/quant_matmul.py, because the
scale tile is then lane-dense ([K/128, bn], stored unpadded) and the kernel
dequantizes a weight tile by splitting its rows into blocks of 128 and
multiplying each by its scale row: no lane is moved, where blocks along the
last axis cost a lane-splitting reshape of every tile.

The pairs' outputs come back as [S, k, D], a dead pair's zeros (an absent
expert's, a padding token's), for the caller's sum over k: a gather of
every pair's row of the grouped outputs, or, where a chip holds a share of
the experts and the list lies in HBM, a second kernel that fetches the rows
of the pairs that hold one (:func:`_pairs_rows`, ``moe_combine``): the same
array bit for bit, so the sum keeps its operand and its order.

Modes (``DLT_MOE_EXPERTS``: kernel | interpret | fallback | auto = kernel
iff TPU), recorded as ``ops.dispatch.moe_experts.<path>`` (and
``ops.dispatch.moe_combine.<path>`` where the second kernel is taken).  The fallback is
``jax.lax.ragged_dot`` over the same sorted pairs (dequantizing the stacks
first): the reference the kernel is parity-tested against, and the path of
float weights (tests, training).  Inference-only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch

_TILE_BYTES = 4 * 1024 * 1024  # one int8 weight tile; double-buffered
_BM_MIN, _BM_MAX = 16, 256


def _is_quantized(w) -> bool:
    return hasattr(w, "bits") and hasattr(w, "scale") and hasattr(w, "data")


def row_tile(pairs: int, experts: int) -> int:
    """Rows a tile: about two mean groups, a power of two in [16, 256].
    Small tiles waste no rows on padding when groups are small (a decode
    step: 64 pairs over 32 experts); large ones re-read an expert's weights
    less often when groups are large (an admission)."""
    bm = _BM_MIN
    while bm < _BM_MAX and bm * experts < 2 * pairs:
        bm *= 2
    return bm


def list_shape(pairs: int, experts: int,
               of_experts: int | None = None) -> tuple[int, int]:
    """(rows a tile, rows of the grouped list) for ``pairs`` pairs over
    ``experts`` held experts of ``of_experts`` (None: all are held): the
    tile is sized for the share of the pairs that an even routing sends
    here, the list for all of them, every group padded to whole tiles."""
    bm = row_tile(pairs * experts // of_experts if of_experts else pairs,
                  experts)
    return bm, (-(-pairs // bm) + experts) * bm  # sum_e ceil(c_e/bm) tiles


def _tiles(k: int, n: int, qb: int) -> tuple[int, int] | None:
    """(bk, bn) of the weight tile, or None if the shape cannot be tiled:
    bn a multiple of 128 lanes, bk whole scale blocks, and the scale tile
    [bk // qb, bn] either the whole axis or a multiple of 8 sublanes.

    The rows are cut before the columns: a tile of whole rows is ONE run
    of bytes in HBM (LFM2: [1024, 3584] and a whole [1792, 2048]), and on
    the chip its time does not depend on where the stacks lie, where the
    half-width tiles of a 2 MiB budget ([1024, 1792], 56 KB runs at a 112
    KB stride) were 3% slower at most addresses (PERF.md, PR 28).  A.X-K1:
    [1024, 4096] of [7168, 4096], whole rows; [1024, 3584] of [2048,
    7168], where 1024 whole rows are 7 MiB."""
    if qb != 128 or k % qb or n % 128:
        return None
    # Row blocks that keep the scale tile whole sublanes: the whole axis,
    # or a divisor of it that is a multiple of 8 scale blocks.
    bks = [k] + [b for b in range(k - k % (8 * qb), 0, -8 * qb)
                 if b < k and k % b == 0]
    for bk in bks:
        if bk * n <= _TILE_BYTES:
            return bk, n
    bk, bn = bks[-1], n  # no run of whole rows fits: halve the columns
    while bk * bn > _TILE_BYTES:
        if bn % 2 or (bn // 2) % 128:
            return None
        bn //= 2
    return bk, bn


def _kernel(te_ref, nt_ref, ly_ref, x_ref, q_ref, s_ref, o_ref, acc_ref, *,
            qb, nk, act=None):
    del te_ref, ly_ref  # read by the index maps only
    t, k = pl.program_id(0), pl.program_id(2)

    @pl.when(t < nt_ref[0])
    def _tile():
        @pl.when(k == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        bk, bn = q_ref.shape
        w = q_ref[...].astype(jnp.int32).astype(jnp.float32)
        w = w.reshape(bk // qb, qb, bn) * s_ref[...][:, None, :]
        acc_ref[...] += jnp.dot(
            x_ref[...], w.reshape(bk, bn).astype(x_ref.dtype),
            preferred_element_type=jnp.float32,
        )

        @pl.when(k == nk - 1)
        def _():
            acc = acc_ref[...]
            o_ref[...] = (acc if act is None else act(acc)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bk", "bn", "interpret", "act"))
def _grouped_quant_matmul(x, q, s, tile_expert, num_tiles, layer, *, bm, bk,
                          bn, interpret=False, act=None):
    """x [Pp, K] (row tiles of ``bm``, each of one expert) @ layer
    ``layer`` [1] of the int8 stack q [L, E, K, N] with scales s
    [L, E, K/qb, N] -> [Pp, N].  The stack goes in whole, every layer's
    experts, and the index maps pick (layer, expert): sliced a layer it
    would be copied, 0.36 GB a layer a step in LFM2 (a Pallas call takes
    each operand as a buffer of its own).  Tiles at and past ``num_tiles``
    are skipped: their index maps repeat the last real step's blocks, so
    nothing is fetched, computed or written for them.  ``num_tiles`` may
    be 0 (a chip's share of the experts, and no pair fell on it): every
    step then names block 0, and nothing is computed.  ``act``: an
    elementwise function of the float32 sums, applied as a tile is written
    (the activation of an expert of two matrices: no pass over the list
    between the two calls, whose rows past the live tiles nobody wrote)."""
    pp, kd = x.shape
    n = q.shape[3]
    qb = kd // s.shape[2]
    grid = (pp // bm, n // bn, kd // bk)
    last = (grid[1] - 1, grid[2] - 1)

    def where(t, j, k, nt):
        # (no tile at all, when no pair fell on a held expert: block 0,
        # fetched once and never computed on)
        live = t < nt[0]
        return (jnp.where(live, t, jnp.maximum(nt[0] - 1, 0)),
                jnp.where(live, j, last[0]), jnp.where(live, k, last[1]))

    def x_map(t, j, k, te, nt, ly):
        t, _, k = where(t, j, k, nt)
        return t, k

    def w_map(t, j, k, te, nt, ly):
        t, j, k = where(t, j, k, nt)
        return ly[0], te[t], k, j

    def o_map(t, j, k, te, nt, ly):
        t, j, _ = where(t, j, k, nt)
        return t, j

    return pl.pallas_call(
        functools.partial(_kernel, qb=qb, nk=grid[2], act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), x_map),
                pl.BlockSpec((None, None, bk, bn), w_map),
                pl.BlockSpec((None, None, bk // qb, bn), w_map),
            ],
            out_specs=pl.BlockSpec((bm, bn), o_map),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((pp, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
        name="moe_experts",  # the operation's name in a trace
    )(tile_expert, num_tiles, layer, x, q, s)


def _mode() -> str:
    return dispatch.kernel_mode("DLT_MOE_EXPERTS")


def _layer_of(w, layer, dtype):
    """Layer ``layer``'s experts [E, K, N] out of a stack, dequantized: the
    fallback's copy of one layer (the kernel never makes it)."""
    w = jax.tree.map(lambda a: a[layer], w)
    if not _is_quantized(w):
        return w.astype(dtype)
    from ..checkpoint.quantize import dequantize

    return dequantize(w, dtype)


_RANK_BLOCK = 256  # pairs a block of the ranks' triangular product


def _grouped_list(eid, live, e, bm, rows):
    """Each ``live`` pair's row in the grouped layout: its expert's first
    row (every group padded to whole tiles of bm) plus its rank among the
    expert's live pairs.  The rank is the pairs before it in its block of
    ``_RANK_BLOCK``, a product of the block's 0/1 columns with a strictly
    lower triangle of ones on the MXU, plus the blocks before, a cumulative
    sum over [P / block, E]: 0/1 operands and a float32 sum are exact below
    2^24, and nothing costs by P x E x log P as a cumulative sum over
    [P, E] does (PERF.md section 6, PR 56: 5.6 against 12.3 us a layer at a
    decode step's 512 pairs, 0.03 against 0.26 ms at an admission block's
    45,056).  A dead pair (an absent expert's, a padding token's) has no
    column, no count and the row ``rows``, past the list.
    -> (dest [P], counts [E], ends [E])."""
    p = eid.shape[0]
    blk = _RANK_BLOCK
    key = jnp.pad(jnp.where(live, eid, e), (0, -p % blk), constant_values=e)
    oh = jax.nn.one_hot(key, e, dtype=jnp.bfloat16).reshape(-1, blk, e)
    lower = jnp.tril(jnp.ones((blk, blk), jnp.bfloat16), -1)
    within = jnp.einsum("ij,bje->bie", lower, oh,
                        preferred_element_type=jnp.float32)
    sums = jnp.sum(oh, axis=1, dtype=jnp.float32)  # [P / block, E]
    before = jnp.cumsum(sums, axis=0) - sums
    counts = (before[-1] + sums[-1]).astype(jnp.int32)
    padded = -(-counts // bm) * bm
    ends = jnp.cumsum(padded)
    first = (ends - padded).astype(jnp.float32) + before  # [P / block, E]
    dest = jnp.sum((within + first[:, None, :]) * oh, axis=-1,
                   dtype=jnp.float32).reshape(-1)[:p].astype(jnp.int32)
    return jnp.where(live, dest, rows), counts, ends


_STAGE_BYTES = 16 * 1024 * 1024  # the fetched tiles of two blocks of pairs
# When the pairs' rows are fetched by the pairs that hold one and not gathered
# for every pair (PERF.md section 6, PR 58; both by what a trace can see):
# - the list is at least _COPY_MIN_BYTES: a smaller one XLA keeps in fast
#   memory, where its gather reads a row in 6-22 ns whatever the row's width
#   (K-EXAONE's 512-token block, 62.9 MB: 0.05 ms a call against the
#   kernel's 0.13), and from HBM 24 ns at 2 KB a row and 113 at 14 (A.X-K1's
#   512-token block, 69.7 MB: 0.37 ms a call against 0.135);
# - the rows the gather moves for one held pair, ``of_experts / E`` of them,
#   come to _COPY_PAIR_BYTES: a held pair costs the kernel 0.07-0.15 us
#   beside its bytes, what the gather needs for 20 KB of rows (A.X-K1's
#   sixteen rows of 14 KB a held pair: 0.52 ms a block against 1.86;
#   nemotron's four of 2 KB stay gathered: 0.73 against 1.12, and behind a
#   custom call XLA makes the reshape to [S, 22, D] float32, 0.30 more:
#   PERF.md section 7, From PR 58 (a), has what would take it).
_COPY_MIN_BYTES = 64 * 1024 * 1024
_COPY_PAIR_BYTES = 20 * 1024


def _copy_block(p: int, d: int) -> int | None:
    """Pairs a block of :func:`_pairs_rows`: the largest power of two up to
    256 that divides ``p`` and keeps two blocks' fetched tiles, 8 rows of
    16 bits a pair, in ``_STAGE_BYTES`` (A.X-K1's D of 7,168: 64), or None
    where the kernel does not apply: 32 pairs are a word of the held
    pairs' bits, D is whole lanes."""
    if d % 128 or p % 32:
        return None
    bp = 32
    while (bp < 256 and p % (2 * bp) == 0
           and 2 * (2 * bp) * 8 * d * 2 <= _STAGE_BYTES):
        bp *= 2
    return bp


def _copy_kernel(bits_ref, dest_ref, yp_ref, o_ref, o32, stage, sems, count,
                 held, *, bp):
    """One block of ``bp`` pairs.  The 16-bit rows of ``yp`` lie two to a
    32-bit word (rows 2r and 2r + 1, the even one low) and eight to a tile
    in HBM, and nothing smaller than a tile is copied from there: a held
    pair's row comes with the seven beside it, as the tile's 4 rows of
    words, and its half of one of them is put into the pair's half of the
    block's word row, which began as zeros (a dead pair's stay).  The held
    pairs are the set bits of ``bits_ref``, 32 pairs a word, so a dead
    pair costs nothing; step i lists block i's and starts their copies
    before it waits for block i - 1's, whose place in the output it holds
    (one step more than blocks, each half of the body traced once)."""
    i, n = pl.program_id(0), pl.num_programs(0) - 1  # n blocks, n + 1 steps
    yp32 = yp_ref.bitcast(jnp.uint32)  # [rows / 2, D]

    def tile(d, slot, c):  # the tile that holds row d -> the slot's c-th
        return pltpu.make_async_copy(
            yp32.at[pl.ds(d // 8 * 4, 4)], stage.at[slot, c], sems.at[slot])

    @pl.when(i < n)
    def _fetch():  # block i: list its held pairs, start their copies
        slot = i % 2

        def word(wi, c):
            def bit(state):
                w, c = state
                low = w & -w
                j = wi * 32 + 31 - jax.lax.clz(low)
                d = dest_ref[i * bp + j]
                tile(d, slot, c).start()
                held[slot, 0, c] = j
                held[slot, 1, c] = d
                return w ^ low, c + 1

            return jax.lax.while_loop(
                lambda state: state[0] != 0, bit,
                (bits_ref[i * (bp // 32) + wi], c))[1]

        count[slot] = jax.lax.fori_loop(0, bp // 32, word, jnp.int32(0))

    @pl.when(i > 0)
    def _place():  # block i - 1: wait for its copies, put its rows together
        slot = (i - 1) % 2
        o32[...] = jnp.zeros_like(o32)

        def wait(_, carry):
            tile(0, slot, 0).wait()
            return carry

        jax.lax.fori_loop(0, count[slot], wait, 0)

        def place(c, carry):
            j, d = held[slot, 0, c], held[slot, 1, c]
            w = stage[slot, c, pl.ds(d % 8 // 2, 1), :]
            half = (w >> (d % 2 * 16).astype(jnp.uint32)) & 0xFFFF
            at = pl.ds(j // 2, 1)
            o32[at, :] = o32[at, :] | (
                half << (j % 2 * 16).astype(jnp.uint32))
            return carry

        jax.lax.fori_loop(0, count[slot], place, 0)
        o_ref[...] = pltpu.bitcast(o32[...], o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pairs_rows(yp, dest, *, interpret=False):
    """yp [rows, D] in 16 bits, dest [P] (a pair's row of ``yp``, ``rows``
    for a dead pair) -> [P, D]: ``yp.at[dest].get(mode="fill",
    fill_value=0)`` bit for bit, made by fetching the held pairs' rows and
    writing each block once, where XLA's gather fetches a tile for every
    pair, dead or held."""
    rows, d = yp.shape
    p = dest.shape[0]
    bp = _copy_block(p, d)
    bits = jnp.sum(  # the held pairs, 32 a word, the first pair lowest
        (dest.reshape(-1, 32) < rows).astype(jnp.uint32)
        << jnp.arange(32, dtype=jnp.uint32), axis=1, dtype=jnp.uint32)
    return pl.pallas_call(
        functools.partial(_copy_kernel, bp=bp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(p // bp + 1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(
                (bp, d), lambda i, *_: (jnp.maximum(i - 1, 0), 0)),
            scratch_shapes=[
                pltpu.VMEM((bp // 2, d), jnp.uint32),  # the block, in words
                pltpu.VMEM((2, bp, 4, d), jnp.uint32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.SMEM((2, 2, bp), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((p, d), yp.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret,
        name="moe_combine",  # the operation's name in a trace
    )(jax.lax.bitcast_convert_type(bits, jnp.int32), dest, yp)


def grouped_swiglu(xf: jax.Array, topi: jax.Array, w_gate_up, w_down,
                   layer: jax.Array | int = 0,
                   of_experts: int | None = None,
                   act=jax.nn.silu, gated: bool = True,
                   token_mask: jax.Array | None = None,
                   count_fetched: bool = False):
    """Every (token, choice) pair through its expert's gated MLP,
    ``(act(x W_gate) * (x W_up)) W_down``: ``act`` is the configuration's
    (layers.gate_fn; silu for a SwiGLU).  ``gated`` False: an expert is two
    matrices, ``act(x W_up) W_down``, and ``w_gate_up`` is ``W_up`` alone,
    [L, E, D, F].

    xf [S, D]; topi [S, k] int32 expert ids; w_gate_up [L, E, D, 2F] and
    w_down [L, E, F, D], every layer's experts in one stack, arrays or
    ``QuantizedTensor`` (block_axis -2); ``layer`` names the layer to read
    (traced inside a layer scan).  Returns the pairs' outputs [S, k, D] in
    xf.dtype, unweighted: the caller applies the routing weights.

    ``of_experts``: the stacks hold E of that many experts, a chip's
    share, and ``topi`` counts from the first one held, so an id outside
    [0, E) names an expert that is elsewhere.  Such a pair leaves the
    grouped list (it is given no row, and its output is zeros); the row
    tile is sized for the share of the pairs that an even routing sends
    here, the list for all of them.

    ``token_mask`` [S] bool marks the real tokens (None: all): the padding
    of an admission's block and a row that is not decoding get no row
    either, and zeros.  A live pair's tile and bits do not depend on the
    dead ones.

    ``count_fetched``: return (pairs' outputs, rows fetched), the second the
    held pairs whose rows :func:`_pairs_rows` fetched, int32 and 0 where the
    gather made the outputs (the source of ``moe.combine_rows``)."""
    s, d = xf.shape
    k = topi.shape[1]
    quant = _is_quantized(w_gate_up)
    _, e, _, f2 = (w_gate_up.data if quant else w_gate_up).shape
    f = f2 // 2 if gated else f2
    p = s * k
    eid = topi.reshape(p).astype(jnp.int32)  # pair (token, choice) -> expert
    token = jnp.arange(p, dtype=jnp.int32) // k

    mode = _mode()
    tiles = None
    if quant and w_gate_up.bits == 8 and mode != "fallback":
        plans = [
            # The interpreter has no tiling rules: whole-axis tiles will do.
            _tiles(kd, n, kd // w.scale.shape[2])
            or ((kd, n) if mode == "interpret" else None)
            for w, kd, n in ((w_gate_up, d, f2), (w_down, f, d))
        ]
        tiles = plans if all(plans) else None
    share = of_experts is not None
    # (the fallback pads no group)
    bm, rows = list_shape(p, e, of_experts) if tiles else (1, p)

    live = jnp.logical_and(eid >= 0, eid < e)
    if token_mask is not None:
        live = jnp.logical_and(live, jnp.repeat(token_mask, k))
    dest, counts, ends = _grouped_list(eid, live, e, bm, rows)
    src = jnp.zeros((rows,), jnp.int32).at[dest].set(token, mode="drop")
    xp = xf[src]  # padding rows repeat token 0: computed, never read back

    # A share's pairs are mostly dead: the rows of a large list are fetched
    # by the pairs that hold one (_pairs_rows).  Where every expert is held,
    # at a decode step's sizes and for narrow rows the gather is the faster
    # of the two, and stays (_COPY_MIN_BYTES, _COPY_PAIR_BYTES).
    copy = (tiles is not None and share and xf.dtype.itemsize == 2
            and rows * d * 2 >= _COPY_MIN_BYTES
            and d * 2 * of_experts >= _COPY_PAIR_BYTES * e
            and _copy_block(p, d) is not None)

    def pairs(yp):  # (a dead pair's row is past the list: zeros)
        with jax.named_scope("moe_combine"):
            if copy:
                dispatch.record("moe_combine", mode, (p, rows, d))
                y = _pairs_rows(yp, dest, interpret=mode == "interpret")
            else:
                y = yp.at[dest].get(mode="fill", fill_value=0)
            y = y.reshape(s, k, d)
        if not count_fetched:
            return y
        return y, jnp.sum(live, dtype=jnp.int32) if copy else jnp.int32(0)

    def hidden(h):  # the first projection's output -> the second's input
        return act(h[:, :f]) * h[:, f:] if gated else act(h)

    if tiles is None:
        dispatch.record("moe_experts", "fallback", (p, e, d, f))
        h = jax.lax.ragged_dot(
            xp, _layer_of(w_gate_up, layer, xf.dtype), counts)
        yp = jax.lax.ragged_dot(
            hidden(h), _layer_of(w_down, layer, xf.dtype), counts)
        return pairs(yp)

    dispatch.record("moe_experts", mode, (p, e, d, f))
    first_row = jnp.arange(rows // bm, dtype=jnp.int32) * bm
    tile_expert = jnp.minimum(
        jnp.sum(ends[None, :] <= first_row[:, None], axis=1), e - 1
    ).astype(jnp.int32)
    num_tiles = (ends[-1:] // bm).astype(jnp.int32)
    kw = dict(bm=bm, interpret=mode == "interpret")
    where = (tile_expert, num_tiles, jnp.asarray(layer, jnp.int32).reshape(1))
    (bk1, bn1), (bk2, bn2) = tiles
    # (an expert of two matrices takes its activation as the first call
    # writes its tiles; a gate's product needs both halves of a row)
    h = _grouped_quant_matmul(xp, w_gate_up.data, w_gate_up.scale, *where,
                              bk=bk1, bn=bn1, act=None if gated else act,
                              **kw)
    yp = _grouped_quant_matmul(hidden(h) if gated else h, w_down.data,
                               w_down.scale, *where, bk=bk2, bn=bn2, **kw)
    return pairs(yp)
