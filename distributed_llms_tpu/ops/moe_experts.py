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

Modes (``DLT_MOE_EXPERTS``: kernel | interpret | fallback | auto = kernel
iff TPU), recorded as ``ops.dispatch.moe_experts.<path>``.  The fallback is
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


def grouped_swiglu(xf: jax.Array, topi: jax.Array, w_gate_up, w_down,
                   layer: jax.Array | int = 0,
                   of_experts: int | None = None,
                   act=jax.nn.silu, gated: bool = True,
                   token_mask: jax.Array | None = None) -> jax.Array:
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
    dead ones."""
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
    bm = row_tile(p * e // of_experts if share else p, e) if tiles else 1
    rows = (-(-p // bm) + e) * bm if tiles else p  # sum_e ceil(c_e/bm) tiles

    live = jnp.logical_and(eid >= 0, eid < e)
    if token_mask is not None:
        live = jnp.logical_and(live, jnp.repeat(token_mask, k))
    dest, counts, ends = _grouped_list(eid, live, e, bm, rows)
    src = jnp.zeros((rows,), jnp.int32).at[dest].set(token, mode="drop")
    xp = xf[src]  # padding rows repeat token 0: computed, never read back

    def pairs(yp):  # (a dead pair's row is past the list: zeros)
        return yp.at[dest].get(mode="fill", fill_value=0).reshape(s, k, d)

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
