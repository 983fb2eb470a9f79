"""Flash attention: fused blockwise attention as a Pallas TPU kernel.

Net-new relative to the reference, whose only compute was a placeholder
per-parameter ``torch.matmul`` (src/worker/node.py:24-32).  This is the
"native tier" of the new stack (SURVEY §2 intro): the hot O(T²) op written
directly against the TPU memory hierarchy instead of relying on XLA fusion.

Design (standard flash-attention recurrence, TPU-tiled):

- grid ``(B, H, num_q_blocks, num_k_blocks)``; the K-block axis is innermost,
  so VMEM scratch accumulators (running max / numerator / denominator)
  persist across K blocks of one Q block while ``pallas_call`` double-buffers
  the K/V block DMAs;
- each step computes a ``[block_q, block_k]`` score tile on the MXU in f32
  and folds it into the online softmax;
- grouped-query attention is native: the K/V ``BlockSpec`` index maps divide
  the query-head grid index by ``q_per_kv``, so K/V blocks are fetched once
  per KV head — queries in the same group reuse them;
- **static-causal fast path** (the training / prefill hot path, detected when
  positions and validity are the standard contiguous layout): above-diagonal
  tiles are skipped *and their K/V index maps are clamped to the diagonal*,
  so the dead tiles issue no new DMA; fully-visible tiles skip masking
  entirely; only diagonal tiles pay for the iota mask.  Its grid covers the
  tiles that can hold a live (query, key) pair and no others: a windowed
  call's K axis spans the band's tiles and not the row's, and a call told
  how many leading rows are real (``rows``: an admission's padded bucket)
  ends its Q axis at the last tile that holds one (:func:`live_tiles`).  The
  same kernel scores a row's CONTINUATION, Tq new tokens behind ``start``
  cached ones in a cache of ``max_len`` slots: the causal diagonal is
  shifted by a traced ``start``, the tiles of the cached run are the fully
  visible ones and no tile past the new tokens is fetched
  (:func:`_flash_continuation_call`, :func:`live_keys`);
- **dynamic path** (ragged prompts, padded KV caches): per-tile masks are
  built from global position / validity vectors, and fully-masked tiles skip
  their MXU work via ``pl.when``.

Differentiation: the kernel carries a ``custom_vjp`` whose backward pass
recomputes attention densely (flash-checkpoint style — nothing but q/k/v is
saved from the forward).  Gradients therefore cost O(T²) memory in the
backward only; a fused backward kernel can replace it without touching
callers.  Interpret mode runs automatically off-TPU so the CPU fake-mesh
tests exercise the same path.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch
from .quant_matmul import _zero_kernel

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# Shared online-softmax accumulate
# ---------------------------------------------------------------------------

def _accumulate(s, v, acc_ref, m_ref, l_ref):
    """Fold one masked f32 score tile ``s`` [bq, bk] and its V block into the
    running (acc, m, l) scratch state."""
    m_prev = m_ref[:, 0]  # [bq]
    l_prev = l_ref[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    # Rows with every key masked so far sit at finite finfo.min; using that as
    # the softmax shift would make masked entries exp(0)=1.  Shift by 0
    # instead so they underflow to exp(_NEG_INF)=0.
    safe = jnp.where(m_new <= _NEG_INF * 0.5, 0.0, m_new)
    p = jnp.exp(s - safe[:, None])  # [bq, bk] f32
    alpha = jnp.exp(m_prev - safe)  # 0 while unseeded
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[:, 0] = m_new
    l_ref[:, 0] = l_new


def _scores(q, k, scale):
    return (
        jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        * scale
    )


def _finish(o_ref, acc_ref, l_ref):
    l = jnp.maximum(l_ref[:, 0], 1e-37)
    o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# Static-causal kernel (training / prefill hot path)
# ---------------------------------------------------------------------------

def _band(qi, bq: int, bk: int, window: int | None, start=0,
          tokens: int | None = None):
    """(first, last) of the K tiles that hold a key some row of Q tile ``qi``
    sees: up to the diagonal and, with ``window``, from the tile of the
    first row's oldest key (row - window + 1, at least 0).  The one rule of
    the kernel, its index maps (``qi`` traced) and the host's counts (an
    int).  A continuation's tile (:func:`_tile_rows`) sits ``start`` down
    the diagonal."""
    host = isinstance(qi, int) and isinstance(start, int)
    div = (lambda a, b: a // b) if host else jax.lax.div
    # (the tile's first position is formed anew for each bound, as it always
    # was: a row start's kernel stays the program it has been, to the bit)
    q_start, span = _tile_rows(qi, bq, start, tokens)
    last = div(q_start + span - 1, bk)
    if window is None:
        return 0, last
    q_start = _tile_rows(qi, bq, start, tokens)[0]
    first_col = (max if host else jnp.maximum)(q_start - (window - 1), 0)
    return div(first_col, bk), last


def _tile_rows(qi, bq: int, start=0, tokens: int | None = None):
    """(position of its first row, positions it spans) of Q tile ``qi``.  A
    row's start: tile qi's rows are tokens qi * bq onward, one a position.
    A continuation (``tokens``: :func:`_flash_continuation_call`): the rows
    are runs of ``tokens`` tokens, one run a query head, each run's token j
    at position ``start`` + j, and a tile holds whole runs or a part of
    one."""
    if tokens is None:
        return qi * bq, bq
    return start + (qi * bq) % tokens, min(bq, tokens)


def _band_widths(nq: int, bq: int, bk: int, window: int | None) -> list[int]:
    """K tiles in the band of each of ``nq`` Q tiles."""
    return [last - first + 1 for first, last in
            (_band(qi, bq, bk, window) for qi in range(nq))]


def _tile(t: int, s: int, block_q: int, block_k: int) -> tuple[int, int]:
    """(bq, bk) of a call of ``t`` queries over ``s`` keys.  Q tile: sublane
    dim of the score tile (min 8 rows); K tile: lane dim (pad short
    sequences up to one 128-lane tile)."""
    return min(block_q, _round_up(t, 8)), _k_tile(s, block_k)


def _k_tile(s: int, block_k: int) -> int:
    """The K tile of a call over ``s`` keys (:func:`_tile`'s second)."""
    return min(block_k, _round_up(s, 128))


def live_tiles(t: int, rows: int, block: int, window: int | None = None
               ) -> tuple[int, int]:
    """(scored pairs, of them scored when told that the first ``rows`` of
    the ``t`` queries are real) of one static-causal call in tiles of
    ``block``: the tiles live by the causal band and the window, and those
    of them whose Q tile holds a real row (every one where ``t`` is a
    single Q tile), each counted by its area so that calls in different
    tiles add up.  Host arithmetic, for the batcher's counters."""
    bq, bk = _tile(t, t, block, block)
    nq = -(-t // bq)
    live = nq if nq == 1 else min(nq, -(-rows // bq))
    tiles = _band_widths(nq, bq, bk, window)
    return sum(tiles) * bq * bk, sum(tiles[:live]) * bq * bk


def live_keys(s: int, keys: int, block_k: int) -> int:
    """Slots of a cache of ``s`` that a continuation which leaves ``keys``
    in it fetches and scores: the K tiles up to the last that holds a key.
    Host arithmetic, for the batcher's counters."""
    bk = _k_tile(s, block_k)
    return min(max(-(-keys // bk), 1) * bk, _round_up(s, bk))


def _kernel_static(
    q_ref,  # [1, bq, D]
    k_ref,  # [1, bk, D]
    v_ref,  # [1, bk, D]
    o_ref,  # [1, bq, D]
    acc_ref,  # [bq, D] f32
    m_ref,  # [bq, 128] f32
    l_ref,  # [bq, 128] f32
    *,
    scale: float,
    num_k_blocks: int,  # steps of the K axis: the row's tiles, or a band's
    block_q: int,
    block_k: int,
    window: int | None = None,  # sliding window: keys in (row - window, row]
    start_ref=None,  # [1] int32 in SMEM: a continuation's first position
    tokens: int | None = None,  # ... and the length of a head's run of rows
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    start = 0 if start_ref is None else start_ref[0]
    q_start, span = _tile_rows(qi, block_q, start, tokens)
    if window is not None:  # step ki is the band's ki-th tile (:func:`_band`)
        ki_abs = ki + _band(qi, block_q, block_k, window, start, tokens)[0]
    else:
        ki_abs = ki
    k_start = ki_abs * block_k
    # Tile classes: fully visible (every (row, col) pair inside the causal —
    # and, when windowed, the window — band), boundary (crosses the diagonal
    # or the window's lower edge: iota-masked), dead (fully outside; index
    # maps clamp its K/V fetch so it costs no DMA and no MXU work).
    visible = k_start + block_k - 1 <= q_start
    dead = k_start > q_start + span - 1  # above the diagonal
    if window is not None:
        # Fully visible additionally needs every col > every row - window;
        # fully below the window's lower edge is dead.
        visible = jnp.logical_and(
            visible, k_start > q_start + span - 1 - window
        )
        dead = jnp.logical_or(dead, k_start + block_k - 1 <= q_start - window)
    boundary = jnp.logical_not(jnp.logical_or(visible, dead))

    @pl.when(visible)
    def _full():
        s = _scores(q_ref[0], k_ref[0], scale)
        _accumulate(s, v_ref[0], acc_ref, m_ref, l_ref)

    @pl.when(boundary)
    def _edge():
        s = _scores(q_ref[0], k_ref[0], scale)
        if tokens is None:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        else:  # row r of the tile is token r % tokens of its run: a column
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (s.shape[0], 1), 0) % tokens
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = cols <= rows
        if window is not None:
            keep = jnp.logical_and(keep, cols > rows - window)
        s = jnp.where(keep, s, _NEG_INF)
        _accumulate(s, v_ref[0], acc_ref, m_ref, l_ref)

    @pl.when(ki == num_k_blocks - 1)
    def _done():
        _finish(o_ref, acc_ref, l_ref)


# ---------------------------------------------------------------------------
# Dynamic kernel (ragged prompts / padded caches / explicit validity)
# ---------------------------------------------------------------------------

def _kernel_dynamic(
    qpos_ref,  # [1, 1, bq] int32 — global positions of this Q block's rows
    kpos_ref,  # [1, 1, bk] int32 — global positions of this K block's slots
    kval_ref,  # [1, 1, bk] int32 — 1 where the K slot is a real/valid key
    q_ref,  # [1, bq, D]
    k_ref,  # [1, bk, D]
    v_ref,  # [1, bk, D]
    o_ref,  # [1, bq, D]
    acc_ref,
    m_ref,
    l_ref,
    *,
    causal: bool,
    scale: float,
    num_k_blocks: int,
    window: int | None = None,  # sliding window in POSITION space
):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    qp = qpos_ref[0, 0, :]  # [bq]
    kp = kpos_ref[0, 0, :]  # [bk]
    kv = kval_ref[0, 0, :]  # [bk]
    mask = (kv != 0)[None, :]  # [1, bk]
    if causal:
        mask = jnp.logical_and(mask, kp[None, :] <= qp[:, None])  # [bq, bk]
    if window is not None:
        # layers.and_window semantics: keys at positions (p - window, p].
        mask = jnp.logical_and(mask, kp[None, :] > qp[:, None] - window)
    mask = jnp.broadcast_to(mask, (qp.shape[0], kp.shape[0]))

    @pl.when(jnp.any(mask))
    def _block():
        s = jnp.where(mask, _scores(q_ref[0], k_ref[0], scale), _NEG_INF)
        _accumulate(s, v_ref[0], acc_ref, m_ref, l_ref)

    @pl.when(ki == num_k_blocks - 1)
    def _done():
        _finish(o_ref, acc_ref, l_ref)


# ---------------------------------------------------------------------------
# Host-side wrapper
# ---------------------------------------------------------------------------

def _pad_to(x: jax.Array, axis: int, mult: int, value) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _flash_call(q, k, v, q_positions, k_positions, k_valid, causal, block_q, block_k, interpret, window, scale=None, rows=None):
    # Inside shard_map (e.g. the Ulysses body) the inputs carry varying
    # manual axes (vma); the output must declare the same set.
    vma = frozenset().union(*(jax.typeof(x).vma for x in (q, k, v)))
    b, tq, h, d = q.shape
    s = k.shape[1]
    kvh = k.shape[2]
    dv = v.shape[3]  # a value head may be narrower than a key's (MLA)
    if interpret and vma:
        # The Pallas HLO *interpreter* (off-TPU test path) loses vma on its
        # internal dynamic_slices; run the numerically-identical dense
        # reference there.  Real TPU lowering takes the kernel.
        dispatch.record("flash", "fallback", (b, tq, s, h, kvh, d))
        return _dense_reference(
            q, k, v, q_positions, k_positions, k_valid, causal, window, scale
        )
    dispatch.record(
        "flash", "interpret" if interpret else "kernel", (b, tq, s, h, kvh, d)
    )
    assert h % kvh == 0, (h, kvh)
    g = h // kvh
    if scale is None:
        scale = d**-0.5

    bq, bk = _tile(tq, s, block_q, block_k)

    # The hot path: standard contiguous positions, every key slot valid, and
    # query rows aligned with key slots (training forward / full prefill).
    static_causal = (
        causal and q_positions is None and k_positions is None
        and k_valid is None and tq == s
    )

    # [B, H, T, D] layout: contiguous [T, D] tiles per head.
    qt = _pad_to(q.transpose(0, 2, 1, 3), 2, bq, 0)
    kt = _pad_to(k.transpose(0, 2, 1, 3), 2, bk, 0)
    vt = _pad_to(v.transpose(0, 2, 1, 3), 2, bk, 0)
    tq_p, s_p = qt.shape[2], kt.shape[2]
    nq, nk = tq_p // bq, s_p // bk
    grid = (b, h, nq, nk)
    if rows is not None and (not static_causal or b != 1):
        raise ValueError(
            "rows is the count of real tokens of ONE right-padded sequence "
            "that attends causally among its own tokens")
    if nq == 1:  # one Q tile holds every row: nothing to end early
        rows = None
    scratch = [
        pltpu.VMEM((bq, dv), jnp.float32),
        pltpu.VMEM((bq, 128), jnp.float32),
        pltpu.VMEM((bq, 128), jnp.float32),
    ]
    q_spec = pl.BlockSpec((1, bq, d), lambda bi, hi, qi, ki: (bi * h + hi, qi, 0))
    o_spec = pl.BlockSpec((1, bq, dv), lambda bi, hi, qi, ki: (bi * h + hi, qi, 0))
    out_shape = jax.ShapeDtypeStruct((b * h, tq_p, dv), q.dtype, vma=vma)
    args = (
        qt.reshape(b * h, tq_p, d),
        kt.reshape(b * kvh, s_p, d),
        vt.reshape(b * kvh, s_p, dv),
    )

    if static_causal:
        # The K axis walks a Q tile's band (:func:`_band`): every tile up
        # to the diagonal, or with a window as many as the widest band
        # holds, from the band's first.  A step past the diagonal is dead;
        # its fetch is clamped to the diagonal's tile: repeated index =>
        # the pipeline issues no new DMA, so a windowed prefill's work
        # scales with the window, not the sequence.
        steps = (nk if window is None
                 else max(_band_widths(nq, bq, bk, window)))

        def kv_index(bi, hi, qi, ki):
            first, last_needed = _band(qi, bq, bk, window)
            kk = jnp.minimum(ki if window is None else ki + first, last_needed)
            return (bi * kvh + hi // g, kk, 0)

        # The Q tiles that hold a real row: the grid stops there (a traced
        # bound), so the padded queries, the dearest rows of the triangle,
        # are not scored, and the kernel and its index maps are the same
        # either way.  (With bq == bk every K tile past them is above the
        # diagonal already.)
        live = nq if rows is None else jnp.clip(pl.cdiv(rows[0], bq), 0, nq)
        out = pl.pallas_call(
            functools.partial(
                _kernel_static, scale=scale, num_k_blocks=steps,
                block_q=bq, block_k=bk, window=window,
            ),
            grid=(b, h, live, steps),
            in_specs=[
                q_spec,
                pl.BlockSpec((1, bk, d), kv_index),
                pl.BlockSpec((1, bk, dv), kv_index),
            ],
            out_specs=o_spec,
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
            name="flash_attn",  # the operation's name in a trace
        )(*args)
        if rows is not None:
            # ... and are written as zeros, where they lie in the output.
            out = pl.pallas_call(
                _zero_kernel,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(b * h, nq - live),
                    in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                    out_specs=pl.BlockSpec(
                        (1, bq, dv), lambda i, qi, first: (i, first[0] + qi, 0)),
                ),
                out_shape=out_shape,
                input_output_aliases={1: 0},
                interpret=interpret,
                name="flash_attn_padding",
            )(live.reshape(1), out)
    else:
        # Freshly created defaults are not device-varying over any manual
        # mesh axis; align them with q/k/v so vma tracking stays consistent
        # inside shard_map bodies (same trick as ops/ring.py).
        align = (
            (lambda x: jax.lax.pcast(x, tuple(vma), to="varying")) if vma
            else (lambda x: x)
        )
        if q_positions is None:
            q_positions = align(
                jnp.broadcast_to(jnp.arange(tq, dtype=jnp.int32), (b, tq))
            )
        if k_positions is None:
            k_positions = align(
                jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
            )
        kval = (
            align(jnp.ones((b, s), jnp.int32))
            if k_valid is None
            else k_valid.astype(jnp.int32)
        )
        # Padded q rows get position -1 (causal-masks every key -> zero
        # output); padded k slots get valid=0.  Vectors go in as [B*n, 1, blk]
        # (block dims equal array dims => satisfies the (8,128) tiling rule
        # without replicating across sublanes).
        qpos = _pad_to(q_positions.astype(jnp.int32), 1, bq, -1)
        kpos = _pad_to(k_positions.astype(jnp.int32), 1, bk, 2**30)
        kval = _pad_to(kval, 1, bk, 0)
        out = pl.pallas_call(
            functools.partial(
                _kernel_dynamic, causal=causal, scale=scale, num_k_blocks=nk,
                window=window,
            ),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq), lambda bi, hi, qi, ki: (bi * nq + qi, 0, 0)),
                pl.BlockSpec((1, 1, bk), lambda bi, hi, qi, ki: (bi * nk + ki, 0, 0)),
                pl.BlockSpec((1, 1, bk), lambda bi, hi, qi, ki: (bi * nk + ki, 0, 0)),
                q_spec,
                pl.BlockSpec((1, bk, d), lambda bi, hi, qi, ki: (bi * kvh + hi // g, ki, 0)),
                pl.BlockSpec((1, bk, dv), lambda bi, hi, qi, ki: (bi * kvh + hi // g, ki, 0)),
            ],
            out_specs=o_spec,
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
            name="flash_attn",  # the operation's name in a trace
        )(
            qpos.reshape(b * nq, 1, bq),
            kpos.reshape(b * nk, 1, bk),
            kval.reshape(b * nk, 1, bk),
            *args,
        )
    out = out.reshape(b, h, tq_p, dv)[:, :, :tq]
    return out.transpose(0, 2, 1, 3)


def _flash_continuation_call(q, k, v, start, block_q, block_k, interpret,
                             window, scale=None):
    """The static-causal kernel over a row's continuation: the Tq queries
    are tokens ``start`` .. ``start`` + Tq - 1 of a row whose keys lie in the
    cache's slots of the same index (slot == position, the cached run first
    and the new tokens behind it), and no slot past them holds a key.  The
    diagonal is the row start's, ``start`` down: the cached run's tiles are
    the fully visible ones (no mask is built for them), the tiles the new
    tokens lie in are the boundary's, and a step past those fetches nothing
    (its index is clamped to the diagonal's tile, :func:`_band`).  Laid
    before the kernel so that a K tile serves a whole KV group:

    - the g query heads of a KV group are g runs of rows behind one
      another, each of the Tq tokens (padded to whole Q tiles), and a Q
      tile holds whole runs where they fit (7 x 128 rows of one K tile) or
      a part of one: a K tile is fetched once a KV head and not once a
      query head, and a grid step, which costs its 0.6 us whatever it
      holds, scores g times the pairs;
    - K and V stay as the cache has them, [B, S, KVH x D] with a head a
      block of lanes, and are not transposed whole to [KVH, S, D] first
      (on the chip that takes heads of whole 128-lane registers).

    Forward only."""
    b, tq, h, d = q.shape
    s, kvh, dv = k.shape[1], k.shape[2], v.shape[3]
    assert h % kvh == 0, (h, kvh)
    g = h // kvh
    dispatch.record(
        "flash", "interpret" if interpret else "kernel", (b, tq, s, h, kvh, d))
    if scale is None:
        scale = d**-0.5
    bk = _k_tile(s, block_k)
    tokens = _round_up(tq, 8)
    if g * tokens <= block_q:
        bq = g * tokens  # one tile: every head's run
    else:
        bq = min(block_q, tokens)  # tiles of one run; a run is whole tiles
        tokens = _round_up(tokens, bq)
    qf = _pad_to(q, 1, tokens, 0).reshape(b, tokens, kvh, g, d).transpose(
        0, 2, 3, 1, 4).reshape(b * kvh, g * tokens, d)
    kl = _pad_to(k, 1, bk, 0)
    vl = _pad_to(v, 1, bk, 0)
    s_p = kl.shape[1]
    nq, nk = g * tokens // bq, s_p // bk
    # The K axis: the row's tiles, or as many as a band can touch (the
    # window and the tile's own positions, wherever ``start`` puts them).
    steps = nk if window is None else min(
        nk, -(-(window - 1 + min(bq, tokens)) // bk) + 1)

    def kv_index(bi, hi, qi, ki, start_ref):
        first, last_needed = _band(qi, bq, bk, window, start_ref[0], tokens)
        # (a run's padded rows lie past the last key: what they would see
        # is not fetched either, and a real row masks it whole)
        last_key = jax.lax.div(start_ref[0] + (tq - 1), bk)
        kk = jnp.minimum(ki if window is None else ki + first,
                         jnp.minimum(last_needed, last_key))
        return (bi, jnp.minimum(kk, nk - 1), hi)

    out = pl.pallas_call(
        lambda start_ref, *refs: _kernel_static(
            *refs, scale=scale, num_k_blocks=steps, block_q=bq, block_k=bk,
            window=window, start_ref=start_ref, tokens=tokens),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kvh, nq, steps),
            in_specs=[
                pl.BlockSpec((1, bq, d),
                             lambda bi, hi, qi, ki, _: (bi * kvh + hi, qi, 0)),
                pl.BlockSpec((1, bk, d), kv_index),
                pl.BlockSpec((1, bk, dv), kv_index),
            ],
            out_specs=pl.BlockSpec(
                (1, bq, dv), lambda bi, hi, qi, ki, _: (bi * kvh + hi, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, dv), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * kvh, g * tokens, dv), q.dtype),
        interpret=interpret,
        name="flash_attn",  # the operation's name in a trace
    )(
        start.astype(jnp.int32).reshape(1), qf,
        kl.reshape(b, s_p, kvh * d), vl.reshape(b, s_p, kvh * dv),
    )
    out = out.reshape(b, kvh, g, tokens, dv)[:, :, :, :tq]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, tq, h, dv)


# ---------------------------------------------------------------------------
# Autodiff: dense-recompute backward (flash-checkpoint style)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash(q, k, v, q_positions, k_positions, k_valid, causal, block_q, block_k, interpret, window, scale=None):
    return _flash_call(
        q, k, v, q_positions, k_positions, k_valid, causal, block_q, block_k,
        interpret, window, scale)


def _dense_reference(q, k, v, q_positions, k_positions, k_valid, causal,
                     window=None, scale=None):
    """Same math and masking semantics as the kernel, in plain XLA ops — the
    VJP target for the backward pass."""
    b, tq, h, d = q.shape
    s, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    if g > 1:
        k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, kvh, g, d)).reshape(b, s, h, d)
        v = jnp.broadcast_to(
            v[:, :, :, None, :], (b, s, kvh, g, v.shape[3])
        ).reshape(b, s, h, v.shape[3])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * (d**-0.5 if scale is None else scale)
    qp = (
        jnp.broadcast_to(jnp.arange(tq, dtype=jnp.int32), (b, tq))
        if q_positions is None
        else q_positions
    )
    kp = (
        jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        if k_positions is None
        else k_positions
    )
    mask = jnp.ones((b, 1, 1, s), bool) if not causal else (
        kp[:, None, None, :] <= qp[:, None, :, None]
    )
    if k_valid is not None:
        mask = jnp.logical_and(mask, k_valid[:, None, None, :])
    if window is not None:
        # layers.and_window semantics: keys at positions (p - window, p].
        mask = jnp.logical_and(
            mask, kp[:, None, None, :] > qp[:, None, :, None] - window
        )
    logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


def _flash_fwd(q, k, v, q_positions, k_positions, k_valid, causal, block_q, block_k, interpret, window, scale):
    out = _flash(
        q, k, v, q_positions, k_positions, k_valid, causal, block_q, block_k,
        interpret, window, scale,
    )
    return out, (q, k, v, q_positions, k_positions, k_valid)


def _flash_bwd(causal, block_q, block_k, interpret, window, scale, res, g):
    q, k, v, q_positions, k_positions, k_valid = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _dense_reference(
            q_, k_, v_, q_positions, k_positions, k_valid, causal, window,
            scale,
        ),
        q, k, v,
    )
    dq, dk, dv = vjp(g)
    zero = lambda x: None if x is None else np.zeros(x.shape, jax.dtypes.float0)
    return dq, dk, dv, zero(q_positions), zero(k_positions), zero(k_valid)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "window",
                     "scale"),
)
def flash_attention(
    q: jax.Array,  # [B, Tq, H, D]
    k: jax.Array,  # [B, S, KVH, D]  (KVH divides H — GQA-aware)
    v: jax.Array,  # [B, S, KVH, D]
    q_positions: jax.Array | None = None,  # [B, Tq] int32 global positions
    k_positions: jax.Array | None = None,  # [B, S] int32 global positions
    k_valid: jax.Array | None = None,  # [B, S] bool — False masks the slot
    causal: bool = True,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool | None = None,
    window: int | None = None,  # sliding window (layers.and_window
    #   semantics: keys at positions (p - window, p]); static.  The
    #   static-causal path skips — and never DMAs — tiles fully outside
    #   the window band, so windowed prefill work scales with the window.
    scale: float | None = None,  # the softmax scale; None: D ** -0.5.  Static
    #   (latent attention's is YaRN's, and its zero-padded heads' width is
    #   not the width the scale is of)
    rows: jax.Array | None = None,  # [1] int32: the first ``rows`` of the Tq
    #   tokens are real, ONE right-padded sequence on the static-causal path
    #   (an admission's bucket).  The Q tiles past them are not visited and
    #   come back as zeros; a real token's output is the call's without
    #   ``rows``, bit for bit.  Forward only.  None: all Tq
    start: jax.Array | None = None,  # [1] int32: a row's CONTINUATION.  The
    #   Tq queries are tokens start .. start + Tq - 1 of a row whose keys lie
    #   in slots [0, start + Tq) of the S, slot == position, every row of
    #   the batch alike (the cached run and the new tokens behind it, in a
    #   cache of max_len slots).  It stands for the positions and the
    #   validity vector; no K tile past the new tokens is fetched
    #   (:func:`_flash_continuation_call`).  Forward only
) -> jax.Array:
    """Fused attention.  Matches ``layers.dot_product_attention`` with mask
    ``(k_pos <= q_pos if causal) & k_valid [& window band]`` but never
    materializes the [Tq, S] score matrix in the forward.  v's heads may be
    of another width Dv than q's and k's.  Differentiable (dense-recompute
    backward).  Returns [B, Tq, H, Dv] in q.dtype."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if window is not None:
        if not causal:
            raise ValueError("window requires causal attention")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if start is not None:
        if not causal or any(x is not None for x in (
                q_positions, k_positions, k_valid, rows)):
            raise ValueError(
                "start says where a continuation's queries and keys lie: "
                "it stands for q_positions, k_positions and k_valid, and "
                "rows is a row's start's")
        return _flash_continuation_call(
            q, k, v, start, block_q, block_k, interpret, window, scale)
    if rows is not None:  # (a traced grid bound has no derivative rule)
        return _flash_call(
            q, k, v, q_positions, k_positions, k_valid, causal, block_q,
            block_k, interpret, window, scale, rows)
    return _flash(
        q, k, v, q_positions, k_positions, k_valid, causal, block_q, block_k,
        interpret, window, scale,
    )
