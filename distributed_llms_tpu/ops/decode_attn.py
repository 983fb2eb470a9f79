"""Ragged decode attention: single-token attention that reads only each
row's real cache depth.

VERDICT r3 weak #5: the continuous batcher's ``decode_chunk`` attends over
the full cache width S every step ([B, S] mask on the dense path) — fine at
S=512, a real HBM cost at 8k-context serving where rows admitted at
different times sit at very different depths.  This kernel makes the decode
read ragged: grid ``(B, num_k_blocks)`` with the K/V BlockSpec index
clamped to each row's last needed block, so blocks past ``lengths[b]``
issue no DMA (repeated index => the Pallas pipeline skips the fetch) and no
MXU work (``pl.when``).  HBM traffic per step drops from B*S to
sum(lengths) KV bytes — the long-context batcher cost model.

Each K/V block carries ALL kv heads — ``(1, bk, KVH, D)`` out of the native
``[B, S, KVH, D]`` cache — and the kernel unrolls a static loop over heads.
Mosaic requires a block's last two dims to be (8,128)-divisible or equal to
the array dims; blocking heads at 1 (``(1, bk, 1, D)``) lowers only when
KVH == 1, which the first on-chip parity sweep caught (interpret mode
cannot).  Whole-KVH blocks satisfy the rule for every head count at the
same total HBM traffic per row.

The PAGED kernel (:func:`paged_decode_attention`: every served decode
step) reads the same depth out of a pool of pages, and walks a row's pages
a RUN at a time: its grid is the rows, and inside a row it loops over runs
of about a mebibyte of pages (:func:`_run_pages`: 8 pages of qwen2's, 1 of
pythia's), fetching each page of a run with a DMA of its own out of the
pool where it lies, all of a run's in flight together and the next run's
started before this one is computed on.  A row takes ceil(pages held /
run) turns and reads the pages it holds, no more; a page slot it cannot
fill costs nothing.  One BlockSpec block a grid step, as the contiguous
kernel has them, cost 2.2 us a 128-KB page and 0.14 us an empty slot, a
tenth of HBM's rate (PERF.md, PR 31).  A run is computed on as it lies,
rows (token, KV head): one score product for all heads
(:func:`_softmax_all_heads`), because pulling one head out of interleaved
rows costs more than fetching them.

The contract matches the batcher's canonical mask exactly: row ``b``
attends to cache slots ``[0, lengths[b])`` (its valid prefix INCLUDING the
slot its own token was just written to — lengths = cache_index + 1).
``models.model._attention`` routes here when ``cfg.ragged_decode`` is set
(the ContinuousBatcher sets it; the flag is the caller's assertion that its
mask is this prefix mask).  Sliding-window models pass ``window``: the
read narrows to ``[lengths[b] - window, lengths[b])`` — exact because the
contract layout is slot == position, so the slot band IS the position
window — and per-step HBM traffic drops from O(length) to O(window).

No reference counterpart: the reference's compute was a placeholder matmul
(src/worker/node.py:24-32) with no KV cache at all.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..core.observability import METRICS
from . import dispatch

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _kernel(
    lengths_ref,  # scalar-prefetch [B] int32
    q_ref,  # [1, KVH*Gp, D] — per-kv-head query groups, sublane-padded
    k_ref,  # [bk, KVH, D] — a block of the cache in its NATIVE layout
    v_ref,  # [bk, KVH, D]
    *rest,  # int8 leg: [ks_ref [bk, KVH] f32, vs_ref], then o_ref and
    #   the three VMEM scratch refs (acc [KVH*Gp, D], m/l [KVH*Gp, 128])
    scale: float,
    block_k: int,
    num_k_blocks: int,
    kvh: int,
    gp: int,
    window: int | None = None,  # row b reads [length - window, length)
    #   instead of [0, length) — exact under the contract layout
    #   (slot == position), where the query sits at position length - 1
    quant: bool = False,  # int8 K/V blocks + per-(slot, head) absmax
    #   scales: score = (q . k_i8) * k_scale and out folds v_scale into
    #   the softmax weights — the dequant never materializes in VMEM
    #   beyond one cast block
):
    if quant:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
        scales = (ks_ref, vs_ref)
    else:
        scales = None
        o_ref, acc_ref, m_ref, l_ref = rest
    bi, ji = pl.program_id(0), pl.program_id(1)
    length = lengths_ref[bi]
    last_needed = jax.lax.div(jnp.maximum(length - 1, 0), block_k)
    if window is None:
        first_needed = 0
    else:
        first_needed = jax.lax.div(
            jnp.maximum(length - window, 0), block_k
        )

    @pl.when(ji == 0)
    def _init():
        _softmax_init(acc_ref, m_ref, l_ref)

    @pl.when(jnp.logical_and(ji <= last_needed, ji >= first_needed))
    def _block():
        _softmax_block(
            q_ref, k_ref, v_ref, scales, acc_ref, m_ref, l_ref,
            first_key=ji * block_k, length=length, scale=scale, kvh=kvh,
            gp=gp, window=window,
        )

    @pl.when(ji == num_k_blocks - 1)
    def _done():
        _softmax_done(o_ref, acc_ref, l_ref)


def _softmax_init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _softmax_done(o_ref, acc_ref, l_ref):
    l = jnp.maximum(l_ref[:, 0], 1e-37)
    o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _softmax_block(
    q_ref,  # [1, KVH*Gp, D]
    k_ref,  # [bk, KVH, D] keys in the cache's layout ([bk, D]: one head)
    v_ref,
    scales,  # int8 leg: (ks_ref, vs_ref), each [bk, KVH] f32; else None
    acc_ref, m_ref, l_ref,
    *, first_key, length, scale: float, kvh: int, gp: int,
    window: int | None = None,
):
    """One online-softmax update of every head's state with the ``bk``
    keys at positions ``first_key ...``: the body the contiguous kernel
    runs a K block at a time and the paged kernel a run of pages at a
    time."""
    bk = k_ref.shape[0]
    key_pos = first_key + jax.lax.broadcasted_iota(
        jnp.int32, (gp, bk), dimension=1
    )
    keep = key_pos < length
    if window is not None:
        # layers.and_window in slot space: keys in
        # [length - window, length) == positions (p - window, p].
        keep = jnp.logical_and(keep, key_pos >= length - window)
    # Static unrolled loop over kv heads: each iteration slices one head
    # out of the whole-KVH block already resident in VMEM and updates its
    # own Gp-row slice of the online-softmax state.
    for hh in range(kvh):
        # Per-head cast to the compute dtype: the cache may live at a
        # different dtype (kv_dtype knob) and casting here keeps the
        # HBM read at the cache's width — never a full-cache copy.
        # Int8 leg: the cast is the only widening (one block in VMEM);
        # the absmax scales fold into the contraction below instead of
        # dequantizing the block.
        kb = _head(k_ref, hh).astype(q_ref.dtype)
        vb = _head(v_ref, hh).astype(q_ref.dtype)
        s = (
            jax.lax.dot_general(
                q_ref[0, hh * gp:(hh + 1) * gp, :], kb,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [Gp, bk] f32
        if scales is not None:
            # score = (q . k_i8) * k_scale — per-(slot, head) scales
            # sit outside the head-dim dot product by construction
            # (checkpoint.quantize.kv_quantize blocks on HD).
            s = s * scales[0][:, hh][None, :]
        # out = sum_i p_i * (v_scale_i * v_i8_i): the scale folds into
        # the softmax weights (f32) before the value matmul.
        _softmax_update(
            jnp.where(keep, s, _NEG_INF), vb, acc_ref, m_ref, l_ref,
            rows=slice(hh * gp, (hh + 1) * gp),
            v_scale=None if scales is None else scales[1][:, hh][None, :],
        )


def _softmax_update(s, vb, acc_ref, m_ref, l_ref, rows=slice(None),
                    v_scale=None):
    """Fold masked scores ``s`` [R, bk] and their values ``vb`` [bk, D]
    into rows ``rows`` of the running maximum, sum and accumulator."""
    m_prev = m_ref[rows, 0]
    l_prev = l_ref[rows, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    safe = jnp.where(m_new <= _NEG_INF * 0.5, 0.0, m_new)
    p = jnp.exp(s - safe[:, None])
    alpha = jnp.exp(m_prev - safe)
    l_ref[rows, 0] = l_prev * alpha + jnp.sum(p, axis=-1)
    if v_scale is not None:
        p = p * v_scale
    acc_ref[rows, :] = acc_ref[rows, :] * alpha[:, None] + (
        jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    )
    m_ref[rows, 0] = m_new


def _head(ref, hh: int):
    """Head ``hh``'s [bk, D] out of K/V keys [bk, KVH, D], or the [bk, D]
    keys of a pool that holds a single head (see _paged_impl)."""
    return ref[:, hh, :] if len(ref.shape) == 3 else ref[...]


def _softmax_all_heads(
    q_ref,  # [1, Hp, D]: every head's query, head h = row // g
    k_ref,  # [n, D]: keys as rows (token, KV head), the order a page
    v_ref,  #   [BLK, KVH, D] lies in memory
    acc_ref, m_ref, l_ref,
    *, first_key, length, scale: float, kvh: int, g: int,
    zero_unread: bool = False,
):
    """:func:`_softmax_block` without its loop over heads: ONE score
    product of all the queries with all the rows, each query keeping the
    columns of its own KV head (``col % kvh``), and one value product in
    which the others' weights are zeros.  Pulling a head's [bk, D] out of
    rows that interleave the heads costs the vector unit a cycle a row,
    more than the DMA that brought them (PERF.md, PR 31); the matrix unit
    takes the rows as they lie, and its time goes by the rows of keys,
    which are the same.  The arithmetic a (query, key) pair sees is
    _softmax_block's.  ``zero_unread``: the rows past ``length`` are read as
    zeros on the value side too (a ring's entries past its count are
    another row's leftovers, whatever they hold: 0.0 x NaN is NaN)."""
    n = k_ref.shape[0]
    qa = q_ref[0]
    kb = k_ref[...].astype(qa.dtype)
    vb = v_ref[...].astype(qa.dtype)
    if zero_unread:
        token = first_key + jax.lax.broadcasted_iota(
            jnp.int32, (n, 1), 0) // kvh
        vb = jnp.where(token < length, vb, jnp.zeros_like(vb))
    s = (
        jax.lax.dot_general(
            qa, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * scale
    )  # [Hp, n] f32
    col = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (qa.shape[0], 1), 0)
    keep = jnp.logical_and(
        col % kvh == row // g,  # (a padded query row is no head's)
        first_key + col // kvh < length,
    )
    _softmax_update(jnp.where(keep, s, _NEG_INF), vb, acc_ref, m_ref, l_ref)


def _kernel_latent_paged(
    lengths_ref,  # scalar-prefetch [B] int32
    tables_ref,  # scalar-prefetch [B, P] int32: each row's page ids
    layer_ref,  # scalar-prefetch [1] int32: the layer of the stack to read
    q_ref,  # [1, H, W]: every head's absorbed query
    k_hbm,  # [L, NB, BLK, W]: the latent pool where it lies
    o_ref,  # [1, H, latent]
    k_buf,  # [SLOTS, run * BLK, W]: a run's rows, the keys of every head
    sem,  # DMA[SLOTS]
    slot_ref,  # SMEM[1]
    acc_ref, m_ref, l_ref,  # [H, latent], [H, 128], [H, 128] float32
    *, scale: float, blk: int, run: int, latent: int,
):
    """:func:`_kernel_paged` for latent pages: the same walk (grid the
    rows, a row's pages a run of a mebibyte at a time, fetched by the kernel
    out of the pool where it lies, later runs' copies started before this
    one is computed on), with a body and an issue of its own.  The other
    legs are paced by their bytes (7-28 operations a byte); this one
    multiplies H queries by rows of W lanes, then by ``latent`` of them
    again (121 operations a byte at 64 heads), and is paced by the
    instructions it issues: under the shared walk a row took 0.34 us + 0.045
    a page (its copy started and awaited, a page a turn of a loop) + 1.00 a
    RUN, whatever the run held, where a page's bytes take 0.10 (PERF.md,
    PR 63).  Three things differ:

    - the body is ONE online-softmax update over the blocks of a run that
      hold a page of the row (blocks of :func:`_latent_block_pages`): the
      number of them picks the program, one a size.  An update costs 0.35
      us before its first key (the chain from the scores through the rows'
      maxima to the value product) and 0.8 ns a key, so the live blocks
      are not walked one update each, which lost to the whole run.  A
      block's pages past the row's last keep what an earlier run left
      there: masked out of the scores, and on the value side multiplied by
      0.0 (the buffers start as zeros).  The arithmetic a (query, key)
      pair sees is :func:`_softmax_all_heads`' at one KV head;
    - a run's copies are started and awaited by the powers of two its page
      count is the sum of, each power straight-line code (0.018 us a page;
      a wait of n pages is one wait: a DMA semaphore counts what arrived);
    - the copies run TWO runs ahead, through three buffers: with the next
      run alone in flight a row's short last run, once its products follow
      what it holds, ends before the next row's first run has landed."""
    block = _latent_block_pages(run)
    slots = k_buf.shape[0]
    bi, rows = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]
    powers = [1 << i for i in reversed(range(run.bit_length()))]

    def pages_of(b):
        # At least one: a row of length 0 takes one (wholly masked) run.
        return jnp.clip(
            pl.cdiv(lengths_ref[b], blk), 1, tables_ref.shape[1])

    def by_powers(pages, fn):
        """``fn(first, n)`` for the pieces [first, first + n) of ``pages``,
        n a power of two (static), largest first."""
        for n in powers:
            @pl.when(pages & n != 0)
            def _(n=n):
                fn(pages - (pages & (2 * n - 1)), n)

    def start(b, j, slot):
        def pieces(first, n):
            for r in range(n):
                pltpu.make_async_copy(
                    k_hbm.at[layer, tables_ref[b, j * run + first + r]],
                    k_buf.at[slot, pl.ds((first + r) * blk, blk)],
                    sem.at[slot]).start()
        by_powers(jnp.minimum(run, pages_of(b) - j * run), pieces)

    def wait(slot, pages):
        def pieces(first, n):
            into = k_buf.at[slot, pl.ds(first * blk, n * blk)]
            pltpu.make_async_copy(into, into, sem.at[slot]).wait()
        by_powers(pages, pieces)

    def ahead(b, j, turns: int):
        """The run ``turns`` after row ``b``'s run ``j`` in the order they
        are computed on: the row's next, else the next row's first (row
        ``rows``: there is none)."""
        for _ in range(turns):
            more = j + 1 < pl.cdiv(pages_of(jnp.minimum(b, rows - 1)), run)
            b, j = jnp.where(more, b, b + 1), jnp.where(more, j + 1, 0)
        return b, j

    def start_ahead(b, j, slot):
        @pl.when(b < rows)
        def _():
            start(b, j, slot)

    @pl.when(bi == 0)
    def _first():
        # (see _kernel_paged: never-written VMEM could hold a NaN)
        k_buf[...] = jnp.zeros_like(k_buf)
        slot_ref[0] = 0
        for turns in range(slots - 1):
            start_ahead(*ahead(0, 0, turns), turns)

    _softmax_init(acc_ref, m_ref, l_ref)
    length = lengths_ref[bi]
    runs = pl.cdiv(pages_of(bi), run)
    qa = q_ref[0]

    def one_run(j, slot):
        start_ahead(*ahead(bi, j, slots - 1), (slot + slots - 1) % slots)
        live = jnp.minimum(run, pages_of(bi) - j * run)
        wait(slot, live)
        jax.lax.switch(pl.cdiv(live, block) - 1, [
            functools.partial(
                _latent_update, qa, k_buf.at[slot], acc_ref, m_ref, l_ref,
                n=k * block * blk, latent=latent, first_key=j * (run * blk),
                length=length, scale=scale)
            for k in range(1, run // block + 1)])
        return (slot + 1) % slots

    slot_ref[0] = jax.lax.fori_loop(0, runs, one_run, slot_ref[0])
    _softmax_done(o_ref, acc_ref, l_ref)


def _lanes(x, n: int):
    """[H, 128] with a row's value in every lane, as [H, n]."""
    if n % 128 == 0:
        return pltpu.repeat(x, n // 128, 1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _latent_update(qa, k_ref, acc_ref, m_ref, l_ref, *, n: int, latent: int,
                   first_key, length, scale: float):
    """One online-softmax update of every head's state with the first
    ``n`` rows of ``k_ref``, keys at positions ``first_key ...``:
    :func:`_softmax_all_heads`' arithmetic at one KV head, the values the
    first ``latent`` columns of the keys' rows, with the running maximum
    and sum read and written as they lie, a row's value in every lane of
    its [H, 128] scratch row (a column pulled out into a lane vector and
    put back, twice an update, was 0.10 us of an update's 0.45 before its
    first key: PERF.md, PR 63)."""
    s = (
        jax.lax.dot_general(
            qa, k_ref[:n, :].astype(qa.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * scale
    )  # [H, n] f32
    col = first_key + jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    s = jnp.where(col < length, s, _NEG_INF)
    vb = k_ref[:n, :latent].astype(qa.dtype)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    safe = jnp.where(m_new <= _NEG_INF * 0.5, 0.0, m_new)
    p = jnp.exp(s - _lanes(safe, n))
    alpha = jnp.exp(m_prev - safe)
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * _lanes(alpha, latent) + (
        jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    )
    m_ref[...] = m_new


def _kernel_paged(
    lengths_ref,  # scalar-prefetch [B] int32
    tables_ref,  # scalar-prefetch [B, P] int32: each row's page ids
    layer_ref,  # scalar-prefetch [1] int32: the layer of the stack to read
    q_ref,  # [1, Hp, D] (int8 leg: [1, KVH*Gp, D], as _kernel's)
    k_hbm,  # the pool where it lies, never blocked or copied:
    #   [L, NB, BLK*KVH, D] (int8 leg: [L, NB, BLK, KVH, D])
    *rest,  # v_hbm; int8 leg: [ks_hbm
    #   [NB, BLK, 128] f32 (this layer's), vs_hbm]; then o_ref and the
    #   scratch: k_buf / v_buf [2, run pages' rows, D] ([ks_buf / vs_buf
    #   [2, run*BLK, 128]]), sem DMA[2], slot SMEM[1], acc [Hp, D],
    #   m / l [Hp, 128]
    scale: float,
    blk: int,
    run: int,  # pages a run: fetched together, one softmax update for all
    kvh: int,
    g: int,  # queries a KV head (int8 leg: padded to eight, _kernel's gp)
    quant: bool = False,
    zero_unread: bool = False,  # see _softmax_all_heads (the rings)
):
    """Paged variant: grid ``(B,)``, and inside a row a loop over its RUNS
    of ``run`` pages.  The kernel fetches a run itself — a DMA a page (and
    its scales'), straight from the page table's entry in this layer of
    the pool, all of a run's in flight together — into one of two buffers,
    and starts the next run's (the row's, or the next row's first) before
    it computes on this one's.  A row takes ceil(pages it holds / run)
    turns of the loop and none for page slots it cannot fill; only pages
    the row holds are read.  The compute is one online-softmax update a
    run: :func:`_softmax_all_heads`, or for int8 pages, whose scales lie a
    head to a lane, the contiguous kernel's :func:`_softmax_block`.
    (Latent pages have a kernel of their own on the same walk,
    :func:`_kernel_latent_paged`.)"""
    if quant:
        (v_hbm, ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem,
         slot_ref, acc_ref, m_ref, l_ref) = rest
    else:
        v_hbm, o_ref, k_buf, v_buf, sem, slot_ref, acc_ref, m_ref, l_ref = rest
    bi, rows = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]
    page_rows = k_buf.shape[1] // run

    def pages_of(b):
        # At least one: a row of length 0 takes one (wholly masked) run.
        return jnp.clip(
            pl.cdiv(lengths_ref[b], blk), 1, tables_ref.shape[1])

    def copies(b, j, slot, start: bool):
        """Start, or wait for, the DMAs of row ``b``'s run ``j`` into
        buffer ``slot``: one a page the row holds there (a wait needs the
        copy's shape and semaphore, not its source)."""
        first = j * run

        def page(r, carry):
            pg = tables_ref[b, first + r] if start else 0
            into = pl.ds(r * page_rows, page_rows)
            pairs = [(k_hbm.at[layer, pg], k_buf.at[slot, into]),
                     (v_hbm.at[layer, pg], v_buf.at[slot, into])]
            if quant:
                into = pl.ds(r * blk, blk)
                pairs += [(ks_hbm.at[pg], ks_buf.at[slot, into]),
                          (vs_hbm.at[pg], vs_buf.at[slot, into])]
            for src, dst in pairs:
                dma = pltpu.make_async_copy(src, dst, sem.at[slot])
                dma.start() if start else dma.wait()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(run, pages_of(b) - first), page, 0)

    @pl.when(bi == 0)
    def _first():
        # A run's buffer past the row's last page keeps what an earlier
        # run left there, masked out of the scores (``keep``) but still
        # multiplied (by 0.0) in the value product: never-written VMEM
        # could hold a NaN, so the value side starts as zeros.
        v_buf[...] = jnp.zeros_like(v_buf)
        if quant:
            vs_buf[...] = jnp.zeros_like(vs_buf)
        slot_ref[0] = 0
        copies(0, 0, 0, start=True)

    _softmax_init(acc_ref, m_ref, l_ref)
    length = lengths_ref[bi]
    runs = pl.cdiv(pages_of(bi), run)

    def one_run(j, slot):
        @pl.when(j + 1 < runs)
        def _next_run():
            copies(bi, j + 1, 1 - slot, start=True)

        @pl.when(jnp.logical_and(j + 1 == runs, bi + 1 < rows))
        def _next_row():
            copies(bi + 1, 0, 1 - slot, start=True)

        copies(bi, j, slot, start=False)
        state = dict(first_key=j * (run * blk), length=length, scale=scale,
                     kvh=kvh)
        if quant:
            _softmax_block(q_ref, k_buf.at[slot], v_buf.at[slot],
                           (ks_buf.at[slot], vs_buf.at[slot]),
                           acc_ref, m_ref, l_ref, gp=g, **state)
        else:
            _softmax_all_heads(q_ref, k_buf.at[slot], v_buf.at[slot],
                               acc_ref, m_ref, l_ref, g=g,
                               zero_unread=zero_unread, **state)
        return 1 - slot

    slot_ref[0] = jax.lax.fori_loop(0, runs, one_run, slot_ref[0])
    _softmax_done(o_ref, acc_ref, l_ref)


def _dequant(k, v, k_scale, v_scale, dtype):
    """Restore int8 K/V to the compute dtype for the dense fallback —
    checkpoint.quantize.kv_dequantize numerics (f32(data) * scale), the
    reference the fused kernel leg is parity-tested against."""
    from ..checkpoint.quantize import kv_dequantize

    return kv_dequantize(k, k_scale, dtype), kv_dequantize(v, v_scale, dtype)


def _check_quant(k, k_scale, v_scale):
    """Validate the int8 leg's argument contract (both scales or neither;
    int8 data when scales are present)."""
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    if k_scale is not None and k.dtype != jnp.int8:
        raise ValueError(
            f"KV scales given but pages are {k.dtype}, not int8"
        )
    return k_scale is not None


def _dense_reference(q, k, v, lengths, window=None):
    """Masked dot-product prefix attention — the numerics the kernel must
    match and the fallback for untileable shapes / non-kernel modes.
    Mirrors layers.dot_product_attention exactly (f32 score accumulation,
    f32 softmax, probs cast to v.dtype) so substituting this fallback under
    ``cfg.ragged_decode`` cannot move tokens relative to the dense path."""
    from ..models import layers

    b, t, h, d = q.shape
    s = k.shape[1]
    g = h // k.shape[2]
    kf = layers.repeat_kv(k.astype(q.dtype), g)
    vf = layers.repeat_kv(v.astype(q.dtype), g)
    slots = jnp.arange(s, dtype=jnp.int32)
    mask = slots[None, :] < lengths[:, None]  # [B, S]
    if window is not None:
        mask = jnp.logical_and(mask, slots[None, :] >= lengths[:, None] - window)
    return layers.dot_product_attention(q, kf, vf, mask[:, None, None, :])


def ragged_decode_attention(
    q: jax.Array,  # [B, 1, H, D] — one query token per row
    k: jax.Array,  # [B, S, KVH, D] full cache width
    v: jax.Array,  # [B, S, KVH, D]
    lengths: jax.Array,  # [B] int32 — row b attends slots [0, lengths[b])
    block_k: int = 256,
    window: int | None = None,  # sliding window: row b attends only
    #   [lengths[b] - window, lengths[b]) — the index maps clamp the DMA
    #   walk into that band, so windowed long-context decode reads
    #   O(window) KV bytes per row instead of O(length)
    k_scale: jax.Array | None = None,  # [B, S, KVH] f32 absmax scales —
    #   int8 leg: k/v are int8 and the kernel folds the per-(slot, head)
    #   scales into the attention contraction (q.k_i8 * scale)
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Returns [B, 1, H, D] in q.dtype.  Inference-only (no VJP).

    Under a tensor-parallel mesh (:func:`dispatch.sharded`) each shard
    runs the unchanged kernel on its local KV-head slice, with no
    collective (attention heads are independent per KV head); lengths
    shard with the batch axis (or replicate on a pure-TP mesh)."""
    mode = dispatch.attention_mode()
    quant = _check_quant(k, k_scale, v_scale)
    impl = functools.partial(
        _ragged_impl, block_k=block_k, window=window, mode=mode
    )
    args = (q, k, v, lengths.astype(jnp.int32))
    if quant:
        args += (k_scale.astype(jnp.float32), v_scale.astype(jnp.float32))
    mesh = dispatch.mesh()
    if mesh is None or mode == "fallback":  # XLA partitions the dense path
        return impl(*args)
    specs, out_spec = spmd_operand_specs(
        mesh, q.shape, k.shape, paged=False, quant=quant
    )
    return dispatch.per_shard(
        impl, mesh, tuple(specs.values()), out_spec
    )(*args)


def _ragged_impl(
    q, k, v, lengths, k_scale=None, v_scale=None, *,
    block_k: int = 256, window: int | None = None, mode: str = "fallback",
) -> jax.Array:
    """The single-shard body: kernel when the (local) shapes tile, dense
    fallback otherwise — total over any shard, exactly like
    quant_matmul._qmm_flat."""
    b, t, h, d = q.shape
    assert t == 1, "ragged decode attention is single-token by construction"
    quant = k_scale is not None
    s, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    # Largest K block that tiles the cache width exactly — a width that is a
    # 128-multiple but not a block_k-multiple (384, 640, ...) must step down
    # to a smaller block, not silently lose the kernel to the dense path —
    # AND whose whole-KVH K+V blocks fit double-buffered in VMEM.
    bk = next(
        (
            c
            for c in (min(block_k, 512), 256, 128)
            if c <= s and s % c == 0 and _kv_vmem_ok(c, kvh, d, k.dtype)
        ),
        None,
    )
    tileable = bk is not None and d % 128 == 0
    if mode == "fallback" or not tileable:
        dispatch.record("ragged_decode", "fallback", (b, s, h, kvh, d))
        if quant:
            k, v = _dequant(k, v, k_scale, v_scale, q.dtype)
        return _dense_reference(q, k, v, lengths, window)
    dispatch.record("ragged_decode", mode, (b, s, h, kvh, d))

    gp = _round_up(g, 8)  # sublane-pad the per-kv-head query group
    # [B, KVH, G, D]: head ordering h = kv*g + i matches repeat_kv /
    # flash's hi // g convention.  Reshaping/padding q copies only the tiny
    # query; k/v stay in the cache's NATIVE [B, S, KVH, D] layout — a 4D
    # BlockSpec slices (1, bk, KVH, D) blocks straight out of HBM, so the
    # cache is never transposed or copied (it is also the decode loop's
    # carry; a relayout would be a full extra read+write per step).
    qt = q[:, 0].reshape(b, kvh, g, d)
    if gp != g:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    nk = s // bk

    def kv_index(bi, ji, lengths_ref):
        last = jax.lax.div(jnp.maximum(lengths_ref[bi] - 1, 0), bk)
        kk = jnp.minimum(ji, last)
        if window is not None:
            first = jax.lax.div(
                jnp.maximum(lengths_ref[bi] - window, 0), bk
            )
            kk = jnp.maximum(kk, first)
        return (bi, kk, 0, 0)

    def scale_index(bi, ji, lengths_ref):
        # Same DMA walk as the K/V blocks, one axis shorter ([B, S, KVH]).
        return kv_index(bi, ji, lengths_ref)[:3]

    in_specs = [
        pl.BlockSpec((1, kvh * gp, d), lambda bi, ji, L: (bi, 0, 0)),
        pl.BlockSpec((None, bk, kvh, d), kv_index),
        pl.BlockSpec((None, bk, kvh, d), kv_index),
    ]
    operands = [lengths.astype(jnp.int32), qt.reshape(b, kvh * gp, d), k, v]
    if quant:
        in_specs += [
            pl.BlockSpec((None, bk, kvh), scale_index),
            pl.BlockSpec((None, bk, kvh), scale_index),
        ]
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=d**-0.5, block_k=bk, num_k_blocks=nk,
            kvh=kvh, gp=gp, window=window, quant=quant,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, kvh * gp, d), lambda bi, ji, L: (bi, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((kvh * gp, d), jnp.float32),
                pltpu.VMEM((kvh * gp, 128), jnp.float32),
                pltpu.VMEM((kvh * gp, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh * gp, d), q.dtype),
        interpret=mode == "interpret",
        name="ragged_decode_attn",  # the operation's name in a trace
    )(*operands)
    out = out.reshape(b, kvh, gp, d)[:, :, :g]  # [B, KVH, G, D]
    return out.reshape(b, 1, h, d)


def _kv_vmem_ok(bk: int, kvh: int, d: int, dtype) -> bool:
    """Whole-KVH K+V blocks, double-buffered, must leave room for scratch
    and the Mosaic pipeline inside ~16 MB of VMEM; budget half of it."""
    return 4 * bk * kvh * d * jnp.dtype(dtype).itemsize <= 8 * 1024 * 1024


def _run_pages(blk: int, kvh: int, d: int, dtype, p: int) -> int:
    """Pages the paged kernel walks at a time: a mebibyte of keys and
    values, at most the ``p`` a row can hold.  A run's copies then take
    HBM 1.3 us and a turn of the kernel's loop costs 0.15 us beside them;
    a run of one 128-KB page takes 0.5 us for 0.16 us of copies (PERF.md,
    PR 31).  Two such buffers each for keys and values are a quarter of
    what :func:`_kv_vmem_ok` allows."""
    page = 2 * blk * kvh * d * jnp.dtype(dtype).itemsize
    return max(1, min((1 << 20) // page, p))


def paged_decode_attention(
    q: jax.Array,  # [B, 1, H, D]
    k_pages: jax.Array,  # [L, NB, BLK, KVH, D] — the shared page pool, every
    #                     layer's pages in one stack (or one layer's
    #                     [NB, BLK, KVH, D]: the stack of one layer)
    v_pages: jax.Array,  # same shape
    lengths: jax.Array,  # [B] int32 — row b attends its first lengths[b] slots
    tables: jax.Array,  # [B, P] int32 — page ids; entries past the row's
    #                     depth may be arbitrary (never dereferenced by the
    #                     kernel: it fetches the pages a row holds; the
    #                     fallback masks their scores)
    k_scale: jax.Array | None = None,  # [L, NB, BLK, KVH] f32 absmax scales
    #                     (one axis fewer for a rank-4 pool) — int8 leg:
    #                     pages are int8 (QuantKVCache pools) and
    #                     the kernel fuses scale into the contraction, so
    #                     the pool reads 1 byte/elem and a dequantized page
    #                     never exists in HBM
    v_scale: jax.Array | None = None,
    layer: jax.Array | int = 0,  # which layer of the stack to read (traced
    #                     inside the layer scan)
) -> jax.Array:
    """Paged variant of :func:`ragged_decode_attention`: the KV cache lives
    as pool pages indexed per row through a block table (vLLM-style memory
    management, TPU-native static shapes).  The page table and the layer
    index are scalar-prefetched; the pool stays in HBM whole, and the
    kernel copies each row's pages out of that layer of the stack itself,
    a run of pages at a time (:func:`_kernel_paged`), so it reads only the
    row's real depth and takes no step for page slots the row cannot
    fill: the caller never slices a layer out of the pool (a Pallas call
    wants each operand as a buffer of its own, so a slice handed in is a
    copy of that layer, every layer, every step).
    Returns [B, 1, H, D] in q.dtype.  Inference-only.

    Under a tensor-parallel mesh (:func:`dispatch.sharded`) the pool (and
    its int8 scales) shard over the KV-head axis, each shard runs the
    kernel on its local head slice, and the page table + lengths
    replicate on a pure-TP mesh (they shard only with an explicit batch
    axis)."""
    mode = dispatch.attention_mode()
    quant = _check_quant(k_pages, k_scale, v_scale)
    if k_pages.ndim == 4:  # one layer's pages: the stack of one layer
        k_pages, v_pages = k_pages[None], v_pages[None]
        if quant:
            k_scale, v_scale = k_scale[None], v_scale[None]
    impl = functools.partial(_paged_impl, mode=mode)
    args = (q, k_pages, v_pages, lengths.astype(jnp.int32),
            tables.astype(jnp.int32),
            jnp.asarray(layer, jnp.int32).reshape(1))
    if quant:
        args += (k_scale.astype(jnp.float32), v_scale.astype(jnp.float32))
    mesh = dispatch.mesh()
    if mesh is None or mode == "fallback":  # XLA partitions the dense path
        return impl(*args)
    specs, out_spec = spmd_operand_specs(
        mesh, q.shape, k_pages.shape, paged=True, quant=quant
    )
    return dispatch.per_shard(
        impl, mesh, tuple(specs.values()), out_spec
    )(*args)


def _paged_impl(
    q, k_pages, v_pages, lengths, tables, layer, k_scale=None, v_scale=None,
    *, mode: str = "fallback", run: int | None = None,
    op: str = "paged_decode",
) -> jax.Array:
    """Single-shard body of the paged kernel (see _ragged_impl): the pool
    is the stack [L, NB, BLK, KVH, D] and ``layer`` [1] int32 names the
    layer to read.  ``run`` is :func:`_run_pages`' to work out; only
    tools/paged_attn_bench.py, which times the others, names one.  ``op``
    names the call in the dispatch record and, with ``_attn`` behind it,
    in a trace: "swa_decode" reads the windowed layers' rings
    (:func:`swa_decode_attention`)."""
    b, t, h, d = q.shape
    assert t == 1, "paged decode attention is single-token by construction"
    quant = k_scale is not None
    blk = k_pages.shape[2]
    # A pool of heads narrower than a 128-lane row keeps ``fold`` of them
    # to a row (pool_head_shape): [.., KVH / fold, fold * D].
    fold = k_pages.shape[4] // d
    kvh = k_pages.shape[3] * fold
    p = tables.shape[1]
    g = h // kvh
    tileable = (
        blk % 8 == 0 and (d * fold) % 128 == 0
        and _kv_vmem_ok(blk, kvh, d, k_pages.dtype)
    )
    if mode == "fallback" or not tileable:
        dispatch.record(op, "fallback", (b, blk, h, kvh, d))
        # Gather the rows' pages out of the layer into contiguous
        # [B, P*BLK] caches (the fallback materializes; the kernel never
        # does).  Int8 pools dequantize the gathered rows at
        # kv_dequantize numerics.
        k_rows = k_pages[layer[0]][tables].reshape(b, p * blk, kvh, d)
        v_rows = v_pages[layer[0]][tables].reshape(b, p * blk, kvh, d)
        if quant:
            k_rows, v_rows = _dequant(
                k_rows, v_rows,
                k_scale[layer[0]][tables].reshape(b, p * blk, kvh),
                v_scale[layer[0]][tables].reshape(b, p * blk, kvh),
                q.dtype,
            )
        return _dense_reference(q, k_rows, v_rows, lengths)

    run = run or _run_pages(blk, kvh, d, k_pages.dtype, p)
    dispatch.record(op, mode, (b, blk, h, kvh, d, run))
    if op == "paged_decode":
        METRICS.set_gauge("ops.dispatch.paged_decode.run_pages", run)
    scale = d**-0.5
    qt = q[:, 0].reshape(b, kvh, g, d)
    if fold > 1:
        qt = _fold_queries(qt, fold)
        _, kvh, g, d = qt.shape
    if quant:
        # The contiguous kernel's rows: a KV head's queries padded to
        # eight, its keys pulled out of the page a head at a time.
        gp = _round_up(g, 8)
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
        # A pool of ONE KV head (qwen2's four over mesh.model=4) goes in
        # without the axis: the device keeps [L, NB, BLK, 1, D] tiled over
        # (BLK, D), where a Pallas operand of that rank must be tiled
        # over (1, D) — the whole stack would be copied into that layout,
        # in every layer.  Without the axis the reshape is free.
        page = (blk, kvh, d) if kvh > 1 else (blk, d)
    else:
        # Every head's queries in one block, and a page as the rows
        # (token, KV head) it is in memory: the device tiles
        # [.., BLK, KVH, D] and [.., BLK * KVH, D] alike, so the reshape
        # is free and the operand is the pool as it lies.
        gp = g
        page = (blk * kvh, d)
    k_pages, v_pages = (x.reshape(*x.shape[:2], *page)
                        for x in (k_pages, v_pages))
    hp = _round_up(kvh * gp, 8)
    qt = jnp.pad(qt.reshape(b, kvh * gp, d),
                 ((0, 0), (0, hp - kvh * gp), (0, 0)))
    # The pool stays in HBM whole (pl.ANY): the kernel copies the pages a
    # row holds, a run at a time, into its own two buffers.
    q_spec = pl.BlockSpec((1, hp, d), lambda bi, L, T, Y: (bi, 0, 0))
    in_specs = [q_spec] + [pl.BlockSpec(memory_space=pl.ANY)] * 2
    operands = [
        lengths.astype(jnp.int32), tables.astype(jnp.int32), layer, qt,
        k_pages, v_pages,
    ]
    scratch = [
        pltpu.VMEM((2, run * page[0], *page[1:]), k_pages.dtype)] * 2
    if quant:
        # The scales go in as THIS layer's slice, not as the stack, and
        # a page's as [BLK, 128] float32 rows with the heads in the first
        # KVH lanes: an operand left in HBM is tiled a 128-lane row at a
        # time, so four heads pad to 128 lanes either way, 32-fold.  The
        # slice is padded as it is cut (17 MB for 512 pages); the stack
        # handed whole would be padded whole, in every layer.
        lanes = _round_up(kvh, 128)
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        operands += [
            jnp.pad(
                jax.lax.dynamic_index_in_dim(
                    x.astype(jnp.float32), layer[0], keepdims=False),
                ((0, 0), (0, 0), (0, lanes - kvh)))
            for x in (k_scale, v_scale)
        ]
        scratch += [pltpu.VMEM((2, run * blk, lanes), jnp.float32)] * 2
    out = pl.pallas_call(
        functools.partial(
            _kernel_paged, scale=scale, blk=blk, run=run, kvh=kvh, g=gp,
            quant=quant, zero_unread=op == "swa_decode",
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=scratch + [
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((hp, d), jnp.float32),
                pltpu.VMEM((hp, 128), jnp.float32),
                pltpu.VMEM((hp, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hp, d), q.dtype),
        # Rows run in order: each starts the next one's first fetch.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=mode == "interpret",
        name=f"{op}_attn",  # the operation's name in a trace
    )(*operands)
    out = out[:, : kvh * gp].reshape(b, kvh, gp, d)[:, :, :g]
    if fold > 1:
        # Query group i of a folded head reads its answer in lane group i.
        out = jnp.einsum(
            "bjigkd,ik->bjigd",
            out.reshape(b, kvh, fold, g // fold, fold, d // fold),
            jnp.eye(fold, dtype=out.dtype),
        )
    return out.reshape(b, 1, h, q.shape[-1])


_RING_BLOCK = 64  # tokens a block of a ring that is walked in blocks


def ring_block(w: int, kvh: int, d: int, dtype) -> int:
    """Tokens a page of a windowed layer's ring of ``w`` tokens, as
    :func:`swa_decode_attention` hands it to the paged kernel.  A ring
    whose keys and values are at most the mebibyte the kernel fetches at a
    time (:func:`_run_pages`) is ONE page, a run of its own (K-EXAONE's 128
    tokens at 8 heads of 128: 512 KB).  A larger one (4,096 tokens at 4
    heads of 128: 8 MB, which :func:`_kv_vmem_ok` refuses as a page) is
    walked in blocks of ``_RING_BLOCK`` tokens, the pool's own page size,
    so that a row is fetched by the live blocks it holds, a run of them at
    a time, and a window no multiple of the block stays one page (and then
    takes the dense body if it is too large)."""
    one_run = 2 * w * kvh * d * jnp.dtype(dtype).itemsize <= 1 << 20
    return w if one_run or w % _RING_BLOCK else _RING_BLOCK


def swa_decode_attention(
    q: jax.Array,  # [B, 1, H, D]
    ring_k: jax.Array,  # [Lw, B, W, KVH, D]: every windowed layer's rings,
    ring_v: jax.Array,  #   one a batch slot (kv_cache.HybridCache)
    counts: jax.Array,  # [B] int32: row b attends entries [0, counts[b])
    layer: jax.Array | int = 0,  # which windowed layer's rings to read
) -> jax.Array:
    """A decode step's attention over a windowed layer's RINGS: row b's
    last min(length, W) keys and values lie in ring b in no order that
    matters (the key of position p at p mod W, already rotated), so the
    read is the paged kernel's with the rings as a pool: [Lw, B, W, ..] is
    [Lw, B * W/blk, blk, ..] by a free reshape, row b's page table is
    arithmetic (b * W/blk + j) and its length the count, and
    :func:`_paged_impl`'s walk does the rest: the stack stays in HBM
    whole, the kernel copies the pages (layer, row) holds where they lie,
    a run at a time, the LIVE ones only (a row of 700 tokens in a ring of
    4,096 fetches 11 blocks of 64, not 64), and masks by count.  A small
    ring is one page (:func:`ring_block`).  Entries past the count (a
    shorter row, a finished row's leftovers) are read as zeros whatever
    they hold.  Under its own name in a trace (``swa_decode_attn``) and in
    the dispatch record (``ops.dispatch.swa_decode.*``).  Returns
    [B, 1, H, D].  Single-device (the rings refuse a mesh)."""
    lw, b, w, kvh, d = ring_k.shape
    blk = ring_block(w, kvh, d, ring_k.dtype)
    n = w // blk  # pages a row
    tables = jnp.arange(b, dtype=jnp.int32)[:, None]
    if n > 1:
        ring_k, ring_v = (x.reshape(lw, b * n, blk, kvh, d)
                          for x in (ring_k, ring_v))
        tables = tables * n + jnp.arange(n, dtype=jnp.int32)[None, :]
    return _paged_impl(
        q, ring_k, ring_v, counts.astype(jnp.int32), tables,
        jnp.asarray(layer, jnp.int32).reshape(1), mode=dispatch.attention_mode(),
        op="swa_decode")


def _latent_run_pages(blk: int, w: int, dtype, p: int) -> int:
    """:func:`_run_pages` for latent pages, which have one buffer where
    keys and values have two: a mebibyte of rows (12 pages of 64 x 640
    bf16), at most the ``p`` a row can hold, and whole blocks of
    ``_LATENT_BLOCK_PAGES`` where it is more than one."""
    run = max(1, min((1 << 20) // (blk * w * jnp.dtype(dtype).itemsize), p))
    return run - run % min(run, _LATENT_BLOCK_PAGES)


# Pages a block of the latent kernel's body (:func:`_kernel_latent_paged`):
# 256 keys of a 64-token page, two 128-wide tiles of the matrix unit, three
# sizes of update a run of 12.  Chosen on the chip among 2, 4 and 6 (2.95,
# 2.88 and 2.91 us a row: PERF.md, PR 63); a constant of the kernel.
_LATENT_BLOCK_PAGES = 4
# Buffers of a run the latent kernel's copies go through: the one computed
# on and the two runs after it (2.9 MB of VMEM at 12 pages of 64 x 640).
_LATENT_SLOTS = 3


def _latent_block_pages(run: int) -> int:
    """Pages a block where a run is ``run`` pages: ``_LATENT_BLOCK_PAGES``,
    or the most under it that divide the run (a run that
    tools/paged_attn_bench.py names; :func:`_latent_run_pages` gives whole
    blocks)."""
    return next(n for n in range(min(run, _LATENT_BLOCK_PAGES), 0, -1)
                if run % n == 0)


def mla_scored_keys(lengths: jax.Array, blk: int, w: int, dtype,
                    p: int) -> jax.Array:
    """Keys the latent kernel's products cover for rows of ``lengths``
    tokens, a layer: the pages a row holds (one at least, ``p`` at most)
    in whole blocks.  ``lengths`` over it is the share of the products that
    falls on a key the row holds (``mla.decode.scored_keys``)."""
    # A run is whole blocks, so a row's blocks are those of its pages.
    n = _latent_block_pages(_latent_run_pages(blk, w, dtype, p))
    return pl.cdiv(jnp.clip(pl.cdiv(lengths, blk), 1, p), n) * (n * blk)


def mla_paged_decode_attention(
    q: jax.Array,  # [B, 1, H, W]: a head's absorbed query, [q_nope W_uk^T
    #               (latent) | rotated q_rope | zeros] as a page row lies
    pages: jax.Array,  # [L, NB, BLK, W] latent page pool, every layer's
    lengths: jax.Array,  # [B] int32
    tables: jax.Array,  # [B, P] int32
    *, latent: int,  # the first ``latent`` columns of a row are its values
    scale: float,
    layer: jax.Array | int = 0,
) -> jax.Array:
    """:func:`paged_decode_attention` for latent (MLA) pages, the absorbed
    form: a token's row [c_kv | k_rope | 0] is the key of EVERY head (one
    KV head, H queries a group) and its first ``latent`` columns are every
    head's value, so a page is fetched once and serves both products.  The
    walk is :func:`_kernel_paged`'s (the pool left in HBM, a row's pages
    fetched a run of about a mebibyte at a time) in a kernel of its own,
    :func:`_kernel_latent_paged`: one update over the blocks of a run that
    hold a page of the row, the copies two runs ahead.  Returns [B, 1, H, latent]: per head the attention-weighted sum
    of latents, which the caller multiplies by the head's W_uv.
    Single-device (the format refuses a mesh)."""
    return _mla_paged_impl(
        q, pages, lengths.astype(jnp.int32), tables.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1), latent=latent, scale=scale,
        mode=dispatch.attention_mode())


def _mla_paged_impl(q, pages, lengths, tables, layer, *, latent: int,
                    scale: float, mode: str = "fallback",
                    run: int | None = None) -> jax.Array:
    b, t, h, w = q.shape
    assert t == 1, "paged decode attention is single-token by construction"
    blk = pages.shape[2]
    p = tables.shape[1]
    tileable = (blk % 8 == 0 and w % 128 == 0 and latent % 128 == 0
                and h % 8 == 0)
    if mode == "fallback" or not tileable:
        # ("paged_decode" too: the family of kernels that serve a decode
        # step from pages, which the benchmark's dispatch check names.)
        dispatch.record("paged_decode", "fallback", (b, blk, h, 1, w))
        dispatch.record("mla_paged_decode", "fallback", (b, blk, h, w))
        rows = pages[layer[0]][tables].reshape(b, p * blk, w).astype(q.dtype)
        s = jnp.einsum("bhw,bsw->bhs", q[:, 0], rows,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.arange(p * blk, dtype=jnp.int32)[None, :] < lengths[:, None]
        s = jnp.where(mask[:, None, :], s, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhs,bsc->bhc", probs, rows[..., :latent])[:, None]
    run = run or _latent_run_pages(blk, w, pages.dtype, p)
    dispatch.record("paged_decode", mode, (b, blk, h, 1, w, run))
    dispatch.record("mla_paged_decode", mode, (b, blk, h, w, run))
    q_spec = pl.BlockSpec((1, h, w), lambda bi, L, T, Y: (bi, 0, 0))
    out = pl.pallas_call(
        functools.partial(
            _kernel_latent_paged, scale=scale, blk=blk, run=run,
            latent=latent,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(
                (1, h, latent), lambda bi, L, T, Y: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((_LATENT_SLOTS, run * blk, w), pages.dtype),
                pltpu.SemaphoreType.DMA((_LATENT_SLOTS,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((h, latent), jnp.float32),
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, latent), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=mode == "interpret",
        name="mla_paged_decode_attn",  # the operation's name in a trace
    )(lengths, tables, layer, q[:, 0], pages)
    return out[:, None]


def pool_head_shape(kvh: int, d: int, fold_narrow: bool) -> tuple[int, int]:
    """(heads, width) of a page pool's last two axes.  With ``fold_narrow``
    heads narrower than the 128 lanes of a row lie 128 // d to a row,
    [.., KVH / fold, fold * D]: a [.., 8, 64] array is tiled (8, 128), half
    of it padding, and handing it to the 128-lane kernel reshaped would
    copy the whole pool into the other layout in every layer.  The bytes
    and their order are those of [.., KVH, D].  Heads WIDER than a row
    (256) fold too, all KVH of them into one row of KVH * D lanes: the
    device tiles [.., KVH, 256] a (KVH, 128) lane group at a time, the
    kernel's rows (token, head) lie in another order, and the reshape would
    be the same copy of the pool (2 GB a layer a step at 5,184 pages:
    tools/aot_decode.py's ``temp_gb``); [.., 1, KVH * 256] is tiled over
    (BLK, KVH * 256) and goes in as it lies."""
    if fold_narrow and d > 128 and d % 128 == 0:
        return 1, kvh * d
    fold = 128 // d if fold_narrow and d < 128 and 128 % d == 0 else 1
    return (kvh // fold, d * fold) if kvh % fold == 0 else (kvh, d)


def _fold_queries(qt, fold: int):
    """Serve heads of 128 / ``fold`` lanes from the 128-lane kernel.  The
    pool keeps ``fold`` neighbouring KV heads in one 128-lane row
    (:func:`pool_head_shape`), which the kernel reads as ONE head; their
    query groups go in side by side, each with zeros in the other heads'
    lanes.  The zeros make the score of query group i the dot product with
    head i alone, and the value product leaves head i's answer in lane
    group i (:func:`_paged_impl` picks it out).  The kernel's body is the
    128-wide one; the price is ``fold`` times the arithmetic of an
    operation that is bound by its reads.

    qt [B, KVH, G, D] -> [B, KVH/fold, fold*G, fold*D]."""
    b, kvh, g, d = qt.shape
    return jnp.einsum(
        "bjigd,ik->bjigkd", qt.reshape(b, kvh // fold, fold, g, d),
        jnp.eye(fold, dtype=qt.dtype),
    ).reshape(b, kvh // fold, fold * g, fold * d)


# ---------------------------------------------------------------------------
# Operand placement on tensor-parallel serving meshes
# ---------------------------------------------------------------------------
#
# q heads and KV heads shard together over 'model' (the grouped ratio
# g = H/KVH is shard-invariant), the batch over 'data'.  Lengths and page
# tables shard only with the batch axis — on a pure-TP mesh they replicate;
# int8 absmax scales shard with their pages on the KV-head axis.  Pool
# pages are shared across rows, so the page axis never shards; nor does the
# pool's leading layer axis, and the layer index replicates.


def _ragged_operand_specs(b_ax, h_ax, quant: bool) -> dict:
    specs = {
        "q": P(b_ax, None, h_ax, None),
        "k": P(b_ax, None, h_ax, None),
        "v": P(b_ax, None, h_ax, None),
        "lengths": P(b_ax),
    }
    if quant:
        specs["k_scale"] = P(b_ax, None, h_ax)
        specs["v_scale"] = P(b_ax, None, h_ax)
    return specs


def _paged_operand_specs(b_ax, h_ax, quant: bool) -> dict:
    specs = {
        "q": P(b_ax, None, h_ax, None),
        "k_pages": P(None, None, None, h_ax, None),
        "v_pages": P(None, None, None, h_ax, None),
        "lengths": P(b_ax),
        "tables": P(b_ax, None),
        "layer": P(None),
    }
    if quant:
        specs["k_scale"] = P(None, None, None, h_ax)
        specs["v_scale"] = P(None, None, None, h_ax)
    return specs


def spmd_operand_specs(
    mesh, q_shape: tuple, kv_shape: tuple, *, paged: bool,
    quant: bool = False,
):
    """(operand-spec dict in call order, output spec) for one decode call
    on ``mesh``: the in_specs/out_specs the shard_map dispatch above runs
    with, and the audit surface tools/graftcheck GC2 structure-matches
    against abstract operand trees (axis names, rank, divisibility).
    Every shard must hold WHOLE heads on both operands (the kernel's
    static head loop), so an axis that does not divide replicates.
    ``kv_shape`` is the K operand: [B, S, KVH, D] contiguous or the
    [L, NB, BLK, KVH, D] pool stack."""
    b, _, h, _ = q_shape
    b_ax = dispatch.axis(mesh, "data", b)
    h_ax = dispatch.axis(mesh, "model", h, kv_shape[-2])
    build = _paged_operand_specs if paged else _ragged_operand_specs
    return build(b_ax, h_ax, quant), P(b_ax, None, h_ax, None)
