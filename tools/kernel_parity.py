#!/usr/bin/env python
"""On-device kernel parity check: the first thing to run on a new chip.

Compiles the five Pallas kernels (fused dequant-matmul, flash attention,
ragged and paged decode attention, the grouped expert matmul) on the
default JAX backend and compares against the einsum/dense references.
Interpret-mode CI (tests/ops/) proves the kernels' *programs*; this script proves Mosaic *lowering* — tiling, VMEM
budgets, sublane int4 unpack — which interpret mode cannot catch.  Exit 0 =
all parities hold compiled on this backend; exit 1 = mismatch, lowering
failure (stack trace printed), or a leg that did not take the path it was
meant to.

On the chip: `python tools/kernel_parity.py`.  Here, to check the script
itself on the Pallas interpreter: `JAX_PLATFORMS=cpu python
tools/kernel_parity.py`.
"""
from __future__ import annotations

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# TPU: force the compiled-kernel path (never a silent fallback "pass").
# Elsewhere: interpret mode — validates this script's own logic, proves
# nothing about Mosaic lowering.
ON_TPU = jax.default_backend() == "tpu"
MODE = "kernel" if ON_TPU else "interpret"
os.environ["DLT_QUANT_MATMUL"] = MODE
os.environ["DLT_RAGGED_DECODE"] = MODE
os.environ["DLT_MOE_EXPERTS"] = MODE

import jax.numpy as jnp
import numpy as np

from distributed_llms_tpu.checkpoint.quantize import (
    QuantizedTensor, dequantize, quantize)
from distributed_llms_tpu.core.observability import METRICS
from distributed_llms_tpu.models.layers import gate_fn
from distributed_llms_tpu.ops import decode_attn, moe_experts
from distributed_llms_tpu.ops.flash import _dense_reference, flash_attention
from distributed_llms_tpu.ops.quant_matmul import quant_contract

# The page size and head geometry chip_smoke.py serves (qwen2-7b: GQA 28/4
# at head dim 128, --page-size 64), beside the 128-slot pages of the
# original legs.
SERVED = dict(blk=64, h=28, kvh=4)
# pythia-6.9b's: 32 heads with no grouping, a page block eight times wider.
SERVED_MHA = dict(blk=64, h=32, kvh=32)
# One device's share of qwen2-7b under mesh.model=4: a single KV head,
# which the paged kernel reads out of a pool without its head axis.
SERVED_TP4 = dict(blk=64, h=7, kvh=1)
# lfm2-8b-a1b's: GQA 32/8 at head dim 64, two heads to a 128-lane pool row.
SERVED_H64 = dict(blk=64, h=32, kvh=8, d=64)


def _stacked(pool, layer, fill):
    """``pool`` as layer ``layer`` of a 3-layer stack [L, NB, BLK, ...] —
    what the serving programs hand the paged kernel — the other layers
    holding ``fill``; ``pool`` itself for the rank-4 form (``layer``
    None)."""
    if layer is None:
        return pool
    return jnp.full((3, *pool.shape), fill, pool.dtype).at[layer].set(pool)


def _layer_kw(layer) -> dict:
    """The keyword that reads layer ``layer`` of a stack (none for the
    rank-4 form)."""
    return {} if layer is None else {"layer": layer}


def check(name: str, got, want, rtol: float, atol: float) -> None:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol)
    err = float(np.max(np.abs(got - want)))
    print(f"  PASS {name:40s} max|err|={err:.3e}")


STACKED_LEGS = (
    (8, ((3584,), 18944), 2), (8, ((18944,), 3584), 2),
    (8, ((28, 128), 3584), 3), (4, ((4096,), 1024), 3),
    (8, ((4096,), 16384), 2), (8, ((16384,), 4096), 2),
    (8, ((2048,), 6144), 2), (8, ((7168,), 2048), 2),
    (8, ((7168,), 1536), 2), (8, ((1536,), 12288), 2),
    (8, ((8192,), 7168), 2),
)


def quant_parity() -> None:
    key = jax.random.PRNGKey(0)
    for bits in (8, 4):
        for m, k, n in ((8, 1024, 2048), (4, 4096, 4096)):
            kx, kw = jax.random.split(jax.random.fold_in(key, bits * 100 + m))
            x = jax.random.normal(kx, (m, k), jnp.bfloat16)
            w = jax.random.normal(kw, (k, n), jnp.float32) / np.sqrt(k)
            qt = quantize(w, bits=bits)
            got = jax.jit(lambda x, qt: quant_contract(x, qt, k_lead=1))(x, qt)
            want = jnp.asarray(x, jnp.float32) @ dequantize(qt, jnp.float32)
            # bf16 activations: kernel accumulates f32 but inputs quantize the
            # signal; match the suite's bf16 tolerance.
            check(f"quant int{bits} [{m}x{k}]@[{k}x{n}]", got, want,
                  rtol=2e-2, atol=2e-2)
    # The stacked form the layer scans hand over: every layer's weight
    # [L, N, K] in one operand, read at an index traced inside a scan, at
    # qwen2-7b's shapes (tiles of four blocks of whole rows for w_gate, of
    # every row's runs of 512 for w_down; wo's two contracted axes), as
    # int4 (one block a tile), and at pythia-6.9b's (w_in, w_out: a block
    # of whole rows a tile), lfm2-8b-a1b's (the convolution's in_proj, the
    # dense FFN's w_down) and A.X-K1's (wq_a, wq_b, wo).
    for bits, (k_shape, n), layers in STACKED_LEGS:
        kx, kw = jax.random.split(jax.random.fold_in(key, bits + n))
        x = jax.random.normal(kx, (16, *k_shape), jnp.bfloat16)
        k = int(np.prod(k_shape))

        def draw(i):
            w = jax.random.normal(jax.random.fold_in(kw, i), (*k_shape, n),
                                  jnp.float32) / np.sqrt(k)
            qt = quantize(w, bits=bits, k_axes=len(k_shape))
            return qt.data, qt.scale

        data, scale = jax.jit(lambda: jax.lax.map(draw, jnp.arange(layers)))()
        qt = QuantizedTensor(data=data, scale=scale, bits=bits,
                             orig_shape=(layers, *k_shape, n),
                             k_axes=len(k_shape))
        got = jax.jit(lambda x, qt: jax.lax.scan(
            lambda c, at: (c, quant_contract(x, qt.at(at), len(k_shape))),
            None, jnp.arange(layers, dtype=jnp.int32))[1])(x, qt)
        want = jnp.einsum(
            "mk,lkn->lmn", jnp.asarray(x, jnp.float32).reshape(16, k),
            dequantize(qt, jnp.float32).reshape(layers, k, n))
        check(f"quant int{bits} stacked L{layers} [16x{k}]@[{k}x{n}]", got,
              want, rtol=2e-2, atol=2e-2)


def quant_parent_arithmetic(k: int = 3584, n: int = 18944) -> None:
    """The lane-dense kernel on the turned leaf [N, K] against what the
    kernel of PRs 29-32 computed from the matrix [K, N]: every weight
    ``bf16(f32(q) * s)``, K summed in float32 in runs of 512.  Rows of x
    that pick one k each hand back the dequantized weights themselves:
    equal bit for bit.  Random rows: equal, or one bf16 ulp apart where the
    sums of a run's partial products are taken in another order; the leg
    prints which."""
    kx, kw = jax.random.split(jax.random.PRNGKey(33))
    w = jax.random.normal(kw, (k, n), jnp.float32) / np.sqrt(k)
    qt = jax.jit(quantize)(w)
    scale_kn = jnp.swapaxes(qt.scale, 0, 1)  # [K, N/128]
    w_ref = (jnp.swapaxes(qt.data, 0, 1).astype(jnp.float32).reshape(
        k, n // 128, 128) * scale_kn[:, :, None]).reshape(k, n).astype(
            jnp.bfloat16)
    run = jax.jit(lambda x, qt: quant_contract(x, qt, k_lead=1))

    @jax.jit
    def parent(x):
        acc = jnp.zeros((x.shape[0], n), jnp.float32)
        for r in range(0, k, 512):
            acc += jnp.dot(x[:, r:r + 512], w_ref[r:r + 512],
                           preferred_element_type=jnp.float32)
        return acc.astype(x.dtype)

    for m in (16, 256):
        picks = jax.random.permutation(jax.random.fold_in(kx, m), k)[:m]
        got_w = run(jax.nn.one_hot(picks, k, dtype=jnp.bfloat16), qt)
        if not bool(jnp.all(got_w == w_ref[picks])):
            raise AssertionError(
                f"dequantized weights differ from bf16(f32(q) * s) at M={m}")
        x = jax.random.normal(jax.random.fold_in(kx, m + 1), (m, k),
                              jnp.bfloat16)
        bits = [np.asarray(a).view(np.int16).astype(np.int32)
                for a in (run(x, qt), parent(x))]
        ulp = int(np.max(np.abs(bits[0] - bits[1])))
        if ulp > 1:
            raise AssertionError(f"outputs {ulp} bf16 ulp apart at M={m}")
        said = ("equal" if ulp == 0 else
                f"{int(np.sum(bits[0] != bits[1]))} of {m * n} one bf16 ulp "
                "apart (summation order)")
        print(f"  PASS quant int8 [{m}x{k}]@[{k}x{n}] weights bit-equal to "
              f"the [K, N] form's; outputs {said}")


def flash_parity() -> None:
    key = jax.random.PRNGKey(1)
    for b, t, s, h, kvh, d in ((2, 512, 512, 8, 4, 128),
                               (1, 2048, 2048, 8, 8, 128),
                               (1, 2048, 2048, 32, 8, 64)):  # lfm2's heads
        ks = jax.random.split(jax.random.fold_in(key, t), 3)
        q = jax.random.normal(ks[0], (b, t, h, d), jnp.bfloat16)
        kk = jax.random.normal(ks[1], (b, s, kvh, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, s, kvh, d), jnp.bfloat16)
        got = jax.jit(
            lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            interpret=not ON_TPU)
        )(q, kk, v)
        want = _dense_reference(q, kk, v, None, None, None, True)
        check(f"flash causal B{b} T{t} S{s} H{h}/{kvh} D{d}", got, want,
              rtol=3e-2, atol=3e-2)
    # Sliding-window band (Mistral/Phi-3 prefill): dead-tile clamping +
    # boundary iota masks on both edges must survive Mosaic lowering.
    ks = jax.random.split(jax.random.fold_in(key, 9), 3)
    b, t, h, kvh, d, win = 1, 2048, 8, 4, 128, 512
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.bfloat16)
    kk = jax.random.normal(ks[1], (b, t, kvh, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, t, kvh, d), jnp.bfloat16)
    got = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=win,
                                        interpret=not ON_TPU)
    )(q, kk, v)
    want = _dense_reference(q, kk, v, None, None, None, True, win)
    check(f"flash windowed T{t} win{win}", got, want, rtol=3e-2, atol=3e-2)
    # K-EXAONE's admissions (models.model._self_attention): 8 query heads a
    # key/value head on a long row, the full layers in tiles of 1,024 and
    # the windowed layers' band of 128 in tiles of 512.
    ks = jax.random.split(jax.random.fold_in(key, 11), 3)
    b, t, h, kvh, d = (1, 4096, 16, 2, 128) if ON_TPU else (1, 1024, 8, 1, 128)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.bfloat16)
    kk = jax.random.normal(ks[1], (b, t, kvh, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, t, kvh, d), jnp.bfloat16)
    for win, block in ((None, 1024), (128, 512)):
        got = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=win, block_q=block, block_k=block,
            interpret=not ON_TPU))(q, kk, v)
        want = _dense_reference(q, kk, v, None, None, None, True, win)
        check(f"flash T{t} H{h}/{kvh} win{win} tiles{block}", got, want,
              rtol=3e-2, atol=3e-2)
    # Admissions since PR 35 (a fresh row attends among its own tokens):
    # the static-causal path in tiles of 1,024 at qwen2-7b's and
    # pythia-6.9b's head layouts, a small and the largest bucket; and a
    # scale and a value width of the kernel's own at A.X-K1's expanded
    # latent heads, 64 of 192 for q and k and 128 for v (their widths as
    # they are, and with zero lanes to 256: the same bits).  That cell's
    # admissions take the dense body (its golden: models.model.
    # _self_attention); a latent model with heads of whole registers would
    # take this one.
    for t, h, kvh, d, dv, scale in (
            (64, 28, 4, 128, 128, None), (2048, 28, 4, 128, 128, None),
            (512, 32, 32, 128, 128, None),
            (2048 if ON_TPU else 512, 64, 64, 192, 128, 0.1147)):
        ks = jax.random.split(jax.random.fold_in(key, 13 + t + h), 3)
        q = jax.random.normal(ks[0], (1, t, h, d), jnp.bfloat16)
        kk = jax.random.normal(ks[1], (1, t, kvh, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, t, kvh, dv), jnp.bfloat16)
        run = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=1024, block_k=1024,
            interpret=not ON_TPU, scale=scale))
        got = run(q, kk, v)
        want = _dense_reference(q, kk, v, None, None, None, True, None, scale)
        check(f"flash admission T{t} H{h}/{kvh} D{d}/{dv}", got, want,
              rtol=3e-2, atol=3e-2)
        if d % 128:
            pad = ((0, 0),) * 3 + ((0, -d % 128),)
            if not bool(jnp.all(run(jnp.pad(q, pad), jnp.pad(kk, pad), v)
                                == got)):
                raise AssertionError(
                    f"zero lanes to {d + -d % 128} change the output")


# The paged legs' rows: 19 page slots each (no run length divides it), six
# rows whose depths the walk a run at a time treats apart.
PAGED_ROWS, PAGED_SLOTS = 6, 19


def _paged_case(blk: int, kvh: int, d: int, dtype, run: int | None = None,
                rows: tuple | None = None):
    """(lengths, tables, pool size) of a paged leg.  Depths, with ``run``
    the pages the kernel walks at a time: 1 token; inside a run; a run's
    last slot; the next run's first key; every slot full; one page.  The
    last row starts with the pages of the row before it (a shared prefix),
    and every id past a row's depth names the pool's last page, which the
    leg fills with NaNs (int8: NaN scales): never to be read.  ``rows`` =
    (page slots, the six depths in tokens) names other rows."""
    b, pages = PAGED_ROWS, PAGED_SLOTS
    run = run or decode_attn._run_pages(blk, kvh, d, dtype, pages)
    lengths = [1, 2 * blk + 44, run * blk, run * blk + 1, pages * blk, blk]
    if rows:
        pages, lengths = rows
    pool = b * pages + 1
    rng = np.random.RandomState(0)
    tables = rng.permutation(pool - 1).reshape(b, pages)
    tables[5, :2] = tables[4, :2]
    held = -(-np.asarray(lengths) // blk)
    junk = np.where(np.arange(pages)[None, :] < held[:, None], tables,
                    pool - 1)
    return jnp.asarray(lengths, jnp.int32), tables, junk, pool


def _to_pool(rows, tables, pool, fill, junk, layer, noise):
    """[B, S, ...] rows as pages [pool, BLK, ...] at ``tables``, the last
    page ``junk``, the whole as ``_stacked`` has it."""
    b, pages = tables.shape
    tail = rows.shape[2:]
    blk = rows.shape[1] // pages
    pages_ = jnp.full((pool, blk, *tail), fill, rows.dtype).at[
        tables.reshape(-1)
    ].set(rows.reshape(b * pages, blk, *tail)).at[pool - 1].set(junk)
    return _stacked(pages_, layer, noise)


def paged_parity(blk: int = 128, h: int = 8, kvh: int = 4,
                 layer: int | None = None, d: int = 128,
                 rows: tuple | None = None) -> None:
    key = jax.random.PRNGKey(3)
    # Heads narrower than a row lie folded in the pool, as the batcher of
    # a hybrid model keeps them (decode_attn.pool_head_shape).
    fold = decode_attn.pool_head_shape(kvh, d, fold_narrow=True)
    ln, tables, junk, pool = _paged_case(blk, *fold, jnp.bfloat16, rows=rows)
    b, pages = tables.shape
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.bfloat16)
    k_rows = jax.random.normal(ks[1], (b, pages * blk, kvh, d), jnp.bfloat16)
    v_rows = jax.random.normal(ks[2], (b, pages * blk, kvh, d), jnp.bfloat16)
    shared = min(2, pages) * blk
    k_rows = k_rows.at[5, :shared].set(k_rows[4, :shared])
    v_rows = v_rows.at[5, :shared].set(v_rows[4, :shared])
    k_pool, v_pool = (
        _to_pool(x.reshape(b, pages * blk, *fold), tables, pool, 0, np.nan,
                 layer, fill)
        for x, fill in ((k_rows, 3.0), (v_rows, -3.0)))
    got = jax.jit(decode_attn.paged_decode_attention)(
        q, k_pool, v_pool, ln, jnp.asarray(junk, jnp.int32),
        **_layer_kw(layer)
    )
    want = decode_attn._dense_reference(q, k_rows, v_rows, ln)
    form = "" if layer is None else f" L3[{layer}]"
    check(f"paged decode B{b} pool{pool} blk{blk} H{h}/{kvh} D{d}{form}", got,
          want, rtol=3e-2, atol=3e-2)


def swa_parity(w: int = 128, h: int = 64, kvh: int = 8, d: int = 128,
               layer: int = 1, lengths=None) -> None:
    """The rings' decode kernel at K-EXAONE's shapes: 64 query heads over
    8 key/value heads, a ring of 128 tokens a row, layer 1 of a 3-layer
    stack.  Rows: one token; shorter than the window; exactly the window;
    wrapped (300 tokens went by: the key of position p lies at p mod 128,
    the oldest overwritten, and the answer is that of the last 128 in
    their own order).  Entries past a row's count hold NaNs: never to be
    read, on the value side either.  A ring too large for one page
    (SmallThinker's 4,096 tokens at 4 heads of 128, ``ring_block``) is
    walked in blocks of 64, the live ones only: the NaNs lie in the blocks
    a row does not hold as well as in the tail of its last one."""
    lengths = lengths or [1, 37, w, 300]
    b, s = len(lengths), max(lengths)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(kq, (b, 1, h, d), jnp.bfloat16)
    k_rows = jax.random.normal(kk, (b, s, kvh, d), jnp.bfloat16)
    v_rows = jax.random.normal(kv, (b, s, kvh, d), jnp.bfloat16)
    pos = np.arange(s)

    def ring(rows):
        out = np.full((b, w, kvh, d), np.nan, np.float32)
        for r, n in enumerate(lengths):
            out[r, pos[:n] % w] = np.asarray(rows[r, :n], np.float32)
        return _stacked(jnp.asarray(out, jnp.bfloat16), layer, 3.0)

    counts = jnp.minimum(jnp.asarray(lengths, jnp.int32), w)
    got = jax.jit(decode_attn.swa_decode_attention)(
        q, ring(k_rows), ring(v_rows), counts, layer)
    # The reference: each row's last min(n, w) tokens, first in first.
    last = jnp.stack([jnp.roll(x, -max(n - w, 0), axis=0)
                      for x, n in zip(k_rows, lengths)])
    last_v = jnp.stack([jnp.roll(x, -max(n - w, 0), axis=0)
                        for x, n in zip(v_rows, lengths)])
    want = decode_attn._dense_reference(q, last, last_v, counts)
    blk = decode_attn.ring_block(w, kvh, d, jnp.bfloat16)
    check(f"swa decode B{b} ring{w} blk{blk} H{h}/{kvh} D{d} L3[{layer}]", got,
          want, rtol=3e-2, atol=3e-2)


def mla_paged_parity(blk: int = 64, h: int = 64, latent: int = 512,
                     rope: int = 64, layer: int = 2,
                     block_edges: bool = False) -> None:
    """The latent decode kernel at A.X-K1's shapes: 64 heads against page
    rows of 640 lanes (latent 512, the shared rotated key 64, 64 zeros),
    values the first 512 columns of the same rows, layer 2 of a 3-layer
    stack.  The rows are :func:`_paged_case`'s (length 1, inside a run, on
    a run's last slot, on the next run's first key, full, one page that
    shares the pages of the row before it), junk ids name a NaN page.
    ``block_edges``: the depths the kernel's update over a run's live
    BLOCKS treats apart instead: a block's last key, the next block's
    first, a last run of one page, a block's last key in the second run,
    the last key before a run's last block, one page."""
    w = -(-(latent + rope) // 128) * 128
    run = decode_attn._latent_run_pages(blk, w, jnp.bfloat16, PAGED_SLOTS)
    rows = None
    if block_edges:
        n = decode_attn._latent_block_pages(run) * blk
        rows = (PAGED_SLOTS, [n, n + 1, run * blk + 30, run * blk + n,
                              run * blk - n, blk])
    ln, tables, junk, pool = _paged_case(blk, 1, w, jnp.bfloat16, run=run,
                                         rows=rows)
    b, pages = tables.shape
    kq, kr = jax.random.split(jax.random.PRNGKey(11))
    lanes = (jnp.arange(w) < latent + rope).astype(jnp.bfloat16)
    q = jax.random.normal(kq, (b, 1, h, w), jnp.bfloat16) * lanes
    rows = jax.random.normal(kr, (b, pages * blk, w), jnp.bfloat16) * lanes
    rows = rows.at[5, : 2 * blk].set(rows[4, : 2 * blk])
    scale = 0.1 * (latent + rope) ** -0.5
    got = jax.jit(lambda q, p, ln, t: decode_attn.mla_paged_decode_attention(
        q, p, ln, t, latent=latent, scale=scale, layer=layer))(
        q, _to_pool(rows, tables, pool, 0, np.nan, layer, 3.0), ln,
        jnp.asarray(junk, jnp.int32))
    s = jnp.einsum("bhw,bsw->bhs", q[:, 0], rows,
                   preferred_element_type=jnp.float32) * scale
    keep = jnp.arange(pages * blk)[None, :] < ln[:, None]
    probs = jax.nn.softmax(jnp.where(keep[:, None, :], s, -jnp.inf), axis=-1)
    want = jnp.einsum("bhs,bsc->bhc", probs.astype(jnp.bfloat16),
                      rows[..., :latent])[:, None]
    check(f"mla paged decode B{b} pool{pool} blk{blk} H{h} W{w} run{run} "
          f"L3[{layer}]{' block edges' if block_edges else ''}", got, want,
          rtol=3e-2, atol=3e-2)


def moe_parity(e: int = 32, d: int = 2048, f: int = 1792, k: int = 4,
               of_experts: int | None = None, act: str = "silu",
               gated: bool = True) -> None:
    """The expert kernel at lfm2-8b-a1b's widths (32 experts of 2048 x
    1792, int8 with blocks along the contracted axis), layer 1 of a
    2-layer stack: a decode step's 64 pairs (tiles of 16 rows, most
    experts one tile, some none) and an admission's 2,048 (tiles of 128).
    ``of_experts``: the stack holds ``e`` of that many (A.X-K1: 12 of 192
    at [7168 x 4096] and [2048 x 7168]); ids are drawn over twice the held
    ones, so half the pairs name an absent expert, leave the list and come
    back as zeros.  ``act``: the gate's activation (layers.gate_fn; relu:
    SmallThinker's 64 experts of [2560 x 1536] and [768 x 2560]).
    ``gated`` False: two matrices an expert, ``act(x U) V`` (Nemotron-H's
    128 of 512 on a latent of 1,024: [1024 x 2688] and [2688 x 1024], 22 a
    token, relu2)."""
    gate = gate_fn(act)
    up = 2 * f if gated else f
    key = jax.random.PRNGKey(5)
    key13, key2 = jax.random.split(key)

    def stack(key, kd, n):
        """[2, E, kd, n] drawn and quantized an expert at a time."""
        def one(i):
            w = jax.random.normal(jax.random.fold_in(key, i), (kd, n),
                                  jnp.float32) * kd ** -0.5
            qt = quantize(w, block_axis=-2)
            return qt.data, qt.scale
        data, scale = jax.lax.map(one, jnp.arange(2 * e))
        return QuantizedTensor(
            data=data.reshape(2, e, kd, n),
            scale=scale.reshape(2, e, kd // 128, n), bits=8,
            orig_shape=(2, e, kd, n), block_axis=-2)

    w13 = jax.jit(lambda: stack(key13, d, up))()
    w2 = jax.jit(lambda: stack(key2, f, d))()
    for s in (16, 512):
        kx, kt = jax.random.split(jax.random.fold_in(key, s))
        x = jax.random.normal(kx, (s, d), jnp.bfloat16)
        topi = jax.random.randint(
            kt, (s, k), 0, 2 * e if of_experts else e, jnp.int32)
        got = jax.jit(lambda x, t, a, b: moe_experts.grouped_swiglu(
            x, t, a, b, 1, of_experts=of_experts, act=gate, gated=gated))(
            x, topi, w13, w2)

        def want_of(x, topi, w13, w2):
            # Every expert for every token, the chosen ones picked out.
            xf = x.astype(jnp.float32)
            d13 = dequantize(jax.tree.map(lambda a: a[1], w13), jnp.float32)
            d2 = dequantize(jax.tree.map(lambda a: a[1], w2), jnp.float32)
            g = jnp.einsum("sd,edf->sef", xf, d13)
            y = jnp.einsum(
                "sef,efd->sed",
                gate(g[..., :f]) * g[..., f:] if gated else gate(g), d2)
            y = jnp.take_along_axis(
                y, jnp.minimum(topi, e - 1)[:, :, None], axis=1)
            return jnp.where((topi < e)[:, :, None], y, 0.0)

        with jax.default_matmul_precision("highest"):
            want = jax.jit(want_of)(x, topi, w13, w2)
        held = f" of {of_experts}" if of_experts else ""
        check(f"moe experts S{s} k{k} E{e}{held} [{d}x{up}] [{f}x{d}] "
              f"{act}", got, want, rtol=3e-2, atol=3e-2)


def combine_parity(s: int, k: int, e: int, of: int, d: int) -> None:
    """The rows of an admission block's pairs fetched by the pairs that hold
    one (``moe_experts._pairs_rows``, the kernel ``moe_combine``) against
    the gather of every pair's row, bit for bit, zeros included: ``s``
    tokens of ``k`` picks over ``of`` experts of which ``e`` are held, the
    last quarter of the tokens padding."""
    rs = np.random.RandomState(s + k)
    p = s * k
    eid = jnp.asarray(
        np.argsort(rs.rand(s, of), axis=1)[:, :k].reshape(p), jnp.int32)
    live = (eid < e) & (jnp.arange(p) // k < s - s // 4)
    bm, rows = moe_experts.list_shape(p, e, of)
    dest = moe_experts._grouped_list(eid, live, e, bm, rows)[0]
    yp = jnp.asarray(rs.randn(rows, d), jnp.bfloat16)
    want = jax.jit(lambda y, i: y.at[i].get(mode="fill", fill_value=0))(
        yp, dest)
    got = moe_experts._pairs_rows(yp, dest, interpret=not ON_TPU)
    same = bool(jnp.array_equal(
        jax.lax.bitcast_convert_type(got, jnp.uint16),
        jax.lax.bitcast_convert_type(want, jnp.uint16)))
    print(f"  moe combine S{s} k{k} E{e} of {of} D{d} "
          f"held {int(jnp.sum(live))} of {p}: bits equal {same}")
    if not same:
        raise AssertionError("moe_combine: the fetched rows are not the "
                             "gather's")


def flash_band_parity(t: int, h: int, kvh: int, win: int) -> None:
    """The flash kernel as a windowed layer's long admission calls it
    (models.model._self_attention: tiles of 512): a band of ``win`` keys on
    a row of ``t``, so tiles below the band are skipped as well as those
    above the diagonal."""
    ks = jax.random.split(jax.random.PRNGKey(17), 3)
    q = jax.random.normal(ks[0], (1, t, h, 128), jnp.bfloat16)
    kk = jax.random.normal(ks[1], (1, t, kvh, 128), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, t, kvh, 128), jnp.bfloat16)
    got = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=win, block_q=512, block_k=512,
        interpret=not ON_TPU))(q, kk, v)
    want = _dense_reference(q, kk, v, None, None, None, True, win)
    check(f"flash T{t} H{h}/{kvh} win{win} tiles512", got, want, rtol=3e-2,
          atol=3e-2)


def flash_cut_parity(t: int, real: int, h: int, kvh: int, win: int | None,
                     block: int, d: int = 128) -> None:
    """The flash kernel told an admission's count of real tokens
    (``rows``): its grid ends at the last tile of queries that holds one.
    Against the call without the count, which the legs above hold to the
    dense reference: the live tiles bit for bit, zeros past them, and NaN
    in q, k and v past them reaching nothing."""
    ks = jax.random.split(jax.random.PRNGKey(19), 3)
    live = -(-real // block) * block
    q, kk, v = (
        jax.random.normal(key, (1, t, heads, d), jnp.bfloat16)
        for key, heads in zip(ks, (h, kvh, kvh)))
    run = jax.jit(lambda q, k, v, rows=None: flash_attention(
        q, k, v, causal=True, window=win, block_q=block, block_k=block,
        interpret=not ON_TPU, rows=rows))
    want = run(q, kk, v)
    got = run(*(a.at[:, live:].set(jnp.nan) for a in (q, kk, v)),
              jnp.asarray([real], jnp.int32))
    if np.asarray(got[:, live:], np.float32).any():
        raise AssertionError("a tile of queries past the real tokens is not "
                             "zeros")
    check(f"flash T{t} real{real} H{h}/{kvh} D{d} win{win} tiles{block}",
          got[:, :live], want[:, :live], rtol=0, atol=0)


def flash_continuation_parity(tq: int, prefix: int, s: int, h: int, kvh: int,
                              win: int | None = None) -> None:
    """The flash kernel as a row's continuation calls it (models.model.
    _continuation_attention: ``start``, Q tiles of 1,024 and K tiles of
    512): ``tq`` new tokens behind ``prefix`` cached ones in a row cache of
    ``s`` slots, the query heads of a KV group one tile of queries, K and V
    read where the cache has them.  Against the dense reference on the
    clean row; every key past the new tokens is NaN, and every value past
    the last live tile."""
    from distributed_llms_tpu.ops.flash import live_keys

    ks = jax.random.split(jax.random.PRNGKey(23), 3)
    q = jax.random.normal(ks[0], (1, tq, h, 128), jnp.bfloat16)
    kk = jax.random.normal(ks[1], (1, s, kvh, 128), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, s, kvh, 128), jnp.bfloat16)
    keys = prefix + tq
    qpos = (prefix + jnp.arange(tq, dtype=jnp.int32))[None]
    want = _dense_reference(
        q, kk, v, qpos, None, (jnp.arange(s) < keys)[None], True, win)
    slot = jnp.arange(s)[None, :, None, None]
    got = jax.jit(lambda q, k, v, start: flash_attention(
        q, k, v, causal=True, window=win, block_q=1024, block_k=512,
        interpret=not ON_TPU, start=start))(
            q, jnp.where(slot >= keys, jnp.nan, kk),
            jnp.where(slot >= live_keys(s, keys, 512), jnp.nan, v),
            jnp.asarray([prefix], jnp.int32))
    check(f"flash continuation T{tq} behind {prefix} S{s} H{h}/{kvh} "
          f"win{win}", got, want, rtol=3e-2, atol=3e-2)


def retention_parity(t: int, real: int, h: int, kvh: int, chunk: int,
                     slots: int, dtype=jnp.float32) -> None:
    """Power retention's two kernels (ops/retention.py) against the plain
    ``jax.numpy`` forms: an admission of ``real`` tokens in a bucket of
    ``t`` (chunks of ``chunk``; the chunks of padding are not walked)
    against the chunked scan in float32 and, where it fits, against the
    attention form; then one recurrence step of every batch slot in layer 1
    of a stack of two, the slots' states where they lie, against the
    attention form's next row.  Gates near 1, so that every chunk boundary
    behind a token weighs on it."""
    from distributed_llms_tpu.ops import retention as R

    ks = jax.random.split(jax.random.key(50), 4)
    q = jax.random.normal(ks[0], (t, h, 128), dtype)
    k = jax.random.normal(ks[1], (t, kvh, 128), dtype)
    v = jax.random.normal(ks[2], (t, kvh, 128), dtype)
    lg = -0.01 * jnp.abs(jax.random.normal(ks[3], (t, kvh)))
    n = real - 1  # the admission; token n is the decode step's
    loose = dtype != jnp.float32
    with jax.default_matmul_precision("highest"):
        o, s, z = jax.jit(functools.partial(R.retention_prefill, chunk=chunk))(
            q, k, v, lg, jnp.int32(n))
        f32 = lambda x: x.astype(jnp.float32)
        live = (jnp.arange(t) < n)[:, None]
        tp = -(-t // chunk) * chunk
        pad = lambda x: jnp.pad(x, ((0, tp - t),) + ((0, 0),) * (x.ndim - 1))
        want_o, want_s, want_z = jax.jit(
            functools.partial(R._prefill_dense, c=min(chunk, tp)))(
            pad(f32(q)).reshape(tp, kvh, h // kvh, 128),
            pad(jnp.where(live[:, :, None], f32(k), 0.0)), pad(f32(v)),
            pad(jnp.where(live, lg, 0.0)))
        want_o = want_o.reshape(tp, h, 128)
    tag = f"retention {jnp.dtype(dtype).name} {real}/{t} h{h}/{kvh}"
    rt, at = (5e-2, 5e-2) if loose else (2e-3, 2e-3)
    check(f"{tag} admission", o[:n], want_o[:n], rt, at)
    scale = float(jnp.max(jnp.abs(want_s)))
    check(f"{tag} state", s / scale, want_s / scale,
          0, 2e-2 if loose else 1e-5)
    check(f"{tag} normaliser", z, want_z, rt, (1.0 if loose else 1e-2))
    # one step for every slot, the row above in slot 1 and in the last one
    states = jnp.zeros((2, slots, *s.shape), jnp.float32)
    norms = jnp.zeros((2, slots, *z.shape), jnp.float32)
    for b in (1, slots - 1):
        states, norms = states.at[1, b].set(want_s), norms.at[1, b].set(want_z)
    rows = lambda x: jnp.broadcast_to(x[n], (slots, *x.shape[1:]))
    live = jnp.ones((slots,), bool).at[0].set(False)
    step = jax.jit(R.retention_decode, donate_argnums=(4, 5))
    with jax.default_matmul_precision("highest"):
        got, states, norms = step(rows(q), rows(k), rows(v), rows(lg), states,
                                  norms, jnp.int32(1), live)
        want = R.attention_form(f32(q[:real]), f32(k[:real]), f32(v[:real]),
                                lg[:real])[n] if real <= 2048 else None
    if want is not None:
        check(f"{tag} decode step", got[1], want, rt, at)
        check(f"{tag} decode step, last slot", got[slots - 1], want, rt, at)
    assert not np.asarray(states[0]).any(), "layer 0's states were written"
    assert not np.asarray(states[1, 0]).any(), "a row that did not decode moved"


def ssm_parity(t: int, real: int, h: int, g: int, slots: int,
               dtype=jnp.float32) -> None:
    """Mamba-2's two kernels (ops/ssm.py) against the plain ``jax.numpy``
    forms: an admission of ``real`` tokens in a bucket of ``t`` (chunks of
    128; the chunks of padding are not walked) against the chunked scan in
    float32 and against the recurrence token by token; then one recurrence
    step of every batch slot in layer 1 of a stack of two, the slots'
    states where they lie, against the recurrence's next token.  ``h`` heads
    of 64 x 128 in ``g`` groups; dt and A as the published initialiser draws
    them, so that every chunk boundary behind a token weighs on it."""
    from distributed_llms_tpu.ops import ssm as S

    ks = jax.random.split(jax.random.key(55), 5)
    x = jax.random.normal(ks[0], (t, h, 64), dtype)
    bm = (0.3 * jax.random.normal(ks[1], (t, g, 128))).astype(dtype)
    cm = (0.3 * jax.random.normal(ks[2], (t, g, 128))).astype(dtype)
    dt = jnp.exp(jax.random.uniform(
        ks[3], (t, h), minval=np.log(1e-3), maxval=np.log(0.1)))
    a = -jax.random.uniform(ks[4], (h,), minval=1.0, maxval=16.0)
    n = real - 1  # the admission; token n is the decode step's
    loose = dtype != jnp.float32
    f32 = lambda v: v.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, s = jax.jit(S.ssm_prefill)(x, bm, cm, dt, a, jnp.int32(n))
        want_y, want_s = jax.jit(S.recurrence)(
            x[:real], bm[:real], cm[:real], dt[:real], a)
        _, at_n = jax.jit(S.recurrence)(x[:n], bm[:n], cm[:n], dt[:n], a)
    tag = f"ssm {jnp.dtype(dtype).name} {real}/{t} h{h}/{g}"
    rt, at = (2e-2, 2e-2) if loose else (1e-4, 1e-4)
    check(f"{tag} admission", f32(y[:n]), want_y[:n], rt, at)
    check(f"{tag} state", S.from_layout(s, 64), at_n, 0, 1e-5)
    # one step for every slot, the row above in slot 1 and in the last one
    states = jnp.zeros((2, slots, *s.shape), jnp.float32)
    for b in (1, slots - 1):
        states = states.at[1, b].set(S.to_layout(at_n))
    rows = lambda v: jnp.broadcast_to(v[n], (slots, *v.shape[1:]))
    live = jnp.ones((slots,), bool).at[0].set(False)
    step = jax.jit(S.ssm_decode, donate_argnums=(5,))
    got, states = step(rows(x), rows(bm), rows(cm), rows(dt), a, states,
                       jnp.int32(1), live)
    check(f"{tag} decode step", got[1], want_y[n], 0, 1e-5)
    check(f"{tag} decode step, last slot", got[slots - 1], want_y[n], 0, 1e-5)
    check(f"{tag} state after the step", S.from_layout(states[1, 1], 64),
          want_s, 0, 1e-5)
    assert not np.asarray(states[0]).any(), "layer 0's states were written"
    assert not np.asarray(states[1, 0]).any(), "a row that did not decode moved"


def gdn_parity(t: int, real: int, hk: int, hv: int, slots: int,
               dtype=jnp.float32) -> None:
    """Gated DeltaNet's two kernels (ops/gdn.py) against the recurrence token
    by token: an admission of ``real`` tokens in a bucket of ``t`` (chunks of
    64, a triangle solved in each; the chunks of padding are not walked), the
    state it leaves, then one recurrence step of every batch slot in layer 1
    of a stack of two, the slots' states where they lie.  ``hk`` key heads
    and ``hv`` value heads of 128 x 128; q and k raw silu outputs (the
    operators normalise them; any two keys share a part), beta a sigmoid, the
    decay as
    ``models.model.ssm_leaf`` draws it."""
    from distributed_llms_tpu.ops import gdn as G

    ks = jax.random.split(jax.random.key(59), 7)
    q = jax.nn.silu(jax.random.normal(ks[0], (t, hk, 128))).astype(dtype)
    kk = jax.nn.silu(jax.random.normal(ks[1], (t, hk, 128))).astype(dtype)
    v = jax.random.normal(ks[2], (t, hv, 128), dtype)
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (t, hv)))
    g = -jax.random.uniform(ks[4], (hv,), minval=1.0, maxval=16.0) * jnp.exp(
        jax.random.uniform(ks[5], (t, hv), minval=np.log(1e-3),
                           maxval=np.log(0.1)))
    x = (q, kk, v, g, beta)
    n = real - 1  # the admission; token n is the decode step's
    # The admission is read in float32 whatever ``dtype``: the bfloat16
    # leg's v goes in as the same VALUES in float32 (the output takes v's
    # dtype), and is held to the recurrence on those values at the float32
    # leg's tolerance: q and k in bfloat16 take the products of 1 and 3 MXU
    # passes, which lose no bit (v20).  As served, in bfloat16, the output is
    # that one rounded.
    wide = (q, kk, v.astype(jnp.float32), g, beta)
    with jax.default_matmul_precision("highest"):
        o, s = jax.jit(G.gdn_prefill)(*wide, jnp.int32(n))
        want_o, want_s = jax.jit(G.recurrence)(*(a[:real] for a in x))
        _, at_n = jax.jit(G.recurrence)(*(a[:n] for a in x))
    tag = f"gdn {jnp.dtype(dtype).name} {real}/{t} h{hk}/{hv}"
    check(f"{tag} admission", o[:n], want_o[:n], 1e-4, 1e-5)
    check(f"{tag} state", s, at_n, 0, 1e-4)
    if dtype != jnp.float32:
        served, s16 = jax.jit(G.gdn_prefill)(*x, jnp.int32(n))
        assert served.dtype == dtype
        check(f"{tag} admission as served", served[:n].astype(jnp.float32),
              o[:n].astype(dtype).astype(jnp.float32), 0, 0)
        check(f"{tag} state as served", s16, s, 0, 0)
    # one step for every slot, the row above in slot 1 and in the last one
    states = jnp.zeros((2, slots, *s.shape), jnp.float32)
    for b in (1, slots - 1):
        states = states.at[1, b].set(at_n)
    rows = [jnp.broadcast_to(a[n], (slots, *a.shape[1:])) for a in x]
    live = jnp.ones((slots,), bool).at[0].set(False)
    step = jax.jit(G.gdn_decode, donate_argnums=(5,))
    got, states = step(*rows, states, jnp.int32(1), live)
    check(f"{tag} decode step", got[1], want_o[n], 0, 1e-5)
    check(f"{tag} decode step, last slot", got[slots - 1], want_o[n], 0, 1e-5)
    check(f"{tag} state after the step", states[1, 1], want_s, 0, 1e-4)
    assert not np.asarray(states[0]).any(), "layer 0's states were written"
    assert not np.asarray(states[1, 0]).any(), "a row that did not decode moved"


def ragged_parity() -> None:
    key = jax.random.PRNGKey(2)
    for b, s, h, kvh, d, lengths in (
        (4, 512, 8, 4, 128, (3, 200, 512, 64)),
        (2, 2048, 8, 8, 128, (1500, 2048)),
    ):
        ks = jax.random.split(jax.random.fold_in(key, s), 3)
        q = jax.random.normal(ks[0], (b, 1, h, d), jnp.bfloat16)
        kk = jax.random.normal(ks[1], (b, s, kvh, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, s, kvh, d), jnp.bfloat16)
        ln = jnp.asarray(lengths, jnp.int32)
        got = jax.jit(decode_attn.ragged_decode_attention)(q, kk, v, ln)
        want = decode_attn._dense_reference(q, kk, v, ln)
        check(f"ragged decode B{b} S{s} H{h}/{kvh}", got, want,
              rtol=3e-2, atol=3e-2)
    # Sliding-window band: first/last block clamps + in-block mask.
    ks = jax.random.split(jax.random.fold_in(key, 11), 3)
    b, s, h, kvh, d, win = 2, 2048, 8, 4, 128, 300
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.bfloat16)
    kk = jax.random.normal(ks[1], (b, s, kvh, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, kvh, d), jnp.bfloat16)
    ln = jnp.asarray([1900, 64], jnp.int32)
    got = jax.jit(functools.partial(
        decode_attn.ragged_decode_attention, window=win
    ))(q, kk, v, ln)
    want = decode_attn._dense_reference(q, kk, v, ln, window=win)
    check(f"ragged windowed S{s} win{win}", got, want, rtol=3e-2, atol=3e-2)


def _int8_inputs(b: int, s: int, h: int, kvh: int, d: int = 128):
    """Query plus int8 K/V rows with their scales, for the int8 KV-page
    legs (--kv-bits 8): the scale-fused kernels vs the
    dequantize-then-dense reference (checkpoint.quantize.kv_dequantize
    numerics — exactly what the CPU fallback computes)."""
    from distributed_llms_tpu.checkpoint.quantize import kv_quantize

    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.bfloat16)
    kq, ksc = kv_quantize(jax.random.normal(ks[1], (b, s, kvh, d), jnp.bfloat16))
    vq, vsc = kv_quantize(jax.random.normal(ks[2], (b, s, kvh, d), jnp.bfloat16))
    return q, kq, ksc, vq, vsc


def _int8_reference(q, kq, ksc, vq, vsc, ln):
    from distributed_llms_tpu.checkpoint.quantize import kv_dequantize

    return decode_attn._dense_reference(
        q, kv_dequantize(kq, ksc, q.dtype), kv_dequantize(vq, vsc, q.dtype),
        ln,
    )


def ragged_int8_parity() -> None:
    b, s = 4, 512
    q, kq, ksc, vq, vsc = _int8_inputs(b, s, 8, 4)
    ln = jnp.asarray([3, 200, 512, 64], jnp.int32)
    got = jax.jit(decode_attn.ragged_decode_attention)(
        q, kq, vq, ln, k_scale=ksc, v_scale=vsc
    )
    check(f"ragged int8 B{b} S{s}", got,
          _int8_reference(q, kq, ksc, vq, vsc, ln), rtol=3e-2, atol=3e-2)


def paged_int8_parity(blk: int = 128, h: int = 8, kvh: int = 4,
                      layer: int | None = None) -> None:
    ln, tables, junk, pool = _paged_case(blk, kvh, 128, jnp.int8)
    b, pages = tables.shape
    q, kq, ksc, vq, vsc = _int8_inputs(b, pages * blk, h, kvh)
    shared = 2 * blk
    kq, ksc, vq, vsc = (x.at[5, :shared].set(x[4, :shared])
                        for x in (kq, ksc, vq, vsc))
    got = jax.jit(decode_attn.paged_decode_attention)(
        q, _to_pool(kq, tables, pool, 0, 99, layer, 77),
        _to_pool(vq, tables, pool, 0, 99, layer, -77), ln,
        jnp.asarray(junk, jnp.int32),
        k_scale=_to_pool(ksc, tables, pool, 1, np.nan, layer, 9.0),
        v_scale=_to_pool(vsc, tables, pool, 1, np.nan, layer, 9.0),
        **_layer_kw(layer),
    )
    form = "" if layer is None else f" L3[{layer}]"
    check(f"paged int8 B{b} pool{pool} blk{blk} H{h}/{kvh}{form}", got,
          _int8_reference(q, kq, ksc, vq, vsc, ln), rtol=3e-2, atol=3e-2)


def quant_padding_rows(m: int = 2048) -> None:
    """A call of eight row tiles (three off the chip) told how many of its rows are real (an
    admission's bucket; ops/quant_matmul.py, PR 39) against the call that
    is not told, at qwen2-7b's w_gate and w_down and as int4: the row
    tiles that hold a real row equal it bit for bit, the others are
    zeros."""
    key = jax.random.PRNGKey(39)
    m = m if ON_TPU else 768
    for bits, k, n in ((8, 3584, 18944), (8, 18944, 3584), (4, 4096, 1024)):
        if not ON_TPU:
            k, n = k // 8 // 128 * 128, n // 8 // 128 * 128
        kx, kw = jax.random.split(jax.random.fold_in(key, k))
        x = jax.random.normal(kx, (1, m, k), jnp.bfloat16)
        qt = jax.jit(lambda w: quantize(w, bits=bits))(
            jax.random.normal(kw, (k, n), jnp.float32) / np.sqrt(k))
        run = jax.jit(lambda x, qt, rows=None: quant_contract(
            x, qt, k_lead=1, rows=rows))
        want = np.asarray(run(x, qt)[0].astype(jnp.float32))
        for rows in (0, 1, m * 1100 // 2048, m - 1, m):
            got = np.asarray(run(x, qt, jnp.array([rows], jnp.int32)
                                 )[0].astype(jnp.float32))
            live = -(-rows // 256) * 256
            if not (np.array_equal(got[:live], want[:live])
                    and not got[live:].any()):
                raise AssertionError(
                    f"int{bits} [{m}x{k}]@[{k}x{n}] told {rows} real rows: "
                    "a live tile differs or a padding tile is not zeros")
        print(f"  PASS quant int{bits} [{m}x{k}]@[{k}x{n}] told 0, 1, "
              f"{m * 1100 // 2048}, {m - 1}, {m} real rows: live tiles "
              "bit-equal, padding tiles zeros")


def main() -> int:
    backend = jax.default_backend()
    print(f"kernel_parity: backend={backend} devices={jax.device_count()}")
    if backend != "tpu":
        print(f"  WARNING: backend={backend} — running kernels in INTERPRET "
              "mode (validates this script, NOT Mosaic lowering).")
    quant_parity()
    quant_parent_arithmetic()
    quant_padding_rows()
    flash_parity()
    ragged_parity()
    paged_parity()
    ragged_int8_parity()
    paged_int8_parity()
    paged_parity(**SERVED)
    paged_int8_parity(**SERVED)
    # The stacked form the serving programs use: the pool is every layer's
    # pages and the kernel reads layer 2 (GQA) or layer 0 (no grouping).
    paged_parity(**SERVED, layer=2)
    paged_int8_parity(**SERVED, layer=2)
    paged_parity(**SERVED_MHA, layer=0)
    paged_int8_parity(**SERVED_MHA, layer=0)
    paged_parity(**SERVED_TP4, layer=1)
    paged_int8_parity(**SERVED_TP4, layer=1)
    paged_parity(**SERVED_H64, layer=2)
    moe_parity()
    mla_paged_parity()
    mla_paged_parity(block_edges=True)
    # A chip's share of the experts at A.X-K1's widths (off the chip the
    # interpreter gets the same list at a tenth of the widths).
    moe_parity(e=12, d=7168, f=2048, k=8, of_experts=192) if ON_TPU else \
        moe_parity(e=12, d=1024, f=256, k=8, of_experts=192)
    # K-EXAONE's: the paged kernel at 8 query heads a key/value head on
    # rows of one page, 94 pages and all 128 of an 8,192-token row (junk
    # ids past every depth), layer 2 of the stack; the rings' kernel; 16 of
    # 128 experts held at [6144 x 4096] and [2048 x 6144].
    slots = 128 if ON_TPU else 12
    deep = slots * 64
    paged_parity(blk=64, h=64, kvh=8, layer=2, rows=(slots, [
        1, 64, deep * 94 // 128 - 17, deep - 63, deep, 64]))
    swa_parity()
    moe_parity(e=16, d=6144, f=2048, k=8, of_experts=128) if ON_TPU else \
        moe_parity(e=16, d=768, f=256, k=8, of_experts=128)
    # SmallThinker's: a ring of 4,096 tokens at 28 query heads over 4,
    # walked in blocks of 64 (rows of one token, inside a block, on a
    # block's last slot, of several runs, full, wrapped); 64 ReLU-gated
    # experts of [2560 x 1536] and [768 x 2560], 6 a token; the flash
    # kernel with the 4,096 band in tiles of 512 on a row of 8,192 (one
    # key/value head's group of 7: the reference scores densely).
    swa_parity(w=4096, h=28, kvh=4, lengths=[1, 37, 64, 700, 4096, 5000]) \
        if ON_TPU else \
        swa_parity(w=1024, h=8, kvh=4, lengths=[1, 37, 64, 300, 1024, 1300])
    moe_parity(e=64, d=2560, f=768, k=6, act="relu") if ON_TPU else \
        moe_parity(e=64, d=256, f=128, k=6, act="relu")
    flash_band_parity(*((8192, 7, 1, 4096) if ON_TPU else (1024, 4, 2, 512)))
    # An admission's count of real tokens ends the flash kernel's grid:
    # SmallThinker's 28 / 4 heads at a mean prompt of its 8,192 bucket, a
    # windowed layer (tiles of 512) and a full one (1,024); K-EXAONE's 64 /
    # 8 with its band of 128.
    for leg in (((8192, 5690, 28, 4, 4096, 512), (8192, 5690, 28, 4, None, 1024),
                 (8192, 5552, 64, 8, 128, 512)) if ON_TPU else
                ((1024, 300, 4, 2, 512, 128), (1024, 300, 4, 2, None, 256),
                 (1024, 513, 8, 1, 128, 128))):
        flash_cut_parity(*leg)
    # A row's continuation behind cached pages: doc-qa's suffix buckets
    # behind its shortest, a mean and its longest document at qwen2-7b's
    # heads and row (a prefix that ends inside a tile, on a tile's edge,
    # and a row filled to its last slot), a long suffix whose heads are
    # runs of whole tiles, pythia's heads with no grouping, and a window.
    for leg in (((128, 784, 4096, 28, 4), (64, 1280, 4096, 28, 4),
                 (128, 1408, 4096, 28, 4), (128, 3968, 4096, 28, 4),
                 (2048, 1280, 4096, 28, 4), (128, 640, 2048, 32, 32),
                 (128, 1280, 4096, 28, 4, 1024)) if ON_TPU else
                ((16, 100, 1024, 4, 2), (8, 504, 1024, 4, 2),
                 (16, 496, 1024, 4, 2), (16, 1008, 1024, 4, 2),
                 (300, 513, 1024, 4, 2), (16, 100, 1024, 2, 2),
                 (16, 600, 1024, 4, 2, 200))):
        flash_continuation_parity(*leg)
    # Power retention (Brumby: 40 query heads over 8): an admission of the
    # 2,048 bucket with a mean prompt in float32 and in bfloat16, the
    # smallest bucket (padded to one chunk of 128), and the 16 slots' step.
    for leg in (((2048, 1500, 40, 8, 256, 16), (64, 33, 40, 8, 256, 16),
                 (2048, 1500, 40, 8, 256, 16, jnp.bfloat16)) if ON_TPU else
                ((192, 150, 4, 2, 64, 3), (8, 5, 4, 2, 64, 3))):
        retention_parity(*leg)
    # Mamba-2 (Nemotron-H: 128 heads of 64 x 128 in 8 groups): an admission
    # of the 2,048 bucket with a mean prompt in float32 and in bfloat16, the
    # smallest bucket (padded to one chunk of 128), and the 64 slots' step;
    # its 128 of 512 non-gated experts on the latent, 22 a token.
    for leg in (((2048, 1500, 128, 8, 64), (64, 33, 128, 8, 64),
                 (2048, 1500, 128, 8, 64, jnp.bfloat16)) if ON_TPU else
                ((300, 260, 16, 2, 3), (8, 5, 16, 2, 3))):
        ssm_parity(*leg)
    if ON_TPU:
        moe_parity(e=128, d=1024, f=2688, k=22, of_experts=512, act="relu2",
                   gated=False)
    else:
        moe_parity(e=8, d=256, f=384, k=3, of_experts=32, act="relu2",
                   gated=False)
    # Gated DeltaNet (Qwen3-Next: 16 key heads and 32 value heads of 128 x
    # 128): an admission of the 2,048 bucket with a mean prompt in float32
    # and in bfloat16, the smallest bucket (padded to one grid step of 128),
    # and the 64 slots' step; its 128 of 512 gated experts of [2048 x 1024]
    # and [512 x 2048], 10 a token; its attention's heads of 256, 16 over 2,
    # both in ONE pool row of 512 lanes, on rows 1 to 151 pages deep; the
    # flash kernel at heads of 256.
    # v20: the bfloat16 leg at the float32 leg's tolerance, value heads
    # singly (``hk == hv``) and four a key head (two pairs).
    for leg in (((2048, 1500, 16, 32, 64), (64, 33, 16, 32, 64),
                 (2048, 1500, 16, 32, 64, jnp.bfloat16),
                 (2048, 1500, 32, 32, 64, jnp.bfloat16),
                 (2048, 1500, 8, 32, 64, jnp.bfloat16)) if ON_TPU else
                ((300, 260, 2, 4, 3), (8, 5, 2, 4, 3),
                 (300, 260, 2, 4, 3, jnp.bfloat16),
                 (300, 260, 2, 2, 3, jnp.bfloat16),
                 (300, 260, 1, 4, 3, jnp.bfloat16))):
        gdn_parity(*leg)
    if ON_TPU:
        moe_parity(e=128, d=2048, f=512, k=10, of_experts=512)
        deep = 64 * 64
        paged_parity(blk=64, h=16, kvh=2, d=256, layer=2, rows=(64, [
            1, 64, deep * 94 // 128 - 17, deep - 63, deep, 64]))
        flash_cut_parity(8192, 5690, 16, 2, None, 1024, d=256)
    else:
        moe_parity(e=8, d=256, f=128, k=4, of_experts=32)
        paged_parity(blk=64, h=4, kvh=2, d=256, layer=2, rows=(12, [
            1, 64, 12 * 64 * 94 // 128 - 17, 12 * 64 - 63, 12 * 64, 64]))
        flash_cut_parity(1024, 300, 4, 2, None, 256, d=256)
    # The combine's operand at A.X-K1's and K-EXAONE's 2,048-token blocks
    # and at A.X-K1's 512 (the smallest list that takes the kernel), and at
    # nemotron's k = 22 (which keeps the gather in the served model).
    for leg in (((2048, 8, 12, 192, 7168), (2048, 8, 16, 128, 6144),
                 (512, 8, 12, 192, 7168), (2048, 22, 128, 512, 1024))
                if ON_TPU else ((64, 8, 4, 32, 256), (32, 22, 16, 64, 128))):
        combine_parity(*leg)
    # No leg may pass on another path than the one asked for: the dispatch
    # record (ops/dispatch.py) counts every trace by the path it took.
    took = {k[len("ops.dispatch."):]: int(v)
            for k, v in METRICS.snapshot()["counters"].items()
            if k.startswith("ops.dispatch.")}
    print(f"  dispatch record: {took}")
    # (quant_matmul.stacked counts stacks handed over whole, beside the
    # path; quant_matmul.k_minor the traces of the lane-dense leg: all)
    stacked = took.pop("quant_matmul.stacked", 0)
    if stacked != len(STACKED_LEGS):
        raise AssertionError(f"{stacked} of {len(STACKED_LEGS)} stacked legs "
                             "went in as stacks")
    if took.pop("quant_matmul.k_minor", 0) != took.get(f"quant_matmul.{MODE}"):
        raise AssertionError("a quant_matmul trace left the lane-dense leg")
    stray = {k: v for k, v in took.items() if not k.endswith("." + MODE)}
    if stray:
        raise AssertionError(f"legs dispatched off the {MODE} path: {stray}")
    mode = "compiled" if ON_TPU else "interpret"
    # v5: the paged legs (bf16 and int8) also run at the page size and
    # head geometries the benchmark serves, as one layer's pages and as a
    # layer of the stacked pool — 21 legs.  v6: flash and the paged kernel at
    # a head of 64, and the expert kernel at a decode step's and an
    # admission's pairs — 25 legs.  v7: quant_matmul's stacks read at an
    # index, at qwen2-7b's shapes — 29 legs.  v8: the 11 paged legs' rows
    # have the depths a walk by runs treats apart, share pages and carry
    # junk ids (_paged_case).  v9: the latent (MLA) decode kernel on those
    # rows, and the expert kernel holding 12 of 192 experts at A.X-K1's
    # widths with absent experts' pairs in the list — 32 legs.  v10: the
    # quantized leaves lie [N, K]; the stacked legs at pythia's, lfm2's and
    # A.X-K1's shapes too, and one leg against the arithmetic of the [K, N]
    # kernel (weights bit-equal, outputs equal or one ulp) — 40 legs.  v11:
    # the paged kernel at 64 query heads over 8 on rows 128 pages deep, the
    # rings' kernel (swa_decode_attn: short, full, wrapped, NaNs past the
    # count) and the expert kernel holding 16 of 128 at K-EXAONE's widths
    # and the flash kernel as its admissions call it — 46 legs.  v12: the
    # flash kernel as the dense cells' admissions call it since PR 35, at
    # qwen2-7b's and pythia-6.9b's heads, and with a scale and a value
    # width of its own (192 / 128: A.X-K1's expanded latent heads) — 50
    # legs.  v13: SmallThinker's ring walked in blocks, its ReLU-gated
    # experts and its admissions' 4,096 band — 54 legs.  v14: the flash
    # kernel's grid ended at an admission's last real tile of queries, at
    # SmallThinker's and K-EXAONE's heads and bands — 57 legs.  v15: the
    # flash kernel over a row's continuation (``start``): the diagonal
    # shifted by the cached run, a KV group's query heads one tile, K and V
    # read where the cache has them, at qwen2-7b's and pythia's heads — 64
    # legs.  v16: power retention's chunked scan and recurrence step at
    # Brumby's heads, float32 and bfloat16, the smallest bucket — 67 legs.
    # v17: Mamba-2's chunked scan and recurrence step at Nemotron-H's heads,
    # float32 and bfloat16, the smallest bucket, and the expert kernel's
    # non-gated leg holding 128 of 512 on the latent — 72 legs.  v18: the
    # combine's operand fetched by the pairs that hold a row (moe_combine)
    # against the gather, bit for bit, at A.X-K1's, K-EXAONE's and
    # nemotron's blocks — 76 legs.  v19: Gated DeltaNet's chunked scan (a
    # triangle a chunk) and recurrence step at Qwen3-Next's heads, float32
    # and bfloat16, the smallest bucket; its 128 of 512 experts of 3.1 M;
    # the paged kernel at heads of 256 in one pool row of 512 lanes; the
    # flash kernel at heads of 256 — 82 legs.  v20: gdn_prefill's bfloat16
    # leg (q and k in bfloat16: products of 1 and 3 MXU passes) held to the
    # recurrence at the float32 leg's tolerance and, as served, to that
    # output rounded; value heads singly and four a key head — 84 legs.
    # v21: the latent kernel's rows at the depths its update over a run's
    # live blocks treats apart (a block's edges, a last run of one page) —
    # 85 legs.
    print(f"kernel_parity: ALL PASS v21 ({mode}, backend={backend})")
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:
        import traceback

        traceback.print_exc()
        print("kernel_parity: FAIL")
        sys.exit(1)
