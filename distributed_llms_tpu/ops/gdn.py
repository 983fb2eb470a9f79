"""Gated DeltaNet's delta-rule scan (layer kind "gdn"): a row's memory in a
layer is one float32 state a value head, whatever the row's length.

For value head ``h`` (``dv`` values) reading key head ``h // (HV / HK)``
(``dk`` keys; the operators are handed ``q`` and ``k`` RAW, as the layer's
convolution leaves them, and take ``q <- q / sqrt(|q|^2 + 1e-6) / sqrt(dk)``,
``k <- k / sqrt(|k|^2 + 1e-6)`` themselves, in float32: the admission's kernel
on the chunk it has loaded, so no normalised copy of an admission's q and k
passes through HBM), with a log decay
``g_t <= 0`` and a step ``beta_t`` in (0, 1) a token and a value head, and the
state ``S`` [dk keys x dv values]:

- as a recurrence: ``S <- exp(g_t) S;  d_t = beta_t (v_t - S^T k_t);  S <- S +
  k_t d_t^T;  o_t = S^T q_t``: a rank-one CORRECTION of what the state answers
  to ``k_t``, not a gated sum (``S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1}
  + beta_t k_t v_t^T``);
- in chunks of ``c`` tokens, with ``l_i = sum_{s <= i} g_s`` counted from the
  chunk's start, ``gamma_i = exp(l_i)`` and ``G_ij = exp(l_i - l_j)`` (masked
  to ``j <= i`` BEFORE the exponential): ``A = strict_lower(diag(beta) (K K^T o
  G))``, ``T = (I + A)^-1 diag(beta)``, ``W = T (K o gamma)``, ``U = T V``;
  against the state ``S`` the chunk came in with ``V' = U - W S``, ``O = (Q o
  gamma) S + tril(Q K^T o G) V'`` and ``S <- gamma_c S + (K o gamma_c /
  gamma)^T V'``.  The same sums; no quotient of decays is formed, every one
  is the exponential of a difference.

The inverse of the unit lower-triangular ``I + A`` is what neither
``retention_prefill`` nor ``ssm_prefill`` has (:func:`_unit_lower_inverse`):
the diagonal blocks of 16 by the product ``(I - D)(I + D^2)(I + D^4)(I +
D^8)`` (``D^16 = 0``), then the blocks merged by twos, ``X <- X - X M X``
with ``M`` the part of ``A`` under the diagonal blocks, which is the block
inverse exactly.  All matmuls, float32 at full precision.  The product taken
over the whole chunk at once (six factors) is the same in exact arithmetic and
is NOT taken: its powers reach ``binomial(63, 31)`` where neighbouring keys
agree and ``beta`` is near 1, and cancel; over blocks of 16 they stay under
``binomial(15, 7)`` = 6,435 (tests/models/test_qwen3_next.py holds both
against the recurrence).

How a row's state lies: ``[HV, dk, dv]`` float32, a value head's ``S`` as the
recurrence writes it, whole 128-lane tiles (no layout of its own): a step's
``v``, ``d`` and ``o`` lie along the lanes as the activations do, ``k`` and
``q`` go down the sublanes (a decode step hands them over turned), ``S^T k``
and ``S^T q`` are sums over sublanes and no lane moves.

Two operators, each a Pallas kernel with a plain ``jax.numpy`` body behind it
(``DLT_RAGGED_DECODE``: kernel on a TPU, ``interpret`` for the tests,
``fallback`` the CPU's default), each under its own name in a trace and in the
dispatch record (``ops.dispatch.gdn_prefill.*`` / ``gdn_decode.*``):

- :func:`gdn_prefill`: one row's T tokens from an empty state, in chunks; only
  the chunks that hold a real token are walked, and the state is left AT THE
  TRUE LENGTH (a padded position has ``beta`` 0 and ``g`` 0).
- :func:`gdn_decode`: one recurrence step for every batch slot against the
  whole stack of every layer's states, which is the decode scans' carry and is
  updated where it lies (aliased in and out, indexed by a prefetched layer).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch

LANES = 128
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
_BLOCK = 16  # the triangle's diagonal blocks
# Tokens a grid step of the admission's kernel takes: the keys go in turned
# too ([dk, tokens]), and a block's last axis is whole 128-lane rows.
_STEP = 128
# A row's whole state in a layer is one block of the decode kernel (2 MiB at
# 32 value heads of 128 x 128), in and out and each twice for the pipeline.
_VMEM_LIMIT = 64 * 1024 * 1024

_dot = functools.partial(jax.lax.dot_general, precision=HIGHEST,
                         preferred_element_type=F32)
_NN = (((1,), (0,)), ((), ()))  # x @ y
_NT = (((1,), (1,)), ((), ()))  # x @ y^T
_TN = (((0,), (0,)), ((), ()))  # x^T @ y
L2_EPS = 1e-6


def _unit(x: jax.Array) -> jax.Array:
    """``x / sqrt(|x|^2 + eps)`` over the last axis, float32."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def state_shape(value_heads: int, key_dim: int, value_dim: int
                ) -> tuple[int, int, int]:
    """Shape of ONE row's state in ONE layer, float32."""
    return value_heads, key_dim, value_dim


def state_bytes(value_heads: int, key_dim: int, value_dim: int) -> int:
    """Bytes of one row's state in one layer."""
    return value_heads * key_dim * value_dim * 4


# ---------------------------------------------------------------------------
# A chunk (shared by the kernel and the jax.numpy body: 2-D float32 alone)
# ---------------------------------------------------------------------------

def _iotas(c: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0),
            jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _unit_lower_inverse(a: jax.Array, whole: bool = False) -> jax.Array:
    """``(I + a)^-1`` for ``a`` [c, c] strictly lower-triangular, c a power
    of two of 16 or more (module docstring).  ``whole``: the product over the
    whole chunk, the form that is NOT served (the tests' control)."""
    c = a.shape[0]
    ii, jj = _iotas(c)
    eye = (ii == jj).astype(F32)
    block = c if whole else _BLOCK
    shift = lambda size: size.bit_length() - 1  # (sizes are powers of two)
    same = lambda size: (jnp.right_shift(ii, shift(size))
                         == jnp.right_shift(jj, shift(size)))
    d = jnp.where(same(block), a, 0.0)
    x, p, size = eye - d, d, 2
    while size < block:  # (I + d^2)(I + d^4) ...: polynomials in d commute
        p = _dot(p, p, _NN)
        x = x + _dot(x, p, _NN)
        size *= 2
    size = block
    while size < c:  # [[X1, 0], [-X2 M X1, X2]] = X - X M X, X block-diagonal
        m = jnp.where(same(2 * size) & ~same(size), a, 0.0)
        x = x - _dot(x, _dot(m, x, _NN), _NN)
        size *= 2
    return x


def _chunk(kk, qk, q, k, v, lcol, lrow, bcol, s):
    """One value head's chunk of c tokens against the state ``s`` [dk, dv] it
    came in with -> (o [c, dv], the state at the chunk's end).  ``kk`` /
    ``qk`` [c, c]: ``K K^T`` and ``Q K^T`` of its key head; q, k [c, dk]
    (normalised); v [c, dv]; ``lcol`` [c, 1] / ``lrow`` [1, c] the running
    log decay from the chunk's start down the rows and along the lanes;
    ``bcol`` [c, 1] beta."""
    c = q.shape[0]
    ii, jj = _iotas(c)
    g = jnp.exp(jnp.where(jj <= ii, lcol - lrow, -jnp.inf))  # G_ij, j <= i
    a = jnp.where(jj < ii, bcol * kk * g, 0.0)
    t = _unit_lower_inverse(a)
    gam = jnp.exp(lcol)
    dk = k.shape[1]
    wu = _dot(t, jnp.concatenate([bcol * gam * k, bcol * v], axis=1), _NN)
    vp = wu[:, dk:] - _dot(wu[:, :dk], s, _NN)  # V' = U - W S
    o = _dot(q * gam, s, _NN) + _dot(qk * g, vp, _NN)
    last = lcol[c - 1:c, :]  # [1, 1]: the chunk's whole decay
    # (along the lanes first: Mosaic has no broadcast of [1, 1] both ways)
    s = (jnp.exp(jnp.broadcast_to(last, (1, s.shape[1]))) * s
         + _dot(k * jnp.exp(last - lcol), vp, _TN))
    return o, s


# ---------------------------------------------------------------------------
# A decode step
# ---------------------------------------------------------------------------

def _decode_kernel(layer_ref, qt_ref, kt_ref, v_ref, dec_ref, beta_ref, s_ref,
                   so_ref, o_ref, *, per_key: int):
    """One row of one layer: every value head's ``S`` [dk, dv] decayed,
    corrected by the token's rank-one update and read out.  ``qt_ref`` /
    ``kt_ref`` [dk, HK]: a key head's q and k down the sublanes; ``v_ref`` /
    ``dec_ref`` / ``beta_ref`` [HV, dv]: v, ``exp(g)`` and beta along the
    lanes (the two scalars repeated)."""
    del layer_ref  # read by the index maps only
    for h in range(s_ref.shape[0]):
        kh = h // per_key
        kcol, qcol = kt_ref[:, kh:kh + 1], qt_ref[:, kh:kh + 1]  # [dk, 1]
        row = slice(h, h + 1)
        s = s_ref[h] * dec_ref[row, :]
        d = beta_ref[row, :] * (
            v_ref[row, :] - jnp.sum(s * kcol, axis=0, keepdims=True))
        s = s + kcol * d
        so_ref[h] = s
        o_ref[row, :] = jnp.sum(s * qcol, axis=0, keepdims=True)


def _decode_call(states, layer, qt, kt, v, dec, beta, *, interpret: bool):
    _, b, hv, dk, dv = states.shape
    hk = qt.shape[-1]
    row = lambda *tail: pl.BlockSpec(
        (None, *tail), lambda i, l: (i,) + (0,) * len(tail))
    state = pl.BlockSpec((None, None, hv, dk, dv),
                         lambda i, l: (l[0], i, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[row(dk, hk), row(dk, hk), row(hv, dv), row(hv, dv),
                  row(hv, dv), state],
        out_specs=[state, row(hv, dv)],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, per_key=hv // hk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(states.shape, states.dtype),
                   jax.ShapeDtypeStruct((b, hv, dv), F32)],
        # operands: layer, qt, kt, v, dec, beta, states -> the stack is
        # updated where it lies (only the rows of ``layer`` pass through VMEM)
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="gdn_decode",  # the operation's name in a trace
    )(layer, qt, kt, v, dec, beta, states)


def gdn_decode(
    q: jax.Array,  # [B, HK, dk]: raw (normalised and scaled here)
    k: jax.Array,  # [B, HK, dk]: raw (normalised here)
    v: jax.Array,  # [B, HV, dv]
    g: jax.Array,  # [B, HV] float32, <= 0: the log decay
    beta: jax.Array,  # [B, HV] float32, in (0, 1)
    states: jax.Array,  # [L, B, HV, dk, dv] float32: every layer's
    layer: jax.Array | int,
    live: jax.Array | None = None,  # [B] bool: rows that take a step (None:
    #   all).  Any other row's state stays as it is, bit for bit
) -> tuple[jax.Array, jax.Array]:
    """One recurrence step a row (module docstring), in float32 whatever the
    activations' dtype.  Returns (o [B, HV, dv] float32, states'): the stack
    with layer ``layer`` advanced where it lies."""
    b, hv, dv = v.shape
    hk = q.shape[1]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    dec, beta = jnp.exp(g.astype(F32)), beta.astype(F32)
    if live is not None:  # a row that does not decode: decay 1, no correction
        dec = jnp.where(live[:, None], dec, 1.0)
        beta = jnp.where(live[:, None], beta, 0.0)
    q = _unit(q.astype(F32)) * q.shape[-1] ** -0.5
    k, v = _unit(k.astype(F32)), v.astype(F32)
    mode = dispatch.attention_mode()
    dispatch.record("gdn_decode", mode, (b, hk, hv, q.shape[-1], dv))
    if mode == "fallback":
        kh, qh = (jnp.repeat(x, hv // hk, axis=1) for x in (k, q))
        s = states[layer[0]] * dec[:, :, None, None]
        d = beta[:, :, None] * (
            v - jnp.sum(s * kh[..., None], axis=2))  # [B, HV, dv]
        s = s + kh[..., None] * d[:, :, None, :]
        o = jnp.sum(s * qh[..., None], axis=2)
        return o, jax.lax.dynamic_update_slice_in_dim(
            states, s[None], layer[0], 0)
    wide = lambda x: jnp.broadcast_to(x[:, :, None], (b, hv, dv))
    turned = lambda x: jnp.transpose(x, (0, 2, 1))  # [B, dk, HK]
    states, o = _decode_call(
        states, layer, turned(q), turned(k), v, wide(dec), wide(beta),
        interpret=mode == "interpret")
    return o, states


# ---------------------------------------------------------------------------
# An admission
# ---------------------------------------------------------------------------

def _prefill_kernel(nlive_ref, q_ref, k_ref, v_ref, lt_ref, l_ref, bt_ref,
                    o_ref, s_ref, *, c: int):
    """One (key head, ``_STEP`` tokens): the chunks of ``c`` tokens in it, one
    after the other, for each value head of the key head.  ``s_ref`` [value
    heads of the key head, dk, dv] is resident across the token axis.
    ``q_ref`` / ``k_ref`` [_STEP, dk], raw: normalised here; ``v_ref``
    [_STEP, heads x dv]; ``lt_ref`` / ``bt_ref`` [_STEP, heads] the running
    log decay and beta down the rows, ``l_ref`` [heads, _STEP] the former
    along the lanes."""
    ti = pl.program_id(1)
    per_key, _, dv = s_ref.shape

    @pl.when(ti == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    for j in range(_STEP // c):
        chunk = ti * (_STEP // c) + j
        rows = slice(j * c, (j + 1) * c)

        @pl.when(chunk >= nlive_ref[0])
        def _():  # a chunk of padding alone: nothing read out, nothing added
            o_ref[rows, :] = jnp.zeros((c, per_key * dv), o_ref.dtype)

        @pl.when(chunk < nlive_ref[0])
        def _():
            q = _unit(q_ref[rows, :].astype(F32)) * q_ref.shape[1] ** -0.5
            k = _unit(k_ref[rows, :].astype(F32))
            kk, qk = _dot(k, k, _NT), _dot(q, k, _NT)
            for h in range(per_key):
                o, s = _chunk(
                    kk, qk, q, k,
                    v_ref[rows, h * dv:(h + 1) * dv].astype(F32),
                    lt_ref[rows, h:h + 1], l_ref[h:h + 1, rows],
                    bt_ref[rows, h:h + 1], s_ref[h])
                o_ref[rows, h * dv:(h + 1) * dv] = o.astype(o_ref.dtype)
                s_ref[h] = s


def _prefill_call(q, k, v, lt, l, bt, nlive, *, c: int, interpret: bool):
    tp, kw = q.shape
    hk, _, per_key = lt.shape
    dk = kw // hk
    dv = v.shape[1] // (hk * per_key)
    tok = lambda width: pl.BlockSpec(
        (_STEP, width), lambda g, i, nl: (i, g))
    by_rows = pl.BlockSpec((None, _STEP, per_key), lambda g, i, nl: (g, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(hk, tp // _STEP),
        in_specs=[
            tok(dk), tok(dk), tok(per_key * dv), by_rows,
            pl.BlockSpec((None, per_key, _STEP), lambda g, i, nl: (g, 0, i)),
            by_rows,
        ],
        out_specs=[
            tok(per_key * dv),
            pl.BlockSpec((per_key, dk, dv), lambda g, i, nl: (g, 0, 0)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_prefill_kernel, c=c),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((hk * per_key, dk, dv), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="gdn_prefill",  # the operation's name in a trace
    )(nlive, q, k, v, lt, l, bt)


def _prefill_dense(q, k, v, l, beta, c: int):
    """The chunked form in plain ``jax.numpy`` (the kernel's reference and
    the CPU's body): a scan over the chunks, the state [HV, dk, dv] its
    carry, :func:`_chunk` a value head.  q, k [T, HK, dk] (normalised), v
    [T, HV, dv], l (the running log decay from each chunk's start) and beta
    [T, HV], all float32."""
    t, hv, dv = v.shape
    hk, dk = q.shape[1:]
    per_key = hv // hk

    def chunk(s, xs):
        qc, kc, vc, lc, bc = xs  # [c, heads, ..]
        kk = jnp.einsum("ihd,jhd->hij", kc, kc, precision=HIGHEST)
        qk = jnp.einsum("ihd,jhd->hij", qc, kc, precision=HIGHEST)
        up = lambda x: jnp.repeat(x, per_key, axis=0)  # key head -> its value heads
        heads = lambda x: jnp.moveaxis(x, 1, 0)  # [c, H, ..] -> [H, c, ..]
        o, s = jax.vmap(_chunk)(
            up(kk), up(qk), up(heads(qc)), up(heads(kc)), heads(vc),
            heads(lc)[:, :, None], heads(lc)[:, None, :],
            heads(bc)[:, :, None], s)
        return s, jnp.moveaxis(o, 0, 1)

    cut = lambda x: x.reshape(t // c, c, *x.shape[1:])
    s, o = jax.lax.scan(chunk, jnp.zeros((hv, dk, dv), F32),
                        (cut(q), cut(k), cut(v), cut(l), cut(beta)))
    return o.reshape(t, hv, dv), s


def gdn_prefill(
    q: jax.Array,  # [T, HK, dk]: raw (the kernel normalises and scales it)
    k: jax.Array,  # [T, HK, dk]: raw (the kernel normalises it)
    v: jax.Array,  # [T, HV, dv]
    g: jax.Array,  # [T, HV] float32, <= 0: the log decay
    beta: jax.Array,  # [T, HV] float32, in (0, 1)
    n: jax.Array | None = None,  # int32 scalar: the first ``n`` tokens are
    #   real (None: all T).  A padded position decays nothing and corrects
    #   nothing: the state is the one AT THE TRUE LENGTH
    chunk: int = 64,
) -> tuple[jax.Array, jax.Array]:
    """One row's T tokens from an empty state.  Returns (o [T, HV, dv] in v's
    dtype, state [HV, dk, dv] float32).  ``chunk`` tokens at a time; the
    kernel walks only the chunks that hold a real token (the outputs of the
    others are zeros)."""
    t, hv, dv = v.shape
    hk, dk = q.shape[1:]
    c = chunk
    tp = -(-t // _STEP) * _STEP
    n = jnp.asarray(t if n is None else n, jnp.int32)
    real = (jnp.arange(tp) < n)[:, None]
    pad = lambda x: jnp.pad(x, ((0, tp - t),) + ((0, 0),) * (x.ndim - 1))
    q, k, v = pad(q), pad(k), pad(v)
    g = jnp.where(real, pad(g.astype(F32)), 0.0)
    beta = jnp.where(real, pad(beta.astype(F32)), 0.0)
    l = jnp.cumsum(g.reshape(tp // c, c, hv), axis=1).reshape(tp, hv)
    mode = dispatch.attention_mode()
    dispatch.record("gdn_prefill", mode, (tp, hk, hv, dk, dv, c))
    if mode == "fallback":
        o, s = _prefill_dense(
            _unit(q.astype(F32)) * dk ** -0.5, _unit(k.astype(F32)),
            v.astype(F32), l, beta, c)
        return o[:t].astype(v.dtype), s
    per_key = hv // hk
    by_key = lambda x: jnp.transpose(  # [T, HV] -> [HK, T, heads of a key]
        x.reshape(tp, hk, per_key), (1, 0, 2))
    lt = by_key(l)
    o, s = _prefill_call(
        q.reshape(tp, hk * dk), k.reshape(tp, hk * dk),
        v.reshape(tp, hv * dv), lt,
        jnp.transpose(lt, (0, 2, 1)), by_key(beta),
        (-(-n // c)).reshape(1), c=c, interpret=mode == "interpret")
    return o.reshape(tp, hv, dv)[:t], s


def recurrence(q, k, v, g, beta):
    """The same operator token by token, in float32 (what the tests hold the
    two above to): q, k [T, HK, dk] raw, v [T, HV, dv], g / beta [T, HV] ->
    (o [T, HV, dv], the last state [HV, dk, dv])."""
    hv, dv = v.shape[1:]
    per_key = hv // q.shape[1]
    q = _unit(q.astype(F32)) * q.shape[-1] ** -0.5
    k = _unit(k.astype(F32))

    def step(s, xs):
        qt, kt, vt, gt, bt = xs
        kh, qh = jnp.repeat(kt, per_key, axis=0), jnp.repeat(qt, per_key, axis=0)
        s = jnp.exp(gt)[:, None, None] * s
        d = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kh,
                                           precision=HIGHEST))
        s = s + kh[:, :, None] * d[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qh, precision=HIGHEST)

    s, o = jax.lax.scan(
        step, jnp.zeros((hv, k.shape[-1], dv), F32),
        tuple(x.astype(F32) for x in (q, k, v, g, beta)))
    return o, s
