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
  G))``, ``T = (I + A)^-1``; against the state ``S`` the chunk came in with
  ``V' = T diag(beta) (V - diag(gamma) K S)`` (``W = T diag(beta gamma) K`` of
  ``V' = U - W S`` is never formed: one ``[c x c] x [c x dv]`` product where
  there were ``[c x c] x [c x (dk + dv)]`` and ``W S``), ``O = diag(gamma) Q S
  + tril(Q K^T o G) V'`` and ``S <- gamma_c S + K^T diag(gamma_c / gamma)
  V'``.  The same sums; no quotient of decays is formed, every one is the
  exponential of a difference.

The inverse of the unit lower-triangular ``I + A`` is what neither
``retention_prefill`` nor ``ssm_prefill`` has (:func:`_unit_lower_inverse`):
the diagonal blocks of 16 by the product ``(I - D)(I + D^2)(I + D^4)(I +
D^8)`` (``D^16 = 0``), then the blocks merged by twos, ``X <- X - X M X``
with ``M`` the part of ``A`` under the diagonal blocks, which is the block
inverse exactly: ten matmuls, float32 at full precision.  The product taken
over the whole chunk at once (six factors) is the same in exact arithmetic and
is NOT taken: its powers reach ``binomial(63, 31)`` where neighbouring keys
agree and ``beta`` is near 1, and cancel; over blocks of 16 they stay under
``binomial(15, 7)`` = 6,435 (tests/models/test_qwen3_next.py holds both
against the recurrence).

The MXU passes a product takes are read off its operands' dtypes (:func:`_mm`;
a float32 x float32 product at ``Precision.HIGHEST`` is six passes over the
operands' bfloat16 pieces and drops the smallest cross terms):

- ``q`` and ``k`` in bfloat16 (every served admission: the layer's
  convolution leaves them so) stay as they are; the normalised rows are those
  values times ONE float32 scalar a row, ``rk = rsqrt(|k|^2 + eps)`` and ``rq =
  rsqrt(|q|^2 + eps) dk^-0.5``.  ``K K^T = rk (K_raw K_raw^T) rk^T`` and ``Q
  K^T = rq (Q_raw K_raw^T) rk^T``: 1 pass each, every product of two bfloat16
  values exact in float32, and a scaling on the VPU.  ``[K_raw ; Q_raw] S``
  and the state's update ``K_raw^T (diag(rk gamma_c / gamma) V')``: the
  float32 operand split into the three bfloat16 pieces whose sum it is
  (:func:`_pieces`), 3 passes, each product exact, which is MORE than
  ``HIGHEST`` keeps.  The inverse, ``T R`` and ``tril(Q K^T o G) V'`` have
  two float32 operands and stay at ``HIGHEST``.
- ``q`` and ``k`` in float32 (the tests, the tools' float32 legs) hold
  nothing a bfloat16 piece would: normalised first, every product at
  ``HIGHEST``, as before.

Neither route loses a bit, no switch chooses between them, and nothing that is
not already bfloat16-exact is cast to bfloat16.  A key head's value heads are
taken TWO AT A TIME where they pair off (:func:`_chunk`): their triangles ``[c
x c]`` lie side by side in 128 lanes, ``[X1 | X2] x blockdiag(Y1, Y2)`` is both
heads' products in the MXU rows of one (:func:`_blocks`), so the inverse is
ten matmuls for BOTH and every ``[c x 2c]`` step on the VPU fills its vregs;
their values and states lie side by side too, ``[c, 2 dv]`` and ``[dk, 2
dv]``.  An odd count takes the heads singly.  MXU rows a value head and a
chunk of 64, bfloat16 route, in pairs: 64 (``K K^T``, ``Q K^T``) + 1,920 (the
inverse: 10 x 64 x 6 / 2) + 384 (``[K ; Q] S``) + 384 (``T R``) + 384 + 384
(the update) = 3,520, where the form with ``W``, single heads and six passes
throughout took 6,912.

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
  TRUE LENGTH (a padded position has ``beta`` 0 and ``g`` 0).  A grid step
  takes a key head's next four chunks: what no state enters (``K K^T``, ``Q
  K^T`` and the triangles' inverses) is made for all four first, the four
  chains of ten matmuls IN STEP, a link of each after the same link of the
  one before, because a chain alone waits out each product's latency (217 ns
  a link on the v5e, 76 with four side by side); then the chunks one after
  the other against the state.
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

from ..core.observability import METRICS
from . import dispatch

LANES = 128
F32 = jnp.float32
BF16 = jnp.bfloat16
HIGHEST = jax.lax.Precision.HIGHEST
_BLOCK = 16  # the triangle's diagonal blocks
# Tokens a grid step of the admission's kernel takes: four chunks of 64, whose
# triangles are inverted in step (measured on the v5e at Qwen3-Next's heads,
# an 8,192 bucket with 5,690 tokens, 9 layers: 33.9 ms at 128, 30.3 at 256,
# 30.5 at 512; PERF.md section 6, PR 60).
_STEP = 256
# A row's whole state in a layer is one block of the decode kernel (2 MiB at
# 32 value heads of 128 x 128), in and out and each twice for the pipeline.
_VMEM_LIMIT = 64 * 1024 * 1024

_dot = functools.partial(jax.lax.dot_general, precision=HIGHEST,
                         preferred_element_type=F32)
_NN = (((1,), (0,)), ((), ()))  # x @ y
_NT = (((1,), (1,)), ((), ()))  # x @ y^T
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
# A chunk (shared by the kernel and the jax.numpy body: 2-D arrays alone)
# ---------------------------------------------------------------------------

def _pieces(x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """A float32 array as the three bfloat16 ones whose sum it is, exactly:
    8 + 8 + 8 bits of a 24-bit mantissa."""
    hi = x.astype(BF16)
    rest = x - hi.astype(F32)
    mid = rest.astype(BF16)
    return hi, mid, (rest - mid.astype(F32)).astype(BF16)


def _mm(a: jax.Array, b: jax.Array, dims) -> jax.Array:
    """``a x b`` in float32, in the MXU passes the operands' dtypes ask for
    (module docstring): two bfloat16 operands 1, a bfloat16 ``a`` against a
    float32 ``b``'s three pieces 3, two float32 ones
    ``HIGHEST``'s 6 (:func:`_key_head` hands over no other pair)."""
    one = functools.partial(  # (DEFAULT whatever the caller's context asks:
        jax.lax.dot_general,  # a bfloat16 operand is ONE piece)
        dimension_numbers=dims, precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=F32)
    if a.dtype == BF16 and b.dtype == BF16:
        return one(a, b)
    if a.dtype == BF16:
        hi, mid, lo = _pieces(b)
        return one(a, hi) + one(a, mid) + one(a, lo)
    return _dot(a, b, dims)


def _iotas(c: int, n: int = 1):
    """Row, and column WITHIN ITS HEAD'S BLOCK of ``c`` lanes, of [c, n c]
    (c a power of two)."""
    return (jax.lax.broadcasted_iota(jnp.int32, (c, n * c), 0),
            jax.lax.broadcasted_iota(jnp.int32, (c, n * c), 1) & (c - 1))


def _wide(col: jax.Array, w: int) -> jax.Array:
    """[c, n], a column a head -> [c, n w]: head ``h``'s column along lanes
    ``h w .. (h + 1) w``."""
    c, n = col.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, n * w), 1)
    out = jnp.broadcast_to(col[:, n - 1:], (c, n * w))
    for h in reversed(range(n - 1)):
        out = jnp.where(lane < (h + 1) * w, col[:, h:h + 1], out)
    return out


def _blocks(x: jax.Array, n: int) -> jax.Array:
    """[c, n w], ``n`` heads side by side -> [n c, n w] with head ``h``'s
    block at rows ``h c ..`` and zeros elsewhere: ``[Y1 | Y2] x _blocks([X1 |
    X2])`` is ``[Y1 X1 | Y2 X2]``, both heads' products in the MXU rows of
    one."""
    if n == 1:
        return x
    w = x.shape[1] // n
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.concatenate(
        [jnp.where((lane >= h * w) & (lane < (h + 1) * w), x, 0.0)
         for h in range(n)], axis=0)


# (The pieces a step repeats are each under a ``jit`` of their own: traced
# once a kernel and bound again, not traced four times a step and forty a
# step for the products, which was 2.4 s of a server's set-up a bucket on the
# chip's host; Mosaic lowers each where it is bound, in the same order.)
@functools.partial(jax.jit, static_argnames=("n",))
def _by(x: jax.Array, y: jax.Array, n: int) -> jax.Array:
    """``[X1 | X2] x blockdiag(Y1, Y2)`` at full precision."""
    return _dot(x, _blocks(y, n), _NN)


def _unit_lower_inverses(a: list[jax.Array], whole: bool = False
                         ) -> list[jax.Array]:
    """``(I + a)^-1`` for each ``a`` [c, c] strictly lower-triangular, c a
    power of two of 16 or more (module docstring), or ``n`` such triangles
    side by side, [c, n c]: ten matmuls for them all.  The arrays of the list
    are inverted IN STEP, a link of every chain after the same link of the
    one before: the chains do not wait on one another, and the MXU takes a
    link of one while another's drains.  ``whole``: the product over the
    whole chunk, the form that is NOT served (the tests' control)."""
    c = a[0].shape[0]
    n = a[0].shape[1] // c
    ii, jj = _iotas(c, n)
    eye = (ii == jj).astype(F32)
    block = c if whole else _BLOCK
    shift = lambda size: size.bit_length() - 1  # (sizes are powers of two)
    same = lambda size: (jnp.right_shift(ii, shift(size))
                         == jnp.right_shift(jj, shift(size)))
    by = functools.partial(_by, n=n)  # a head's x times its y
    p = [jnp.where(same(block), ai, 0.0) for ai in a]
    x, size = [eye - d for d in p], 2
    while size < block:  # (I + d^2)(I + d^4) ...: polynomials in d commute
        p = [by(pi, pi) for pi in p]
        x = [xi + by(xi, pi) for xi, pi in zip(x, p)]
        size *= 2
    size = block
    while size < c:  # [[X1, 0], [-X2 M X1, X2]] = X - X M X, X block-diagonal
        under = same(2 * size) & ~same(size)
        mx = [by(jnp.where(under, ai, 0.0), xi) for ai, xi in zip(a, x)]
        x = [xi - by(xi, mxi) for xi, mxi in zip(x, mx)]
        size *= 2
    return x


def _unit_lower_inverse(a: jax.Array, whole: bool = False) -> jax.Array:
    """One of :func:`_unit_lower_inverses`."""
    return _unit_lower_inverses([a], whole)[0]


@functools.partial(jax.jit, static_argnames=("n",))
def _key_head(q: jax.Array, k: jax.Array, n: int):
    """What the value heads of one key head share in a chunk.  q, k [c, dk]
    RAW, in the dtype they came in -> ``kk`` / ``qk`` [c, n c]: ``K K^T`` and
    ``Q K^T`` of the NORMALISED rows, once a head of a group of ``n`` along
    the lanes (a matmul of c rows whatever ``n``); ``kq`` [2c, dk]: the raw
    ``[K ; Q]``; ``kt`` [dk, c]: the raw ``K^T``, turned once for all the
    groups; ``rk`` / ``rq`` [c, 1] float32: what normalises a row of K and of
    Q (the latter times ``dk^-0.5``)."""
    c, dk = k.shape
    norm = lambda x: jax.lax.rsqrt(jnp.sum(
        jnp.square(x.astype(F32)), axis=-1, keepdims=True) + L2_EPS)
    rk, rq = norm(k), norm(q) * dk ** -0.5
    if not (q.dtype == BF16 and k.dtype == BF16):
        # float32 rows hold nothing a bfloat16 piece would: normalised
        # BEFORE their products, as they always were, and their scales are 1
        k, q = k.astype(F32) * rk, q.astype(F32) * rq
        rk = rq = jnp.ones((c, 1), F32)
    ii, jj = _iotas(c, n)
    # rk along the lanes, the same numbers: the diagonal of its broadcast
    rk_row = jnp.sum(jnp.where(ii == jj, rk, 0.0), axis=0, keepdims=True)
    k2 = jnp.concatenate([k] * n, axis=0)
    return ((rk * rk_row) * _mm(k, k2, _NT), (rq * rk_row) * _mm(q, k2, _NT),
            jnp.concatenate([k, q], axis=0),
            k.astype(F32).T.astype(k.dtype),  # (exact both ways)
            rk, rq)


@jax.jit
def _triangle(kk, lcol, lrow, bcol):
    """What a chunk of ``n`` value heads needs that no state enters: ``g`` [c,
    n c], ``G_ij`` of each head side by side, and ``a`` likewise, the
    triangle ``A`` to invert.  ``kk``: :func:`_key_head`'s; ``lcol`` [c, n] /
    ``lrow`` [1, n c] the running log decay from the chunk's start down the
    rows and along the lanes; ``bcol`` [c, n] beta."""
    c = lcol.shape[0]
    ii, jj = _iotas(c, lcol.shape[1])
    g = jnp.exp(jnp.where(jj <= ii, _wide(lcol, c) - lrow, -jnp.inf))
    return g, jnp.where(jj < ii, _wide(bcol, c) * kk * g, 0.0)


@jax.jit
def _chunk(g, t, qk, kq, kt, rk, rq, v, lcol, bcol, s):
    """A chunk of c tokens of ``n`` value heads of one key head, side by side
    along the lanes, against the states ``s`` [dk, n dv] they came in with ->
    (o [c, n dv], the states at the chunk's end).  ``g``, ``t``:
    :func:`_triangle`'s; ``qk`` .. ``rq``: :func:`_key_head`'s; v [c, n dv]
    float32; ``lcol`` / ``bcol`` [c, n]."""
    c, n = lcol.shape
    dv = v.shape[1] // n
    l = _wide(lcol, dv)
    gam = jnp.exp(l)
    ks_qs = _mm(kq, s, _NN)  # [2c, n dv]: the raw [K ; Q] S
    # V' = T diag(beta) (V - diag(gamma) K S): W = T (beta gamma K) unformed
    vp = _dot(t, _blocks(
        _wide(bcol, dv) * (v - (gam * rk) * ks_qs[:c]), n), _NN)
    o = (gam * rq) * ks_qs[c:] + _dot(qk * g, _blocks(vp, n), _NN)
    # [1, n dv]: the chunk's whole decay, a head (its last row, summed out:
    # Mosaic has no broadcast of a sliced [1, 1] both ways)
    row = jax.lax.broadcasted_iota(jnp.int32, l.shape, 0)
    last = jnp.sum(jnp.where(row == c - 1, l, 0.0), axis=0, keepdims=True)
    s = jnp.exp(last) * s + _mm(kt, rk * jnp.exp(last - l) * vp, _NN)
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

def _group(per_key: int) -> int:
    """Value heads of a key head that a chunk takes side by side: 2 where
    they pair off (their triangles [c, c] fill the 128 lanes of an MXU pass
    together), else 1."""
    return 1 if per_key % 2 else 2


def _prefill_kernel(nlive_ref, q_ref, k_ref, v_ref, lt_ref, l_ref, bt_ref,
                    o_ref, s_ref, *, c: int, n: int):
    """One (key head, ``_STEP`` tokens): the chunks of ``c`` tokens in it for
    the value heads of the key head in groups of ``n``.  What no state enters
    (a chunk's products of q and k and its triangles' inverses: ten matmuls
    in a chain) is made for EVERY chunk of the step first, so that the
    chains, which do not wait on one another, fill the MXU's latency side by
    side; then the chunks one after the other against the state.  ``s_ref``
    [value heads of the key head, dk, dv] is resident across the token axis.
    ``q_ref`` / ``k_ref`` [_STEP, dk], raw: normalised here; ``v_ref``
    [_STEP, heads x dv]; ``lt_ref`` / ``bt_ref`` [_STEP, heads] the running
    log decay and beta down the rows, ``l_ref`` [groups, n x _STEP] the
    former along the lanes, a chunk's ``n`` heads side by side."""
    ti = pl.program_id(1)
    per_key, _, dv = s_ref.shape
    chunks, groups = _STEP // c, per_key // n
    first, nlive = ti * chunks, nlive_ref[0]
    rows = lambda j: slice(j * c, (j + 1) * c)
    heads = lambda p: slice(p * n, (p + 1) * n)
    lanes = lambda p: slice(p * n * dv, (p + 1) * n * dv)

    @pl.when(ti == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def against_state(j, shared, triangles):
        for p in range(groups):
            o, s = _chunk(
                *triangles[p], *shared[1:],
                v_ref[rows(j), lanes(p)].astype(F32), lt_ref[rows(j), heads(p)],
                bt_ref[rows(j), heads(p)],
                jnp.concatenate([s_ref[h] for h in range(
                    p * n, (p + 1) * n)], axis=1))
            o_ref[rows(j), lanes(p)] = o.astype(o_ref.dtype)
            for h in range(n):
                s_ref[p * n + h] = s[:, h * dv:(h + 1) * dv]

    @pl.when(first >= nlive)
    def _():  # a step of padding alone: nothing read out, nothing added
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(first < nlive)
    def _():
        shared = [_key_head(q_ref[rows(j), :], k_ref[rows(j), :], n)
                  for j in range(chunks)]
        ga = [[_triangle(shared[j][0], lt_ref[rows(j), heads(p)],
                         l_ref[p:p + 1, j * n * c:(j + 1) * n * c],
                         bt_ref[rows(j), heads(p)]) for p in range(groups)]
              for j in range(chunks)]
        t = iter(_unit_lower_inverses([a for row in ga for _, a in row]))
        for j in range(chunks):
            pl.when(first + j < nlive)(functools.partial(
                against_state, j, shared[j], [(g, next(t)) for g, _ in ga[j]]))

            @pl.when(first + j >= nlive)
            def _():  # a chunk of padding in the row's last step
                o_ref[rows(j), :] = jnp.zeros((c, per_key * dv), o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("c", "interpret"))
def _prefill_call(q, k, v, lt, l, bt, nlive, *, c: int, interpret: bool):
    """(Under its own ``jit``: the layers of a scanned run hand over the same
    shapes, and the kernel's body, four chunks of straight code, is traced
    and lowered ONCE for them all and not once a layer: set-up time.)"""
    tp, kw = q.shape
    hk, _, per_key = lt.shape
    groups = l.shape[1]
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
            pl.BlockSpec((None, groups, per_key // groups * _STEP),
                         lambda g, i, nl: (g, 0, i)),
            by_rows,
        ],
        out_specs=[
            tok(per_key * dv),
            pl.BlockSpec((per_key, dk, dv), lambda g, i, nl: (g, 0, 0)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_prefill_kernel, c=c, n=per_key // groups),
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


def _prefill_dense(q, k, v, lt, l, bt, c: int):
    """The chunked form in plain ``jax.numpy`` (the kernel's reference and
    the CPU's body): a scan over the chunks, :func:`_key_head` a key head and
    :func:`_chunk` a group of its value heads, as the kernel takes them; the
    carry is the states a group side by side, [HK x groups, dk, n dv].  The
    operands are the kernel's: q, k [T, HK x dk] raw, v [T, HV x dv], ``lt``
    / ``bt`` [HK, T, heads of a key], ``l`` [HK, groups, n T]."""
    tp = q.shape[0]
    hk, _, per_key = lt.shape
    groups = l.shape[1]
    n = per_key // groups
    dk, dv = q.shape[1] // hk, v.shape[1] // (hk * per_key)
    # [T, heads x w] -> [chunks, heads, c, w]
    cut = lambda x, heads: jnp.transpose(
        x.reshape(tp // c, c, heads, -1), (0, 2, 1, 3))
    # [HK, T, heads of a key] -> [chunks, HK, groups, c, n]
    cols = lambda x: jnp.transpose(
        x.reshape(hk, tp // c, c, groups, n), (1, 0, 3, 2, 4))
    rows = jnp.transpose(  # -> [chunks, HK, groups, 1, n c]
        l.reshape(hk, groups, tp // c, 1, n * c), (2, 0, 1, 3, 4))
    up = lambda x: jnp.repeat(x, groups, axis=0)  # key head -> its groups
    flat = lambda x: x.reshape(tp // c, hk * groups, *x.shape[3:])

    def chunk(s, xs):
        qc, kc, vc, lc, lr, bc = xs
        kk, *shared = map(up, jax.vmap(
            functools.partial(_key_head, n=n))(qc, kc))
        g, a = jax.vmap(_triangle)(kk, lc, lr, bc)
        o, s = jax.vmap(_chunk)(
            g, jax.vmap(_unit_lower_inverse)(a), *shared, vc, lc, bc, s)
        return s, o  # o [HK x groups, c, n dv]

    s, o = jax.lax.scan(
        chunk, jnp.zeros((hk * groups, dk, n * dv), F32),
        (cut(q, hk), cut(k, hk), cut(v, hk * groups).astype(F32),
         flat(cols(lt)), flat(rows), flat(cols(bt))))
    o = o.reshape(tp // c, hk, groups, c, n * dv)
    o = jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(tp, hk * per_key * dv)
    s = jnp.transpose(s.reshape(hk, groups, dk, n, dv), (0, 1, 3, 2, 4))
    return o.astype(v.dtype), s.reshape(hk * per_key, dk, dv)


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
    others are zeros).  ``q`` and ``k`` in bfloat16 (every served admission)
    take the products of 1 and 3 MXU passes, in float32 ``HIGHEST``'s 6
    (module docstring): neither loses a bit."""
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
    per_key = hv // hk
    side = _group(per_key)
    if q.dtype == BF16 and k.dtype == BF16:
        METRICS.inc("ops.gdn_prefill.bf16_operands")
    if side > 1:
        METRICS.inc("ops.gdn_prefill.paired_heads")
    by_key = lambda x: jnp.transpose(  # [T, HV] -> [HK, T, heads of a key]
        x.reshape(tp, hk, per_key), (1, 0, 2))
    # the same along the lanes, a chunk's ``side`` heads side by side:
    # [HK, groups, chunks x side x c]
    along = jnp.transpose(
        l.reshape(tp // c, c, hk, per_key // side, side), (2, 3, 0, 4, 1))
    args = (q.reshape(tp, hk * dk), k.reshape(tp, hk * dk),
            v.reshape(tp, hv * dv), by_key(l),
            along.reshape(hk, per_key // side, side * tp), by_key(beta))
    if mode == "fallback":
        o, s = _prefill_dense(*args, c)
    else:
        o, s = _prefill_call(*args, (-(-n // c)).reshape(1), c=c,
                             interpret=mode == "interpret")
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
