"""Mamba-2's selective state-space scan (layer kind "ssm"): a row's memory in
a layer is one float32 state a head, whatever the row's length.

For head ``h`` of ``P`` channels in group ``g`` (the heads of a group share
``B_t`` and ``C_t``, ``N`` wide), with ``dt_t > 0`` a token and a head and
``A_h < 0`` a head:

- as a recurrence: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` ([P, N]),
  ``y_t = S_t C_t``;
- in chunks of ``c`` tokens, with ``l_i = sum_{s <= i} dt_s A`` counted from
  the chunk's start: among the chunk's own tokens the masked form ``y_i =
  sum_{j <= i} (C_i . B_j) exp(l_i - l_j) dt_j x_j``; from the chunks before
  ``exp(l_i) S C_i``; and the state moves to the chunk's end as ``exp(l_c) S
  + sum_j exp(l_c - l_j) dt_j x_j B_j^T``.  The same sums.

(The skip ``D_h x_t``, the gate and the grouped norm are the layer's:
models.model.ssm_layer.)

How a row's state lies (the layout is this module's and nobody else's):
``[R, N, 128]`` float32 with ``R = heads * P / 128``: row ``r`` holds the
``128 // P`` heads ``r * (128 // P) ...`` side by side on the lanes, each
TRANSPOSED, ``S^T`` [N, P] (:func:`to_layout`).  So a recurrence step is
``new = tile * decay_row + B_col * (dt x)_row`` and ``y = sum over the
sublanes of new * C_col``: the per-token vectors that differ a head lie along
the lanes as the activations do, the two that a group shares go down the
sublanes (one transpose a group, not a head), the readout is a sum over
sublanes and no lane moves.  Stored [P, N] a head, every head would need its
own lane-to-sublane move of ``x`` and a sum over the lanes.  The bytes are the
same: heads * P * N * 4.

Two operators, each a Pallas kernel with a plain ``jax.numpy`` body behind it
(``DLT_RAGGED_DECODE``: kernel on a TPU, ``interpret`` for the tests,
``fallback`` the CPU's default), each under its own name in a trace and in the
dispatch record (``ops.dispatch.ssm_prefill.*`` / ``ssm_decode.*``):

- :func:`ssm_prefill`: one row's T tokens from an empty state, in chunks; only
  the chunks that hold a real token are walked, and the state is left AT THE
  TRUE LENGTH.
- :func:`ssm_decode`: one recurrence step for every batch slot against the
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
# A row's whole state in a layer is one block of the decode kernel (4 MiB at
# 128 heads of 64 x 128), in and out and each twice for the pipeline.
_VMEM_LIMIT = 96 * 1024 * 1024


def state_shape(heads: int, head_dim: int, n: int) -> tuple[int, int, int]:
    """Shape of ONE row's state in ONE layer, float32: [R, N, 128]."""
    return heads * head_dim // LANES, n, LANES


def state_bytes(heads: int, head_dim: int, n: int) -> int:
    """Bytes of one row's state in one layer."""
    return heads * head_dim * n * 4


def to_layout(s: jax.Array) -> jax.Array:
    """States [..., H, P, N] as the recurrence writes them -> [..., R, N,
    128] as they are stored."""
    *lead, h, p, n = s.shape
    per = LANES // p
    s = s.reshape(*lead, h // per, per, p, n)
    return jnp.moveaxis(s, -1, -3).reshape(*lead, h // per, n, LANES)


def from_layout(s: jax.Array, head_dim: int) -> jax.Array:
    """The inverse of :func:`to_layout`: [..., R, N, 128] -> [..., H, P, N]."""
    *lead, r, n, _ = s.shape
    per = LANES // head_dim
    s = s.reshape(*lead, r, n, per, head_dim)
    return jnp.moveaxis(s, -3, -1).reshape(*lead, r * per, head_dim, n)


# ---------------------------------------------------------------------------
# A decode step
# ---------------------------------------------------------------------------

def _decode_kernel(layer_ref, xdt_ref, dec_ref, b_ref, c_ref, s_ref, so_ref,
                   y_ref, col_ref, *, groups: int):
    """One row of one layer: every tile ``S^T`` [N, 128] of its state
    decayed, given the token's outer product and read out.  ``xdt_ref`` /
    ``dec_ref`` [R, 128]: ``dt x`` and ``exp(dt A)`` along the lanes as the
    heads lie; ``b_ref`` / ``c_ref`` [G, N]: a group's B and C, turned down
    the sublanes once a group (``col_ref``)."""
    del layer_ref  # read by the index maps only
    r_all, n, _ = s_ref.shape
    per_group = r_all // groups
    for g in range(groups):
        col_ref[0] = jnp.broadcast_to(b_ref[g:g + 1, :], (LANES, n)).T
        col_ref[1] = jnp.broadcast_to(c_ref[g:g + 1, :], (LANES, n)).T

        def tile(i, carry, g=g):
            r = g * per_group + i
            new = (s_ref[r] * dec_ref[pl.ds(r, 1), :]
                   + col_ref[0] * xdt_ref[pl.ds(r, 1), :])
            so_ref[r] = new
            y_ref[pl.ds(r, 1), :] = jnp.sum(
                new * col_ref[1], axis=0, keepdims=True)
            return carry

        jax.lax.fori_loop(0, per_group, tile, 0)


def _decode_call(states, layer, xdt, dec, bm, cm, *, interpret: bool):
    _, b, r, n, _ = states.shape
    groups = bm.shape[1]
    row = lambda *tail: pl.BlockSpec(
        (None, *tail), lambda i, l: (i,) + (0,) * len(tail))
    state = pl.BlockSpec((None, None, r, n, LANES),
                         lambda i, l: (l[0], i, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[row(r, LANES), row(r, LANES), row(groups, n),
                  row(groups, n), state],
        out_specs=[state, row(r, LANES)],
        scratch_shapes=[pltpu.VMEM((2, n, LANES), F32)],  # B, C down the rows
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, groups=groups),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(states.shape, states.dtype),
                   jax.ShapeDtypeStruct((b, r, LANES), F32)],
        # operands: layer, xdt, dec, bm, cm, states -> the stack is updated
        # where it lies (only the rows of ``layer`` pass through VMEM)
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ssm_decode",  # the operation's name in a trace
    )(layer, xdt, dec, bm, cm, states)


def ssm_decode(
    x: jax.Array,  # [B, H, P]: the convolution's output, a head's channels
    bm: jax.Array,  # [B, G, N]
    cm: jax.Array,  # [B, G, N]
    dt: jax.Array,  # [B, H] float32, > 0 (softplus taken)
    a: jax.Array,  # [H] float32, < 0
    states: jax.Array,  # [L, B, R, N, 128] float32: every layer's
    layer: jax.Array | int,
    live: jax.Array | None = None,  # [B] bool: rows that take a step (None:
    #   all).  Any other row's state stays as it is, bit for bit
) -> tuple[jax.Array, jax.Array]:
    """One recurrence step a row, ``S <- exp(dt A) S + dt x B^T``, ``y = S
    C``, in float32 whatever the activations' dtype.  Returns (y [B, H, P]
    float32, states'): the stack with layer ``layer`` advanced where it
    lies."""
    b, h, p = x.shape
    r = h * p // LANES
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    dt = dt.astype(F32)
    dec = jnp.exp(dt * a.astype(F32))  # [B, H]
    xdt = x.astype(F32) * dt[:, :, None]
    if live is not None:  # a row that does not decode: decay 1, nothing added
        dec = jnp.where(live[:, None], dec, 1.0)
        xdt = jnp.where(live[:, None, None], xdt, 0.0)
    dec = jnp.repeat(dec, p, axis=1).reshape(b, r, LANES)
    xdt = xdt.reshape(b, r, LANES)
    bm, cm = bm.astype(F32), cm.astype(F32)
    mode = dispatch.attention_mode()
    dispatch.record("ssm_decode", mode, (b, h, p, bm.shape[-1]))
    if mode == "fallback":
        rows = r // bm.shape[1]  # state rows a group
        bcol = jnp.repeat(bm, rows, axis=1)[..., None]  # [B, R, N, 1]
        ccol = jnp.repeat(cm, rows, axis=1)[..., None]
        new = (states[layer[0]] * dec[:, :, None, :]
               + bcol * xdt[:, :, None, :])
        y = jnp.sum(new * ccol, axis=2)
        states = jax.lax.dynamic_update_slice_in_dim(
            states, new[None], layer[0], 0)
    else:
        states, y = _decode_call(
            states, layer, xdt, dec, bm, cm, interpret=mode == "interpret")
    return y.reshape(b, h, p), states


# ---------------------------------------------------------------------------
# An admission
# ---------------------------------------------------------------------------

def _prefill_kernel(nlive_ref, x_ref, b_ref, c_ref, bt_ref, lt_ref, dtt_ref,
                    l_ref, y_ref, s_ref, *, head_dim: int):
    """One (group, chunk of ``c`` tokens): the masked form among the chunk's
    tokens, the state of the chunks before for the rest, then the state moved
    to the chunk's end.  ``s_ref`` [rows of the group, N, 128] is resident
    across the chunk axis.  ``x_ref`` [c, heads of the group x P]; ``b_ref`` /
    ``c_ref`` [c, N] and ``bt_ref`` [N, c] (B turned); ``lt_ref`` / ``dtt_ref``
    [c, heads] the chunk's running log decay and dt down the rows, ``l_ref``
    [heads, c] the former along the lanes."""
    ci = pl.program_id(1)
    c = x_ref.shape[0]
    rows = s_ref.shape[0]
    per = LANES // head_dim
    dot = functools.partial(jax.lax.dot_general, precision=HIGHEST,
                            preferred_element_type=F32)
    nt = (((1,), (1,)), ((), ()))  # x @ y^T
    nn = (((1,), (0,)), ((), ()))

    @pl.when(ci == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(ci >= nlive_ref[0])
    def _():  # a chunk of padding alone: nothing to read out, nothing to add
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(ci < nlive_ref[0])
    def _():
        bm, cm = b_ref[...].astype(F32), c_ref[...].astype(F32)
        bt = bt_ref[...].astype(F32)
        cb = dot(cm, bm, nt)  # [c, c]: C_i . B_j, the group's
        ii = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (c, LANES), 1)

        def by_head(cols):  # [c, 1] a head -> [c, 128] as the heads lie
            out = cols[-1]
            for k in range(per - 2, -1, -1):
                out = jnp.where(lane < (k + 1) * head_dim, cols[k], out)
            return out

        for r in range(rows):
            heads = [r * per + k for k in range(per)]
            lcols = [lt_ref[:, h:h + 1] for h in heads]
            lcol = by_head(lcols)  # [c, 128]
            xdt = (x_ref[:, r * LANES:(r + 1) * LANES].astype(F32)
                   * by_head([dtt_ref[:, h:h + 1] for h in heads]))
            among = None
            for k, h in enumerate(heads):
                decay = jnp.exp(jnp.where(
                    jj <= ii, lcols[k] - l_ref[h:h + 1, :], -jnp.inf))
                yk = dot(cb * decay, xdt, nn)  # (head k's lanes are right)
                among = yk if among is None else jnp.where(
                    lane >= k * head_dim, yk, among)
            tile = s_ref[r]
            y = among + jnp.exp(lcol) * dot(cm, tile, nn)
            y_ref[:, r * LANES:(r + 1) * LANES] = y.astype(y_ref.dtype)
            last = lcol[c - 1:c, :]  # [1, 128]: the chunk's whole decay
            s_ref[r] = (jnp.exp(last) * tile
                        + dot(bt, xdt * jnp.exp(last - lcol), nn))


def _prefill_call(x, bm, cm, bt, lt, dtt, l, nlive, *, c: int, head_dim: int,
                  interpret: bool):
    tp, width = x.shape
    groups, _, n = bm.shape
    hpg = lt.shape[2]
    gw = width // groups  # lanes of a group's heads
    rows = gw // LANES
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(groups, tp // c),
        in_specs=[
            pl.BlockSpec((c, gw), lambda g, i, nl: (i, g)),
            pl.BlockSpec((None, c, n), lambda g, i, nl: (g, i, 0)),
            pl.BlockSpec((None, c, n), lambda g, i, nl: (g, i, 0)),
            pl.BlockSpec((None, n, c), lambda g, i, nl: (g, 0, i)),
            pl.BlockSpec((None, c, hpg), lambda g, i, nl: (g, i, 0)),
            pl.BlockSpec((None, c, hpg), lambda g, i, nl: (g, i, 0)),
            pl.BlockSpec((None, hpg, c), lambda g, i, nl: (g, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((c, gw), lambda g, i, nl: (i, g)),
            pl.BlockSpec((rows, n, LANES), lambda g, i, nl: (g, 0, 0)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_prefill_kernel, head_dim=head_dim),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((groups * rows, n, LANES), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="ssm_prefill",  # the operation's name in a trace
    )(nlive, x, bm, cm, bt, lt, dtt, l)


def _prefill_dense(x, bm, cm, dt, l, c: int):
    """The chunked form in plain ``jax.numpy`` (the kernel's reference and
    the CPU's body): a scan over the chunks, the state [H, P, N] its carry.
    x [T, H, P], bm / cm [T, G, N], dt [T, H], l [T, H] (the running log decay
    from each chunk's start), all float32."""
    t, h, p = x.shape
    groups, n = bm.shape[1:]
    hpg = h // groups
    tri = jnp.arange(c)[None, :] <= jnp.arange(c)[:, None]
    ein = functools.partial(jnp.einsum, precision=HIGHEST)

    def chunk(s, xs):
        xc, bc, cc, dtc, lc = xs
        bh, ch = jnp.repeat(bc, hpg, axis=1), jnp.repeat(cc, hpg, axis=1)
        decay = jnp.exp(jnp.where(
            tri[:, :, None], lc[:, None, :] - lc[None, :, :], -jnp.inf))
        xdt = xc * dtc[:, :, None]
        among = ein("ihn,jhn,ijh,jhp->ihp", ch, bh, decay, xdt)
        before = jnp.exp(lc)[:, :, None] * ein("ihn,hpn->ihp", ch, s)
        to_end = jnp.exp(lc[-1][None] - lc)  # [c, H]
        s = (jnp.exp(lc[-1])[:, None, None] * s
             + ein("jhp,jhn->hpn", xdt * to_end[:, :, None], bh))
        return s, among + before

    cut = lambda v: v.reshape(t // c, c, *v.shape[1:])
    s, y = jax.lax.scan(chunk, jnp.zeros((h, p, n), F32),
                        (cut(x), cut(bm), cut(cm), cut(dt), cut(l)))
    return y.reshape(t, h, p), to_layout(s)


def ssm_prefill(
    x: jax.Array,  # [T, H, P]: the convolution's output, a head's channels
    bm: jax.Array,  # [T, G, N]
    cm: jax.Array,  # [T, G, N]
    dt: jax.Array,  # [T, H] float32, > 0 (softplus taken)
    a: jax.Array,  # [H] float32, < 0
    n: jax.Array | None = None,  # int32 scalar: the first ``n`` tokens are
    #   real (None: all T).  A padded position decays nothing and adds
    #   nothing: the state is the one AT THE TRUE LENGTH
    chunk: int = 128,
) -> tuple[jax.Array, jax.Array]:
    """One row's T tokens from an empty state.  Returns (y [T, H, P] in x's
    dtype, state [R, N, 128] float32).  ``chunk`` tokens at a time; the
    kernel walks only the chunks that hold a real token (the outputs of the
    others are zeros)."""
    t, h, p = x.shape
    groups, ns = bm.shape[1:]
    c = chunk
    tp = -(-t // c) * c
    n = jnp.asarray(t if n is None else n, jnp.int32)
    real = (jnp.arange(tp) < n)[:, None]
    pad = lambda v: jnp.pad(v, ((0, tp - t),) + ((0, 0),) * (v.ndim - 1))
    x, bm, cm = pad(x), pad(bm), pad(cm)
    dt = jnp.where(real, pad(dt.astype(F32)), 0.0)
    l = jnp.cumsum((dt * a.astype(F32)).reshape(tp // c, c, h), axis=1)
    l = l.reshape(tp, h)
    mode = dispatch.attention_mode()
    dispatch.record("ssm_prefill", mode, (tp, h, p, ns, c))
    if mode == "fallback":
        y, s = _prefill_dense(
            x.astype(F32), bm.astype(F32), cm.astype(F32), dt, l, c)
        return y[:t].astype(x.dtype), s
    hpg = h // groups
    by_group = lambda v: jnp.transpose(  # [T, H] -> [G, T, heads of a group]
        v.reshape(tp, groups, hpg), (1, 0, 2))
    turned = lambda v: jnp.transpose(v, (1, 0, 2))  # [T, G, N] -> [G, T, N]
    lt = by_group(l)
    y, s = _prefill_call(
        x.reshape(tp, h * p), turned(bm), turned(cm),
        jnp.transpose(bm, (1, 2, 0)), lt, by_group(dt),
        jnp.transpose(lt, (0, 2, 1)), (-(-n // c)).reshape(1), c=c,
        head_dim=p, interpret=mode == "interpret")
    return y.reshape(tp, h, p)[:t], s


def recurrence(x, bm, cm, dt, a):
    """The same operator token by token, in float32 (what the tests hold the
    two above to): x [T, H, P], bm / cm [T, G, N], dt [T, H], a [H] ->
    (y [T, H, P], the last state [H, P, N])."""
    _, h, p = x.shape
    hpg = h // bm.shape[1]

    def step(s, xs):
        xt, bt, ct, dtt = xs
        bh, ch = jnp.repeat(bt, hpg, axis=0), jnp.repeat(ct, hpg, axis=0)
        s = (jnp.exp(dtt * a)[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bh[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, ch, precision=HIGHEST)

    s, y = jax.lax.scan(
        step, jnp.zeros((h, p, bm.shape[-1]), F32),
        (x.astype(F32), bm.astype(F32), cm.astype(F32), dt.astype(F32)))
    return y, s
