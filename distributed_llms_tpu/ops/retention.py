"""Power retention of degree 2 (Brumby's layer kind "ret"): a row's whole
memory in a layer is one float32 state a key/value head, and no key.

For one key/value head and its ``g`` query heads, with a scalar gate a token
``log g_t <= 0`` and ``G_ij = exp(sum_{j < s <= i} log g_s)``:

- as attention: ``a_ij = (q_i . k_j)^2 G_ij`` for ``j <= i``,
  ``o_i = sum_j a_ij v_j / sum_j a_ij``;
- as a recurrence: ``(q . k)^2 = phi(q) . phi(k)`` with ``phi`` the products
  of pairs of a head's 128 entries, so ``S_t = g_t S_{t-1} + phi(k_t)
  v_t^T``, ``Z_t = g_t Z_{t-1} + k_t k_t^T`` and ``o_t = phi(q_t)^T S_t /
  q_t^T Z_t q_t``.

How the products lie in the state (the layout is this module's and nobody
else's): ``(q . k)^2 = sum_{a, b} q_a q_b k_a k_b`` is a sum over the
symmetric 128 x 128 square, walked here by its 65 CYCLIC DIAGONALS: diagonal
d holds the 128 products ``x_a x_{(a + d) mod 128}``.  Diagonals 1..63 each
hold 128 distinct unordered pairs, which the square holds twice (weight 2 on
the key's side); diagonal 0 is the squares and diagonal 64 holds each of its
64 pairs twice already (weight 1).  So ``phi_q(x)[d, a] = x_a x_{a+d}``
[65, 128], ``phi_k = w_d phi_q``, and the state of a key/value head is
``S[d, v, a]`` [65, 128, 128] float32: 8,320 rows of 128 values where the
symmetric count is 8,256 (0.8% over it, every tile whole 128-lane
registers, a diagonal made by one lane rotation), against 16,384 for the
full square.  The normaliser needs no products: ``phi(q) . z = q^T Z q``
with ``Z = sum_j G_j k_j k_j^T`` a plain [128, 128] matrix.

Two operators, each a Pallas kernel with a plain ``jax.numpy`` body behind
it (``DLT_RAGGED_DECODE``: kernel on a TPU, ``interpret`` for the tests,
``fallback`` the CPU's default), each under its own name in a trace and in
the dispatch record (``ops.dispatch.retention_prefill.*`` /
``retention_decode.*``):

- :func:`retention_prefill`: one row's T tokens from an empty state, in
  chunks: the attention form inside a chunk, the state between chunks.
- :func:`retention_decode`: one recurrence step for every batch slot,
  against the whole stack of every layer's states, updated where it lies
  (the stack is the decode scans' carry and is never copied): the kernel
  leaves it in HBM and passes the layer's heads through VMEM by its own
  copies, reads and writes by turns.

Both kernels are given q, k, v and the gates as they are and make a
diagonal's products themselves, one lane rotation a diagonal: nothing the
size of phi(q) or phi(k) is ever built in HBM.  :func:`phi_q` and
:func:`phi_k` are the plain bodies' and the tests'.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch

HD = 128  # a head's width: the layout is written for whole 128-lane rows
DIAGS = HD // 2 + 1  # cyclic diagonals of the symmetric square: 65
# The decode kernel's loop over a head's diagonals unrolls 5: 13 take twice as
# long to trace for the same time on the chip, 1 does not hide under the copies
_RUN = 5
# The decode call's VMEM: 52 MiB for its heads' buffers, and the rest of 96
# claimed on purpose: into what the call leaves free XLA copies the next
# matmuls' whole scale stacks, 36 MB a layer beside the kernel's turns
_VMEM_BUDGET = 52 * 1024 * 1024
_VMEM_LIMIT = 96 * 1024 * 1024
F32 = jnp.float32


def state_shapes(kv_heads: int) -> tuple[tuple, tuple]:
    """(state, normaliser) shapes of ONE row in ONE layer, float32:
    [KVH, 65, 128, 128] and [KVH, 128, 128]."""
    return (kv_heads, DIAGS, HD, HD), (kv_heads, HD, HD)


def state_bytes(kv_heads: int) -> int:
    """Bytes of one row's state and normaliser in one layer."""
    return kv_heads * (DIAGS + 1) * HD * HD * 4


def _key_weights() -> jax.Array:
    """w_d [65]: 1 for diagonals 0 and 64, 2 between."""
    d = jnp.arange(DIAGS)
    return jnp.where((d == 0) | (d == DIAGS - 1), 1.0, 2.0).astype(F32)


def _turn(kv_heads: int) -> int:
    """Key/value heads of a row that the decode kernel reads, and then
    writes, in one turn: as many as divide the row's heads and fit
    ``_VMEM_BUDGET`` three turns deep (one read, one computed, one
    written).  Longer turns lose less to the change of direction: 675 GB/s
    at one head a turn, 688 at two, 695 at four (PERF.md, PR 54)."""
    head = DIAGS * HD * HD * 4
    return max(t for t in range(1, kv_heads + 1)
               if kv_heads % t == 0 and 3 * t * head <= _VMEM_BUDGET)


def phi_q(x: jax.Array) -> jax.Array:
    """[..., 128] -> [..., 65, 128]: x_a x_{(a + d) mod 128}."""
    idx = (jnp.arange(HD)[None, :] + jnp.arange(DIAGS)[:, None]) % HD
    return x[..., None, :] * x[..., idx]


def phi_k(x: jax.Array) -> jax.Array:
    """:func:`phi_q` with the key's weights: phi_q(q) . phi_k(k) summed
    over both axes is (q . k)^2."""
    return phi_q(x) * _key_weights()[:, None]


# ---------------------------------------------------------------------------
# A decode step
# ---------------------------------------------------------------------------

def _decode_kernel(layer_ref, x_ref, s_hbm, so_hbm, o_ref, buf, sem, acc_ref,
                   ph_ref, vb_ref, *, groups: int, turn: int):
    """Every (row, key/value head) of one layer: S <- g S + v phi_k^T where
    it lies, and the readout of the ``groups`` query heads.  The stack stays
    in HBM (``s_hbm`` and ``so_hbm`` are one array) and the kernel copies
    ``turn`` heads of a row at a time through three buffers, reads and
    writes BY TURNS and never together: HBM gives a stream that is read and
    written at once 630-660 GB/s on this chip and 690-695 by turns (reads
    alone 757, writes alone 657; PERF.md, PR 54), and every vector pass
    hides under either.  While a turn's heads are computed, the turn
    before is written and then the turn after is read."""
    layer = layer_ref[0]
    _, b, kvh = s_hbm.shape[:3]
    per_row = kvh // turn
    turns = b * per_row

    def row_head(t, j):  # of head ``j`` of turn ``t``
        return t // per_row, (t % per_row) * turn + j

    def copies(t, slot, into_vmem: bool):
        for j in range(turn):
            there, here = (layer, *row_head(t, j)), buf.at[slot, j]
            yield (pltpu.make_async_copy(s_hbm.at[there], here, sem.at[slot])
                   if into_vmem else pltpu.make_async_copy(
                       here, so_hbm.at[there], sem.at[slot]))

    def start(t, slot, into_vmem):
        for dma in copies(t, slot, into_vmem):
            dma.start()

    def wait(t, slot, into_vmem):
        for dma in copies(t, slot, into_vmem):
            dma.wait()

    start(0, 0, True)
    wait(0, 0, True)

    def one_turn(t, slot):
        before, after = (slot + 2) % 3, (slot + 1) % 3
        more = t + 1 < turns

        @pl.when(t > 0)
        def _():
            start(t - 1, before, False)

        @pl.when(jnp.logical_and(t == 0, more))
        def _():  # nothing is being written yet
            start(1, after, True)

        def head(j, carry):
            @pl.when(jnp.logical_and(
                j == turn // 2, jnp.logical_and(t > 0, more)))
            def _():  # the turn's middle: the copies change direction
                wait(t - 1, before, False)
                start(t + 1, after, True)

            _decode_head(x_ref, buf.at[slot, j], o_ref, acc_ref, ph_ref,
                         vb_ref, *row_head(t, j), groups)
            return carry

        jax.lax.fori_loop(0, turn, head, 0)

        @pl.when(jnp.logical_and(t > 0, jnp.logical_not(more)))
        def _():  # the last turn: nothing to read, so nothing to take turns
            wait(t - 1, before, False)

        @pl.when(more)
        def _():
            wait(t + 1, after, True)

        return after

    last = (jax.lax.fori_loop(0, turns, one_turn, 0) + 2) % 3
    start(turns - 1, last, False)
    wait(turns - 1, last, False)


def _decode_head(x_ref, s_ref, o_ref, acc_ref, ph_ref, vb_ref, r, h,
                 groups: int):
    """One (row, key/value head) on its state in VMEM, ``s_ref`` [65, 128,
    128], updated in place: the 65 diagonals in order, a loop over runs of
    ``_RUN`` unrolled, the readout accumulated a [128, 128] tile a query
    head and summed over the lanes at the end.  ``x_ref[r, h]`` is the
    row's head as it is, a row of 128 lanes each: the query heads, then k,
    v and the gate (every lane the gate).  A run's products are made here,
    one lane rotation of all the rows a diagonal: ``ph_ref[i]`` holds ``x_a
    x_{a + d}`` of every row, the key's row with its weight (phi_q's and
    phi_k's float32 products to the bit), and ``vb_ref`` v down the rows,
    transposed once a head."""
    kr, vr, gr = groups, groups + 1, groups + 2  # the rows of k, v, g in x
    acc_ref[...] = jnp.zeros_like(acc_ref)
    vb_ref[...] = jnp.broadcast_to(x_ref[r, h, vr:vr + 1, :], (HD, HD)).T
    x = x_ref[r, h]  # [rows, 128]
    key = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) == kr
    g = x_ref[r, h, gr:gr + 1, :]  # [1, 128], every lane the gate

    def run(n, carry):
        for i in range(_RUN):
            d = n * _RUN + i
            w = jnp.where((d == 0) | (d == DIAGS - 1), 1.0, 2.0)
            ph_ref[i] = (x * pltpu.roll(x, (HD - d) % HD, 1)
                         * jnp.where(key, w, 1.0))
        vb = vb_ref[...]  # [128 v, 128]: v down the rows, in every lane
        for i in range(_RUN):
            d = n * _RUN + i
            new = g * s_ref[d] + vb * ph_ref[i, kr:kr + 1, :]
            s_ref[d] = new
            for j in range(groups):
                acc_ref[j] += new * ph_ref[i, j:j + 1, :]
        return carry

    jax.lax.fori_loop(0, DIAGS // _RUN, run, 0)
    ones = jnp.ones((8, HD), F32)
    for j in range(groups):
        # sum over the lanes (a), laid along the lanes (v): ones @ acc^T
        num = jax.lax.dot_general(
            ones, acc_ref[j], (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=F32)
        o_ref[r, h, j:j + 1, :] = num[0:1]


def _decode_call(states, layer, x, *, groups: int, interpret: bool,
                 turn: int | None = None):
    _, b, kvh = states.shape[:3]
    rows = x.shape[2]
    turn = _turn(kvh) if turn is None else turn
    whole = lambda shape: pl.BlockSpec(shape, lambda i, l: (0,) * len(shape))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[whole(x.shape), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY), whole((b, kvh, 8, HD))],
        scratch_shapes=[
            pltpu.VMEM((3, turn, DIAGS, HD, HD), F32),  # three turns' heads
            pltpu.SemaphoreType.DMA((3,)),
            pltpu.VMEM((groups, HD, HD), F32),  # a head's readout
            pltpu.VMEM((_RUN, rows, HD), F32),  # a run's products
            pltpu.VMEM((HD, HD), F32),  # v down the rows
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, groups=groups, turn=turn),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(states.shape, states.dtype),
            jax.ShapeDtypeStruct((b, kvh, 8, HD), F32),
        ],
        # operands: layer, x, states -> the stack is updated where it lies
        # (only the heads of ``layer`` are copied in and out)
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="retention_decode",  # the operation's name in a trace
    )(layer, x, states)


def retention_decode(
    q: jax.Array,  # [B, H, 128], normed and rotated
    k: jax.Array,  # [B, KVH, 128]
    v: jax.Array,  # [B, KVH, 128]
    log_g: jax.Array,  # [B, KVH] float32, <= 0
    states: jax.Array,  # [L, B, KVH, 65, 128, 128] float32: every layer's
    norms: jax.Array,  # [L, B, KVH, 128, 128] float32
    layer: jax.Array | int,
    live: jax.Array | None = None,  # [B] bool: rows that take a step (None:
    #   all).  Any other row's state stays as it is, bit for bit
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One recurrence step a row: ``S <- g S + phi_k(k) v^T``, ``Z <- g Z +
    k k^T``, ``o = phi_q(q)^T S / q^T Z q``, all in float32 whatever the
    activations' dtype.  Returns (o [B, H, 128] in q's dtype, states',
    norms'): the stacks with layer ``layer`` advanced, the state by the
    kernel where it lies."""
    b, h, _ = q.shape
    kvh = k.shape[1]
    groups = h // kvh
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    qf = q.astype(F32).reshape(b, kvh, groups, HD)
    kf, vf = k.astype(F32), v.astype(F32)
    g = jnp.exp(log_g.astype(F32))
    if live is not None:  # a row that does not decode: g = 1, nothing added
        g = jnp.where(live[:, None], g, 1.0)
        kf = jnp.where(live[:, None, None], kf, 0.0)
    z = (g[..., None, None] * norms[layer[0]]
         + kf[..., :, None] * kf[..., None, :])
    den = jnp.einsum("bhga,bhac,bhgc->bhg", qf, z, qf)
    norms = jax.lax.dynamic_update_slice_in_dim(norms, z[None], layer[0], 0)
    mode = dispatch.attention_mode()
    dispatch.record("retention_decode", mode, (b, kvh, groups))
    if mode == "fallback":
        pq, pk = phi_q(qf), phi_k(kf)  # [B, KVH, G, 65, 128], [.., 65, 128]
        s = (g[..., None, None, None] * states[layer[0]]
             + vf[:, :, None, :, None] * pk[:, :, :, None, :])
        num = jnp.einsum("bhgda,bhdva->bhgv", pq, s)
        states = jax.lax.dynamic_update_slice_in_dim(
            states, s[None], layer[0], 0)
    else:  # the kernel makes its own products: q, k, v and the gate as rows
        pad = -(groups + 3) % 8
        x = jnp.concatenate([
            qf, kf[:, :, None], vf[:, :, None],
            jnp.broadcast_to(g[..., None, None], (b, kvh, 1 + pad, HD))],
            axis=2)
        states, num = _decode_call(
            states, layer, x, groups=groups, interpret=mode == "interpret")
        num = num[:, :, :groups]
    o = num / jnp.where(den == 0.0, 1.0, den)[..., None]
    return o.reshape(b, h, HD).astype(q.dtype), states, norms


# ---------------------------------------------------------------------------
# An admission
# ---------------------------------------------------------------------------

def _precision(dtype):
    """Float32 activations (a check's mechanism leg, the tests) contract at
    full precision; bfloat16 ones in one MXU pass, accumulated in float32."""
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == F32
            else jax.lax.Precision.DEFAULT)


def _prefill_kernel(nlive_ref, q_ref, k_ref, v_ref, vwt_ref, kwt_ref,
                    lcol_ref, lrow_ref, o_ref, s_ref, z_ref, *,
                    groups: int, c: int):
    """One (key/value head, chunk of ``c`` tokens): the attention form
    among the chunk's tokens, the state of the chunks before for the rest,
    then the state moved to the chunk's end.  ``s_ref`` / ``z_ref`` are the
    head's state and normaliser, resident across the chunk axis."""
    ci = pl.program_id(1)
    dt = q_ref.dtype
    prec = _precision(dt)
    dot = functools.partial(jax.lax.dot_general, precision=prec,
                            preferred_element_type=F32)
    nt = (((1,), (1,)), ((), ()))  # x @ y^T
    nn = (((1,), (0,)), ((), ()))

    @pl.when(ci == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    @pl.when(ci >= nlive_ref[0])
    def _():  # a chunk of padding alone: nothing to score, nothing to add
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(ci < nlive_ref[0])
    def _():
        q2 = jnp.concatenate(
            [q_ref[:, j * HD:(j + 1) * HD] for j in range(groups)], axis=0)
        k, v = k_ref[...], v_ref[...]
        lcol, lrow = lcol_ref[0], lrow_ref[0]  # [c, 1], [1, c]
        # among the chunk's own tokens
        s = dot(q2, k, nt).reshape(groups, c, c)
        ii = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
        decay = jnp.exp(jnp.where(jj <= ii, lcol - lrow, -jnp.inf))
        a = (s * s * decay[None]).reshape(groups * c, c)
        den = jnp.sum(a, axis=-1, keepdims=True)
        num = dot(a.astype(dt), v, nn)
        # what the chunks before left, decayed to each token
        qf, kf = q2.astype(F32), k.astype(F32)
        zq = dot(q2, z_ref[0].astype(dt), nn)
        den_before = jnp.sum(zq * qf, axis=-1, keepdims=True)
        to_end = jnp.exp(lrow[:, c - 1:c])  # [1, 1]: the chunk's whole decay
        vwt, kwt = vwt_ref[0], kwt_ref[0]  # [128, c], decayed to the end

        def diagonal(d, num_before):
            shift = (HD - d) % HD
            sd = s_ref[0, d]  # [128 v, 128 a]
            pq = (qf * pltpu.roll(qf, shift, 1)).astype(dt)
            num_before = num_before + dot(pq, sd.astype(dt), nt)
            w = jnp.where((d == 0) | (d == DIAGS - 1), 1.0, 2.0)
            pk = (kf * pltpu.roll(kf, shift, 1) * w).astype(dt)
            s_ref[0, d] = to_end * sd + dot(vwt, pk, nn)
            return num_before

        num_before = jax.lax.fori_loop(
            0, DIAGS, diagonal, jnp.zeros((groups * c, HD), F32))
        z_ref[0] = to_end * z_ref[0] + dot(kwt, k, nn)
        e = jnp.exp(jnp.concatenate([lcol] * groups, axis=0))  # [g * c, 1]
        den = den + e * den_before
        out = (num + e * num_before) / jnp.where(den == 0.0, 1.0, den)
        for j in range(groups):
            o_ref[:, j * HD:(j + 1) * HD] = out[j * c:(j + 1) * c].astype(
                o_ref.dtype)


def _prefill_call(q, k, v, vwt, kwt, lcol, lrow, nlive, *, c: int,
                  interpret: bool):
    t, hw = q.shape
    kvh = k.shape[1] // HD
    groups = hw // HD // kvh
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(kvh, t // c),
        in_specs=[
            pl.BlockSpec((c, groups * HD), lambda h, i, n: (i, h)),
            pl.BlockSpec((c, HD), lambda h, i, n: (i, h)),
            pl.BlockSpec((c, HD), lambda h, i, n: (i, h)),
            pl.BlockSpec((1, HD, c), lambda h, i, n: (h, 0, i)),
            pl.BlockSpec((1, HD, c), lambda h, i, n: (h, 0, i)),
            pl.BlockSpec((1, c, 1), lambda h, i, n: (h, i, 0)),
            pl.BlockSpec((1, 1, c), lambda h, i, n: (h, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((c, groups * HD), lambda h, i, n: (i, h)),
            pl.BlockSpec((1, DIAGS, HD, HD), lambda h, i, n: (h, 0, 0, 0)),
            pl.BlockSpec((1, HD, HD), lambda h, i, n: (h, 0, 0)),
        ],
    )
    s_shape, z_shape = state_shapes(kvh)
    return pl.pallas_call(
        functools.partial(_prefill_kernel, groups=groups, c=c),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(s_shape, F32),
            jax.ShapeDtypeStruct(z_shape, F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=96 * 1024 * 1024),
        interpret=interpret,
        name="retention_prefill",  # the operation's name in a trace
    )(nlive, q, k, v, vwt, kwt, lcol, lrow)


def _prefill_dense(q, k, v, lg, c: int):
    """The chunked form in plain ``jax.numpy`` (the kernel's reference and
    the CPU's body): a scan over the chunks, the state its carry.  q [T, KVH,
    G, 128], k / v [T, KVH, 128], lg [T, KVH] float32, all float32."""
    t, kvh, groups, _ = q.shape
    n = t // c
    s0 = jnp.zeros(state_shapes(kvh)[0], F32)
    z0 = jnp.zeros(state_shapes(kvh)[1], F32)
    tri = jnp.arange(c)[None, :] <= jnp.arange(c)[:, None]

    def chunk(carry, xs):
        s, z = carry
        qc, kc, vc, lc = xs
        cum = jnp.cumsum(lc, axis=0)  # [c, KVH]
        diff = cum[:, None, :] - cum[None, :, :]  # [i, j, KVH]
        decay = jnp.exp(jnp.where(tri[:, :, None], diff, -jnp.inf))
        sc = jnp.einsum("ihgd,jhd->ijhg", qc, kc)
        a = sc * sc * decay[..., None]
        e = jnp.exp(cum)[:, :, None]  # [i, KVH, 1]
        num = (jnp.einsum("ijhg,jhv->ihgv", a, vc)
               + e[..., None] * jnp.einsum("ihgda,hdva->ihgv", phi_q(qc), s))
        den = (jnp.sum(a, axis=1)
               + e * jnp.einsum("ihga,hab,ihgb->ihg", qc, z, qc))
        w = jnp.exp(cum[-1][None] - cum)  # [j, KVH]: decay to the end
        to_end = jnp.exp(cum[-1])
        s = (to_end[:, None, None, None] * s
             + jnp.einsum("jhv,jhda->hdva", vc * w[..., None], phi_k(kc)))
        z = (to_end[:, None, None] * z
             + jnp.einsum("jha,jhb->hab", kc * w[..., None], kc))
        return (s, z), num / jnp.where(den == 0.0, 1.0, den)[..., None]

    cut = lambda x: x.reshape(n, c, *x.shape[1:])
    (s, z), o = jax.lax.scan(chunk, (s0, z0), (cut(q), cut(k), cut(v), cut(lg)))
    return o.reshape(t, kvh, groups, HD), s, z


def retention_prefill(
    q: jax.Array,  # [T, H, 128], normed and rotated
    k: jax.Array,  # [T, KVH, 128]
    v: jax.Array,  # [T, KVH, 128]
    log_g: jax.Array,  # [T, KVH] float32, <= 0
    n: jax.Array | None = None,  # int32 scalar: the first ``n`` tokens are
    #   real (None: all T).  A padded position adds nothing to the state
    #   and gates nothing: the state is the one AT THE TRUE LENGTH
    chunk: int = 256,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One row's T tokens from an empty state.  Returns (o [T, H, 128] in
    q's dtype, state [KVH, 65, 128, 128] float32, normaliser [KVH, 128,
    128] float32).  ``chunk`` tokens at a time: inside a chunk the attention
    form (a [chunk, chunk] score tile a head), between chunks the state;
    the kernel walks only the chunks that hold a real token."""
    t, h, _ = q.shape
    kvh = k.shape[1]
    groups = h // kvh
    unit = min(chunk, HD)
    tp = -(-t // unit) * unit
    c = min(chunk, tp)
    tp = -(-tp // c) * c
    n = jnp.asarray(t if n is None else n, jnp.int32)
    real = (jnp.arange(tp) < n)[:, None]
    pad = lambda x: jnp.pad(x, ((0, tp - t),) + ((0, 0),) * (x.ndim - 1))
    q, k, v = pad(q), pad(k), pad(v)
    lg = jnp.where(real, pad(log_g.astype(F32)), 0.0)
    k = jnp.where(real[:, :, None], k, jnp.zeros((), k.dtype))
    mode = dispatch.attention_mode()
    dispatch.record("retention_prefill", mode, (tp, h, c))
    if mode == "fallback":
        o, s, z = _prefill_dense(
            q.astype(F32).reshape(tp, kvh, groups, HD), k.astype(F32),
            v.astype(F32), lg, c)
        return o.reshape(tp, h, HD)[:t].astype(q.dtype), s, z
    cum = jnp.cumsum(lg.reshape(tp // c, c, kvh), axis=1)
    w = jnp.exp(cum[:, -1:] - cum).reshape(tp, kvh, 1)  # decay to chunk end
    turned = lambda x: jnp.transpose(  # [T, KVH, 128] -> [KVH, 128, T]
        (x.astype(F32) * w).astype(q.dtype), (1, 2, 0))
    cum = cum.reshape(tp, kvh).T  # [KVH, T]
    o, s, z = _prefill_call(
        q.reshape(tp, h * HD), k.reshape(tp, kvh * HD),
        v.reshape(tp, kvh * HD), turned(v), turned(k),
        cum[:, :, None], cum[:, None, :],
        (-(-n // c)).reshape(1), c=c, interpret=mode == "interpret")
    return o.reshape(tp, h, HD)[:t], s, z


def attention_form(q, k, v, log_g):
    """The same operator as attention, in float32 (what the tests hold the
    two above to): q [T, H, 128], k / v [T, KVH, 128], log_g [T, KVH] ->
    o [T, H, 128]."""
    t, h, _ = q.shape
    kvh = k.shape[1]
    qf = q.astype(F32).reshape(t, kvh, h // kvh, HD)
    cum = jnp.cumsum(log_g.astype(F32), axis=0)
    tri = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    decay = jnp.exp(jnp.where(
        tri[:, :, None], cum[:, None, :] - cum[None, :, :], -jnp.inf))
    sc = jnp.einsum("ihgd,jhd->ijhg", qf, k.astype(F32))
    a = sc * sc * decay[..., None]
    o = jnp.einsum("ijhg,jhv->ihgv", a, v.astype(F32)) / jnp.sum(
        a, axis=1)[..., None]
    return o.reshape(t, h, HD)
