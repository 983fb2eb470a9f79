"""Gated DeltaNet layers whose rows hold a float32 state BESIDE a page pool
for gated attention layers, and small experts behind a gated shared expert
(Qwen3-Next): an admission's chunked scan (a triangle solved a chunk) and a
decode step's recurrence, served FROM the pool through the batcher's own PAGED
programs, against the plain reference (the recurrence token by token) on the
CPU with ``qwen3-next-tiny`` in float32: two periods of (gdn, gdn, gdn, attn),
2 key heads and 4 value heads of 128 x 128, attention heads of 64 of which 16
dims rotate, 32 experts of which 8 are held, 4 a token.  Logits are compared,
never sampled tokens.

Tolerance ``ATOL`` 3e-4 on logits of about unit size: float32 end to end on
both sides, so what differs is the order of summation (a chunk's triangle and
the state between chunks against one step a token; pairs grouped by expert
against a loop over the experts); the readings are 2e-5 to 6e-5.  The state
held in bfloat16 moves the same logits by 3e-3 and more
(``test_a_bfloat16_state_fails_the_tolerance``), the wrong models by 1 and
more."""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.core.observability import METRICS
from distributed_llms_tpu.models import kv_cache, layers, model as model_lib
from distributed_llms_tpu.models.presets import get_preset
from distributed_llms_tpu.models.reference import qwen3_next
from distributed_llms_tpu.ops import gdn
from distributed_llms_tpu.runtime import batcher as B
from tools.reference_check import reference_cfg

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ATOL = 3e-4
PAGE = 16
S = 512  # the row length the tests serve: 32 pages of 16
STEPS = 8


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset("qwen3-next-tiny")
    return cfg, model_lib.init_params(jax.random.key(0), cfg)


def reference(params, cfg, tokens, held=True, **changed):
    tree = dict(params, layers=list(model_lib.hybrid_layers(params, cfg)))
    return np.asarray(qwen3_next.forward(
        tree, {**reference_cfg(cfg), **changed}, jnp.asarray(tokens),
        experts_held=(cfg.experts_offset, cfg.experts_held) if held else None,
        query_block=128))


def tokens_of(n, seed=0):
    return np.random.RandomState(seed).randint(0, 256, (n,)).astype(np.int32)


def pages_of(slot):
    """A row's page list: slot ``s`` owns pages 1 + 32 s .. (page 0 is the
    scratch page)."""
    per = S // PAGE
    return jnp.arange(1 + slot * per, 1 + (slot + 1) * per, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnums=(1,), donate_argnums=(2,))
def _admit(params, cfg, pool, slot, page_list, prompt, plen):
    """The batcher's paged admission, its logits handed out:
    ``admit_row_paged``'s own prefill and splice."""
    logits, row, counts = B._prefill_row(
        model_lib.forward, params, cfg, kv_cache.row_dtype(pool), S, prompt,
        plen)
    return (kv_cache.write_row(pool, page_list, row, slot), logits[0, 0],
            counts)


@functools.partial(jax.jit, static_argnums=(1,), donate_argnums=(2,))
def _step(params, cfg, pool, last, lens, active, tables):
    """The forward call of ``_decode_steps`` in the paged mode."""
    return model_lib.forward(
        params, cfg, last[:, None], positions=lens[:, None], cache=pool,
        cache_index=lens, kv_tables=tables,
        seq_lens=active.astype(jnp.int32), return_aux=True)


def _bf16(pool):
    return dataclasses.replace(
        pool, gdn_s=pool.gdn_s.astype(jnp.bfloat16).astype(jnp.float32))


def new_pool(cfg, slots=3):
    return kv_cache.make_pool(cfg, 1 + slots * (S // PAGE), PAGE, slots=slots)


def served(params, cfg, toks, n, bucket, slot=1, slots=3, pool=None,
           state=lambda c: c):
    """Logits [len(toks) - n + 1, V]: the last prompt position of a paged
    admission of the first ``n`` tokens at ``bucket``, then a decode step a
    further token, in batch slot ``slot``.  ``state`` is applied to the pool
    between the programs (a control's lower precision)."""
    if pool is None:
        pool = new_pool(cfg, slots)
    prompt = np.zeros((bucket,), np.int32)
    prompt[:n] = toks[:n]
    pool, first, _ = _admit(params, cfg, pool, jnp.int32(slot),
                            pages_of(slot), jnp.asarray(prompt), jnp.int32(n))
    out = [np.asarray(first)]
    active = jnp.zeros((slots,), bool).at[slot].set(True)
    tables = jnp.zeros((slots, S // PAGE), jnp.int32).at[slot].set(
        pages_of(slot))
    for j, t in enumerate(toks[n:]):
        pool = state(pool)
        logits, pool, _ = _step(
            params, cfg, pool,
            jnp.zeros((slots,), jnp.int32).at[slot].set(int(t)),
            jnp.zeros((slots,), jnp.int32).at[slot].set(n + j), active,
            tables)
        out.append(np.asarray(logits[slot, 0]))
    return np.stack(out), pool


def scan_inputs(t, hk=2, hv=4, d=128, seed=0, beta=None, g=None, agree=0.0):
    """q, k, v, g, beta of one layer as the layer makes them: q and k RAW
    silu outputs (the operators normalise them; any two keys share a positive
    part), beta a sigmoid, the decay from ``A`` in [1, 16] and ``dt`` log-uniform in [0.001,
    0.1].  ``beta`` / ``g``: every token's, where the triangle is to be at
    its worst; ``agree``: the share of a key that ALL keys of a head have in
    common (neighbouring keys that agree)."""
    ks = jax.random.split(jax.random.key(seed), 7)
    key = jax.nn.silu(jax.random.normal(ks[0], (t, hk, d)))
    key = (1 - agree) * key + agree * jnp.abs(
        jax.random.normal(ks[6], (1, hk, d)))
    dt = jnp.exp(jax.random.uniform(
        ks[3], (t, hv), minval=np.log(1e-3), maxval=np.log(0.1)))
    a = jax.random.uniform(ks[4], (hv,), minval=1.0, maxval=16.0)
    return (jax.nn.silu(jax.random.normal(ks[1], (t, hk, d))), key,
            jax.random.normal(ks[2], (t, hv, d)),
            -a * dt if g is None else jnp.full((t, hv), g),
            jax.nn.sigmoid(jax.random.normal(ks[5], (t, hv)))
            if beta is None else jnp.full((t, hv), beta))


# -- (a) the identity the design rests on ---------------------------------

CASES = [
    (100, None, False),  # ends inside its second chunk of 64
    (128, None, False),  # ends on a chunk's edge
    (400, None, False),  # crosses six chunk boundaries
    (512, 300, False),   # 212 padded positions behind 300 real ones
    (256, None, True),   # beta 0.999 and g -1e-4: the triangle at its worst
    (256, 130, True),    # the same, ending 2 tokens past a chunk's edge
]


@pytest.mark.parametrize("t,n,hard", CASES)
def test_the_chunked_form_is_the_recurrence(t, n, hard):
    x = scan_inputs(t, seed=t, **(dict(beta=0.999, g=-1e-4) if hard else {}))
    o, s = gdn.gdn_prefill(*x, None if n is None else jnp.int32(n), chunk=64)
    m = t if n is None else n
    want_o, want_s = gdn.recurrence(*(a[:m] for a in x))
    # (float32 both; outputs of about 0.1-0.3 and a state of about 1-4)
    np.testing.assert_allclose(o[:m], want_o, atol=3e-6)
    np.testing.assert_allclose(s, want_s, atol=3e-5)
    assert np.abs(np.asarray(want_o)).max() > 0.05
    assert np.abs(np.asarray(want_s)).max() > 0.5


def bfloat16_rows(x):
    """The same inputs as a served admission hands them over: q and k in
    bfloat16 (the route of 1 and 3 MXU passes).  v holds bfloat16 VALUES in
    float32, so that the operator's output comes back in float32 and can be
    read at the float32 cases' tolerances (the output takes v's dtype; in
    bfloat16 it is this one rounded: ``test_the_routes_...``)."""
    q, k, v, g, beta = x
    return (q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
            v.astype(jnp.bfloat16).astype(jnp.float32), g, beta)


@pytest.mark.parametrize("t,n,hard", CASES)
def test_the_chunked_form_is_the_recurrence_on_bfloat16_rows(t, n, hard):
    """The six cases above with q and k in bfloat16, against the recurrence
    run in float32 on the same bfloat16 values, AT THE SAME TOLERANCES: the
    products of 1 and 3 passes lost no bit."""
    x = bfloat16_rows(scan_inputs(
        t, seed=t, **(dict(beta=0.999, g=-1e-4) if hard else {})))
    o, s = gdn.gdn_prefill(*x, None if n is None else jnp.int32(n), chunk=64)
    m = t if n is None else n
    want_o, want_s = gdn.recurrence(*(a[:m] for a in x))
    assert o.dtype == jnp.float32
    np.testing.assert_allclose(o[:m], want_o, atol=3e-6)
    np.testing.assert_allclose(s, want_s, atol=3e-5)
    assert np.abs(np.asarray(want_o)).max() > 0.05
    assert np.abs(np.asarray(want_s)).max() > 0.5


def test_the_hard_triangle_on_bfloat16_rows():
    """The triangle of the test below with q and k in bfloat16: ``K K^T`` is
    ONE pass over the raw rows, scaled after, and the recurrence is met at
    the float32 case's tolerances."""
    x = bfloat16_rows(scan_inputs(128, seed=3, beta=0.999, g=-1e-4,
                                  agree=0.9))
    want_o, want_s = gdn.recurrence(*x)
    o, s = gdn.gdn_prefill(*x, chunk=64)
    np.testing.assert_allclose(o, want_o, atol=2e-4)  # (reads 6.6e-5)
    np.testing.assert_allclose(s, want_s, atol=5e-4)  # (reads 2.6e-4)


@pytest.mark.parametrize("mode", ["fallback", "interpret"])
@pytest.mark.parametrize("per_key", [1, 2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_routes_by_dtype_and_by_pairs_are_the_recurrence(
        monkeypatch, dtype, per_key, mode):
    """Both routes (float32 rows: every product at ``HIGHEST``; bfloat16
    rows: 1 and 3 passes), value heads singly (``hk == hv``) and in pairs
    (two and four a key head), the kernel's program and the plain body: 200
    tokens of which 150 are real, at the float32 tolerances.  And v in
    bfloat16 is the same output rounded once."""
    monkeypatch.setenv("DLT_RAGGED_DECODE", mode)
    x = scan_inputs(200, hk=2, hv=2 * per_key, seed=per_key)
    if dtype == jnp.bfloat16:
        x = bfloat16_rows(x)
    o, s = gdn.gdn_prefill(*x, jnp.int32(150))
    want_o, want_s = gdn.recurrence(*(a[:150] for a in x))
    np.testing.assert_allclose(o[:150], want_o, atol=3e-6)
    np.testing.assert_allclose(s, want_s, atol=3e-5)
    low = x[:2] + (x[2].astype(jnp.bfloat16),) + x[3:]
    o16, s16 = gdn.gdn_prefill(*low, jnp.int32(150))
    if dtype == jnp.bfloat16:  # (float32 v holds other values)
        np.testing.assert_array_equal(o16, o.astype(jnp.bfloat16))
        np.testing.assert_array_equal(s16, s)
    assert o16.dtype == jnp.bfloat16 and s16.dtype == jnp.float32


def _dots(fn, *args):
    """(lhs dtype, rhs dtype, lhs shape, rhs shape) of every ``dot_general``
    in ``fn``'s jaxpr, the kernel's body and the branches of its ``pl.when``
    included."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                a, b = (v.aval for v in eqn.invars)
                found.append((a.dtype, b.dtype, a.shape, b.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_the_kernel_asks_for_the_passes_its_operands_need(monkeypatch):
    """The route is the one reckoned, read off the kernel's own jaxpr (a
    grid step holds four chunks of 64).  bfloat16 q and k, a PAIR of value
    heads a key head: the only products of two float32 operands are the
    triangle's ten, ``[64 x 128] x [128 x 128]`` for both heads, ``T R`` and
    ``tril(Q K^T o G) V'``; ``K K^T`` and ``Q K^T`` are one product of
    bfloat16 operands each, ``[K ; Q] S`` and the state's update three (a
    float32 operand's pieces).  float32 q and k: no bfloat16 operand at
    all.  ``hk == hv``: the triangles singly, ``[64 x 64]``."""
    monkeypatch.setenv("DLT_RAGGED_DECODE", "interpret")
    f32, bf16 = jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)
    before = METRICS.snapshot()["counters"]
    took = lambda name: METRICS.snapshot()["counters"].get(
        name, 0) - before.get(name, 0)
    chunks = gdn._STEP // 64
    pair = _dots(gdn.gdn_prefill, *bfloat16_rows(scan_inputs(128)))
    assert took("ops.gdn_prefill.bf16_operands") == 1
    assert took("ops.gdn_prefill.paired_heads") == 1
    assert {d[:2] for d in pair} == {(f32, f32), (bf16, bf16)}
    full = [d[2:] for d in pair if d[0] == f32]
    assert sorted(full) == sorted(chunks * (
        10 * [((64, 128), (128, 128))] + 2 * [((64, 128), (128, 256))]))
    exact = [d[2:] for d in pair if d[0] == bf16]
    assert sorted(exact) == sorted(chunks * (
        2 * [((64, 128), (128, 128))]        # K K^T, Q K^T: once a head
        + 3 * [((128, 128), (128, 256))]     # [K ; Q] x the state's pieces
        + 3 * [((128, 64), (64, 256))]))     # K^T x the update's pieces
    plain = _dots(gdn.gdn_prefill, *scan_inputs(128))
    assert {d[:2] for d in plain} == {(f32, f32)}
    assert len(plain) == chunks * 16
    single = _dots(gdn.gdn_prefill, *bfloat16_rows(scan_inputs(128, hv=2)))
    assert sorted(d[2:] for d in single if d[0] == f32) == sorted(
        chunks * (10 * [((64, 64), (64, 64))]
                  + 2 * [((64, 64), (64, 128))]))
    assert took("ops.gdn_prefill.bf16_operands") == 2
    assert took("ops.gdn_prefill.paired_heads") == 2


def test_the_triangle_is_solved_by_blocks_and_not_by_one_product():
    """Keys of a head that agree to nine parts in ten, beta 0.999 and no
    decay: ``I + A`` is nearly the all-ones triangle, whose powers reach
    ``binomial(63, 31)``.  The served form (blocks of 16, merged) stays the
    recurrence and inverts the triangle to float32's rounding; the product
    over the whole chunk, equal in exact arithmetic, does not."""
    x = scan_inputs(128, seed=3, beta=0.999, g=-1e-4, agree=0.9)
    want_o, want_s = gdn.recurrence(*x)
    o, s = gdn.gdn_prefill(*x, chunk=64)
    # (outputs of 0.14, a state of 1: readings 6e-5 and 1.7e-4)
    np.testing.assert_allclose(o, want_o, atol=2e-4)
    np.testing.assert_allclose(s, want_s, atol=5e-4)
    # the first chunk's triangle of value head 0, as ``_chunk`` makes it
    k = gdn._unit(x[1][:64, 0])
    a = jnp.tril(0.999 * (k @ k.T) * jnp.exp(
        -1e-4 * (jnp.arange(64)[:, None] - jnp.arange(64)[None, :])), -1)
    left = lambda inv: np.abs(np.asarray(
        (jnp.eye(64) + a) @ inv - jnp.eye(64))).max()
    assert left(gdn._unit_lower_inverse(a)) < 2e-3  # (reads 3e-4)
    assert not left(gdn._unit_lower_inverse(a, whole=True)) < 1.0


def test_a_state_is_whole_tiles_as_the_recurrence_writes_it():
    assert gdn.state_shape(32, 128, 128) == (32, 128, 128)
    assert gdn.state_bytes(32, 128, 128) == 2_097_152


# -- (b) the batcher's paged programs against the reference ----------------

@pytest.mark.parametrize("n,bucket", [
    (40, 64),     # under one chunk of 64
    (390, 512),   # crosses six chunk boundaries, 6 tokens past the sixth
    (256, 256),   # fills its bucket exactly, and its last chunk
])
def test_admission_then_decode_steps_against_the_reference(tiny, n, bucket):
    cfg, params = tiny
    toks = tokens_of(n + STEPS, seed=n)
    got, _ = served(params, cfg, toks, n, bucket)
    want = reference(params, cfg, toks)[n - 1:]
    assert np.abs(got - want).max() < ATOL
    assert np.abs(want).max() > 1.0  # (logits of about unit size)


def test_a_plain_forward_is_the_reference(tiny):
    cfg, params = tiny
    toks = tokens_of(150, seed=5)
    got, _ = model_lib.forward(params, cfg, jnp.asarray(toks)[None])
    assert np.abs(np.asarray(got[0]) - reference(params, cfg, toks)).max() \
        < ATOL


def test_a_quarter_of_a_head_rotates_and_the_rest_carries_no_position(tiny):
    """``rotary_pct`` 0.25 in the hybrid family's attention (what Qwen3-Next
    asks at 0.25 of 256, here of 64): ``models.model._attention`` against the
    reference's attention, a layer alone; and with every dim rotated the same
    layer is another function."""
    cfg, params = tiny
    p = next(l for l in model_lib.hybrid_layers(params, cfg) if "attn" in l)
    u = jax.random.normal(jax.random.key(3), (1, 40, cfg.hidden_size))
    call = model_lib.call_of((1, 40))
    got, _ = model_lib._attention(u, p["attn"], cfg, True, call, None)
    with jax.default_matmul_precision("highest"):
        want = qwen3_next.attention(u[0], p["attn"], reference_cfg(cfg))
        full = qwen3_next.attention(
            u[0], p["attn"], {**reference_cfg(cfg), "rotary_pct": 1.0})
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    assert np.abs(np.asarray(full - want)).max() > 0.05
    assert int(cfg.head_dim_ * cfg.rotary_pct) == 16
    real = get_preset("qwen3-next-ep4")
    assert int(real.head_dim_ * real.rotary_pct) == 64


# -- (c) state and taps are those at the true length -----------------------

def test_a_padded_admission_leaves_state_and_taps_of_its_true_length(tiny):
    """140 tokens in a bucket of 256 (four chunks, the third of 12 real
    tokens, the fourth of padding alone) against the same 140 tokens at a
    bucket they fill but for 4: a padded position decays nothing (g 0) and
    corrects nothing (beta 0), whatever the pad token's own projections are,
    and the taps end at the last real token.  (To float32's rounding: the
    projections in front of the scan are matmuls of another shape at another
    bucket.  State at the bucket's end would differ by 0.1 and more.)"""
    cfg, params = tiny
    toks = tokens_of(140, seed=7)
    _, padded = served(params, cfg, toks, 140, 256)
    _, tight = served(params, cfg, toks, 140, 144)
    for f in ("gdn_s", "gdn_conv"):
        a, b = np.asarray(getattr(padded, f)), np.asarray(getattr(tight, f))
        assert np.abs(a[:, 1]).max() > 0.5
        np.testing.assert_allclose(a, b, atol=1e-5)


# -- (d) a slot's leftovers never reach the next row -----------------------

def test_a_slot_that_held_a_long_row_serves_a_short_one_as_a_fresh_one(tiny):
    cfg, params = tiny
    long_, short = tokens_of(390 + STEPS, seed=1), tokens_of(9 + STEPS, seed=4)
    _, used = served(params, cfg, long_, 390, 512)
    again, _ = served(params, cfg, short, 9, 16, pool=used)
    fresh, _ = served(params, cfg, short, 9, 16)
    np.testing.assert_array_equal(again, fresh)


# -- (e) rows side by side --------------------------------------------------

def test_two_rows_of_unlike_length_do_not_move_each_other(tiny):
    cfg, params = tiny
    a, b = tokens_of(200 + STEPS, seed=8), tokens_of(20 + STEPS, seed=9)
    alone_a, _ = served(params, cfg, a, 200, 256, slot=0)
    alone_b, _ = served(params, cfg, b, 20, 32, slot=2)
    pool = new_pool(cfg)
    tables = jnp.zeros((3, S // PAGE), jnp.int32)
    for slot, toks, n, bucket, alone in (
            (0, a, 200, 256, alone_a), (2, b, 20, 32, alone_b)):
        prompt = np.zeros((bucket,), np.int32)
        prompt[:n] = toks[:n]
        pool, first, counts = _admit(
            params, cfg, pool, jnp.int32(slot), pages_of(slot),
            jnp.asarray(prompt), jnp.int32(n))
        np.testing.assert_array_equal(np.asarray(first), alone[0])
        # behind the experts' six: the scan's tokens, chunks, row-steps
        assert [int(x) for x in counts[6:]] == [n, -(-n // 64), 0]
        tables = tables.at[slot].set(pages_of(slot))
    active = jnp.asarray([True, False, True])
    for j in range(STEPS):
        logits, pool, counts = _step(
            params, cfg, pool,
            jnp.asarray([a[200 + j], 0, b[20 + j]], jnp.int32),
            jnp.asarray([200 + j, 0, 20 + j], jnp.int32), active, tables)
        np.testing.assert_allclose(logits[0, 0], alone_a[1 + j], atol=2e-6)
        np.testing.assert_allclose(logits[2, 0], alone_b[1 + j], atol=2e-6)
        assert [int(x) for x in counts[6:]] == [0, 0, 2]
    # the slot that did not decode kept its (empty) state and taps
    assert not np.asarray(pool.gdn_s[:, 1]).any()
    assert not np.asarray(pool.gdn_conv[:, 1]).any()


# -- (f) the kernels against the jax.numpy operators ------------------------

def test_the_scan_kernels_in_interpreter_mode_are_the_dense_operator(
        monkeypatch):
    x = scan_inputs(301, seed=11)

    def both(fn):
        out = {}
        for mode in ("fallback", "interpret"):
            monkeypatch.setenv("DLT_RAGGED_DECODE", mode)
            out[mode] = fn()
        return out["fallback"], out["interpret"]

    before = METRICS.snapshot()["counters"]
    dense, kernel = both(lambda: gdn.gdn_prefill(
        *(a[:300] for a in x), n=jnp.int32(260)))
    np.testing.assert_allclose(dense[0][:260], kernel[0][:260], atol=2e-6)
    np.testing.assert_allclose(dense[1], kernel[1], atol=5e-6)
    # a decode step for rows 0 and 2 of three, in layer 1 of two
    states = jnp.zeros((2, 3, 4, 128, 128)).at[1, 2].set(dense[1])
    states = states.at[0].set(1.0)
    live = jnp.asarray([True, False, True])
    rows = [jnp.broadcast_to(a[260], (3, *a.shape[1:])) for a in x]
    dense, kernel = both(lambda: gdn.gdn_decode(*rows, states, 1, live))
    np.testing.assert_allclose(dense[0], kernel[0], atol=2e-6)
    np.testing.assert_allclose(dense[1], kernel[1], atol=2e-6)
    # row 2 stepped from the state at 260 tokens: token 261 of the recurrence
    keep = np.r_[0:260, 260]
    want, _ = gdn.recurrence(*(a[keep] for a in x))
    np.testing.assert_allclose(kernel[0][2], want[260], atol=3e-6)
    np.testing.assert_array_equal(kernel[1][0], states[0])  # layer 0 whole
    np.testing.assert_array_equal(kernel[1][1, 1], states[1, 1])  # not live
    after = METRICS.snapshot()["counters"]
    took = lambda name: after.get(name, 0) - before.get(name, 0)
    for op in ("gdn_prefill", "gdn_decode"):
        assert took(f"ops.dispatch.{op}.interpret") == 1
        assert took(f"ops.dispatch.{op}.fallback") == 1
    # float32 rows, two value heads a key head: both traces paired the heads
    # and neither took the bfloat16 operands' route
    assert took("ops.gdn_prefill.paired_heads") == 2
    assert took("ops.gdn_prefill.bf16_operands") == 0


# -- (g) the share ties to the model ----------------------------------------

def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer(tiny):
    """One expert layer of the reference over all 32 experts against its four
    shares of 8 plus the GATED shared expert ONCE; and the served layer,
    which holds share 0, is that share."""
    cfg, params = tiny
    p = next(model_lib.hybrid_layers(params, cfg))["mlp"]
    full = model_lib.init_params(
        jax.random.key(0), dataclasses.replace(cfg, experts_held=None))
    whole = next(model_lib.hybrid_layers(full, cfg))["mlp"]
    rc = reference_cfg(cfg)
    u = jax.random.normal(jax.random.key(2), (40, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        uncut = qwen3_next.experts(u, whole, rc)
        shares = [qwen3_next.experts(
            u, {**whole, "experts": jax.tree.map(
                lambda w: w[8 * i: 8 * i + 8], whole["experts"])}, rc,
            experts_held=(8 * i, 8), shared=False) for i in range(4)]
        once = qwen3_next.experts(u, whole, rc, experts_held=(0, 0))
        ungated = qwen3_next.experts(
            u, whole, {**rc, "shared_gate": False}, experts_held=(0, 0))
    np.testing.assert_allclose(sum(shares) + once, uncut, atol=2e-5)
    assert all(np.abs(np.asarray(s)).max() > 0.05 for s in shares)
    assert np.abs(np.asarray(ungated - once)).max() > 0.05
    # the served layer: share 0 of ITS OWN stacks, without the shared expert
    y, stats = layers.moe_dropless(
        u[None], params["blocks"]["moe"], cfg, layer=0)
    with jax.default_matmul_precision("highest"):
        want = qwen3_next.experts(u, p, rc, experts_held=(0, 8), shared=False)
    np.testing.assert_allclose(y[0], want, atol=2e-5)
    assert int(stats[0]) == 40 * 4 and 0 < int(stats[4]) < 40 * 4


# -- (h) every refusal, by name and with its reason; a pool IS accepted ------

def _batcher(cfg, params, **kw):
    kw = {"paged_pages": 40, "page_size": 8, **kw}
    return B.ContinuousBatcher(cfg, params, batch_slots=2, max_len=64,
                               chunk_steps=2, eos_id=-1, **kw)


REFUSED = {
    "prefix_cache": (dict(prefix_cache=True), "snapshots the state"),
    "kv_bits": (dict(kv_bits=8), "never quantized"),
    "host_pages": (dict(host_pages=4), "parks"),
    "prefill_chunk": (dict(prefill_chunk=16), "from bite to bite"),
    "token_budget": (dict(token_budget=32), "from bite to bite"),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_what_cannot_carry_the_state_refuses_at_start_up(tiny, name):
    cfg, params = tiny
    kw, reason = REFUSED[name]
    with pytest.raises(ValueError, match=(
            f"{name} is not supported.*recurrent state beside their pages"
            f".*Gated DeltaNet.*{reason}")):
        _batcher(cfg, params, **kw)


def test_it_is_served_from_the_pool_and_from_nothing_else(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="from the page pool only.*"
                       "delta-rule layers' state beside it; pass paged_pages"):
        _batcher(cfg, params, paged_pages=None)
    b = _batcher(cfg, params)
    assert b.paged and isinstance(b.cache, kv_cache.HybridCache)
    assert b.cache.k.shape[0] == 2 and b.cache.gdn_s.shape[:2] == (6, 2)


def test_speculative_and_a_mesh_refuse(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match=(
            "speculative is not supported.*roll the state back")):
        _batcher(cfg, params, draft_params=params, draft_cfg=cfg)
    with pytest.raises(ValueError, match=(
            "mesh is not supported.*no sharding rule")):
        kv_cache.refuse_unpaged_state(cfg, paged_pages=8, mesh=True)


@pytest.mark.parametrize("call,name", [
    (lambda b: b.register_prefix("sys", [1, 2, 3]), "named_prefix"),
    (lambda b: b.submit_kv_import([], None, None, None), "kv_import"),
    (lambda b: b.submit_kv_export([1, 2], None), "kv_export"),
    (lambda b: b.export_prefix_pages([1, 2]), "kv_export"),
])
def test_snapshots_and_shipments_refuse_by_name(tiny, call, name):
    cfg, params = tiny
    with pytest.raises(ValueError, match=(
            f"{name} is not supported.*recurrent state beside their pages")):
        call(_batcher(cfg, params))


def test_the_engine_refuses_sessions_and_padded_generate(tiny):
    from distributed_llms_tpu.core.config import RuntimeConfig
    from distributed_llms_tpu.runtime.engine import InferenceEngine

    cfg = dataclasses.replace(tiny[0], vocab_size=512)
    params = model_lib.init_params(jax.random.key(0), cfg)
    eng = InferenceEngine(cfg, RuntimeConfig(), params)
    with pytest.raises(ValueError, match="sessions is not supported"):
        eng.start_session(["hello"])
    with pytest.raises(ValueError, match="padded_generate is not supported"):
        eng.generate_text(["hello", "hi there"])
    b = eng.continuous_batcher(batch_slots=2, max_len=64, paged_pages=24,
                               page_size=8)
    rid = b.submit("hello there, hello", max_new_tokens=3)
    assert len(b.run()[rid]) == 3


def test_a_row_cannot_be_continued_from_a_state(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="no prefix to continue from"):
        model_lib.forward(
            params, cfg, jnp.zeros((1, 8), jnp.int32),
            cache=kv_cache.init_cache(cfg, 1, 64), cache_index=jnp.int32(8))


def test_the_config_refuses_what_the_layer_cannot_lay_out():
    cfg = get_preset("qwen3-next-tiny")
    for changed in (dict(gdn_key_dim=64), dict(gdn_value_heads=3),
                    dict(gdn_chunk=48), dict(gdn_chunk=8),
                    dict(layer_types=("gdn", "conv") * 4)):
        with pytest.raises(ValueError, match="gated delta-rule layers"):
            dataclasses.replace(cfg, **changed)
    with pytest.raises(ValueError, match="attn_out_gate and moe_shared_gate"):
        dataclasses.replace(get_preset("k-exaone-tiny"), attn_out_gate=True)


# -- (i) the controls --------------------------------------------------------

def test_a_bfloat16_state_fails_the_tolerance(tiny):
    """The state rounded to bfloat16 between the admission and every decode
    step, everything else as served: outside ``ATOL`` by an order of
    magnitude, so the tolerance would catch a precision below the
    configuration's."""
    cfg, params = tiny
    toks = tokens_of(390 + STEPS, seed=390)
    want = reference(params, cfg, toks)[389:]
    sound, _ = served(params, cfg, toks, 390, 512)
    lower, _ = served(params, cfg, toks, 390, 512, state=_bf16)
    assert np.abs(sound - want).max() < ATOL
    assert np.abs(lower[1:] - want[1:]).max() > 10 * ATOL
    # (the admission's own logits do not pass through the stored state)
    np.testing.assert_array_equal(lower[0], sound[0])


@pytest.mark.parametrize("changed", [
    {"attn_gate": False}, {"shared_gate": False}, {"beta_one": True},
    {"gate_first": True}, {"num_experts_per_token": 3}])
def test_a_wrong_model_is_far_outside_the_tolerance(tiny, changed):
    """The attention's gate left off, the shared expert's gate left off,
    ``beta`` fixed at 1, the gate before the norm, 3 picks for 4."""
    cfg, params = tiny
    toks = tokens_of(48, seed=2)
    right = reference(params, cfg, toks)
    assert np.abs(reference(params, cfg, toks, **changed) - right).max() \
        > 500 * ATOL


# -- the batcher, the folds, the bytes ---------------------------------------

def test_the_batcher_serves_it_from_the_pool_and_counts(tiny):
    cfg, params = tiny
    toks = tokens_of(390 + STEPS, seed=390)
    before = METRICS.snapshot()["counters"]
    b = B.ContinuousBatcher(cfg, params, batch_slots=3, max_len=S,
                            chunk_steps=4, eos_id=-1, paged_pages=100,
                            page_size=PAGE)
    state = 3 * 6 * (gdn.state_bytes(4, 128, 128) + 3 * 1024 * 4)
    assert METRICS.snapshot()["gauges"]["batcher.gdn_state_bytes"] == state
    rid = b.submit([int(t) for t in toks[:390]], STEPS)
    out = b.run()[rid]
    want = reference(params, cfg, np.concatenate(
        [toks[:390], np.asarray(out[:-1], np.int32)]))[389:]
    assert out == [int(np.argmax(r)) for r in want]
    lps = [float(jax.nn.log_softmax(jnp.asarray(r))[t])
           for r, t in zip(want, out)]
    np.testing.assert_allclose(b.result_logprobs[rid], lps, atol=ATOL)
    after = METRICS.snapshot()["counters"]
    took = lambda name: after.get(name, 0) - before.get(name, 0)
    assert took("gdn.admit.tokens") == 390
    assert took("gdn.admit.chunks") == 7  # of 64, the seventh holds 6 tokens
    assert took("gdn.decode.row_steps") == STEPS - 1
    assert took("moe.routed_pairs") == 8 * 4 * (390 + STEPS - 1)
    assert 0 < took("moe.held_pairs") < took("moe.routed_pairs")
    assert took("moe.layer_passes") == 8 * STEPS


def test_the_published_pattern_folds_into_scanned_runs():
    cfg = get_preset("qwen3-next-ep4")
    assert (len(cfg.gdn_layers), len(cfg.attn_layers),
            cfg.ffn_kinds.count("moe")) == (9, 3, 12)
    assert cfg.attn_layers == (3, 7, 11)  # full_attention_interval 4
    unit = (("gdn", "moe"),) * 3 + (("attn", "moe"),)
    # one scanned run: 12 blocks are not 12 unrolled layers
    assert model_lib.layer_runs(cfg) == ((unit, 3),)
    assert model_lib.layer_runs(get_preset("qwen3-next-tiny")) == ((unit, 2),)


def test_bytes_of_the_real_preset():
    """ISSUE 59's arithmetic against the leaves ``init_params_quantized``
    would build, ``page_bytes`` and ``make_pool`` under ``jax.eval_shape``:
    4.83 G expert weights and 421.5 M others in int8 blocks, 1.236 GB of
    state at 64 slots, 2.038 GB of pages."""
    from distributed_llms_tpu.checkpoint import quantize as quant_lib

    cfg = get_preset("qwen3-next-ep4")
    shapes = jax.eval_shape(lambda k: model_lib.init_params(k, cfg),
                            jax.random.key(0))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    name = lambda path: "/".join(str(p.key) for p in path)
    sizes = {name(p): (int(np.prod(sd.shape)), sd) for p, sd in flat}
    quant = {n: c for n, (c, sd) in sizes.items()
             if n.startswith("blocks/") and quant_lib.leaf_plan(n, sd)[0]}
    experts = sum(c for n, c in quant.items() if "/experts/" in n)
    assert experts == 12 * 128 * 3_145_728 == 4_831_838_208
    others = sum(quant.values()) - experts
    # 9 delta-rule layers' W_qkvz and W_out, 3 attention layers' four, 12
    # shared experts: exactly 12 x 2,048 x 17,152 (``matmuls_per_layer``)
    assert others == 9 * 33_554_432 + 3 * 27_262_976 + 12 * 3_145_728
    assert others == 421_527_552 == 12 * 2048 * 17_152
    assert sizes["blocks/gdn/w_qkvz"][1].shape == (9, 2048, 12_288)
    assert sizes["blocks/gdn/w_ba"][1].shape == (9, 2048, 64)
    assert sizes["blocks/attn/wq"][1].shape == (3, 2048, 8192)
    assert sizes["blocks/moe/experts/w_gate_up"][1].shape == (
        12, 128, 2048, 1024)
    assert sizes["blocks/moe/experts/w_down"][1].shape == (12, 128, 512, 2048)
    assert sizes["blocks/moe/shared_gate"][1].shape == (12, 2048)
    routers = sizes["blocks/moe/router"][0] * 4
    assert round(routers / 1e9, 3) == 0.050
    table = sizes["embed/wte"][0] * 2
    assert sizes["embed/wte"][1].shape == (37_984, 2048)
    assert 4 * 37_984 == 151_936 and round(table / 1e9, 3) == 0.156
    assert round(experts * 1.03125 / 1e9, 3) == 4.983
    assert round(others * 1.03125 / 1e9, 3) == 0.435
    pool = jax.eval_shape(
        lambda: kv_cache.make_pool(cfg, 5184, 64, slots=64))
    assert pool.gdn_s.shape == (9, 64, 32, 128, 128)
    assert pool.gdn_s.dtype == jnp.float32
    assert pool.gdn_conv.shape == (9, 64, 3, 8192)
    assert pool.gdn_conv.dtype == jnp.bfloat16
    state = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in (pool.gdn_s, pool.gdn_conv))
    assert state == 64 * 9 * (2_097_152 + 49_152)
    assert round(state / 1e9, 3) == 1.236
    assert kv_cache.page_bytes(cfg, 64) == 393_216 == 64 * 6_144
    # (both KV heads of 256 in ONE row of 512 lanes: a [.., 2, 256] pool is
    # tiled a lane group at a time and the paged kernel's view of it would
    # be a copy of the pool a layer a step: ops.decode_attn.pool_head_shape)
    assert pool.k.shape == (3, 5184, 64, 1, 512)
    assert round(5184 * 393_216 / 1e9, 3) == 2.038
    assert (2_097_152 + 49_152) * 9 // 6_144 == 3_144  # tokens a state is worth


def test_the_quantized_tree_keeps_what_sets_the_memory_float():
    from distributed_llms_tpu.checkpoint.quantize import QuantizedTensor

    cfg = dataclasses.replace(get_preset("qwen3-next-tiny"), dtype="bfloat16")
    p = model_lib.init_params_quantized(jax.random.key(0), cfg, 8)["blocks"]
    for leaf in ("A_log", "dt_bias"):
        assert p["gdn"][leaf].dtype == jnp.float32
    for leaf in ("taps", "norm_w", "w_ba"):
        assert p["gdn"][leaf].dtype == jnp.bfloat16
    assert p["moe"]["router"].dtype == jnp.float32
    assert p["moe"]["shared_gate"].dtype == jnp.bfloat16
    assert p["attn"]["q_norm"].dtype == jnp.bfloat16
    # dt in [0.001, 0.1] and A in [1, 16], as ``ssm_leaf`` draws them
    dt = jax.nn.softplus(p["gdn"]["dt_bias"])
    assert 0.001 <= float(dt.min()) and float(dt.max()) <= 0.1001
    a = jnp.exp(p["gdn"]["A_log"])
    assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0
    for path in (("gdn", "w_qkvz"), ("gdn", "out_proj"), ("attn", "wq"),
                 ("attn", "wk"), ("attn", "wv"), ("attn", "wo"),
                 ("moe", "shared", "w_gate"), ("moe", "shared", "w_up"),
                 ("moe", "shared", "w_down"), ("moe", "experts", "w_gate_up"),
                 ("moe", "experts", "w_down")):
        leaf = p
        for key in path:
            leaf = leaf[key]
        assert isinstance(leaf, QuantizedTensor), path
    # (stored [L, N, K]: 4 heads of [query 64 | gate 64])
    assert p["attn"]["wq"].data.shape == (2, 4 * 2 * 64, 256)
    assert p["moe"]["experts"]["w_gate_up"].block_axis == -2


def test_the_benchmarks_reference_is_this_one_byte_for_byte():
    with open(os.path.join(ROOT, "distributed_llms_tpu", "models",
                           "reference", "qwen3_next.py"), "rb") as f:
        mine = f.read()
    with open(os.path.join(ROOT, "benchmark", "reference", "qwen3_next.py"),
              "rb") as f:
        assert f.read() == mine
    assert b"distributed_llms_tpu" not in mine.replace(
        b"distributed_llms_tpu/models", b"")
