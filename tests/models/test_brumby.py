"""Power retention of degree 2 in front of a SwiGLU, every layer (Brumby):
a row's whole memory a float32 state a key/value head and no key, an
admission's chunked scan and a decode step's recurrence, served WITHOUT a
pool through the batcher's own programs, against the plain reference (the
attention form) on the CPU with ``brumby-tiny`` in float32: heads of 128, so
that the state's 65 diagonals of 128 x 128 are real, 10 query heads over 2,
chunks of 64.  Logits are compared, never sampled tokens.

Tolerance ``ATOL`` 1e-4 on logits of about unit size: float32 end to end on
both sides, so what differs is the order of summation (a chunk's [64, 64]
tile and a state of 8,320 rows against one [T, T] matrix of weights a
head); the readings are 1e-5 to 4e-5.  The state held in bfloat16 moves the
same logits by 3e-3 to 1e-1 (``test_a_bfloat16_state_fails_the_tolerance``),
and the wrong models by 0.1 and more."""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.core.observability import METRICS
from distributed_llms_tpu.models import kv_cache, model as model_lib
from distributed_llms_tpu.models.presets import get_preset
from distributed_llms_tpu.models.reference import brumby
from distributed_llms_tpu.ops import retention
from distributed_llms_tpu.runtime import batcher as B
from tools.reference_check import reference_cfg

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ATOL = 1e-4
S = 256  # the row length the tests serve
STEPS = 8


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset("brumby-tiny")
    return cfg, model_lib.init_params(jax.random.key(0), cfg)


def reference(params, cfg, tokens, **changed):
    tree = dict(params, layers=list(model_lib.hybrid_layers(params, cfg)))
    return np.asarray(brumby.forward(
        tree, {**reference_cfg(cfg), **changed}, jnp.asarray(tokens),
        query_block=64))


def tokens_of(n, seed=0):
    return np.random.RandomState(seed).randint(0, 256, (n,)).astype(np.int32)


@functools.partial(jax.jit, static_argnums=(1,), donate_argnums=(2,))
def _admit(params, cfg, cache, slot, prompt, plen):
    """The batcher's admission, its logits handed out: ``admit_row``'s own
    prefill and splice."""
    logits, row, counts = B._prefill_row(
        model_lib.forward, params, cfg, cache.k.dtype, S, prompt, plen)
    return B._splice_row(cache, slot, row), logits[0, 0], counts


@functools.partial(jax.jit, static_argnums=(1,), donate_argnums=(2,))
def _step(params, cfg, cache, last, lens, active):
    """The forward call of ``_decode_steps`` in the contiguous mode."""
    mask = (jnp.arange(S)[None, :] <= lens[:, None])[:, None, None, :]
    return model_lib.forward(
        params, cfg, last[:, None], positions=lens[:, None], cache=cache,
        cache_index=lens, attn_mask=mask, seq_lens=active.astype(jnp.int32),
        return_aux=True)


def _bf16(cache):
    return dataclasses.replace(cache, **{
        f: getattr(cache, f).astype(jnp.bfloat16).astype(jnp.float32)
        for f in ("ret_s", "ret_z")})


def served(params, cfg, toks, n, bucket, slot=1, slots=3, cache=None,
           state=lambda c: c):
    """Logits [len(toks) - n + 1, V]: the last prompt position of an
    admission of the first ``n`` tokens at ``bucket``, then a decode step a
    further token, in batch slot ``slot``.  ``state`` is applied to the
    cache between the programs (a control's lower precision)."""
    if cache is None:
        cache = kv_cache.init_cache(cfg, slots, S)
    prompt = np.zeros((bucket,), np.int32)
    prompt[:n] = toks[:n]
    cache, first, _ = _admit(params, cfg, cache, jnp.int32(slot),
                             jnp.asarray(prompt), jnp.int32(n))
    out = [np.asarray(first)]
    active = jnp.zeros((slots,), bool).at[slot].set(True)
    for j, t in enumerate(toks[n:]):
        cache = state(cache)
        logits, cache, _ = _step(
            params, cfg, cache,
            jnp.zeros((slots,), jnp.int32).at[slot].set(int(t)),
            jnp.zeros((slots,), jnp.int32).at[slot].set(n + j), active)
        out.append(np.asarray(logits[slot, 0]))
    return np.stack(out), cache


# -- (a) the identity the design rests on ---------------------------------

@pytest.mark.parametrize("gates", [0.02, 0.7])
def test_the_recurrence_is_the_attention_form(gates):
    """phi(q) . phi(k) = (q . k)^2 over the 65 diagonals, and the state's
    recurrence, token by token, gives the attention form's outputs: with
    gates near 1 (a long memory: every chunk boundary matters) and near a
    half (random weights')."""
    ks = jax.random.split(jax.random.key(3), 4)
    t, h, kvh = 40, 4, 2
    q = jax.random.normal(ks[0], (t, h, 128))
    k = jax.random.normal(ks[1], (t, kvh, 128))
    v = jax.random.normal(ks[2], (t, kvh, 128))
    lg = -gates * jnp.abs(jax.random.normal(ks[3], (t, kvh)))
    dots = jnp.einsum("ta,ta->t", q[:, 0], k[:, 0])
    feats = jnp.einsum("tda,tda->t", retention.phi_q(q[:, 0]),
                       retention.phi_k(k[:, 0]))
    # (8,320 products of either sign that sum to a square: float32 leaves
    # a thousandth of their size, absolute)
    np.testing.assert_allclose(feats, dots ** 2, rtol=1e-5, atol=2e-3)
    want = retention.attention_form(q, k, v, lg)
    states = jnp.zeros((1, 1, kvh, 65, 128, 128))
    norms = jnp.zeros((1, 1, kvh, 128, 128))
    step = jax.jit(retention.retention_decode)
    for i in range(t):
        o, states, norms = step(
            q[i][None], k[i][None], v[i][None], lg[i][None], states, norms,
            jnp.int32(0))
        # (a lone early token's weight (q . k)^2 may be a thousandth of
        # |q|^2 |k|^2 = 128^2, and float32 leaves 1e-7 of THAT: the model's
        # own tests, with many tokens a sum, hold 1e-4)
        np.testing.assert_allclose(o[0], want[i], atol=1e-3, rtol=1e-3)
    # ... and the chunked scan leaves the same state.
    _, s, z = retention.retention_prefill(q, k, v, lg, chunk=16)
    np.testing.assert_allclose(s, states[0, 0], atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(z, norms[0, 0], atol=1e-4, rtol=1e-5)


# -- (b) the batcher's programs against the reference ----------------------

@pytest.mark.parametrize("n,bucket", [
    (40, 64),     # under one chunk of 64
    (131, 256),   # crosses two chunk boundaries, 3 tokens past the second
    (128, 128),   # fills its bucket exactly, and its last chunk
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


@pytest.mark.parametrize("changed", [
    {"degree": 3}, {"gated": False}, {"qk_norm": False}, {"rope": False}])
def test_a_wrong_model_is_far_outside_the_tolerance(tiny, changed):
    cfg, params = tiny
    toks = tokens_of(48, seed=2)
    right = reference(params, cfg, toks)
    assert np.abs(reference(params, cfg, toks, **changed) - right).max() \
        > 1000 * ATOL


# -- (c) the state is the one at the true length ---------------------------

def test_a_padded_admission_leaves_the_state_of_its_true_length(tiny):
    """70 tokens in a bucket of 128 (two chunks, the second 58 of padding)
    against the same 70 tokens at a bucket they fill but for 2: bit for
    bit, since a padded position gates nothing (log g 0) and adds nothing
    (k 0), whatever the pad token's own projections are."""
    cfg, params = tiny
    toks = tokens_of(70, seed=7)
    _, padded = served(params, cfg, toks, 70, 128)
    _, tight = served(params, cfg, toks, 70, 72)
    for f in ("ret_s", "ret_z"):
        a, b = np.asarray(getattr(padded, f)), np.asarray(getattr(tight, f))
        assert np.abs(a[:, 1]).max() > 0
        np.testing.assert_array_equal(a, b)


# -- (d) a slot's leftovers never reach the next row -----------------------

def test_a_slot_that_held_a_long_row_serves_a_short_one_as_a_fresh_one(tiny):
    cfg, params = tiny
    long_, short = tokens_of(131 + STEPS, seed=1), tokens_of(9 + STEPS, seed=4)
    _, used = served(params, cfg, long_, 131, 256)
    again, _ = served(params, cfg, short, 9, 16, cache=used)
    fresh, _ = served(params, cfg, short, 9, 16)
    np.testing.assert_array_equal(again, fresh)


# -- (e) rows side by side --------------------------------------------------

def test_two_rows_of_unlike_length_do_not_move_each_other(tiny):
    cfg, params = tiny
    a, b = tokens_of(100 + STEPS, seed=8), tokens_of(20 + STEPS, seed=9)
    alone_a, _ = served(params, cfg, a, 100, 128, slot=0)
    alone_b, _ = served(params, cfg, b, 20, 32, slot=2)
    cache = kv_cache.init_cache(cfg, 3, S)
    firsts = []
    for slot, toks, n, bucket in ((0, a, 100, 128), (2, b, 20, 32)):
        prompt = np.zeros((bucket,), np.int32)
        prompt[:n] = toks[:n]
        cache, first, _ = _admit(params, cfg, cache, jnp.int32(slot),
                                 jnp.asarray(prompt), jnp.int32(n))
        firsts.append(np.asarray(first))
    np.testing.assert_array_equal(firsts[0], alone_a[0])
    np.testing.assert_array_equal(firsts[1], alone_b[0])
    active = jnp.asarray([True, False, True])
    for j in range(STEPS):
        logits, cache, counts = _step(
            params, cfg, cache,
            jnp.asarray([a[100 + j], 0, b[20 + j]], jnp.int32),
            jnp.asarray([100 + j, 0, 20 + j], jnp.int32), active)
        np.testing.assert_allclose(logits[0, 0], alone_a[1 + j], atol=2e-6)
        np.testing.assert_allclose(logits[2, 0], alone_b[1 + j], atol=2e-6)
        # two rows stepped, holding 101 + j and 21 + j tokens
        assert [int(x) for x in counts] == [0, 0, 2, 122 + 2 * j]
    # the slot that did not decode kept its (empty) state, bit for bit
    assert not np.asarray(cache.ret_s[:, 1]).any()


# -- (f) the kernels against the jax.numpy operator -------------------------

def test_the_kernels_in_interpreter_mode_are_the_dense_operator(monkeypatch):
    ks = jax.random.split(jax.random.key(11), 4)
    t, h, kvh = 200, 4, 2
    q = jax.random.normal(ks[0], (t, h, 128))
    k = jax.random.normal(ks[1], (t, kvh, 128))
    v = jax.random.normal(ks[2], (t, kvh, 128))
    lg = -0.05 * jnp.abs(jax.random.normal(ks[3], (t, kvh)))

    def both(fn):
        out = {}
        for mode in ("fallback", "interpret"):
            monkeypatch.setenv("DLT_RAGGED_DECODE", mode)
            out[mode] = fn()
        return out["fallback"], out["interpret"]

    before = METRICS.snapshot()["counters"]
    dense, kernel = both(lambda: retention.retention_prefill(
        q, k, v, lg, n=jnp.int32(150), chunk=64))
    for x, y in zip(dense, kernel):
        np.testing.assert_allclose(x[:150], y[:150], atol=2e-5, rtol=1e-5)
    # a decode step for rows 0 and 2 of three, in layer 1 of two
    states = jnp.zeros((2, 3, kvh, 65, 128, 128)).at[1, 2].set(dense[1])
    norms = jnp.zeros((2, 3, kvh, 128, 128)).at[1, 2].set(dense[2])
    live = jnp.asarray([True, False, True])
    rows = lambda x: jnp.broadcast_to(x[150], (3, *x.shape[1:]))
    dense, kernel = both(lambda: retention.retention_decode(
        rows(q), rows(k), rows(v), rows(lg), states, norms, 1, live))
    for x, y in zip(dense[1:], kernel[1:]):  # the states
        np.testing.assert_allclose(x, y, atol=1e-4, rtol=1e-5)
    # (row 0 starts from an empty state: one token's weight, see above)
    np.testing.assert_allclose(dense[0], kernel[0], atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(
        kernel[0][2], retention.attention_form(
            q[:151], k[:151], v[:151], lg[:151])[150], atol=2e-5)
    np.testing.assert_array_equal(kernel[1][0], states[0])  # layer 0 whole
    np.testing.assert_array_equal(kernel[1][1, 1], states[1, 1])  # not live
    after = METRICS.snapshot()["counters"]
    took = lambda name: after.get(name, 0) - before.get(name, 0)
    assert took("ops.dispatch.retention_prefill.interpret") == 1
    assert took("ops.dispatch.retention_decode.interpret") == 1
    assert took("ops.dispatch.retention_prefill.fallback") == 1
    assert took("ops.dispatch.retention_decode.fallback") == 1


@pytest.fixture(scope="module")
def decode_step():
    """One step of the decode kernel under ``interpret``: 3 rows, 5 query
    heads a key/value head over 2, layer 1 of two.  Row 0 steps from a
    random non-zero state, row 1 is not live, row 2 steps from an empty
    state.  XLA's CPU backend contracts ``g S + v pk`` to a fused
    multiply-add in the interpreter's loop and not in the plain expression,
    so row 0's values are chosen to make both products exact in float32
    (powers of two in the state, k and v of 8 significant bits: a product of
    the key's is still a product of two of ITS entries, weighed) and the sum
    rounded once either way; row 2, with nothing to add to, has every bit of
    k and v."""
    ks = jax.random.split(jax.random.key(51), 7)
    b, kvh, groups = 3, 2, 5
    short = lambda x: x.at[0].set(
        x[0].astype(jnp.bfloat16).astype(jnp.float32))
    states = jnp.exp2(jnp.round(jax.random.normal(
        ks[4], (2, b, kvh, 65, 128, 128)))) * jax.random.rademacher(
        ks[6], (2, b, kvh, 65, 128, 128), jnp.float32)
    given = dict(
        q=jax.random.normal(ks[0], (b, kvh * groups, 128)),
        k=short(jax.random.normal(ks[1], (b, kvh, 128))),
        v=short(jax.random.normal(ks[2], (b, kvh, 128))),
        log_g=-jnp.abs(jax.random.normal(ks[3], (b, kvh))),
        states=states.at[:, 2].set(0.0),
        norms=jax.random.normal(ks[5], (2, b, kvh, 128, 128)),
        layer=1, live=jnp.asarray([True, False, True]))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DLT_RAGGED_DECODE", "interpret")
        return given, retention.retention_decode(**given)


@pytest.mark.parametrize("diagonal", range(retention.DIAGS))
def test_the_decode_kernel_makes_phi_ks_products_to_the_bit(
        decode_step, diagonal):
    """The kernel is given q, k, v and the gate and rotates the products out
    of them itself: on every one of the 65 diagonals (0 and 64, weight 1,
    among them) the state it writes is ``g S + v phi_k(k)^T`` with ``phi_k``
    in ``jax.numpy``, bit for bit; a row that is not live and the other
    layer keep theirs."""
    x, (_, new, _) = decode_step
    old = x["states"][:, :, :, diagonal]  # [L, B, KVH, 128 v, 128 a]
    pk = retention.phi_k(x["k"])[:, :, diagonal]  # [B, KVH, 128 a]
    want = (jnp.exp(x["log_g"])[:, :, None, None] * old[1]
            + x["v"][:, :, :, None] * pk[:, :, None, :])
    want = jnp.where(x["live"][:, None, None, None], want, old[1])
    assert np.abs(np.asarray(want[0])).min() > 0  # (row 0: a real sum)
    np.testing.assert_array_equal(new[1, :, :, diagonal], want)
    np.testing.assert_array_equal(new[0, :, :, diagonal], old[0])


def test_the_decode_kernels_numerator_is_phi_q_against_the_state_it_wrote(
        decode_step):
    x, (o, new, norms) = decode_step
    b, kvh = x["k"].shape[:2]
    qf = x["q"].reshape(b, kvh, -1, 128)
    pq, s = (np.asarray(retention.phi_q(qf), np.float64),
             np.asarray(new[1], np.float64))
    num = np.einsum("bhgda,bhdva->bhgv", pq, s)
    # the wrapper's own denominator, which it divided the numerator by
    den = jnp.einsum("bhga,bhac,bhgc->bhg", qf, norms[1], qf)
    got = np.asarray(o.reshape(b, kvh, -1, 128) * den[..., None])
    # relative to a head's largest numerator where the state holds many
    # tokens' worth (rows 0 and 1), to the 8,320 x 128 terms' sizes summed
    # where it holds one (row 2: its numerator is v (q . k)^2, a hundredth
    # of |q|^2 |k|^2 |v|, and float32 leaves 1e-7 of THAT)
    err = np.abs(got - num)
    assert (err / np.abs(num).max(axis=-1, keepdims=True))[:2].max() < 1e-5
    terms = np.einsum("bhgda,bhdva->bhgv", np.abs(pq), np.abs(s))
    assert (err / terms.max(axis=-1, keepdims=True)).max() < 1e-6


@pytest.fixture(scope="module")
def decode_turns(decode_step):
    """The same step through ``_decode_call`` a key/value head a turn (six
    turns: each head read, computed and written on its own) and at the turn
    the module derives for itself, a row's two heads together: (states,
    numerator) each."""
    given, _ = decode_step
    got = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DLT_RAGGED_DECODE", "interpret")
        call = retention._decode_call
        for turn in (1, None):
            def spy(*args, turn=turn, **kw):
                got[turn] = call(*args, turn=turn, **kw)
                return got[turn]
            mp.setattr(retention, "_decode_call", spy)
            retention.retention_decode(**given)
    return got


@pytest.mark.parametrize("part", ["state", "numerator"])
@pytest.mark.parametrize("row", ["live", "not live", "live, empty state"])
def test_the_decode_kernel_writes_the_same_bits_whatever_its_turn(
        decode_turns, row, part):
    """How many heads the kernel copies in a turn, and so which of its three
    buffers a head passes through and beside which copies it is computed,
    moves no bit: the state and the 5 query heads' numerators are equal for
    a row that steps, one that is not live and one that steps from
    nothing."""
    assert retention._turn(2) == 2  # (the module's own is another than 1)
    r = ["live", "not live", "live, empty state"].index(row)
    (s1, n1), (s, n) = decode_turns[1], decode_turns[None]
    if part == "state":
        np.testing.assert_array_equal(s[:, r], s1[:, r])
        assert np.asarray(s[1, r]).any() or row != "live"
    else:
        assert n.shape == (3, 2, 8, 128)
        np.testing.assert_array_equal(n[r, :, :5], n1[r, :, :5])
        assert np.asarray(n[r, :, :5]).any()


@pytest.mark.parametrize("kv_heads,turn", [(8, 4), (2, 2), (6, 3), (7, 1)])
def test_a_turn_is_the_most_heads_of_a_row_that_fit_three_deep(
        kv_heads, turn):
    """Brumby's 8 heads go four a turn: 17 MB read, then 17 MB written,
    three turns' buffers 51 MB of the call's 96 MiB."""
    assert retention._turn(kv_heads) == turn
    head = retention.DIAGS * 128 * 128 * 4
    assert 3 * turn * head <= retention._VMEM_BUDGET < retention._VMEM_LIMIT


# -- (h) the control --------------------------------------------------------

def test_a_bfloat16_state_fails_the_tolerance(tiny):
    """The state (and its normaliser) rounded to bfloat16 between the
    admission and every decode step, everything else as served: outside
    ``ATOL`` by more than an order of magnitude, so the tolerance would
    catch a precision below the configuration's."""
    cfg, params = tiny
    toks = tokens_of(131 + STEPS, seed=131)
    want = reference(params, cfg, toks)[130:]
    sound, _ = served(params, cfg, toks, 131, 256)
    lower, _ = served(params, cfg, toks, 131, 256, state=_bf16)
    assert np.abs(sound - want).max() < ATOL
    assert np.abs(lower[1:] - want[1:]).max() > 10 * ATOL
    # (the admission's own logits do not pass through the stored state)
    np.testing.assert_array_equal(lower[0], sound[0])


# -- the batcher, the reference's copy --------------------------------------

def test_the_batcher_serves_it_without_a_pool_and_counts(tiny):
    cfg, params = tiny
    toks = tokens_of(131 + STEPS, seed=131)
    before = METRICS.snapshot()["counters"]
    b = B.ContinuousBatcher(cfg, params, batch_slots=3, max_len=S,
                            chunk_steps=4, eos_id=-1)
    assert not b.paged and isinstance(b.cache, kv_cache.HybridCache)
    assert b.cache.k.size == 0
    assert METRICS.snapshot()["gauges"]["batcher.ret_state_bytes"] == \
        3 * 2 * retention.state_bytes(cfg.num_kv_heads)
    rid = b.submit([int(t) for t in toks[:131]], STEPS)
    out = b.run()[rid]
    want = reference(params, cfg, np.concatenate(
        [toks[:131], np.asarray(out[:-1], np.int32)]))[130:]
    assert out == [int(np.argmax(r)) for r in want]
    lps = [float(jax.nn.log_softmax(jnp.asarray(r))[t])
           for r, t in zip(want, out)]
    np.testing.assert_allclose(b.result_logprobs[rid], lps, atol=ATOL)
    after = METRICS.snapshot()["counters"]
    took = lambda name: after.get(name, 0) - before.get(name, 0)
    assert took("ret.admit.tokens") == 131
    assert took("ret.admit.chunks") == 3  # of 64, the third holds 3 tokens
    assert took("ret.decode.row_steps") == STEPS - 1
    assert took("ret.decode.resident_tokens") == sum(
        132 + j for j in range(STEPS - 1))
    assert took("batcher.admit.self_attention") == 1
    assert took("batcher.prefix_cache.miss_tokens") == 131


def test_the_benchmarks_reference_is_this_one_byte_for_byte():
    with open(os.path.join(ROOT, "distributed_llms_tpu", "models",
                           "reference", "brumby.py"), "rb") as f:
        mine = f.read()
    with open(os.path.join(ROOT, "benchmark", "reference", "brumby.py"),
              "rb") as f:
        assert f.read() == mine
    assert b"distributed_llms_tpu" not in mine.replace(
        b"distributed_llms_tpu/models", b"")


def test_the_quantized_tree_keeps_the_gate_in_the_models_dtype():
    from distributed_llms_tpu.checkpoint.quantize import QuantizedTensor

    cfg = dataclasses.replace(get_preset("brumby-tiny"), dtype="bfloat16")
    p = model_lib.init_params_quantized(jax.random.key(0), cfg, 8)["blocks"]
    assert p["ret"]["wg"].dtype == jnp.bfloat16
    assert p["ret"]["wg"].shape == (2, 64, 2)
    assert isinstance(p["ret"]["wq"], QuantizedTensor)
    assert isinstance(p["dense"]["w_down"], QuantizedTensor)
    assert model_lib.layer_runs(cfg) == (((("ret", "dense"),), 2),)


# -- (g) every refusal, by name and with its reason --------------------------

def _batcher(cfg, params, **kw):
    return B.ContinuousBatcher(cfg, params, batch_slots=2, max_len=64,
                               chunk_steps=2, eos_id=-1, **kw)


REFUSED = {
    "paged_pages": (dict(paged_pages=12, page_size=8), "no key and no value"),
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
            f"{name} is not supported.*recurrent state and no key.*{reason}")):
        _batcher(cfg, params, **kw)


def test_speculative_and_a_mesh_refuse(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match=(
            "speculative is not supported.*roll the state back")):
        _batcher(cfg, params, draft_params=params, draft_cfg=cfg)
    with pytest.raises(ValueError, match=(
            "mesh is not supported.*no sharding rule")):
        kv_cache.refuse_unpaged_state(cfg, mesh=True)


@pytest.mark.parametrize("call,name", [
    (lambda b: b.register_prefix("sys", [1, 2, 3]), "named_prefix"),
    (lambda b: b.submit_kv_import([], None, None, None), "kv_import"),
    (lambda b: b.submit_kv_export([1, 2], None), "kv_export"),
    (lambda b: b.export_prefix_pages([1, 2]), "kv_export"),
])
def test_snapshots_and_shipments_refuse_by_name(tiny, call, name):
    cfg, params = tiny
    with pytest.raises(ValueError, match=(
            f"{name} is not supported.*recurrent state and no key")):
        call(_batcher(cfg, params))


def test_the_engine_refuses_sessions_padded_generate_and_spec_decode(tiny):
    from distributed_llms_tpu.core.config import RuntimeConfig
    from distributed_llms_tpu.runtime.engine import InferenceEngine

    cfg = dataclasses.replace(tiny[0], vocab_size=512)
    params = model_lib.init_params(jax.random.key(0), cfg)
    eng = InferenceEngine(cfg, RuntimeConfig(), params)
    with pytest.raises(ValueError, match="sessions is not supported"):
        eng.start_session(["hello"])
    with pytest.raises(ValueError, match="padded_generate is not supported"):
        eng.generate_text(["hello", "hi there"])
    with pytest.raises(ValueError, match="speculative is not supported"):
        InferenceEngine(cfg, RuntimeConfig(spec_decode=True), params)
    # ... and serves without a pool: paged_pages 0 is the server's own word
    b = eng.continuous_batcher(batch_slots=2, max_len=64, paged_pages=0)
    assert not b.paged
    rid = b.submit("hello there, hello", max_new_tokens=3)
    assert len(b.run()[rid]) == 3


def test_a_row_cannot_be_continued_from_a_state(tiny):
    """No prefix to continue from: a call behind a prefix is refused in
    the model, whoever asks."""
    cfg, params = tiny
    with pytest.raises(ValueError, match="no prefix to continue from"):
        model_lib.forward(
            params, cfg, jnp.zeros((1, 8), jnp.int32),
            cache=kv_cache.init_cache(cfg, 1, 64), cache_index=jnp.int32(8))
