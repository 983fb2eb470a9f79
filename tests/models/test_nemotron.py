"""Mamba-2 layers whose rows hold a float32 state BESIDE a page pool for the
attention layers, and non-gated experts that work in a latent (Nemotron-H):
an admission's chunked scan and a decode step's recurrence, served FROM the
pool through the batcher's own PAGED programs, against the plain reference
(the recurrence token by token) on the CPU with ``nemotron3-super-tiny`` in
float32: pattern ``MEM*EME``, 16 heads of 64 x 128 in 2 groups, 32 experts of
which 8 are held, 6 a token, the latent a quarter of the hidden width.
Logits are compared, never sampled tokens.

Tolerance ``ATOL`` 2e-4 on logits of about unit size: float32 end to end on
both sides, so what differs is the order of summation (a chunk's [128, 128]
tile and the state between chunks against one step a token; pairs grouped by
expert against a loop over the experts); the readings are 1e-5 to 6e-5.  The
state held in bfloat16 moves the same logits by 2e-3 and more
(``test_a_bfloat16_state_fails_the_tolerance``), the wrong expert layers by
0.1 and more."""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.core.observability import METRICS
from distributed_llms_tpu.models import kv_cache, layers, model as model_lib
from distributed_llms_tpu.models.presets import blocks_of_pattern, get_preset
from distributed_llms_tpu.models.reference import nemotron_h
from distributed_llms_tpu.ops import moe_experts, ssm
from distributed_llms_tpu.runtime import batcher as B
from tools.reference_check import reference_cfg

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ATOL = 2e-4
PAGE = 16
S = 512  # the row length the tests serve: 32 pages of 16
STEPS = 8


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset("nemotron3-super-tiny")
    return cfg, model_lib.init_params(jax.random.key(0), cfg)


def reference(params, cfg, tokens, held=True, **changed):
    tree = dict(params, layers=list(model_lib.hybrid_layers(params, cfg)))
    return np.asarray(nemotron_h.forward(
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
        pool, ssm_h=pool.ssm_h.astype(jnp.bfloat16).astype(jnp.float32))


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


def scan_inputs(t, h=16, p=64, g=2, n=128, seed=0):
    """x, B, C, dt, A of one layer as the published initialiser would leave
    them: dt log-uniform in [0.001, 0.1], A uniform in [1, 16]."""
    ks = jax.random.split(jax.random.key(seed), 5)
    dt = jnp.exp(jax.random.uniform(
        ks[3], (t, h), minval=np.log(1e-3), maxval=np.log(0.1)))
    return (jax.random.normal(ks[0], (t, h, p)),
            0.3 * jax.random.normal(ks[1], (t, g, n)),
            0.3 * jax.random.normal(ks[2], (t, g, n)), dt,
            -jax.random.uniform(ks[4], (h,), minval=1.0, maxval=16.0))


# -- (a) the identity the design rests on ---------------------------------

@pytest.mark.parametrize("t,n", [
    (100, None),   # under one chunk of 128
    (400, None),   # crosses three chunk boundaries
    (512, None),   # fills its bucket, and its last chunk
    (512, 300),    # 212 padded positions behind 300 real ones
])
def test_the_chunked_form_is_the_recurrence(t, n):
    x, bm, cm, dt, a = scan_inputs(t, seed=t)
    y, s = ssm.ssm_prefill(x, bm, cm, dt, a,
                           None if n is None else jnp.int32(n), chunk=128)
    m = t if n is None else n
    want_y, want_s = ssm.recurrence(x[:m], bm[:m], cm[:m], dt[:m], a)
    # (float32 both; outputs and state of about unit size)
    np.testing.assert_allclose(y[:m], want_y, atol=5e-6)
    np.testing.assert_allclose(ssm.from_layout(s, 64), want_s, atol=2e-6)
    assert np.abs(np.asarray(want_y)).max() > 0.3


def test_the_layout_puts_two_heads_transposed_side_by_side():
    s = jax.random.normal(jax.random.key(1), (3, 16, 64, 128))
    laid = ssm.to_layout(s)
    assert laid.shape == (3, *ssm.state_shape(16, 64, 128)) == (3, 8, 128, 128)
    np.testing.assert_array_equal(laid[2, 5, :, :64], s[2, 10].T)
    np.testing.assert_array_equal(laid[2, 5, :, 64:], s[2, 11].T)
    np.testing.assert_array_equal(ssm.from_layout(laid, 64), s)
    assert ssm.state_bytes(128, 64, 128) == 4_194_304


# -- (b) the batcher's paged programs against the reference ----------------

@pytest.mark.parametrize("n,bucket", [
    (40, 64),     # under one chunk of 128
    (390, 512),   # crosses three chunk boundaries, 6 tokens past the third
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


# -- (c) state and taps are those at the true length -----------------------

def test_a_padded_admission_leaves_state_and_taps_of_its_true_length(tiny):
    """140 tokens in a bucket of 256 (two chunks, the second 116 of padding)
    against the same 140 tokens at a bucket they fill but for 4: a padded
    position decays nothing (dt 0) and adds nothing, whatever the pad
    token's own projections are, and the taps end at the last real token.
    (To float32's rounding: the projections in front of the scan are
    matmuls of another shape at another bucket.  State at the bucket's end
    would differ by 0.1 and more.)"""
    cfg, params = tiny
    toks = tokens_of(140, seed=7)
    _, padded = served(params, cfg, toks, 140, 256)
    _, tight = served(params, cfg, toks, 140, 144)
    for f in ("ssm_h", "ssm_conv"):
        a, b = np.asarray(getattr(padded, f)), np.asarray(getattr(tight, f))
        assert np.abs(a[:, 1]).max() > 0.5
        np.testing.assert_allclose(a, b, atol=5e-6)


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
        assert [int(x) for x in counts[6:]] == [n, -(-n // 128), 0]
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
    assert not np.asarray(pool.ssm_h[:, 1]).any()
    assert not np.asarray(pool.ssm_conv[:, 1]).any()


# -- (f) the kernels against the jax.numpy operators ------------------------

def test_the_scan_kernels_in_interpreter_mode_are_the_dense_operator(
        monkeypatch):
    x, bm, cm, dt, a = scan_inputs(301, seed=11)

    def both(fn):
        out = {}
        for mode in ("fallback", "interpret"):
            monkeypatch.setenv("DLT_RAGGED_DECODE", mode)
            out[mode] = fn()
        return out["fallback"], out["interpret"]

    before = METRICS.snapshot()["counters"]
    dense, kernel = both(lambda: ssm.ssm_prefill(
        x[:300], bm[:300], cm[:300], dt[:300], a, n=jnp.int32(260)))
    np.testing.assert_allclose(dense[0][:260], kernel[0][:260], atol=5e-6)
    np.testing.assert_allclose(dense[1], kernel[1], atol=2e-6)
    # a decode step for rows 0 and 2 of three, in layer 1 of two
    states = jnp.zeros((2, 3, 8, 128, 128)).at[1, 2].set(dense[1])
    states = states.at[0].set(1.0)
    live = jnp.asarray([True, False, True])
    rows = lambda v: jnp.broadcast_to(v[260], (3, *v.shape[1:]))
    dense, kernel = both(lambda: ssm.ssm_decode(
        rows(x), rows(bm), rows(cm), rows(dt), a, states, 1, live))
    np.testing.assert_allclose(dense[0], kernel[0], atol=2e-6)
    np.testing.assert_allclose(dense[1], kernel[1], atol=1e-6)
    # row 2 stepped from the state at 260 tokens: token 261 of the recurrence
    keep = np.r_[0:260, 260]
    want, _ = ssm.recurrence(x[keep], bm[keep], cm[keep], dt[keep], a)
    np.testing.assert_allclose(kernel[0][2], want[260], atol=5e-6)
    np.testing.assert_array_equal(kernel[1][0], states[0])  # layer 0 whole
    np.testing.assert_array_equal(kernel[1][1, 1], states[1, 1])  # not live
    after = METRICS.snapshot()["counters"]
    took = lambda name: after.get(name, 0) - before.get(name, 0)
    for op in ("ssm_prefill", "ssm_decode"):
        assert took(f"ops.dispatch.{op}.interpret") == 1
        assert took(f"ops.dispatch.{op}.fallback") == 1


@pytest.mark.parametrize("held", [None, 8])
def test_the_expert_kernels_non_gated_leg_is_the_ragged_dot(monkeypatch, held):
    """Two int8 matrices an expert, ``relu(x U)^2 V``, on a latent of 256:
    the grouped kernel on the interpreter against ``ragged_dot`` over the
    dequantized stacks; with a chip's share of 32 experts, the pairs that
    fell elsewhere are zeros in both."""
    from distributed_llms_tpu.checkpoint.quantize import quantize

    ks = jax.random.split(jax.random.key(5), 4)
    e, d, f, s, k = held or 8, 256, 384, 24, 3
    x = jax.random.normal(ks[0], (s, d), jnp.float32)
    topi = jax.random.randint(ks[1], (s, k), 0, 32 if held else e)
    q = lambda key, shape: quantize(
        jax.random.normal(key, shape) * shape[-2] ** -0.5, bits=8,
        block_axis=-2)
    up, down = q(ks[2], (2, e, d, f)), q(ks[3], (2, e, f, d))
    out = {}
    for mode in ("fallback", "interpret"):
        monkeypatch.setenv("DLT_MOE_EXPERTS", mode)
        out[mode] = moe_experts.grouped_swiglu(
            x, topi, up, down, 1, of_experts=32 if held else None,
            act=layers.gate_fn("relu2"), gated=False)
    assert out["interpret"].shape == (s, k, d)
    np.testing.assert_allclose(out["interpret"], out["fallback"], atol=2e-5,
                               rtol=1e-5)
    if held:
        away = np.asarray(topi) >= e
        assert away.any() and not np.asarray(out["interpret"])[away].any()


# -- (g) the share ties to the model ----------------------------------------

def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer(tiny):
    """One expert layer of the reference over all 32 experts against its
    four shares of 8 (each summed in the latent and sent through ``W_up``)
    plus the shared expert ONCE; and the served layer, which holds share 0,
    is that share."""
    cfg, params = tiny
    p = next(l["mlp"] for l in model_lib.hybrid_layers(params, cfg)
             if l["mlp"] is not None)
    full = model_lib.init_params(
        jax.random.key(0), dataclasses.replace(cfg, experts_held=None))
    whole = next(l["mlp"] for l in model_lib.hybrid_layers(full, cfg)
                 if l["mlp"] is not None)
    rc = reference_cfg(cfg)
    u = jax.random.normal(jax.random.key(2), (40, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        uncut = nemotron_h.experts(u, whole, rc)
        shares = [nemotron_h.experts(
            u, {**whole, "experts": jax.tree.map(
                lambda w: w[8 * i: 8 * i + 8], whole["experts"])}, rc,
            experts_held=(8 * i, 8), shared=False) for i in range(4)]
        once = nemotron_h.experts(u, whole, rc, experts_held=(0, 0))
    np.testing.assert_allclose(sum(shares) + once, uncut, atol=2e-5)
    assert all(np.abs(np.asarray(s)).max() > 0.05 for s in shares)
    # the served layer: share 0 of ITS OWN stacks, without the shared expert
    y, stats = layers.moe_dropless(
        u[None], params["blocks"]["moe"], cfg, layer=0)
    with jax.default_matmul_precision("highest"):
        want = nemotron_h.experts(u, p, rc, experts_held=(0, 8), shared=False)
    np.testing.assert_allclose(y[0], want, atol=2e-5)
    assert int(stats[0]) == 40 * 6 and 0 < int(stats[4]) < 40 * 6


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
            f".*{reason}")):
        _batcher(cfg, params, **kw)


def test_it_is_served_from_the_pool_and_from_nothing_else(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="from the page pool only.*state "
                       "beside it; pass paged_pages"):
        _batcher(cfg, params, paged_pages=None)
    b = _batcher(cfg, params)
    assert b.paged and isinstance(b.cache, kv_cache.HybridCache)
    assert b.cache.k.shape[0] == 1 and b.cache.ssm_h.shape[:2] == (3, 2)


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
    {"expert_act": "silu"}, {"latent": False}, {"gate_first": False},
    {"conv_bias": False}])
def test_a_wrong_model_is_far_outside_the_tolerance(tiny, changed):
    """An expert layer with a SiLU for the squared ReLU, the latent left
    out, the norm before the gate, no convolution bias."""
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
    state = 3 * 3 * (ssm.state_bytes(16, 64, 128) + 3 * 1536 * 4)
    assert METRICS.snapshot()["gauges"]["batcher.ssm_state_bytes"] == state
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
    assert took("ssm.admit.tokens") == 390
    assert took("ssm.admit.chunks") == 4  # of 128, the fourth holds 6 tokens
    assert took("ssm.decode.row_steps") == STEPS - 1
    assert took("moe.routed_pairs") == 3 * 6 * (390 + STEPS - 1)
    assert 0 < took("moe.held_pairs") < took("moe.routed_pairs")
    assert took("moe.layer_passes") == 3 * STEPS


def test_the_published_pattern_folds_into_blocks_and_runs():
    assert blocks_of_pattern("MEM*EME") == dict(
        num_layers=4, layer_types=("ssm", "ssm", "attn", "ssm"),
        no_ffn_layers=(1,))
    cfg = get_preset("nemotron3-super-ep4")
    assert (len(cfg.ssm_layers), len(cfg.attn_layers),
            cfg.ffn_kinds.count("moe")) == (10, 2, 10)
    unit = (("ssm", "moe"),) * 3 + (("ssm", None), ("attn", "moe"))
    # two scanned runs: 22 sub-layers are not 22 unrolled layers
    assert model_lib.layer_runs(cfg) == ((unit, 2), ((("ssm", "moe"),), 2))
    with pytest.raises(ValueError, match="no operator in front of it"):
        blocks_of_pattern("EM")


def test_bytes_of_the_real_preset():
    """ISSUE 55's arithmetic against the leaves ``init_params_quantized``
    would build, ``page_bytes`` and ``make_pool`` under ``jax.eval_shape``:
    9.63 GB of weights, 2.724 GB of state at 64 slots, 0.679 GB of pages."""
    from distributed_llms_tpu.checkpoint import quantize as quant_lib

    cfg = get_preset("nemotron3-super-ep4")
    shapes = jax.eval_shape(lambda k: model_lib.init_params(k, cfg),
                            jax.random.key(0))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    name = lambda path: "/".join(str(p.key) for p in path)
    sizes = {name(p): (int(np.prod(sd.shape)), sd) for p, sd in flat}
    quant = {n: c for n, (c, sd) in sizes.items()
             if n.startswith("blocks/") and quant_lib.leaf_plan(n, sd)[0]}
    experts = sum(c for n, c in quant.items() if "/experts/" in n)
    assert experts == 10 * 128 * 5_505_024 == 7_046_430_720
    assert sum(quant.values()) - experts == 1_691_353_088
    routers = sizes["blocks/moe/router"][0] * 4
    table = (sizes["embed/wte"][0] + sizes["lm_head/w"][0]) * 2
    weights = sum(quant.values()) * 1.03125 + routers + table
    assert round(weights / 1e9, 2) == 9.63
    pool = jax.eval_shape(
        lambda: kv_cache.make_pool(cfg, 5184, 64, slots=64))
    assert pool.ssm_h.shape == (10, 64, 64, 128, 128)
    assert pool.ssm_h.dtype == jnp.float32
    assert pool.ssm_conv.shape == (10, 64, 3, 10240)
    state = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in (pool.ssm_h, pool.ssm_conv))
    assert state == 64 * 10 * (4_194_304 + 61_440)
    assert round(state / 1e9, 3) == 2.724
    assert kv_cache.page_bytes(cfg, 64) == 131_072
    assert pool.k.shape == (2, 5184, 64, 2, 128)
    assert round(5184 * 131_072 / 1e9, 3) == 0.679


def test_the_quantized_tree_keeps_what_sets_the_memory_float():
    from distributed_llms_tpu.checkpoint.quantize import QuantizedTensor

    cfg = dataclasses.replace(
        get_preset("nemotron3-super-tiny"), dtype="bfloat16", hidden_size=256,
        moe_latent_size=128, moe_intermediate_size=128)
    p = model_lib.init_params_quantized(jax.random.key(0), cfg, 8)["blocks"]
    for leaf in ("A_log", "dt_bias", "D"):
        assert p["ssm"][leaf].dtype == jnp.float32
    for leaf in ("taps", "conv_bias", "norm_w"):
        assert p["ssm"][leaf].dtype == jnp.bfloat16
    assert p["moe"]["router"].dtype == jnp.float32
    # dt in [0.001, 0.1] and A in [1, 16], as the initialiser draws them
    dt = jax.nn.softplus(p["ssm"]["dt_bias"])
    assert 0.001 <= float(dt.min()) and float(dt.max()) <= 0.1001
    a = jnp.exp(p["ssm"]["A_log"])
    assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0
    for path in (("ssm", "in_proj"), ("ssm", "out_proj"),
                 ("moe", "latent", "w_dn"), ("moe", "latent", "w_up"),
                 ("moe", "shared", "w_up"), ("moe", "experts", "w_up"),
                 ("moe", "experts", "w_down")):
        leaf = p
        for key in path:
            leaf = leaf[key]
        assert isinstance(leaf, QuantizedTensor), path
    assert "w_gate" not in p["moe"]["shared"]
    assert p["moe"]["experts"]["w_up"].block_axis == -2


def test_the_benchmarks_reference_is_this_one_byte_for_byte():
    with open(os.path.join(ROOT, "distributed_llms_tpu", "models",
                           "reference", "nemotron_h.py"), "rb") as f:
        mine = f.read()
    with open(os.path.join(ROOT, "benchmark", "reference", "nemotron_h.py"),
              "rb") as f:
        assert f.read() == mine
    assert b"distributed_llms_tpu" not in mine.replace(
        b"distributed_llms_tpu/models", b"")
