"""Continuous batching: admit requests into an in-flight decode batch.

The reference (and round-2's engine) serve request *groups*: a batch enters
prefill together, decodes together, and the whole batch drains before the
next group starts — short requests wait for the longest one, and free batch
rows ride along empty.  Continuous batching (the scheduling model of modern
serving stacks) keeps a fixed set of batch SLOTS decoding at all times:
when a row finishes, a queued request is prefilled into that row between
decode chunks while the other rows keep generating.

Works single-device or on a GSPMD data/tensor-parallel mesh (VERDICT r3
next-step 5): pass ``parallel=`` (a parallel.api.ParallelModel with no
pipe/seq axes) and the shared KV cache shards over the mesh ('data' on the
batch axis, 'model' on KV heads) while the per-chunk scheduling state
(last_tok, valid, active, budget — a few hundred bytes) is constrained
replicated, then pulled back to HOST numpy mirrors between chunks.  On a
mesh spanning processes every host therefore feeds identical replicated
inputs to the same jit sequence and reads back identical mirrors — the
admission loop stays in lockstep with no cross-host control traffic at
all (pinned by the 2-process mixed-budget leg of
tests/cluster/test_multihost.py).  Pipelined / sequence-parallel meshes
keep their own decode schedules (wavefront, ring) — the batcher rejects
them loudly.

PAGED mode is mesh-native too (ROADMAP item 3): the page pool (and the
int8 QuantKVCache pool) shards its KV-head axis over 'model'
(models.kv_cache.pool_specs — per-chip pool bytes divide by tp, so
per-chip row capacity multiplies by the mesh), the ragged/paged decode
kernels partition through their own custom_partitioning rules
(ops/decode_attn — each shard runs its local head slice; page tables and
lengths replicate on a pure-TP mesh), and every pool-carrying jit in this
module (admission splices, growth/swap scatter-gathers, KV-import
adoption, the decode carry) re-constrains its pool output so one
placement — and one compile key per bucket — serves the whole engine.
Host-facing semantics (digests, tiering, preemption, temp-0 bytes) are
identical to the single-device paged engine, pinned by
tests/runtime/test_mesh_paged.py.  KV heads must divide over 'model';
batch_slots must divide over 'data'.  Speculative batching stays
single-device contiguous.

TPU-native formulation (everything static-shaped, two compiled functions):

- ``admit_row``: prefill ONE request into batch slot ``i`` of the shared
  KV cache — the row prefills against a transient single-row cache (dense
  causal, flash-eligible) whose K/V then overwrite that batch row via one
  ``dynamic_update_slice`` along the batch axis.  Prompts pad to
  power-of-two buckets so admission compiles once per bucket, not per
  length.
- ``decode_chunk``: K decode steps for ALL slots at once, with PER-ROW
  cache write positions (rows admitted at different times sit at different
  depths).  The per-row single-token forward is ``jax.vmap``-ed over the
  batch axis: each row carries its own position, write slot, and validity
  mask; XLA turns the vmapped ``dynamic_update_slice`` into a scatter and
  re-batches the matmuls onto the MXU.  Inactive rows compute harmlessly
  into never-validated slots (no per-step cache select, which would copy
  the cache) and their outputs are masked to pad.

Invariant pinned by tests/runtime/test_batcher.py: at temperature 0 every
request's tokens are IDENTICAL to running runtime.generate.generate_tokens
on that request alone — continuous batching changes scheduling, never
results.

Scheduling POLICY lives in runtime/scheduler.py (admission order, prefill
chunk sizing against the token budget, victim selection, the pressure
ladder, the overlap sync-trigger list — declared hooks the run loop
delegates through ``self.sched``); this module keeps the MECHANISM.  The
default ``schedule="mixed"`` policy runs chunked-prefill bites INSIDE the
decode dispatch (:func:`mixed_step` — one fused token-budget program), so
resident decode rows never stall for a serialized prefill forward; the
host-RAM KV tier lives in runtime/kv_tier.py, the page allocator and the
prefix cache's index in runtime/pages.py, and how a page is stored in
models/kv_cache.py.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core import profiling
from ..core.config import ModelConfig
from ..core.observability import METRICS, get_logger
from ..models import kv_cache, model as model_lib
from ..models.kv_cache import KVCache
from ..ops import decode_attn
from ..ops.quant_matmul import live_rows
from . import constrain as constrain_lib
from . import sampling
from . import scheduler as scheduler_lib
from .kv_tier import HostTier
from .pages import PagePool, PrefixCache
from .scheduler import make_scheduler
from .shapes import bucket_length as _bucket

log = get_logger("batcher")

# Acceptance-EMA smoothing for the adaptive spec_k downshift: ~5 rounds of
# history — fast enough that a cold draft downshifts within one long row,
# slow enough that a single unlucky round doesn't collapse k.
_SPEC_EMA_ALPHA = 0.2


# Overwrite batch row ``slot`` of a contiguous cache with a prefilled
# single-row cache: how a row lies there is models/kv_cache.py's.
_splice_row = kv_cache.splice_row


def _fwd(pm):
    """The forward to trace: the mesh-parallel one when ``pm`` is set (a
    ParallelModel — hashable frozen dataclass, so jit caches per mesh), else
    the single-device model forward.  Both share the (params, cfg, tokens,
    ...) signature."""
    return model_lib.forward if pm is None else pm._forward_adapter


def _replicated(pm, *xs):
    """Constrain small scheduling state replicated on the mesh: every host
    of a multi-process mesh then mirrors identical values (np.asarray on a
    fully-replicated array is legal and equal everywhere), keeping the
    host-side admission loop in lockstep.  No-op single-device."""
    if pm is None:
        return xs if len(xs) > 1 else xs[0]
    from jax.sharding import NamedSharding, PartitionSpec as P

    s = NamedSharding(pm.mesh, P())
    out = tuple(jax.lax.with_sharding_constraint(x, s) for x in xs)
    return out if len(out) > 1 else out[0]


def _last_real(n):
    """forward's ``logits_at`` for one right-padded row of ``n`` real
    tokens: its last real position, [1]."""
    return jnp.maximum(n - 1, 0)[None]


def _sample_first(logits, rng, temperature, top_k, top_p,
                  temp_req=None, topp_req=None, topk_req=None,
                  mask_req=None):
    """Sample the admitted row's first token from the last real position's
    logits ``logits`` [1, 1, V] — the one sampling tail shared by every
    admission path.  The prefill computed the head for that position alone
    (models.model.forward's ``logits_at``: :func:`_last_real`), so no
    admission holds [T, V] logits.
    ``temp_req``/``topp_req``/``topk_req`` (traced scalars) override the
    static knobs for per-request sampling without a recompile per value.
    ``mask_req`` [V] is a constrained/biased request's start-state token
    mask (runtime/constrain.py): applied before the draw AND the greedy
    argmax, never to the logprob (the logprobs contract stays
    raw-distribution)."""
    next_logits = logits[:, 0]
    src = next_logits if mask_req is None else next_logits + mask_req[None, :]
    if temp_req is None:
        tok = sampling.sample(rng, src, temperature, top_k, top_p)[0]
    else:
        tok = sampling.sample_rows(
            rng, src, jnp.reshape(temp_req, (1,)), top_k,
            jnp.reshape(topp_req, (1,)),
            top_k_rows=(None if topk_req is None
                        else jnp.reshape(topk_req, (1,))),
        )[0]
    # Chosen-token logprob under the RAW model distribution (the OpenAI
    # logprobs contract) — one [V] log-softmax, trivial next to the
    # prefill that produced the logits.
    lp = jax.nn.log_softmax(next_logits[0].astype(jnp.float32))[tok]
    return tok, lp


def _row_state(fwd, cfg, n):
    """What the forward is told of one right-padded row's ``n`` real
    tokens (None: nothing known).  A model with state that is not keys and
    values (family "hybrid") needs the count and hands back its expert
    layers' counts; every other family's quantized matmuls use it to skip
    the row tiles that hold only padding (models.model.real_rows), which
    changes no real token's arithmetic.  The mesh-parallel forward takes
    no count."""
    if n is None or (cfg.family != "hybrid" and fwd is not model_lib.forward):
        return {}
    state = {"seq_lens": n[None]}
    if cfg.family == "hybrid":
        state["return_aux"] = True
    return state


def _prefill_row(fwd, params, cfg, cache_dtype, s, prompt, plen=None):
    """Causal prefill of one request into a transient single-row cache of
    ``s`` slots — shared by the contiguous and paged admissions.  The model
    is told, while tracing, that the row STARTS here (the Python 0, no
    mask): the prompt's T tokens attend among themselves and take the
    cache's first T slots, and no slot past T is read or scored, however
    long the cache (models.model._self_attention: the flash kernel on the
    chip for heads of whole 128-lane registers, dense over the T keys for
    other heads, on the CPU and under a mesh).  ``fwd`` is _fwd(pm): the
    mesh-parallel forward on a mesh batcher, the plain model forward
    otherwise.  The model is told the prompt's true length ``plen``
    (:func:`_row_state`): a model with state that is not keys and values
    (family "hybrid") leaves in the row cache the state AT that length, not
    at the padded bucket's end, and returns its expert layers' counts of
    the real tokens third (forward's ``return_aux``).  With ``plen`` the
    logits are the last real position's alone, [1, 1, V]
    (:func:`_last_real`); without it (the draft's cache fill, which
    samples nothing) every position's."""
    (tp,) = prompt.shape
    row_cache = kv_cache.init_cache(cfg, 1, s, dtype=cache_dtype)
    positions = jnp.arange(tp, dtype=jnp.int32)[None, :]
    return fwd(
        params, cfg, prompt[None, :], positions=positions,
        cache=row_cache, cache_index=0, **_row_state(fwd, cfg, plen),
        **({} if plen is None else {"logits_at": _last_real(plen)}),
    )


def _prefill_row_with_prefix(fwd, params, cfg, row_cache, prefix_len, chunk,
                             last, clen=None):
    """Prefix-seeded prefill: only the request's suffix runs through the
    model (session-style continuation math) — shared by the contiguous and
    paged prefix admissions.  ``row_cache`` is the transient contiguous row
    cache that holds the prefix (key/value rows, or latent rows:
    kv_cache.row_cache_of).  The model is told the suffix's true length
    ``clen``, and a hybrid-family model returns its expert counts third, as
    in :func:`_prefill_row`.  ``last`` is the suffix's true length whoever
    else is told it: the logits are its last real position's, [1, 1, V].
    The row holds an ungapped run, slot == position: ``prefix_len`` slots
    of what came before and the chunk behind them.  So the model is handed
    no mask: a scalar write offset says it all, "causal by position, keys
    below prefix_len + Tc", and the model scores it as a row's continuation
    (models.model._continuation_attention: the flash kernel over the key
    tiles the row holds where it can, the dense body over every slot
    elsewhere)."""
    (tc,) = chunk.shape
    positions = (prefix_len + jnp.arange(tc, dtype=jnp.int32))[None, :]
    return fwd(
        params, cfg, chunk[None, :], positions=positions,
        cache=row_cache, cache_index=prefix_len,
        logits_at=_last_real(last), **_row_state(fwd, cfg, clen),
    )


def _finish_admission(
    cache, slot, row_cache, logits, rng, temperature, top_k, top_p,
    total_len, temp_req=None, topp_req=None, topk_req=None, mask_req=None,
):
    """Shared admission tail (plain and prefix-cached paths): sample the
    first token from the last real position's logits, splice the prefilled
    row into the shared cache, report the row's valid slots."""
    tok, lp = _sample_first(logits, rng, temperature, top_k, top_p,
                            temp_req, topp_req, topk_req, mask_req)
    cache = _splice_row(cache, slot, row_cache)
    s = cache.k.shape[-3]
    row_valid = jnp.arange(s, dtype=jnp.int32) < total_len
    return cache, tok, row_valid, lp


@partial(
    jax.jit,
    static_argnames=("cfg", "temperature", "top_k", "top_p", "pm"),
    donate_argnames=("cache",),
)
def admit_row(
    params: Any,
    cfg: ModelConfig,
    cache: Any,  # shared KVCache, [L, B, S, KVH, HD] leaves
    slot: jax.Array,  # scalar int32 — batch row to fill
    prompt: jax.Array,  # [Tp] int32, right-padded (bucketed length)
    plen: jax.Array,  # scalar int32 true length
    rng: jax.Array,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    pm: Any = None,  # ParallelModel — GSPMD dp/tp mesh batching
    temp_req: jax.Array | None = None,  # traced per-request overrides
    topp_req: jax.Array | None = None,
    topk_req: jax.Array | None = None,
    mask_req: jax.Array | None = None,  # [V] constrained first-token mask
) -> tuple[Any, jax.Array, jax.Array, jax.Array]:
    """Prefill one request into batch row ``slot``.  Returns
    (cache', first_token, row_valid [S], first_token_logprob) —
    real_lens/budget bookkeeping is the caller's.  The transient row cache is deliberately NOT
    mesh-constrained: batch 1 can't shard over 'data'; XLA places it (TP
    still shards the matmuls via the weights)."""
    logits, row_cache, *counts = _prefill_row(
        _fwd(pm), params, cfg, cache.k.dtype, cache.k.shape[-3], prompt, plen
    )
    cache, tok, row_valid, lp = _finish_admission(
        cache, slot, row_cache, logits, rng, temperature, top_k, top_p,
        total_len=plen, temp_req=temp_req, topp_req=topp_req,
        topk_req=topk_req, mask_req=mask_req,
    )
    # (a hybrid-family model's counts of the pass ride out last: the
    # retention layers' tokens and chunks, see _prefill_row)
    return (cache, *_replicated(pm, tok, row_valid, lp), *counts)


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def admit_row_kv(
    params: Any,
    cfg: ModelConfig,
    cache: Any,  # shared KVCache (the DRAFT's, in speculative mode)
    slot: jax.Array,  # scalar int32
    prompt: jax.Array,  # [Tp] int32 right-padded FULL prompt (prefix+suffix)
    plen: jax.Array,  # scalar int32 true length
) -> Any:
    """KV-only admission: prefill one row and splice it into the shared
    cache, sampling nothing.  Speculative batching uses it to seed the
    DRAFT model's cache for a newly admitted request (prefix caching only
    stores target KV, so the draft prefills the full prompt)."""
    del plen  # the transient prefill writes all Tp slots; masks gate reads
    _, row_cache = _prefill_row(
        model_lib.forward, params, cfg, cache.k.dtype, cache.k.shape[-3],
        prompt,
    )
    return _splice_row(cache, slot, row_cache)


@partial(
    jax.jit,
    static_argnames=("cfg", "draft_cfg", "k", "eos_id", "pad_id",
                     "temperature", "top_k", "top_p"),
    donate_argnames=("cache", "draft_cache"),
)
def spec_chunk(
    params: Any,
    cfg: ModelConfig,
    draft_params: Any,
    draft_cfg: ModelConfig,
    cache: Any,        # target shared KVCache
    draft_cache: Any,  # draft shared KVCache (same slot layout)
    last_tok: jax.Array,   # [B] int32
    real_lens: jax.Array,  # [B] int32
    valid: jax.Array,      # [B, S] bool
    active: jax.Array,     # [B] bool
    budget: jax.Array,     # [B] int32
    k: int,
    eos_id: int = -1,
    pad_id: int = 0,
    counts: jax.Array | None = None,  # [B, V] int32 output-token histogram
    pres_row: jax.Array | None = None,  # [B] traced presence penalties
    freq_row: jax.Array | None = None,  # [B] traced frequency penalties
    temperature: float = 0.0,  # 0 => greedy (bit-exact vs decode_chunk);
    #   > 0 => speculative SAMPLING (distribution-preserving, engine-wide
    #   warp — the same Leviathan/Chen rejection scheme as
    #   runtime/speculative.py, one round per call instead of a while_loop)
    top_k: int = 0,
    top_p: float = 1.0,
    rng: jax.Array | None = None,  # required when temperature > 0
    tables: jax.Array | None = None,  # [B, P] page table — the TARGET
    #   cache is a page-pool (KVCache or int8 QuantKVCache) and the
    #   verify window writes through it (the paged spec leg; the draft
    #   cache stays contiguous)
    k_row: jax.Array | None = None,  # [B] int32 adaptive per-row draft
    #   length (acceptance clamped at j < k_row; traced, so the whole
    #   spec_k ladder shares one compiled program)
) -> tuple:
    """ONE speculative round over the batch: draft k tokens per row
    against the draft cache, verify all of them in one (k+1)-token target
    forward, commit each row's accepted prefix + bonus/correction.
    temperature == 0: greedy — tokens bit-identical to decode_chunk's
    greedy output; acceptance only changes how many arrive per round.
    temperature > 0: rejection sampling — draft token d_j ~ q_j accepts
    iff u_j < p_j(d_j)/q_j(d_j), the first rejection draws from
    normalize(max(p - q, 0)), full acceptance draws the bonus from
    p_{k+1} (q zero-extended); the emitted sequence is an exact sample
    from the target's warped distribution, same theorem and residual
    construction as runtime/speculative.py's sampled loop (the RNG stream
    differs from decode_chunk's, so per-seed tokens differ while the
    distribution does not — pinned by the self-calibrated TV test in
    tests/runtime/test_spec_batcher.py).

    Returns (toks [B, k+1] pad-masked, m [B] committed counts, lps
    [B, k+1] chosen-token logprobs, cache', draft_cache', last_tok',
    real_lens', valid', active', budget', counts').  ``lps[b, j]`` is the
    TARGET's raw-distribution log-softmax of the committed token
    ``toks[b, j]`` — the verify forward already computes full logits for
    every position, so serving logprobs costs one log-softmax + gather per
    round.

    Presence/frequency penalties (``counts``+``pres_row``+``freq_row``)
    stay bit-exact vs the penalized plain batcher: verify position j's
    context is [last_tok, d_1..d_j], so its penalty histogram is the base
    counts plus the one-hots of d_1..d_j — and within the accepted lead
    (the only region where greedy[j] can commit) those drafts ARE the
    committed tokens, making the adjusted argmax identical to the
    sequential penalized decode's.  Draft steps penalize with the same
    evolving histogram so acceptance tracks the penalized target.
    Logprobs stay RAW-distribution (pre-penalty), matching decode_chunk.

    Layout: contiguous (slot == position) exactly like decode_chunk; the
    rollback/backfill arguments mirror runtime/speculative.py with the
    frontier convention shifted to the batcher's (a token's KV is written
    by the forward that consumes it, at slot == its position).

    PAGED leg (``tables`` set — the spec x paged tentpole): the TARGET
    cache is the shared page pool and the k-token draft/verify window
    writes THROUGH the page tables (models.model._paged_window_attention
    scatters the k+1 tokens' KV at slots real_lens..real_lens+k and each
    verify query reads its row's prefix through per-offset lengths).
    What the contiguous leg does with the ``+spec_k+1`` headroom slots,
    the paged leg does with per-row SCRATCH-TAIL pages: the growth loop
    provisions pages through slot real_lens+spec_k before every round,
    and rejection rollback is the same pos/length clamp ``commit_clamp``
    applies today — ``real_lens`` only advances by the committed count,
    so the junk KV past the frontier is never read (the kernel's prefix
    contract) and the next round overwrites it.  The small quantized
    self-draft cache stays contiguous; ``valid`` gates only ITS masks
    here.  Temp-0 bytes are identical to the contiguous spec engine and
    to the non-speculative paged engine (tests/runtime/test_spec_paged).

    ``k_row`` (both legs) is the budget-aware adaptive downshift: a
    per-row TRACED draft-length clamp — acceptance stops at j < k_row,
    and the forced stop at j == k_row emits the target's own token for
    that position (greedy: greedy[j]; sampled: a draw from p_j with the
    draft distribution zero-extended past the clamp, which is exactly a
    fresh target sample), so the emitted stream is unchanged at ANY
    clamp — only arrival granularity shrinks, freeing verify-token
    budget for mixed prefill bites.  One compiled program serves the
    whole spec_k ladder (graftcheck GC4 batcher.spec_chunk_paged).

    Chaining contract: like decode_chunk, every returned carry leaf
    (cache', draft_cache', last_tok', real_lens', valid', active',
    budget', counts') is a legal input for the next round — the
    dispatch-ahead engine loop chains speculative rounds device-resident
    exactly as it chains plain decode chunks (both caches are donated;
    the carry vectors are not)."""
    paged = tables is not None
    # Contiguous: draft and target share one slot layout (equal widths),
    # so using the draft's width everywhere leaves the program unchanged;
    # paged: the masks below gate only the contiguous DRAFT cache.
    s = draft_cache.k.shape[-3]
    slots = jnp.arange(s, dtype=jnp.int32)
    penalized = counts is not None
    sampled = temperature > 0.0
    if sampled and rng is None:
        raise ValueError("spec_chunk with temperature > 0 requires rng")
    if sampled:
        rng, kd, ku, kc = jax.random.split(rng, 4)
    else:
        kd = jax.random.key(0)  # uniform scan shape; never consumed

    def _pen(logits, cnt):  # [B(, T), V] logits, [B(, T), V] int32 counts
        if not penalized:
            return logits
        extra = (1,) * (logits.ndim - 2)
        f = freq_row.reshape(-1, *extra, 1)
        p = pres_row.reshape(-1, *extra, 1)
        return (logits - f * cnt.astype(logits.dtype)
                - p * (cnt > 0).astype(logits.dtype))

    def row_mask(hi):  # [B] inclusive frontier -> [B, 1, 1, S]
        own = jnp.logical_and(slots[None, :] >= real_lens[:, None],
                              slots[None, :] <= hi[:, None])
        return jnp.logical_or(valid, own)[:, None, None, :]

    # --- draft: k single-token steps against the draft cache.  Penalized
    # mode carries the evolving histogram (base + drafts so far) so the
    # draft tracks the penalized target; sampled mode also emits each
    # step's full post-warp distribution q_j (the rejection test needs
    # q_j(d_j) and the residual the whole vector).
    def draft_step(dc, inputs):
        draft_cache, cur, cnt = dc
        j, kj = inputs
        idx = real_lens + j
        logits, draft_cache = model_lib.forward(
            draft_params, draft_cfg, cur[:, None], positions=idx[:, None],
            cache=draft_cache, cache_index=idx, attn_mask=row_mask(idx),
        )
        step_logits = _pen(logits[:, 0], cnt)
        if sampled:
            warped = sampling.warp_logits(
                step_logits, temperature, top_k, top_p
            )
            nxt = jax.random.categorical(kj, warped, axis=-1).astype(
                jnp.int32
            )
            out = (nxt, jax.nn.softmax(warped, axis=-1))
        else:
            nxt = jnp.argmax(step_logits, axis=-1).astype(jnp.int32)
            out = nxt
        if penalized:
            cnt = cnt.at[jnp.arange(cnt.shape[0]), nxt].add(1)
        return (draft_cache, nxt, cnt), out

    dcnt0 = counts if penalized else jnp.zeros((), jnp.int32)
    (draft_cache, _, _), draft_ys = jax.lax.scan(
        draft_step, (draft_cache, last_tok, dcnt0),
        (jnp.arange(k, dtype=jnp.int32), jax.random.split(kd, k)),
    )
    if sampled:
        drafts, qs = draft_ys
        qs = jnp.moveaxis(qs, 0, 1)  # [B, k, V]
    else:
        drafts, qs = draft_ys, None
    drafts = drafts.T  # [B, k]

    # --- verify: one (k+1)-token target forward.  Paged: the window
    # writes through the page tables and reads per-offset prefixes (no
    # mask — the kernel's length contract is the causality); contiguous:
    # the explicit row masks, exactly as before.
    vtoks = jnp.concatenate([last_tok[:, None], drafts], axis=1)
    voff = jnp.arange(k + 1, dtype=jnp.int32)
    if paged:
        vlogits, cache = model_lib.forward(
            params, cfg, vtoks,
            positions=real_lens[:, None] + voff[None, :],
            cache=cache, cache_index=real_lens, kv_tables=tables,
        )
    else:
        vmask = jnp.concatenate(
            [row_mask(real_lens + q) for q in range(k + 1)], axis=2
        )  # [B, 1, k+1, S]
        vlogits, cache = model_lib.forward(
            params, cfg, vtoks,
            positions=real_lens[:, None] + voff[None, :],
            cache=cache, cache_index=real_lens, attn_mask=vmask,
        )
    if penalized:
        # counts_j = base + one-hots of d_1..d_j (position j consumed
        # [last_tok, d_1..d_j]; last_tok is already in the base histogram).
        v = vlogits.shape[-1]
        oneh = jax.nn.one_hot(drafts, v, dtype=jnp.int32)       # [B, k, V]
        c = jnp.concatenate(
            [jnp.zeros_like(oneh[:, :1]), jnp.cumsum(oneh, axis=1)], axis=1
        )                                                       # [B, k+1, V]
        pen_vlogits = _pen(vlogits, counts[:, None, :] + c)
    else:
        pen_vlogits = vlogits
    # Shared accept/commit bookkeeping (runtime/speculative.py — the ONE
    # definition; only the frontier convention differs between the loops).
    from .speculative import backfill_coords, commit_clamp, greedy_accept_commit

    j_ar = jnp.arange(k + 1, dtype=jnp.int32)
    b = drafts.shape[0]
    if sampled:
        # Rejection sampling over the (penalized, warped) target vs draft
        # distributions — identical math to speculative_generate_tokens'
        # sampled branch; p and q share the same penalty basis per
        # position so the theorem holds against the penalized target.
        ps = jax.nn.softmax(
            sampling.warp_logits(pen_vlogits, temperature, top_k, top_p),
            axis=-1,
        )  # [B, k+1, V]
        p_at = jnp.take_along_axis(
            ps[:, :k], drafts[..., None], axis=-1
        )[..., 0]                                        # [B, k]
        q_at = jnp.take_along_axis(qs, drafts[..., None], axis=-1)[..., 0]
        u = jax.random.uniform(ku, (b, k))
        accept = u * jnp.maximum(q_at, 1e-20) < p_at
        if k_row is not None:
            # Adaptive downshift, sampled leg: force a stop at j == k_row
            # and zero the draft distribution past it — the "residual" at
            # a forced stop is then max(p - 0, 0) = p itself, i.e. a
            # fresh sample from the target (the draft was never consulted
            # there), so the theorem's output distribution is preserved
            # at any per-row clamp.
            accept = jnp.logical_and(
                accept, jnp.arange(k, dtype=jnp.int32)[None, :]
                < k_row[:, None],
            )
        lead = jnp.cumprod(accept.astype(jnp.int32), axis=1)
        a = jnp.sum(lead, axis=1)                        # [B] in 0..k
        # Unified residual: zero-extend q so position k's "residual" is
        # p_{k+1} itself (the bonus draw).
        q_ext = jnp.concatenate([qs, jnp.zeros_like(ps[:, :1])], axis=1)
        if k_row is not None:
            q_ext = q_ext * (
                j_ar[None, :] < k_row[:, None]
            ).astype(q_ext.dtype)[..., None]
        p_a = jnp.take_along_axis(ps, a[:, None, None], axis=1)[:, 0]
        q_a = jnp.take_along_axis(q_ext, a[:, None, None], axis=1)[:, 0]
        resid = jnp.maximum(p_a - q_a, 0.0)
        norm = jnp.sum(resid, axis=-1, keepdims=True)
        # p == q on the whole support leaves an empty residual; fall back
        # to p (any sample from it is valid there).
        resid = jnp.where(norm > 1e-9, resid / jnp.maximum(norm, 1e-9), p_a)
        corr = jax.random.categorical(
            kc,
            jnp.where(resid > 0, jnp.log(jnp.maximum(resid, 1e-30)),
                      -jnp.inf),
            axis=-1,
        ).astype(jnp.int32)                              # [B]
        cand = jnp.where(
            j_ar[None, :] < a[:, None],
            jnp.concatenate([drafts, drafts[:, -1:]], axis=1),
            corr[:, None],
        )                                                # [B, k+1]
        m, has_eos = commit_clamp(cand, a, active, budget, eos_id, k)
    else:
        greedy = jnp.argmax(pen_vlogits, axis=-1).astype(jnp.int32)
        cand, m, has_eos, _ = greedy_accept_commit(
            drafts, greedy, active, budget, eos_id, k, k_row=k_row
        )
    # Chosen-token logprobs for the committed tokens (OpenAI logprobs
    # contract): vlogits[:, j] predicts the token committed at offset j.
    # Greedy: accepted drafts equal greedy[j] by agreement and the bonus
    # at j == a IS greedy[j].  Sampled: accepted drafts are the sampled
    # d_j and j == a holds the residual/bonus draw — either way the
    # committed token's raw-distribution log-softmax under the TARGET at
    # position j is the contract (decode_chunk reports the same basis).
    lps = jnp.take_along_axis(
        jax.nn.log_softmax(vlogits.astype(jnp.float32), axis=-1),
        cand[..., None], axis=-1,
    )[..., 0]  # [B, k+1]

    # Target KVs at slots real_lens .. real_lens+m-1 hold
    # [last_tok, c_1..c_{m-1}] — all committed; slot real_lens+m (holding
    # d_m's KV when the round mismatched there) stays invalid and is
    # overwritten when the next round consumes the true c_m.
    committed = jnp.logical_and(
        slots[None, :] >= real_lens[:, None],
        slots[None, :] <= (real_lens + m - 1)[:, None],
    )
    valid = valid | (committed & (m > 0)[:, None])

    toks = jnp.where(j_ar[None, :] < m[:, None], cand, jnp.int32(pad_id))
    if penalized:
        # Histogram update: every committed token (EOS included, matching
        # decode_chunk's accounting).
        commit_oneh = jax.nn.one_hot(
            cand, counts.shape[1], dtype=jnp.int32
        ) * (j_ar[None, :] < m[:, None])[..., None]
        counts = counts + jnp.sum(commit_oneh, axis=1)
    new_last = jnp.take_along_axis(
        cand, jnp.maximum(m - 1, 0)[:, None], axis=1
    )[:, 0]
    last_tok = jnp.where(m > 0, new_last, last_tok)
    real_lens = real_lens + m
    budget = budget - m
    active = active & ~has_eos & (budget > 0)

    # Draft backfill: only a fully accepted round (m == k+1) leaves the
    # draft missing c_k's KV one slot below the new frontier
    # (speculative.backfill_coords has the full rationale).
    bf_idx, bf_tok = backfill_coords(cand, m, frontier=real_lens)
    bf_own = slots[None, :] == bf_idx[:, None]
    bf_mask = jnp.logical_or(valid, bf_own)[:, None, None, :]
    _, draft_cache = model_lib.forward(
        draft_params, draft_cfg, bf_tok[:, None], positions=bf_idx[:, None],
        cache=draft_cache, cache_index=bf_idx, attn_mask=bf_mask,
    )
    return (toks, m, lps, cache, draft_cache, last_tok, real_lens, valid,
            active, budget, counts if penalized else None)


@partial(
    jax.jit,
    static_argnames=("cfg", "temperature", "top_k", "top_p", "pm"),
    donate_argnames=("cache",),
)
def admit_row_with_prefix(
    params: Any,
    cfg: ModelConfig,
    cache: Any,  # shared KVCache
    slot: jax.Array,  # scalar int32
    prefix_k: jax.Array,  # [..., 1, S, KVH, HD] — a registered prefix's KV
    prefix_v: jax.Array,
    prefix_len: jax.Array,  # scalar int32
    chunk: jax.Array,  # [Tc] int32 — the request's suffix, right-padded
    clen: jax.Array,  # scalar int32 true suffix length
    rng: jax.Array,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    pm: Any = None,  # ParallelModel — GSPMD dp/tp mesh batching
    temp_req: jax.Array | None = None,  # traced per-request overrides
    topp_req: jax.Array | None = None,
    topk_req: jax.Array | None = None,
    mask_req: jax.Array | None = None,  # [V] constrained first-token mask
) -> tuple[Any, jax.Array, jax.Array, jax.Array]:
    """Prefix-cached admission: the shared prefix's KV (computed ONCE by
    ``register_prefix``) seeds the row; only the request's suffix prefills —
    session-style continuation math (runtime/session.py) for one row.
    Returns (cache', first_token, row_valid, first_token_logprob)."""
    logits, row_cache = _prefill_row_with_prefix(
        _fwd(pm), params, cfg, KVCache(k=prefix_k, v=prefix_v), prefix_len,
        chunk, clen,
    )
    cache, tok, row_valid, lp = _finish_admission(
        cache, slot, row_cache, logits, rng, temperature, top_k, top_p,
        total_len=prefix_len + clen, temp_req=temp_req, topp_req=topp_req,
        topk_req=topk_req, mask_req=mask_req,
    )
    return (cache, *_replicated(pm, tok, row_valid, lp))


@partial(jax.jit, static_argnames=("cfg", "pm"),
         donate_argnames=("row_k", "row_v"))
def prefill_chunk_step(
    params: Any,
    cfg: ModelConfig,
    row_k: jax.Array,   # [..., 1, S, KVH, HD] transient single-row KV
    row_v: jax.Array,
    done: jax.Array,    # scalar int32 — prompt tokens already in the row
    chunk: jax.Array,   # [Tc] int32 — next chunk, right-padded (bucketed)
    clen: jax.Array,    # scalar int32 true chunk length
    pm: Any = None,     # ParallelModel — GSPMD dp/tp mesh batching
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One chunk of a CHUNKED prefill: consume ``chunk`` into the transient
    single-row cache at offset ``done`` — the same continuation math as
    prefix-cached admission (the "prefix" is the row's own partial prompt),
    so the accumulated attention is bit-identical to a monolithic prefill.
    row_k/row_v are DONATED (the update happens in place instead of
    copying the full row cache every chunk) — _start_chunked hands this
    step exclusively-owned buffers, copying a registered prefix's KV once
    up front rather than aliasing it.
    Returns (row_k', row_v', last_logits [1, V] at the chunk's last real
    position — the sampling source once the prompt completes; replicated
    on a mesh batcher so the finishing sample runs lockstep)."""
    return _prefill_leg(params, cfg, row_k, row_v, done, chunk, clen, pm)


def _prefill_leg(params, cfg, row_k, row_v, done, chunk, clen, pm):
    """The one prefill-bite definition, shared VERBATIM by the
    serialized :func:`prefill_chunk_step` and the fused
    :func:`mixed_step` — like `_decode_steps` for the decode leg, a
    single definition is what keeps the two schedules trivially
    byte-identical."""
    logits, row_cache = _prefill_row_with_prefix(
        _fwd(pm), params, cfg, KVCache(k=row_k, v=row_v), done, chunk, clen
    )
    return row_cache.k, row_cache.v, _replicated(pm, logits[:, 0])  # [1, V]


@partial(
    jax.jit,
    static_argnames=("cfg", "temperature", "top_k", "top_p", "pm"),
    donate_argnames=("cache",),
)
def finish_chunked_admission(
    cfg: ModelConfig,
    cache: Any,
    slot: jax.Array,
    row_k: jax.Array,
    row_v: jax.Array,
    last_logits: jax.Array,  # [1, V] from the final prefill_chunk_step
    total_len: jax.Array,    # scalar int32 — full prompt length
    rng: jax.Array,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    pm: Any = None,          # ParallelModel — GSPMD dp/tp mesh batching
    temp_req: jax.Array | None = None,
    topp_req: jax.Array | None = None,
    topk_req: jax.Array | None = None,
    mask_req: jax.Array | None = None,  # [V] constrained first-token mask
) -> tuple[Any, jax.Array, jax.Array, jax.Array]:
    """Tail of a chunked admission: sample the first token from the final
    chunk's last-position logits and splice the fully-prefilled transient
    row into the shared cache — the same _finish_admission used by the
    monolithic paths, so results are bit-identical."""
    cache, tok, row_valid, lp = _finish_admission(
        cache, slot, KVCache(k=row_k, v=row_v), last_logits[:, None, :],
        rng, temperature, top_k, top_p, total_len,
        temp_req=temp_req, topp_req=topp_req, topk_req=topk_req,
        mask_req=mask_req,
    )
    return (cache, *_replicated(pm, tok, row_valid, lp))


@partial(
    jax.jit,
    static_argnames=("temperature", "top_k", "top_p", "pm"),
    donate_argnames=("cache",),  # row_k/row_v feed a gather-reshape XLA
    #   cannot alias — donating them only triggers the unused-donation
    #   warning every admission.
)
def finish_chunked_admission_paged(
    cache: Any,              # page-pool KVCache
    page_list: jax.Array,    # [P] int32, scratch-padded
    row_k: jax.Array,        # [L, 1, P*BLK, KVH, HD] fully-prefilled row
    row_v: jax.Array,
    last_logits: jax.Array,  # [1, V] from the final prefill_chunk_step
    rng: jax.Array,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    pm: Any = None,          # ParallelModel — GSPMD dp/tp mesh batching
    temp_req: jax.Array | None = None,
    topp_req: jax.Array | None = None,
    topk_req: jax.Array | None = None,
    mask_req: jax.Array | None = None,  # [V] constrained first-token mask
) -> tuple[Any, jax.Array, jax.Array]:
    """Tail of a chunked admission in PAGED mode: sample the first token
    from the final chunk's logits and scatter the transient row's pages
    into the pool through ``page_list`` — the same _paged_splice every
    monolithic paged admission uses, so results are bit-identical.  Pages
    are allocated only HERE (on-demand: the whole prefill ran pageless),
    so a long prompt never pins pool pages while it chunks in."""
    return _paged_splice(
        cache, page_list, KVCache(k=row_k, v=row_v),
        last_logits[:, None, :], rng, temperature, top_k,
        top_p, temp_req, topp_req, topk_req, mask_req, pm=pm,
    )


def _paged_splice(cache, page_list, row_cache, logits, rng,
                  temperature, top_k, top_p, temp_req=None, topp_req=None,
                  topk_req=None, mask_req=None, pm=None, slot=None):
    """Admission tail for the paged pool: sample the first token, then
    write the contiguous transient row cache into the row's pages
    (kv_cache.write_row: ``page_list`` is scratch-padded, ``slot`` is
    where a hybrid model's convolution state goes).  On a mesh batcher
    (``pm``) the pool result is re-constrained to its sharding and the
    sampled token/logprob replicate (lockstep mirrors)."""
    tok, lp = _sample_first(logits, rng, temperature, top_k, top_p,
                            temp_req, topp_req, topk_req, mask_req)
    cache = kv_cache.write_row(cache, page_list, row_cache, slot)
    return (kv_cache.constrain(pm, cache), *_replicated(pm, tok, lp))


@partial(
    jax.jit,
    static_argnames=("cfg", "temperature", "top_k", "top_p", "pm"),
    donate_argnames=("cache",),
)
def admit_row_paged(
    params: Any,
    cfg: ModelConfig,
    cache: Any,  # page-pool KVCache, [L, NB, BLK, KVH, HD] leaves
    page_list: jax.Array,  # [P] int32 — the row's pages, scratch-padded
    prompt: jax.Array,  # [Tp] int32, right-padded (bucketed)
    plen: jax.Array,  # scalar int32 true length
    rng: jax.Array,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    pm: Any = None,  # ParallelModel — GSPMD dp/tp mesh batching
    temp_req: jax.Array | None = None,  # traced per-request overrides
    topp_req: jax.Array | None = None,
    topk_req: jax.Array | None = None,
    mask_req: jax.Array | None = None,  # [V] constrained first-token mask
    slot: jax.Array | None = None,  # scalar int32 batch slot: where a
    #   hybrid model's convolution state goes (nothing else needs it)
) -> tuple[Any, jax.Array, jax.Array]:
    """Paged admission: dense causal prefill on a transient contiguous row
    cache, then scatter its pages into the pool.
    Returns (cache', tok, logprob), and for a hybrid model the prefill's
    expert counts (see :func:`_prefill_row`)."""
    logits, row_cache, *moe = _prefill_row(
        _fwd(pm), params, cfg, kv_cache.row_dtype(cache),
        page_list.shape[0] * cache.k.shape[2], prompt, plen,
    )
    return (*_paged_splice(
        cache, page_list, row_cache, logits, rng, temperature, top_k,
        top_p, temp_req, topp_req, topk_req, mask_req, pm=pm, slot=slot,
    ), *moe)


@partial(
    jax.jit,
    static_argnames=("cfg", "temperature", "top_k", "top_p", "pm"),
    donate_argnames=("cache",),
)
def admit_row_with_prefix_paged(
    params: Any,
    cfg: ModelConfig,
    cache: Any,  # page-pool KVCache
    page_list: jax.Array,  # [P] int32, scratch-padded
    prefix_k: jax.Array,  # [L, 1, S, KVH, HD] contiguous prefix KV
    prefix_v: jax.Array,
    prefix_len: jax.Array,  # scalar int32
    chunk: jax.Array,  # [Tc] int32 suffix, right-padded
    clen: jax.Array,  # scalar int32
    rng: jax.Array,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    pm: Any = None,  # ParallelModel — GSPMD dp/tp mesh batching
    temp_req: jax.Array | None = None,  # traced per-request overrides
    topp_req: jax.Array | None = None,
    topk_req: jax.Array | None = None,
    mask_req: jax.Array | None = None,  # [V] constrained first-token mask
) -> tuple[Any, jax.Array, jax.Array]:
    """Prefix-cached paged admission: the prefix KV seeds the transient row
    cache, only the suffix prefills, then the pages scatter into the pool.
    Returns (cache', tok, logprob)."""
    logits, row_cache = _prefill_row_with_prefix(
        _fwd(pm), params, cfg, KVCache(k=prefix_k, v=prefix_v), prefix_len,
        chunk, clen,
    )
    return _paged_splice(
        cache, page_list, row_cache, logits, rng, temperature, top_k,
        top_p, temp_req, topp_req, topk_req, mask_req, pm=pm,
    )


@partial(
    jax.jit,
    static_argnames=("cfg", "temperature", "top_k", "top_p", "pm"),
    donate_argnames=("cache",),
)
def admit_row_auto_paged(
    params: Any,
    cfg: ModelConfig,
    cache: Any,  # page-pool KVCache, [L, NB, BLK, KVH, HD] leaves
    read_list: jax.Array,   # [P] int32 — the row's FULL page table (cached
    #   run first, then freshly allocated pages, scratch-padded)
    write_list: jax.Array,  # [P] int32 — same, but cached positions routed
    #   to the scratch page 0 (shared pages are read-only)
    prefix_len: jax.Array,  # scalar int32 — tokens covered by cached pages
    chunk: jax.Array,  # [Tc] int32 — the un-cached suffix, right-padded
    clen: jax.Array,  # scalar int32 true suffix length
    rng: jax.Array,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    pm: Any = None,  # ParallelModel — GSPMD dp/tp mesh batching
    temp_req: jax.Array | None = None,  # traced per-request overrides
    topp_req: jax.Array | None = None,
    topk_req: jax.Array | None = None,
    mask_req: jax.Array | None = None,  # [V] constrained first-token mask
) -> tuple[Any, jax.Array, jax.Array]:
    """AUTOMATIC prefix-cache admission: the row's cached prefix KV is
    gathered out of its own (shared, refcounted) pool pages into the
    transient contiguous row cache, only the un-cached suffix runs through
    the model (the same continuation math as the named-prefix path), and
    the result scatters back through ``write_list`` — cached positions land
    in the scratch page, so a shared page is never rewritten.  The gather
    reads the pool BEFORE the splice updates it, all inside one donated
    program (an int8 pool dequantizes the gathered run to row_dtype — the
    suffix continues from the same values decode attends to).
    Returns (cache', tok, logprob), and for a hybrid-family model the
    suffix's expert counts."""
    logits, row_cache, *moe = _prefill_row_with_prefix(
        _fwd(pm), params, cfg,
        kv_cache.row_cache_of(cache, *kv_cache.gather_row(cache, read_list)),
        prefix_len, chunk, clen, clen,
    )
    return (*_paged_splice(
        cache, write_list, row_cache, logits, rng, temperature, top_k,
        top_p, temp_req, topp_req, topk_req, mask_req, pm=pm,
    ), *moe)


def _decode_steps(
    params, cfg, cache, last_tok, real_lens, valid, active, budget, rng,
    chunk_steps, temperature, top_k, top_p, eos_id, pad_id, pm, tables,
    temp_row, topp_row, topk_row, counts, pres_row, freq_row, mask_stack,
    next_stack, dfa_state,
):
    """The K-step decode scan shared VERBATIM by :func:`decode_chunk` and
    the fused :func:`mixed_step` — one definition of the decode leg is
    what keeps ``schedule=mixed`` trivially byte-identical to the
    alternating loop's decode math."""
    if tables is None:
        s = cache.k.shape[-3]
        slots = jnp.arange(s, dtype=jnp.int32)

    def step(carry, rng_step):
        (cache, last_tok, real_lens, valid, active, budget, cnts,
         dstate) = carry
        moe = None  # a hybrid model's expert counts of this step
        # One batched forward with PER-ROW write slots (models.model accepts
        # a [B] cache_index: only the KV write scatters; all matmuls stay
        # batched).  Paged mode: the page table routes each row's read and
        # write; the prefix mask is implicit.  Contiguous mode: the mask
        # admits each row's valid slots plus the slot its own token was
        # just written to.
        # A hybrid model's state that is not keys and values (convolution
        # state, rings, a retention layer's state) advances only for the
        # rows that decode (seq_lens 1), so a finished or free row neither
        # moves its own state nor is counted, and the step's counts (the
        # expert layers'; a retention model's) come out beside the logits
        # (return_aux).
        state = ({"seq_lens": active.astype(jnp.int32), "return_aux": True}
                 if cfg.family == "hybrid" else {})
        if tables is not None:
            logits, cache, *aux = _fwd(pm)(
                params, cfg, last_tok[:, None], positions=real_lens[:, None],
                cache=cache, cache_index=real_lens, kv_tables=tables,
                **state,
            )
            moe = aux[0] if aux else None
            if cfg.kv_lora_rank or cfg.swa_layers:
                # Seventh (_note_moe), behind the fifth and sixth that only a
                # chip's share of the experts counts: what the decode kernel of
                # the paged layers (latent pages; the full layers' beside
                # windowed ones) read this step, the tokens each decoding
                # row holds, its new one included.  Eighth, with windowed
                # layers: the min(that, window) of them its ring holds.
                # Ninth, against latent pages (the eighth a zero, so that
                # eight stay the rings'): the keys the latent kernel's
                # products covered for those rows, their pages in whole
                # blocks (decode_attn.mla_scored_keys).
                held = jnp.where(active, real_lens + 1, 0)
                read = [jnp.sum(held, dtype=jnp.int32)[None]]
                if cfg.swa_layers:
                    read.append(jnp.sum(
                        jnp.minimum(held, cfg.sliding_window),
                        dtype=jnp.int32)[None])
                else:
                    scored = decode_attn.mla_scored_keys(
                        held, cache.k.shape[2], cache.k.shape[3],
                        cache.k.dtype, tables.shape[1])
                    read += [jnp.zeros((1,), jnp.int32), jnp.sum(
                        jnp.where(active, scored, 0), dtype=jnp.int32)[None]]
                moe = jnp.concatenate([
                    moe, jnp.zeros((6 - moe.shape[0],), jnp.int32), *read])
        else:
            mask = (valid | (slots[None, :] == real_lens[:, None]))[:, None, None, :]
            # (a model of retention layers is the one hybrid served here,
            # without a pool)
            logits, cache, *aux = _fwd(pm)(
                params, cfg, last_tok[:, None], positions=real_lens[:, None],
                cache=cache, cache_index=real_lens, attn_mask=mask, **state,
            )
            moe = aux[0] if aux else None
        logits = logits[:, 0]
        # The row just wrote last_tok's K/V at slot real_lens; mark it valid
        # for rows that were active (inactive rows wrote junk into a slot
        # that stays invalid — harmless, and re-prefilled on admission).
        # Paged mode has no mask to maintain: validity is implicit in
        # real_lens (the kernel's prefix contract).
        if tables is None:
            valid = valid | (
                active[:, None] & (slots[None, :] == real_lens[:, None])
            )
        real_lens = real_lens + active.astype(jnp.int32)
        # Everything after the forward — penalties, the grammar mask,
        # sampling, the chosen token's logprob — under one profiler
        # scope (metadata only: the program is the same).
        with jax.named_scope("sample"):
            if cnts is not None:
                sample_from = (
                    logits
                    - freq_row[:, None] * cnts.astype(logits.dtype)
                    - pres_row[:, None] * (cnts > 0).astype(logits.dtype)
                )
            else:
                sample_from = logits
            # Grammar/bias mask: gather each row's state mask AFTER penalties
            # (the -1e30 forbidden entries dominate any finite adjustment;
            # free rows gather state 0's all-zero row — exact identity).
            bias = (constrain_lib.gather_bias(mask_stack, dstate)
                    if dstate is not None else None)
            if temp_row is None:
                src = sample_from if bias is None else sample_from + bias
                tok = sampling.sample(rng_step, src, temperature, top_k,
                                      top_p)
            else:
                tok = sampling.sample_rows(
                    rng_step, sample_from, temp_row, top_k,
                    1.0 if topp_row is None else topp_row,
                    top_k_rows=topk_row, mask_rows=bias,
                )
            if dstate is not None:
                # Advance each (pre-step-)active row's automaton on its
                # sampled token — one gather, device-resident, so a chained
                # dispatch-ahead chunk consumes the advanced state directly.
                dstate = jnp.where(
                    carry[4],
                    constrain_lib.advance_states(next_stack, dstate, tok),
                    dstate,
                )
            if cnts is not None:
                cnts = cnts.at[
                    jnp.arange(cnts.shape[0]), tok
                ].add(active.astype(jnp.int32))
            budget = budget - active.astype(jnp.int32)
            if eos_id >= 0:
                active = active & (tok != eos_id)
            active = active & (budget > 0)
            out = jnp.where(
                carry[4], tok, jnp.int32(pad_id)
            )  # mask with PRE-step active
            # Chosen-token logprob under the raw distribution (serving's
            # OpenAI logprobs field) — one log-softmax reduction per step.
            lp = jnp.take_along_axis(
                jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1),
                tok[:, None], axis=-1,
            )[:, 0]
            lp = jnp.where(carry[4], lp, 0.0)
            last_tok = jnp.where(carry[4], tok, last_tok)
        return (
            (cache, last_tok, real_lens, valid, active, budget, cnts,
             dstate),
            (out, lp, moe),
        )

    rngs = jax.random.split(rng, chunk_steps)
    carry0 = (cache, last_tok, real_lens, valid, active, budget, counts,
              dfa_state)
    ((cache, last_tok, real_lens, valid, active, budget, counts,
      dfa_state), (toks, lps, moe)) = jax.lax.scan(step, carry0, rngs)
    toks, lps, last_tok, real_lens, valid, active, budget = _replicated(
        pm, toks.T, lps.T, last_tok, real_lens, valid, active, budget
    )
    if counts is not None:
        # The histogram is scheduling state too: replicated, so every host
        # of a multi-process mesh applies identical penalty adjustments.
        counts = _replicated(pm, counts)
    if dfa_state is not None:
        # The automaton state is replicated scheduling state like the rest
        # of the carry: every host syncs identical states at span end.
        dfa_state = _replicated(pm, dfa_state)
    if tables is not None:
        # Mesh paged decode: pin the pool carry back to its sharding (KV
        # heads over 'model') so chained dispatch-ahead chunks and the
        # scatter/gather jits all consume one placement (no-op off-mesh).
        cache = kv_cache.constrain(pm, cache)
    # A hybrid model's expert counts, summed over the chunk's steps, leave
    # as one more output.
    moe = () if moe is None else (jnp.sum(moe, axis=0),)
    return (toks, cache, last_tok, real_lens, valid, active, budget, lps,
            counts, dfa_state, *moe)


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "chunk_steps", "temperature", "top_k", "top_p", "eos_id",
        "pad_id", "pm",
    ),
    donate_argnames=("cache",),
)
def decode_chunk(
    params: Any,
    cfg: ModelConfig,
    cache: Any,  # shared KVCache
    last_tok: jax.Array,  # [B] int32 — each row's most recent token
    real_lens: jax.Array,  # [B] int32 — tokens resident per row (write pos)
    valid: jax.Array,  # [B, S] bool — per-row valid cache slots
    active: jax.Array,  # [B] bool
    budget: jax.Array,  # [B] int32 — tokens this row may still emit
    rng: jax.Array,
    chunk_steps: int,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    eos_id: int = -1,
    pad_id: int = 0,
    pm: Any = None,  # ParallelModel — GSPMD dp/tp mesh batching
    tables: jax.Array | None = None,  # [B, P] page table — cache is a pool
    temp_row: jax.Array | None = None,  # [B] traced per-row temperature
    topp_row: jax.Array | None = None,  # [B] traced per-row top-p
    topk_row: jax.Array | None = None,  # [B] traced per-row top-k
    counts: jax.Array | None = None,  # [B, V] int32 output-token histogram
    pres_row: jax.Array | None = None,  # [B] traced presence penalties
    freq_row: jax.Array | None = None,  # [B] traced frequency penalties
    mask_stack: jax.Array | None = None,  # [S, V] f32 per-state token mask
    #   (constrain.build_stack: state 0 free, padded up a closed ladder)
    next_stack: jax.Array | None = None,  # [S, V] int32 DFA transitions
    dfa_state: jax.Array | None = None,  # [B] int32 DFA state (0 = free)
) -> tuple[jax.Array, Any, jax.Array, jax.Array, jax.Array, jax.Array,
           jax.Array, jax.Array, jax.Array | None, jax.Array | None]:
    """K decode steps with per-row positions.  Returns
    (toks [B, K], cache', last_tok', real_lens', valid', active', budget',
    logprobs [B, K], counts', dfa_state').  ``temp_row``/``topp_row``/``topk_row``
    switch sampling to the per-row path (sampling.sample_rows) —
    per-request sampling in one shared batch.  ``counts``+``pres_row``+``freq_row`` engage OpenAI
    presence/frequency penalties: logits adjust by
    ``- freq*count - pres*(count > 0)`` per row BEFORE sampling, and the
    histogram tracks every emitted token (rows with zero penalties read
    garbage counts harmlessly — the adjustment multiplies to zero).
    ``mask_stack``+``next_stack``+``dfa_state`` engage grammar-constrained
    structured output (runtime/constrain.py): each row gathers its
    state's token mask, adds it to the sampling logits (after penalties —
    the mask dominates any finite adjustment), and advances its automaton
    state on the sampled token INSIDE this jitted program, so the state
    carry stays device-resident across dispatch-ahead chunks and
    constrained and free rows share one compiled decode step (graftcheck
    GC4 batcher.decode_chunk_constrained).  Free rows ride state 0, whose
    mask row is all zeros — their sampled bytes are untouched.
    Logprobs stay RAW-distribution (pre-penalty, pre-mask), matching the
    logprobs contract elsewhere.

    Chaining contract (the dispatch-ahead engine loop): every returned
    carry leaf (cache', last_tok', real_lens', valid', active', budget',
    counts') is a legal INPUT for the next call — same shapes, same
    dtypes, device-resident — so chunk N+1 can dispatch directly from
    chunk N's outputs with no host round-trip, hitting the same compiled
    program host-mirror inputs would (graftcheck GC4's
    batcher.decode_chunk_overlap case pins this to one compile key).
    Only ``cache`` is donated; the small carry vectors are read-only
    inputs and safe to hold across the chained dispatch."""
    return _decode_steps(
        params, cfg, cache, last_tok, real_lens, valid, active, budget,
        rng, chunk_steps, temperature, top_k, top_p, eos_id, pad_id, pm,
        tables, temp_row, topp_row, topk_row, counts, pres_row, freq_row,
        mask_stack, next_stack, dfa_state,
    )


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "pcfg", "chunk_steps", "temperature", "top_k", "top_p",
        "eos_id", "pad_id", "pm",
    ),
    donate_argnames=("cache", "row_k", "row_v"),
)
def mixed_step(
    params: Any,
    cfg: ModelConfig,   # decode-leg config (ragged decode where enabled)
    pcfg: ModelConfig,  # prefill-leg config (the plain forward)
    cache: Any,
    last_tok: jax.Array,
    real_lens: jax.Array,
    valid: jax.Array,
    active: jax.Array,
    budget: jax.Array,
    rng: jax.Array,
    chunk_steps: int,
    row_k: jax.Array,   # [..., 1, S, KVH, HD] the head pending prefill's
    row_v: jax.Array,   # transient row (DONATED — updated in place)
    done: jax.Array,    # scalar int32 — prompt tokens already in the row
    pchunk: jax.Array,  # [Tw] int32 — the bite, right-padded to the policy's
    #   FIXED bucket width (compile key mix-independent — GC4 mixed_step)
    pclen: jax.Array,   # scalar int32 true bite length
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    eos_id: int = -1,
    pad_id: int = 0,
    pm: Any = None,
    tables: jax.Array | None = None,
    temp_row: jax.Array | None = None,
    topp_row: jax.Array | None = None,
    topk_row: jax.Array | None = None,
    counts: jax.Array | None = None,
    pres_row: jax.Array | None = None,
    freq_row: jax.Array | None = None,
    mask_stack: jax.Array | None = None,
    next_stack: jax.Array | None = None,
    dfa_state: jax.Array | None = None,
) -> tuple:
    """ONE fused token-budget step (``schedule=mixed``): the K-step decode
    scan for every active slot AND one prefill bite of the head pending
    chunked prefill, in the same compiled program — so resident decode
    rows never wait on a separately-dispatched serialized prefill forward
    (the Sarathi-Serve coalescing at Orca's iteration granularity).  The
    prefill leg is :func:`prefill_chunk_step`'s exact math (the segment
    enters variable-length, right-padded up the shared bucket ladder;
    continuation masking keeps pad columns unattended) against the
    prefill's own transient row cache; the decode leg is
    :func:`_decode_steps` verbatim — the legs touch disjoint buffers
    (transient row vs shared pool/cache), so fusion changes dispatch
    count, never bytes, and temp-0 streams are identical to the
    alternating loop.

    Returns :func:`decode_chunk`'s 10-tuple extended with
    ``(row_k', row_v', last_logits [1, V])`` — every leaf is a legal
    input for the next fused call (the dispatch-ahead chaining contract:
    the decode carry AND the prefill row both stay device-resident across
    a span)."""
    prow_k, prow_v, plast = _prefill_leg(
        params, pcfg, row_k, row_v, done, pchunk, pclen, pm
    )
    out = _decode_steps(
        params, cfg, cache, last_tok, real_lens, valid, active, budget,
        rng, chunk_steps, temperature, top_k, top_p, eos_id, pad_id, pm,
        tables, temp_row, topp_row, topk_row, counts, pres_row, freq_row,
        mask_stack, next_stack, dfa_state,
    )
    return (*out, prow_k, prow_v, plast)


def _writable(a: np.ndarray) -> np.ndarray:
    """A writable host array from a ``jax.device_get`` result: the CPU
    backend may hand back a read-only zero-copy view, and admission writes
    into the scheduling mirrors (the copy is paid only when needed)."""
    return a if a.flags.writeable else np.array(a)


@partial(jax.jit, donate_argnames=("counts",))
def _reset_count_row(counts, slot, tok):
    """Zero one row of the output-token histogram and count the admission
    token — a penalized request's penalties see exactly its own output."""
    v = counts.shape[1]
    row = jnp.zeros((v,), jnp.int32).at[tok].set(1)
    return counts.at[slot].set(row)


# _bucket (runtime/shapes.py bucket_length): admission prompt/suffix widths
# pad up the shared decode-shape ladder so compile keys stay bounded;
# tools.graftcheck's GC4 gate traces this path against shapes.bucket_count.


FINISHED_KEEP = 1024  # finished-request ring (/debug/requests)


@dataclass
class _Timeline:
    """Where one request's time went, on the batcher's clock.  WRITE-ONLY
    stamps: they feed ``batcher.queue_wait_seconds`` and the finished-
    request ring (``/debug/requests``) and reach no scheduling decision
    (graftsync GS1 stays clean without an entry).  One object per rid,
    shared by the resume requests a preemption mints, so the parts add up
    across residencies."""
    t_submit: float = 0.0   # submit()
    t_queued: float = 0.0   # submit, or the requeue after a preemption
    t_admit: float = 0.0    # the latest admission's start: its
    #                         selection, or the fetch of the admission it
    #                         was launched behind (_note_admit_start)
    residencies: int = 0    # admissions: 1 + preemptions survived
    queue_s: float = 0.0    # sum over residencies of t_admit - t_queued
    admit_s: float = 0.0    # sum of admission start -> row resident
    pre_submit_s: float = 0.0  # gateway: receipt -> submit (its clock)
    prompt_tokens: int = 0  # as submitted (a resume's ids grow)
    cached_tokens: int = 0  # of the first admission
    # The gap between deliveries (opened by ``_note_resident``, closed in
    # ``_collect`` or by a re-admission's token): the latest delivery's
    # stamps, and what the request's gaps came to.
    t_delivered: float = 0.0         # the latest delivery of tokens
    admit_at_delivered: float = 0.0  # the engine's admit clock then
    deliveries: int = 0     # the first token + each later delivery
    max_gap_s: float = 0.0  # the longest interval between two of them
    stalled_s: float = 0.0  # of those intervals, inside admission rounds

    def deliver(self, now: float, admit_now: float) -> tuple[float, float]:
        """A delivery at ``now``, the admit clock reading ``admit_now``:
        close the gap since the latest one and move the stamps.  Returns
        the gap and the part of it inside admission rounds."""
        gap = now - self.t_delivered
        part = admit_now - self.admit_at_delivered
        self.t_delivered, self.admit_at_delivered = now, admit_now
        self.deliveries += 1
        self.stalled_s += part
        if gap > self.max_gap_s:
            self.max_gap_s = gap
        return gap, part


@dataclass(eq=False)  # identity equality: deque.remove/queue scans then
#   compare C-level object pointers instead of running a generated Python
#   __eq__ per element — the engine thread's queue scans stay atomic under
#   the GIL against the serving loop thread's concurrent submit() appends.
class _Request:
    rid: int
    ids: list[int]  # suffix ids when prefix is set, else the full prompt
    max_new_tokens: int
    prefix: str | None = None
    temperature: float | None = None  # None -> the batcher's config
    top_p: float | None = None
    top_k: int | None = None
    presence_penalty: float = 0.0   # OpenAI-style, applied to output tokens
    frequency_penalty: float = 0.0
    # Grammar-constrained structured output / logit bias / banned tokens
    # (runtime/constrain.py): ONE compiled token-mask automaton covers all
    # three.  The row's automaton state is a pure function of its emitted
    # tokens, so preemption/resume carries nothing extra — re-admission
    # replays the emitted prefix through the automaton on the host.
    constraint: Any = None  # constrain.TokenDFA | None
    prefix_cache: bool = True  # per-request opt-out of AUTOMATIC caching
    digests: list | None = None  # memoized page digests — a back-pressured
    #   request retries admission every round; its prompt hash never changes
    # Overload plane (PR 3): admission order is (priority desc, rid asc) —
    # higher priority admits first and is preempted last; rid breaks ties
    # FIFO (and lets a preempted request resume ahead of later arrivals).
    priority: int = 0
    # Absolute time.perf_counter() deadline: a request still QUEUED past it
    # is shed (results empty, shed[rid] set) instead of admitted doomed.
    deadline: float | None = None
    # Multi-tenant QoS (runtime/scheduler.py TenantScheduler): the tenant
    # this request bills against.  None = the anonymous bucket.  The
    # weighted-fair admission order, virtual token counters, and
    # resident-row caps all key on it; a preempted resume keeps it.
    tenant: str | None = None
    # Preemption-with-recompute state: tokens this request already emitted
    # (and streamed) in a previous residency.  ``ids`` then holds
    # prompt + resume_emitted, so re-admission prefills the full context
    # and the admission token CONTINUES the sequence (temp-0 exact).
    resume_emitted: list[int] | None = None
    resume_lps: list[float] | None = None
    # Swap-preemption state (host-RAM KV tier): the victim's raw pages are
    # parked in the HostTier under ``swap_handle`` and restore scatters
    # them back instead of recomputing — ``swap_pages``/``swap_last_tok``/
    # ``swap_pos`` rebuild the row's device scheduling state verbatim, and
    # ``max_new_tokens`` already holds the remaining budget (no admission
    # token is sampled on restore).  A failed restore (budget dry, drop
    # drill, checksum mismatch) clears swap_handle and falls through to
    # the recompute path above — ``ids`` is prompt + emitted either way.
    swap_handle: int | None = None
    swap_pages: int = 0
    swap_last_tok: int = 0
    swap_pos: int = 0
    timeline: _Timeline = field(default_factory=_Timeline)


@dataclass
class _Prefix:
    ids: list[int]
    k: Any  # [..., 1, S, KVH, HD] single-row KV holding the prefix
    v: Any


@dataclass
class _PendingPrefill:
    """A chunked prefill in flight: the request's prompt enters the row's
    TRANSIENT single-row cache ``prefill_chunk`` tokens per scheduling
    round (decode rounds interleave), splicing into the shared cache only
    when complete."""

    req: _Request
    row_k: Any          # transient [..., 1, S, KVH, HD] accumulating KV
    row_v: Any
    done: int           # prompt tokens already consumed (incl. prefix)
    ids: list[int]      # the request's own ids (prefix KV pre-seeded)
    total_len: int      # prefix + prompt length
    last_logits: Any | None = None  # [1, V] after the latest chunk
    # Automatic prefix-cache hit (paged mode): the cached page run seeding
    # the transient row.  The pages are RETAINED for the whole prefill
    # (mirrored into the reserving _RowState's ``pages`` so cancel/preempt
    # release them and the pool audit sees the references); the finishing
    # splice routes their positions to the scratch page — shared pages are
    # never rewritten.
    cached_pages: list[int] = field(default_factory=list)
    cached_len: int = 0
    digests: list = field(default_factory=list)


@dataclass(eq=False)
class _Admission:
    """A request between its selection for a slot and its activation
    there.  ``serial`` names a path that never pipelines (a ``"swap"``
    restore, a ``"chunked"`` start) and leaves the rest unset.  Of a
    monolithic admission the page fields are what
    :meth:`ContinuousBatcher._reserve_row_pages` handed out; once launched,
    ``outs`` are the program's outputs still on the device (first token,
    logprob, row mask, expert counts) and ``ticket`` its place in the
    engine thread's account of the device.  It owns ``slot`` and its pages
    until it is settled, with no row to show for them yet."""

    slot: int
    req: _Request
    pfx: "_Prefix | None" = None
    serial: str | None = None
    total_len: int = 0
    page_list: Any = None  # [pages_per_row] int32, paged mode
    pages: list[int] = field(default_factory=list)
    cached_pages: list[int] = field(default_factory=list)
    cached_len: int = 0
    digests: list = field(default_factory=list)
    sampling: tuple = ()  # (temperature, top_p, top_k) as the row decodes
    ticket: int = 0
    outs: tuple = ()
    cancelled: bool = False  # cancel_row took it in flight


@dataclass
class _RowState:
    rid: int | None = None
    prefilling: bool = False  # chunked prefill in flight: the slot is
    #                     reserved but must not publish or decode yet
    req: "_Request | None" = None  # the request as admitted — preemption
    #                     rebuilds a resume request from it
    priority: int = 0   # mirror of req.priority (victim selection)
    admit_seq: int = 0  # monotone admission stamp: among equal priorities
    #                     the MOST recently admitted row is preempted first
    #                     (its lost work is smallest, vLLM's policy)
    emitted: list[int] = field(default_factory=list)
    lps: list[float] = field(default_factory=list)  # per-token logprobs
    #                     (raw TARGET distribution), aligned with emitted —
    #                     speculative mode gathers them from verify logits
    remaining: int = 0  # decode tokens this row may still emit (host mirror
    #                     of the device budget — distinguishes real pad-id
    #                     tokens from post-deactivation padding)
    pages: list[int] = field(default_factory=list)  # paged mode: the pool
    #                     pages this row owns (freed on completion)
    streamed: int = 0  # tokens already delivered to run()'s on_tokens


class ContinuousBatcher:
    """Slot-based continuous batching — single-device, or GSPMD dp/tp mesh
    when built with ``parallel=`` (see module docstring).

    Usage::

        batcher = ContinuousBatcher(cfg, params, tokenizer, batch_slots=8,
                                    max_len=512)
        rids = [batcher.submit(p, max_new_tokens=64) for p in prompts]
        results = batcher.run()   # {rid: token list}

    ``run`` drives admit/decode chunks until the queue drains and every row
    finishes.  Scheduling policy is FIFO admission into the first free slot.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        tokenizer: Any = None,
        batch_slots: int = 8,
        max_len: int = 512,
        chunk_steps: int = 8,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        eos_id: int = -1,
        pad_id: int = 0,
        kv_dtype: Any = None,
        seed: int = 0,
        parallel: Any = None,  # parallel.api.ParallelModel (GSPMD dp/tp)
        paged_pages: int | None = None,  # KV page-pool size (pages) — paged
        #   mode: rows admit with pages for the PROMPT plus one decode page
        #   and GROW on demand at chunk boundaries (vLLM's on-demand block
        #   allocation), so the pool can be far smaller than
        #   batch_slots * max_len; a dry pool evicts LRU-cold cached pages,
        #   then preempts the lowest-priority / most-recently-admitted row
        #   (freed pages now, recompute later — temp-0 streams stay exact),
        #   then back-pressures admission instead of OOMing.
        page_size: int = 64,
        # Automatic prefix caching (paged mode only): every full page of an
        # admitted prompt is content-hashed into a PrefixCache; later
        # requests reuse the longest cached page-run COPY-FREE through
        # their page tables (pages are refcounted; unreferenced cached
        # pages persist in an LRU and are evicted only under pool
        # pressure), so only the un-cached suffix prefills.  Transparent:
        # no register_prefix call needed; per-request opt-out via
        # submit(prefix_cache=False).  Tokens at temperature 0 stay
        # identical to solo decodes (tests/runtime/test_prefix_cache.py).
        prefix_cache: bool = False,
        # Speculative batching: every scheduling round drafts spec_k
        # tokens per row with the draft model and verifies them in ONE
        # target forward.  temperature == 0: tokens stay bit-identical to
        # the plain batcher (acceptance only changes how many arrive per
        # round); engine-wide temperature > 0: distribution-preserving
        # rejection sampling (spec_chunk docstring).  Single-device
        # contiguous mode (no mesh, no paging).
        draft_params: Any = None,
        draft_cfg: ModelConfig | None = None,
        spec_k: int = 4,
        # Adaptive spec_k downshift (greedy engines, schedule=mixed): a
        # per-row acceptance-rate EMA feeds the scheduler's spec_round_k
        # hook, which clamps each row's draft length — a cold draft stops
        # burning n_active*(spec_k+1) verify tokens of the step budget on
        # rounds that commit one token.  The clamp is a TRACED input
        # (one compiled program across the whole ladder) and the forced
        # stop emits the target's own token, so streams stay byte-exact
        # at any clamp; only arrival granularity changes.
        spec_adaptive_k: bool = True,
        # Chunked prefill: admission consumes at most this many prompt
        # tokens per scheduling round PER PENDING PREFILL (up to
        # ``prefill_concurrency`` advance concurrently), so a long prompt
        # never stalls in-flight decodes for its whole prefill — the
        # serving-QoS lever for mixed long/short traffic.  None =
        # monolithic admission.  Results stay token-identical (the chunk
        # steps are the prefix-continuation math against the row's own
        # partial prompt; logprob values agree to float drift — the same
        # attention reduced in different shapes).  Single-device
        # contiguous plain mode.
        prefill_chunk: int | None = None,
        # How many chunked prefills may be in flight at once: two long
        # prompts interleave their admission chunks instead of serializing
        # head-of-line (strict FIFO still gates STARTING one — the queue
        # front waits for a free prefill slot, never jumps it).
        prefill_concurrency: int = 2,
        # Deterministic fault injection (runtime/faults.py FaultPlane):
        # sites batcher.admit / batcher.decode / batcher.page_alloc are
        # consulted each scheduling round, so tests and operator drills can
        # crash, stall, or dry-pool the engine at an exact chunk.  None
        # disables (zero overhead beyond one attribute check per round).
        faults: Any = None,
        # KV memory tiering (paged mode): kv_bits=8 stores pool pages as
        # int8 with blockwise absmax scales (half the bytes/token -> ~1.9x
        # concurrent rows per pool byte; dequant fuses into the decode
        # attention read, greedy outputs are parity-bounded vs bf16, not
        # bit-exact).  host_pages > 0 arms a host-RAM tier behind the
        # pool: preemption SWAPS victims' raw pages out (restore is
        # byte-exact, cheaper than recompute for long prefixes; falls back
        # to exact recompute when the budget is dry) and the prefix-cache
        # LRU spills cold pages there before hard-evicting (a later hit
        # restores instead of re-prefilling).
        kv_bits: int = 16,
        host_pages: int = 0,
        # Dispatch-ahead engine loop: while no scheduling work is pending
        # (nothing queued, no chunked prefill / KV import / growth /
        # cancel), chunk N+1 dispatches DIRECTLY from chunk N's
        # device-resident carry (JAX async dispatch) and chunk N's host
        # work — token D2H, delivery/streaming callbacks, digest hashing,
        # metrics — runs while N+1 executes on device.  The host
        # scheduling mirrors refresh lazily at the next sync trigger, so
        # admission/growth/preemption semantics are byte-for-byte
        # unchanged and temp-0 outputs are byte-identical to overlap=False
        # (tests/runtime/test_overlap.py).  Mesh-legal, multi-process
        # included: the device-resident carry is replicated scheduling
        # state (every chunk fn constrains it P()), so a deferred sync
        # reads identical mirrors on every process and the lockstep
        # contract holds with the overlap on.
        overlap: bool = True,
        # Scheduling policy (runtime/scheduler.py): "mixed" (default)
        # fuses pending prefill-chunk bites into the decode step as one
        # compiled token-budget program so decode rows never stall for a
        # serialized prefill forward and a pending prefill no longer
        # parks the dispatch-ahead span; "alternate" keeps the serialized
        # prefill_chunk_step rounds.  Temp-0 bytes identical either way.
        schedule: str = "mixed",
        # Per-step token budget the mixed policy sizes prefill bites
        # against: each fused step runs one decode leg per active slot
        # plus up to token_budget - n_active prompt tokens.  None = bites
        # stay prefill_chunk-sized; set, it also auto-chunks any prompt
        # longer than the budget even when prefill_chunk is unset.
        token_budget: int | None = None,
        # Multi-tenant weighted-fair admission (runtime/scheduler.py
        # TenantScheduler): "gold:4,free:1"-style weights (or a parsed
        # dict; "*" sets the default weight) turn the mixed policy into
        # per-tenant virtual-token-counter fairness — submit(tenant=)
        # bills each request against its tenant's counter.  None keeps
        # the tenant-blind policies.
        tenant_weights: "str | dict | None" = None,
        # Per-tenant RESIDENT-row cap: a tenant at the cap defers
        # admission (others admit past it), so one tenant can never hold
        # every batch slot.  None = uncapped.
        tenant_max_rows: int | None = None,
        # The LOCKSTEP CLOCK: the one time source scheduling DECISIONS
        # may consult (today: queue-deadline shedding in
        # _shed_expired_queued — submit(deadline=) timestamps are read
        # against it).  Defaults to time.perf_counter for single-process
        # engines; a multi-process harness injects a deterministic clock
        # (e.g. derived from the scheduling round counter) so every
        # process sheds the same requests in the same round — decision
        # paths reading the wall clock directly are a graftsync GS101
        # finding (LOCKSTEP_DECISIONS, runtime/scheduler.py).  The spans
        # and the starved-time account read this clock too: they are
        # observability, never decisions.
        clock: "Callable[[], float] | None" = None,
    ) -> None:
        # Snapshot the constructor arguments FIRST (before any local
        # variables or normalization appear) so respawn() can rebuild an
        # identical fresh batcher after an engine crash — params/tokenizer/
        # fault plane are shared by reference; caches and pools are rebuilt.
        self._ctor_args = {
            k: v for k, v in locals().items() if k not in ("self", "__class__")
        }
        # Injectable lockstep clock (see the ``clock`` parameter note):
        # decisions read self._clock(), never time.perf_counter() —
        # the reference (not a call) below is the single default-wiring
        # point.
        self._clock = clock if clock is not None else time.perf_counter
        kv_cache.refuse_unpaged_state(
            cfg, paged_pages=paged_pages, prefix_cache=prefix_cache,
            kv_bits=kv_bits == 8, host_pages=host_pages,
            speculative=draft_params is not None,
            prefill_chunk=prefill_chunk, token_budget=token_budget,
            mesh=parallel is not None,
        )
        if cfg.num_experts and cfg.moe_capacity:
            if parallel is None:
                # A served model never drops: under the capacity rule a
                # row's logits would depend on what its batch-mates chose.
                cfg = dataclasses.replace(cfg, moe_capacity=False)
            else:
                log.warning(
                    "expert layers keep their capacity rule on a mesh (the "
                    "expert-parallel path has no other): a row's output "
                    "may depend on its batch-mates"
                )
        if max_len > cfg.max_seq_len:
            raise ValueError(
                f"max_len {max_len} exceeds model max_seq_len {cfg.max_seq_len}"
            )
        if kv_bits not in (16, 8):
            raise ValueError(f"kv_bits must be 16 or 8, got {kv_bits}")
        if kv_bits == 8 and paged_pages is None:
            raise ValueError(
                "int8 KV pages live in the paged pool; pass paged_pages "
                "(contiguous caches stay full-width)"
            )
        if host_pages < 0:
            raise ValueError(f"host_pages must be >= 0, got {host_pages}")
        if host_pages and paged_pages is None:
            raise ValueError(
                "the host-RAM KV tier backs the paged pool; pass paged_pages"
            )
        if paged_pages is not None:
            if parallel is not None and not (
                parallel.pipelined or parallel.seq_parallel
            ):
                # Mesh-native paged serving: the pool shards its KV-head
                # axis over 'model' (models.kv_cache.pool_specs) and
                # the paged decode kernel runs per shard under shard_map
                # (ops/decode_attn.py) — each shard holds whole
                # heads, so the head count must divide.  Pipelined /
                # seq-parallel meshes fall through to the generic
                # rejection below (paged x pipelined stays unsupported
                # with the same message every batching mode gets).
                tp = parallel.mesh.shape.get("model", 1)
                if tp > 1 and cfg.num_kv_heads % tp:
                    raise ValueError(
                        f"paged KV on a tensor-parallel mesh shards the "
                        f"pool on the KV-head axis: num_kv_heads "
                        f"{cfg.num_kv_heads} must divide over 'model' "
                        f"({tp})"
                    )
            if cfg.model_window is not None:
                raise ValueError(
                    "paged KV cannot serve sliding-window models (the paged "
                    "decode kernel attends the full cache prefix); use "
                    "contiguous mode, which serves windowed models single-"
                    "device or on dp/tp meshes via the ragged kernel's "
                    "window band"
                )
            if max_len % page_size:
                raise ValueError(
                    f"max_len {max_len} must be a multiple of page_size "
                    f"{page_size}"
                )
            # Speculative rows need scratch-TAIL pages past max_len (the
            # verify window writes up to spec_k+1 slots beyond the
            # frontier — the paged analogue of the contiguous engine's
            # headroom slots), so a full-depth spec row holds a bit more
            # than max_len/page_size pages.
            _tail = spec_k + 1 if draft_params is not None else 0
            if paged_pages < -(-(max_len + _tail) // page_size) + 1:
                raise ValueError(
                    f"paged_pages {paged_pages} cannot hold even one "
                    f"full-depth row (+1 scratch page)"
                )
        if parallel is not None:
            if parallel.pipelined or parallel.seq_parallel:
                raise ValueError(
                    "continuous batching supports pure data/tensor-parallel "
                    "meshes; pipelined (wavefront) and sequence-parallel "
                    "(ring) meshes bring their own decode schedules"
                )
            dp = parallel.mesh.shape.get("data", 1)
            if batch_slots % dp:
                raise ValueError(
                    f"batch_slots {batch_slots} must divide over the mesh "
                    f"'data' axis ({dp})"
                )
        self.speculative = draft_params is not None
        if self.speculative:
            if draft_cfg is None:
                raise ValueError("draft_params needs draft_cfg")
            if parallel is not None:
                # The TARGET's KV rides the shared (shardable) pool in
                # paged mode, but the draft/verify chain itself has no
                # SPMD rule — spec x mesh stays fenced with a clear error
                # while spec x paged (prefix cache, int8 pages, the swap
                # tier, mixed budgets) composes since round 17.
                raise ValueError(
                    "speculative batching runs single-device (contiguous "
                    "or paged); serve mesh engines through the plain "
                    "batcher — the draft/verify chain has no SPMD rule"
                )
            # Engine-wide temperature/top_k/top_p compose with speculation
            # (distribution-preserving rejection sampling in spec_chunk);
            # only PER-REQUEST overrides are rejected (submit) — the
            # rejection test warps p and q with one static config.
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size}"
                )
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1, got {prefill_chunk}"
                )
            if self.speculative:
                # Paged mode composes since PR 3 (the prefill runs against
                # the pageless transient row; pages are allocated only at
                # the finishing splice) and dp/tp meshes compose since the
                # chunk step threads the mesh forward (pm) with its
                # last-logits replicated.  Only the speculative draft's
                # monolithic full-prompt admission remains incompatible.
                raise ValueError(
                    "chunked prefill does not compose with speculative "
                    "batching (the draft admission prefills the full "
                    "prompt monolithically)"
                )
        if prefill_concurrency < 1:
            # Validated regardless of prefill_chunk: a bad value must not
            # pass construction just because chunking happens to be off.
            raise ValueError(
                f"prefill_concurrency must be >= 1, got "
                f"{prefill_concurrency}"
            )
        if prefix_cache and paged_pages is None:
            raise ValueError(
                "automatic prefix caching runs over the paged KV pool; "
                "pass paged_pages (or use register_prefix for the "
                "contiguous named-prefix path)"
            )
        # The dispatch-ahead loop is mesh-legal, multi-process included
        # (PR 10 degraded it there with a warning): the device carry is
        # small scheduling state every chunk fn returns CONSTRAINED
        # REPLICATED (_replicated, like _fwd's mirrors), so a deferred
        # _sync_carry reads identical values on every process, and the
        # sync triggers themselves (_overlap_ok) consult only
        # deterministic host state the lockstep contract already keeps
        # identical (queue contents, prefills, imports, pool accounting —
        # never wall clocks).  No degrade needed.
        self.prefill_chunk = prefill_chunk
        self.prefill_concurrency = prefill_concurrency
        # THE scheduling policy (runtime/scheduler.py): every decision the
        # run loop takes — admission order, chunk sizing against the token
        # budget, victim selection, the pressure ladder, the overlap
        # sync-trigger list — delegates to this object's declared hooks.
        self.sched = make_scheduler(
            schedule, chunk_steps=chunk_steps, prefill_chunk=prefill_chunk,
            prefill_concurrency=prefill_concurrency,
            token_budget=token_budget, speculative=self.speculative,
            spec_adaptive=bool(spec_adaptive_k),
            tenant_weights=tenant_weights, tenant_max_rows=tenant_max_rows,
        )
        self._prefills: dict[int, _PendingPrefill] = {}  # slot -> pending
        self.draft_params = draft_params
        self.draft_cfg = draft_cfg
        self.spec_k = spec_k
        # Per-row acceptance-rate EMA (fraction of drafted tokens accepted
        # recently; optimistic 1.0 at admission so a fresh row drafts the
        # full k) + cumulative spec accounting for bench/tests — a pure
        # function of the committed stream, so downshifts are
        # deterministic run to run.
        self.spec_ema = np.ones((batch_slots,), np.float64)
        self.spec_stats = {
            "rounds": 0, "accepted": 0, "rejected": 0, "downshifts": 0,
        }
        self.pm = parallel
        self.cfg = cfg
        # Decode-chunk variant of the config: ragged decode attention (row b
        # reads only its cache prefix — ops/decode_attn.py) when the kernel
        # would actually run (TPU, or DLT_RAGGED_DECODE=kernel/interpret).
        # Meshes included: the ragged/paged kernels run per shard under
        # shard_map (ops/decode_attn.py — each shard its local head
        # slice).  Not on the CPU "fallback" mode, whose dense math is a
        # different op from the masked dot path (the exact-token invariant
        # is against the latter); that choice goes on the dispatch record
        # like any other fallback.
        from ..ops import dispatch

        # (Sliding-window models ride the ragged kernel too: it takes the
        # window bound and reads only [length - window, length) per row —
        # slot == position in this contiguous layout, so the slot-space
        # band equals the position-space window exactly.)
        if dispatch.attention_mode() != "fallback":
            self.cfg_decode = dataclasses.replace(cfg, ragged_decode=True)
        else:
            self.cfg_decode = cfg
            if paged_pages is None and cfg.attn_layers:  # (paged decode
                # records its own path; a model without keys has none)
                dispatch.record(
                    "ragged_decode", "fallback",
                    (batch_slots, max_len, cfg.num_heads, cfg.num_kv_heads,
                     cfg.head_dim_),
                )
        self.params = params
        self.tokenizer = tokenizer
        self.b = batch_slots
        self.s = max_len
        self.chunk_steps = chunk_steps
        self.sampling = dict(temperature=temperature, top_k=top_k, top_p=top_p)
        self.eos_id = eos_id
        self.pad_id = pad_id
        # Speculative mode reserves k+1 HEADROOM cache slots past max_len:
        # a near-capacity row's verify forward writes up to k+1 slots
        # beyond its frontier, and dynamic_update_slice CLAMPS an
        # overflowing start — without headroom the last committed slot's KV
        # would be silently overwritten with misaligned values (admission
        # capacity checks still enforce max_len; the extra slots are never
        # valid, never committed, only overwritten).
        cache_len = max_len + (spec_k + 1 if self.speculative else 0)
        if parallel is not None:
            # Mesh-sharded shared cache: 'data' on the batch axis, 'model'
            # on KV heads.  An explicit kv_dtype must not be silently
            # dropped: thread it onto the (frozen, so value-hashed — jit
            # keys stay stable) ParallelModel when it carries none, and
            # reject a conflict loudly.
            if kv_dtype is not None:
                want = jnp.dtype(kv_dtype).name
                if parallel.kv_dtype is None:
                    parallel = self.pm = dataclasses.replace(
                        parallel, kv_dtype=want
                    )
                elif jnp.dtype(parallel.kv_dtype).name != want:
                    raise ValueError(
                        f"kv_dtype {want!r} conflicts with the mesh's "
                        f"kv_dtype {parallel.kv_dtype!r}"
                    )
            if paged_pages is not None:
                # Mesh-sharded PAGE POOL: every leaf [L, NB, BLK, KVH, HD]
                # (and the int8 scale stacks) shards its KV-head axis over
                # 'model' — per-chip pool bytes divide by tp, so per-chip
                # row capacity multiplies by the mesh.  Built under jit so
                # zeros+constraint materialize the GLOBAL sharded pool
                # directly (same reasoning as the contiguous mesh cache
                # below).  Pages are shared across rows (prefix cache,
                # handoff imports), so no axis shards over 'data'.
                pm_built = parallel

                def build_pool():
                    return kv_cache.constrain(pm_built, kv_cache.make_pool(
                        cfg, paged_pages, page_size, kv_bits=kv_bits,
                        dtype=(jnp.dtype(parallel.kv_dtype)
                               if parallel.kv_dtype else None),
                    ))

                self.cache = jax.jit(build_pool)()
            else:
                # Under jit so the zeros+constraint build the GLOBAL
                # sharded cache directly — on a mesh spanning processes an
                # eager host-local zeros could not be constrained onto it.
                self.cache = jax.jit(
                    lambda: parallel.init_cache(batch_slots, max_len)
                )()
        elif paged_pages is not None:
            self.cache = kv_cache.make_pool(
                cfg, paged_pages, page_size, kv_bits=kv_bits,
                dtype=jnp.dtype(kv_dtype) if kv_dtype else None,
                slots=batch_slots,
            )
            sizes = kv_cache.format_bytes(self.cache, cfg)
            if "conv_state" in sizes:
                METRICS.set_gauge("batcher.conv_state_bytes",
                                  sizes["conv_state"])
            if "latent_page" in sizes:
                METRICS.set_gauge("batcher.latent_page_bytes",
                                  sizes["latent_page"])
            if "ssm_state" in sizes:
                METRICS.set_gauge("batcher.ssm_state_bytes",
                                  sizes["ssm_state"])
            if "gdn_state" in sizes:
                METRICS.set_gauge("batcher.gdn_state_bytes",
                                  sizes["gdn_state"])
            if "window_state" in sizes:
                METRICS.set_gauge("batcher.window_state_bytes",
                                  sizes["window_state"])
                METRICS.set_gauge(
                    "batcher.pool_token_bytes",
                    kv_cache.page_bytes(cfg, page_size) // page_size)
        else:
            self.cache = kv_cache.init_cache(
                cfg, batch_slots, cache_len,
                dtype=jnp.dtype(kv_dtype) if kv_dtype else None,
            )
            sizes = kv_cache.format_bytes(self.cache, cfg)
            if "ret_state" in sizes:
                METRICS.set_gauge("batcher.ret_state_bytes",
                                  sizes["ret_state"])
        if self.speculative:
            self.draft_cache = kv_cache.init_cache(
                draft_cfg, batch_slots, cache_len,
                dtype=jnp.dtype(kv_dtype) if kv_dtype else None,
            )
        self.page_size = page_size
        self.paged = paged_pages is not None
        self.kv_bits = kv_bits
        self.prefix_cache: PrefixCache | None = None
        self.pool: PagePool | None = None
        self.host_tier: HostTier | None = None
        self.faults = faults  # FaultPlane | None (runtime/faults.py)
        if self.paged:
            # Speculative page tables carry the scratch-tail pages too:
            # the verify window writes through slot real_lens + spec_k,
            # so a full-depth row's table must reach past max_len by the
            # k+1-token window (the contiguous engine's headroom slots,
            # as pages).
            self.pages_per_row = (
                -(-(max_len + spec_k + 1) // page_size)
                if self.speculative else max_len // page_size
            )
            if prefix_cache:
                self.prefix_cache = PrefixCache()
            if host_pages:
                self.host_tier = HostTier(host_pages)
            # Page 0 is the permanent scratch page: fixed-shape admissions
            # pad their page lists with it, and no row ever reads it.
            self.pool = PagePool(paged_pages, prefix_cache=self.prefix_cache,
                                 host_tier=self.host_tier)
            self.tables = np.zeros((batch_slots, self.pages_per_row), np.int32)
        # Scheduling state lives as HOST numpy mirrors: every process holds
        # the same values (the jitted chunk fns return them constrained
        # replicated, and np.asarray of a replicated output is legal on all
        # processes), and feeding numpy back in treats it as a replicated
        # input — no eager device ops on global arrays anywhere, which is
        # what keeps a multi-process mesh in lockstep.
        self.last_tok = np.zeros((batch_slots,), np.int32)
        self.real_lens = np.zeros((batch_slots,), np.int32)
        # Sized to the CACHE width (speculative mode pads k+1 headroom slots
        # past max_len; admission row_valid vectors come back cache-sized).
        # Paged mode keeps per-row logical width (the cache is a page pool).
        # Paged mode keeps per-row logical width (the target cache is a
        # page pool) — EXCEPT under speculation, where ``valid`` gates the
        # contiguous DRAFT cache's masks and must span its headroom slots.
        self.valid = np.zeros(
            (batch_slots,
             cache_len if (self.speculative or not self.paged) else max_len),
            bool,
        )
        self.active = np.zeros((batch_slots,), bool)
        self.budget = np.zeros((batch_slots,), np.int32)
        # Per-row sampling mirrors: rows admitted with explicit per-request
        # knobs diverge from the batcher config; decode chunks switch to
        # the traced per-row sampling path only while such a row is live.
        self.temp_row = np.full((batch_slots,), temperature, np.float32)
        self.topp_row = np.full((batch_slots,), top_p, np.float32)
        self.topk_row = np.full((batch_slots,), top_k, np.int32)
        self.pres_row = np.zeros((batch_slots,), np.float32)
        self.freq_row = np.zeros((batch_slots,), np.float32)
        # Constrained-decoding mirrors: each constrained row's automaton
        # state LOCAL to its own TokenDFA (the span plan rebases to
        # absolute stack indices), synced back from the device carry at
        # span end.  ``_con_stack`` memoizes the span's (bias, next,
        # offsets) stack across spans with an unchanged constraint mix.
        self.dfa_row = np.zeros((batch_slots,), np.int32)
        self._con_stack: tuple | None = None  # (key, bias_j, next_j, offs)
        self._dfa_carry: jax.Array | None = None  # device [B] abs states
        # Output-token histogram [B, V], allocated on the first penalized
        # admission (1 MB at 32k vocab — but zero cost for servers that
        # never see a penalty).
        self.tok_counts: jax.Array | None = None
        self.rows = [_RowState() for _ in range(batch_slots)]
        # Dispatch-ahead engine loop (overlap): per-batcher counts the
        # tests read directly, each beside its METRICS counter
        # (``batcher.overlap.*``).  ``_cancel_dirty`` flags a resident-row
        # cancel taken while the decode carry was device-resident — the
        # next chunk boundary must SYNC so the cancelled row actually
        # stops.
        self.overlap = bool(overlap)
        self.overlap_stats = {"dispatched_ahead": 0, "carry_syncs": 0}
        self._cancel_dirty = False
        self._tables_dirty = False
        # The admission side of the same switch: with ``overlap`` on a
        # round's admissions are pipelined one deep (:meth:`_admit_pending`),
        # and this is the one launched whose outputs nobody has fetched.
        self._admit_inflight: _Admission | None = None
        # The engine thread's account of the device (``_launch``,
        # ``_note_fetched``, ``_charge_starved``), on the batcher's clock:
        # model programs dispatched and, of them, those a blocking fetch
        # has shown complete (the device runs them in order, so one count
        # each); while the two are equal nothing is in flight and
        # ``_starved_at`` is since when (None while something is, and
        # before a run's first dispatch: a parked engine is not starved);
        # ``_loop_at`` names the batcher.loop.* span the thread is in.
        self._n_dispatched = 0
        self._n_fetched = 0
        self._starved_at: float | None = None
        self._loop_at: str | None = None
        # The admit clock: the seconds this thread has spent inside
        # ``batcher.loop.admit``, advanced where a round's span exits
        # (``_loop_exit``); ``_admit_t0`` is when the round it is in began
        # (None outside one).  A delivery stamps its reading on the
        # request's timeline, so the part of a gap that admission rounds
        # filled is a difference.
        self._admit_clock = 0.0
        self._admit_t0: float | None = None
        # Submission lock: the ONE cross-thread boundary of this class.
        # Serving front-ends submit() from their own thread while the
        # engine thread scans/admits; PR 3 relied on GIL-atomic deque ops
        # and list() snapshots for this, which graftlint's lock-discipline
        # rule (GL101) now rejects — every queue/_next_rid access below
        # holds this lock instead.  Held only for host bookkeeping, never
        # across a device call or a user callback.
        self._lock = threading.Lock()
        self.queue: deque[_Request] = deque()  # guarded-by: self._lock
        # Overload plane: rids shed while still queued (deadline expired
        # before admission) with the reason — serving front-ends read it at
        # the done delivery to answer 503 instead of a bare empty result.
        self.shed: dict[int, str] = {}
        self.preemptions = 0  # rows preempted for pool pressure (cumulative)
        self._admit_seq = 0   # monotone admission stamp (victim selection)
        self.results: dict[int, list[int]] = {}
        # Per-token logprobs of each finished request; same lifecycle as
        # ``results`` (speculative mode gathers them from verify logits).
        self.result_logprobs: dict[int, list[float]] = {}
        # Prompt tokens served from the automatic prefix cache, per rid —
        # set at admission, read by serving front-ends for usage reporting
        # (OpenAI prompt_tokens_details.cached_tokens); same lifecycle as
        # ``results``.
        self.prefix_cached_tokens: dict[int, int] = {}
        self.prefixes: dict[str, _Prefix] = {}
        self._rng = jax.random.key(seed)
        self._next_rid = 0  # guarded-by: self._lock
        self._on_tokens = None  # set per run() call (streaming callback)
        # KV-handoff plane (disaggregated serving): verified transfers
        # queued by the serving loop thread, adopted by the ENGINE thread
        # at the next scheduling-round boundary — the pool scatter is a
        # device call and the pool/prefix-cache bookkeeping is
        # engine-owned, exactly like admission.
        self._kv_imports: deque = deque()  # guarded-by: self._lock
        # Cross-replica pull plane: cached-run export requests queued by
        # the serving loop (/v1/kv_export), gathered by the ENGINE thread
        # at the next round boundary — the pool gather is a device call,
        # same ownership rule as imports.
        self._kv_exports: deque = deque()  # guarded-by: self._lock
        # The operator's slow-request record: the last FINISHED_KEEP
        # finished requests with where their time went (the gateway
        # serves it at GET /debug/requests; the supervisor carries it
        # over a respawn).  Engine thread appends, the serving loop reads.
        self.finished: deque[dict] = deque(maxlen=FINISHED_KEEP)  # guarded-by: self._lock

    # -- prefix caching ------------------------------------------------------

    def register_prefix(self, name: str, prefix: str | list[int]) -> None:
        """Prefill a shared prefix (e.g. a system prompt) ONCE; requests
        submitted with ``prefix=name`` reuse its KV instead of recomputing
        it — admission then prefills only the request's suffix."""
        kv_cache.refuse_unpaged_state(self.cfg, named_prefix=True)
        ids = (
            self.tokenizer.encode(prefix)
            if isinstance(prefix, str)
            else list(prefix)
        )
        if len(ids) >= self.s:
            raise ValueError(
                f"prefix ({len(ids)} tokens) does not fit slot capacity {self.s}"
            )
        # Contiguous mode: CACHE width, not self.s — speculative mode pads
        # headroom slots and the admission splice needs shape-matched rows.
        # Paged mode: the TABLE width (pages_per_row * page_size — equal to
        # self.s except under speculation, whose tables carry scratch-tail
        # pages), since kv_cache.write_row reshapes the row into exactly the
        # page-list's pages.
        width = (self.pages_per_row * self.page_size if self.paged
                 else self.cache.k.shape[-3])
        row_cache = kv_cache.init_cache(
            self.cfg, 1, width, dtype=kv_cache.row_dtype(self.cache)
        )
        positions = jnp.arange(len(ids), dtype=jnp.int32)[None, :]
        _, row_cache = _fwd(self.pm)(
            self.params, self.cfg, jnp.asarray([ids], jnp.int32),
            positions=positions, cache=row_cache, cache_index=jnp.int32(0),
        )
        self.prefixes[name] = _Prefix(ids, jax.block_until_ready(row_cache.k), row_cache.v)

    # -- paged pool allocator (PagePool; refcounted, prefix-cache LRU) -----

    @property
    def free_pages(self) -> list[int]:
        """The pool's free list (paged mode) — kept as a property so tests
        and callers that predate the PagePool extraction keep working."""
        return self.pool.free_pages

    @property
    def page_refs(self) -> dict[int, int]:
        return self.pool.page_refs

    def _pages_available(self) -> int:
        return self.pool.available()

    def _alloc_pages(self, n: int) -> list[int]:
        """Pool allocation with the spill tier in front: any LRU-cached
        page this alloc would hard-evict first has its content moved to
        the host tier (content-addressed by digest), so a later
        prefix-cache hit restores it instead of re-prefilling.  Best
        effort: a dry host budget (or a kv.spill drop drill) degrades to
        plain eviction — correct, just cold."""
        if self.host_tier is not None and n:
            self._spill_cold_pages(n)
        return self.pool.alloc(n)

    def _spill_cold_pages(self, n: int) -> None:
        """ENGINE THREAD, immediately before an alloc(n): park the
        eviction candidates' raw page bytes in the host tier.  The device
        gather is dispatched here; the D2H copy runs on the tier's worker
        thread — the pressure path never blocks on a host transfer."""
        cand = self.pool.eviction_candidates(n)
        if not cand:
            return
        if not self.host_tier.can_fit(1):
            # Saturated with swap parcels (never evicted for spills):
            # don't pay the device gather just for park_spill to refuse.
            return
        rule = (self.faults.fire("kv.spill", tag="out")
                if self.faults is not None else None)
        if rule is not None and rule.action == "drop":
            return
        corrupt = rule is not None and rule.action == "corrupt"
        pages = [p for p, _ in cand]
        payload = kv_cache.export_raw(
            self.cache, jnp.asarray(self._padded_page_list(pages))
        )
        parked = self.host_tier.park_spill(
            [d for _, d in cand], payload, corrupt=corrupt
        )
        if parked:
            METRICS.inc("batcher.host_tier.spilled_pages", parked)

    def _page_digests(self, ids: list[int], n_pages: int) -> list[bytes]:
        """This pool's content digests: chained over token ids AND the KV
        width (kv_bits salts the chain), so an int8 page can never alias
        a bf16 page across engines or tiers."""
        return PrefixCache.page_digests(ids, self.page_size, n_pages,
                                        kv_bits=self.kv_bits)

    def _padded_page_list(self, pages: list[int]) -> np.ndarray:
        """Pages padded with the scratch page 0 up the shared bucket
        ladder — the raw export/import jits take the padded width as a
        compile dimension, so page counts must walk the same closed
        ladder prompt lengths do (graftcheck GC4's discipline): a
        preemption storm over varied row lengths must never pay a fresh
        XLA compile per count on the engine thread.  Padded slots gather
        /scatter the scratch page, which no live row ever reads."""
        nb = min(_bucket(len(pages)), self.pages_per_row)
        out = np.zeros((nb,), np.int32)
        out[: len(pages)] = pages
        return out

    def _retain_page(self, p: int) -> None:
        self.pool.retain(p)

    def _release_pages(self, pages: list[int]) -> None:
        self.pool.release(pages)

    def capacity_tokens(self) -> int:
        """KV capacity in tokens: the denominator of the serving gateway's
        estimated-cost admission gate.  Paged mode counts usable pool pages
        (page 0 is scratch); contiguous mode counts slot-owned width."""
        if self.paged:
            return (self.pool.num_pages - 1) * self.page_size
        return self.b * self.s

    def assert_pool_consistent(self) -> None:
        """Audit the page pool against the resident rows (no-op in
        contiguous mode), and the host tier against the queued resume
        requests when one is armed — every swap parcel must be owned by
        exactly one queued request, or host RAM leaked.  The serving
        supervisor runs this after every engine restart; paged tests run
        it after each workload — a failure means refcounts, cache pins,
        or host parcels leaked, the recovery-path bug class this audit
        exists to catch."""
        if self.pool is not None:
            self.pool.assert_consistent(
                [r.pages for r in self.rows if r.pages],
                swap_handles=[
                    r.swap_handle for r in self.queue_snapshot()
                    if r.swap_handle is not None
                ],
            )

    # -- KV handoff (disaggregated prefill/decode) -------------------------

    def export_prefix_pages(
        self, ids: list[int]
    ) -> "tuple[list[bytes], np.ndarray, np.ndarray] | None":
        """ENGINE THREAD: gather the prompt's longest cached full-page run
        out of the pool for handoff to a decode-role engine.  Returns
        (chained page digests, k pages [L, P, BLK, KVH, HD], v pages) in
        host numpy, or None when nothing exportable is resident (prompt
        shorter than a page, caching off, or the run was evicted).  The
        run is capped one page short of the prompt — the importer's
        matcher caps hits the same way, so shipping the last partial page
        would be dead weight.  Pages are retained across the gather so
        pool pressure cannot reclaim them mid-export."""
        kv_cache.refuse_unpaged_state(self.cfg, kv_export=True)
        pc = self.prefix_cache
        if self.pool is None or pc is None:
            return None
        blk = self.page_size
        n = (len(ids) - 1) // blk
        if n < 1:
            return None
        digests = self._page_digests(ids, n)
        pages = pc.match(digests)
        if not pages:
            return None
        for p in pages:
            self._retain_page(p)
        try:
            row_k, row_v = kv_cache.gather_row(
                self.cache, jnp.asarray(np.asarray(pages, np.int32))
            )
            l, _one, _w, kvh, hd = row_k.shape
            k = np.asarray(row_k).reshape(l, len(pages), blk, kvh, hd)
            v = np.asarray(row_v).reshape(l, len(pages), blk, kvh, hd)
        finally:
            self._release_pages(pages)
        METRICS.inc("batcher.kv_pages_exported", len(pages))
        return digests[: len(pages)], k, v

    def has_kv_imports(self) -> bool:
        """Whether a verified handoff awaits adoption (any thread)."""
        with self._lock:
            return bool(self._kv_imports)

    def submit_kv_import(self, digests: list[bytes], k_pages, v_pages,
                         on_done) -> None:
        """Queue a VERIFIED transfer's pages for adoption (any thread —
        the decode server's KV listener calls this from the event loop).
        The engine thread applies it at its next round boundary and calls
        ``on_done(ok, reason)`` from there; the caller is responsible for
        waking the engine."""
        kv_cache.refuse_unpaged_state(self.cfg, kv_import=True)
        with self._lock:
            self._kv_imports.append((digests, k_pages, v_pages, on_done))

    def _drain_kv_imports(self) -> None:
        """ENGINE THREAD, at a scheduling-round boundary: adopt every
        queued handoff into the pool.  Device work and pool bookkeeping
        happen outside the submission lock (the lock is host-bookkeeping
        only, never held across a device call)."""
        while True:
            with self._lock:
                if not self._kv_imports:
                    return
                digests, k_pages, v_pages, on_done = \
                    self._kv_imports.popleft()
            ok, reason = self._import_kv_pages(digests, k_pages, v_pages)
            try:
                on_done(ok, reason)
            except Exception:
                log.exception("kv-import completion callback raised")

    def has_kv_exports(self) -> bool:
        """Whether a cached-run export awaits the engine (any thread)."""
        with self._lock:
            return bool(self._kv_exports)

    def submit_kv_export(self, ids: list[int], on_done) -> None:
        """Queue a cached-run export for the engine thread (any thread —
        the serving loop's /v1/kv_export handler calls this).  The engine
        gathers the prompt's longest cached full-page run at its next
        round boundary and calls ``on_done(payload_or_None)`` from there
        (the :meth:`export_prefix_pages` result); the caller is
        responsible for waking the engine."""
        kv_cache.refuse_unpaged_state(self.cfg, kv_export=True)
        with self._lock:
            self._kv_exports.append((list(ids), on_done))

    def _drain_kv_exports(self) -> None:
        """ENGINE THREAD, at a scheduling-round boundary: serve every
        queued cross-replica export.  Purely a cache read — nothing is
        admitted, no row state changes; a prompt whose run is not
        resident answers None (the puller recomputes locally)."""
        while True:
            with self._lock:
                if not self._kv_exports:
                    return
                ids, on_done = self._kv_exports.popleft()
            payload = self.export_prefix_pages(ids)
            try:
                on_done(payload)
            except Exception:
                log.exception("kv-export completion callback raised")

    def _import_kv_pages(self, digests, k_pages, v_pages):
        """Adopt one transfer: allocate pool pages, scatter the payload,
        publish the digests, and park the pages in the prefix-cache LRU —
        content-addressed and unreferenced, exactly like a completed local
        prompt's pages.  The handed-off request's admission then RETAINS
        them through the ordinary cache-hit path (refcounted on its
        _RowState, released on completion/cancel/preempt), and only its
        un-shipped suffix prefills.  Idempotent: digests already resident
        ack "duplicate" without touching the pool."""
        pc = self.prefix_cache
        if self.pool is None or pc is None:
            return False, "not a decode-role engine"
        l, _nb, blk, kvh, hd = self.cache.k.shape
        if (k_pages.shape != (l, len(digests), blk, kvh, hd)
                or v_pages.shape != k_pages.shape):
            return False, "pool shape mismatch"
        # Import only the pages whose content is NOT already addressable:
        # a duplicate delivery (retry racing a delayed ack) acks without
        # touching the pool, and a PARTIAL overlap (another transfer or a
        # local prompt already published a prefix of this chain) neither
        # demands capacity for pages it does not need nor pays a scatter
        # for content that would lose first-writer-wins anyway.
        missing = [i for i, d in enumerate(digests) if d not in pc.by_hash]
        if not missing:
            return True, "duplicate"
        if self._pages_available() < len(missing):
            return False, "no capacity"
        pages = self._alloc_pages(len(missing))
        # The scatter's page count is a compile dimension; distinct
        # overlap widths compile distinct (tiny) programs — bounded by
        # pages_per_row, and imports sit far off the decode hot path.
        self.cache = kv_cache.import_full(
            self.cache, jnp.asarray(np.asarray(pages, np.int32)),
            jnp.asarray(np.ascontiguousarray(k_pages[:, missing])),
            jnp.asarray(np.ascontiguousarray(v_pages[:, missing])),
            pm=self.pm,
        )
        for p, i in zip(pages, missing):
            # First writer wins: a digest published since the scan above
            # leaves ours private (it frees on the release below).
            self.pool.publish_prefix(p, digests[i])
        self._release_pages(pages)
        METRICS.inc("batcher.kv_pages_imported", len(pages))
        log.info("imported %d handed-off KV page(s) (%d already resident)",
                 len(pages), len(digests) - len(pages))
        return True, "imported"

    # -- crash recovery ----------------------------------------------------

    def respawn(self) -> "ContinuousBatcher":
        """A fresh batcher built from this one's construction arguments:
        new KV pool/cache and prefix cache, empty queue and rows, zeroed
        scheduling state.  This is the crash-recovery primitive: after
        ``run`` raises, the device state is unreconstructable (the jitted
        chunk programs donate the cache), so the supervisor discards the
        instance wholesale and re-admits work into a respawn.  Weights,
        tokenizer, and the fault plane carry over by reference; rid
        continuity (``_next_rid``) and named-prefix KV are the caller's to
        transplant."""
        return ContinuousBatcher(**self._ctor_args)

    # -- submission --------------------------------------------------------

    @property
    def next_rid(self) -> int:
        """The rid the next ``submit`` call will return.  Serving front-ends
        register their delivery state under this id BEFORE submitting:
        once ``submit`` appends to the queue, an engine thread already
        inside ``run()`` may admit the request and fire ``on_tokens``
        immediately — registering afterwards would race it.  Only valid
        when all submissions happen on one thread."""
        with self._lock:
            return self._next_rid

    def has_queued(self) -> bool:
        """Whether any request is waiting for admission (any thread)."""
        with self._lock:
            return bool(self.queue)

    def queue_snapshot(self) -> "list[_Request]":
        """Point-in-time copy of the submission queue, safe from any
        thread — serving front-ends read queued work (healthz, the
        estimated-cost gate) while the engine admits concurrently."""
        with self._lock:
            return list(self.queue)

    def submit(
        self, prompt: str | list[int], max_new_tokens: int = 32,
        prefix: str | None = None, temperature: float | None = None,
        top_p: float | None = None, top_k: int | None = None,
        presence_penalty: float = 0.0,
        frequency_penalty: float = 0.0, prefix_cache: bool = True,
        priority: int = 0, deadline: float | None = None,
        response_format: dict | None = None,
        logit_bias: dict | None = None,
        banned_tokens: list[int] | None = None,
        constraint: Any = None,  # pre-compiled constrain.TokenDFA — a
        #   serving front-end that already compiled OFF its event loop
        #   passes the automaton itself, closing the window where an LRU
        #   eviction between its compile and this submit would force a
        #   synchronous rebuild on the caller's thread
        tenant: str | None = None,  # multi-tenant QoS: the tenant this
        #   request bills against (weighted-fair admission order, virtual
        #   token counters, resident-row caps — runtime/scheduler.py
        #   TenantScheduler).  None = the anonymous bucket.
        pre_submit_s: float = 0.0,  # gateway: receipt -> this call, kept
        #   only for the finished-request record
    ) -> int:
        """Queue a request.  ``temperature``/``top_p``/``top_k`` override
        the batcher's sampling config FOR THIS REQUEST (serving
        front-ends: per-request sampling in a shared batch; per-row top_k
        rides a traced mask, no recompile per value).  None keeps the
        config value.  ``presence_penalty``/``frequency_penalty`` (OpenAI
        semantics, [-2, 2]) adjust logits against this request's own
        output tokens before sampling.  ``prefix_cache=False`` opts this
        request out of AUTOMATIC prefix caching (its prompt is neither
        matched against nor published into the shared page cache).

        ``response_format`` constrains the OUTPUT to a grammar
        (``{"type": "json_schema", "json_schema": {...}}`` or
        ``{"type": "regex", "regex": ...}``): the constraint compiles to
        a token-mask automaton (runtime/constrain.py; LRU-cached per
        (constraint, tokenizer) pair) applied as a traced per-row mask
        inside the shared decode step — constrained and free rows share
        one compiled program, and free neighbors' outputs are
        byte-identical to a constraint-free batch.  ``logit_bias``
        (token id -> [-100, 100]) and ``banned_tokens`` ride the SAME
        mask mechanism.  Malformed constraints raise
        :class:`~.constrain.ConstraintError` (a ValueError) here, before
        anything is queued.

        ``priority`` orders admission (higher first; FIFO within a
        priority) and shields the row from preemption by lower-priority
        work.  ``deadline`` is an ABSOLUTE time.perf_counter() timestamp:
        a request still queued past it is shed (``shed[rid]`` records the
        reason, results stay empty) instead of admitted doomed —
        single-device only; multi-process meshes ignore deadlines (clocks
        diverge across hosts and the admission loop must stay lockstep)."""
        ids = (
            self.tokenizer.encode(prompt)
            if isinstance(prompt, str)
            else list(prompt)
        )
        if not ids:
            # admit_row would sample the "first token" from a pad position.
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if temperature is not None:
            import math

            if not (math.isfinite(temperature) and temperature >= 0.0):
                raise ValueError(f"temperature must be >= 0, got {temperature}")
            if self.speculative and temperature != self.sampling["temperature"]:
                raise ValueError(
                    "speculative batching samples with the engine-wide "
                    f"temperature ({self.sampling['temperature']}); "
                    "per-request overrides are not supported (the rejection "
                    "test warps target and draft with one static config)"
                )
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if (top_p is not None and self.speculative
                and top_p != self.sampling["top_p"]):
            raise ValueError(
                "speculative batching samples with the engine-wide top_p "
                f"({self.sampling['top_p']}); per-request overrides are "
                "not supported"
            )
        if top_k is not None:
            # Upper bound: the per-row override rides an int32 traced
            # scalar — an unbounded Python int would overflow jnp.int32 at
            # admission and crash the engine thread instead of 400-ing.
            if isinstance(top_k, bool) or not isinstance(top_k, int) \
                    or not 0 <= top_k <= 2**31 - 1:
                raise ValueError(
                    f"top_k must be an int in [0, 2**31), got {top_k!r}"
                )
            if self.speculative and top_k != self.sampling["top_k"]:
                raise ValueError(
                    "speculative batching samples with the engine-wide "
                    f"top_k ({self.sampling['top_k']}); per-request "
                    "overrides are not supported"
                )
            eff_t = (self.sampling["temperature"] if temperature is None
                     else temperature)
            if eff_t == 0.0:
                # A greedy row takes the argmax regardless of top_k;
                # dropping the no-op override keeps the static decode
                # program (the traced per-row mask pays a per-step [B, V]
                # sort for output that cannot change).
                top_k = None
        if not isinstance(prefix_cache, bool):
            raise ValueError(
                f"prefix_cache must be a bool, got {prefix_cache!r}"
            )
        if isinstance(priority, bool) or not isinstance(priority, int) \
                or not -(2**31) <= priority < 2**31:
            raise ValueError(
                f"priority must be an int in [-2**31, 2**31), got {priority!r}"
            )
        if tenant is not None and (
            not isinstance(tenant, str) or not tenant or len(tenant) > 64
        ):
            raise ValueError(
                f"tenant must be a non-empty string of <= 64 chars, "
                f"got {tenant!r}"
            )
        if deadline is not None:
            import math

            if isinstance(deadline, bool) \
                    or not isinstance(deadline, (int, float)) \
                    or not math.isfinite(float(deadline)):
                raise ValueError(
                    f"deadline must be a finite perf_counter timestamp, "
                    f"got {deadline!r}"
                )
            deadline = float(deadline)
        for name, pen in (("presence_penalty", presence_penalty),
                          ("frequency_penalty", frequency_penalty)):
            if not -2.0 <= pen <= 2.0:  # also rejects NaN/inf
                raise ValueError(f"{name} must be in [-2, 2], got {pen}")
        if (response_format is not None or logit_bias is not None
                or banned_tokens is not None or constraint is not None):
            if self.speculative:
                raise ValueError(
                    "speculative batching does not support constrained or "
                    "biased sampling (response_format/logit_bias/"
                    "banned_tokens) yet — the draft/verify chain would "
                    "need the mask on both models; serve constrained "
                    "traffic through a plain engine"
                )
            if constraint is None:
                # Compiles (or LRU-hits — serving front-ends pre-compile
                # off this thread and pass ``constraint=``) the request's
                # token-mask automaton; malformed input raises
                # ConstraintError (a ValueError) here, before anything is
                # queued.
                constraint = constrain_lib.compile_request(
                    response_format, logit_bias, banned_tokens,
                    tokenizer=self.tokenizer,
                    vocab_size=self.cfg.vocab_size, eos_id=self.eos_id,
                )
        # Presence/frequency penalties serve everywhere the batcher does:
        # single-device, speculative, and GSPMD dp/tp meshes (the [B, V]
        # histogram rides decode_chunk replicated, like the rest of the
        # scheduling state).
        pfx_len = 0
        if prefix is not None:
            if prefix not in self.prefixes:
                raise KeyError(f"unknown prefix {prefix!r} (register_prefix first)")
            pfx_len = len(self.prefixes[prefix].ids)
        if pfx_len + len(ids) + max_new_tokens > self.s:
            raise ValueError(
                f"prompt ({pfx_len}+{len(ids)} tokens) + {max_new_tokens} new "
                f"exceeds slot capacity {self.s}"
            )
        now = self._clock()
        timeline = _Timeline(
            t_submit=now, t_queued=now, pre_submit_s=float(pre_submit_s),
            prompt_tokens=pfx_len + len(ids),
        )
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            self.queue.append(_Request(
                rid, ids, max_new_tokens, prefix=prefix,
                temperature=temperature, top_p=top_p, top_k=top_k,
                presence_penalty=float(presence_penalty),
                frequency_penalty=float(frequency_penalty),
                constraint=constraint,
                prefix_cache=prefix_cache, priority=priority,
                deadline=deadline, tenant=tenant, timeline=timeline,
            ))
        return rid

    def _span(self, name: str, **attrs) -> profiling.span:
        """A :class:`core.profiling.span` on the batcher's clock.  The six
        ``batcher.loop.*`` spans partition the engine thread's time, so
        each also says which of them the thread is in and, on exit, takes
        the starved time that fell in it (:meth:`_charge_starved`)."""
        on_exit = None
        if name.startswith("batcher.loop."):
            self._loop_at = name
            on_exit = self._loop_exit
        # graftlint: ignore[GL302](forwarded: GL302 checks the self._span("...") call sites)
        return profiling.span(name, clock=self._clock, on_exit=on_exit,
                              **attrs)

    def _loop_exit(self, now: float) -> None:
        """A ``batcher.loop.*`` span closes at ``now``: it takes the starved
        time that fell in it, and an admission round advances the admit
        clock by its length."""
        self._charge_starved(now)
        if self._admit_t0 is not None:  # (the loop's spans do not nest)
            self._admit_clock += now - self._admit_t0
            self._admit_t0 = None

    def _admit_now(self, now: float) -> float:
        """The admit clock at ``now``: the rounds that ended, and of the
        round the thread is in (an admission's first token is stamped
        inside its own round) the part up to ``now``."""
        t0 = self._admit_t0
        return self._admit_clock + (now - t0 if t0 is not None else 0.0)

    def _launch(self, program, *operands, **kw):
        """Dispatch a model program (an admission program, a prefill bite,
        a draft prefill, a decode / speculative / mixed chunk: what keeps
        the device busy until a later fetch; not ``_split_rng``'s split or
        a one-row scatter).  The caller's operands are built by now (Python
        evaluated them before this call), so if nothing was in flight the
        starved time ends HERE, at the call that dispatches.  The program's
        ticket for :meth:`_note_fetched` is ``_n_dispatched`` on return."""
        if self._starved_at is not None:
            self._charge_starved(self._clock())
            self._starved_at = None
        self._n_dispatched += 1
        return program(*operands, **kw)

    def _note_fetched(self, ticket: int) -> None:
        """A blocking fetch (under a ``*.wait_device`` span) returned an
        output of program ``ticket``: it and every program dispatched
        before it are complete.  If none was dispatched after it the
        device is starved from now until the next :meth:`_launch`.
        A lower bound of the device's idle time by construction: the
        device finished a little before the fetch returned and starts a
        little after the dispatch call."""
        self._n_fetched = max(self._n_fetched, ticket)
        if self._n_fetched == self._n_dispatched:
            self._starved_at = self._clock()

    def _charge_starved(self, now: float) -> None:
        """Add the time nothing has been in flight since it was last
        charged, up to ``now``, to the counter of the loop span the thread
        is in: a gap that crosses spans is split at their boundaries (each
        span's exit calls this).  What falls between two spans goes to the
        later one; ``wait_device`` has no counter (something is in flight
        whenever the thread enters it)."""
        if self._starved_at is None:
            return
        dt, self._starved_at = now - self._starved_at, now
        match self._loop_at:
            case "batcher.loop.admit":
                METRICS.inc("batcher.starved.admit_seconds", dt)
            case "batcher.loop.grow":
                METRICS.inc("batcher.starved.grow_seconds", dt)
            case "batcher.loop.plan":
                METRICS.inc("batcher.starved.plan_seconds", dt)
            case "batcher.loop.dispatch":
                METRICS.inc("batcher.starved.dispatch_seconds", dt)
            case "batcher.loop.deliver":
                METRICS.inc("batcher.starved.deliver_seconds", dt)

    def _fetch_admission(self, ticket: int, *outs) -> tuple:
        """The ONE blocking fetch of an admission: what the host needs of
        its outputs (first token, its logprob, and where the program
        hands them out the row's mask and the expert counts) in one
        ``jax.device_get``, after every dispatch the admission makes and,
        with ``overlap`` on, after the NEXT admission's launch
        (:meth:`_admit_pending`), so the fetch that returns leaves a program in
        flight and opens no starved interval but at a round's end.  The
        row spans' self time (``batcher.admit.row`` minus this span) is
        the host's part of the admissions."""
        with self._span("batcher.admit.wait_device"):
            host = jax.device_get(outs)
        self._note_fetched(ticket)
        return host

    def _note_resident(self, req: "_Request",
                       first_token: bool = False) -> None:
        """An admission ended (row resident, or finished by its admission
        token): close the timeline's admission part.  Its ``first_token``
        (a swap restore samples none) is a delivery.  A request's first
        OPENS its first gap: it stamps and observes nothing.  A resumed
        request has its stamps already (they live on the timeline), so the
        token its re-admission samples CLOSES the gap across the
        preemption, ONE gap with the requeue wait and the re-admission up
        to here inside it, its admit part every round that ran meanwhile;
        after a swap restore the resumed row's first chunk closes it."""
        tl = req.timeline
        now = self._clock()
        tl.admit_s += now - tl.t_admit
        if not first_token:
            return
        admit_now = self._admit_now(now)
        if tl.deliveries:
            gap, part = tl.deliver(now, admit_now)
            METRICS.observe("batcher.row.gap_seconds", gap)
            METRICS.inc("batcher.row.gap_admit_seconds", part)
        else:
            tl.t_delivered, tl.admit_at_delivered = now, admit_now
            tl.deliveries = 1

    def _note_finished(self, req: "_Request", out_tokens: int,
                       finish: str) -> None:
        """Append the request's record to the finished ring.  ``finish``
        is what the batcher saw: ``eos``/``length`` (ran to its end),
        ``cancelled`` (the gateway's timeout, stop string or disconnect),
        ``shed`` (queue deadline).  decode_ms is the rest of submit ->
        done once queue and admission are taken out: resident time,
        neighbours' admissions included.  ``stalled_ms`` is that part of
        it: of the intervals between the request's ``deliveries`` (its
        first token and every chunk that brought it a token), the time the
        engine thread was inside admission rounds (other requests', and
        after a preemption the start of its own re-admission);
        ``max_gap_ms`` is the longest of those intervals.  The interval
        that a cancel or a deadline cuts is in none of the three."""
        tl = req.timeline
        total = self._clock() - tl.t_submit
        rec = {
            "rid": req.rid, "tenant": req.tenant,
            "prompt_tokens": tl.prompt_tokens,
            "cached_tokens": tl.cached_tokens, "out_tokens": out_tokens,
            "pre_submit_ms": tl.pre_submit_s * 1e3,
            "queue_ms": tl.queue_s * 1e3, "admit_ms": tl.admit_s * 1e3,
            "decode_ms": max(0.0, total - tl.queue_s - tl.admit_s) * 1e3,
            "residencies": tl.residencies, "finish": finish,
            "deliveries": tl.deliveries, "max_gap_ms": tl.max_gap_s * 1e3,
            "stalled_ms": tl.stalled_s * 1e3,
        }
        with self._lock:
            self.finished.append(rec)

    def finished_requests(self, n: int = FINISHED_KEEP) -> list[dict]:
        """The last ``n`` finished requests, oldest first (any thread)."""
        with self._lock:
            recs = list(self.finished)
        return recs[-n:] if n > 0 else []

    def _drop_req_swap(self, req: "_Request") -> None:
        """Free a queued resume request's host swap parcel (cancel/shed:
        nothing will ever restore it)."""
        if req.swap_handle is not None and self.host_tier is not None:
            self.host_tier.drop_swap(req.swap_handle)
            req.swap_handle = None

    def cancel_row(self, rid: int) -> bool:
        """Cancel a submitted request (serving front-ends: client went away,
        or a stop sequence hit mid-row).  A queued request is dropped; an
        admitted row is deactivated and its slot freed for the next
        admission.  Either way ``results[rid]`` records whatever tokens had
        been committed (possibly none) and NO ``done=True`` callback fires
        for the rid — the canceller initiated this and already knows.

        Thread contract: call from ``run()``'s ``on_tokens`` callback
        (which executes between device chunks, on the thread driving
        ``run``) or while ``run()`` is not executing.  On a multi-process
        mesh every process must cancel the same rid in the same scheduling
        round, or the host scheduling mirrors diverge.

        Returns True if the rid was found queued or resident."""
        # Queue scan under the submission lock: a serving front-end may
        # append from its own thread mid-scan.
        dropped: _Request | None = None
        with self._lock:
            for req in self.queue:
                if req.rid == rid:
                    dropped = req
                    break
            if dropped is not None:
                self.queue.remove(dropped)
        if dropped is not None:
            # A preempted request waiting for recompute already emitted
            # (and streamed) a prefix — that IS its partial result.  A
            # swap-preempted one also frees its host parcel (nothing will
            # ever restore it — the tier audit would catch the leak).
            self._drop_req_swap(dropped)
            self.results[rid] = list(dropped.resume_emitted or [])
            self.result_logprobs[rid] = list(dropped.resume_lps or [])
            METRICS.inc("batcher.cancelled")
            self._note_finished(dropped, len(self.results[rid]), "cancelled")
            return True
        adm = self._admit_inflight
        if adm is not None and adm.req.rid == rid and not adm.cancelled:
            # Launched and not yet activated (the canceller runs in the
            # callback of the admission before it): its program runs to its
            # end on the chip, its token is never delivered.  The pages go
            # back now (the device orders later writers behind it, as behind
            # a cancelled row's last chunk), the slot when it is settled.
            adm.cancelled = True
            self.results[rid] = list(adm.req.resume_emitted or [])
            self.result_logprobs[rid] = list(adm.req.resume_lps or [])
            if adm.cached_pages or adm.pages:
                self._release_pages(adm.cached_pages + adm.pages)
                self.tables[adm.slot] = 0
            self.sched.note_freed(adm.req, len(self.results[rid]))
            self._note_finished(adm.req, len(self.results[rid]), "cancelled")
            METRICS.inc("batcher.cancelled")
            return True
        for i in range(self.b):
            row = self.rows[i]
            if row.rid == rid:
                if self.eos_id >= 0 and self.eos_id in row.emitted:
                    cut = row.emitted.index(self.eos_id) + 1
                    row.emitted = row.emitted[:cut]
                    row.lps = row.lps[:cut]
                self.results[rid] = row.emitted
                self.result_logprobs[rid] = row.lps
                if row.pages:
                    self._release_pages(row.pages)
                    self.tables[i] = 0
                # A chunked prefill in flight just drops its transient row
                # cache — nothing was spliced into the shared cache yet.
                self._prefills.pop(i, None)
                if row.req is not None:
                    self.sched.note_freed(row.req, len(row.emitted))
                    self._note_finished(row.req, len(row.emitted),
                                        "cancelled")
                self.rows[i] = _RowState()
                self.active[i] = False
                self.budget[i] = 0
                # If the decode carry is device-resident (dispatch-ahead
                # in flight), the device still believes this row is
                # active — force a carry sync at the next chunk boundary
                # so the cancel takes effect there, exactly as it does on
                # the synchronous path.
                self._cancel_dirty = True
                METRICS.inc("batcher.cancelled")
                return True
        return False

    # -- scheduling loop ---------------------------------------------------

    def _split_rng(self) -> jax.Array:
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _free_slot(self) -> int | None:
        taken = self._admit_inflight
        for i in range(self.b):
            if not self.active[i] and self.rows[i].rid is None \
                    and (taken is None or i != taken.slot):
                return i
        return None

    def _next_request(self) -> "_Request | None":
        """Admission order — the scheduler's ``admission_order`` hook,
        consulted under the submission lock (the serving loop thread
        appends concurrently).  Returns None on an empty queue."""
        with self._lock:
            return self.sched.admission_order(self.queue)

    def _unqueue(self, req: "_Request", behind: bool = False) -> None:
        """Remove an admitted request from the queue (identity compare —
        _Request is eq=False) under the submission lock.  This is the
        ONE admission-commit point (plain, chunked-start, and swap-
        restore paths all pass through it), so the scheduler's tenant
        accounting charges exactly once per residency here — the paired
        ``note_freed`` fires wherever the row later releases its slot
        (completion sweep, cancel, preemption).  Its queue wait ends here
        too, unless it is launched ``behind`` an admission still on the
        chip: then it ends when that one's fetch returns
        (:meth:`_settle_admission`)."""
        with self._lock:
            self.queue.remove(req)
        self.sched.note_admitted(req, len(req.ids) + req.max_new_tokens)
        req.timeline.residencies += 1
        if not behind:
            self._note_admit_start(req)

    def _note_admit_start(self, req: "_Request") -> None:
        """The request's wait in the queue is over and its admission
        begins: the chip is free to take it up."""
        tl = req.timeline
        tl.t_admit = self._clock()
        tl.queue_s += tl.t_admit - tl.t_queued
        METRICS.observe("batcher.queue_wait_seconds",
                        tl.t_admit - tl.t_queued)

    def _shed_expired_queued(self) -> None:
        """Drop queued requests whose deadline has already passed: a
        request that cannot possibly deliver a token before its deadline
        must be SHED (the client gets 503 + Retry-After from the serving
        gateway) rather than admitted doomed — admitting it would burn a
        prefill plus pool pages on work nobody is waiting for.  A
        PREEMPTED request waiting for recompute is different: it already
        streamed tokens, so it finishes with that partial output (the
        serving layer's own deadline reports ``finish_reason: "timeout"``)
        — shedding it would discard delivered work and falsely tell the
        client a retry is safe.  Reads the INJECTED lockstep clock
        (``self._clock``, default perf_counter), never the wall clock
        directly — the graftsync GS101 contract for this declared
        decision (LOCKSTEP_DECISIONS).  Multi-process meshes still skip
        it outright: the default clock diverges across hosts, and the
        admission loop must stay lockstep unless the harness injected a
        deterministic clock AND owns the deadline semantics."""
        if self.pm is not None:
            return
        now = self._clock()
        # Collect expired requests under the submission lock, then deliver
        # OUTSIDE it: the on_tokens callback may re-enter this class
        # (serving's cancel sweep calls cancel_row), which takes the lock.
        expired: list[_Request] = []
        with self._lock:
            for req in list(self.queue):
                if req.deadline is None or req.deadline > now:
                    continue
                self.queue.remove(req)
                expired.append(req)
        for req in expired:
            self._drop_req_swap(req)
            self.results[req.rid] = list(req.resume_emitted or [])
            self.result_logprobs[req.rid] = list(req.resume_lps or [])
            if req.resume_emitted:
                # Mid-generation expiry (preempted, then the deadline
                # lapsed while requeued): finish with the tokens already
                # streamed — they ARE the response.
                METRICS.inc("batcher.cancelled")
                log.info(
                    "finished preempted request %d at deadline with %d "
                    "token(s)", req.rid, len(req.resume_emitted),
                )
            else:
                self.shed[req.rid] = "queue deadline expired before admission"
                METRICS.inc("batcher.shed_total")
                log.info("shed queued request %d (deadline expired)", req.rid)
            self._note_finished(
                req, len(req.resume_emitted or []),
                "cancelled" if req.resume_emitted else "shed")
            if self._on_tokens is not None:
                self._on_tokens(req.rid, [], True, None)

    # -- overload plane: preemption + on-demand growth (paged mode) --------

    def _pick_victim(self, below_priority: int | None = None) -> int | None:
        """Victim selection — the scheduler's ``select_victim`` hook over
        the preemptable rows.  Rows holding no pool pages (chunked
        prefills in flight) are excluded — preempting them frees nothing.
        INACTIVE rows are excluded too: a row that finished at admission
        (max_new_tokens == 1, or EOS as its first token) still holds rid
        and pages until _collect's publish sweep — preempting it would
        requeue a COMPLETED request with a fresh 1-token budget and emit
        a token past its max_tokens/EOS; its pages free at the chunk
        boundary anyway."""
        cands = [
            (i, r.priority, r.admit_seq) for i, r in enumerate(self.rows)
            if r.rid is not None and r.pages and self.active[i]
        ]
        return self.sched.select_victim(cands, below_priority=below_priority)

    def _preempt_row(self, i: int, reason: str) -> None:
        """Preempt resident row ``i``: free its pages NOW, keep the tokens
        it already emitted, and requeue it for RECOMPUTE — the resume
        request prefills prompt + emitted prefix (cheap when the automatic
        prefix cache still holds the prompt pages; a resume long enough to
        take the CHUNKED prefill path consults the cache too and chunks
        only the un-cached suffix) and its admission token
        continues the sequence, so at temperature 0 the reunited stream is
        token-identical to an unpreempted run (pinned by
        tests/runtime/test_overload.py)."""
        if self.faults is not None:
            # Injection site "batcher.preempt": one hit per preemption —
            # a "raise" rule crashes mid-preemption (the supervisor-restart
            # drill for this path); tests read rule.fired for determinism.
            self.faults.fire("batcher.preempt")
        row = self.rows[i]
        req = row.req
        pp = self._prefills.pop(i, None)
        if pp is not None or row.prefilling:
            # Chunked prefill in flight: nothing reached the pool yet —
            # drop the transient row cache and requeue the request as-is.
            resume = req
        else:
            prior = list(req.resume_emitted or [])
            base_ids = (req.ids[: len(req.ids) - len(prior)]
                        if prior else req.ids)
            resume = _Request(
                req.rid, list(base_ids) + list(row.emitted),
                max(1, row.remaining), prefix=req.prefix,
                temperature=req.temperature, top_p=req.top_p,
                top_k=req.top_k, presence_penalty=req.presence_penalty,
                frequency_penalty=req.frequency_penalty,
                # The compiled automaton rides the resume request; its
                # state rebuilds from the emitted prefix at re-admission
                # (TokenDFA.advance), so the reunited stream stays
                # byte-exact under the same masks.
                constraint=req.constraint,
                prefix_cache=req.prefix_cache, priority=req.priority,
                deadline=req.deadline, tenant=req.tenant,
                resume_emitted=list(row.emitted),
                resume_lps=list(row.lps), timeline=req.timeline,
            )
            # SWAP tier (host_pages): park the victim's raw pages on the
            # host instead of throwing the prefix away — restore scatters
            # them back (byte-exact, no recompute).  A dry host budget or
            # a kv.swap_out drill leaves swap_handle None and the request
            # takes the recompute path above unchanged.  The swap rung is
            # the scheduler's to declare: a policy without it sends every
            # victim straight to exact recompute.
            handle = (self._swap_out_row(i, row)
                      if "swap_preempt" in self.sched.pressure_rungs()
                      else None)
            if handle is not None:
                resume.swap_handle = handle
                resume.swap_pages = len(row.pages)
                resume.swap_last_tok = int(self.last_tok[i])
                resume.swap_pos = int(self.real_lens[i])
        freed = len(row.pages)
        if row.pages:
            self._release_pages(row.pages)
            self.tables[i] = 0
        # Tenant accounting: this residency ends (the requeued resume
        # re-charges at its own re-admission).
        self.sched.note_freed(req, len(row.emitted))
        self.rows[i] = _RowState()
        self.active[i] = False
        self.budget[i] = 0
        resume.timeline.t_queued = self._clock()
        with self._lock:
            self.queue.append(resume)
        self.preemptions += 1
        METRICS.inc("batcher.preemptions_total")
        log.info(
            "preempted rid %d from slot %d (%s): freed %d page(s), "
            "%d token(s) kept for %s", resume.rid, i, reason, freed,
            len(resume.resume_emitted or []),
            "swap restore" if resume.swap_handle is not None else "recompute",
        )

    def _swap_out_row(self, i: int, row: "_RowState") -> int | None:
        """Try to park resident row ``i``'s raw pages in the host tier
        (swap-preemption).  Returns the parcel handle, or None to fall
        back to exact recompute (no tier, budget dry, or an injected
        kv.swap_out drop).  The device gather is dispatched here; the
        D2H copy runs on the tier's worker thread."""
        tier = self.host_tier
        if tier is None or not row.pages:
            return None
        rule = (self.faults.fire("kv.swap_out")
                if self.faults is not None else None)
        corrupt = False
        if rule is not None:
            if rule.action == "drop":
                METRICS.inc("batcher.kv_swaps.fallback")
                return None
            corrupt = rule.action == "corrupt"
        if not tier.can_fit(len(row.pages)):
            METRICS.inc("batcher.kv_swaps.fallback")
            return None
        payload = kv_cache.export_raw(
            self.cache, jnp.asarray(self._padded_page_list(row.pages))
        )
        handle = tier.park_swap(payload, len(row.pages), corrupt=corrupt)
        if handle is None:  # lost the budget race to nothing — advisory check
            METRICS.inc("batcher.kv_swaps.fallback")
            return None
        METRICS.inc("batcher.kv_swaps.out")
        return handle

    def _try_restore_swapped(self, i: int, req: "_Request") -> bool | None:
        """Restore a swap-preempted request into free slot ``i`` by
        scattering its parked raw pages back into freshly allocated pool
        pages — no model call, no token sampled: the row's device
        scheduling state is rebuilt verbatim and decode continues from
        ``swap_last_tok``, so the reunited stream is byte-exact against
        the never-preempted run at either KV width.

        Returns True on restore, False after degrading the request to
        exact recompute (parcel dropped/corrupted/missing — swap_handle
        cleared, request stays queued), None on back-pressure (nothing
        consumed; the caller stops admitting this round)."""
        tier = self.host_tier
        rule = (self.faults.fire("kv.swap_in")
                if self.faults is not None else None)
        if tier is None or (rule is not None and rule.action == "drop"):
            if tier is not None:
                tier.drop_swap(req.swap_handle)
            req.swap_handle = None
            METRICS.inc("batcher.kv_swaps.fallback")
            log.warning("swap restore for rid %d dropped; recomputing",
                        req.rid)
            return False
        corrupt = rule is not None and rule.action == "corrupt"
        n = req.swap_pages
        if not self._ensure_pages(n, "admit", below_priority=req.priority):
            return None  # parcel stays parked; retry next round
        payload = tier.take_swap(req.swap_handle, corrupt=corrupt)
        req.swap_handle = None
        if payload is None:
            METRICS.inc("batcher.kv_swaps.fallback")
            log.warning(
                "swap restore for rid %d failed verification; recomputing",
                req.rid,
            )
            return False
        self._unqueue(req)
        page_list = np.zeros((self.pages_per_row,), np.int32)
        pages = self._alloc_pages(n)
        page_list[:n] = pages
        self.tables[i] = page_list
        # The parcel was exported bucket-padded; scatter through the same
        # padded list (pad slots rewrite the scratch page — never read).
        self.cache = kv_cache.import_raw(
            self.cache, jnp.asarray(self._padded_page_list(pages)),
            *(jnp.asarray(a) for a in payload), pm=self.pm,
        )
        req_t = (self.sampling["temperature"] if req.temperature is None
                 else float(req.temperature))
        req_p = (self.sampling["top_p"] if req.top_p is None
                 else float(req.top_p))
        req_k = (self.sampling["top_k"] if req.top_k is None
                 else int(req.top_k))
        self.temp_row[i] = req_t
        self.topp_row[i] = req_p
        self.topk_row[i] = req_k
        self.pres_row[i] = req.presence_penalty
        self.freq_row[i] = req.frequency_penalty
        emitted = list(req.resume_emitted or [])
        if req.presence_penalty or req.frequency_penalty:
            # The penalty histogram must see every token this request has
            # emitted across residencies — identical rebuild to the
            # recompute-resume path in _activate_row.
            if self.tok_counts is None:
                self.tok_counts = jnp.zeros(
                    (self.b, self.cfg.vocab_size), jnp.int32
                )
            rowc = np.zeros((self.cfg.vocab_size,), np.int32)
            np.add.at(rowc, np.asarray(emitted, np.int64), 1)
            self.tok_counts = self.tok_counts.at[i].set(jnp.asarray(rowc))
        if req.constraint is not None:
            # Rebuild the row's automaton state by replaying the tokens it
            # already emitted — the state is a pure function of them, so a
            # swap-restored constrained row continues under the exact
            # masks the unpreempted run would have seen.
            self.dfa_row[i] = req.constraint.advance(0, emitted)
        if self.speculative:
            # Rebuild the DRAFT cache from prompt + emitted: the draft is
            # never swapped (small, quantized, contiguous) — one KV-only
            # prefill of the first swap_pos tokens restores exactly the
            # resident-KV invariant (the newest emitted token's KV is
            # written by the round that consumes it, for both caches), so
            # the reunited spec stream is byte-exact vs the never-
            # preempted run.  req.ids already holds prompt + emitted.
            seed_ids = req.ids[: req.swap_pos]
            td = min(_bucket(len(seed_ids)), self.s)
            dprompt = np.full((td,), self.pad_id, np.int32)
            dprompt[: len(seed_ids)] = seed_ids
            self.draft_cache = self._launch(
                admit_row_kv,
                self.draft_params, self.draft_cfg, self.draft_cache,
                jnp.int32(i), jnp.asarray(dprompt),
                jnp.int32(len(seed_ids)),
            )
            self.spec_ema[i] = 1.0
        self.last_tok[i] = req.swap_last_tok
        self.real_lens[i] = req.swap_pos
        self.valid[i] = np.arange(self.valid.shape[1]) < req.swap_pos
        self.active[i] = True
        # No admission token on a swap restore: max_new_tokens IS the
        # remaining budget (set by _preempt_row from row.remaining).
        self.budget[i] = req.max_new_tokens
        self._admit_seq += 1
        self.rows[i] = _RowState(
            rid=req.rid, emitted=emitted, lps=list(req.resume_lps or []),
            remaining=req.max_new_tokens, pages=pages, req=req,
            priority=req.priority, admit_seq=self._admit_seq,
            streamed=len(emitted),
        )
        METRICS.inc("batcher.kv_swaps.in")
        log.info("restored swapped rid %d into slot %d (%d page(s))",
                 req.rid, i, n)
        self._note_resident(req)
        return True

    def _ensure_pages(self, need: int, tag: str,
                      below_priority: int | None = None,
                      self_slot: int | None = None) -> bool:
        """THE pressure loop (one definition for admission, chunked-finish
        and growth): fire the ``batcher.page_alloc`` fault site (an
        ``exhaust`` rule simulates a dry pool), then preempt victims until
        :meth:`available` covers ``need`` pages.  ``below_priority``
        restricts victims to STRICTLY lower priority (the admission paths:
        a newcomer never preempts its own class, which would livelock two
        requests trading the same pages); ``self_slot`` is the growth
        path's fallback — with no other victim the grower itself yields
        (requeued for recompute so higher-priority residents keep their
        pages).  Returns True when ``need`` pages are obtainable (the
        caller allocs); False on back-pressure or self-preemption
        (nothing was allocated)."""
        rule = (self.faults.fire("batcher.page_alloc", tag=tag)
                if self.faults is not None else None)
        exhaust = rule is not None and rule.action == "exhaust"
        avail = 0 if exhaust else self._pages_available()
        if avail < need and self._admit_inflight is not None:
            # Victims are chosen among the rows the serial order would
            # show: the admission in flight becomes one first, and what its
            # callback cancels gives its pages back.
            self._drain_admission()
            avail = 0 if exhaust else self._pages_available()
        while avail < need:
            v = self._pick_victim(below_priority=below_priority)
            if v is None:
                if self_slot is None:
                    return False
                v = self_slot  # no other victim: the grower itself yields
            self._preempt_row(
                v, "admission" if below_priority is not None else "growth"
            )
            if v == self_slot:
                return False
            avail = self._pages_available()
        return True

    def _host_restorable(self, digests: list[bytes], start: int,
                         cap: int) -> list[bytes]:
        """The consecutive digest run PAST the device-cached run whose
        pages are parked in the host spill tier — candidates for restore
        instead of re-prefill."""
        if self.host_tier is None:
            return []
        out: list[bytes] = []
        for d in digests[start:cap]:
            if not self.host_tier.has_spill(d):
                break
            out.append(d)
        return out

    def _match_tiered(self, digests: list[bytes], cap: int,
                      n_init: int = 0) -> list[int]:
        """The longest cached page run across BOTH tiers: alternate
        device matches (retained) and host-tier restores until the chain
        breaks.  LRU eviction reclaims a run's OLDEST (head) pages first,
        so the common spill shape is a host-parked head in front of a
        still-resident tail — a device-only match would miss the whole
        run.  Restores use spare capacity only (``n_init`` is what the
        admission itself still needs; restores never preempt live rows),
        and restored pages come back row-held + published, so a
        back-pressured caller releasing them simply parks them in the
        device LRU — addressable again, nothing leaks."""
        pc = self.prefix_cache
        pages = pc.match(digests[:cap])
        for p in pages:
            self._retain_page(p)
        k = len(pages)
        while k < cap:
            run = self._host_restorable(digests, k, cap)
            if not run:
                break
            spare = self._pages_available() - max(
                0, n_init - k - len(run)
            )
            restored = self._restore_spilled_run(run[: max(spare, 0)])
            if not restored:
                break
            pages += restored
            k += len(restored)
            if len(restored) < len(run):
                break
            more = pc.match(digests[k:cap])
            if not more:
                break
            for p in more:
                self._retain_page(p)
            pages += more
            k += len(more)
        return pages

    def _restore_spilled_run(self, run: list[bytes]) -> list[int]:
        """Scatter a host-spilled digest run back into freshly allocated
        pool pages and publish the digests — the pages come back exactly
        as they left (raw bytes), so a hit over them is byte-identical to
        a hit over never-evicted pages.  The caller guarantees pool
        availability (restores never preempt live rows: a cache restore
        must not evict live work).  Returns the restored page list (a
        prefix of ``run``; empty when a kv.spill restore drill drops it
        or verification fails on the first page)."""
        if not run:
            return []
        rule = (self.faults.fire("kv.spill", tag="restore")
                if self.faults is not None else None)
        if rule is not None and rule.action == "drop":
            return []
        payloads = []
        for d in run:
            got = self.host_tier.take_spill(d)
            if got is None:
                break
            payloads.append(got)
        if not payloads:
            return []
        pages = self._alloc_pages(len(payloads))
        # Stack the per-page parcels and pad BOTH the payload and the
        # destination list up the bucket ladder (pad slots target the
        # scratch page) — restore counts must not compile per width.
        padded = self._padded_page_list(pages)
        nb = padded.shape[0]

        def stack(j):
            s = np.stack([p[j] for p in payloads], axis=1)
            if nb > s.shape[1]:
                s = np.concatenate(
                    [s, np.zeros((s.shape[0], nb - s.shape[1]) + s.shape[2:],
                                 s.dtype)], axis=1,
                )
            return jnp.asarray(s)

        self.cache = kv_cache.import_raw(
            self.cache, jnp.asarray(padded),
            *(stack(j) for j in range(len(payloads[0]))), pm=self.pm,
        )
        for pg, d in zip(pages, run):
            self.pool.publish_prefix(pg, d)
        METRICS.inc("batcher.host_tier.restored_pages", len(pages))
        METRICS.inc("batcher.host_tier.hits")
        return pages

    def _reserve_row_pages(self, i, req, total_len, pfx):
        """Paged admission reservation, ON-DEMAND: pages for the prompt
        plus one decode page — NOT the full prompt+budget footprint (PR 1's
        policy), which left most reserved pages empty while the queue
        back-pressured.  The chunk-boundary growth loop (:meth:`_grow_rows`)
        allocates the rest only as the row actually reaches them.  A dry
        pool first evicts LRU-cold cached pages (inside alloc, spilling
        their content to the host tier first when one is armed), then
        preempts a STRICTLY lower-priority victim (swap-out when the host
        budget allows, exact recompute otherwise), then back-pressures.
        A prompt whose cached run was evicted to the HOST tier restores
        those pages here (no preemption — restores only use spare
        capacity) and counts them as cache hits.
        Returns (page_list, pages, cached_pages, cached_len, digests), or
        None on back-pressure (nothing allocated, hits released)."""
        blk = self.page_size
        n_full = -(-(total_len + req.max_new_tokens) // blk)
        n_init = min(n_full, -(-total_len // blk) + 1)
        pc = self.prefix_cache
        auto = pc is not None and pfx is None and req.prefix_cache
        cached_pages: list[int] = []
        cached_len = 0
        digests: list[bytes] = []
        if auto:
            # Hash every FULL prompt page (chained digests, memoized on
            # the request — a back-pressured admission retries every round
            # and must not rehash a long prompt each time); hits are
            # capped one page short of the whole prompt so at least one
            # real suffix token always prefills (the admission samples the
            # first token from its logits).  The match walks BOTH tiers
            # (device hits retained, host-spilled pages restored), so an
            # LRU-evicted head no longer hides a resident tail.
            if req.digests is None:
                req.digests = self._page_digests(
                    req.ids, len(req.ids) // blk
                )
            digests = req.digests
            cap = (len(req.ids) - 1) // blk
            cached_pages = self._match_tiered(digests, cap, n_init=n_init)
            cached_len = len(cached_pages) * blk
        need = n_init - len(cached_pages)
        if not self._ensure_pages(need, "admit", below_priority=req.priority):
            # Restored pages release to the device LRU (still
            # addressable); retained hits just drop our reference.
            self._release_pages(cached_pages)
            return None
        if auto:
            pc.record_lookup(cached_len, total_len - cached_len)
        # Build the table BEFORE allocating: the allocation is the last
        # thing that can raise, so no exception path exists between the
        # pool handing out pages and the row table owning them (graftflow
        # GF301 — the refcount-leak shape the pool audit only catches
        # after the fact).
        page_list = np.zeros((self.pages_per_row,), np.int32)
        page_list[: len(cached_pages)] = cached_pages
        pages = self._alloc_pages(need)
        page_list[len(cached_pages): n_init] = pages  # + scratch pad
        self.tables[i] = page_list
        return page_list, pages, cached_pages, cached_len, digests

    def _grow_rows(self) -> None:
        """Chunk-boundary page growth (paged mode): before each decode
        chunk, every active row that will write past its allocated pages
        this chunk gets the missing pages — evicting LRU-cold cached pages
        first, then preempting the lowest-priority / most-recently-admitted
        victim (possibly the growing row itself: it requeues for recompute
        and higher-priority residents keep their pages)."""
        with self._span("batcher.loop.grow"):
            blk = self.page_size
            for i in range(self.b):
                row = self.rows[i]
                if row.rid is None or not self.active[i] or row.prefilling:
                    continue
                if self.speculative:
                    # The verify window writes slots real_lens..real_lens+k
                    # REGARDLESS of budget (rollback clamps commits, not
                    # writes) — pages must cover the whole window before the
                    # round dispatches, exactly the contiguous engine's
                    # headroom contract.
                    horizon = int(self.real_lens[i]) + self.spec_k + 1
                else:
                    horizon = int(self.real_lens[i]) + min(
                        self.chunk_steps, int(self.budget[i])
                    )
                need_pages = -(-horizon // blk)
                have = len(row.pages)
                if need_pages <= have:
                    continue
                n = need_pages - have
                # The fault site (tag "grow") fires only when a row actually
                # needs new pages, so rule windows count real allocation
                # attempts.
                if not self._ensure_pages(n, "grow", self_slot=i):
                    continue  # the grower itself was preempted
                fresh = self._alloc_pages(n)
                row.pages.extend(fresh)
                self.tables[i][have:need_pages] = fresh
                METRICS.inc("batcher.pages_grown", n)

    def _admit_pending(self) -> None:
        with self._span("batcher.loop.admit"):
            self._admit_t0 = self._clock()
            if self.faults is not None:
                # Injection site "batcher.admit": one hit per admission round.
                self.faults.fire("batcher.admit")
            # Adopt handed-off KV pages FIRST: a transfer that raced this
            # round's admissions should be matchable by them.  Then serve
            # cross-replica export requests — after imports, so a freshly
            # landed run is immediately re-exportable.
            self._drain_kv_imports()
            self._drain_kv_exports()
            self._shed_expired_queued()
            # Advance pending chunked prefills.  ALTERNATE: one serialized
            # prefill_chunk_step bite per prefill per round (up to
            # prefill_concurrency * prefill_chunk stall tokens).  MIXED:
            # while decode rows are live, bites ride the fused span instead
            # (_decode_span), so only completed prompts run their finishing
            # splice here; with no decode rows live the classic advance
            # runs.  Re-evaluated per slot: a finishing splice earlier in
            # this loop activates a decode row, and later bites must then
            # ride the span, not stall it.
            for slot in list(self._prefills):
                fused = self.sched.fuse_prefill() and bool(self.active.any())
                self._advance_chunk(slot, advance=not fused)
            # The round's admissions, pipelined one deep: launch admission
            # k+1, THEN fetch and activate k (its program has been running
            # all the while, and the device takes k+1 up behind it through
            # the donated cache), then select k+2.  So the host's part of a
            # row (operands, the dispatch call, the activation and the
            # stream callback of the one before, the selection and the pages
            # of the one after) runs with a program in flight; what stays
            # exposed in a round is its first selection and launch and its
            # last activation.  Every fetch lies inside a row span: the
            # predecessor's after this launch (``fetched_rid``), an
            # admission's own where nothing follows it at once (the round
            # ends, or a swap restore or a chunked start comes next: those
            # run serially, as they did).  :meth:`_select_admission` is
            # where that is decided, and the one place that settles an
            # admission for what comes next; ``overlap`` off settles each
            # before the next is selected: the serial order, the control
            # of tests/runtime/test_admit_pipeline.py.  An exception leaves
            # through the ``finally``, which settles what is in flight.
            # The programs are dispatched from THIS frame: an admission
            # program's lowering is a quarter slower from two frames deeper
            # (PERF.md section 6, PR 44), and that is set-up time.
            try:
                adm = self._select_admission()
                while adm is not None:
                    if adm.serial == "swap":
                        # Swap-preempted resume: scatter the parked pages back
                        # instead of recomputing the prefix.  True = restored
                        # (the next selection admits more); False = the parcel
                        # was unusable and the request fell through to
                        # recompute (still queued, swap_handle cleared:
                        # selected again); None = back-pressure, stop this
                        # round.
                        if self._try_restore_swapped(adm.slot,
                                                     adm.req) is None:
                            return
                        adm = self._select_admission()
                        continue
                    if adm.serial == "chunked":
                        self._unqueue(adm.req)
                        self._start_chunked(adm.slot, adm.req, adm.pfx)
                        adm = self._select_admission()
                        continue
                    i, req, pfx = adm.slot, adm.req, adm.pfx
                    pfx_len = len(pfx.ids) if pfx else 0
                    total_len, page_list = adm.total_len, adm.page_list
                    cached_pages, cached_len = adm.cached_pages, adm.cached_len
                    digests = adm.digests
                    # A fresh row attends among its own bucket of tokens; one
                    # behind a prefix (named or cached) scores the suffix
                    # against every slot of the row cache.
                    fresh = pfx is None and not cached_len
                    bucket = _bucket(len(req.ids) - cached_len)
                    # The rows of the bucket the quantized matmuls compute:
                    # the row tiles that hold a token of the prompt (or
                    # suffix).
                    live = live_rows(bucket, len(req.ids) - cached_len)
                    # The (query, key) pairs the flash kernel's tiles hold
                    # over a fresh row's bucket, and those it scores: the
                    # tiles of queries that hold a token of the prompt.
                    pairs, pairs_live = (
                        model_lib.self_attention_pairs(
                            self.cfg, min(bucket, self.s), len(req.ids))
                        if fresh and self.pm is None else (0, 0))
                    # The slots a layer scores for a row's continuation
                    # (the suffix's bucket behind a named or cached
                    # prefix): the row cache's every slot, or the tiles of
                    # keys the flash kernel fetches.
                    held = pfx_len + cached_len
                    behind = min(bucket, self.s - held)  # the suffix's bucket
                    cont_keys = 0 if fresh else (
                        self.s if self.pm is not None
                        else model_lib.continuation_keys(
                            self.cfg, behind, self.s, held + behind))
                    prev = self._admit_inflight
                    ahead = {} if prev is None else {"fetched_rid": prev.req.rid}
                    if self.cfg.scan_chunk:  # the chunks the scan walks
                        ahead["chunks"] = -(-total_len // self.cfg.scan_chunk)
                    with self._span(
                        "batcher.admit.row", rid=req.rid,
                        prompt_tokens=total_len, cached_tokens=cached_len,
                        bucket=bucket, live_rows=live,
                        attn_pairs_live=pairs_live,
                        key_slots=(min(bucket, self.s) if fresh
                                   else cont_keys),
                        **ahead,
                    ):
                        METRICS.inc("batcher.admit.matmul_rows", bucket)
                        METRICS.inc("batcher.admit.matmul_rows_live", live)
                        METRICS.inc("batcher.admit.attn_pairs", pairs)
                        METRICS.inc("batcher.admit.attn_pairs_live", pairs_live)
                        METRICS.inc("batcher.admit.cont_keys", cont_keys)
                        METRICS.inc("batcher.admit.cont_keys_live",
                                    0 if fresh else total_len)
                        if fresh:
                            METRICS.inc("batcher.admit.self_attention")
                        else:
                            METRICS.inc("batcher.admit.row_cache_attention")
                        self._unqueue(req, behind=prev is not None)
                        self._note_unmatched(req, pfx)
                        # Bucket for compile reuse, but never past what fits after the
                        # prefix: forward's contract is cache_index + T <= max_len, and
                        # dynamic_update_slice CLAMPS an overflowing start — the suffix
                        # K/V would land misaligned with its mask/positions, silently
                        # corrupting the row.  (submit() guaranteed the real prompt fits.)
                        tp = min(_bucket(len(req.ids)), self.s - pfx_len)
                        prompt = np.full((tp,), self.pad_id, np.int32)
                        prompt[: len(req.ids)] = req.ids
                        # Per-request sampling: traced scalar overrides (no recompile
                        # per value) only when the request diverges from the config.
                        req_t = (self.sampling["temperature"] if req.temperature is None
                                 else float(req.temperature))
                        req_p = (self.sampling["top_p"] if req.top_p is None
                                 else float(req.top_p))
                        req_k = (self.sampling["top_k"] if req.top_k is None
                                 else int(req.top_k))
                        custom = (req_t != self.sampling["temperature"]
                                  or req_p != self.sampling["top_p"]
                                  or req_k != self.sampling["top_k"])
                        extra = (
                            dict(temp_req=jnp.float32(req_t), topp_req=jnp.float32(req_p))
                            if custom else {}
                        )
                        if custom and req_k != self.sampling["top_k"]:
                            extra["topk_req"] = jnp.int32(req_k)
                        if req.constraint is not None:
                            # The first output token draws under the automaton's
                            # start-state mask (a resumed request replays its emitted
                            # prefix to recover the state first).
                            st0 = req.constraint.advance(0, req.resume_emitted or [])
                            extra["mask_req"] = jnp.asarray(req.constraint.bias[st0])
                        moe: list = []
                        if self.paged and pfx is not None:
                            self.cache, tok, lp = self._launch(
                                admit_row_with_prefix_paged, self.params, self.cfg, self.cache, jnp.asarray(page_list),
                                pfx.k, pfx.v, jnp.int32(pfx_len),
                                jnp.asarray(prompt), jnp.int32(len(req.ids)),
                                self._split_rng(), pm=self.pm, **self.sampling, **extra,
                            )
                            row_valid = np.arange(self.valid.shape[1]) < total_len
                        elif self.paged and cached_len:
                            # Prefix-cache HIT: the cached run seeds the row through a
                            # pool gather; only the suffix prefills.  Writes for the
                            # cached positions are routed to the scratch page — shared
                            # pages are read-only while any row references them.
                            write_list = page_list.copy()
                            write_list[: len(cached_pages)] = 0
                            suffix = req.ids[cached_len:]
                            tc = min(_bucket(len(suffix)), self.s - cached_len)
                            chunk = np.full((tc,), self.pad_id, np.int32)
                            chunk[: len(suffix)] = suffix
                            self.cache, tok, lp, *moe = self._launch(
                                admit_row_auto_paged,
                                self.params, self.cfg, self.cache,
                                jnp.asarray(page_list), jnp.asarray(write_list),
                                jnp.int32(cached_len), jnp.asarray(chunk),
                                jnp.int32(len(suffix)), self._split_rng(),
                                pm=self.pm, **self.sampling, **extra,
                            )
                            row_valid = np.arange(self.valid.shape[1]) < total_len
                        elif self.paged:
                            if self.cfg.family == "hybrid":
                                extra["slot"] = jnp.int32(i)
                            self.cache, tok, lp, *moe = self._launch(
                                admit_row_paged,
                                self.params, self.cfg, self.cache, jnp.asarray(page_list),
                                jnp.asarray(prompt), jnp.int32(len(req.ids)),
                                self._split_rng(), pm=self.pm, **self.sampling, **extra,
                            )
                            row_valid = np.arange(self.valid.shape[1]) < total_len
                        elif pfx is not None:
                            self.cache, tok, row_valid, lp = self._launch(
                                admit_row_with_prefix,
                                self.params, self.cfg, self.cache, jnp.int32(i),
                                pfx.k, pfx.v, jnp.int32(pfx_len),
                                jnp.asarray(prompt), jnp.int32(len(req.ids)),
                                self._split_rng(), pm=self.pm, **self.sampling, **extra,
                            )
                        else:
                            self.cache, tok, row_valid, lp, *moe = self._launch(
                                admit_row,
                                self.params, self.cfg, self.cache, jnp.int32(i),
                                jnp.asarray(prompt), jnp.int32(len(req.ids)),
                                self._split_rng(), pm=self.pm, **self.sampling, **extra,
                            )
                        ticket = self._n_dispatched
                        if digests:
                            # Publish the row's full prompt pages (first writer wins;
                            # a digest another page already holds leaves ours private).
                            # Pages inside the cached run are already published; the
                            # fresh ones now hold exactly the hashed content — the
                            # admission scatter just wrote it.
                            for j in range(len(cached_pages), len(digests)):
                                self.pool.publish_prefix(int(page_list[j]), digests[j])
                        if self.speculative:
                            # Seed the DRAFT cache for this row: full prompt (prefix
                            # caching stores only target KV, so the draft prefills
                            # prefix + suffix; bucketed for compile reuse).
                            full_ids = (pfx.ids if pfx else []) + req.ids
                            td = min(_bucket(len(full_ids)), self.s)
                            dprompt = np.full((td,), self.pad_id, np.int32)
                            dprompt[: len(full_ids)] = full_ids
                            self.draft_cache = self._launch(
                                admit_row_kv,
                                self.draft_params, self.draft_cfg, self.draft_cache,
                                jnp.int32(i), jnp.asarray(dprompt),
                                jnp.int32(len(full_ids)),
                            )
                        adm.sampling = (req_t, req_p, req_k)
                        adm.ticket, adm.outs = ticket, (tok, lp, row_valid, moe)
                        self._admit_inflight = adm
                        if prev is not None:
                            METRICS.inc("batcher.admit.overlapped")
                            self._settle_admission(prev, then=req)
                        adm = self._select_admission()
            finally:
                self._drain_admission()

    def _select_admission(self) -> "_Admission | None":
        """The round's next admission, or None where the round ends.  ONE
        rule orders it against the admission in flight: only a monolithic
        admission is selected ahead of that one's fetch, and this is then
        the host work its program hides (its slot and its pages are taken
        though no row shows them yet: :meth:`_free_slot`, and the pool has
        handed the pages out).  Whatever else the pick comes to (the round's
        end, a swap restore, a chunked start, and every pick with
        ``overlap`` off) is made in the serial order: the admission in
        flight is settled first and the pick made AGAIN, since its stream
        callback may cancel the very request picked (the server's sweep
        does) or free a slot.  So what the caller gets with ``serial`` set,
        and a None, were picked with nothing in flight."""
        while True:
            if not self.overlap:
                self._drain_admission()
            ahead = self._admit_inflight
            adm = self._pick_admission()
            if ahead is None:
                return adm
            if self._admit_inflight is None:
                # Page pressure settled it in the middle of the pick
                # (:meth:`_ensure_pages`).  The pick stands unless the
                # callback took its request from the queue: then its pages
                # go back.
                with self._lock:
                    stands = adm is None or adm.req in self.queue
                if stands:
                    return adm
                if adm.cached_pages or adm.pages:
                    self._release_pages(adm.cached_pages + adm.pages)
                    self.tables[adm.slot] = 0
            elif adm is not None and adm.serial is None:
                return adm
            else:
                self._drain_admission()

    def _pick_admission(self) -> "_Admission | None":
        """A free slot, the scheduler's pick and, for a monolithic
        admission in paged mode, its pages.  None: no slot, an empty
        queue, the prefill slots full (strict admission order: the selected
        request never gets jumped), or a dry pool with no preemptable
        victim (back-pressure: the request stays queued, never removed)."""
        i = self._free_slot()
        if i is None:
            return None
        req = self._next_request()
        if req is None:
            return None
        if req.swap_handle is not None:
            return _Admission(i, req, serial="swap")
        pfx = self.prefixes[req.prefix] if req.prefix is not None else None
        thr = self.sched.chunk_threshold()
        if thr is not None and len(req.ids) > thr:
            if len(self._prefills) >= self.prefill_concurrency:
                return None
            return _Admission(i, req, pfx, serial="chunked")
        adm = _Admission(i, req, pfx,
                         total_len=(len(pfx.ids) if pfx else 0) + len(req.ids))
        if self.paged:
            got = self._reserve_row_pages(i, req, adm.total_len, pfx)
            if got is None:
                return None
            (adm.page_list, adm.pages, adm.cached_pages, adm.cached_len,
             adm.digests) = got
        return adm

    def _settle_admission(self, adm: _Admission,
                          then: "_Request | None" = None) -> None:
        """The admission's ONE blocking fetch, then its activation (the
        stream callback included).  ``then`` is the request launched
        behind it: the chip takes that one up as this fetch returns, and
        its wait in the queue ends here."""
        tok, lp, row_valid, moe = self._fetch_admission(adm.ticket, *adm.outs)
        if then is not None:
            self._note_admit_start(then)
        self._note_moe(*moe)
        if adm.cancelled:
            return
        req_t, req_p, req_k = adm.sampling
        self._activate_row(adm.slot, adm.req, tok, lp, row_valid,
                           adm.total_len, req_t, req_p,
                           adm.cached_pages + adm.pages, req_k=req_k,
                           cached_len=adm.cached_len)

    def _drain_admission(self) -> None:
        """Settle the admission in flight, if one is: nothing that needs
        the rows as the serial order leaves them (victim selection, a swap
        restore, a chunked start, the decode span) runs ahead of it."""
        adm, self._admit_inflight = self._admit_inflight, None
        if adm is not None:
            self._settle_admission(adm)

    def _activate_row(self, i, req, tok, lp, row_valid, total_len,
                      req_t, req_p, pages, req_k=None, cached_len=0):
        """Host bookkeeping tail of EVERY admission (monolithic and
        chunked): record the sampled first token, arm the row's scheduling
        state, stream the token.  ``tok``, ``lp`` and ``row_valid`` are host
        values (:meth:`_fetch_admission`; replicated outputs, identical on
        every process)."""
        tok = int(tok)
        self.last_tok[i] = tok
        self.spec_ema[i] = 1.0  # fresh rows draft the full k (optimistic)
        if req.constraint is not None:
            # Automaton state after the admission token: replay (resumed
            # prefix +) the token on the host — the state is a pure
            # function of the emitted stream.
            self.dfa_row[i] = req.constraint.advance(
                0, list(req.resume_emitted or []) + [tok]
            )
            METRICS.inc("batcher.constrain.rows")
        else:
            self.dfa_row[i] = 0
        self.temp_row[i] = req_t
        self.topp_row[i] = req_p
        self.topk_row[i] = (self.sampling["top_k"] if req_k is None
                            else req_k)
        self.pres_row[i] = req.presence_penalty
        self.freq_row[i] = req.frequency_penalty
        if self.prefix_cache is not None:
            self.prefix_cached_tokens[req.rid] = cached_len
        if req.timeline.residencies == 1:
            req.timeline.cached_tokens = cached_len
        prior = list(req.resume_emitted or [])
        prior_lps = list(req.resume_lps or [])
        if req.presence_penalty or req.frequency_penalty:
            if self.tok_counts is None:
                self.tok_counts = jnp.zeros(
                    (self.b, self.cfg.vocab_size), jnp.int32
                )
            if prior:
                # Resumed after preemption: the penalty histogram must see
                # every token THIS request has emitted across residencies,
                # or the recompute would sample from differently-penalized
                # logits than the unpreempted run.
                rowc = np.zeros((self.cfg.vocab_size,), np.int32)
                np.add.at(rowc, np.asarray(prior + [tok], np.int64), 1)
                self.tok_counts = self.tok_counts.at[i].set(
                    jnp.asarray(rowc)
                )
            else:
                self.tok_counts = _reset_count_row(
                    self.tok_counts, jnp.int32(i), jnp.int32(tok)
                )
        self.real_lens[i] = total_len
        self.valid[i] = row_valid
        self.active[i] = True
        # The first token came out of admission; the row may emit
        # budget-1 more from decode chunks.
        self.budget[i] = req.max_new_tokens - 1
        self._admit_seq += 1
        self.rows[i] = _RowState(
            rid=req.rid, emitted=prior + [tok], lps=prior_lps + [float(lp)],
            remaining=req.max_new_tokens - 1, pages=pages,
            req=req, priority=req.priority, admit_seq=self._admit_seq,
        )
        log.debug("admitted request %d into slot %d", req.rid, i)
        if req.max_new_tokens == 1 or tok == self.eos_id:
            self.active[i] = False
        if self._on_tokens is not None:
            # Stream the admission token; completion (done=True) is
            # always announced by _collect's publish sweep.  State
            # advances BEFORE the callback so a raising callback can
            # never cause a re-delivery on a later run().  A resumed row's
            # prior tokens were streamed in its previous residency —
            # streamed starts past them, so nothing re-delivers.
            self.rows[i].streamed = len(prior) + 1
            self._on_tokens(req.rid, [tok], False, [float(lp)])
        METRICS.inc("batcher.admitted")
        self._note_resident(req, first_token=True)

    # -- chunked prefill ---------------------------------------------------

    def _start_chunked(self, i: int, req: _Request, pfx) -> None:
        """Reserve slot ``i`` and begin a chunked prefill (first chunk runs
        this round).  Prefix-cached requests seed the transient row with a
        COPY of the registered prefix KV — one copy up front makes the
        buffers exclusively ours, so every chunk step can donate them
        (update in place) instead of copying the row cache per chunk.

        AUTOMATIC prefix caching composes too (closes the PR-3 TODO): the
        prompt's full pages are content-hashed, the longest cached run is
        retained and gathered out of the pool into the transient row ONCE,
        and only the un-cached suffix chunks through the model — the same
        continuation math as the monolithic cache-hit admission, so tokens
        stay temp-0 identical while a long shared prompt skips most of its
        chunked prefill.  Hits are capped one page short of the prompt so
        at least one real token prefills (the finish samples the first
        token from its logits)."""
        cached_pages: list[int] = []
        cached_len = 0
        digests: list[bytes] = []
        pc = self.prefix_cache
        self._note_unmatched(req, pfx)
        if pfx is not None:
            row_k, row_v, done = jnp.copy(pfx.k), jnp.copy(pfx.v), len(pfx.ids)
            total_len = done + len(req.ids)
        else:
            total_len = len(req.ids)
            if pc is not None and req.prefix_cache:
                blk = self.page_size
                if req.digests is None:
                    req.digests = self._page_digests(
                        req.ids, len(req.ids) // blk
                    )
                digests = req.digests
                cap = (len(req.ids) - 1) // blk
                # Tiered match: device hits are retained for the WHOLE
                # prefill (eviction must never reclaim a run the pending
                # chunks continue from) and host-spilled pages restore
                # with spare capacity (a chunked prefill holds no pool
                # pages of its own yet) — the pending chunks then start
                # past them, exactly as if they had never been evicted.
                cached_pages = self._match_tiered(digests, cap)
                cached_len = len(cached_pages) * blk
                pc.record_lookup(cached_len, total_len - cached_len)
            if cached_pages:
                read_list = np.zeros((self.pages_per_row,), np.int32)
                read_list[: len(cached_pages)] = cached_pages
                row_k, row_v = kv_cache.gather_row(
                    self.cache, jnp.asarray(read_list)
                )
                done = cached_len
            else:
                rc = kv_cache.init_cache(self.cfg, 1, self.s,
                                          dtype=kv_cache.row_dtype(self.cache))
                row_k, row_v, done = rc.k, rc.v, 0
        self._admit_seq += 1
        # The reserving row holds the cached pages so cancel_row /
        # _preempt_row release them and the pool audit sees the references
        # (a prefilling row stays inactive, so it is never a victim).
        self.rows[i] = _RowState(rid=req.rid, prefilling=True,
                                 remaining=req.max_new_tokens,
                                 req=req, priority=req.priority,
                                 admit_seq=self._admit_seq,
                                 pages=list(cached_pages))
        self._prefills[i] = _PendingPrefill(
            req=req, row_k=row_k, row_v=row_v, done=done,
            ids=list(req.ids), total_len=total_len,
            cached_pages=cached_pages, cached_len=cached_len,
            digests=digests,
        )
        # Alternate runs the first bite NOW (serialized); mixed defers it
        # to the fused span whenever decode rows are live to stall.
        self._advance_chunk(
            i, advance=not (self.sched.fuse_prefill()
                            and bool(self.active.any())),
        )

    def _advance_chunk(self, i: int, advance: bool = True) -> None:
        """Consume one scheduler-sized bite of slot ``i``'s pending
        prompt (``advance=False`` — the mixed policy's fused span already
        runs the bites on device — only checks for the finishing splice);
        finish the admission when the prompt completes.  In paged mode
        the finish ALLOCATES the row's pages on demand (prompt + one
        decode page) — a dry pool preempts a strictly-lower-priority
        victim, else the finish retries next round (the prefilled transient
        row is kept; no work is lost)."""
        pp = self._prefills[i]
        if advance and pp.done < pp.total_len:
            pfx_len = pp.total_len - len(pp.ids)
            clen = self._clamp_bite(
                pp.done,
                self.sched.prefill_bite(pp.total_len - pp.done,
                                        int(self.active.sum())),
                pp.total_len,
            )
            off = pp.done - pfx_len
            # Bucket for compile reuse, capped so cache_index + T <= width
            # (forward's contract; dynamic_update_slice clamps overflows).
            tc = min(_bucket(clen), self.s - pp.done)
            chunk = np.full((tc,), self.pad_id, np.int32)
            chunk[:clen] = pp.ids[off: off + clen]
            pp.row_k, pp.row_v, pp.last_logits = self._launch(
                prefill_chunk_step, self.params, self.cfg, pp.row_k, pp.row_v, jnp.int32(pp.done),
                jnp.asarray(chunk), jnp.int32(clen), pm=self.pm,
            )
            pp.done += clen
            METRICS.inc("batcher.prefill_chunks")
            METRICS.inc("batcher.sched.prefill_tokens", clen)
            if bool(self.active.any()):
                # Live decode rows just waited out this serialized prefill
                # forward — the alternating loop's inter-token-latency
                # spike the mixed schedule exists to remove (it keeps
                # this counter at zero by fusing the bite instead).
                METRICS.inc("batcher.sched.stall_rounds")
        if pp.done < pp.total_len:
            return
        req = pp.req
        req_t = (self.sampling["temperature"] if req.temperature is None
                 else float(req.temperature))
        req_p = (self.sampling["top_p"] if req.top_p is None
                 else float(req.top_p))
        req_k = (self.sampling["top_k"] if req.top_k is None
                 else int(req.top_k))
        custom = (req_t != self.sampling["temperature"]
                  or req_p != self.sampling["top_p"]
                  or req_k != self.sampling["top_k"])
        extra = (
            dict(temp_req=jnp.float32(req_t), topp_req=jnp.float32(req_p))
            if custom else {}
        )
        if custom and req_k != self.sampling["top_k"]:
            extra["topk_req"] = jnp.int32(req_k)
        if req.constraint is not None:
            # Same first-token masking as the monolithic admissions.
            st0 = req.constraint.advance(0, req.resume_emitted or [])
            extra["mask_req"] = jnp.asarray(req.constraint.bias[st0])
        if self.paged:
            blk = self.page_size
            n_cached = len(pp.cached_pages)
            n_full = -(-(pp.total_len + req.max_new_tokens) // blk)
            n_init = min(n_full, -(-pp.total_len // blk) + 1)
            if not self._ensure_pages(n_init - n_cached, "admit",
                                      below_priority=req.priority):
                return  # retry the finish next round; prefill is kept
            # Table first, allocation last (graftflow GF301): nothing
            # between the pool handing out pages and the table owning
            # them may raise.
            page_list = np.zeros((self.pages_per_row,), np.int32)
            page_list[:n_cached] = pp.cached_pages
            pages = self._alloc_pages(n_init - n_cached)
            page_list[n_cached:n_init] = pages
            self.tables[i] = page_list
            # Cache-hit positions scatter to the scratch page: the shared
            # pages already hold exactly that KV and other rows may be
            # reading them (same write routing as admit_row_auto_paged).
            write_list = page_list.copy()
            write_list[:n_cached] = 0
            self.cache, tok, lp = self._launch(
                finish_chunked_admission_paged, self.cache, jnp.asarray(write_list), pp.row_k, pp.row_v,
                pp.last_logits, self._split_rng(), pm=self.pm,
                **self.sampling, **extra,
            )
            # Publish the freshly-written full prompt pages (first writer
            # wins) — the cached run is already published.
            for j in range(n_cached, len(pp.digests)):
                self.pool.publish_prefix(int(page_list[j]), pp.digests[j])
            row_valid = np.arange(self.s) < pp.total_len
            pages = pp.cached_pages + pages
        else:
            pages = []
            self.cache, tok, row_valid, lp = self._launch(
                finish_chunked_admission, self.cfg, self.cache, jnp.int32(i), pp.row_k, pp.row_v,
                pp.last_logits, jnp.int32(pp.total_len), self._split_rng(),
                pm=self.pm, **self.sampling, **extra,
            )
        del self._prefills[i]
        tok, lp, row_valid = self._fetch_admission(
            self._n_dispatched, tok, lp, row_valid)
        self._activate_row(i, req, tok, lp, row_valid, pp.total_len,
                           req_t, req_p, pages=pages, req_k=req_k,
                           cached_len=pp.cached_len)

    def _collect(
        self, toks: np.ndarray, was_active: np.ndarray,
        counts: np.ndarray | None = None, lps: np.ndarray | None = None,
        active_host: np.ndarray | None = None,
    ) -> None:
        # ``active_host``: the post-chunk activity vector.  The dispatch-
        # ahead path passes the fetched chunk output directly (the host
        # mirrors are stale while the carry is device-resident); the
        # synchronous path leaves it None and reads the freshly-synced
        # mirror, exactly as before.
        #
        # A row that this call brings at least one new token (streamed
        # below, or final) has a DELIVERY: the interval since its last one
        # is a sample of ``batcher.row.gap_seconds``, and the admit clock's
        # advance over it is the part other requests' admission rounds
        # filled.  One clock reading a call: the rows of a chunk are
        # delivered together.  A ``done`` that brings no token observes
        # nothing, and the last partial interval of a cancelled or
        # deadline-cut row is dropped with the row (the closing cut of a
        # benchmark's window is the benchmark's, not a user's wait).
        now = self._clock()
        admit_now = self._admit_now(now)
        gaps: list[float] = []
        gap_admit = 0.0
        committed = 0
        for i in range(self.b):
            row = self.rows[i]
            if row.rid is None or not was_active[i]:
                continue
            had = len(row.emitted)
            # Speculative rounds emit a VARIABLE count per row; columns past
            # counts[i] are padding, not tokens (a legit pad-id token inside
            # the count still collects).  decode_chunk's fixed-step output
            # keeps the remaining-guarded full sweep.
            row_toks = toks[i] if counts is None else toks[i][: counts[i]]
            for j, t in enumerate(row_toks):
                if row.remaining <= 0:
                    break
                t = int(t)
                row.emitted.append(t)
                if lps is not None:
                    row.lps.append(float(lps[i][j]))
                row.remaining -= 1
                if t == self.eos_id:
                    break
            new = len(row.emitted) - had
            committed += new
            if new and row.req is not None:
                gap, part = row.req.timeline.deliver(now, admit_now)
                gaps.append(gap)
                gap_admit += part
        if committed:
            METRICS.inc("batcher.decode.committed_tokens", committed)
        if gaps:
            METRICS.observe_many("batcher.row.gap_seconds", gaps)
            METRICS.inc("batcher.row.gap_admit_seconds", gap_admit)
        # Rows that finished this chunk publish their result and free up.
        # (Chunked prefills in flight are inactive but NOT finished.)
        if active_host is None:
            active_host = self.active
        for i in range(self.b):
            row = self.rows[i]
            if row.rid is not None and not active_host[i] and not row.prefilling:
                # Trim anything emitted past the row's EOS.
                if self.eos_id >= 0 and self.eos_id in row.emitted:
                    cut = row.emitted.index(self.eos_id) + 1
                    row.emitted = row.emitted[:cut]
                    row.lps = row.lps[:cut]
                self.results[row.rid] = row.emitted
                self.result_logprobs[row.rid] = row.lps
                rid, final = row.rid, row.emitted[row.streamed:]
                if row.pages:  # paged: drop the row's page references
                    self._release_pages(row.pages)
                    self.tables[i] = 0
                final_lps = row.lps[row.streamed:]
                if row.req is not None:  # tenant true-up at completion
                    self.sched.note_freed(row.req, len(row.emitted))
                    self._note_finished(
                        row.req, len(row.emitted),
                        "eos" if row.emitted[-1:] == [self.eos_id]
                        else "length")
                self.rows[i] = _RowState()
                METRICS.inc("batcher.completed")
                if self._on_tokens is not None:
                    # Final delivery: whatever landed since the last stream
                    # (possibly nothing), with done=True exactly once.  Row
                    # state is already reset, so a raising callback cannot
                    # cause a duplicate done on a later run().
                    self._on_tokens(rid, final, True, final_lps)
        if self._on_tokens is not None:
            # Still-active rows stream this chunk's new tokens (streamed
            # advances before the callback — same raise-safety).
            for i in range(self.b):
                row = self.rows[i]
                if row.rid is not None and len(row.emitted) > row.streamed:
                    new = row.emitted[row.streamed:]
                    new_lps = row.lps[row.streamed:]
                    row.streamed = len(row.emitted)
                    self._on_tokens(row.rid, new, False, new_lps)

    def run(self, on_tokens=None) -> dict[int, list[int]]:
        """Drive until every submitted request has a result.

        ``on_tokens(rid, new_tokens, done, logprobs)`` streams
        incrementally: called with each request's newly committed token ids
        as scheduling chunks complete (admission token first, then
        per-chunk), and exactly once with ``done=True`` carrying any final
        tokens — the concatenation of all deliveries for a rid equals its
        entry in the returned dict.  ``logprobs`` aligns 1:1 with
        ``new_tokens`` (raw-distribution chosen-token logprobs — in
        speculative mode gathered from the verify pass's logits, identical
        to the plain batcher's at temperature 0).
        Exceptions from the callback propagate (and abort the run).

        With ``overlap`` on (the default) the loop dispatches ahead:
        while no scheduling work is pending, chunk N+1 runs on device
        concurrently with chunk N's host work (callbacks included), so a
        callback observes each chunk one dispatch later than the
        synchronous loop would — the token STREAM per rid is unchanged,
        and temp-0 bytes are identical either way.  After ``run`` raises
        (an injected crash, a callback exception) the host scheduling
        mirrors may be stale; recover through :meth:`respawn`, the
        supervisor contract.
        """
        self._on_tokens = on_tokens
        try:
            return self._run_loop()
        finally:
            self._on_tokens = None

    def _run_loop(self) -> dict[int, list[int]]:
        # A fresh run's first dispatch follows no observed completion: since
        # the last run's last fetch the engine was parked, not starved.
        self._starved_at = None
        while self.has_queued() or bool(self.active.any()) or any(
            r.rid is not None for r in self.rows
        ) or self.has_kv_imports() or self.has_kv_exports():
            self._admit_pending()
            if self.paged:
                # Chunk-boundary growth: rows about to write past their
                # allocated pages get them NOW (or preempt / yield) — the
                # decode chunk below must never scatter a live row's KV
                # into the scratch page.  (Occupancy gauges are published
                # at /metrics scrape time, not here: the decode loop is
                # the latency-critical path.)
                self._grow_rows()
            was_active = self.active.copy()
            if not was_active.any():
                # Publish any 1-token requests finished by admission alone.
                with self._span("batcher.loop.deliver"):
                    self._collect(
                        np.zeros((self.b, 0), np.int32), was_active
                    )
                if not self.has_queued() and not self.has_kv_imports() \
                        and not self.has_kv_exports() \
                        and all(r.rid is None for r in self.rows):
                    break
                continue
            self._decode_span(was_active)
        return dict(self.results)

    # -- dispatch-ahead decode (the overlap plane) -------------------------

    def _span_plan(self) -> dict:
        """The traced-argument plan for ONE decode span, decided once from
        the (fresh) host mirrors at span start and reused for every chunk
        the span dispatches ahead: the per-row sampling / penalty kwargs
        select which COMPILED PROGRAM runs, and a dispatched-ahead chunk
        must reuse the first chunk's program (graftcheck GC4 pins the
        chained decode to one compile key).  Row sampling state only
        changes at admission — a span never admits — so the snapshot stays
        valid for the whole span; a row finishing mid-span merely keeps
        the (correct, slightly wider) program engaged until the sync."""
        plan: dict = {
            "tables": jnp.asarray(self.tables) if self.paged else None,
            "constrain": None,
        }
        self._tables_dirty = False  # plan holds the current snapshot
        pen_live = self.active & (
            (self.pres_row != 0.0) | (self.freq_row != 0.0)
        )
        # Penalized path only while a penalized row is live — the
        # all-default batch keeps the smaller static program.
        plan["counts"] = bool(pen_live.any())
        if self.speculative:
            per_spec = {}
            if plan["counts"]:
                per_spec["pres_row"] = jnp.asarray(self.pres_row)
                per_spec["freq_row"] = jnp.asarray(self.freq_row)
            # The adaptive k_row clamp is NOT part of the span-frozen
            # plan: it is a traced [B] input (values never touch the
            # compile key — graftcheck GC4 batcher.spec_chunk_paged), so
            # _dispatch_chunk re-plans it per dispatch from the freshest
            # EMA mirrors and ``k_hist`` pairs each dispatched clamp with
            # its fetch (FIFO — chunks fetch in dispatch order) for the
            # acceptance accounting.
            plan["k_hist"] = deque()
            plan["per_spec"] = per_spec
        else:
            # Per-row sampling path only while a custom-sampled row is
            # live: the all-default batch keeps the static program
            # (greedy compiles to a bare argmax — no per-step vocab
            # sort paid for traffic that never asked for sampling).
            rows_live = self.active & (
                (self.temp_row != self.sampling["temperature"])
                | (self.topp_row != self.sampling["top_p"])
                | (self.topk_row != self.sampling["top_k"])
            )
            per_row = {}
            if bool(rows_live.any()):
                per_row["temp_row"] = jnp.asarray(self.temp_row)
                if not bool((self.topp_row[self.active] == 1.0).all()):
                    # All-1.0 top_p skips the per-step [B, V] sort+
                    # softmax+cumsum mask entirely (sample_rows takes
                    # the static keep-everything path).
                    per_row["topp_row"] = jnp.asarray(self.topp_row)
                if not bool((
                    self.topk_row[self.active] == self.sampling["top_k"]
                ).all()):
                    # Engaged only while a row's top_k diverges from
                    # the engine-wide static value — the traced mask
                    # pays a per-step [B, V] sort the static path
                    # doesn't.
                    per_row["topk_row"] = jnp.asarray(self.topk_row)
            if plan["counts"]:
                per_row["pres_row"] = jnp.asarray(self.pres_row)
                per_row["freq_row"] = jnp.asarray(self.freq_row)
            # Constrained structured output: stack the live rows' token
            # automata into ONE (bias, next) pair the decode step gathers
            # from; the per-row state vector rides the DEVICE carry
            # (self._dfa_carry — _dispatch_chunk chains it chunk to
            # chunk) and syncs back to the dfa_row mirrors at span end.
            # The state axis pads up the shared bucket ladder so the
            # compile key is independent of the live schema mix.
            con = [
                i for i in range(self.b)
                if self.active[i] and self.rows[i].req is not None
                and self.rows[i].req.constraint is not None
            ]
            if con:
                # Memo key: (slot, rid) per constrained row.  rids are
                # minted monotonically and a row's constraint is fixed
                # for its whole residency, so the pair identifies the
                # stacked automata exactly — and deterministically,
                # unlike the id()-based key this replaces (object
                # addresses diverge across lockstep processes; graftsync
                # GS101 audits _span_plan as a declared decision).
                key = tuple(
                    (i, self.rows[i].rid) for i in con
                )
                if self._con_stack is None or self._con_stack[0] != key:
                    dfas = [self.rows[i].req.constraint for i in con]
                    total = 1 + sum(d.n_states for d in dfas)
                    bias, nxt, offs = constrain_lib.build_stack(
                        dfas, self.cfg.vocab_size,
                        pad_states_to=_bucket(total),
                    )
                    # The memo keeps the automata alongside the device
                    # tables (the rid key no longer needs an id pin;
                    # they document what the stack was built from).
                    self._con_stack = (
                        key, jnp.asarray(bias), jnp.asarray(nxt), offs,
                        dfas,
                    )
                _, bias_j, nxt_j, offs, _dfas = self._con_stack
                abs_state = np.zeros((self.b,), np.int32)
                for off, i in zip(offs, con):
                    abs_state[i] = off + int(self.dfa_row[i])
                per_row["mask_stack"] = bias_j
                per_row["next_stack"] = nxt_j
                self._dfa_carry = jnp.asarray(abs_state)
                plan["constrain"] = [
                    (i, off, self.rows[i].rid) for off, i in zip(offs, con)
                ]
            else:
                # Constrained traffic drained: release the memoized stack
                # (device tables + pinned automata) — it rebuilds on the
                # next constrained span at the same cost it was built.
                self._con_stack = None
            plan["per_row"] = per_row
        # Fused token-budget step (schedule=mixed): the HEAD pending
        # prefill rides every chunk this span dispatches; bites are
        # sized per dispatch against the span-start live row count.
        # "Head" = the FIRST (start-order) prefill with prompt work left
        # — a completed head whose finishing splice is back-pressured
        # must not starve a later prefill of its bites (the finish
        # itself retries at the round boundaries the prefill_finish
        # sync trigger forces).
        plan["n_active"] = int(self.active.sum())
        plan["mixed"] = None
        if self.sched.fuse_prefill() and not self.speculative:
            for slot, pp in self._prefills.items():
                if pp.done < pp.total_len:
                    plan["mixed"] = slot
                    break
        return plan

    def _dispatch_chunk(self, plan: dict, carry: tuple) -> tuple:
        """Dispatch one decode/speculative chunk (JAX async dispatch —
        returns immediately with device futures).  ``carry`` is the
        scheduling carry (last_tok, real_lens, valid, active, budget):
        host mirrors for the first chunk of a span, the PREVIOUS chunk's
        device-resident outputs for a dispatched-ahead chunk — both feed
        the same compiled program.  Returns (toks, lps, m, carry', moe,
        ticket) with ``m`` the speculative per-row commit counts (None on
        the plain path), ``moe`` a hybrid model's expert counts of the chunk
        (None for any other model) and ``ticket`` the chunk's place in the
        order of dispatches (:meth:`_launch`);
        ``self.cache``/``self.draft_cache``/``self.tok_counts``
        advance to the new chunk's (not-yet-materialized) outputs."""
        with self._span("batcher.loop.dispatch"):
            if self._tables_dirty:
                # In-span growth extended a row's table: this chunk
                # must read/write through the grown pages.
                plan["tables"] = jnp.asarray(self.tables)
                self._tables_dirty = False
            last_tok, real_lens, valid, active, budget = carry
            METRICS.inc("batcher.decode.chunks")
            m = moe = None
            dfa_out = None
            if self.speculative:
                per_spec = dict(plan["per_spec"])
                if plan["counts"]:
                    per_spec["counts"] = self.tok_counts
                if self.sampling["temperature"] > 0.0:
                    # Sampled rounds consume RNG; greedy rounds must not
                    # (greedy spec stays bit-stable across configs).
                    per_spec["rng"] = self._split_rng()
                if self.faults is not None:
                    # Injection site "batcher.spec_verify": the round is ONE
                    # compiled draft+verify program, so both tags fire at its
                    # dispatch — the tag selects which drill phase a rule
                    # targets ('draft' = the k draft steps, 'verify' = the
                    # (k+1)-token target pass).  A 'raise' here is the
                    # supervisor-restart drill for the speculative leg.
                    self.faults.fire("batcher.spec_verify", tag="draft")
                    self.faults.fire("batcher.spec_verify", tag="verify")
                # Per-dispatch adaptive clamp (the scheduler's spec_round_k
                # hook: token-budget clamp + acceptance-EMA downshift).
                # Greedy engines only: the sampled forced-stop draw is
                # distribution-preserving but changes the per-seed stream,
                # and flipping the downshift on must never change sampled
                # outputs.  The clamp is ALWAYS passed as a traced [B]
                # vector (full k when inert) so one compiled program serves
                # every value.  Mid-span the activity mirrors are stale by
                # construction — stale the same way every run, so the
                # downshift schedule stays deterministic.
                live = self.active & np.asarray(
                    [r.rid is not None for r in self.rows]
                )
                emas = tuple(
                    float(self.spec_ema[i]) if live[i] else 1.0
                    for i in range(self.b)
                )
                if self.sampling["temperature"] == 0.0:
                    ks = self.sched.spec_round_k(
                        self.spec_k, emas, int(live.sum())
                    )
                else:
                    ks = [self.spec_k] * self.b
                kh = np.clip(np.asarray(ks, np.int32), 1, self.spec_k)
                plan["k_hist"].append(kh)
                per_spec["k_row"] = jnp.asarray(kh)
                METRICS.inc("batcher.spec.rounds")
                self.spec_stats["rounds"] += 1
                # Budget accounting: a round charges (k_row+1) COMMITTABLE
                # tokens per live row against the ledger (spec_round_k
                # already clamped the sum against token_budget).  The
                # dispatched program is always k+1 wide — the ledger bounds
                # commits, not flops (one compile key).
                METRICS.inc("batcher.sched.decode_tokens",
                            int(np.sum((kh + 1)[live])))
                METRICS.inc("batcher.decode.slot_steps",
                            self.b * (self.spec_k + 1))
                if bool((kh[live] < self.spec_k).any()):
                    METRICS.inc("batcher.spec.k_downshifts")
                    self.spec_stats["downshifts"] += 1
                (toks, m, lps, self.cache, self.draft_cache, last_tok,
                 real_lens, valid, active, budget, counts_out) = self._launch(
                    spec_chunk, self.params, self.cfg, self.draft_params, self.draft_cfg,
                    self.cache, self.draft_cache, last_tok, real_lens, valid,
                    active, budget, k=self.spec_k, eos_id=self.eos_id,
                    pad_id=self.pad_id, tables=plan["tables"],
                    **self.sampling, **per_spec,
                )
            else:
                per_row = dict(plan["per_row"])
                if plan["counts"]:
                    per_row["counts"] = self.tok_counts
                if plan["constrain"]:
                    # The automaton-state carry chains like the KV cache: a
                    # dispatched-ahead chunk consumes the PREVIOUS chunk's
                    # (not-yet-materialized) state output directly.
                    per_row["dfa_state"] = self._dfa_carry
                METRICS.inc("batcher.sched.decode_tokens",
                            plan["n_active"] * self.chunk_steps)
                METRICS.inc("batcher.decode.slot_steps",
                            self.b * self.chunk_steps)
                pp = (self._prefills.get(plan["mixed"])
                      if plan["mixed"] is not None else None)
                if pp is not None and pp.done < pp.total_len:
                    (toks, self.cache, last_tok, real_lens, valid, active,
                     budget, lps, counts_out, dfa_out) = self._dispatch_mixed(
                        plan, (last_tok, real_lens, valid, active, budget),
                        per_row, pp,
                    )
                else:
                    if self.faults is not None and self.sched.fuse_prefill():
                        # Injection site "batcher.mixed_step" tag "decode":
                        # a mixed-schedule dispatch with no prefill riding.
                        self.faults.fire("batcher.mixed_step", tag="decode")
                    (toks, self.cache, last_tok, real_lens, valid, active,
                     budget, lps, counts_out, dfa_out, *moe) = \
                        self._launch(
                            decode_chunk, self.params, self.cfg_decode, self.cache, last_tok,
                            real_lens, valid, active, budget,
                            self._split_rng(), self.chunk_steps,
                            eos_id=self.eos_id, pad_id=self.pad_id, pm=self.pm,
                            tables=plan["tables"],
                            **self.sampling, **per_row,
                        )
                    # A hybrid model's expert counts: fetched with the
                    # chunk's tokens, added at delivery (_note_moe).
                    moe = moe[0] if moe else None
            if counts_out is not None:
                self.tok_counts = counts_out
            if dfa_out is not None:
                self._dfa_carry = dfa_out
            return (toks, lps, m, (last_tok, real_lens, valid, active, budget),
                    moe, self._n_dispatched)

    def _mixed_width(self, done: int) -> int:
        """Prefill-leg width of a fused step: ONE bucket sized to the
        policy's largest possible bite, so the steady-state compile key
        is independent of the live prefill mix (graftcheck GC4
        batcher.mixed_step).  At the row TAIL — where cache_index + T <=
        width must hold (dynamic_update_slice CLAMPS an overflowing
        start, which would misalign the suffix) — the width shrinks DOWN
        the shared bucket ladder, never to a raw remainder
        (:meth:`_clamp_bite` guarantees a bite boundary never lands
        inside the last sub-floor slots): tail keys stay on the closed
        ladder (one per bucket, the tentpole's GC4 budget) instead of
        compiling per prompt length on the engine thread mid-span."""
        cap = self.sched.token_budget or self.sched.prefill_chunk or self.s
        w = _bucket(min(cap, self.s))
        room = self.s - done
        while w > room and w > 8:  # 8 = shapes.BUCKET_FLOOR
            w //= 2
        return min(w, room)

    def _clamp_bite(self, done: int, bite: int, total_len: int) -> int:
        """Keep every bite boundary OFF the row's last sub-floor slots
        (s-8 < done' < total_len would force the NEXT chunk's width to a
        raw off-ladder remainder and a fresh XLA trace mid-span): a bite
        that would end there shortens to land exactly on s-8, and a bite
        STARTING at the boundary finishes the prompt outright (<= 7
        tokens, the budget floor notwithstanding — once per prompt at
        most).  Applied to fused and serialized bites alike, so chunk
        splits — and therefore nothing byte-visible — stay
        schedule-invariant."""
        if self.s - 8 <= done:
            return total_len - done
        end = done + bite
        if end < total_len and self.s - end < 8:
            bite = (self.s - 8) - done
        return bite

    def _dispatch_mixed(self, plan: dict, carry: tuple, per_row: dict,
                        pp: "_PendingPrefill") -> tuple:
        """Dispatch ONE fused token-budget step (schedule=mixed): the
        decode chunk AND the head pending prefill's next bite as one
        compiled program — resident decode rows never wait on a separate
        serialized prefill forward.  Host bookkeeping (``pp.done``, bite
        metrics) advances at dispatch time; the transient prefill row and
        its last-logits chain device-resident across dispatch-ahead
        chunks exactly like the decode carry.  Returns decode_chunk's
        10-tuple."""
        last_tok, real_lens, valid, active, budget = carry
        tw = self._mixed_width(pp.done)
        # Clamp AFTER the width truncation: min(bite, tw) moves the bite
        # boundary, and only the post-truncation boundary must be kept
        # out of the sub-floor tail zone (clamping first and truncating
        # after could land the boundary right back inside it).
        bite = self._clamp_bite(
            pp.done,
            min(self.sched.prefill_bite(pp.total_len - pp.done,
                                        plan["n_active"]), tw),
            pp.total_len,
        )
        bite = min(bite, tw)  # the finish branch is invariant-bounded;
        #                       never trust it past the chunk width
        off = pp.done - (pp.total_len - len(pp.ids))
        chunk = np.full((tw,), self.pad_id, np.int32)
        chunk[:bite] = pp.ids[off: off + bite]
        if self.faults is not None:
            # Injection site "batcher.mixed_step" tag "prefill": one hit
            # per fused dispatch carrying a prefill bite.
            self.faults.fire("batcher.mixed_step", tag="prefill")
        (toks, cache, last_tok, real_lens, valid, active, budget, lps,
         counts_out, dfa_out, pp.row_k, pp.row_v, pp.last_logits) = \
            self._launch(
                mixed_step, self.params, self.cfg_decode, self.cfg, self.cache,
                last_tok, real_lens, valid, active, budget,
                self._split_rng(), self.chunk_steps,
                pp.row_k, pp.row_v, jnp.int32(pp.done),
                jnp.asarray(chunk), jnp.int32(bite),
                eos_id=self.eos_id, pad_id=self.pad_id, pm=self.pm,
                tables=plan["tables"], **self.sampling, **per_row,
            )
        pp.done += bite
        METRICS.inc("batcher.prefill_chunks")
        METRICS.inc("batcher.sched.prefill_tokens", bite)
        budget_t = self.sched.token_budget or (plan["n_active"] + bite)
        METRICS.inc("batcher.sched.budget_tokens", budget_t)
        METRICS.set_gauge("batcher.sched.budget_utilization",
                          (plan["n_active"] + bite) / max(budget_t, 1))
        return (toks, cache, last_tok, real_lens, valid, active, budget,
                lps, counts_out, dfa_out)

    def _overlap_ok(self, was_active: np.ndarray, chunks: int) -> bool:
        """Whether the NEXT chunk may dispatch ahead from the device
        carry, i.e. nothing needs the host scheduling mirrors at this
        boundary — the scheduler's ``sync_triggers`` hook over a host-
        state snapshot (the trigger list and its policy live in
        runtime/scheduler.py; README "Engine overlap" documents it).
        The mixed policy keeps dispatching ahead while the head pending
        prefill still has bites to ride the fused step; the alternate
        policy parks the span for any pending prefill.  ``head_left``
        reports the first prefill WITH WORK (the one _span_plan fuses)
        — but any COMPLETED prefill awaiting its finishing splice forces
        0, so the finish retries at every chunk boundary instead of
        waiting out a sibling's whole prefill."""
        head_left = 0
        for pp in self._prefills.values():
            left = pp.total_len - pp.done
            if left <= 0:
                head_left = 0
                break
            if head_left == 0:
                head_left = left
        view = scheduler_lib.SyncView(
            any_active=bool(was_active.any()),
            cancel_dirty=self._cancel_dirty,
            queued=self.has_queued(),
            kv_imports=self.has_kv_imports(),
            prefills=len(self._prefills),
            head_prefill_left=head_left,
            live_budgets=tuple(
                int(self.budget[i]) for i in range(self.b)
                if self.rows[i].rid is not None and self.active[i]
                and not self.rows[i].prefilling
            ),
            chunks_ahead=chunks,
            grow_blocked=lambda: (
                self.paged and not self._grow_ahead(chunks + 1)
            ),
        )
        return not self.sched.sync_triggers(view)

    def _spec_note(self, m, was_active: np.ndarray, plan: dict) -> None:
        """Per-round speculative accounting from the fetched commit
        counts: update each row's acceptance-rate EMA (feeding the
        scheduler's adaptive spec_k downshift at the next span plan) and
        the spec metrics.  ``accepted`` counts committed DRAFTS (the
        bonus/correction token excluded); EOS/budget clamps deflate it —
        that loss is data, matching the standalone loop's accounting.
        Everything here is a pure function of the committed stream and
        the span structure, so two identical runs downshift
        identically."""
        if m is None:
            return
        # FIFO pairing: chunks fetch in dispatch order, so the head of
        # k_hist is exactly the clamp this fetched chunk drafted with.
        kh = (plan["k_hist"].popleft() if plan["k_hist"]
              else np.full((self.b,), self.spec_k, np.int32))
        acc = rej = 0
        for i in range(self.b):
            if not was_active[i] or m[i] <= 0:
                continue
            drafted = int(kh[i])
            accepted = min(int(m[i]) - 1, drafted)
            acc += accepted
            rej += drafted - accepted
            self.spec_ema[i] = (
                (1.0 - _SPEC_EMA_ALPHA) * float(self.spec_ema[i])
                + _SPEC_EMA_ALPHA * (accepted / max(drafted, 1))
            )
        if acc:
            METRICS.inc("batcher.spec.accepted_tokens", acc)
        if rej:
            METRICS.inc("batcher.spec.rejected_tokens", rej)
        self.spec_stats["accepted"] += acc
        self.spec_stats["rejected"] += rej
        total = self.spec_stats["accepted"] + self.spec_stats["rejected"]
        if total:
            # The cumulative acceptance gauge, fed by the same per-round
            # fraction engine.spec_acceptance observes for the standalone
            # speculative loop — one histogram serves both paths.
            METRICS.set_gauge(
                "batcher.spec.acceptance",
                self.spec_stats["accepted"] / total,
            )
        if acc + rej:
            METRICS.observe("engine.spec_acceptance", acc / (acc + rej))

    def _grow_ahead(self, horizon_chunks: int) -> bool:
        """Page growth ON the overlapped window: growth needs the page
        POOL, not the carry mirrors, so a span can keep dispatching ahead
        across page boundaries — rows grow against a CONSERVATIVE frontier
        bound off the stale mirrors (``horizon_chunks`` chunks may have
        advanced every row since the last sync; budget only shrinks, so
        ``min(..., budget)`` stays an upper bound).  A still-live row
        over-allocates at most one page (written as it arrives); a row
        that already died (EOS) but whose fetch hasn't landed yet can
        transiently hold up to ``horizon_chunks * chunk_steps /
        page_size`` pages it will never write — they release at that
        fetch's publish sweep, a chunk later.  Best-effort
        only: growth that would need PRESSURE (preemption reads/writes
        the mirrors and must never run against stale ones) returns False
        and the span syncs — the normal growth path then applies today's
        exact evict -> preempt -> back-pressure ladder.  Fault-armed
        engines also return False: the ``batcher.page_alloc`` drill
        windows must keep counting exactly one hit per growth round."""
        if self.faults is not None:
            return False
        blk = self.page_size
        for i in range(self.b):
            row = self.rows[i]
            if row.rid is None or not self.active[i] or row.prefilling:
                continue
            if self.speculative:
                # A speculative chunk commits at most spec_k+1 tokens and
                # always writes a spec_k+1 window past its frontier.
                horizon = int(self.real_lens[i]) + min(
                    horizon_chunks * (self.spec_k + 1), int(self.budget[i])
                ) + self.spec_k + 1
            else:
                horizon = int(self.real_lens[i]) + min(
                    horizon_chunks * self.chunk_steps, int(self.budget[i])
                )
            need = -(-horizon // blk) - len(row.pages)
            if need <= 0:
                continue
            if self._pages_available() < need:
                return False  # pressure: sync and let _grow_rows preempt
            have = len(row.pages)
            fresh = self._alloc_pages(need)
            row.pages.extend(fresh)
            self.tables[i][have: have + need] = fresh
            self._tables_dirty = True
            METRICS.inc("batcher.pages_grown", need)
        return True

    def _note_unmatched(self, req: _Request, pfx) -> None:
        """A prompt that no prefix-cache lookup covers (a batcher without
        the cache, a request that opted out, a named prefix) is prefilled
        fresh, every token of it.  Counted under the name the lookups count
        their misses under (:meth:`PrefixCache.record_lookup`), so that
        ``batcher.prefix_cache.miss_tokens`` is the prompt tokens prefilled
        by every admission, with or without the cache: real tokens, no
        bucket padding."""
        if self.prefix_cache is None or pfx is not None or not req.prefix_cache:
            METRICS.inc("batcher.prefix_cache.miss_tokens", len(req.ids))

    def _note_moe(self, stats=None) -> None:
        """Add a program's expert counts (layers.moe_dropless, real tokens
        only) to ``moe.*``: what a hybrid model's admission or decode chunk
        handed out beside its tokens, fetched with them (host values), None
        for any other model.  Four counts; a fifth and a sixth where the
        config holds a chip's share of the experts; a decode chunk against
        pages and rings hands out eight, one against latent pages nine
        (_decode_steps)."""
        if stats is None:
            return
        counts = [int(x) for x in stats]
        if self.cfg.ret_layers:
            # A model of retention layers hands out its own four instead
            # (models.model.retention_counts): an admission's real tokens
            # and the chunks that hold one, a decode chunk's row-steps and
            # the tokens those rows held.
            METRICS.inc("ret.admit.tokens", counts[0])
            METRICS.inc("ret.admit.chunks", counts[1])
            METRICS.inc("ret.decode.row_steps", counts[2])
            METRICS.inc("ret.decode.resident_tokens", counts[3])
            return
        if self.cfg.ssm_layers or self.cfg.gdn_layers:
            # A model of state-space (or delta-rule) layers hands out three
            # more behind its experts' (models.model.ssm_counts, a layer's):
            # an admission's real tokens and the chunks that hold one, a
            # decode chunk's row-steps.
            *counts, tokens, chunks, row_steps = counts
            if self.cfg.ssm_layers:
                METRICS.inc("ssm.admit.tokens", tokens)
                METRICS.inc("ssm.admit.chunks", chunks)
                METRICS.inc("ssm.decode.row_steps", row_steps)
            else:
                METRICS.inc("gdn.admit.tokens", tokens)
                METRICS.inc("gdn.admit.chunks", chunks)
                METRICS.inc("gdn.decode.row_steps", row_steps)
        METRICS.inc("moe.routed_pairs", counts[0])
        METRICS.inc("moe.layer_passes", counts[1])
        METRICS.inc("moe.experts_touched", counts[2])
        METRICS.inc("moe.max_load_tokens", counts[3])
        if len(counts) > 4:  # a chip's share of the experts: ... and of
            # the held pairs, those whose rows the combine fetched singly
            METRICS.inc("moe.held_pairs", counts[4])
            METRICS.inc("moe.combine_rows", counts[5])
        if len(counts) > 6 and self.cfg.swa_layers:  # a decode chunk: the
            # tokens its rows held (full layers' pages; latent pages)
            METRICS.inc("attn.decode.resident_tokens", counts[6])
        elif len(counts) > 6:  # ... and, ninth, the keys the latent
            # kernel's products covered for them (whole blocks of pages)
            METRICS.inc("mla.decode.resident_tokens", counts[6])
            METRICS.inc("mla.decode.scored_keys", counts[8])
        if len(counts) == 8:  # ... and of them, those inside the window,
            # beside the tokens the rows' rings hold room for: the window
            # times the row-steps that decoded, which are the pairs routed
            # over the k choices of every expert layer (no count of its
            # own in the program: K-EXAONE's decode chunk stays as it was)
            METRICS.inc("swa.decode.window_tokens", counts[7])
            cfg = self.cfg
            METRICS.inc(
                "swa.decode.ring_tokens", cfg.sliding_window * counts[0] // (
                    cfg.num_experts_per_token
                    * (cfg.num_layers - cfg.num_dense_layers)))

    def _fetch_chunk(self, out: tuple) -> tuple:
        """Host work's D2H for a dispatched-ahead chunk: tokens, logprobs,
        speculative commit counts, and the post-chunk activity vector in
        ONE ``jax.device_get`` (blocks until the chunk completes — the
        NEXT chunk is already executing behind it).  The rest of the
        carry stays device-resident."""
        toks, lps, m, carry, moe, ticket = out
        with self._span("batcher.loop.wait_device"):
            toks_h, lps_h, m_h, moe_h, active_h = jax.device_get(
                (toks, lps, m, moe, carry[3]))
        self._note_fetched(ticket)
        return toks_h, lps_h, m_h, moe_h, active_h

    def _sync_carry(self, out: tuple) -> tuple:
        """Refresh the host scheduling mirrors from the chunk's outputs —
        one batched ``jax.device_get`` of tokens + logprobs + the whole
        carry + a constrained span's automaton states (replicated outputs:
        every process reads identical values; copies are taken only where
        the backend hands back read-only views, since admission writes
        into the mirrors).  Slots whose
        host bookkeeping dropped the row while the carry was device-
        resident (cancel mid-span) are forced inactive — the device's
        activity bit for them is stale by construction."""
        toks, lps, m, carry, moe, ticket = out
        with self._span("batcher.loop.wait_device"):
            toks_h, lps_h, m_h, moe_h, (lt, rl, va, ac, bu), dfa_h = \
                jax.device_get((toks, lps, m, moe, carry, self._dfa_carry))
        self._note_fetched(ticket)
        self.last_tok = _writable(lt)
        self.real_lens = _writable(rl)
        self.valid = _writable(va)
        self.active = _writable(ac)
        self.budget = _writable(bu)
        for i in range(self.b):
            if self.rows[i].rid is None and self.active[i]:
                self.active[i] = False
                self.budget[i] = 0
        self._cancel_dirty = False
        return toks_h, lps_h, m_h, moe_h, dfa_h

    def _prehash_queued(self) -> None:
        """Overlapped host window: memoize page digests for requests that
        arrived while this span ran, so the NEXT admission round (a sync
        point — the device waits on it) finds the hashing already paid.
        Engine thread only; the snapshot tolerates concurrent submits and
        a request cancelled mid-hash just wastes the digests."""
        pc = self.prefix_cache
        if pc is None:
            return
        for req in self.queue_snapshot():
            if (req.digests is None and req.prefix_cache
                    and req.prefix is None and req.swap_handle is None):
                req.digests = self._page_digests(
                    req.ids, len(req.ids) // self.page_size
                )

    def _decode_span(self, was_active: np.ndarray) -> None:
        """One decode SPAN: a first chunk dispatched from the fresh host
        mirrors, then — while :meth:`_overlap_ok` holds — chunk N+1
        dispatched directly from chunk N's device-resident carry (JAX
        async dispatch) with chunk N's host work (token D2H, delivery
        callbacks, digest pre-hashing, metrics) running concurrently
        with N+1 on device.  Every span ends by syncing the carry into
        the host mirrors, so code outside the span always sees fresh
        scheduling state.  Temp-0 outputs are byte-identical to the
        fully-synchronous loop: the chained carry feeds the same
        compiled program the mirrors would, and every scheduling
        decision (admission, growth, preemption, shed, cancel) still
        happens against synced mirrors."""
        if self.faults is not None:
            # Injection site "batcher.decode": one hit per decode /
            # speculative chunk about to be dispatched (dispatched-ahead
            # chunks included).  A "raise" rule here is the canonical
            # engine crash (propagates out of run() into the serving
            # supervisor — a dispatched-ahead chunk in flight is simply
            # dropped with the batcher); "stall" models a wedged device
            # call for the watchdog.
            self.faults.fire("batcher.decode")
        # Mirrors are fresh here by construction (every span ends in
        # _sync_carry, and nothing is in flight between spans), so any
        # cancel recorded before this point already landed on them — only
        # a cancel taken DURING the span must force the next sync.
        self._cancel_dirty = False
        with self._span("batcher.loop.plan"):
            plan = self._span_plan()
        # The first chunk of a span follows an OBSERVED completion (the
        # previous span's sync, an admission's fetch): deliver / admit /
        # grow / plan ran with nothing in flight, and each was charged its
        # part of that starved time (_charge_starved).  A dispatched-ahead
        # chunk is charged nothing by construction: its dispatch precedes
        # its predecessor's fetch.
        out = self._dispatch_chunk(plan, (
            self.last_tok, self.real_lens, self.valid, self.active,
            self.budget,
        ))
        chunks = 1
        while self.overlap:
            with self._span("batcher.loop.plan"):
                ahead = self._overlap_ok(was_active, chunks)
            if not ahead:
                break
            if self.faults is not None:
                self.faults.fire("batcher.decode")
            rng_before = self._rng  # ghost refund point (below)
            nxt = self._dispatch_chunk(plan, out[3])
            chunks += 1
            self.overlap_stats["dispatched_ahead"] += 1
            METRICS.inc("batcher.overlap.dispatched_ahead")
            METRICS.set_gauge("batcher.overlap.depth", 1)
            # Chunk N's host work, concurrent with chunk N+1 on device.
            toks, lps, m, moe, active_after = self._fetch_chunk(out)
            with self._span("batcher.loop.deliver"):
                self._note_moe(moe)
                if self.speculative:
                    self._spec_note(m, was_active, plan)
                if not active_after.any():
                    # Every row died (EOS) during the chunk we just
                    # fetched: the chunk dispatched ahead of it is a GHOST
                    # — all rows inactive, nothing sampled, its rng value
                    # irrelevant.  REFUND its split so the engine RNG
                    # stream stays aligned with the synchronous loop (which
                    # never dispatches the ghost): sampled outputs of
                    # later requests match overlap off, not just temp-0
                    # ones.  Only the last chunk of a span can be a ghost
                    # — the next _overlap_ok sees the all-idle activity
                    # vector and syncs.
                    self._rng = rng_before
                self._collect(toks, was_active, counts=m, lps=lps,
                              active_host=active_after)
                self._prehash_queued()
            was_active = active_after
            out = nxt
        # Sync exit: mirrors refresh BEFORE _collect, so a cancel taken
        # inside the delivery callbacks lands on fresh state (the
        # synchronous loop's exact ordering).
        toks, lps, m, moe, abs_states = self._sync_carry(out)
        with self._span("batcher.loop.deliver"):
            self._note_moe(moe)
            if self.speculative:
                self._spec_note(m, was_active, plan)
            METRICS.set_gauge("batcher.overlap.depth", 0)
            if self.overlap:
                self.overlap_stats["carry_syncs"] += 1
                METRICS.inc("batcher.overlap.carry_syncs")
            if plan["constrain"]:
                # Span boundary: pull the advanced automaton states back into
                # the LOCAL per-row mirrors (abs index minus the row's stack
                # offset) — preemption/cancel/admission decisions run against
                # fresh dfa_row, like every other scheduling mirror.  Rows
                # whose host bookkeeping dropped them mid-span are skipped
                # (rid mismatch — their state is garbage by construction).
                for i, off, rid in plan["constrain"]:
                    row = self.rows[i]
                    if row.rid == rid and row.req is not None \
                            and row.req.constraint is not None:
                        self.dfa_row[i] = int(abs_states[i]) - off
            self._dfa_carry = None
            self._collect(toks, was_active, counts=m, lps=lps)
