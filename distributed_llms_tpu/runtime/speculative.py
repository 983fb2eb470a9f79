"""Speculative decoding: draft k tokens with a cheap model, verify them all
in ONE target forward, commit the longest agreeing prefix plus one token.

Decode on TPU is weight-bandwidth bound (one token per full weight
stream; PERF.md, section 5).  Verification reads the target's weights once per ROUND of
up to k+1 tokens instead of once per token, so end-to-end speed multiplies
by ~(mean accepted + 1) while the MXU does a (k+1)-token matmul it is far
better shaped for than single-token decode.  The reference framework has no
speculative path at all (its inference is one placeholder matmul per worker,
src/worker/node.py:24-32) — this is a beyond-parity serving feature.

EXACT at temperature 0: the emitted tokens are identical to
``generate.generate_tokens``'s, for ANY draft model and any k — the draft
only affects speed.  (tests/runtime/test_speculative.py pins this with a
deliberately different draft model.)

DISTRIBUTION-PRESERVING at temperature > 0 (speculative sampling,
Leviathan et al. 2023 / Chen et al. 2023): draft token d_j ~ q_j is
accepted iff u_j < p_j(d_j)/q_j(d_j); on the first rejection the
correction is drawn from normalize(max(p - q, 0)); after k acceptances
the bonus draws from p_{k+1} directly (the unified residual below: q is
zero-extended, so max(p - 0, 0) IS p).  The emitted sequence is an exact
sample from the target's warped (temperature/top-k/top-p) distribution —
the theorem, pinned empirically by tests/runtime/test_speculative.py's
residual-distribution test.  p and q are both post-warp distributions.

TPU-first formulation — the whole loop is one jitted ``lax.while_loop``
with static shapes:

- Rows advance by different amounts per round (per-row acceptance), so all
  cache writes use the per-row ``cache_index`` vector + explicit masks path
  of ``models.model._attention`` (the continuous batcher's machinery).
- Rollback is free: a rejected draft slot is never "undone" — the per-row
  attention masks cap every read at that row's committed frontier, and the
  slot is overwritten the next time the frontier reaches it.  The same
  argument keeps the DRAFT cache correct: its KVs match the committed
  sequence exactly up to the accepted prefix, and everything later is
  masked junk awaiting overwrite.

Slot convention (matches ``generate.generate_tokens``): emitted token i of
row b lives at cache slot T + i with RoPE position prompt_lens[b] + i; a
token's KV is written by the forward call that CONSUMES it.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ..core.config import ModelConfig
from ..models import kv_cache, model as model_lib
from . import sampling


def greedy_accept_commit(
    drafts: jax.Array,   # [B, k] draft tokens d_1..d_k
    greedy: jax.Array,   # [B, k+1] target greedy tokens g_1..g_{k+1}
    live: jax.Array,     # [B] bool — rows that may commit this round
    budget: jax.Array,   # [B] int32 — tokens each row may still emit
    eos_id: int,
    k: int,
    k_row: jax.Array | None = None,  # [B] int32 — per-row effective draft
    #   length (the adaptive spec_k downshift): acceptance is clamped at
    #   j < k_row[b], so a row commits at most k_row[b]+1 tokens.  A
    #   forced stop at j == k_row emits greedy[j] — the token the
    #   sequential greedy decode would emit there — so the stream stays
    #   bit-identical at ANY per-row clamp; only arrival granularity
    #   changes.  Traced, so every clamp value shares one compiled
    #   program (graftcheck GC4 batcher.spec_chunk_paged).
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Greedy acceptance + commit bookkeeping — the SINGLE definition shared
    by the standalone loop and the batcher's spec_chunk (their only
    difference is the cache frontier convention, which stays at the call
    sites).  Returns (cand [B, k+1], m [B], has_eos [B], a [B]): commit
    cand[:m] per row; m accounts for EOS truncation, the budget clamp, and
    dead rows; a is the raw accepted-draft count (for acceptance stats)."""
    agree = drafts == greedy[:, :k]
    if k_row is not None:
        jk = jnp.arange(k, dtype=jnp.int32)
        agree = jnp.logical_and(agree, jk[None, :] < k_row[:, None])
    lead = jnp.cumprod(agree.astype(jnp.int32), axis=1)
    a = jnp.sum(lead, axis=1)                            # [B] in 0..k
    j_ar = jnp.arange(k + 1, dtype=jnp.int32)
    # Accepted drafts then the bonus/correction (greedy[j] at j == a).
    cand = jnp.where(j_ar[None, :] < a[:, None],
                     jnp.concatenate([drafts, drafts[:, -1:]], axis=1),
                     greedy)                             # [B, k+1]
    m, has_eos = commit_clamp(cand, a, live, budget, eos_id, k)
    return cand, m, has_eos, a


def commit_clamp(
    cand: jax.Array,   # [B, k+1] committed candidates
    a: jax.Array,      # [B] accepted-draft counts
    live: jax.Array,   # [B] bool
    budget: jax.Array, # [B] int32
    eos_id: int,
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """The commit count: a+1 candidates, truncated at the first committed
    EOS (inclusive), clamped to the row's budget, zero for dead rows.
    Shared by the greedy and rejection-sampling paths."""
    j_ar = jnp.arange(k + 1, dtype=jnp.int32)
    m = a + 1
    b = cand.shape[0]
    if eos_id >= 0:
        is_eos = jnp.logical_and(cand == eos_id, j_ar[None, :] < m[:, None])
        eos_pos = jnp.argmax(is_eos, axis=1)
        has_eos = jnp.any(is_eos, axis=1)
        m = jnp.where(has_eos, jnp.minimum(m, eos_pos + 1), m)
    else:
        has_eos = jnp.zeros((b,), bool)
    m = jnp.minimum(m, budget)
    m = jnp.where(live, m, 0)
    return m, has_eos


def backfill_coords(
    cand: jax.Array,      # [B, k+1] committed candidates
    m: jax.Array,         # [B] committed counts
    frontier: jax.Array,  # [B] the slot the NEXT round's first feed writes
) -> tuple[jax.Array, jax.Array]:
    """Draft-backfill coordinates (shared by both spec loops): after a
    fully accepted round the draft never consumed the last accepted draft,
    leaving a KV hole one slot below the new frontier.  Rounds with
    2 <= m <= k rewrite an already-correct slot with the same token
    (harmless); m < 2 redirects to the frontier slot, which the next
    round's first feed overwrites before any query reads it."""
    bf_idx = jnp.where(m >= 2, frontier - 1, frontier)
    bf_tok = jnp.take_along_axis(
        cand, jnp.maximum(m - 2, 0)[:, None], axis=1
    )[:, 0]
    return bf_idx, bf_tok


def _prefill(params, cfg, prompt, prompt_lens, max_len):
    b, t = prompt.shape
    cache = kv_cache.init_cache(cfg, b, max_len)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    logits, cache = model_lib.forward(
        params, cfg, prompt, positions=positions, cache=cache,
        cache_index=jnp.int32(0),
    )
    last = jnp.maximum(prompt_lens - 1, 0)
    return jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0], cache


@partial(
    jax.jit,
    static_argnames=(
        "target_cfg", "draft_cfg", "k", "max_new_tokens", "eos_id", "pad_id",
        "return_stats", "temperature", "top_k", "top_p",
    ),
)
def speculative_generate_tokens(
    target_params: Any,
    target_cfg: ModelConfig,
    draft_params: Any,
    draft_cfg: ModelConfig,
    prompt: jax.Array,        # [B, T] int32, right-padded with pad_id
    prompt_lens: jax.Array,   # [B] int32 true lengths
    k: int = 4,               # draft tokens per round
    max_new_tokens: int = 32,
    eos_id: int = -1,         # -1 => never stops early
    pad_id: int = 0,
    return_stats: bool = False,
    temperature: float = 0.0,  # 0 => greedy (bit-exact); > 0 => speculative
    #                            sampling (distribution-preserving)
    top_k: int = 0,
    top_p: float = 1.0,
    rng: jax.Array | None = None,  # required when temperature > 0
) -> jax.Array | tuple[jax.Array, dict[str, jax.Array]]:
    """Speculative decode.  Returns new tokens [B, max_new_tokens]
    (positions after a row's EOS hold pad_id).  temperature == 0: greedy,
    bit-identical to ``generate_tokens(..., temperature=0.0)`` on the
    target alone.  temperature > 0: rejection sampling — an exact sample
    from the target's warped distribution (see module docstring); the RNG
    stream differs from generate_tokens', so per-seed tokens differ while
    the distribution does not.

    With ``return_stats``: also ``{"rounds": scalar, "drafted": scalar,
    "accepted": scalar}`` summed over the batch — mean accepted/drafted is
    the acceptance rate; (accepted + rounds·1)/rounds is tokens per target
    forward, the speedup lever.
    """
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    sampled = temperature > 0.0
    if sampled and rng is None:
        raise ValueError("temperature > 0 requires an rng key")
    for cfg, who in ((target_cfg, "target"), (draft_cfg, "draft")):
        if cfg.ragged_decode:
            # The ragged kernel reads each row's full slot prefix — including
            # right-pad slots the masks here exclude.
            raise ValueError(f"{who} cfg.ragged_decode is unsupported here")
    if target_cfg.vocab_size != draft_cfg.vocab_size:
        raise ValueError(
            "draft and target must share a vocabulary: "
            f"{draft_cfg.vocab_size} != {target_cfg.vocab_size}"
        )

    b, t = prompt.shape
    # Verify can write up to k+1 slots past the last in-budget frontier.
    max_len = t + max_new_tokens + k + 1
    tgt_logits0, tgt_cache = _prefill(
        target_params, target_cfg, prompt, prompt_lens, max_len
    )
    _, drf_cache = _prefill(draft_params, draft_cfg, prompt, prompt_lens, max_len)

    slots = jnp.arange(max_len, dtype=jnp.int32)          # [S]
    prompt_valid = slots[None, :] < prompt_lens[:, None]  # [B, S]
    rows = jnp.arange(b, dtype=jnp.int32)
    # Sliding-window models: true slot->position map for the window mask
    # (shared definition: generate.window_key_positions).
    from .generate import window_key_positions

    def _win_kwargs(cfg):
        if cfg.model_window is None:
            return {}
        return {"key_positions": window_key_positions(t, prompt_lens, max_len)}

    tgt_win = _win_kwargs(target_cfg)
    drf_win = _win_kwargs(draft_cfg)

    def gen_mask(e, q_off):
        """[B, 1, 1, S] valid-keys mask for a query at emitted-index
        e - 1 + q_off (its own write slot included)."""
        hi = t + e - 1 + q_off
        gen = jnp.logical_and(slots[None, :] >= t, slots[None, :] <= hi[:, None])
        return jnp.logical_or(prompt_valid, gen)[:, None, None, :]

    if sampled:
        rng, k0 = jax.random.split(rng)
        tok0 = sampling.sample(k0, tgt_logits0, temperature, top_k, top_p)
    else:
        rng = jax.random.key(0)  # uniform carry shape; never consumed
        tok0 = jnp.argmax(tgt_logits0, axis=-1).astype(jnp.int32)
    out0 = jnp.full((b, max_new_tokens + k + 1), pad_id, jnp.int32)
    out0 = out0.at[:, 0].set(tok0)
    e0 = jnp.ones((b,), jnp.int32)           # tokens emitted so far
    done0 = (tok0 == eos_id) if eos_id >= 0 else jnp.zeros((b,), bool)
    stats0 = jnp.zeros((3,), jnp.int32)      # rounds, drafted, accepted

    def cond(carry):
        _, _, _, e, _, done, _, _ = carry
        return jnp.any(jnp.logical_and(~done, e < max_new_tokens))

    def body(carry):
        tgt_cache, drf_cache, out, e, y, done, stats, rng = carry
        rng, kd, ku, kc = jax.random.split(rng, 4)

        # --- draft: k single-token steps (batched, per-row index).  When
        # sampling, each step also emits its full post-warp distribution
        # q_j — the rejection test needs q_j(d_j) and the residual needs
        # the whole vector.
        def draft_step(dc, inputs):
            drf_cache, cur = dc
            j, kj = inputs
            idx = t + e - 1 + j
            logits, drf_cache = model_lib.forward(
                draft_params, draft_cfg, cur[:, None],
                positions=(prompt_lens + e - 1 + j)[:, None],
                cache=drf_cache, cache_index=idx, attn_mask=gen_mask(e, j),
                **drf_win,
            )
            step_logits = logits[:, 0]
            if sampled:
                warped = sampling.warp_logits(
                    step_logits, temperature, top_k, top_p
                )
                nxt = jax.random.categorical(kj, warped, axis=-1).astype(
                    jnp.int32
                )
                q = jax.nn.softmax(warped, axis=-1)          # [B, V]
                return (drf_cache, nxt), (nxt, q)
            # Greedy emits only the token — no zero-sized q placeholder
            # through the scan (0-element carries inside scan-in-while_loop
            # are exactly the shape XLA:CPU handles worst).
            nxt = jnp.argmax(step_logits, axis=-1).astype(jnp.int32)
            return (drf_cache, nxt), nxt

        (drf_cache, _), draft_ys = jax.lax.scan(
            draft_step, (drf_cache, y),
            (jnp.arange(k, dtype=jnp.int32), jax.random.split(kd, k)),
        )
        if sampled:
            drafts, qs = draft_ys
            qs = jnp.moveaxis(qs, 0, 1)  # [B, k, V]
        else:
            drafts, qs = draft_ys, None
        drafts = drafts.T                # [B, k]: d_1..d_k

        # --- verify: ONE target forward over [y, d_1..d_k] (k+1 tokens).
        vtoks = jnp.concatenate([y[:, None], drafts], axis=1)  # [B, k+1]
        voff = jnp.arange(k + 1, dtype=jnp.int32)
        vmask = jnp.concatenate(
            [gen_mask(e, q) for q in range(k + 1)], axis=2
        )  # [B, 1, k+1, S]
        vlogits, tgt_cache = model_lib.forward(
            target_params, target_cfg, vtoks,
            positions=prompt_lens[:, None] + e[:, None] - 1 + voff[None, :],
            cache=tgt_cache, cache_index=t + e - 1, attn_mask=vmask,
            **tgt_win,
        )
        # Logits after consuming position j of the verify block predict
        # emitted index e+j.
        j_ar = jnp.arange(k + 1, dtype=jnp.int32)
        if sampled:
            ps = jax.nn.softmax(
                sampling.warp_logits(vlogits, temperature, top_k, top_p),
                axis=-1,
            )  # [B, k+1, V]
            # Rejection test: accept d_j iff u_j < p_j(d_j)/q_j(d_j)
            # (u in [0,1) makes min(1, ratio) implicit).
            p_at = jnp.take_along_axis(
                ps[:, :k], drafts[..., None], axis=-1
            )[..., 0]                                        # [B, k]
            q_at = jnp.take_along_axis(
                qs, drafts[..., None], axis=-1
            )[..., 0]                                        # [B, k]
            u = jax.random.uniform(ku, (b, k))
            accept = u * jnp.maximum(q_at, 1e-20) < p_at
            lead = jnp.cumprod(accept.astype(jnp.int32), axis=1)
            a = jnp.sum(lead, axis=1)                        # [B] in 0..k
            # Unified residual: zero-extend q so position k's "residual"
            # is p_{k+1} itself (the bonus draw).
            q_ext = jnp.concatenate(
                [qs, jnp.zeros_like(ps[:, :1])], axis=1
            )                                                # [B, k+1, V]
            p_a = jnp.take_along_axis(ps, a[:, None, None], axis=1)[:, 0]
            q_a = jnp.take_along_axis(q_ext, a[:, None, None], axis=1)[:, 0]
            resid = jnp.maximum(p_a - q_a, 0.0)
            norm = jnp.sum(resid, axis=-1, keepdims=True)
            # Numerical guard: p == q on the whole support leaves an empty
            # residual; fall back to p (any sample from it is valid there).
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
            budget = max_new_tokens - e                      # pre-commit
            m, has_eos = commit_clamp(cand, a, ~done, budget, eos_id, k)
        else:
            greedy_toks = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)
            budget = max_new_tokens - e                      # pre-commit
            cand, m, has_eos, a = greedy_accept_commit(
                drafts, greedy_toks, ~done, budget, eos_id, k
            )

        # Scatter the committed tokens into the (padded-wide) out buffer.
        valid = j_ar[None, :] < m[:, None]                   # [B, k+1]
        idx = jnp.where(valid, e[:, None] + j_ar[None, :],
                        out.shape[1] - 1)                    # scratch col
        vals = jnp.where(valid, cand, pad_id)
        out = out.at[rows[:, None], idx].set(vals)
        # (Duplicate scratch-column writes: XLA picks a winner; all pad_id.)
        out = out.at[:, out.shape[1] - 1].set(pad_id)

        y = jnp.where(
            m > 0, jnp.take_along_axis(cand, jnp.maximum(m - 1, 0)[:, None],
                                       axis=1)[:, 0], y,
        )
        e = e + m
        done = jnp.logical_or(done, jnp.logical_and(has_eos, m > 0))

        # --- draft backfill: after a FULLY accepted round (m == k+1) the
        # draft proposed d_k but never consumed it, leaving a zero-KV hole
        # one slot below the new frontier t+e-1 (backfill_coords has the
        # full rationale; a hole silently wrecks acceptance from then on).
        bf_idx, bf_tok = backfill_coords(cand, m, frontier=t + e - 1)
        bf_gen = jnp.logical_and(slots[None, :] >= t,
                                 slots[None, :] <= bf_idx[:, None])
        bf_mask = jnp.logical_or(prompt_valid, bf_gen)[:, None, None, :]
        _, drf_cache = model_lib.forward(
            draft_params, draft_cfg, bf_tok[:, None],
            positions=(prompt_lens + bf_idx - t)[:, None],
            cache=drf_cache, cache_index=bf_idx, attn_mask=bf_mask,
            **drf_win,
        )
        stats = stats + jnp.array([1, 0, 0], jnp.int32)
        # Drafted counts only drafts that HAD a chance to commit: the budget
        # caps a round at `budget` tokens, so at most min(k, budget) drafts
        # were in play — counting the full k would deflate the acceptance
        # rate of a perfect draft whenever (n-1) % (k+1) lands mid-round.
        # (EOS truncation still counts the post-EOS drafts: that loss is
        # data, not bookkeeping.)  Self-draft, no EOS => accepted == drafted
        # exactly, for ANY n and k — the verify invariant.
        stats = stats.at[1].add(
            jnp.sum(jnp.where(m > 0, jnp.minimum(k, budget), 0))
        )
        # Committed drafts this round: all m tokens when a clamp (EOS/budget)
        # cut the round short of its bonus token, else the a accepted drafts.
        stats = stats.at[2].add(jnp.sum(jnp.minimum(a, m)))
        return tgt_cache, drf_cache, out, e, y, done, stats, rng

    carry = (tgt_cache, drf_cache, out0, e0, tok0, done0, stats0, rng)
    *_, out, _, _, _, stats, _ = jax.lax.while_loop(cond, body, carry)
    toks = out[:, :max_new_tokens]
    if return_stats:
        return toks, {"rounds": stats[0], "drafted": stats[1],
                      "accepted": stats[2]}
    return toks
