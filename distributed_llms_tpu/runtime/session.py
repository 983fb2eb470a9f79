"""Decode sessions with host-DRAM KV spill (SURVEY §7 hard part 3).

The reference kept no state between inference calls (each RUN_INFERENCE was
a fresh placeholder matmul).  Here a *session* keeps its KV cache alive
across turns — continuation prefills only the new chunk — and a bounded
number of sessions stay HBM-resident: the rest are spilled to host DRAM and
restored by ``jax.device_put`` (async; the transfer overlaps the current
request's compute) when the conversation resumes.  This is what makes the
13B-on-8-stages budget work: weights own most of HBM, idle conversations
don't.

Cache layout note: every session's cache is allocated at a fixed
``max_len`` so the jitted step function compiles once per (batch, chunk,
steps) shape, not per history length.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core.config import ModelConfig
from ..core.observability import METRICS, get_logger
from ..models import model as model_lib
from . import sampling

log = get_logger("session")


def continuation_mask(
    valid_mask: jax.Array,  # [B, S] (or [1, S]) prior-content slots
    base: jax.Array,  # scalar int32 — first padded slot of the new chunk
    t: int,  # chunk length (padded)
    slots: jax.Array,  # [S] = arange(S)
) -> jax.Array:
    """[B, 1, T, S] attention mask for prefilling a chunk at slots
    [base, base+t) against existing cache content: query i attends prior
    valid slots plus chunk slots j <= i (right padding means pad slots have
    j greater than every real query's i).  Session continuation's: its
    padded multi-turn layout leaves gaps between the turns.  (The continuous
    batcher's rows have none, slot == position, and hand the model a scalar
    offset and no mask: models.model._continuation_attention.)"""
    rel = slots[None, :] - base  # [1, S]: slot index within the chunk
    chunk_causal = (rel[:, None, :] >= 0) & (
        rel[:, None, :] <= jnp.arange(t, dtype=jnp.int32)[None, :, None]
    )  # [1, T, S]
    return (valid_mask[:, None, :] | chunk_causal)[:, None, :, :]


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "max_new_tokens", "temperature", "top_k", "top_p", "eos_id",
        "pad_id", "forward_fn",
    ),
)
def session_step(
    params: Any,
    cfg: ModelConfig,
    chunk: jax.Array,  # [B, T] int32 new tokens, right-padded
    chunk_lens: jax.Array,  # [B] int32 true lengths
    real_lens: jax.Array,  # [B] int32 tokens already in the session (RoPE base)
    valid_mask: jax.Array,  # [B, S] bool — cache slots holding prior turns
    cache: Any,  # KVCache sized S = session max_len
    base: jax.Array,  # scalar int32 — first free padded cache slot
    rng: jax.Array,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    eos_id: int = -1,
    pad_id: int = 0,
    forward_fn: Any = None,
    slot_positions: jax.Array | None = None,  # [B, S] true RoPE position of
    #   each cache slot — REQUIRED session state for sliding-window models:
    #   the padded multi-turn layout makes slot != position, and the map
    #   depends on every prior turn's base/lens, so it must be carried, not
    #   recomputed.  None for global-attention models.
) -> tuple[jax.Array, Any, jax.Array, jax.Array, jax.Array | None]:
    """Append a chunk to the session and decode.

    Generalizes runtime.generate.generate_tokens: the one-shot case is
    ``base=0, valid_mask=zeros, real_lens=zeros``.  The two are deliberately
    NOT merged — one-shot prefill passes attn_mask=None, which unlocks the
    flash kernel's prefill path, while continuation needs the explicit
    prior-turn mask; tests/runtime/test_session.py pins their equivalence
    (any decode-loop change must land in both).  All rows write the chunk
    at the same padded slots [base, base+T) (single dynamic_update_slice);
    per-row masks keep attention on real slots only; per-row positions
    (``real_lens + i``) keep RoPE/learned-pos correct across turns.

    Returns (new_tokens [B, N], cache, valid_mask', real_lens',
    slot_positions' | None).
    """
    if forward_fn is None:
        forward_fn = _default_forward
    b, t = chunk.shape
    s = cache.k.shape[-3]  # [..., B, S, KVH, HD] -> S
    slots = jnp.arange(s, dtype=jnp.int32)  # [S]

    windowed = cfg.model_window is not None
    if windowed and slot_positions is None:
        raise ValueError(
            "sliding-window sessions need the slot_positions state (the "
            "padded multi-turn layout makes slot != position; engine "
            "sessions allocate and carry it)"
        )

    # --- chunk prefill at padded slots [base, base+t)
    positions = real_lens[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    mask = continuation_mask(valid_mask, base, t, slots)  # [B,1,T,S]
    chunk_kw = {}
    if windowed:
        rel0 = slots[None, :] - base  # [1, S]
        slot_positions = jnp.where(
            (rel0 >= 0) & (rel0 < t),
            real_lens[:, None] + jnp.clip(rel0, 0, t - 1), slot_positions,
        )
        chunk_kw["key_positions"] = slot_positions
    logits, cache = forward_fn(
        params, cfg, chunk, positions=positions, cache=cache,
        cache_index=base, attn_mask=mask, **chunk_kw,
    )
    last_idx = jnp.maximum(chunk_lens - 1, 0)
    next_logits = jnp.take_along_axis(logits, last_idx[:, None, None], axis=1)[:, 0]

    # slots valid after the chunk: prior turns + this chunk's real tokens
    rel = slots[None, :] - base  # [1, S]: slot index within the chunk
    chunk_valid = (rel >= 0) & (rel < chunk_lens[:, None])  # [B, S]
    valid_after_chunk = valid_mask | chunk_valid
    real_after_chunk = real_lens + chunk_lens

    gen_base = base + t  # padded slot where generated tokens start
    gen_kw = {}
    if windowed:
        # Generated slot gen_base + j holds position real_after_chunk + j —
        # fill the whole gen region once (slots past the current step are
        # masked invalid, so early values are never consulted).
        gen_rel = slots[None, :] - gen_base
        slot_positions = jnp.where(
            gen_rel >= 0, real_after_chunk[:, None] + gen_rel, slot_positions
        )
        gen_kw["key_positions"] = slot_positions

    def step(carry, inputs):
        cache, cur_logits, done = carry
        j, rng_step = inputs
        tok = sampling.sample(rng_step, cur_logits, temperature, top_k, top_p)
        tok = jnp.where(done, jnp.int32(pad_id), tok)
        if eos_id >= 0:
            done = jnp.logical_or(done, tok == eos_id)
        gen_valid = (slots[None, :] >= gen_base) & (slots[None, :] <= gen_base + j)
        mask = (valid_after_chunk | gen_valid)[:, None, None, :]
        positions = (real_after_chunk + j)[:, None]
        logits, new_cache = forward_fn(
            params, cfg, tok[:, None],
            positions=positions, cache=cache, cache_index=gen_base + j,
            attn_mask=mask, **gen_kw,
        )
        return (new_cache, logits[:, 0], done), tok

    rngs = jax.random.split(rng, max_new_tokens)
    steps = jnp.arange(max_new_tokens, dtype=jnp.int32)
    done0 = jnp.zeros((b,), dtype=bool)
    (cache, _, _), toks = jax.lax.scan(step, (cache, next_logits, done0), (steps, rngs))
    toks = toks.T  # [B, N]

    gen_valid_final = (slots[None, :] >= gen_base) & (
        slots[None, :] < gen_base + max_new_tokens
    )
    valid_final = valid_after_chunk | gen_valid_final
    real_final = real_after_chunk + max_new_tokens
    return toks, cache, valid_final, real_final, (
        slot_positions if windowed else None
    )


def _default_forward(params, cfg, tokens, positions=None, cache=None,
                     cache_index=None, attn_mask=None, key_positions=None):
    return model_lib.forward(
        params, cfg, tokens, positions=positions, cache=cache,
        cache_index=cache_index, attn_mask=attn_mask,
        key_positions=key_positions,
    )


# ---------------------------------------------------------------------------
# Session state + host spill
# ---------------------------------------------------------------------------

@dataclass
class Session:
    sid: str
    cache: Any  # KVCache (device) when resident; _HostCache when spilled
    valid_mask: jax.Array
    real_lens: jax.Array
    base: int  # next free padded slot (python int — static per call shape)
    max_len: int
    n_real: int = 0  # caller's row count (rest is mesh-divisibility padding)
    # [B, S] true RoPE position per cache slot — sliding-window models only
    # (session_step carries it turn to turn; None for global attention).
    slot_positions: jax.Array | None = None
    last_used: float = field(default_factory=time.monotonic)

    @property
    def spilled(self) -> bool:
        return isinstance(self.cache, _HostCache)


@dataclass
class _HostCache:
    """KV leaves moved to host memory, shardings remembered for restore."""

    leaves: list[np.ndarray]
    treedef: Any
    shardings: list[Any]

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in self.leaves)


class SessionManager:
    """LRU residency manager: at most ``max_resident`` session caches live in
    device memory; the rest live in host DRAM until their next turn."""

    def __init__(self, max_resident: int = 4) -> None:
        if max_resident < 1:
            raise ValueError(f"max_resident must be >= 1, got {max_resident}")
        self.max_resident = max_resident
        self.sessions: dict[str, Session] = {}
        self._counter = 0

    # -- bookkeeping -------------------------------------------------------

    def new_session(self, cache: Any, valid_mask, real_lens, base: int,
                    max_len: int) -> Session:
        self._counter += 1
        sid = f"session-{self._counter}"
        sess = Session(sid, cache, valid_mask, real_lens, base, max_len)
        self.sessions[sid] = sess
        self._enforce_residency(keep=sid)
        return sess

    def get(self, sid: str) -> Session:
        sess = self.sessions.get(sid)
        if sess is None:
            raise KeyError(f"unknown session {sid!r}")
        return sess

    def touch(self, sess: Session) -> None:
        sess.last_used = time.monotonic()
        self._enforce_residency(keep=sess.sid)

    def drop(self, sid: str) -> None:
        self.sessions.pop(sid, None)
        self._update_gauges()

    # -- spill / restore ---------------------------------------------------

    def make_room(self, keep: str | None = None) -> None:
        """Spill LRU residents until one more cache can come in WITHOUT
        exceeding max_resident — called *before* allocating or restoring a
        cache, so peak device memory never holds max_resident + 1 caches
        (the regime kv_host_spill exists for has no slack for that)."""
        resident = sorted(
            (s for s in self.sessions.values() if not s.spilled),
            key=lambda s: s.last_used,
        )
        excess = len(resident) - (self.max_resident - 1)
        for sess in resident:
            if excess <= 0:
                break
            if sess.sid == keep:
                continue
            log.info("spilling %s to host to make room", sess.sid)
            self._spill(sess)
            excess -= 1

    def ensure_resident(self, sess: Session) -> None:
        """Restore a spilled cache onto its original shardings (making room
        first).  device_put is asynchronous — the H2D copy overlaps whatever
        is queued ahead."""
        if not sess.spilled:
            return
        self.make_room(keep=sess.sid)
        hc: _HostCache = sess.cache
        leaves = [
            jax.device_put(a, s) if s is not None else jax.device_put(a)
            for a, s in zip(hc.leaves, hc.shardings)
        ]
        sess.cache = jax.tree.unflatten(hc.treedef, leaves)
        METRICS.inc("kv_spill.restores")
        self._update_gauges()

    def _spill(self, sess: Session) -> None:
        leaves, treedef = jax.tree.flatten(sess.cache)
        shardings = [getattr(a, "sharding", None) for a in leaves]
        host = [np.asarray(a) for a in leaves]  # D2H; frees HBM refs
        sess.cache = _HostCache(host, treedef, shardings)
        METRICS.inc("kv_spill.spills")
        self._update_gauges()

    def _enforce_residency(self, keep: str) -> None:
        resident = [s for s in self.sessions.values() if not s.spilled]
        resident.sort(key=lambda s: s.last_used)
        excess = len(resident) - self.max_resident
        for sess in resident:
            if excess <= 0:
                break
            if sess.sid == keep:
                continue
            log.info("spilling %s to host (%d resident > %d)",
                     sess.sid, len(resident), self.max_resident)
            self._spill(sess)
            excess -= 1
        self._update_gauges()

    def _update_gauges(self) -> None:
        host_bytes = sum(
            s.cache.nbytes for s in self.sessions.values() if s.spilled
        )
        METRICS.set_gauge("kv_spill.host_bytes", host_bytes)
        METRICS.set_gauge(
            "kv_spill.resident_sessions",
            sum(1 for s in self.sessions.values() if not s.spilled),
        )
        METRICS.set_gauge("kv_spill.spilled_sessions",
                      sum(1 for s in self.sessions.values() if s.spilled))
