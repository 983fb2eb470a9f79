"""Core transformer layer primitives (pure functions over param pytrees).

Replaces the reference's compute path end-to-end: its "forward" was a
placeholder per-parameter ``torch.matmul`` (src/worker/node.py:24-32) and its
model loading leaned on torch/transformers (src/model/loader.py:5-25).  Here
the decoder blocks are real, written TPU-first:

- params are plain pytrees of jnp arrays, **stacked over the layer axis** so
  layers run under ``lax.scan`` (one trace, XLA-friendly) and pipeline stages
  are contiguous slices of the stacked axis;
- matmuls are einsums in bf16 hitting the MXU; softmax/norms accumulate f32;
- no data-dependent Python control flow — everything jits.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ..core.config import ModelConfig

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (Llama family)
# ---------------------------------------------------------------------------

def rope_frequencies(
    head_dim: int, theta: float,
    scaling: tuple[float, float, float, int] | None = None,
) -> jax.Array:
    """Inverse frequencies, shape [head_dim // 2], float32.

    ``scaling`` = (factor, low_freq_factor, high_freq_factor,
    original_max_len) applies Llama-3.1's piecewise rescale (HF
    _compute_llama3_parameters): wavelengths beyond
    original_max_len/low_freq_factor divide by ``factor`` (stretched for
    long context), wavelengths under original_max_len/high_freq_factor
    keep their frequency, and the band between interpolates smoothly.
    """
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    inv_freq = 1.0 / (theta**exponent)
    if scaling is not None:
        factor, low, high, old_len = scaling
        wavelen = 2.0 * jnp.pi / inv_freq
        smooth = (old_len / wavelen - low) / (high - low)
        smooth = jnp.clip(smooth, 0.0, 1.0)  # 0 = fully scaled, 1 = kept
        inv_freq = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
    return inv_freq


def yarn_frequencies(
    dim: int, theta: float, factor: float, original_max_len: int,
    beta_fast: float = 32.0, beta_slow: float = 1.0,
) -> jax.Array:
    """YaRN's inverse frequencies (DeepSeek's form), [dim // 2] float32.
    ``corr(r)`` is the (fractional) index of the frequency that turns r
    times within the original context; below ``floor(corr(beta_fast))`` a
    frequency is kept, above ``ceil(corr(beta_slow))`` it is divided by
    ``factor``, and a linear ramp lies between."""
    f = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))

    def corr(rotations):
        return (dim * math.log(original_max_len / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low)
        / max(high - low, 0.001), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope_pairs(
    x: jax.Array, positions: jax.Array, inv_freq: jax.Array,
    mscale: float = 1.0,
) -> jax.Array:
    """Rotate the pairs (2i, 2i + 1) of x's last axis together (DeepSeek's
    layout, which the latent-attention layers use) by ``positions *
    inv_freq``, cos and sin times ``mscale``.  x: [B, T, ..., D];
    positions [B, T]."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, T, D/2]
    angles = angles.reshape(*angles.shape[:2], *(1,) * (x.ndim - 3), -1)
    cos, sin = jnp.cos(angles) * mscale, jnp.sin(angles) * mscale
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    x1, x2 = xf[..., 0], xf[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float,
    scaling: tuple[float, float, float, int] | None = None,
) -> jax.Array:
    """Rotate half-pairs.  x: [B, T, H, D]; positions: [B, T] int32.

    Uses the HF/Llama convention: the head dim is split into two halves
    (x1 = x[..., :D/2], x2 = x[..., D/2:]) rotated jointly — matches the
    checkpoint layout our converter targets.  ``scaling`` is the Llama-3.1
    frequency rescale (see rope_frequencies).
    """
    half = x.shape[-1] // 2
    freqs = rope_frequencies(x.shape[-1], theta, scaling)  # [half]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, half]
    cos = jnp.cos(angles)[:, :, None, :]  # [B, T, 1, half]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x1f = x1.astype(jnp.float32)
    x2f = x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def repeat_kv(x: jax.Array, q_per_kv: int) -> jax.Array:
    """[B, S, KVH, D] -> [B, S, KVH*q_per_kv, D] for grouped-query attention."""
    if q_per_kv == 1:
        return x
    b, s, kvh, d = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, kvh, q_per_kv, d))
    return x.reshape(b, s, kvh * q_per_kv, d)


def dot_product_attention(
    q: jax.Array,  # [B, Tq, H, D]
    k: jax.Array,  # [B, Tk, H, D]
    v: jax.Array,  # [B, Tk, H, D]
    mask: jax.Array | None,  # broadcastable to [B, H, Tq, Tk]; True = attend
    scale: float | None = None,  # default: head width ** -0.5
) -> jax.Array:
    """Softmax(QK^T)V with f32 accumulation.  XLA fuses this into MXU-friendly
    batched matmuls; the Pallas flash kernel in ops/ is the drop-in for long
    sequences.  v's heads may be narrower than q's and k's."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def causal_mask(
    q_positions: jax.Array,
    k_positions: jax.Array,
    k_valid: jax.Array | None = None,
    window: int | None = None,
) -> jax.Array:
    """Boolean mask [B, 1, Tq, Tk]: query at position p attends keys at
    positions <= p.  ``k_valid`` ([B, Tk] bool) masks unwritten cache slots.
    ``window`` (Mistral sliding-window attention) further restricts keys to
    positions in (p - window, p]."""
    mask = k_positions[:, None, None, :] <= q_positions[:, None, :, None]
    if k_valid is not None:
        mask = jnp.logical_and(mask, k_valid[:, None, None, :])
    if window is not None:
        mask = and_window(mask, q_positions, k_positions, window)
    return mask


def and_window(
    mask: jax.Array,
    q_positions: jax.Array,
    k_positions: jax.Array,
    window: int,
) -> jax.Array:
    """AND the sliding-window lower bound (keys in (p - window, p]) into an
    existing attention mask — the single definition of the window semantics,
    shared by causal_mask and the caller-supplied-mask paths in
    models.model._attention."""
    return jnp.logical_and(
        mask,
        k_positions[:, None, None, :] > q_positions[:, None, :, None] - window,
    )


# ---------------------------------------------------------------------------
# Projections (einsum conventions shared by all families)
# ---------------------------------------------------------------------------

def _is_quantized(w: Any) -> bool:
    # Duck-typed (bits/scale/data) to keep layers import-light; the leaf type
    # is checkpoint.quantize.QuantizedTensor.
    return hasattr(w, "bits") and hasattr(w, "scale") and hasattr(w, "data")


def _weight_ndim(w: Any) -> int:
    """Axes of ONE layer's weight as the model has it: a quantized leaf is
    stored as a matrix whatever they are, and may be a whole stack read at
    an index (``QuantizedTensor.at``)."""
    return sum(map(len, w.tail_shape)) if _is_quantized(w) else w.ndim


def _contract(x: jax.Array, w: Any, eq: str, k_lead: int, shard: str) -> jax.Array:
    """einsum for plain weights; fused dequant-matmul (ops/quant_matmul) for
    QuantizedTensor weights under weight-only quantized serving: one
    layer's, or the stack of every layer's carrying the index of the one
    to read (``QuantizedTensor.at``), which the kernel reads where it lies,
    and beside it the count of real rows of a padded admission, if known.
    ``shard`` is the weight's tensor-parallel role, "n" (output axis split
    over 'model') or "k" (contracted axis split), which the kernel needs to
    run per shard; XLA partitions the plain einsum by itself."""
    if _is_quantized(w):
        from ..ops.quant_matmul import quant_contract

        return quant_contract(x, w, k_lead, eq, shard=shard, rows=w.rows)
    return jnp.einsum(eq, x, w)


def _plain(b: Any) -> jax.Array:
    """Rehydrate a (rare, legacy-store) quantized bias/vector leaf."""
    if _is_quantized(b):
        from ..checkpoint.quantize import dequantize

        return dequantize(b)
    return b


def qkv_project(x: jax.Array, p: Params, cfg: ModelConfig) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x: [B, T, D] -> q [B, T, H, hd], k/v [B, T, KVH, hd].

    Weight layout: wq [D, H, hd], wk/wv [D, KVH, hd] — head axis explicit so
    tensor-parallel sharding annotates the head dim directly.
    """
    if _weight_ndim(p["wq"]) == 2:
        # [D, H * hd], the head axes stored flat (the hybrid family).
        # (-1: a gated layer's W_q is twice as wide, [query | gate] a head:
        # cfg.attn_out_gate, split in models.model._attention)
        q, k, v = (
            _contract(x, p[w], "btd,dn->btn", 1, "n").reshape(
                *x.shape[:2], n, -1)
            for w, n in (("wq", cfg.num_heads), ("wk", cfg.num_kv_heads),
                         ("wv", cfg.num_kv_heads))
        )
        return q, k, v
    q = _contract(x, p["wq"], "btd,dhk->bthk", 1, "n")
    k = _contract(x, p["wk"], "btd,dhk->bthk", 1, "n")
    v = _contract(x, p["wv"], "btd,dhk->bthk", 1, "n")
    if "bq" in p:
        q = q + _plain(p["bq"])
        k = k + _plain(p["bk"])
        v = v + _plain(p["bv"])
    return q, k, v


def out_project(x: jax.Array, p: Params,
                gate: jax.Array | None = None) -> jax.Array:
    """x: [B, T, H, hd] -> [B, T, D].  wo: [H, hd, D].  ``gate`` [B, T, H,
    hd]: the attention's output times ``sigmoid(gate)`` first (a gated
    attention layer's, ``cfg.attn_out_gate``)."""
    if gate is not None:
        with jax.named_scope("attn_gate"):
            x = (x.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(x.dtype)
    out = _contract(x, p["wo"], "bthk,hkd->btd", 2, "k")
    if "bo" in p:
        out = out + _plain(p["bo"])
    return out


@jax.named_scope("mlp")  # profiler scope; HLO metadata only
def mlp_gelu(x: jax.Array, p: Params, activation: str = "gelu") -> jax.Array:
    """GPT-2-layout MLP: act(x W_in + b) W_out + b.  ``activation``:
    "relu" (OPT), "gelu_exact" (erf gelu — HF's "gelu"), anything else the
    tanh approximation (HF's "gelu_new", GPT-2's convention)."""
    h = _contract(x, p["w_in"], "btd,df->btf", 1, "n") + _plain(p["b_in"])
    if activation == "relu":
        h = jax.nn.relu(h)
    elif activation == "gelu_exact":
        h = jax.nn.gelu(h, approximate=False)
    elif activation in ("gelu", "gelu_new"):
        h = jax.nn.gelu(h, approximate=True)
    else:  # loud, not silently-gelu: wrong activation = wrong logits
        raise ValueError(f"unsupported MLP activation {activation!r}")
    return _contract(h, p["w_out"], "btf,fd->btd", 1, "k") + _plain(p["b_out"])


def _relu2(g: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(g))


def _gelu_tanh(g: jax.Array) -> jax.Array:
    return jax.nn.gelu(g, approximate=True)


def gate_fn(gate_act: str):
    """The gate's activation of a gated MLP, by ``cfg.gate_act``: "silu"
    (Llama/Qwen2), "gelu_tanh" (Gemma's GeGLU) or "relu" (a ReGLU:
    SmallThinker's experts).  The dense MLP, the shared expert and the
    routed experts of both expert layers read it here.  "relu2" is
    ``relu(.)^2`` and gates nothing: the activation of an MLP of two
    matrices (:func:`mlp_plain`; the latent experts)."""
    # (module-level functions: the expert kernel takes one as a static
    # argument, and a fresh lambda a call would be a fresh program a call)
    return {"silu": jax.nn.silu, "relu": jax.nn.relu,
            "gelu_tanh": _gelu_tanh, "relu2": _relu2}[gate_act]


@jax.named_scope("mlp")  # profiler scope; HLO metadata only
def mlp_swiglu(x: jax.Array, p: Params, gate_act: str = "silu") -> jax.Array:
    """Gated MLP: (act(x W_gate) * (x W_up)) W_down, no biases
    (``gate_act``: :func:`gate_fn`)."""
    gate = _contract(x, p["w_gate"], "btd,df->btf", 1, "n")
    up = _contract(x, p["w_up"], "btd,df->btf", 1, "n")
    h = gate_fn(gate_act)(gate) * up
    return _contract(h, p["w_down"], "btf,fd->btd", 1, "k")


@jax.named_scope("mlp")  # profiler scope; HLO metadata only
def mlp_plain(x: jax.Array, p: Params, act: str) -> jax.Array:
    """MLP of two matrices, no gate, no biases: act(x W_up) W_down."""
    h = gate_fn(act)(_contract(x, p["w_up"], "btd,df->btf", 1, "n"))
    return _contract(h, p["w_down"], "btf,fd->btd", 1, "k")


def route_experts(
    logits: jax.Array, cfg: ModelConfig, bias: jax.Array | None = None,
    summed: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Router logits [S, E] float32 -> (weights [S, k] float32, expert ids
    [S, k]).  ``cfg.moe_score_fn``: "softmax" takes the top k logits and
    softmaxes over them (Mixtral); "sigmoid" scores every expert by
    ``sigmoid(logit)``, picks the top k of score + ``bias`` (the selection
    bias picks, it does not weigh), weighs by the chosen scores over their
    sum + ``moe_norm_eps`` (``moe_norm_topk``) times ``moe_routed_scale``
    (LFM2-MoE).  With ``moe_n_group`` > 1 the experts are that many
    consecutive runs, a group scores as its best member, and only the
    experts of the ``moe_topk_group`` best groups can be picked (A.X-K1,
    DeepSeek-V3's rule without a correction bias).

    ``summed``: the chosen scores as a sum over E of the one score a pick
    names, not a gather of S x k scalars: the same bits, and a scalar
    gathered costs 10 ns on the chip (16 against 2 us a layer at a decode
    step's 64 x 22 picks, 0.47 against 0.05 ms at an admission block's
    2,048 x 22: PERF.md section 6, PR 56).  The served path asks for it
    (:func:`moe_dropless`); the capacity path, which is differentiated and
    was not measured, does not."""
    k = cfg.num_experts_per_token
    if cfg.moe_score_fn == "softmax":
        topv, topi = jax.lax.top_k(logits, k)
        return jax.nn.softmax(topv, axis=-1), topi
    scores = jax.nn.sigmoid(logits)
    pick = scores if bias is None else scores + bias
    if cfg.moe_n_group > 1:
        s, e = pick.shape
        grouped = pick.reshape(s, cfg.moe_n_group, e // cfg.moe_n_group)
        _, best = jax.lax.top_k(jnp.max(grouped, axis=-1), cfg.moe_topk_group)
        kept = jnp.any(
            best[:, :, None] == jnp.arange(cfg.moe_n_group)[None, None, :],
            axis=1)  # [S, groups]
        pick = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(s, e)
    _, topi = jax.lax.top_k(pick, k)
    if summed:
        # Behind a barrier: XLA otherwise folds this sum into the
        # normaliser's sum over k below, another order, and the weights
        # move by an ulp, which the goldens do not forgive.
        w = jax.lax.optimization_barrier(jnp.sum(jnp.where(
            topi[:, :, None] == jnp.arange(scores.shape[-1])[None, None, :],
            scores[:, None, :], 0.0), axis=-1))
    else:
        w = jnp.take_along_axis(scores, topi, axis=-1)
    if cfg.moe_norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + cfg.moe_norm_eps)
    return w * cfg.moe_routed_scale, topi


def router_logits(x: jax.Array, router: jax.Array) -> jax.Array:
    """An expert layer's router: x [..., D] @ router [D, E], both in
    float32 at the highest precision -> logits [..., E] float32."""
    return jnp.einsum(
        "...d,de->...e", x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


def moe_dropless(
    x: jax.Array, p: Params, cfg: ModelConfig,
    token_mask: jax.Array | None = None, layer: jax.Array | int = 0,
    logits: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Expert FFN without a capacity rule (``cfg.moe_capacity`` False; the
    llama family comes here through :func:`moe_dropless_layer`): every
    token gets its k experts whatever the other tokens chose, so a row's
    result never depends on its batch-mates.  The pairs are grouped
    by expert and run through ops/moe_experts.py, which reads int8 expert
    stacks a tile at a time and only the experts some token chose.

    p holds EVERY expert layer's leaves, stacked, and ``layer`` names the
    one to run (traced inside a layer scan): router [L, D, E] float32,
    expert_bias [L, E] (with cfg.moe_expert_bias), experts/w_gate_up
    [L, E, D, 2F], experts/w_down [L, E, F, D].  The router's slice is
    cut out here; the expert stacks go to the kernel whole.
    ``token_mask`` [B, T] marks the real tokens (padding of an admission
    bucket and rows that are not decoding are routed too, but get no row
    of the grouped list, ops.moe_experts.grouped_swiglu, and zeros; they
    are not counted).  Returns (y, stats): stats
    int32 [4] = routed pairs, layer passes (1 if any token is real),
    experts with at least one real token, the fullest expert's real tokens
    — the sources of ``moe.*`` counters (runtime/batcher.py).

    ``logits`` [B, T, E] float32: the router's logits, where the caller
    took them from another tensor than ``x`` (``cfg.moe_router_input``
    "block_input": models.model.run_layers reads the block's input with
    :func:`router_logits` before the operator runs); None: the router
    reads ``x``, what the experts read.

    A config that holds a chip's share of the experts
    (``cfg.experts_held``) routes over all ``num_experts`` and computes
    the pairs that fell on experts [offset, offset + held): the rest add
    nothing here (their chips would), experts touched and the fullest
    expert's load count the held ones, and stats has a fifth entry, the
    pairs that fell on a held expert, and a sixth, those of them whose rows
    the combine fetched singly (ops.moe_experts._pairs_rows; 0 where the
    gather of every pair's row made its operand).  (A shared expert, which every
    token goes through, is the caller's to add: models.model.run_layers.)

    With ``cfg.moe_latent_size`` the routed experts work in a latent:
    ``c = x W_dn`` (p["latent"]["w_dn"] [L, D, latent]) before the pairs are
    grouped, the experts two matrices each on it (``w_up`` [L, E, latent,
    F], ``w_down`` [L, E, F, latent], not gated), and the weighted sum goes
    back through ``W_up`` ([L, latent, D]).  The router still reads ``x``."""
    from ..ops import moe_experts

    b, t, d = x.shape
    xf = x.reshape(b * t, d)
    with jax.named_scope("moe_route"):
        if logits is None:
            logits = router_logits(xf, p["router"][layer])
        logits = logits.reshape(b * t, -1)
        bias = p["expert_bias"][layer] if "expert_bias" in p else None
        w, topi = route_experts(logits, cfg, bias, summed=True)
        real = (jnp.ones((b * t,), bool) if token_mask is None
                else token_mask.reshape(b * t))
        share = cfg.experts_held is not None
        # An id outside the held run has no one-hot column: not counted.
        local = topi - cfg.experts_offset if share else topi
        load = jnp.sum(  # real tokens an expert (a HELD expert): [E]
            jax.nn.one_hot(local, cfg.held_experts, dtype=jnp.int32)
            * real[:, None, None], axis=(0, 1))
        stats = [
            jnp.sum(real) * topi.shape[1] if share else jnp.sum(load),
            jnp.any(real).astype(jnp.int32),
            jnp.sum(load > 0, dtype=jnp.int32), jnp.max(load),
        ]
        stats = jnp.stack(stats + ([jnp.sum(load)] if share else []))
    latent = bool(cfg.moe_latent_size)
    if latent:
        with jax.named_scope("moe_latent"):
            xf = _contract(x, _layer_leaf(p["latent"]["w_dn"], layer, x),
                           "btd,dn->btn", 1, "n").reshape(b * t, -1)
    with jax.named_scope("moe_experts"):
        ex = p["experts"]
        y, fetched = moe_experts.grouped_swiglu(
            xf, local, ex["w_up" if latent else "w_gate_up"], ex["w_down"],
            layer, of_experts=cfg.num_experts if share else None,
            act=gate_fn(cfg.gate_act), gated=not latent,
            token_mask=None if token_mask is None else real,
            count_fetched=True)
        if share:
            stats = jnp.concatenate([stats, fetched[None]])
        y = jnp.sum(y.astype(jnp.float32) * w[:, :, None], axis=1)
    y = y.reshape(b, t, -1).astype(x.dtype)
    if latent:
        with jax.named_scope("moe_latent"):
            y = _contract(y, _layer_leaf(p["latent"]["w_up"], layer, x),
                          "btn,nd->btd", 1, "k")
    return y, stats


def _layer_leaf(w: Any, layer, x: jax.Array) -> Any:
    """Layer ``layer`` of a stacked matrix [L, K, N]: a quantized stack
    whole with the index (``QuantizedTensor.at``: the kernel reads the
    layer's tiles where they lie), a float one sliced."""
    if _is_quantized(w):
        return w.at(layer, None)
    return w[layer].astype(x.dtype)


@jax.named_scope("mlp")  # profiler scope; HLO metadata only
def moe_dropless_layer(x: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    """:func:`moe_dropless` for ONE layer's leaves as the llama family
    keeps them (router [D, E], w_gate / w_up [E, D, F], w_down [E, F, D]:
    what :func:`moe_swiglu` takes), for a config whose ``moe_capacity`` is
    False.  Gate and up are laid side by side here, a copy a call, and
    quantized leaves (blocks along their last axis) are dequantized first
    as moe_swiglu does: the float leg of ops/moe_experts.py runs them.  A
    model that serves int8 experts at speed stacks them as the hybrid
    family does."""
    if any(_is_quantized(w) for w in p.values()):
        from ..checkpoint.quantize import dequantize_tree

        p = dequantize_tree(p, x.dtype)
    stacked = {
        "router": p["router"][None],
        "experts": {
            "w_gate_up": jnp.concatenate(
                [p["w_gate"], p["w_up"]], axis=-1)[None],
            "w_down": p["w_down"][None],
        },
    }
    if "expert_bias" in p:
        stacked["expert_bias"] = p["expert_bias"][None]
    return moe_dropless(x, stacked, cfg)[0]


def causal_conv(x: jax.Array, taps: jax.Array, state: jax.Array | None,
                seq_lens: jax.Array | None) -> tuple[jax.Array, jax.Array]:
    """A causal depthwise convolution of K taps a channel over x [B, T, C]
    behind the row's last K - 1 inputs ``state`` [B, K - 1, C] (None: zeros,
    a row's start); taps [C, K].  -> (the sums [B, T, C] float32, the K - 1
    inputs that end at each row's ``seq_lens`` REAL tokens (None: all T): a
    right-padded prompt leaves the inputs of its true length and a row with
    no real token keeps its own).  The state-space and the delta-rule
    layers' (models.model.ssm_layer, gdn_layer), each with its own bias,
    activation and split behind it."""
    b, t, width = x.shape
    taps = taps.astype(jnp.float32)
    k = taps.shape[-1]
    if state is None:
        state = jnp.zeros((b, k - 1, width), x.dtype)
    win = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    out = sum(taps[:, j] * win[:, j: j + t].astype(jnp.float32)
              for j in range(k))
    if seq_lens is None:
        return out, win[:, t:]
    return out, jax.vmap(
        lambda w, n: jax.lax.dynamic_slice_in_dim(w, n, k - 1, axis=0)
    )(win, seq_lens)


@jax.named_scope("conv")  # profiler scope; HLO metadata only
def short_conv(
    x: jax.Array, p: Params, state: jax.Array | None = None,
    seq_lens: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Gated short convolution (LFM2): ``[B, C, z] = split3(x W_in)``,
    ``g = B * z``, a depthwise causal convolution of K taps a channel over
    g, ``(C * conv) W_out``.  x: [Bt, T, D]; p: in_proj [D, 3D], taps
    [D, K], out_proj [D, D].

    The layer's whole memory is the last K-1 gated inputs a row:
    ``state`` [Bt, K-1, D] (None: zeros, a sequence's start) goes in front
    of g, and the state handed back is the K-1 rows that end at the row's
    ``seq_lens`` REAL new tokens (None: all T).  So a right-padded prompt
    leaves the state of its true length, not of the bucket's end, a row
    with no real token this step (seq_lens 0) keeps its state, and a
    prompt shorter than K-1 keeps zeros in front."""
    bcz = _contract(x, p["in_proj"], "btd,df->btf", 1, "n")
    gate_b, gate_c, z = jnp.split(bcz, 3, axis=-1)
    g = gate_b * z
    taps = p["taps"].astype(jnp.float32)  # [D, K]
    k = taps.shape[-1]
    bt, t, d = g.shape
    if state is None:
        state = jnp.zeros((bt, k - 1, d), g.dtype)
    win = jnp.concatenate([state.astype(g.dtype), g], axis=1)  # [Bt, T+K-1, D]
    conv = sum(
        taps[:, j] * win[:, j: j + t].astype(jnp.float32) for j in range(k)
    ).astype(x.dtype)
    out = _contract(gate_c * conv, p["out_proj"], "btd,de->bte", 1, "k")
    if seq_lens is None:
        return out, win[:, t:]
    new = jax.vmap(
        lambda w, n: jax.lax.dynamic_slice_in_dim(w, n, k - 1, axis=0)
    )(win, seq_lens)
    return out, new


@jax.named_scope("mlp")  # profiler scope; HLO metadata only
def moe_swiglu(
    x: jax.Array, p: Params, cfg: ModelConfig
) -> tuple[jax.Array, jax.Array]:
    """Mixture-of-experts SwiGLU MLP (Mixtral-style routing, GShard-style
    capacity semantics, scatter-based dispatch).  Net-new vs the reference
    (SURVEY §2.3: MoE absent).  Returns (output, aux_load_balance_loss).

    - router: :func:`route_experts` (top-k experts per token, gates =
      softmax over the k logits unless the config says otherwise);
    - dispatch: every (token, choice) claim computes its slot index
      ``expert * cap + position_in_expert`` and the token rows are
      scatter-added into a per-expert buffer [E, C, D] — O(tokens·D) memory,
      not the O(tokens²) of dense one-hot dispatch tensors.  C =
      ceil(capacity_factor · k · tokens / E); earlier-ranked choices win
      capacity first, overflow claims are dropped (contribute zero) — all
      shapes static, XLA-friendly;
    - expert compute: per-expert SwiGLU over stacked weights [E, D, F]; with
      the expert axis sharded over the 'expert' mesh axis, GSPMD turns the
      scatter/gather into the all-to-alls of expert parallelism;
    - aux loss: Switch-Transformer load-balancing term
      ``E · Σ_e importance_e · load_e`` (mean router prob × dispatched
      fraction) — scale by ``cfg.moe_aux_loss_weight`` and add to the task
      loss, or the router collapses and capacity silently drops most tokens.

    p: router [D, E], w_gate/w_up [E, D, F], w_down [E, F, D].

    Quantized-resident expert weights rehydrate here (per layer, inside the
    scan): the fused kernel targets 2D contractions, not the batched
    per-expert einsums below.  This is the path WITH the capacity rule
    (``cfg.moe_capacity``: the training path, mixtral-8x7b, moe-tiny); a
    served model takes :func:`moe_dropless`.
    """
    if any(_is_quantized(w) for w in p.values()):
        from ..checkpoint.quantize import dequantize_tree

        p = dequantize_tree(p, x.dtype)
    b, t, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_token
    s = b * t
    cap = max(1, int(-(-cfg.moe_capacity_factor * k * s // e)))  # ceil
    xf = x.reshape(s, d)

    logits = jnp.einsum(
        "sd,de->se", xf, p["router"], preferred_element_type=jnp.float32
    )
    gates, topi = route_experts(logits, cfg, p.get("expert_bias"))  # [s, k]

    # Choice-major claim order: every token's 1st choice claims capacity
    # before any 2nd choice does.  eid: [k*s] expert id per claim.
    eid = topi.T.reshape(k * s)
    oh = jax.nn.one_hot(eid, e, dtype=jnp.float32)  # [k*s, e]
    pos = jnp.sum((jnp.cumsum(oh, axis=0) - 1.0) * oh, axis=-1)  # [k*s]
    keep = pos < cap
    slot = jnp.where(keep, eid * cap + pos.astype(jnp.int32), e * cap)

    token_idx = jnp.tile(jnp.arange(s), k)  # claim -> source token
    buf = jnp.zeros((e * cap + 1, d), xf.dtype)  # +1: overflow dump row
    buf = buf.at[slot].add(xf[token_idx] * keep[:, None].astype(xf.dtype))
    xe = buf[:-1].reshape(e, cap, d)

    g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    ye = jnp.einsum(
        "ecf,efd->ecd", gate_fn(cfg.gate_act)(g) * u, p["w_down"])

    yflat = jnp.concatenate([ye.reshape(e * cap, d), jnp.zeros((1, d), ye.dtype)])
    gathered = yflat[slot]  # [k*s, d]; dropped claims hit the zero row
    w = (gates.T.reshape(k * s) * keep).astype(gathered.dtype)
    y = jnp.sum((gathered * w[:, None]).reshape(k, s, d), axis=0)

    # Switch-style load-balance aux: importance (mean prob) x load (dispatch
    # fraction) per expert, scaled by E so the balanced value is ~1.
    probs = jax.nn.softmax(logits, axis=-1)  # [s, e] f32
    importance = jnp.mean(probs, axis=0)
    load = jnp.sum(oh * keep[:, None].astype(oh.dtype), axis=0) / (s * k)
    aux = e * jnp.sum(importance * load)
    return y.reshape(b, t, d).astype(x.dtype), aux
