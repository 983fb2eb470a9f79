"""Plain reference of the A.X-K1 forward pass (``model_type`` ``axk1``).

What the served path is held to: ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernel, no cache, no
batching, one Python loop over the layers and one over the experts.  It
imports nothing of the package, so that a change to the system cannot move
it; ``benchmark/reference/axk1.py`` is a byte-for-byte copy
(tests/models/test_axk1.py).

Equations, from the published ``config.json`` and the family's public
modelling code (DeepSeek-V2/V3, which ``axk1`` follows).
``rms(x; w) = w * x / sqrt(mean(x^2) + eps)``.

    h_0     = E[tokens]
    y       = x + attn_l(rms(x; w_1))           x = h_l
    h_{l+1} = y + ffn_l(rms(y; w_2))
    logits  = rms(h_L; w_final) @ W_head        (head untied)

- ``attn_l``, multi-head latent attention: ``c_q = rms(u W_qa)``; a head's
  query ``[q_nope | q_rope] = c_q W_qb``.  ``[c | k_r] = u W_kva``,
  ``c_kv = rms(c)``, ``k_rope = rope(k_r)``: ONE rotated key all heads
  share.  ``W_kvb`` holds ``[W_uk | W_uv]`` a head: ``k_h = [c_kv W_uk,h |
  k_rope]``, ``v_h = c_kv W_uv,h``; causal softmax of ``s q_h . k_h`` with
  ``s = (nope + rope)^-1/2 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor)
  + 1``; ``out = concat_h(a v_h) W_o``.  Here keys and values are always
  EXPANDED: what the served decode step absorbs into the query is the same
  sum in another order.
- ``rope`` with YaRN (``rope_scaling.type == "yarn"``) over the rope dims
  d: ``f_i = theta^(-2i/d)``; ``corr(r) = d ln(L0 / (2 pi r)) / (2 ln
  theta)`` with ``L0 = original_max_position_embeddings``; ``low =
  max(floor(corr(beta_fast)), 0)``, ``high = min(ceil(corr(beta_slow)),
  d - 1)``; ``ramp_i = clip((i - low) / (high - low), 0, 1)``; ``inv_freq_i
  = f_i / factor * ramp_i + f_i * (1 - ramp_i)``; cos and sin times
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.  The pairs
  ``(2i, 2i + 1)`` rotate together (ASSUMED: the config does not give
  ``rope_interleave``; the family's checkpoints are laid out so).
- ``ffn_l``, ``l < first_k_dense_replace``: ``W_2(silu(W_1 u) * W_3 u)``.
- ``ffn_l`` otherwise: ``sc = sigmoid(u W_r)`` in float32 over all
  ``n_routed_experts``; the ``n_group`` groups are consecutive runs, a
  group's score is its largest ``sc`` (ASSUMED for ``topk_method`` "none":
  the family's rule without a correction bias), the ``topk_group`` best
  groups are kept, the ``num_experts_per_tok`` largest ``sc`` among their
  experts chosen; ``w_e = sc_e / (sum_chosen sc + 1e-20)`` when
  ``norm_topk_prob``, times ``routed_scaling_factor``; output ``sum_chosen
  w_e E_e(u) + E_shared(u)``, ``E(u) = W_2(silu(W_1 u) * W_3 u)``.

``experts_held`` = ``(first, count)`` computes a chip's share: routing is
over all experts, the sum runs over the chosen experts in ``[first, first +
count)`` only (the expert stacks handed in then hold those ``count``
experts), the shared expert is added once if ``shared`` says so, and what
the absent experts would add is left out.  ``None`` is the uncut layer.

Departures from the published model, each of storage and not of arithmetic:
the tree's names are this repository's (``layers`` one dict a layer, as
``models.model.hybrid_layers`` cuts them out of the served stacks; ``wq_b``
[q_rank, H * (nope + rope)], ``wkv_b`` [kv_rank, H * (nope + v)], ``wo``
[H * v, D], the experts' ``W1`` and ``W3`` side by side in
``experts/w_gate_up`` [E, D, 2F]).  ``layers`` may be any iterable, so a
caller can hand the layers over one at a time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def mscale(factor, m):
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def rope_table(positions, dim, cfg):
    """cos, sin [T, dim // 2] of the rotation of pair i at each position."""
    theta = cfg["rope_theta"]
    f = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    scaling, m = cfg.get("rope_scaling"), 1.0
    if scaling:
        factor = scaling["factor"]
        span = scaling["original_max_position_embeddings"]

        def corr(rotations):
            return (dim * math.log(span / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(corr(scaling["beta_fast"])), 0)
        high = min(math.ceil(corr(scaling["beta_slow"])), dim - 1)
        ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                        / max(high - low, 0.001), 0.0, 1.0)
        f = f / factor * ramp + f * (1.0 - ramp)
        m = (mscale(factor, scaling["mscale"])
             / mscale(factor, scaling["mscale_all_dim"]))
    ang = jnp.asarray(positions, F32)[:, None] * f[None, :]
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def rope(x, cfg):
    """Rotate the pairs (2i, 2i + 1) of the last axis.  x: [T, ..., d]."""
    t, d = x.shape[0], x.shape[-1]
    cos, sin = rope_table(jnp.arange(t), d, cfg)
    shape = (t,) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attention(u, p, cfg):
    t = u.shape[0]
    eps = cfg["norm_eps"]
    h, dn, dr, dv = (cfg[k] for k in ("num_heads", "qk_nope_head_dim",
                                      "qk_rope_head_dim", "v_head_dim"))
    r = cfg["kv_lora_rank"]
    cq = rms(u @ jnp.asarray(p["wq_a"], F32), jnp.asarray(p["q_norm"], F32),
             eps)
    q = (cq @ jnp.asarray(p["wq_b"], F32)).reshape(t, h, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], cfg)], axis=-1)
    ckr = u @ jnp.asarray(p["wkv_a"], F32)
    c_kv = rms(ckr[:, :r], jnp.asarray(p["kv_norm"], F32), eps)
    k_rope = rope(ckr[:, r:], cfg)  # [T, dr]: one head
    kv = (c_kv @ jnp.asarray(p["wkv_b"], F32)).reshape(t, h, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope[:, None, :], (t, h, dr))],
        axis=-1)
    v = kv[..., dn:]
    scaling = cfg.get("rope_scaling")
    m = mscale(scaling["factor"], scaling["mscale_all_dim"]) if scaling else 1.0
    s = jnp.einsum("qhk,shk->hqs", q, k) * ((dn + dr) ** -0.5 * m * m)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqs,shv->qhv", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(t, h * dv) @ jnp.asarray(p["wo"], F32)


def swiglu(u, w1, w3, w2):
    return (jax.nn.silu(u @ w1) * (u @ w3)) @ w2


def route(u, p, cfg):
    """-> weights [T, E] float32 over ALL routed experts, zero outside each
    token's chosen set."""
    s = jax.nn.sigmoid(u @ jnp.asarray(p["router"], F32))
    t, e = s.shape
    g = cfg["n_group"]
    groups = s.reshape(t, g, e // g)
    _, best = jax.lax.top_k(jnp.max(groups, axis=-1), cfg["topk_group"])
    kept = jnp.zeros((t, g), bool).at[jnp.arange(t)[:, None], best].set(True)
    pick = jnp.where(kept[:, :, None], groups, -jnp.inf).reshape(t, e)
    _, idx = jax.lax.top_k(pick, cfg["num_experts_per_token"])
    chosen = jnp.zeros_like(s).at[jnp.arange(t)[:, None], idx].set(1.0)
    w = s * chosen
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"]


def experts(u, p, cfg, experts_held=None, shared=True):
    """The expert layer, or a chip's share of it (module docstring)."""
    w = route(u, p, cfg)
    w13s, w2s = p["experts"]["w_gate_up"], p["experts"]["w_down"]
    first, count = experts_held or (0, w.shape[1])
    f = w2s.shape[1]
    out = jnp.zeros_like(u)
    for e in range(count):
        w13 = jnp.asarray(w13s[e], F32)
        y = swiglu(u, w13[:, :f], w13[:, f:], jnp.asarray(w2s[e], F32))
        out = out + w[:, first + e: first + e + 1] * y
    if shared and "shared" in p:
        out = out + swiglu(u, *(jnp.asarray(p["shared"][k], F32)
                                for k in ("w_gate", "w_up", "w_down")))
    return out


def forward(params, cfg, tokens, experts_held=None):
    """``params``: the tree above; ``cfg``: a dict with ``norm_eps``,
    ``rope_theta``, ``rope_scaling`` (the published group, or None),
    ``num_heads``, ``kv_lora_rank``, the three head sizes,
    ``num_dense_layers``, ``num_experts_per_token``, ``n_group``,
    ``topk_group``, ``norm_topk_prob``, ``routed_scaling_factor``;
    ``tokens``: [T] ids; ``experts_held``: ``(first, count)`` of the routed
    experts the stacks hold, None for all.  -> logits [T, V]."""
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(params["embed"]["wte"], F32)[jnp.asarray(tokens)]
        eps = cfg["norm_eps"]
        for l, p in enumerate(params["layers"]):
            u = rms(h, jnp.asarray(p["ln1"]["scale"], F32), eps)
            h = h + attention(u, p["mla"], cfg)
            u = rms(h, jnp.asarray(p["ln2"]["scale"], F32), eps)
            m = p["mlp"]
            if l < cfg["num_dense_layers"]:
                h = h + swiglu(u, *(jnp.asarray(m[k], F32)
                                    for k in ("w_gate", "w_up", "w_down")))
            else:
                h = h + experts(u, m, cfg, experts_held)
        h = rms(h, jnp.asarray(params["final_norm"]["scale"], F32), eps)
        return h @ jnp.asarray(params["lm_head"]["w"], F32)
