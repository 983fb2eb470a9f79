"""Plain reference of the LFM2-MoE forward pass (``model_type`` ``lfm2_moe``).

What the served path is held to: ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernel, no cache, no
batching, one Python loop over the layers.  It imports nothing of the
package, so that a change to the system cannot move it;
``benchmark/reference/lfm2_moe.py`` is a byte-for-byte copy
(tests/models/test_lfm2_reference.py).

Equations, from the published ``config.json`` and the public ``lfm2_moe``
modelling code.  ``rms(x; w) = w * x / sqrt(mean(x^2) + eps)``.

    h_0     = E[tokens]
    y       = x + op_l(rms(x; w_op))            x = h_l
    h_{l+1} = y + ffn_l(rms(y; w_ffn))
    logits  = rms(h_L; w_final) @ E^T           (head tied to E)

- ``op_l``, ``layer_types[l] == "conv"``: ``[B, C, z] = split3(u @ W_in)``,
  ``g = B * z``, ``c_t = sum_j k[:, j] * g_{t - (K-1) + j}`` with ``g_t = 0``
  for ``t < 0`` (depthwise, causal, K = ``conv_L_cache`` taps a channel, no
  bias), ``op = (C * c) @ W_out``.
- ``op_l``, ``"full_attention"``: GQA without biases; q and k RMS-normalised
  per head (learned [head_dim] scales) BEFORE rotate-half RoPE over the whole
  head; causal softmax of ``q k^T / sqrt(head_dim)``.
- ``ffn_l``, ``l < num_dense_layers``: ``W_2(silu(W_1 u) * W_3 u)``.
- ``ffn_l`` otherwise: ``s = sigmoid(u @ W_r)`` in float32, the chosen set
  ``top_k(s + b)`` (``b`` = ``expert_bias``: it picks, it does not weigh),
  ``w_e = s_e / (sum_chosen s + 1e-6)`` when ``norm_topk_prob``, times
  ``routed_scaling_factor``; output ``sum_chosen w_e * W2_e(silu(W1_e u) *
  W3_e u)``.  No shared expert, no capacity: every token gets its k experts.

Departures from the published model, each of storage and not of arithmetic:
the tree's names are this repository's (``layers`` one dict a layer, as
``models.model.hybrid_layers`` cuts them out of the served stacks;
``in_proj`` [D, 3D], ``taps`` [D, K], ``wq`` [D, H * hd] ..., the experts'
``W1`` and ``W3`` side by side in ``experts/w_gate_up`` [E, D, 2F]); the
final norm (``embedding_norm`` there) is ``final_norm`` here.  ``layers`` may
be any iterable, so a caller can hand the layers over one at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, theta):
    """Rotate-half RoPE over the whole head.  x: [T, H, hd]."""
    t, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]  # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def short_conv(u, p):
    w_in, taps, w_out = (jnp.asarray(p[k], F32)
                         for k in ("in_proj", "taps", "out_proj"))
    b, c, z = jnp.split(u @ w_in, 3, axis=-1)
    g = b * z  # [T, D]
    k = taps.shape[1]
    gp = jnp.concatenate([jnp.zeros((k - 1, g.shape[1]), F32), g])
    conv = sum(taps[:, j] * gp[j: j + g.shape[0]] for j in range(k))
    return (c * conv) @ w_out


def attention(u, p, cfg):
    t = u.shape[0]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    hd = p["q_norm"].shape[0]  # wq/wk/wv are [D, H * hd], the heads flat
    q, k, v = ((u @ jnp.asarray(p[w], F32)).reshape(t, -1, hd)
               for w in ("wq", "wk", "wv"))
    q = rope(rms(q, jnp.asarray(p["q_norm"], F32), eps), theta)
    k = rope(rms(k, jnp.asarray(p["k_norm"], F32), eps), theta)
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhk,shk->hqs", q, k) / jnp.sqrt(F32(q.shape[-1]))
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("qhk,hkd->qd", o, jnp.asarray(p["wo"], F32))


def swiglu(u, w1, w3, w2):
    return (jax.nn.silu(u @ w1) * (u @ w3)) @ w2


def route(u, p, cfg):
    """-> weights [T, E] float32, zero outside each token's chosen set."""
    s = jax.nn.sigmoid(u @ jnp.asarray(p["router"], F32))
    pick = s + jnp.asarray(p["expert_bias"], F32) if "expert_bias" in p else s
    _, idx = jax.lax.top_k(pick, cfg["num_experts_per_token"])
    chosen = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(1.0)
    w = s * chosen
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return w * cfg["routed_scaling_factor"]


def experts(u, p, cfg):
    w = route(u, p, cfg)
    w13s, w2s = p["experts"]["w_gate_up"], p["experts"]["w_down"]
    f = w2s.shape[1]
    out = jnp.zeros_like(u)
    for e in range(w2s.shape[0]):
        w13 = jnp.asarray(w13s[e], F32)
        y = swiglu(u, w13[:, :f], w13[:, f:], jnp.asarray(w2s[e], F32))
        out = out + w[:, e: e + 1] * y
    return out


def forward(params, cfg, tokens):
    """``params``: the tree above; ``cfg``: a dict with ``norm_eps``,
    ``rope_theta``, ``layer_types``, ``num_dense_layers``,
    ``num_experts_per_token``, ``norm_topk_prob``,
    ``routed_scaling_factor``; ``tokens``: [T] ids.  -> logits [T, V]."""
    with jax.default_matmul_precision("highest"):
        emb = jnp.asarray(params["embed"]["wte"], F32)
        h = emb[jnp.asarray(tokens)]
        eps = cfg["norm_eps"]
        for l, p in enumerate(params["layers"]):
            u = rms(h, jnp.asarray(p["ln1"]["scale"], F32), eps)
            if cfg["layer_types"][l] == "conv":
                h = h + short_conv(u, p["conv"])
            else:
                h = h + attention(u, p["attn"], cfg)
            u = rms(h, jnp.asarray(p["ln2"]["scale"], F32), eps)
            m = p["mlp"]
            if l < cfg["num_dense_layers"]:
                h = h + swiglu(u, *(jnp.asarray(m[k], F32)
                                    for k in ("w_gate", "w_up", "w_down")))
            else:
                h = h + experts(u, m, cfg)
        h = rms(h, jnp.asarray(params["final_norm"]["scale"], F32), eps)
        return h @ emb.T
