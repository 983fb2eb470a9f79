"""Plain reference of the K-EXAONE forward pass (``model_type``
``exaone_moe``).

What the served path is held to: ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernel, no cache, no ring,
no batching, one Python loop over the layers and one over the experts;
every layer masks a full score matrix.  It imports nothing of the package,
so that a change to the system cannot move it;
``benchmark/reference/exaone_moe.py`` is a byte-for-byte copy
(tests/models/test_kexaone.py).

Equations, from the published ``config.json`` and the family's convention
(EXAONE 4.0's hybrid attention).  ``rms(x; w) = w * x / sqrt(mean(x^2) +
eps)``.

    h_0     = E[tokens]
    y       = x + attn_l(rms(x; w_1))           x = h_l
    h_{l+1} = y + ffn_l(rms(y; w_2))
    logits  = rms(h_L; w_final) @ W_head        (head untied)

- ``attn_l``, every layer: ``q = u W_q`` (``num_heads`` heads of
  ``head_dim``), ``k = u W_k``, ``v = u W_v`` (``num_kv_heads`` heads), no
  biases; q and k RMS-normalised per head over the head's dims with learned
  scales (ASSUMED: QK-norm is the family's, the config has no key for it);
  query head g reads key/value head ``g // (num_heads // num_kv_heads)``;
  softmax in float32 of ``head_dim^-1/2 q . k``; ``out = concat_g(o_g)
  W_o``.
- a ``sliding_attention`` layer (``"swa"``): q and k rotate over all of the
  head's dims by RoPE at ``rope_theta``, no scaling, the halves ``(i, i +
  d/2)`` together; the query at position p attends keys j with ``p -
  sliding_window < j <= p``.
- a ``full_attention`` layer (``"attn"``): NO rotation (ASSUMED, the
  family's: "global attention: no rotary positional embedding";
  ``full_rope`` True rotates, for a control); the query at p attends every
  ``j <= p``.
- ``ffn_l``, ``l < num_dense_layers``: ``W_2(silu(W_1 u) * W_3 u)``.
- ``ffn_l`` otherwise: ``sc = sigmoid(u W_r)`` in float32 over all routed
  experts (``n_group`` 1: no groups; no correction bias); the
  ``num_experts_per_token`` largest chosen; ``w_e = sc_e / (sum_chosen sc +
  1e-20)`` when ``norm_topk_prob``, times ``routed_scaling_factor``; output
  ``sum_chosen w_e E_e(u) + E_shared(u)``, ``E(u) = W_2(silu(W_1 u) * W_3
  u)``.
- The norm sits at each sub-layer's INPUT (ASSUMED: pre-norm, as the
  repository's other configurations have it).
- Not modelled: the multi-token-prediction layer behind the last layer,
  which is no part of the next-token function.

``experts_held`` = ``(first, count)`` computes a chip's share: routing is
over all experts, the sum runs over the chosen experts in ``[first, first +
count)`` only (the expert stacks handed in then hold those ``count``
experts), the shared expert is added once if ``shared`` says so, and what
the absent experts would add is left out.  ``None`` is the uncut layer.

``query_block`` cuts a layer's queries into runs of that many rows, each
against all the keys under the same mask: a softmax row is a query's own,
so the numbers are the same, and 64 heads x 6,000 x 6,000 scores need not
exist at once.

Departures from the published model, each of storage and not of arithmetic:
the tree's names are this repository's (``layers`` one dict a layer, as
``models.model.hybrid_layers`` cuts them out of the served stacks, the
attention under ``"swa"`` or ``"attn"`` by its kind; ``wq`` [D, H * hd],
``wk`` / ``wv`` [D, KVH * hd], ``wo`` [H, hd, D], the experts' ``W1`` and
``W3`` side by side in ``experts/w_gate_up`` [E, D, 2F]).  ``layers`` may
be any iterable, so a caller can hand the layers over one at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, theta):
    """Rotate the halves (i, i + d/2) of the last axis.  x: [T, H, d]."""
    t, d = x.shape[0], x.shape[-1]
    f = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * f[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(u, p, cfg, kind, query_block=None):
    t = u.shape[0]
    h, kvh, d = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = (u @ jnp.asarray(p["wq"], F32)).reshape(t, h, d)
    k = (u @ jnp.asarray(p["wk"], F32)).reshape(t, kvh, d)
    v = (u @ jnp.asarray(p["wv"], F32)).reshape(t, kvh, d)
    if cfg.get("qk_norm", True):
        q = rms(q, jnp.asarray(p["q_norm"], F32), cfg["norm_eps"])
        k = rms(k, jnp.asarray(p["k_norm"], F32), cfg["norm_eps"])
    if kind == "swa" or cfg.get("full_rope", False):
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k, v = (jnp.repeat(a, h // kvh, axis=1) for a in (k, v))
    pos = jnp.arange(t)
    outs = []
    for start in range(0, t, query_block or t):
        qp = pos[start: start + (query_block or t)]
        s = jnp.einsum("qhd,shd->hqs", q[qp], k) * d ** -0.5
        keep = pos[None, :] <= qp[:, None]
        if kind == "swa":
            keep &= pos[None, :] > qp[:, None] - cfg["sliding_window"]
        s = jnp.where(keep[None], s, -jnp.inf)
        outs.append(jnp.einsum("hqs,shd->qhd", jax.nn.softmax(s, axis=-1), v))
    o = jnp.concatenate(outs, axis=0)
    return o.reshape(t, h * d) @ jnp.asarray(p["wo"], F32).reshape(h * d, -1)


def swiglu(u, w1, w3, w2):
    return (jax.nn.silu(u @ w1) * (u @ w3)) @ w2


def route(u, p, cfg):
    """-> weights [T, E] float32 over ALL routed experts, zero outside each
    token's chosen set."""
    s = jax.nn.sigmoid(u @ jnp.asarray(p["router"], F32))
    t = s.shape[0]
    _, idx = jax.lax.top_k(s, cfg["num_experts_per_token"])
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


def forward(params, cfg, tokens, experts_held=None, query_block=None):
    """``params``: the tree above; ``cfg``: a dict with ``norm_eps``,
    ``rope_theta``, ``num_heads``, ``num_kv_heads``, ``head_dim``,
    ``sliding_window``, ``num_dense_layers``, ``num_experts_per_token``,
    ``norm_topk_prob``, ``routed_scaling_factor`` (and, for controls,
    ``qk_norm`` and ``full_rope``); ``tokens``: [T] ids; ``experts_held``:
    ``(first, count)`` of the routed experts the stacks hold, None for
    all.  -> logits [T, V]."""
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(params["embed"]["wte"], F32)[jnp.asarray(tokens)]
        eps = cfg["norm_eps"]
        for l, p in enumerate(params["layers"]):
            kind = "swa" if "swa" in p else "attn"
            u = rms(h, jnp.asarray(p["ln1"]["scale"], F32), eps)
            h = h + attention(u, p[kind], cfg, kind, query_block)
            u = rms(h, jnp.asarray(p["ln2"]["scale"], F32), eps)
            m = p["mlp"]
            if l < cfg["num_dense_layers"]:
                h = h + swiglu(u, *(jnp.asarray(m[k], F32)
                                    for k in ("w_gate", "w_up", "w_down")))
            else:
                h = h + experts(u, m, cfg, experts_held)
        h = rms(h, jnp.asarray(params["final_norm"]["scale"], F32), eps)
        return h @ jnp.asarray(params["lm_head"]["w"], F32)
