"""Plain reference of the SmallThinker forward pass (``model_name``
``smallthinker_21b_instruct``: SmallThinker-21BA3B-Instruct).

What the served path is held to: ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernel, no cache, no ring,
no pages, no batching, one Python loop over the layers and one over the
experts; every layer masks a full score matrix.  It imports nothing of the
package, so that a change to the system cannot move it;
``benchmark/reference/smallthinker.py`` is a byte-for-byte copy
(tests/models/test_smallthinker.py).

Equations, from the published ``config.json`` and, where it has no key,
from the catalog's ``described_as`` (each such place is marked ASSUMED and
listed under ``assumed`` in benchmark/configs/smallthinker-21ba3b-int8.json).
``rms(x; w) = w * x / sqrt(mean(x^2) + eps)``, no biases anywhere.  Layer l,
with x the block's input [T, D]:

    r       = x W_r                              float32, W_r [D, E]
    y       = x + attn_l(rms(x; w_1))
    h_{l+1} = y + experts(rms(y; w_2), r)
    logits  = rms(h_L; w_final) @ W_head         (head untied)

- The router reads THE BLOCK'S INPUT ITSELF, before ``w_1`` and before
  attention (ASSUMED: ``described_as`` "router placed before attention";
  as the public llama.cpp graph of this model builds it.  ``router_input``
  "ffn_norm" reads ``rms(y; w_2)`` and "attn_norm" reads ``rms(x; w_1)``,
  for controls).
- ``attn_l``: ``q = u W_q`` (``num_heads`` heads of ``head_dim``), ``k = u
  W_k``, ``v = u W_v`` (``num_kv_heads`` heads); no QK-norm (ASSUMED: no
  key for one); query head g reads key/value head ``g // (num_heads //
  num_kv_heads)``; softmax in float32 of ``head_dim^-1/2 q . k``; ``out =
  concat_g(o_g) W_o``.
- a layer whose ``rope_layout`` and ``sliding_window_layout`` entries are 1
  (``"swa"``: both lists are 0 at l = 0, 4, 8, ... and 1 elsewhere): q and k
  rotate over all of the head's dims by RoPE at ``rope_theta``, no
  scaling, the halves ``(i, i + d/2)`` together (ASSUMED layout); the query
  at position p attends keys j with ``p - sliding_window < j <= p``
  (ASSUMED edge).  ``swa_rope`` False leaves them unrotated, for a control.
- a layer whose entries are 0 (``"attn"``): NO rotation (``full_rope`` True
  rotates, for a control); the query at p attends every ``j <= p``.
- ``experts(u, r)``: ``(v, idx)`` the ``num_experts_per_token`` largest of
  r; ``w = softmax(v)`` over the chosen (``moe_primary_router_apply_softmax``
  and ``norm_topk_prob`` both true: a softmax over all E, the chosen
  renormalised, is the same number).  Expert e: ``f_e(u) = (relu(u W_g,e) *
  (u W_u,e)) W_d,e`` (ASSUMED: ReLU as the gate, ``described_as`` "sparse
  ReGLU"; the config has no ``hidden_act``).  Output ``sum_j w_j
  f_idx_j(u)``.  No shared expert, no dense layer.  Controls:
  ``gate_act`` "silu"; ``norm_topk_prob`` False (the softmax over all E,
  not renormalised); ``score_fn`` "sigmoid" (sigmoid scores of the chosen
  over their sum).
- Not modelled: nothing the config declares (the "secondary experts" of
  ``described_as`` have no key in it).

``query_block`` cuts a layer's queries into runs of that many rows, each
against all the keys under the same mask: a softmax row is a query's own,
so the numbers are the same, and 28 heads x 6,000 x 6,000 scores need not
exist at once.

Departures from the published model, each of storage and not of arithmetic:
the tree's names are this repository's (``layers`` one dict a layer, as
``models.model.hybrid_layers`` cuts them out of the served stacks, the
attention under ``"swa"`` or ``"attn"`` by its kind; ``wq`` [D, H * hd],
``wk`` / ``wv`` [D, KVH * hd], ``wo`` [H, hd, D], the experts' ``W_g`` and
``W_u`` side by side in ``experts/w_gate_up`` [E, D, 2F]).  ``layers`` may
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
    rotate = (cfg.get("swa_rope", True) if kind == "swa"
              else cfg.get("full_rope", False))
    if rotate:
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


def route(r, cfg):
    """Router logits r [T, E] -> weights [T, E] float32, zero outside each
    token's chosen set."""
    t = r.shape[0]
    _, idx = jax.lax.top_k(r, cfg["num_experts_per_token"])
    chosen = jnp.zeros_like(r).at[jnp.arange(t)[:, None], idx].set(1.0)
    if cfg.get("score_fn", "softmax") == "sigmoid":
        w = jax.nn.sigmoid(r) * chosen
        return w / jnp.sum(w, axis=-1, keepdims=True)
    w = jax.nn.softmax(r, axis=-1) * chosen
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w


def experts(u, r, p, cfg):
    """sum_chosen w_e (act(u W_g,e) * (u W_u,e)) W_d,e."""
    w = route(r, cfg)
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[
        cfg.get("gate_act", "relu")]
    wgus, wds = p["experts"]["w_gate_up"], p["experts"]["w_down"]
    f = wds.shape[1]
    out = jnp.zeros_like(u)
    for e in range(w.shape[1]):
        wgu = jnp.asarray(wgus[e], F32)
        y = (act(u @ wgu[:, :f]) * (u @ wgu[:, f:])) @ jnp.asarray(wds[e], F32)
        out = out + w[:, e: e + 1] * y
    return out


def forward(params, cfg, tokens, query_block=None):
    """``params``: the tree above; ``cfg``: a dict with ``norm_eps``,
    ``rope_theta``, ``num_heads``, ``num_kv_heads``, ``head_dim``,
    ``sliding_window``, ``num_experts_per_token`` (and, for controls,
    ``router_input``, ``gate_act``, ``full_rope``, ``swa_rope``,
    ``norm_topk_prob``, ``score_fn``); ``tokens``: [T] ids.
    -> logits [T, V]."""
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(params["embed"]["wte"], F32)[jnp.asarray(tokens)]
        eps = cfg["norm_eps"]
        reads = cfg.get("router_input", "block_input")
        for p in params["layers"]:
            kind = "swa" if "swa" in p else "attn"
            x = h
            u1 = rms(x, jnp.asarray(p["ln1"]["scale"], F32), eps)
            y = x + attention(u1, p[kind], cfg, kind, query_block)
            u2 = rms(y, jnp.asarray(p["ln2"]["scale"], F32), eps)
            r = {"block_input": x, "attn_norm": u1, "ffn_norm": u2}[
                reads] @ jnp.asarray(p["mlp"]["router"], F32)
            h = y + experts(u2, r, p["mlp"], cfg)
        h = rms(h, jnp.asarray(params["final_norm"]["scale"], F32), eps)
        return h @ jnp.asarray(params["lm_head"]["w"], F32)
