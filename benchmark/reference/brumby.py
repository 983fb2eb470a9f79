"""Plain reference of the Brumby forward pass (``model_type`` ``brumby``:
manifestai/Brumby-14B-Base), every layer a power-retention layer of degree 2
in front of a SwiGLU.

What the served path is held to: ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, the ATTENTION form of the
operator: no state, no chunk, no cache, no kernel, no batching, one Python
loop over the layers; every layer weighs a full [T, T] matrix of squared
scores.  It imports nothing of the package, so that a change to the system
cannot move it; ``benchmark/reference/brumby.py`` is a byte-for-byte copy
(tests/models/test_brumby.py).

Equations, from the published ``config.json`` and, where it has no key,
from the public description (Manifest AI, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239, and the ``retention`` package's
``power_retention(q, k, v, log_g, deg=2)``; each such place is marked
ASSUMED and listed under ``assumed`` in
benchmark/configs/brumby-14b-int8.json).  ``rms(x; w) = w * x /
sqrt(mean(x^2) + eps)``, no biases anywhere.  Layer l, x its input [T, D]:

    y       = x + ret_l(rms(x; w_1))
    h_{l+1} = y + (silu(u W_g) * (u W_u)) W_d,  u = rms(y; w_2)
    logits  = rms(h_L; w_final) @ W_head         (head untied)

``ret_l(u)``: ``q = u W_q`` (``num_heads`` heads of ``head_dim``), ``k = u
W_k``, ``v = u W_v`` (``num_kv_heads`` heads); q and k are RMS-normalised
per head with learned [head_dim] scales (ASSUMED: Qwen3's QK-norm, which the
model was initialised from; the config has no key for it) and then rotated
over all of the head's dims at ``rope_theta``, the halves ``(i, i + d/2)``
together, no scaling; query head g reads key/value head ``g // (num_heads
// num_kv_heads)``.  A scalar gate a token and a key/value head, ``log g_t
= logsigmoid(u_t . w_gate)`` (ASSUMED: the projection hidden -> KVH and the
log-sigmoid), and with ``G_ij = exp(sum_{j < s <= i} log g_s)``:

    a_ij = (q_i . k_j)^p G_ij   for j <= i,  p = 2 (ASSUMED degree)
    o_i  = sum_j a_ij v_j / sum_j a_ij        (ASSUMED: the division by the
                                              sum of weights; no epsilon)

with no scale on ``q . k`` (a scale cancels in the division), and ``out =
concat_g(o_g) W_o``.  Controls: ``degree`` (another power), ``gated`` False
(every ``log g`` 0), ``qk_norm`` False, ``rope`` False.

``query_block`` cuts a layer's queries into runs of that many rows, each
against all the keys: a row of weights is a query's own, so the numbers are
the same, and 40 heads x 6,000 x 6,000 weights need not exist at once.

Departures from the published model, each of storage and not of arithmetic:
the tree's names are this repository's (``layers`` one dict a layer, as
``models.model.hybrid_layers`` cuts them out of the served stacks, the
operator under ``"ret"``; ``wq`` [D, H * hd], ``wk`` / ``wv`` [D, KVH * hd],
``wo`` [H, hd, D], ``wg`` [D, KVH]).  ``layers`` may be any iterable, so a
caller can hand the layers over one at a time.  Nothing is kept between
tokens: the served state (float32, ASSUMED precision) is the recurrent
form of the same sums.
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


def retention(u, p, cfg, query_block=None):
    t = u.shape[0]
    h, kvh, d = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = (u @ jnp.asarray(p["wq"], F32)).reshape(t, h, d)
    k = (u @ jnp.asarray(p["wk"], F32)).reshape(t, kvh, d)
    v = (u @ jnp.asarray(p["wv"], F32)).reshape(t, kvh, d)
    if cfg.get("qk_norm", True):
        q = rms(q, jnp.asarray(p["q_norm"], F32), cfg["norm_eps"])
        k = rms(k, jnp.asarray(p["k_norm"], F32), cfg["norm_eps"])
    if cfg.get("rope", True):
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    log_g = jax.nn.log_sigmoid(u @ jnp.asarray(p["wg"], F32))  # [T, KVH]
    if not cfg.get("gated", True):
        log_g = jnp.zeros_like(log_g)
    cum = jnp.repeat(jnp.cumsum(log_g, axis=0), h // kvh, axis=1)  # [T, H]
    k, v = (jnp.repeat(a, h // kvh, axis=1) for a in (k, v))
    pos = jnp.arange(t)
    outs = []
    for start in range(0, t, query_block or t):
        qp = pos[start: start + (query_block or t)]
        s = jnp.einsum("qhd,shd->hqs", q[qp], k) ** cfg.get("degree", 2)
        keep = pos[None, :] <= qp[:, None]
        decay = jnp.exp(jnp.where(
            keep[None], cum[qp].T[:, :, None] - cum.T[:, None, :], -jnp.inf))
        a = s * decay
        outs.append(jnp.einsum("hqs,shd->qhd", a, v)
                    / jnp.sum(a, axis=-1).T[:, :, None])
    o = jnp.concatenate(outs, axis=0)
    return o.reshape(t, h * d) @ jnp.asarray(p["wo"], F32).reshape(h * d, -1)


def swiglu(u, p):
    return ((jax.nn.silu(u @ jnp.asarray(p["w_gate"], F32))
             * (u @ jnp.asarray(p["w_up"], F32)))
            @ jnp.asarray(p["w_down"], F32))


def forward(params, cfg, tokens, query_block=None):
    """``params``: the tree above; ``cfg``: a dict with ``norm_eps``,
    ``rope_theta``, ``num_heads``, ``num_kv_heads``, ``head_dim`` (and,
    for controls, ``degree``, ``gated``, ``qk_norm``, ``rope``);
    ``tokens``: [T] ids.  -> logits [T, V]."""
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(params["embed"]["wte"], F32)[jnp.asarray(tokens)]
        eps = cfg["norm_eps"]
        for p in params["layers"]:
            u1 = rms(h, jnp.asarray(p["ln1"]["scale"], F32), eps)
            y = h + retention(u1, p["ret"], cfg, query_block)
            u2 = rms(y, jnp.asarray(p["ln2"]["scale"], F32), eps)
            h = y + swiglu(u2, p["mlp"])
        h = rms(h, jnp.asarray(params["final_norm"]["scale"], F32), eps)
        return h @ jnp.asarray(params["lm_head"]["w"], F32)
