"""Plain reference of the Qwen3-Next forward pass (``model_type``
``qwen3_next``: Qwen/Qwen3-Next-80B-A3B-Instruct): three Gated DeltaNet layers
and one gated softmax-attention layer a period, an expert layer with a gated
shared expert behind every one.

What the served path is held to: ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, the delta rule as its RECURRENCE
token by token (``lax.scan``: no chunk, no triangle, no cache, no kernel),
attention dense in blocks of queries, the experts a Python loop over the held
ones, no batching.  It imports nothing of the package, so that a change to the
system cannot move it; ``benchmark/reference/qwen3_next.py`` is a
byte-for-byte copy (tests/models/test_qwen3_next.py).

Equations, from the published ``config.json`` and, where it has no key, from
the published modelling code and the Gated DeltaNet paper (Yang, Kautz and
Hatamizadeh, "Gated Delta Networks", arXiv:2412.06464; each such place is
marked ASSUMED and listed under ``assumed`` in
benchmark/configs/qwen3-next-int8-ep4.json).  ``rms(x; w) = w * x /
sqrt(mean(x^2) + eps)``: the published weight is stored zero-centred and
applied as ``1 + w``; here ``w`` IS the factor (ASSUMED storage: ones where
the checkpoint holds zeros).  No biases.  Every layer is ``h <- h +
mixer_l(rms(h)); h <- h + moe(rms(h))``.

Gated DeltaNet (``HK`` key heads and ``HV`` value heads of ``dk`` = ``dv``,
``K`` taps): ``[q | k | v | z] = u W_qkvz`` and ``[b | a] = u W_ba`` (ASSUMED
order: each flat by head; the published code interleaves them a key head,
which is the same function of random weights); ``[q | k | v] <- silu(causal
depthwise conv_K([q | k | v]))``, no bias; value head ``h`` reads key head ``h
// (HV / HK)``; ``q <- q / sqrt(|q|^2 + 1e-6) / sqrt(dk)``, ``k <- k /
sqrt(|k|^2 + 1e-6)`` (ASSUMED eps); ``beta = sigmoid(b)``, ``g = -exp(A_log)
softplus(a + dt_bias)``; for a value head with the state ``S`` [dk, dv]:

    S <- exp(g_t) S;  d_t = beta_t (v_t - S^T k_t);  S <- S + k_t d_t^T
    o_t = S^T q_t

then ``o <- rms_dv(o; w) * silu(z)`` a head (ASSUMED order: the norm first,
then the gate) and ``o W_out``.

Gated attention: ``W_q`` gives a head ``[query | gate]``; RMS norm of ``q``
and ``k`` over the head before the rotation; the first ``rotary_pct`` of a
head's dims rotate (pairs ``(i, i + rot / 2)``), the rest carry no position;
causal softmax of ``q . k / sqrt(head_dim)`` over the whole prefix; ``o <- o
* sigmoid(gate)``; ``W_o``.

Expert layer: ``p = softmax(u W_r)`` over all experts, the
``num_experts_per_token`` largest, weights ``p_e / sum_picked p`` (the softmax
over the picked logits); ``sum_e w_e (silu(u G_e) * (u U_e)) D_e``; plus
``sigmoid(u w_s) * shared(u)``, one SwiGLU behind a SCALAR gate a token.
``experts_held`` ``(first, count)``: the stacks hold that run of the routed
experts, a chip's share; a pair on an absent expert adds nothing (its chip
would).  ``shared`` False leaves the shared expert out, so that the shares of
a layer can be added up with it counted ONCE.

Controls (a wrong model each): ``attn_gate`` False (the attention's gate left
off), ``shared_gate`` False (the shared expert ungated), ``beta_one`` True
(``beta`` fixed at 1: a full overwrite a token), ``gate_first`` True (the gate
before the norm).

Departures from the published model, each of storage and not of arithmetic:
the tree's names are this repository's (``layers`` one dict a block, as
``models.model.hybrid_layers`` cuts them out of the served stacks: the
operator under ``"gdn"`` or ``"attn"``; ``w_qkvz`` [D, 2 HK dk + 2 HV dv],
``w_ba`` [D, 2 HV], ``taps`` [channels, K], ``wq`` [D, H * 2 hd], ``wo`` [H,
hd, D], ``experts/w_gate_up`` [E, D, 2 F], ``experts/w_down`` [E, F, D],
``shared_gate`` [D]).  ``layers`` may be any iterable, so a caller can hand
the blocks over one at a time.  The multi-token-prediction layer is not
modelled.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_net(u, p, cfg):
    t = u.shape[0]
    hk, hv, dk, dv = (cfg["gdn_key_heads"], cfg["gdn_value_heads"],
                      cfg["gdn_key_dim"], cfg["gdn_value_dim"])
    kw, k = hk * dk, cfg["gdn_conv_kernel"]
    width = 2 * kw + hv * dv
    qkvz = u @ jnp.asarray(p["w_qkvz"], F32)
    qkv, z = qkvz[:, :width], qkvz[:, width:]
    ba = u @ jnp.asarray(p["w_ba"], F32)
    beta = jax.nn.sigmoid(ba[:, :hv])
    if cfg.get("beta_one"):
        beta = jnp.ones_like(beta)
    g = -jnp.exp(jnp.asarray(p["A_log"], F32)) * jax.nn.softplus(
        ba[:, hv:] + jnp.asarray(p["dt_bias"], F32))  # [T, HV]
    taps = jnp.asarray(p["taps"], F32)  # [channels, K]
    win = jnp.concatenate([jnp.zeros((k - 1, width), F32), qkv], axis=0)
    qkv = jax.nn.silu(sum(taps[:, j] * win[j: j + t] for j in range(k)))
    q = unit(qkv[:, :kw].reshape(t, hk, dk)) * dk ** -0.5
    kk = unit(qkv[:, kw:2 * kw].reshape(t, hk, dk))
    v = qkv[:, 2 * kw:].reshape(t, hv, dv)
    q, kk = (jnp.repeat(a, hv // hk, axis=1) for a in (q, kk))  # [T, HV, dk]

    def step(s, xs):  # s [HV, dk, dv]
        qt, kt, vt, gt, bt = xs
        s = jnp.exp(gt)[:, None, None] * s
        d = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt))
        s = s + kt[:, :, None] * d[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt)

    _, o = jax.lax.scan(step, jnp.zeros((hv, dk, dv), F32),
                        (q, kk, v, g, beta))
    w = jnp.asarray(p["norm_w"], F32)
    gate = jax.nn.silu(z.reshape(t, hv, dv))
    if cfg.get("gate_first"):
        o = rms(o * gate, w, cfg["norm_eps"])
    else:
        o = rms(o, w, cfg["norm_eps"]) * gate
    return o.reshape(t, hv * dv) @ jnp.asarray(p["out_proj"], F32)


def rope(x, pos, theta, rot):
    """The first ``rot`` of the last axis rotated, pairs ``(i, i + rot /
    2)``; x [T, H, D], pos [T]."""
    half = rot // 2
    freqs = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = pos.astype(F32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def attention(u, p, cfg, query_block=None):
    t = u.shape[0]
    h, kvh, d = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    qg = (u @ jnp.asarray(p["wq"], F32)).reshape(t, h, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (u @ jnp.asarray(p["wk"], F32)).reshape(t, kvh, d)
    v = (u @ jnp.asarray(p["wv"], F32)).reshape(t, kvh, d)
    q = rms(q, jnp.asarray(p["q_norm"], F32), cfg["norm_eps"])
    k = rms(k, jnp.asarray(p["k_norm"], F32), cfg["norm_eps"])
    pos = jnp.arange(t)
    rot = int(d * cfg["rotary_pct"])
    q, k = (rope(a, pos, cfg["rope_theta"], rot) for a in (q, k))
    k, v = (jnp.repeat(a, h // kvh, axis=1) for a in (k, v))
    outs = []
    for start in range(0, t, query_block or t):
        qp = pos[start: start + (query_block or t)]
        s = jnp.einsum("qhd,shd->hqs", q[qp], k) * d ** -0.5
        s = jnp.where((pos[None, :] <= qp[:, None])[None], s, -jnp.inf)
        outs.append(jnp.einsum("hqs,shd->qhd", jax.nn.softmax(s, axis=-1), v))
    o = jnp.concatenate(outs, axis=0)
    if cfg.get("attn_gate", True):
        o = o * jax.nn.sigmoid(gate)
    return o.reshape(t, h * d) @ jnp.asarray(p["wo"], F32).reshape(h * d, -1)


def route(u, p, cfg):
    """-> weights [T, E] float32 over ALL routed experts, zero outside each
    token's chosen set."""
    probs = jax.nn.softmax(u @ jnp.asarray(p["router"], F32), axis=-1)
    t = probs.shape[0]
    _, idx = jax.lax.top_k(probs, cfg["num_experts_per_token"])
    chosen = jnp.zeros_like(probs).at[jnp.arange(t)[:, None], idx].set(1.0)
    w = probs * chosen
    return w / jnp.sum(w, axis=-1, keepdims=True)  # norm_topk_prob: true


def swiglu(u, gate_up, down):
    f = down.shape[0]
    hid = u @ jnp.asarray(gate_up, F32)
    return (jax.nn.silu(hid[:, :f]) * hid[:, f:]) @ jnp.asarray(down, F32)


def experts(u, p, cfg, experts_held=None, shared=True):
    """The expert layer, or a chip's share of it (module docstring)."""
    w = route(u, p, cfg)
    ups, downs = p["experts"]["w_gate_up"], p["experts"]["w_down"]
    first, count = experts_held or (0, w.shape[1])
    out = jnp.zeros_like(u)
    for e in range(count):
        out = out + w[:, first + e: first + e + 1] * swiglu(
            u, ups[e], downs[e])
    if shared and "shared" in p:
        s = p["shared"]
        y = swiglu(u, jnp.concatenate(
            [jnp.asarray(s["w_gate"], F32), jnp.asarray(s["w_up"], F32)],
            axis=1), s["w_down"])
        if cfg.get("shared_gate", True):
            y = y * jax.nn.sigmoid(
                u @ jnp.asarray(p["shared_gate"], F32))[:, None]
        out = out + y
    return out


def forward(params, cfg, tokens, experts_held=None, query_block=None):
    """``params``: the tree above; ``cfg``: a dict with ``norm_eps``,
    ``rope_theta``, ``rotary_pct``, ``num_heads``, ``num_kv_heads``,
    ``head_dim``, ``gdn_key_heads``, ``gdn_value_heads``, ``gdn_key_dim``,
    ``gdn_value_dim``, ``gdn_conv_kernel``, ``num_experts_per_token`` (and,
    for controls, ``attn_gate``, ``shared_gate``, ``beta_one``,
    ``gate_first``); ``tokens``: [T] ids; ``experts_held``: ``(first,
    count)`` of the routed experts the stacks hold, None for all.
    -> logits [T, V]."""
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(params["embed"]["wte"], F32)[jnp.asarray(tokens)]
        eps = cfg["norm_eps"]
        for p in params["layers"]:
            u = rms(h, jnp.asarray(p["ln1"]["scale"], F32), eps)
            if "gdn" in p:
                h = h + delta_net(u, p["gdn"], cfg)
            else:
                h = h + attention(u, p["attn"], cfg, query_block)
            u = rms(h, jnp.asarray(p["ln2"]["scale"], F32), eps)
            h = h + experts(u, p["mlp"], cfg, experts_held)
        h = rms(h, jnp.asarray(params["final_norm"]["scale"], F32), eps)
        return h @ jnp.asarray(params["lm_head"]["w"], F32)
