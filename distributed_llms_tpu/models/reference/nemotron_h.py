"""Plain reference of the Nemotron-H forward pass (``model_type``
``nemotron_h``: nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16), a stack of
single sub-layers, each Mamba-2, attention or LatentMoE.

What the served path is held to: ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, Mamba-2 as its RECURRENCE token
by token (``lax.scan``: no chunk, no cache, no kernel), attention dense in
blocks of queries, the experts a Python loop over the held ones, no
batching.  It imports nothing of the package, so that a change to the system
cannot move it; ``benchmark/reference/nemotron_h.py`` is a byte-for-byte
copy (tests/models/test_nemotron.py).

Equations, from the published ``config.json`` and, where it has no key, from
the public descriptions (Dao and Gu, "Transformers are SSMs", arXiv:2405.21060;
NVIDIA's Nemotron-H and Nemotron 3 reports; each such place is marked ASSUMED
and listed under ``assumed`` in
benchmark/configs/nemotron3-super-int8-ep4.json).  ``rms(x; w) = w * x /
sqrt(mean(x^2) + eps)``, no biases but the convolution's.  Every published
layer is ONE sub-layer, ``h <- h + f_l(rms(h; w_l))``, ``f_l`` by the
``hybrid_override_pattern`` (``M``, ``*``, ``E``); here two sub-layers in a
row that are (operator, experts) come as one dict (``ln1`` the operator's
norm, ``ln2`` the experts'), and an operator that no ``E`` follows has
``mlp`` None.

``M``, Mamba-2 (``H`` heads of ``P``, ``G`` groups, state ``N``, ``K``
taps): ``[z | xBC | dt] = u W_in`` (ASSUMED order), ``xBC <- silu(causal
depthwise conv_K(xBC) + b)``, ``xBC = [x (H x P) | B (G x N) | C (G x N)]``,
``dt = softplus(dt + dt_bias)`` (ASSUMED: not clamped above), ``A =
-exp(A_log)``; for head ``h`` of group ``g = h // (H / G)``:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T     ([P, N], float32)
    y_t = S_t C_t + D_h x_t

then ``y <- rms_G(y * silu(z)) * w`` (ASSUMED: the gate first, then an RMS
norm over each group's ``H P / G`` channels) and ``y W_out``.

``*``, attention: ``num_heads`` query heads over ``num_kv_heads`` key/value
heads of ``head_dim``, softmax of ``q . k / sqrt(head_dim)`` over the whole
prefix, NO rotation (ASSUMED: the family puts no position into its attention
layers) and no per-head norm.

``E``, LatentMoE: ``s = sigmoid(u W_r)``; the ``num_experts_per_token``
experts of largest ``s + b`` (the correction bias picks, it does not weigh);
weights ``routed_scaling_factor * s_e / sum_picked s``; ``c = u W_dn``;
``r = sum_e w_e relu(c U_e)^2 V_e`` (NOT gated: two matrices an expert);
output ``r W_up + relu(u U_s)^2 V_s`` (ASSUMED: the router and the shared
expert read the hidden width ``u``, only the routed experts the latent).
``experts_held`` ``(first, count)``: the stacks hold that run of the routed
experts, a chip's share; a pair on an absent expert adds nothing (its chip
would), and the partial sum goes through ``W_up``.  ``shared`` False leaves
the shared expert out, so that the shares of a layer can be added up with it
counted ONCE.

Controls (a wrong model each): ``expert_act`` "silu" (``silu(c U_e)`` for
``relu(.)^2``), ``latent`` False (``W_dn`` and ``W_up`` left out: the experts
read the first channels of ``u`` and write them), ``gate_first`` False (the
norm before the gate), ``conv_bias`` False.

Departures from the published model, each of storage and not of arithmetic:
the tree's names are this repository's (``layers`` one dict a block, as
``models.model.hybrid_layers`` cuts them out of the served stacks: the
operator under ``"ssm"`` or ``"attn"``; ``in_proj`` [D, 2 H P + 2 G N + H],
``taps`` [channels, K], ``wq`` [D, H * hd], ``wo`` [H, hd, D],
``experts/w_up`` [E, latent, F], ``experts/w_down`` [E, F, latent]).
``layers`` may be any iterable, so a caller can hand the blocks over one at a
time.  The multi-token-prediction layer is not modelled.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def mamba2(u, p, cfg):
    t = u.shape[0]
    nh, hd, ng, ns = (cfg["ssm_heads"], cfg["ssm_head_dim"],
                      cfg["ssm_groups"], cfg["ssm_state"])
    inner, k = nh * hd, cfg["conv_kernel"]
    width = inner + 2 * ng * ns
    zxd = u @ jnp.asarray(p["in_proj"], F32)
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:inner + width], \
        zxd[:, inner + width:]
    taps = jnp.asarray(p["taps"], F32)  # [channels, K]
    win = jnp.concatenate([jnp.zeros((k - 1, width), F32), xbc], axis=0)
    conv = sum(taps[:, j] * win[j: j + t] for j in range(k))
    if cfg.get("conv_bias", True):
        conv = conv + jnp.asarray(p["conv_bias"], F32)
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(t, nh, hd)
    bm = xbc[:, inner:inner + ng * ns].reshape(t, ng, ns)
    cm = xbc[:, inner + ng * ns:].reshape(t, ng, ns)
    dt = jax.nn.softplus(dt + jnp.asarray(p["dt_bias"], F32))  # [T, H]
    a = -jnp.exp(jnp.asarray(p["A_log"], F32))  # [H]

    def step(s, xs):  # s [H, P, N]
        xt, bt, ct, dtt = xs
        bh = jnp.repeat(bt, nh // ng, axis=0)  # [H, N]
        ch = jnp.repeat(ct, nh // ng, axis=0)
        s = (jnp.exp(dtt * a)[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bh[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, ch)

    _, y = jax.lax.scan(step, jnp.zeros((nh, hd, ns), F32), (x, bm, cm, dt))
    y = (y + jnp.asarray(p["D"], F32)[:, None] * x).reshape(t, inner)
    w = jnp.asarray(p["norm_w"], F32)
    grouped = lambda v: rms(v.reshape(t, ng, inner // ng), 1.0,
                            cfg["norm_eps"]).reshape(t, inner)
    if cfg.get("gate_first", True):
        y = grouped(y * jax.nn.silu(z)) * w
    else:
        y = grouped(y) * w * jax.nn.silu(z)
    return y @ jnp.asarray(p["out_proj"], F32)


def attention(u, p, cfg, query_block=None):
    t = u.shape[0]
    h, kvh, d = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = (u @ jnp.asarray(p["wq"], F32)).reshape(t, h, d)
    k = (u @ jnp.asarray(p["wk"], F32)).reshape(t, kvh, d)
    v = (u @ jnp.asarray(p["wv"], F32)).reshape(t, kvh, d)
    k, v = (jnp.repeat(a, h // kvh, axis=1) for a in (k, v))
    pos = jnp.arange(t)
    outs = []
    for start in range(0, t, query_block or t):
        qp = pos[start: start + (query_block or t)]
        s = jnp.einsum("qhd,shd->hqs", q[qp], k) * d ** -0.5
        s = jnp.where((pos[None, :] <= qp[:, None])[None], s, -jnp.inf)
        outs.append(jnp.einsum("hqs,shd->qhd", jax.nn.softmax(s, axis=-1), v))
    o = jnp.concatenate(outs, axis=0)
    return o.reshape(t, h * d) @ jnp.asarray(p["wo"], F32).reshape(h * d, -1)


def route(u, p, cfg):
    """-> weights [T, E] float32 over ALL routed experts, zero outside each
    token's chosen set."""
    s = jax.nn.sigmoid(u @ jnp.asarray(p["router"], F32))
    t = s.shape[0]
    pick = s + jnp.asarray(p["expert_bias"], F32) if "expert_bias" in p else s
    _, idx = jax.lax.top_k(pick, cfg["num_experts_per_token"])
    chosen = jnp.zeros_like(s).at[jnp.arange(t)[:, None], idx].set(1.0)
    w = s * chosen
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"]


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def experts(u, p, cfg, experts_held=None, shared=True):
    """The expert layer, or a chip's share of it (module docstring)."""
    w = route(u, p, cfg)
    ups, downs = p["experts"]["w_up"], p["experts"]["w_down"]
    first, count = experts_held or (0, w.shape[1])
    act = jax.nn.silu if cfg.get("expert_act", "relu2") == "silu" else relu2
    latent = cfg.get("latent", True)
    width = downs.shape[-1]
    c = u @ jnp.asarray(p["latent"]["w_dn"], F32) if latent else u[:, :width]
    r = jnp.zeros_like(c)
    for e in range(count):
        y = act(c @ jnp.asarray(ups[e], F32)) @ jnp.asarray(downs[e], F32)
        r = r + w[:, first + e: first + e + 1] * y
    if latent:
        out = r @ jnp.asarray(p["latent"]["w_up"], F32)
    else:
        out = jnp.pad(r, ((0, 0), (0, u.shape[1] - width)))
    if shared and "shared" in p:
        out = out + (relu2(u @ jnp.asarray(p["shared"]["w_up"], F32))
                     @ jnp.asarray(p["shared"]["w_down"], F32))
    return out


def forward(params, cfg, tokens, experts_held=None, query_block=None):
    """``params``: the tree above; ``cfg``: a dict with ``norm_eps``,
    ``num_heads``, ``num_kv_heads``, ``head_dim``, ``ssm_heads``,
    ``ssm_head_dim``, ``ssm_groups``, ``ssm_state``, ``conv_kernel``,
    ``num_experts_per_token``, ``norm_topk_prob``, ``routed_scaling_factor``
    (and, for controls, ``expert_act``, ``latent``, ``gate_first``,
    ``conv_bias``); ``tokens``: [T] ids; ``experts_held``: ``(first,
    count)`` of the routed experts the stacks hold, None for all.
    -> logits [T, V]."""
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(params["embed"]["wte"], F32)[jnp.asarray(tokens)]
        eps = cfg["norm_eps"]
        for p in params["layers"]:
            u = rms(h, jnp.asarray(p["ln1"]["scale"], F32), eps)
            if "ssm" in p:
                h = h + mamba2(u, p["ssm"], cfg)
            else:
                h = h + attention(u, p["attn"], cfg, query_block)
            if p.get("mlp") is not None:
                u = rms(h, jnp.asarray(p["ln2"]["scale"], F32), eps)
                h = h + experts(u, p["mlp"], cfg, experts_held)
        h = rms(h, jnp.asarray(params["final_norm"]["scale"], F32), eps)
        return h @ jnp.asarray(params["lm_head"]["w"], F32)
