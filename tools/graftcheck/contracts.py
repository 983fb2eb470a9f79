"""The contract registry: what graftcheck holds the code to.

Every rule module consumes a declarative registry built here — op
shape/dtype contracts (GC1), preset x mesh sharding audits and collective
audits (GC2), hot-function dtype contracts (GC3), recompilation scenarios
(GC4), and donation contracts (GC5).  The registries are also the source of
the README "Semantic checks" table (``python -m tools.graftcheck
--write-docs``), so the docs can never drift from what is actually gated.

Everything imports the REAL package lazily (inside builders) and traces the
real functions — no mocks: a contract that passes here is a program XLA
would accept with these shapes on hardware.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp

# Repo-relative paths findings attribute to (line 0: semantic findings are
# whole-file; the baseline format is line-free anyway).
P_FLASH = "distributed_llms_tpu/ops/flash.py"
P_RING = "distributed_llms_tpu/ops/ring.py"
P_ULYSSES = "distributed_llms_tpu/ops/ulysses.py"
P_DECODE = "distributed_llms_tpu/ops/decode_attn.py"
P_QMM = "distributed_llms_tpu/ops/quant_matmul.py"
P_MODEL = "distributed_llms_tpu/models/model.py"
P_KV_CACHE = "distributed_llms_tpu/models/kv_cache.py"
P_SPECS = "distributed_llms_tpu/parallel/specs.py"
P_SAMPLING = "distributed_llms_tpu/runtime/sampling.py"
P_CONSTRAIN = "distributed_llms_tpu/runtime/constrain.py"
P_BATCHER = "distributed_llms_tpu/runtime/batcher.py"
P_ENGINE = "distributed_llms_tpu/runtime/engine.py"


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def key_sds():
    """Abstract typed PRNG key."""
    return jax.eval_shape(lambda: jax.random.key(0))


@functools.lru_cache(maxsize=None)
def preset(name: str, **overrides):
    from distributed_llms_tpu.models.presets import get_preset

    return get_preset(name, **overrides)


@functools.lru_cache(maxsize=None)
def abstract_params(cfg):
    from distributed_llms_tpu.models import model as model_lib

    return jax.eval_shape(
        lambda: model_lib.init_params(jax.random.key(0), cfg)
    )


def abstract_cache(cfg, batch: int, max_len: int):
    from distributed_llms_tpu.models import kv_cache

    return jax.eval_shape(lambda: kv_cache.init_cache(cfg, batch, max_len))


def abstract_pool(cfg, num_pages: int, page_size: int, kv_bits: int = 16):
    """KV page pool; ``kv_bits=8`` the int8 one (QuantKVCache: data int8
    + f32 absmax scales)."""
    from distributed_llms_tpu.models import kv_cache

    return jax.eval_shape(
        lambda: kv_cache.make_pool(cfg, num_pages, page_size, kv_bits)
    )


def fake_mesh(**axes: int):
    """AbstractMesh over the standard axis names — sharding semantics with
    zero devices (jax.eval_shape/make_jaxpr accept it everywhere a real
    mesh would go)."""
    from jax.sharding import AbstractMesh

    names = ("data", "pipe", "model", "seq", "expert")
    return AbstractMesh(tuple(axes.get(n, 1) for n in names), names)


# ---------------------------------------------------------------------------
# GC1 — op shape/dtype contracts
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class OpCase:
    label: str
    fn: Callable        # callable over the abstract args
    args: tuple         # abstract (or small concrete) argument pytrees
    want: tuple         # ((shape, dtype-str), ...) for every output leaf


@dataclass(frozen=True)
class OpContract:
    name: str           # e.g. "ops.flash.flash_attention"
    path: str
    doc: str            # one line for the README table
    build: Callable[[], list[OpCase]]


def _flash_cases() -> list[OpCase]:
    from distributed_llms_tpu.ops import flash

    cases = []
    # (b, tq, s, h, kvh, d, window, dtype): batch 1, non-power-of-two
    # lengths, GQA/MQA head ratios, windowed band, both serving dtypes.
    for b, tq, s, h, kvh, d, win, dt in [
        (1, 1, 1, 4, 4, 64, None, jnp.float32),
        (2, 7, 7, 4, 2, 64, None, jnp.bfloat16),
        (3, 33, 33, 8, 1, 64, None, jnp.bfloat16),
        (2, 128, 128, 4, 4, 64, 16, jnp.bfloat16),
        (2, 16, 48, 4, 2, 64, None, jnp.float32),  # prefill into longer cache
    ]:
        q = sds((b, tq, h, d), dt)
        kv = sds((b, s, kvh, d), dt)
        qp = sds((b, tq), jnp.int32)
        kp = sds((b, s), jnp.int32)
        kval = sds((b, s), jnp.bool_)
        aligned = tq == s
        fn = (
            (lambda q, k, v: flash.flash_attention(q, k, v))
            if aligned else
            (lambda q, k, v, qp, kp, kval: flash.flash_attention(
                q, k, v, q_positions=qp, k_positions=kp, k_valid=kval))
        )
        args = (q, kv, kv) if aligned else (q, kv, kv, qp, kp, kval)
        if win is not None:
            fn = functools.partial(
                lambda w, q, k, v: flash.flash_attention(q, k, v, window=w),
                win,
            )
            args = (q, kv, kv)
        cases.append(OpCase(
            label=f"b{b} tq{tq} s{s} h{h}/{kvh} d{d} win{win} {jnp.dtype(dt).name}",
            fn=fn, args=args,
            want=(((b, tq, h, d), jnp.dtype(dt).name),),
        ))
    return cases


def _ring_cases() -> list[OpCase]:
    import functools as ft

    from distributed_llms_tpu.ops import ring
    from jax.sharding import PartitionSpec as P

    cases = []
    for seq, b, t, h, kvh, d, dt in [
        (2, 1, 16, 4, 2, 64, jnp.bfloat16),
        (4, 2, 32, 4, 4, 64, jnp.float32),
        (4, 2, 96, 8, 2, 64, jnp.bfloat16),  # non-pow2 global length
    ]:
        mesh = fake_mesh(seq=seq)
        body = ft.partial(ring.ring_attention, axis_name="seq")
        sh, ps = P(None, "seq", None, None), P(None, "seq")

        def fn(q, k, v, pos, body=body, mesh=mesh, sh=sh, ps=ps):
            return jax.shard_map(
                lambda q, k, v, p: body(q, k, v, p, p),
                mesh=mesh, in_specs=(sh, sh, sh, ps), out_specs=sh,
                axis_names={"seq"},
            )(q, k, v, pos)

        cases.append(OpCase(
            label=f"seq{seq} b{b} t{t} h{h}/{kvh} {jnp.dtype(dt).name}",
            fn=fn,
            args=(sds((b, t, h, d), dt), sds((b, t, kvh, d), dt),
                  sds((b, t, kvh, d), dt), sds((b, t), jnp.int32)),
            want=(((b, t, h, d), jnp.dtype(dt).name),),
        ))
    return cases


def _seq_decode_cases() -> list[OpCase]:
    from distributed_llms_tpu.ops import ring
    from jax.sharding import PartitionSpec as P

    cases = []
    for seq, b, s_loc, n_dec, h, kvh, d in [(2, 2, 32, 8, 4, 2, 64),
                                            (4, 1, 16, 4, 4, 4, 64)]:
        mesh = fake_mesh(seq=seq)
        seq_kv = P(None, "seq", None, None)

        def fn(q, ck, cv, dk, dv, ml, md, mesh=mesh, seq_kv=seq_kv):
            return jax.shard_map(
                lambda q, ck, cv, dk, dv, ml, md:
                    ring.seq_cached_decode_attention(
                        q, ck, cv, dk, dv, ml, md, axis_name="seq"),
                mesh=mesh,
                in_specs=(P(), seq_kv, seq_kv, P(), P(), P(None, "seq"), P()),
                out_specs=P(),
                axis_names={"seq"},
            )(q, ck, cv, dk, dv, ml, md)

        dt = jnp.bfloat16
        cases.append(OpCase(
            label=f"seq{seq} b{b} sloc{s_loc} dec{n_dec} h{h}/{kvh}",
            fn=fn,
            args=(sds((b, 1, h, d), dt),
                  sds((b, s_loc * seq, kvh, d), dt),
                  sds((b, s_loc * seq, kvh, d), dt),
                  sds((b, n_dec, kvh, d), dt), sds((b, n_dec, kvh, d), dt),
                  sds((b, s_loc * seq), jnp.bool_),
                  sds((b, n_dec), jnp.bool_)),
            want=(((b, 1, h, d), "bfloat16"),),
        ))
    return cases


def _ulysses_cases() -> list[OpCase]:
    import functools as ft

    from distributed_llms_tpu.ops import ulysses
    from jax.sharding import PartitionSpec as P

    cases = []
    for seq, b, t, h, kvh, d in [(2, 2, 16, 4, 2, 64), (4, 1, 32, 8, 4, 64)]:
        mesh = fake_mesh(seq=seq)
        sh, ps = P(None, "seq", None, None), P(None, "seq")
        body = ft.partial(ulysses.ulysses_attention, axis_name="seq")

        def fn(q, k, v, pos, body=body, mesh=mesh, sh=sh, ps=ps):
            return jax.shard_map(
                body, mesh=mesh, in_specs=(sh, sh, sh, ps), out_specs=sh,
                axis_names={"seq"},
            )(q, k, v, pos)

        cases.append(OpCase(
            label=f"seq{seq} b{b} t{t} h{h}/{kvh}",
            fn=fn,
            args=(sds((b, t, h, d), jnp.bfloat16),
                  sds((b, t, kvh, d), jnp.bfloat16),
                  sds((b, t, kvh, d), jnp.bfloat16),
                  sds((b, t), jnp.int32)),
            want=(((b, t, h, d), "bfloat16"),),
        ))
    return cases


def _ragged_cases() -> list[OpCase]:
    from distributed_llms_tpu.ops import decode_attn

    cases = []
    for b, s, h, kvh, d, win in [
        (1, 128, 4, 2, 128, None),   # kernel-tileable width
        (3, 384, 8, 2, 128, None),   # 128-multiple but not 512: block stepdown
        (2, 40, 4, 4, 64, None),     # untileable -> dense fallback path
        (2, 256, 4, 2, 128, 64),     # windowed band
    ]:
        dt = jnp.bfloat16
        cases.append(OpCase(
            label=f"b{b} s{s} h{h}/{kvh} d{d} win{win}",
            fn=functools.partial(
                lambda w, q, k, v, ln: decode_attn.ragged_decode_attention(
                    q, k, v, ln, window=w), win),
            args=(sds((b, 1, h, d), dt), sds((b, s, kvh, d), dt),
                  sds((b, s, kvh, d), dt), sds((b,), jnp.int32)),
            want=(((b, 1, h, d), "bfloat16"),),
        ))
    return cases


# Layers of the stacked pool [L, NB, BLK, KVH, D] the paged contracts hand
# the kernel, with the layer to read as a traced scalar — the operands the
# layer scan passes (models.model._paged_attention).
_POOL_LAYERS = 3


def _paged_cases() -> list[OpCase]:
    from distributed_llms_tpu.ops import decode_attn

    cases = []
    for b, nb, blk, p, h, kvh, d in [
        (1, 16, 8, 4, 4, 2, 128),    # page-boundary: length can hit p*blk
        (3, 8, 64, 2, 4, 4, 64),     # untileable d -> gather fallback
        (2, 32, 16, 8, 8, 2, 128),
    ]:
        dt = jnp.bfloat16
        pool = (_POOL_LAYERS, nb, blk, kvh, d)
        cases.append(OpCase(
            label=f"b{b} nb{nb} blk{blk} p{p} h{h}/{kvh} d{d}",
            fn=lambda q, k, v, ln, tb, layer:
                decode_attn.paged_decode_attention(
                    q, k, v, ln, tb, layer=layer),
            args=(sds((b, 1, h, d), dt), sds(pool, dt), sds(pool, dt),
                  sds((b,), jnp.int32), sds((b, p), jnp.int32),
                  sds((), jnp.int32)),
            want=(((b, 1, h, d), "bfloat16"),),
        ))
    # One layer's rank-4 pages: the stack of one layer, layer 0.
    b, nb, blk, p, h, kvh, d = 2, 32, 16, 8, 8, 2, 128
    cases.append(OpCase(
        label=f"rank-4 pool b{b} nb{nb} blk{blk} p{p} h{h}/{kvh} d{d}",
        fn=decode_attn.paged_decode_attention,
        args=(sds((b, 1, h, d), jnp.bfloat16),
              sds((nb, blk, kvh, d), jnp.bfloat16),
              sds((nb, blk, kvh, d), jnp.bfloat16), sds((b,), jnp.int32),
              sds((b, p), jnp.int32)),
        want=(((b, 1, h, d), "bfloat16"),),
    ))
    return cases


def _decode_int8_cases() -> list[OpCase]:
    """Int8 legs of BOTH decode-attention kernels: quantized K/V (+ f32
    absmax scales) in, q.dtype out, across the same (batch, seq, heads,
    pages) sweep as the full-width contracts — tileable kernel shapes AND
    the dense/gather fallbacks."""
    from distributed_llms_tpu.ops import decode_attn

    cases = []
    dt = jnp.bfloat16
    for b, s, h, kvh, d in [
        (1, 128, 4, 2, 128),   # kernel-tileable
        (2, 40, 4, 4, 64),     # untileable -> dense fallback
        (3, 384, 8, 2, 128),   # block stepdown
    ]:
        cases.append(OpCase(
            label=f"ragged b{b} s{s} h{h}/{kvh} d{d}",
            fn=lambda q, k, v, ln, ks, vs:
                decode_attn.ragged_decode_attention(
                    q, k, v, ln, k_scale=ks, v_scale=vs),
            args=(sds((b, 1, h, d), dt), sds((b, s, kvh, d), jnp.int8),
                  sds((b, s, kvh, d), jnp.int8), sds((b,), jnp.int32),
                  sds((b, s, kvh), jnp.float32),
                  sds((b, s, kvh), jnp.float32)),
            want=(((b, 1, h, d), "bfloat16"),),
        ))
    for b, nb, blk, p, h, kvh, d in [
        (1, 16, 8, 4, 4, 2, 128),    # kernel-tileable, page boundary
        (3, 8, 64, 2, 4, 4, 64),     # untileable d -> gather fallback
        (2, 32, 16, 8, 8, 2, 128),
    ]:
        pool = (_POOL_LAYERS, nb, blk, kvh, d)
        cases.append(OpCase(
            label=f"paged b{b} nb{nb} blk{blk} p{p} h{h}/{kvh} d{d}",
            fn=lambda q, k, v, ln, tb, ks, vs, layer:
                decode_attn.paged_decode_attention(
                    q, k, v, ln, tb, k_scale=ks, v_scale=vs, layer=layer),
            args=(sds((b, 1, h, d), dt), sds(pool, jnp.int8),
                  sds(pool, jnp.int8), sds((b,), jnp.int32),
                  sds((b, p), jnp.int32), sds(pool[:-1], jnp.float32),
                  sds(pool[:-1], jnp.float32), sds((), jnp.int32)),
            want=(((b, 1, h, d), "bfloat16"),),
        ))
    return cases


def _decode_spmd_cases() -> list[OpCase]:
    """Per-SHARD shapes of the decode-attention SPMD rule (mesh-native
    paged serving): under `ops.dispatch.per_shard`
    each device runs the kernel on its local head slice — H and KVH both
    divided by tp, page table and cache width intact.  These cases trace
    exactly those local calls at tp2/tp4 slices of the full-head
    contracts, both legs, bf16 AND int8 — a head-slice shape the kernel
    cannot serve would mean the partition rule hands shards an illegal
    program."""
    from distributed_llms_tpu.ops import decode_attn

    cases = []
    dt = jnp.bfloat16
    # Ragged local shards: (tp, b, s, h, kvh, d).
    for tp, b, s, h, kvh, d in [(2, 2, 128, 8, 4, 128),
                                (4, 1, 256, 8, 4, 128)]:
        hl, kl = h // tp, kvh // tp
        cases.append(OpCase(
            label=f"ragged tp{tp} shard b{b} s{s} h{hl}/{kl} d{d}",
            fn=lambda q, k, v, ln: decode_attn.ragged_decode_attention(
                q, k, v, ln),
            args=(sds((b, 1, hl, d), dt), sds((b, s, kl, d), dt),
                  sds((b, s, kl, d), dt), sds((b,), jnp.int32)),
            want=(((b, 1, hl, d), "bfloat16"),),
        ))
        cases.append(OpCase(
            label=f"ragged-int8 tp{tp} shard b{b} s{s} h{hl}/{kl} d{d}",
            fn=lambda q, k, v, ln, ks, vs:
                decode_attn.ragged_decode_attention(
                    q, k, v, ln, k_scale=ks, v_scale=vs),
            args=(sds((b, 1, hl, d), dt), sds((b, s, kl, d), jnp.int8),
                  sds((b, s, kl, d), jnp.int8), sds((b,), jnp.int32),
                  sds((b, s, kl), jnp.float32), sds((b, s, kl), jnp.float32)),
            want=(((b, 1, hl, d), "bfloat16"),),
        ))
    # Paged local shards: (tp, b, nb, blk, p, h, kvh, d) — the pool's
    # page axes stay whole, only KVH slices.
    for tp, b, nb, blk, p, h, kvh, d in [(2, 2, 16, 8, 4, 8, 4, 128),
                                         (4, 1, 32, 16, 8, 8, 4, 128)]:
        hl, kl = h // tp, kvh // tp
        pool = (_POOL_LAYERS, nb, blk, kl, d)
        cases.append(OpCase(
            label=f"paged tp{tp} shard b{b} nb{nb} blk{blk} h{hl}/{kl}",
            fn=lambda q, k, v, ln, tb, layer:
                decode_attn.paged_decode_attention(
                    q, k, v, ln, tb, layer=layer),
            args=(sds((b, 1, hl, d), dt), sds(pool, dt), sds(pool, dt),
                  sds((b,), jnp.int32), sds((b, p), jnp.int32),
                  sds((), jnp.int32)),
            want=(((b, 1, hl, d), "bfloat16"),),
        ))
        cases.append(OpCase(
            label=f"paged-int8 tp{tp} shard b{b} nb{nb} blk{blk} h{hl}/{kl}",
            fn=lambda q, k, v, ln, tb, ks, vs, layer:
                decode_attn.paged_decode_attention(
                    q, k, v, ln, tb, k_scale=ks, v_scale=vs, layer=layer),
            args=(sds((b, 1, hl, d), dt), sds(pool, jnp.int8),
                  sds(pool, jnp.int8), sds((b,), jnp.int32),
                  sds((b, p), jnp.int32), sds(pool[:-1], jnp.float32),
                  sds(pool[:-1], jnp.float32), sds((), jnp.int32)),
            want=(((b, 1, hl, d), "bfloat16"),),
        ))
    return cases


def _quant_cases() -> list[OpCase]:
    import numpy as np

    from distributed_llms_tpu.checkpoint.quantize import quantize
    from distributed_llms_tpu.ops import quant_matmul

    cases = []
    rng = np.random.default_rng(0)
    for bits in (8, 4):
        w = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
        qt = quantize(w, bits=bits, block=32)
        m = 7  # non-power-of-two row count
        cases.append(OpCase(
            label=f"int{bits} k_lead1 m{m}",
            fn=functools.partial(
                lambda qt, x: quant_matmul.quant_contract(
                    x, qt, k_lead=1, eq="mk,kn->mn"), qt),
            args=(sds((m, 64), jnp.float32),),
            want=(((m, 128), "float32"),),
        ))
    return cases


def _forward_cases() -> list[OpCase]:
    from distributed_llms_tpu.models import model as model_lib

    cases = []
    # Plain forward across families: logits [B, T, V] ALWAYS float32
    # (unembed's preferred_element_type), whatever the param dtype.
    for pname in ("llama-tiny", "gpt2-tiny", "neox-tiny", "moe-tiny"):
        for b, t in [(1, 1), (2, 7), (3, 16)]:
            cfg = preset(pname, dtype="bfloat16")
            params = abstract_params(cfg)
            cases.append(OpCase(
                label=f"{pname} fwd b{b} t{t}",
                fn=functools.partial(
                    lambda cfg, p, tok: model_lib.forward(p, cfg, tok)[0],
                    cfg),
                args=(params, sds((b, t), jnp.int32)),
                want=(((b, t, cfg.vocab_size), "float32"),),
            ))
    # Cached per-row decode (the continuous batcher's step): cache dtype is
    # PRESERVED (kv_cache_dtype contract) and logits stay float32.
    cfg = preset("llama-tiny", dtype="bfloat16")
    params = abstract_params(cfg)
    for b, s in [(2, 32), (1, 64), (3, 48)]:
        cache = abstract_cache(cfg, b, s)
        l, kvh, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_
        cases.append(OpCase(
            label=f"llama-tiny rowdecode b{b} s{s}",
            fn=functools.partial(
                lambda cfg, p, tok, pos, c, ci, m: (
                    lambda out: (out[0], out[1].k, out[1].v)
                )(model_lib.forward(
                    p, cfg, tok, positions=pos, cache=c, cache_index=ci,
                    attn_mask=m)), cfg),
            args=(params, sds((b, 1), jnp.int32), sds((b, 1), jnp.int32),
                  cache, sds((b,), jnp.int32),
                  sds((b, 1, 1, s), jnp.bool_)),
            want=(((b, 1, cfg.vocab_size), "float32"),
                  ((l, b, s, kvh, hd), "bfloat16"),
                  ((l, b, s, kvh, hd), "bfloat16")),
        ))
    # Paged decode through a page table: pool shapes round-trip unchanged.
    for b, nb, blk, p in [(2, 8, 8, 4), (1, 16, 8, 8)]:
        pool = abstract_pool(cfg, nb, blk)
        l, kvh, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_
        cases.append(OpCase(
            label=f"llama-tiny pageddecode b{b} nb{nb} blk{blk}",
            fn=functools.partial(
                lambda cfg, prm, tok, pos, c, ci, tb: (
                    lambda out: (out[0], out[1].k, out[1].v)
                )(model_lib.forward(
                    prm, cfg, tok, positions=pos, cache=c, cache_index=ci,
                    kv_tables=tb)), cfg),
            args=(params, sds((b, 1), jnp.int32), sds((b, 1), jnp.int32),
                  pool, sds((b,), jnp.int32), sds((b, p), jnp.int32)),
            want=(((b, 1, cfg.vocab_size), "float32"),
                  ((l, nb, blk, kvh, hd), "bfloat16"),
                  ((l, nb, blk, kvh, hd), "bfloat16")),
        ))
    # Int8 paged decode (--kv-bits 8): the pool round-trips at int8 with
    # f32 scales — logits stay f32, nothing silently re-widens.
    for b, nb, blk, p in [(2, 8, 8, 4), (1, 16, 8, 8)]:
        qpool = abstract_pool(cfg, nb, blk, kv_bits=8)
        l, kvh, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_
        cases.append(OpCase(
            label=f"llama-tiny int8-pageddecode b{b} nb{nb} blk{blk}",
            fn=functools.partial(
                lambda cfg, prm, tok, pos, c, ci, tb: (
                    lambda out: (out[0], *jax.tree.leaves(out[1]))
                )(model_lib.forward(
                    prm, cfg, tok, positions=pos, cache=c, cache_index=ci,
                    kv_tables=tb)), cfg),
            args=(params, sds((b, 1), jnp.int32), sds((b, 1), jnp.int32),
                  qpool, sds((b,), jnp.int32), sds((b, p), jnp.int32)),
            want=(((b, 1, cfg.vocab_size), "float32"),
                  ((l, nb, blk, kvh, hd), "int8"),
                  ((l, nb, blk, kvh, hd), "int8"),
                  ((l, nb, blk, kvh), "float32"),
                  ((l, nb, blk, kvh), "float32")),
        ))
    return cases


def _kv_transfer_cases() -> list[OpCase]:
    """Disaggregated KV handoff: the export gather pulls a page run out of
    the pool into row layout ([L, 1, P*BLK, KVH, HD], pool dtype), and the
    import scatter adopts a page stack ([L, P, BLK, KVH, HD]) back into a
    pool whose shape/dtype must round-trip UNCHANGED — a widened pool or a
    silently-promoted dtype would corrupt every later admission."""
    import jax.numpy as jnp

    from distributed_llms_tpu.models import kv_cache

    cfg = preset("llama-tiny", dtype="bfloat16")
    l, kvh, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_
    cases = []
    # (pool pages, page size, pages in transit) incl. 1-page and
    # non-power-of-two transfers.
    for nb, blk, p in [(8, 8, 1), (16, 16, 3), (12, 8, 7)]:
        pool = abstract_pool(cfg, nb, blk)
        cases.append(OpCase(
            label=f"export gather nb{nb} blk{blk} p{p}",
            fn=kv_cache.gather_row,
            args=(pool, sds((p,), jnp.int32)),
            want=(((l, 1, p * blk, kvh, hd), "bfloat16"),
                  ((l, 1, p * blk, kvh, hd), "bfloat16")),
        ))
        cases.append(OpCase(
            label=f"import scatter nb{nb} blk{blk} p{p}",
            fn=lambda c, pl, k, v: (
                lambda out: (out.k, out.v)
            )(kv_cache.import_full(c, pl, k, v)),
            args=(pool, sds((p,), jnp.int32),
                  sds((l, p, blk, kvh, hd), jnp.float32),  # host payload
                  sds((l, p, blk, kvh, hd), jnp.float32)),
            want=(((l, nb, blk, kvh, hd), "bfloat16"),
                  ((l, nb, blk, kvh, hd), "bfloat16")),
        ))
    return cases


def _mixed_step_cases() -> list[OpCase]:
    """The fused mixed step's segment legs (the mixed-segment attention
    leg of ``schedule=mixed``): across prefill-bite buckets and
    contiguous/paged pools, the decode leg keeps [B, K] int32 tokens +
    [B, K] f32 logprobs, the prefill segment's transient row keeps its
    shape AND dtype (the continuation-mask attention must not widen it —
    the row splices into the shared cache at the finish), and the
    finishing-splice logits stay [1, V] f32."""
    import jax.numpy as jnp

    from distributed_llms_tpu.runtime import batcher as batcher_lib

    cfg = preset("llama-tiny", dtype="bfloat16")
    l, kvh, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_
    v = cfg.vocab_size
    b, s, k = 4, 128, 8
    params = abstract_params(cfg)
    row = abstract_cache(cfg, 1, s)

    def pick(out):
        # (toks, lps, row_k', row_v', last_logits) — the fused step's
        # segment-leg outputs; the cache carry is pinned by the GC4
        # chaining contract and the decode_chunk GC1 cases already.
        return out[0], out[7], out[10], out[11], out[12]

    want = (((b, k), "int32"), ((b, k), "float32"),
            ((l, 1, s, kvh, hd), "bfloat16"),
            ((l, 1, s, kvh, hd), "bfloat16"),
            ((1, v), "float32"))
    cases = []
    for pw in (8, 32, 64):  # bite buckets up the shared ladder
        cases.append(OpCase(
            label=f"contiguous pw{pw}",
            fn=lambda p, c, lt, rl, va, ac, bu, rng, rk, rv, dn, pc, pl:
                pick(batcher_lib.mixed_step(
                    p, cfg, cfg, c, lt, rl, va, ac, bu, rng, k,
                    rk, rv, dn, pc, pl)),
            args=(params, abstract_cache(cfg, b, s), sds((b,), jnp.int32),
                  sds((b,), jnp.int32), sds((b, s), jnp.bool_),
                  sds((b,), jnp.bool_), sds((b,), jnp.int32), key_sds(),
                  row.k, row.v, sds((), jnp.int32),
                  sds((pw,), jnp.int32), sds((), jnp.int32)),
            want=want,
        ))
    nb, blk, p = 16, 16, 8  # pool pages, page size, pages per row (= s)
    for pw in (8, 64):
        cases.append(OpCase(
            label=f"paged pw{pw}",
            fn=lambda prm, c, lt, rl, va, ac, bu, rng, rk, rv, dn, pc, pl,
                tb:
                pick(batcher_lib.mixed_step(
                    prm, cfg, cfg, c, lt, rl, va, ac, bu, rng, k,
                    rk, rv, dn, pc, pl, tables=tb)),
            args=(params, abstract_pool(cfg, nb, blk), sds((b,), jnp.int32),
                  sds((b,), jnp.int32), sds((b, s), jnp.bool_),
                  sds((b,), jnp.bool_), sds((b,), jnp.int32), key_sds(),
                  row.k, row.v, sds((), jnp.int32),
                  sds((pw,), jnp.int32), sds((), jnp.int32),
                  sds((b, p), jnp.int32)),
            want=want,
        ))
    return cases


def _spec_chunk_paged_cases() -> list[OpCase]:
    """The paged speculative round (spec x paged tentpole): across spec_k
    values and BOTH pool widths, the round keeps [B, k+1] int32 tokens +
    f32 logprobs and [B] int32 commit counts (``commit_clamp``'s
    pos/length rollback output), the POOL leaves keep pool-storage dtypes
    (the scratch-tail window writes must not widen int8 data or f32
    scales), and the contiguous DRAFT cache keeps its dtype.  ``k_row``
    (the adaptive downshift) and the page tables are engaged in every
    case — the shapes the engine actually dispatches."""
    import jax.numpy as jnp

    from distributed_llms_tpu.runtime import batcher as batcher_lib

    cfg = preset("llama-tiny", dtype="bfloat16")
    l, kvh, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_
    b, s, nb, blk, p = 2, 128, 16, 16, 8
    params = abstract_params(cfg)
    draft = abstract_cache(cfg, b, s)

    def pick(out):
        # (toks, m, lps, cache', draft_cache') — the carry vectors are
        # pinned by the GC4 chaining scenario; counts is None here.
        return out[0], out[1], out[2], out[3], out[4]

    cases = []
    for k in (2, 4):
        head = (((b, k + 1), "int32"), ((b,), "int32"),
                ((b, k + 1), "float32"))
        draft_want = (((l, b, s, kvh, hd), "bfloat16"),) * 2
        for kv_bits in (16, 8):
            if kv_bits == 8:
                pool = abstract_pool(cfg, nb, blk, kv_bits=8)
                pool_want = (
                    ((l, nb, blk, kvh, hd), "int8"),
                    ((l, nb, blk, kvh, hd), "int8"),
                    ((l, nb, blk, kvh), "float32"),
                    ((l, nb, blk, kvh), "float32"),
                )
            else:
                pool = abstract_pool(cfg, nb, blk)
                pool_want = (((l, nb, blk, kvh, hd), "bfloat16"),) * 2
            cases.append(OpCase(
                label=f"k{k} kv{kv_bits}",
                fn=(lambda prm, dprm, c, dc, lt, rl, va, ac, bu, tb, kr,
                    _k=k:
                    pick(batcher_lib.spec_chunk(
                        prm, cfg, dprm, cfg, c, dc, lt, rl, va, ac, bu,
                        k=_k, tables=tb, k_row=kr))),
                args=(params, params, pool, draft, sds((b,), jnp.int32),
                      sds((b,), jnp.int32), sds((b, s), jnp.bool_),
                      sds((b,), jnp.bool_), sds((b,), jnp.int32),
                      sds((b, p), jnp.int32), sds((b,), jnp.int32)),
                want=head + pool_want + draft_want,
            ))
    return cases


def _sampling_cases() -> list[OpCase]:
    from distributed_llms_tpu.runtime import sampling

    cases = []
    for b, v in [(1, 256), (5, 1000)]:
        cases.append(OpCase(
            label=f"sample greedy b{b} v{v}",
            fn=functools.partial(
                lambda rng, lg: sampling.sample(rng, lg, 0.0)),
            args=(key_sds(), sds((b, v), jnp.float32)),
            want=(((b,), "int32"),),
        ))
        cases.append(OpCase(
            label=f"sample_rows b{b} v{v}",
            fn=lambda rng, lg, t, p, k: sampling.sample_rows(
                rng, lg, t, top_p=p, top_k_rows=k),
            args=(key_sds(), sds((b, v), jnp.float32),
                  sds((b,), jnp.float32), sds((b,), jnp.float32),
                  sds((b,), jnp.int32)),
            want=(((b,), "int32"),),
        ))
    return cases


def _constrain_cases() -> list[OpCase]:
    """Constraint mask ops (runtime/constrain.py): the per-row mask
    gather returns [B, V] float32 and the DFA advance returns [B] int32,
    over a (batch, states, vocab) sweep covering the byte-tokenizer and
    real-checkpoint vocab scales plus 1-state bias-only automata."""
    from distributed_llms_tpu.runtime import constrain

    cases = []
    for b, s, v in [(1, 1, 259), (4, 33, 512), (8, 300, 32000)]:
        cases.append(OpCase(
            label=f"gather_bias b{b} s{s} v{v}",
            fn=constrain.gather_bias,
            args=(sds((s, v), jnp.float32), sds((b,), jnp.int32)),
            want=(((b, v), "float32"),),
        ))
        cases.append(OpCase(
            label=f"advance_states b{b} s{s} v{v}",
            fn=constrain.advance_states,
            args=(sds((s, v), jnp.int32), sds((b,), jnp.int32),
                  sds((b,), jnp.int32)),
            want=(((b,), "int32"),),
        ))
    return cases


def op_contracts() -> list[OpContract]:
    return [
        OpContract("ops.flash.flash_attention", P_FLASH,
                   "out [B,Tq,H,D] in q.dtype across GQA/window/k_valid sweeps",
                   _flash_cases),
        OpContract("ops.ring.ring_attention", P_RING,
                   "out [B,T,H,D] under shard_map('seq') on fake meshes",
                   _ring_cases),
        OpContract("ops.ring.seq_cached_decode_attention", P_RING,
                   "psum-merged decode [B,1,H,D], replicated over 'seq'",
                   _seq_decode_cases),
        OpContract("ops.ulysses.ulysses_attention", P_ULYSSES,
                   "all-to-all head scatter round-trips to [B,T,H,D]",
                   _ulysses_cases),
        OpContract("ops.decode_attn.ragged_decode_attention", P_DECODE,
                   "[B,1,H,D] in q.dtype; tileable, stepdown, dense, window",
                   _ragged_cases),
        OpContract("ops.decode_attn.paged_decode_attention", P_DECODE,
                   "[B,1,H,D] through page tables incl. page-boundary sizes",
                   _paged_cases),
        OpContract("ops.decode_attn_int8", P_DECODE,
                   "int8 pages + absmax scales in, q.dtype out "
                   "(ragged + paged legs, kernel and fallback shapes)",
                   _decode_int8_cases),
        OpContract("ops.decode_attn_spmd", P_DECODE,
                   "per-shard head-slice shapes of the SPMD rule stay "
                   "legal (ragged + paged, bf16 + int8, tp2/tp4 slices)",
                   _decode_spmd_cases),
        OpContract("ops.quant_matmul.quant_contract", P_QMM,
                   "int8/int4 contraction keeps activation dtype and N axes",
                   _quant_cases),
        OpContract("models.model.forward", P_MODEL,
                   "logits f32, cache dtype preserved: plain/row-decode/paged",
                   _forward_cases),
        OpContract("runtime.sampling", P_SAMPLING,
                   "samplers return [B] int32 for static and per-row paths",
                   _sampling_cases),
        OpContract("runtime.constrain.mask_ops", P_CONSTRAIN,
                   "mask gather [B,V] f32 + DFA advance [B] i32 over a "
                   "batch/state/vocab sweep",
                   _constrain_cases),
        OpContract("models.kv_cache.page_transfer", P_KV_CACHE,
                   "handoff export/import: pool shape+dtype round-trip, "
                   "payload cast to pool dtype",
                   _kv_transfer_cases),
        OpContract("batcher.mixed_step", P_BATCHER,
                   "fused mixed-segment legs: decode toks/lps shapes, "
                   "prefill row shape+dtype preserved, splice logits "
                   "[1,V] f32 (contiguous + paged, bite-bucket sweep)",
                   _mixed_step_cases),
        OpContract("batcher.spec_chunk_paged", P_BATCHER,
                   "paged speculative round: toks [B,k+1] i32 / commit "
                   "counts [B] i32 (the rollback clamp) / lps f32, pool "
                   "storage dtypes preserved (bf16 + int8-with-scales "
                   "scratch-tail page writes), draft cache dtype kept",
                   _spec_chunk_paged_cases),
    ]


# ---------------------------------------------------------------------------
# GC2 — sharding-spec audits
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SpecAudit:
    name: str       # "llama-tiny@tp4"
    path: str
    build: Callable[[], tuple]  # -> (param_tree, spec_tree, mesh)


@dataclass(frozen=True)
class CollectiveAudit:
    name: str
    path: str
    doc: str
    build: Callable[[], tuple]  # -> (fn, args, mesh)


MESH_LADDER: tuple[tuple[str, dict], ...] = (
    ("tp2", dict(model=2)),
    ("tp4", dict(model=4)),
    ("tp8", dict(model=8)),
    ("pp2", dict(pipe=2)),
    ("pp2tp4", dict(pipe=2, model=4)),
    ("ep2tp2", dict(expert=2, model=2)),
)


def spec_audits() -> list[SpecAudit]:
    from distributed_llms_tpu.models.presets import PRESETS

    out = []
    for pname in sorted(PRESETS):
        for mlabel, axes in MESH_LADDER:
            def build(pname=pname, axes=axes):
                from distributed_llms_tpu.parallel import specs as specs_lib

                cfg = preset(pname)
                mesh = fake_mesh(**axes)
                return (abstract_params(cfg),
                        specs_lib.param_specs(cfg, mesh), mesh)

            out.append(SpecAudit(f"{pname}@{mlabel}", P_SPECS, build))
    # Staged (pipelined) tree: blocks reshaped [L,...] -> [P, L/P, ...] must
    # structure-match staged_param_specs on a divisible preset.
    def build_staged():
        from distributed_llms_tpu.parallel import api as api_lib
        from distributed_llms_tpu.parallel import pipeline as pipeline_lib

        cfg = preset("llama-tiny")
        mesh = fake_mesh(pipe=2)
        tree = dict(abstract_params(cfg))
        tree["blocks"] = jax.eval_shape(
            lambda b: pipeline_lib.split_stages(b, 2), tree["blocks"]
        )
        return tree, api_lib.staged_param_specs(cfg, mesh), mesh

    out.append(SpecAudit("llama-tiny@staged-pp2",
                         "distributed_llms_tpu/parallel/api.py",
                         build_staged))
    out += _page_pool_audits()
    out += _decode_spmd_audits()
    return out


_MESH_PAGED_LADDER: tuple[tuple[str, dict], ...] = (
    ("tp2", dict(model=2)),
    ("tp4", dict(model=4)),
    ("dp2tp2", dict(data=2, model=2)),
)


def _page_pool_audits() -> list[SpecAudit]:
    """Sharded page-pool layout (mesh-native paged serving): the pool
    trees `make_pool` builds must structure-match
    `models.kv_cache.pool_specs` — KV heads over 'model', int8 absmax
    scales sharded with their pages — with axis names and divisibility
    checked over the tp ladder.  llama-tiny (2 KV heads) exercises the
    non-divisible degrade at tp4; gpt2-tiny (4 heads) shards at both."""
    out = []
    for pname in ("llama-tiny", "gpt2-tiny"):
        for mlabel, axes in _MESH_PAGED_LADDER:
            for bits in (16, 8):
                def build(pname=pname, axes=axes, bits=bits):
                    from distributed_llms_tpu.models import kv_cache

                    cfg = preset(pname)
                    mesh = fake_mesh(**axes)
                    pool = abstract_pool(cfg, 16, 16, kv_bits=bits)
                    return pool, kv_cache.pool_specs(cfg, mesh, pool), mesh

                out.append(SpecAudit(
                    f"page-pool[kv{bits}|{pname}]@{mlabel}", P_KV_CACHE,
                    build,
                ))
    return out


def _decode_spmd_audits() -> list[SpecAudit]:
    """The decode-attention SPMD rule's operand placement
    (`ops.decode_attn.spmd_operand_specs` — the very specs the
    shard_map dispatch runs with): every operand spec
    must name real mesh axes and divide its dims over the ladder, for
    the ragged and paged legs at both KV widths."""
    out = []
    b, s, h, kvh, d = 4, 128, 8, 4, 128
    nb, blk, p = 16, 16, 8
    for mlabel, axes in _MESH_PAGED_LADDER:
        for paged in (False, True):
            for quant in (False, True):
                def build(axes=axes, paged=paged, quant=quant):
                    from distributed_llms_tpu.ops import decode_attn

                    mesh = fake_mesh(**axes)
                    kv_shape = ((_POOL_LAYERS, nb, blk, kvh, d) if paged
                                else (b, s, kvh, d))
                    kv_dt = jnp.int8 if quant else jnp.bfloat16
                    tree = {"q": sds((b, 1, h, d), jnp.bfloat16),
                            "lengths": sds((b,), jnp.int32)}
                    if paged:
                        tree["k_pages"] = sds(kv_shape, kv_dt)
                        tree["v_pages"] = sds(kv_shape, kv_dt)
                        tree["tables"] = sds((b, p), jnp.int32)
                        tree["layer"] = sds((1,), jnp.int32)
                    else:
                        tree["k"] = sds(kv_shape, kv_dt)
                        tree["v"] = sds(kv_shape, kv_dt)
                    if quant:
                        scale_shape = kv_shape[:-1]
                        tree["k_scale"] = sds(scale_shape, jnp.float32)
                        tree["v_scale"] = sds(scale_shape, jnp.float32)
                    specs, _ = decode_attn.spmd_operand_specs(
                        mesh, (b, 1, h, d), kv_shape, paged=paged,
                        quant=quant,
                    )
                    return tree, specs, mesh

                leg = "paged" if paged else "ragged"
                bits = "int8" if quant else "bf16"
                out.append(SpecAudit(
                    f"decode-attn-spmd[{leg}|{bits}]@{mlabel}", P_DECODE,
                    build,
                ))
    return out


def collective_audits() -> list[CollectiveAudit]:
    audits = []

    def build_ring():
        case = _ring_cases()[1]  # seq4 f32
        return case.fn, case.args, fake_mesh(seq=4)

    def build_ring_decode():
        case = _seq_decode_cases()[0]  # seq2
        return case.fn, case.args, fake_mesh(seq=2)

    def build_ulysses():
        case = _ulysses_cases()[1]  # seq4
        return case.fn, case.args, fake_mesh(seq=4)

    audits.append(CollectiveAudit(
        "ops.ring.ring_attention", P_RING,
        "ppermute rotation rides the mesh's 'seq' axis", build_ring))
    audits.append(CollectiveAudit(
        "ops.ring.seq_cached_decode_attention", P_RING,
        "pmax/psum stat merge over 'seq'", build_ring_decode))
    audits.append(CollectiveAudit(
        "ops.ulysses.ulysses_attention", P_ULYSSES,
        "all_to_all/all_gather over 'seq'", build_ulysses))
    return audits


# ---------------------------------------------------------------------------
# GC3 — dtype-promotion contracts
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class HotFnContract:
    name: str
    path: str
    doc: str
    build: Callable[[], tuple]      # -> (fn, args)
    allow_upcast: frozenset = frozenset()  # function names allowed bf16->f32


# Deliberate f32-stability upcasts in the model stack: norms compute in
# f32, RoPE builds its rotation table in f32, the MoE router softmaxes in
# f32.  Anything ELSE converting bf16 activations up is an accidental
# double-width HBM bill and fails GC302.
MODEL_UPCAST_ALLOW = frozenset(
    {"rms_norm", "layer_norm", "apply_rope", "moe_swiglu"}
)


def hot_contracts() -> list[HotFnContract]:
    from distributed_llms_tpu.models import model as model_lib

    out = []
    for pname in ("llama-tiny", "gpt2-tiny", "neox-tiny", "moe-tiny"):
        def build_fwd(pname=pname):
            cfg = preset(pname, dtype="bfloat16")
            return (
                functools.partial(
                    lambda cfg, p, t: model_lib.forward(p, cfg, t)[0], cfg),
                (abstract_params(cfg), sds((2, 8), jnp.int32)),
            )

        out.append(HotFnContract(
            f"models.model.forward[{pname}]", P_MODEL,
            "bf16 prefill upcasts only in norm/rope/router",
            build_fwd, MODEL_UPCAST_ALLOW))

    def build_decode():
        cfg = preset("llama-tiny", dtype="bfloat16")
        cache = abstract_cache(cfg, 2, 32)
        return (
            functools.partial(
                lambda cfg, p, t, pos, c, ci, m: model_lib.forward(
                    p, cfg, t, positions=pos, cache=c, cache_index=ci,
                    attn_mask=m)[0], cfg),
            (abstract_params(cfg), sds((2, 1), jnp.int32),
             sds((2, 1), jnp.int32), cache, sds((2,), jnp.int32),
             sds((2, 1, 1, 32), jnp.bool_)),
        )

    out.append(HotFnContract(
        "models.model.forward[row-decode]", P_MODEL,
        "bf16 cached decode step stays bf16 outside norm/rope",
        build_decode, MODEL_UPCAST_ALLOW))

    def build_sampling():
        from distributed_llms_tpu.runtime import sampling

        return (
            lambda rng, lg, t, p, k: sampling.sample_rows(
                rng, lg, t, top_p=p, top_k_rows=k),
            (key_sds(), sds((4, 512), jnp.float32), sds((4,), jnp.float32),
             sds((4,), jnp.float32), sds((4,), jnp.int32)),
        )

    out.append(HotFnContract(
        "runtime.sampling.sample_rows", P_SAMPLING,
        "no float64 anywhere in the per-row sampler",
        build_sampling, frozenset()))
    return out


# ---------------------------------------------------------------------------
# GC4 — recompilation scenarios
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class RecompileScenario:
    name: str
    path: str
    doc: str
    ladder: tuple[int, ...]             # raw request lengths swept
    width_of: Callable[[int], int]      # raw length -> jit-visible width
    allowed_widths: tuple[int, ...]     # the CLOSED ladder (GC402)
    max_keys: int                       # declared compile-key bound (GC401)
    trace: Callable[[int], str]         # width -> compile-cache key


_GC4_LADDER = (1, 2, 3, 5, 7, 8, 9, 13, 16, 17, 24, 31, 32, 33, 47, 63,
               64, 65, 100, 120)


def recompile_scenarios() -> list[RecompileScenario]:
    from distributed_llms_tpu.runtime import shapes as shapes_lib

    from .core import jaxpr_hash

    out = []
    s_cap = 128  # tiny-config cache width the sweeps run against
    cfg = preset("llama-tiny")

    # -- batcher admission: prompt widths must walk the shared ladder, and
    # each distinct width is ONE compiled program.
    def admit_trace(width: int) -> str:
        from distributed_llms_tpu.runtime import batcher as batcher_lib

        params = abstract_params(cfg)
        cache = abstract_cache(cfg, 4, s_cap)
        return jaxpr_hash(
            lambda p, c, slot, prompt, plen, rng: batcher_lib.admit_row(
                p, cfg, c, slot, prompt, plen, rng),
            params, cache, sds((), jnp.int32), sds((width,), jnp.int32),
            sds((), jnp.int32), key_sds(),
            statics={"cfg": cfg},
        )

    out.append(RecompileScenario(
        name="batcher.admit_row", path=P_BATCHER,
        doc="admission prefill compiles once per prompt bucket",
        ladder=_GC4_LADDER,
        width_of=lambda n: min(shapes_lib.bucket_length(n), s_cap),
        allowed_widths=tuple(shapes_lib.bucket_ladder(s_cap)),
        max_keys=shapes_lib.bucket_count(s_cap),
        trace=admit_trace,
    ))

    # -- decode step: shapes are depth-independent, so the WHOLE ladder is
    # one compile key (depths are traced values, not shapes).
    def decode_trace(width: int) -> str:
        from distributed_llms_tpu.runtime import batcher as batcher_lib

        b = 4
        params = abstract_params(cfg)
        cache = abstract_cache(cfg, b, s_cap)
        return jaxpr_hash(
            lambda p, c, lt, rl, va, ac, bu, rng: batcher_lib.decode_chunk(
                p, cfg, c, lt, rl, va, ac, bu, rng, chunk_steps=8),
            params, cache, sds((b,), jnp.int32), sds((b,), jnp.int32),
            sds((b, s_cap), jnp.bool_), sds((b,), jnp.bool_),
            sds((b,), jnp.int32), key_sds(),
            statics={"cfg": cfg, "chunk_steps": 8},
        )

    out.append(RecompileScenario(
        name="batcher.decode_chunk", path=P_BATCHER,
        doc="decode chunk is ONE program across every resident depth",
        ladder=_GC4_LADDER,
        width_of=lambda n: s_cap,
        allowed_widths=(s_cap,),
        max_keys=1,
        trace=decode_trace,
    ))

    # -- int8 paged decode step: the quantized leg (per-step KV quantize
    # + scale-fused attention read) must still be ONE compiled program —
    # neither depths nor page contents are shapes.
    def decode_int8_trace(width: int) -> str:
        from distributed_llms_tpu.runtime import batcher as batcher_lib

        b, nb, blk, p = 4, 16, 16, 8
        params = abstract_params(cfg)
        pool = abstract_pool(cfg, nb, blk, kv_bits=8)
        return jaxpr_hash(
            lambda prm, c, lt, rl, va, ac, bu, rng, tb:
                batcher_lib.decode_chunk(
                    prm, cfg, c, lt, rl, va, ac, bu, rng, chunk_steps=8,
                    tables=tb),
            params, pool, sds((b,), jnp.int32), sds((b,), jnp.int32),
            sds((b, p * blk), jnp.bool_), sds((b,), jnp.bool_),
            sds((b,), jnp.int32), key_sds(), sds((b, p), jnp.int32),
            statics={"cfg": cfg, "chunk_steps": 8},
        )

    out.append(RecompileScenario(
        name="batcher.decode_chunk_int8", path=P_BATCHER,
        doc="int8 paged decode (quantized write + scale-fused read) "
            "stays ONE program across every resident depth",
        ladder=_GC4_LADDER,
        width_of=lambda n: s_cap,
        allowed_widths=(s_cap,),
        max_keys=1,
        trace=decode_int8_trace,
    ))

    # -- dispatch-ahead (overlapped) decode: the engine loop chains the
    # carry from one chunk's outputs straight into the next call, with
    # the per-row sampling + penalty-histogram kwargs engaged for the
    # whole span.  That steady-state program must be ONE compile key
    # across every resident depth — a second key would mean the chained
    # dispatch pays a trace on the engine thread mid-span, serializing
    # exactly the window the overlap exists to hide.
    def decode_overlap_trace(width: int) -> str:
        from distributed_llms_tpu.runtime import batcher as batcher_lib

        b = 4
        params = abstract_params(cfg)
        cache = abstract_cache(cfg, b, s_cap)
        return jaxpr_hash(
            lambda p, c, lt, rl, va, ac, bu, rng, tr, pr, kr, cnt, prr, frr:
                batcher_lib.decode_chunk(
                    p, cfg, c, lt, rl, va, ac, bu, rng, chunk_steps=8,
                    temp_row=tr, topp_row=pr, topk_row=kr, counts=cnt,
                    pres_row=prr, freq_row=frr),
            params, cache, sds((b,), jnp.int32), sds((b,), jnp.int32),
            sds((b, s_cap), jnp.bool_), sds((b,), jnp.bool_),
            sds((b,), jnp.int32), key_sds(),
            sds((b,), jnp.float32), sds((b,), jnp.float32),
            sds((b,), jnp.int32), sds((b, cfg.vocab_size), jnp.int32),
            sds((b,), jnp.float32), sds((b,), jnp.float32),
            statics={"cfg": cfg, "chunk_steps": 8},
        )

    out.append(RecompileScenario(
        name="batcher.decode_chunk_overlap", path=P_BATCHER,
        doc="dispatch-ahead decode (carry chained from the previous "
            "chunk, per-row sampling + penalties engaged) stays ONE "
            "program across every resident depth",
        ladder=_GC4_LADDER,
        width_of=lambda n: s_cap,
        allowed_widths=(s_cap,),
        max_keys=1,
        trace=decode_overlap_trace,
    ))

    # -- constrained decode: mixed constrained+free rows (the token-mask
    # stack + per-row automaton states + per-row sampling engaged, as
    # runtime/batcher._span_plan builds it) must still be ONE compiled
    # program across every resident depth — the mask is a traced gather,
    # the DFA advance a traced scatter-free lookup, and the state carry
    # chains device-resident through dispatch-ahead chunks.
    def decode_constrained_trace(width: int) -> str:
        from distributed_llms_tpu.runtime import batcher as batcher_lib

        b, n_states = 4, 32
        params = abstract_params(cfg)
        cache = abstract_cache(cfg, b, s_cap)
        return jaxpr_hash(
            lambda p, c, lt, rl, va, ac, bu, rng, tr, ms, ns, ds:
                batcher_lib.decode_chunk(
                    p, cfg, c, lt, rl, va, ac, bu, rng, chunk_steps=8,
                    temp_row=tr, mask_stack=ms, next_stack=ns,
                    dfa_state=ds),
            params, cache, sds((b,), jnp.int32), sds((b,), jnp.int32),
            sds((b, s_cap), jnp.bool_), sds((b,), jnp.bool_),
            sds((b,), jnp.int32), key_sds(),
            sds((b,), jnp.float32),
            sds((n_states, cfg.vocab_size), jnp.float32),
            sds((n_states, cfg.vocab_size), jnp.int32),
            sds((b,), jnp.int32),
            statics={"cfg": cfg, "chunk_steps": 8},
        )

    out.append(RecompileScenario(
        name="batcher.decode_chunk_constrained", path=P_BATCHER,
        doc="mixed constrained+free decode (token-mask stack, per-row "
            "DFA states, per-row sampling engaged) stays ONE program "
            "across every resident depth",
        ladder=_GC4_LADDER,
        width_of=lambda n: s_cap,
        allowed_widths=(s_cap,),
        max_keys=1,
        trace=decode_constrained_trace,
    ))

    # -- fused mixed step (schedule=mixed): the K-step decode scan AND
    # the head pending prefill's bite in ONE compiled program.  The
    # prefill leg's width is pinned to a single policy-sized bucket
    # (batcher._mixed_width), so the whole prefill-mix ladder — any bite
    # length, any live-row count, any resident depth (all traced values,
    # never shapes) — must land on EXACTLY one compile key: a second key
    # would mean a fused dispatch pays an XLA trace on the engine thread
    # mid-span, serializing exactly the stall the mixed schedule removes.
    def mixed_step_trace(width: int) -> str:
        from distributed_llms_tpu.runtime import batcher as batcher_lib

        b, pw = 4, 32  # pw: the policy's fixed prefill-leg bucket
        params = abstract_params(cfg)
        cache = abstract_cache(cfg, b, s_cap)
        row = abstract_cache(cfg, 1, s_cap)
        return jaxpr_hash(
            lambda p, c, lt, rl, va, ac, bu, rng, rk, rv, dn, pc, pl:
                batcher_lib.mixed_step(
                    p, cfg, cfg, c, lt, rl, va, ac, bu, rng, 8,
                    rk, rv, dn, pc, pl),
            params, cache, sds((b,), jnp.int32), sds((b,), jnp.int32),
            sds((b, s_cap), jnp.bool_), sds((b,), jnp.bool_),
            sds((b,), jnp.int32), key_sds(),
            row.k, row.v, sds((), jnp.int32),
            sds((pw,), jnp.int32), sds((), jnp.int32),
            statics={"cfg": cfg, "pcfg": cfg, "chunk_steps": 8},
        )

    out.append(RecompileScenario(
        name="batcher.mixed_step", path=P_BATCHER,
        doc="fused token-budget step (decode scan + prefill bite, "
            "schedule=mixed) stays ONE program across the whole "
            "prefill-mix ladder",
        ladder=_GC4_LADDER,
        width_of=lambda n: s_cap,
        allowed_widths=(s_cap,),
        max_keys=1,
        trace=mixed_step_trace,
    ))

    # -- paged speculative round (spec x paged tentpole): the draft scan,
    # the (k+1)-token paged verify window (scratch-tail page writes +
    # per-offset prefix reads), the rollback clamp, AND the adaptive
    # k_row downshift are ONE compiled program — depths, page tables,
    # per-row clamp values, and row mixes are all traced values, never
    # shapes.  A second key would mean a downshift (or a new resident
    # depth) pays an XLA trace on the engine thread mid-span — the
    # ladder of k_row values the scheduler emits must be compile-free.
    def spec_paged_trace(width: int) -> str:
        from distributed_llms_tpu.runtime import batcher as batcher_lib

        b, nb, blk, p = 4, 16, 16, 8
        params = abstract_params(cfg)
        pool = abstract_pool(cfg, nb, blk)
        draft = abstract_cache(cfg, b, s_cap)
        return jaxpr_hash(
            lambda prm, dprm, c, dc, lt, rl, va, ac, bu, tb, kr, prr, frr,
            cnt:
                batcher_lib.spec_chunk(
                    prm, cfg, dprm, cfg, c, dc, lt, rl, va, ac, bu, k=4,
                    tables=tb, k_row=kr, pres_row=prr, freq_row=frr,
                    counts=cnt),
            params, params, pool, draft, sds((b,), jnp.int32),
            sds((b,), jnp.int32), sds((b, s_cap), jnp.bool_),
            sds((b,), jnp.bool_), sds((b,), jnp.int32),
            sds((b, p), jnp.int32), sds((b,), jnp.int32),
            sds((b,), jnp.float32), sds((b,), jnp.float32),
            sds((b, cfg.vocab_size), jnp.int32),
            statics={"cfg": cfg, "draft_cfg": cfg, "k": 4},
        )

    out.append(RecompileScenario(
        name="batcher.spec_chunk_paged", path=P_BATCHER,
        doc="paged draft/verify round (page tables, adaptive k_row, "
            "penalties engaged) stays ONE program across the spec_k "
            "ladder, every resident depth, and every row mix",
        ladder=_GC4_LADDER,
        width_of=lambda n: s_cap,
        allowed_widths=(s_cap,),
        max_keys=1,
        trace=spec_paged_trace,
    ))

    # -- whole-batch generate: the engine pads T up the ladder under the
    # sequence budget; every padded width is one compile key.
    n_new, limit = 8, s_cap

    def generate_trace(width: int) -> str:
        from distributed_llms_tpu.runtime import generate as gen_lib

        params = abstract_params(cfg)
        return jaxpr_hash(
            lambda p, prompt, lens, rng: gen_lib.generate_tokens(
                p, cfg, prompt, lens, rng, max_new_tokens=n_new),
            params, sds((2, width), jnp.int32), sds((2,), jnp.int32),
            key_sds(),
            statics={"cfg": cfg, "max_new_tokens": n_new},
        )

    out.append(RecompileScenario(
        name="engine.generate_tokens", path=P_ENGINE,
        doc="whole-batch generate pads T up the ladder (budget-capped)",
        ladder=tuple(n for n in _GC4_LADDER if n <= limit - n_new),
        width_of=lambda n: shapes_lib.generate_pad_len(n, n_new, limit),
        allowed_widths=tuple(shapes_lib.bucket_ladder(limit - n_new)),
        max_keys=shapes_lib.bucket_count(limit - n_new),
        trace=generate_trace,
    ))
    return out


# ---------------------------------------------------------------------------
# GC5 — donation contracts
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DonationContract:
    name: str
    path: str
    doc: str
    build: Callable[[], tuple]   # -> (jitted_fn, [(argname, value), ...], kwargs)
    must_donate: tuple[str, ...]
    may_keep: tuple[str, ...] = ()   # argnames allowed large + non-donated
    static_args: tuple[str, ...] = ("cfg",)  # dropped from Lowered.args_info
    min_bytes: int = 128 * 1024      # "large" threshold for GC502


def donation_contracts() -> list[DonationContract]:
    cfg = preset("llama-tiny")
    out = []

    def build_admit():
        from distributed_llms_tpu.runtime import batcher as batcher_lib

        return (batcher_lib.admit_row, [
            ("params", abstract_params(cfg)), ("cfg", cfg),
            ("cache", abstract_cache(cfg, 4, 128)),
            ("slot", sds((), jnp.int32)), ("prompt", sds((16,), jnp.int32)),
            ("plen", sds((), jnp.int32)), ("rng", key_sds()),
        ], {})

    out.append(DonationContract(
        "batcher.admit_row", P_BATCHER,
        "admission splices in place: the shared KV cache is donated",
        build_admit, must_donate=("cache",), may_keep=("params",)))

    def build_decode():
        from distributed_llms_tpu.runtime import batcher as batcher_lib

        b = 4
        return (batcher_lib.decode_chunk, [
            ("params", abstract_params(cfg)), ("cfg", cfg),
            ("cache", abstract_cache(cfg, b, 128)),
            ("last_tok", sds((b,), jnp.int32)),
            ("real_lens", sds((b,), jnp.int32)),
            ("valid", sds((b, 128), jnp.bool_)),
            ("active", sds((b,), jnp.bool_)),
            ("budget", sds((b,), jnp.int32)), ("rng", key_sds()),
        ], {"chunk_steps": 8})

    out.append(DonationContract(
        "batcher.decode_chunk", P_BATCHER,
        "the decode carry (KV cache) never copies between chunks",
        build_decode, must_donate=("cache",), may_keep=("params",)))

    def build_admit_paged():
        from distributed_llms_tpu.runtime import batcher as batcher_lib

        return (batcher_lib.admit_row_paged, [
            ("params", abstract_params(cfg)), ("cfg", cfg),
            ("cache", abstract_pool(cfg, 32, 16)),
            ("page_list", sds((8,), jnp.int32)),
            ("prompt", sds((16,), jnp.int32)), ("plen", sds((), jnp.int32)),
            ("rng", key_sds()),
        ], {})

    out.append(DonationContract(
        "batcher.admit_row_paged", P_BATCHER,
        "paged admission scatters into a donated pool",
        build_admit_paged, must_donate=("cache",), may_keep=("params",)))

    def build_auto_paged():
        from distributed_llms_tpu.runtime import batcher as batcher_lib

        return (batcher_lib.admit_row_auto_paged, [
            ("params", abstract_params(cfg)), ("cfg", cfg),
            ("cache", abstract_pool(cfg, 32, 16)),
            ("read_list", sds((8,), jnp.int32)),
            ("write_list", sds((8,), jnp.int32)),
            ("prefix_len", sds((), jnp.int32)),
            ("chunk", sds((16,), jnp.int32)), ("clen", sds((), jnp.int32)),
            ("rng", key_sds()),
        ], {})

    out.append(DonationContract(
        "batcher.admit_row_auto_paged", P_BATCHER,
        "prefix-cache-hit admission gathers then scatters one donated pool",
        build_auto_paged, must_donate=("cache",), may_keep=("params",)))

    def build_chunk_step():
        from distributed_llms_tpu.runtime import batcher as batcher_lib

        l, kvh, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_
        row = sds((l, 1, 256, kvh, hd), jnp.float32)
        return (batcher_lib.prefill_chunk_step, [
            ("params", abstract_params(cfg)), ("cfg", cfg),
            ("row_k", row), ("row_v", row), ("done", sds((), jnp.int32)),
            ("chunk", sds((32,), jnp.int32)), ("clen", sds((), jnp.int32)),
        ], {})

    out.append(DonationContract(
        "batcher.prefill_chunk_step", P_BATCHER,
        "chunked prefill updates the transient row KV in place",
        build_chunk_step, must_donate=("row_k", "row_v"),
        may_keep=("params",)))

    def build_spec_chunk():
        from distributed_llms_tpu.runtime import batcher as batcher_lib

        b, s = 2, 128
        return (batcher_lib.spec_chunk, [
            ("params", abstract_params(cfg)), ("cfg", cfg),
            ("draft_params", abstract_params(cfg)), ("draft_cfg", cfg),
            ("cache", abstract_cache(cfg, b, s)),
            ("draft_cache", abstract_cache(cfg, b, s)),
            ("last_tok", sds((b,), jnp.int32)),
            ("real_lens", sds((b,), jnp.int32)),
            ("valid", sds((b, s), jnp.bool_)),
            ("active", sds((b,), jnp.bool_)),
            ("budget", sds((b,), jnp.int32)),
        ], {"k": 3})

    out.append(DonationContract(
        "batcher.spec_chunk", P_BATCHER,
        "speculative round donates BOTH target and draft caches",
        build_spec_chunk, must_donate=("cache", "draft_cache"),
        may_keep=("params", "draft_params"),
        static_args=("cfg", "draft_cfg")))

    def build_spec_chunk_paged():
        from distributed_llms_tpu.runtime import batcher as batcher_lib

        b, s, nb, blk, p = 2, 128, 16, 16, 8
        return (batcher_lib.spec_chunk, [
            ("params", abstract_params(cfg)), ("cfg", cfg),
            ("draft_params", abstract_params(cfg)), ("draft_cfg", cfg),
            ("cache", abstract_pool(cfg, nb, blk)),
            ("draft_cache", abstract_cache(cfg, b, s)),
            ("last_tok", sds((b,), jnp.int32)),
            ("real_lens", sds((b,), jnp.int32)),
            ("valid", sds((b, s), jnp.bool_)),
            ("active", sds((b,), jnp.bool_)),
            ("budget", sds((b,), jnp.int32)),
        ], {"k": 3, "tables": sds((b, p), jnp.int32),
            "k_row": sds((b,), jnp.int32)})

    out.append(DonationContract(
        "batcher.spec_chunk_paged", P_BATCHER,
        "paged speculative round donates the pool and the draft cache "
        "(tables/k_row ride as read-only inputs)",
        build_spec_chunk_paged, must_donate=("cache", "draft_cache"),
        may_keep=("params", "draft_params"),
        static_args=("cfg", "draft_cfg")))
    return out


# ---------------------------------------------------------------------------
# README table (--write-docs)
# ---------------------------------------------------------------------------

DOC_BEGIN = "<!-- graftcheck:contracts:begin -->"
DOC_END = "<!-- graftcheck:contracts:end -->"


def contracts_table() -> str:
    """Markdown table of every registered contract, grouped by family."""
    rows = ["| family | contract | pins |", "|---|---|---|"]
    for c in op_contracts():
        rows.append(f"| GC1 | `{c.name}` | {c.doc} |")
    presets = sorted({a.name.split("@")[0] for a in spec_audits()
                      if "[" not in a.name})
    meshes = ", ".join(label for label, _ in MESH_LADDER)
    rows.append(
        f"| GC2 | `parallel.specs.param_specs` | tree structure, axis "
        f"names, rank, divisibility over {len(presets)} presets x "
        f"({meshes}) + staged blocks |"
    )
    paged_meshes = ", ".join(label for label, _ in _MESH_PAGED_LADDER)
    rows.append(
        f"| GC2 | `models.kv_cache.pool_specs` | sharded page-pool "
        f"layout (KV heads over 'model'; int8 scales shard with their "
        f"pages) over {{kv16, kv8}} x ({paged_meshes}) |"
    )
    rows.append(
        f"| GC2 | `ops.decode_attn.spmd_operand_specs` | decode-attn "
        f"SPMD rule operand placement (ragged + paged, bf16 + int8) "
        f"over ({paged_meshes}) |"
    )
    for a in collective_audits():
        rows.append(f"| GC2 | `{a.name}` | {a.doc} |")
    for h in hot_contracts():
        rows.append(f"| GC3 | `{h.name}` | {h.doc} |")
    for s in recompile_scenarios():
        rows.append(
            f"| GC4 | `{s.name}` | {s.doc} (<= {s.max_keys} compile keys) |"
        )
    for d in donation_contracts():
        rows.append(f"| GC5 | `{d.name}` | {d.doc} |")
    return "\n".join(rows)
