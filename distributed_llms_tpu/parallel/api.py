"""ParallelModel: one object that places a model on a mesh and runs it.

This is the TPU-native successor of the reference's assign/distribute pair
(`assign_shards` round-robin at src/master/node.py:84-104 and
`distribute_shards` shipping pickled bytes over TCP at :106-115): assignment
becomes PartitionSpecs (specs.py + stages.py), distribution becomes
``jax.device_put`` onto the mesh, and execution composes

- data parallelism   : batch sharded over 'data' (GSPMD)
- tensor parallelism : heads/hidden sharded over 'model' (GSPMD collectives)
- pipeline           : blocks staged over 'pipe' (shard_map + ppermute)

behind a single ``forward`` with the same signature family as
``models.model.forward`` so the runtime decode loop plugs in unchanged.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.config import MeshConfig, ModelConfig
from ..models import model as model_lib
from ..models.kv_cache import KVCache
from . import pipeline as pipeline_lib
from . import specs as specs_lib

Params = Any


def staged_param_specs(cfg: ModelConfig, mesh: Mesh) -> Params:
    """Specs for a tree whose blocks have been reshaped [L,...] ->
    [pipe, L/pipe, ...]: prepend 'pipe' to block specs, drop it elsewhere."""
    base = specs_lib.param_specs(cfg, mesh)

    def retag(p: P) -> P:
        # base block specs lead with the layer axis ('pipe' or None); staged
        # trees get an explicit leading stage axis sharded over 'pipe'.
        rest = tuple(p)[1:] if len(p) else ()
        return P("pipe", None, *rest)

    out = dict(base)
    out["blocks"] = jax.tree.map(
        retag, base["blocks"], is_leaf=lambda x: isinstance(x, P)
    )
    return out


def _axis_sz(mesh: Mesh, name) -> int:
    """Size of a PartitionSpec entry (a name or tuple of names) on a mesh."""
    if name is None:
        return 1
    names = name if isinstance(name, tuple) else (name,)
    sz = 1
    for n in names:
        sz *= mesh.shape.get(n, 1)
    return sz


def quantized_layout(qt, spec: P, mesh: Mesh, path: str) -> tuple[P, P, int]:
    """How a QuantizedTensor (arrays or shapes) shards under the plain
    weight's PartitionSpec: (the spec for data, the spec for scale, the
    scale refinement factor).

    The leaf is stored as a matrix [*lead, N, K] (checkpoint/quantize.py;
    the expert stacks [*lead, K, N]): its flattened contracted axes shard as
    the weight's first contracted axis does, its flattened output axes as
    the first output axis (whole heads, for the attention weights; for int4
    the stored rows hold adjacent-row pairs, so a contiguous shard of
    packed rows unpacks to the same contiguous rows — exact).  scale
    [*lead, N/block, K] takes the same names as the weight it lies beside;
    when the spec shards N, scales are refined (each block's scale repeated
    k times = block size / k — numerically identical) until shard
    boundaries land on block boundaries.
    Un-shardable layouts replicate the leaf, loudly.
    """
    from ..core.observability import get_logger

    def replicate(reason: str) -> tuple[P, P, int]:
        get_logger("parallel").warning(
            "quantized leaf %s cannot shard under %s (%s); replicating",
            path, spec, reason,
        )
        return P(), P(), 1

    data_shape = tuple(qt.data.shape)
    n_lead = len(data_shape) - 2
    tail = qt.k_axes + qt.n_axes
    s = tuple(spec) + (None,) * (n_lead + tail - len(spec))  # trailing = replicated
    k_names = s[n_lead: n_lead + qt.k_axes]
    n_names = s[n_lead + qt.k_axes:]
    if any(k_names[1:]) or any(n_names[1:]):
        return replicate("an inner axis of a flattened group is sharded")
    experts = qt.block_axis == -2  # stored [.., K, N], scale [.., K/128, N]
    flat = (*s[:n_lead], *((k_names[0], n_names[0]) if experts
                           else (n_names[0], k_names[0])))
    # Divisibility of every sharded data axis (jax would raise; we want the
    # replicate fallback instead).
    for ax, name in enumerate(flat):
        if _axis_sz(mesh, name) > 1 and data_shape[ax] % _axis_sz(mesh, name):
            return replicate(f"data axis {ax} ({data_shape[ax]}) % shards")
    if experts:
        return P(*flat), P(*flat), 1
    if _axis_sz(mesh, flat[-1]) > 1 and qt.bits == 4 and qt.pack_axis == -1:
        return replicate("spec shards the int4 pack axis at the last dim")
    tp_n = _axis_sz(mesh, flat[-2])
    repeat = 1
    if tp_n > 1:
        dim = math.prod(qt.tail_shape[1])  # N, unpacked
        block = dim // qt.scale.shape[-2]
        per_shard = dim // tp_n
        if per_shard % block:
            # Refine: new block g divides both the old block and the shard
            # width, so each shard holds whole (finer) blocks.
            repeat = block // math.gcd(block, per_shard)
    return P(*flat), P(*flat), repeat


def _place_quantized(leaf, spec: P, mesh: Mesh, path: str):
    """Shard a QuantizedTensor under the plain weight's PartitionSpec
    (:func:`quantized_layout`)."""
    import dataclasses

    data_spec, scale_spec, repeat = quantized_layout(leaf, spec, mesh, path)
    scale = leaf.scale if repeat == 1 else jnp.repeat(leaf.scale, repeat, axis=-2)
    return dataclasses.replace(
        leaf,
        data=jax.device_put(leaf.data, NamedSharding(mesh, data_spec)),
        scale=jax.device_put(scale, NamedSharding(mesh, scale_spec)),
    )


def _place_tree(params: Params, specs: Params, mesh: Mesh) -> Params:
    """device_put a param tree onto the mesh, keeping QuantizedTensor leaves
    quantized-resident (sharded data+scale) instead of rehydrating."""
    from ..checkpoint.quantize import QuantizedTensor

    is_q = lambda x: isinstance(x, QuantizedTensor)  # noqa: E731
    spec_by_path = {
        jax.tree_util.keystr(kp): s
        for kp, s in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P)
        )[0]
    }

    def place(kp, leaf):
        path = jax.tree_util.keystr(kp)
        spec = spec_by_path[path]
        if is_q(leaf):
            return _place_quantized(leaf, spec, mesh, path)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(place, params, is_leaf=is_q)


@dataclass(frozen=True)
class ParallelModel:
    """Mesh-placed model.  Build with :func:`make_parallel_model`."""

    cfg: ModelConfig
    mesh: Mesh
    num_microbatches: int = 1
    kv_dtype: str | None = None  # KV-cache dtype override (default cfg.dtype)

    @property
    def num_stages(self) -> int:
        return self.mesh.shape.get("pipe", 1)

    @property
    def pipelined(self) -> bool:
        return self.num_stages > 1

    @property
    def seq_parallel(self) -> bool:
        return self.mesh.shape.get("seq", 1) > 1

    # -- placement ---------------------------------------------------------

    def shard_params(self, params: Params) -> Params:
        """Stage (if pipelined) and place params onto the mesh.

        QuantizedTensor leaves stay quantized-resident on the mesh (SURVEY §7
        hard part 6): data and scale shard under the plain weight's spec,
        with scale blocks refined where a shard boundary would split a block
        (refinement repeats scales to a finer — numerically identical —
        block size).  Leaves whose layout can't shard cleanly replicate,
        loudly, instead of rehydrating the whole tree.
        """
        if self.pipelined:
            params = dict(params)
            params["blocks"] = pipeline_lib.split_stages(params["blocks"], self.num_stages)
            specs = staged_param_specs(self.cfg, self.mesh)
        else:
            specs = specs_lib.param_specs(self.cfg, self.mesh)
        return _place_tree(params, specs, self.mesh)

    def init_cache(
        self, batch: int, max_len: int, prompt_len: int | None = None
    ) -> KVCache:
        cfg = self.cfg
        kvh, hd = cfg.num_kv_heads, cfg.head_dim_
        tp = self.mesh.shape.get("model", 1)
        kv_ax = "model" if kvh % max(tp, 1) == 0 else None
        if self.seq_parallel:
            # Two-region layout for long-context generation: the prompt's KV
            # sharded over 'seq' (each device writes + keeps its own block),
            # the decode region replicated (bounded by max_new_tokens).
            seq_ax = self.mesh.shape["seq"]
            if prompt_len is None:
                raise ValueError(
                    "sequence-parallel KV cache needs prompt_len (the region "
                    "split point); the session path does not support "
                    "seq-parallel decode"
                )
            if prompt_len % seq_ax:
                raise ValueError(
                    f"padded prompt length {prompt_len} not divisible by "
                    f"seq axis {seq_ax}"
                )
            dt = jnp.dtype(self.kv_dtype or cfg.dtype)
            l = cfg.num_layers

            def region(length, spec):
                return jax.lax.with_sharding_constraint(
                    jnp.zeros((l, batch, length, kvh, hd), dt),
                    NamedSharding(self.mesh, spec),
                )

            # k and v must be DISTINCT buffers: callers (runtime/batcher.py)
            # donate the cache, and donating one aliased buffer through two
            # tree leaves is an XLA Execute error.
            return KVCache(
                k=(region(prompt_len, P(None, "data", "seq", kv_ax, None)),
                   region(max_len - prompt_len, P(None, "data", None, kv_ax, None))),
                v=(region(prompt_len, P(None, "data", "seq", kv_ax, None)),
                   region(max_len - prompt_len, P(None, "data", None, kv_ax, None))),
            )
        if self.pipelined:
            p, lp = self.num_stages, cfg.num_layers // self.num_stages
            shape = (p, lp, batch, max_len, kvh, hd)
            spec = P("pipe", None, "data", None, kv_ax, None)
        else:
            shape = (cfg.num_layers, batch, max_len, kvh, hd)
            spec = P(None, "data", None, kv_ax, None)
        sharding = NamedSharding(self.mesh, spec)

        # with_sharding_constraint works both eagerly and under jit (the
        # decode loop allocates its cache inside generate_tokens' trace).
        # k and v are DISTINCT allocations: callers (runtime/batcher.py)
        # donate the cache, and two tree leaves aliasing one buffer is an
        # XLA "donate the same buffer twice" Execute error.
        def z():
            return jax.lax.with_sharding_constraint(
                jnp.zeros(shape, jnp.dtype(self.kv_dtype or cfg.dtype)), sharding
            )

        return KVCache(k=z(), v=z())

    # -- adapters for runtime.generate (hashable bound methods; frozen
    # dataclass => stable hash => jit cache hits across calls) --------------

    def _guard_windowed_decode(self) -> None:
        """Sliding-window mesh decode: the GSPMD and pipelined adapters
        thread the slot->position map the window mask needs for the
        right-padded generate layout (models.model._attention
        key_positions; pipeline_decode derives it per tick), so windowed
        models serve on data/tensor/pipe meshes.  Only the seq-parallel
        cached paths stay guarded: ring/Ulysses attention and the
        two-region seq cache are causal-only and do not carry a window
        bound — decoding there would silently attend past the window."""
        if self.cfg.model_window is not None and self.seq_parallel:
            raise ValueError(
                "sequence-parallel decode of sliding_window models is "
                "unsupported (ring/Ulysses attention is causal-only, no "
                "window bound); use a data/model/pipe mesh"
            )

    def as_forward_fn(self):
        self._guard_windowed_decode()
        return self._forward_adapter

    def as_make_cache(self):
        self._guard_windowed_decode()
        return self._make_cache_adapter

    def as_decode_fn(self):
        """Fused wavefront decode loop (pipeline.pipeline_decode) for
        runtime.generate: only meaningful when pipelined."""
        self._guard_windowed_decode()
        return self._decode_adapter if self.pipelined else None

    def _decode_adapter(
        self, params, tok0, prompt_lens, prompt_pad_len, cache, rng,
        max_new_tokens, temperature, top_k, top_p, eos_id, pad_id,
    ):
        toks, _, _ = pipeline_lib.pipeline_decode(
            self.mesh, _local_cfg(self.cfg), params, tok0, prompt_lens,
            prompt_pad_len, cache.k, cache.v, max_new_tokens,
            self.num_microbatches, rng,
            temperature=temperature, top_k=top_k, top_p=top_p,
            eos_id=eos_id, pad_id=pad_id,
        )
        return toks

    def _forward_adapter(
        self, params, cfg, tokens, positions=None, cache=None,
        cache_index=None, attn_mask=None, key_positions=None,
        kv_tables=None, logits_at=None,
    ):
        del cfg  # self.cfg is authoritative
        return self.forward(
            params, tokens, positions=positions, cache=cache,
            cache_index=cache_index, attn_mask=attn_mask,
            key_positions=key_positions, kv_tables=kv_tables,
            logits_at=logits_at,
        )

    def _make_cache_adapter(self, cfg, batch, max_len, prompt_len=None):
        del cfg
        return self.init_cache(batch, max_len, prompt_len=prompt_len)

    # -- execution ---------------------------------------------------------

    def _seq_forward(self, params, tokens, positions, remat):
        """Full forward under shard_map over {'seq'}: sequence axis sharded,
        global positions passed through so RoPE/causality stay correct;
        attention runs the ppermute ring (ops/ring.py) or, when the user set
        attn_impl='ulysses', the all-to-all head scatter (ops/ulysses.py);
        'data'/'model' axes remain GSPMD-auto inside the body."""
        cfg = _seq_cfg(self.cfg)
        b, t = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

        def body(params, tokens, positions):
            logits, _ = model_lib.forward(
                params, cfg, tokens, positions=positions, remat=remat
            )
            return logits

        return jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(P(), P(None, "seq"), P(None, "seq")),
            out_specs=P(None, "seq", None),
            axis_names={"seq"},
        )(params, tokens, positions)

    def _seq_prefill_cached(self, params, tokens, positions, cache, cache_index, remat):
        """Cached prefill under 'seq': tokens sharded over the sequence,
        each device writes its prefill-region KV block locally."""
        cfg = _seq_cfg(self.cfg)
        b, t = tokens.shape
        seq_ax = self.mesh.shape["seq"]
        if t % seq_ax:
            raise ValueError(
                f"prompt length {t} not divisible by seq axis {seq_ax} "
                "(the engine pads prompts to the mesh multiple)"
            )
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        (pk, dk), (pv, dv) = cache.k, cache.v

        def body(params, tokens, positions, pk, pv, dk, dv):
            logits, new_cache = model_lib.forward(
                params, cfg, tokens, positions=positions,
                cache=KVCache(k=(pk, dk), v=(pv, dv)),
                cache_index=jnp.int32(0), remat=remat,
            )
            (npk, ndk), (npv, ndv) = new_cache.k, new_cache.v
            return logits, npk, npv, ndk, ndv

        seq_kv = P(None, None, "seq", None, None)
        logits, npk, npv, ndk, ndv = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(P(), P(None, "seq"), P(None, "seq"), seq_kv, seq_kv, P(), P()),
            out_specs=(P(None, "seq", None), seq_kv, seq_kv, P(), P()),
            axis_names={"seq"},
        )(params, tokens, positions, pk, pv, dk, dv)
        return logits, KVCache(k=(npk, ndk), v=(npv, ndv))

    def _seq_decode_cached(self, params, tokens, positions, cache, cache_index, attn_mask, remat):
        """Single-token decode over the seq-sharded cache: partial softmax
        stats merge across 'seq' with one psum; the query is replicated."""
        cfg = _seq_cfg(self.cfg)
        (pk, dk), (pv, dv) = cache.k, cache.v
        t_pref = pk.shape[2]
        if attn_mask is None:
            raise ValueError(
                "seq-parallel cached decode needs the decode loop's explicit "
                "attention mask (runtime.generate supplies it)"
            )
        m = attn_mask[:, 0, 0, :]  # [B, S_total]
        m_pref, m_dec = m[:, :t_pref], m[:, t_pref:]

        def body(params, tokens, positions, pk, pv, dk, dv, m_pref, m_dec, ci):
            logits, new_cache = model_lib.forward(
                params, cfg, tokens, positions=positions,
                cache=KVCache(k=(pk, dk), v=(pv, dv)), cache_index=ci,
                attn_mask=(m_pref, m_dec), remat=remat,
            )
            (npk, ndk), (npv, ndv) = new_cache.k, new_cache.v
            return logits, npk, npv, ndk, ndv

        seq_kv = P(None, None, "seq", None, None)
        logits, npk, npv, ndk, ndv = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(P(), P(), P(), seq_kv, seq_kv, P(), P(),
                      P(None, "seq"), P(), P()),
            out_specs=(P(), seq_kv, seq_kv, P(), P()),
            axis_names={"seq"},
        )(params, tokens, positions, pk, pv, dk, dv, m_pref, m_dec, cache_index)
        return logits, KVCache(k=(npk, ndk), v=(npv, ndv))

    def forward(
        self,
        params: Params,
        tokens: jax.Array,
        positions: jax.Array | None = None,
        cache: KVCache | None = None,
        cache_index: jax.Array | None = None,
        attn_mask: jax.Array | None = None,
        remat: bool = False,
        return_aux: bool = False,
        key_positions: jax.Array | None = None,  # [B, S] slot->position map
        #   (sliding-window decode under the right-padded generate layout)
        kv_tables: jax.Array | None = None,  # [B, P] page table — the cache
        #   holds page POOLS sharded over 'model' on KV heads (mesh-native
        #   paged serving; GSPMD path only — the paged decode kernel runs
        #   per shard on its local heads)
        logits_at: jax.Array | None = None,  # [B]: models.model.forward's
        #   (GSPMD path only: what the mesh batcher's admissions run)
    ) -> tuple[jax.Array, KVCache | None] | tuple[jax.Array, KVCache | None, jax.Array]:
        """Same contract as models.model.forward, but mesh-parallel.
        ``return_aux`` (MoE load-balance loss) flows through on the
        GSPMD paths; the pipeline/seq shard_map schedules return aux=0 —
        train MoE with data/model/expert axes."""
        cfg = self.cfg
        if logits_at is not None and (self.pipelined or self.seq_parallel):
            raise NotImplementedError(
                "logits_at is the GSPMD paths': the pipelined and "
                "sequence-parallel schedules hand out every position")
        if kv_tables is not None and (self.pipelined or self.seq_parallel):
            raise NotImplementedError(
                "paged decode (kv_tables) runs on pure data/tensor-parallel "
                "meshes only — pipelined/seq-parallel schedules keep "
                "contiguous caches"
            )
        if self.seq_parallel and key_positions is not None:
            raise NotImplementedError(
                "sequence-parallel paths do not thread key_positions "
                "(ring/Ulysses are causal-only)"
            )
        if self.seq_parallel and cache is not None:
            # Long-context *generation* (SURVEY §5.7): prompt KV sharded over
            # 'seq' (two-region cache from init_cache); single-token decode
            # merges partial softmax stats with one psum instead of rotating
            # KV to meet one query.
            if tokens.shape[1] > 1:
                if attn_mask is not None:
                    # Loud, not silently-causal: the sharded prefill cannot
                    # honor an arbitrary mask (ring/Ulysses are causal-only).
                    raise NotImplementedError(
                        "sequence-parallel cached prefill supports causal "
                        "masking only; got an explicit attn_mask"
                    )
                out = self._seq_prefill_cached(
                    params, tokens, positions, cache, cache_index, remat
                )
            else:
                out = self._seq_decode_cached(
                    params, tokens, positions, cache, cache_index, attn_mask, remat
                )
            return (*out, jnp.float32(0.0)) if return_aux else out
        if (
            self.seq_parallel
            and cache is None
            and not self.pipelined
            and attn_mask is None
        ):
            # Long-context no-cache path: sequence sharded over 'seq', ring
            # attention rotates KV blocks over ICI (prefill/training; custom
            # masks fall through to the dense path — causal only).
            logits = self._seq_forward(params, tokens, positions, remat)
            return (logits, None, jnp.float32(0.0)) if return_aux else (logits, None)
        cfg = _local_cfg(cfg)
        if not self.pipelined:
            # GSPMD path: name the mesh for the trace, so the Pallas ops
            # (quantized contractions, decode attention) run per shard
            # under shard_map — a bare pallas_call has no SPMD
            # partitioning.  Their dense fallbacks are plain lax ops XLA
            # partitions itself.
            from ..ops import dispatch

            with dispatch.sharded(self.mesh):
                return model_lib.forward(
                    params, cfg, tokens, positions=positions, cache=cache,
                    cache_index=cache_index, remat=remat, attn_mask=attn_mask,
                    return_aux=return_aux, key_positions=key_positions,
                    kv_tables=kv_tables, logits_at=logits_at,
                )

        b, t = tokens.shape
        if positions is None:
            base = cache_index if cache_index is not None else 0
            positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32) + base, (b, t))
        x = model_lib.embed(params, cfg, tokens, positions)
        y, new_cache = pipeline_lib.pipeline_blocks(
            self.mesh, cfg, params["blocks"], x, positions,
            num_microbatches=self.num_microbatches,
            cache_k=cache.k if cache is not None else None,
            cache_v=cache.v if cache is not None else None,
            cache_index=cache_index, attn_mask=attn_mask, remat=remat,
            key_positions=key_positions,
        )
        logits = model_lib.unembed(params, cfg, y)
        new = None if cache is None else KVCache(k=new_cache[0], v=new_cache[1])
        return (logits, new, jnp.float32(0.0)) if return_aux else (logits, new)


def _seq_cfg(cfg: ModelConfig) -> ModelConfig:
    """Pick the sequence-parallel attention impl for the shard_map body:
    the user's 'ulysses' is kept, anything else becomes the ring."""
    import dataclasses

    if cfg.attn_impl == "ulysses":
        return cfg
    return dataclasses.replace(cfg, attn_impl="ring")


def _local_cfg(cfg: ModelConfig) -> ModelConfig:
    """Strip sequence-parallel impls for paths that run *outside* shard_map
    (decode-with-cache, pipeline stages): 'ring'/'ulysses' need a bound seq
    axis and would raise; they degrade to the dense dot path."""
    import dataclasses

    if cfg.attn_impl in ("ring", "ulysses"):
        return dataclasses.replace(cfg, attn_impl="dot")
    return cfg


def make_parallel_model(
    cfg: ModelConfig, mesh_cfg: MeshConfig, num_microbatches: int = 1,
    devices: list | None = None, kv_dtype: str | None = None,
) -> ParallelModel:
    from ..core.mesh import build_mesh

    mesh = build_mesh(mesh_cfg, devices)
    if mesh_cfg.pipe > 1 and cfg.num_layers % mesh_cfg.pipe:
        raise ValueError(
            f"num_layers {cfg.num_layers} not divisible by pipe {mesh_cfg.pipe}"
        )
    if mesh_cfg.pipe > 1 and mesh_cfg.seq > 1:
        # The ring path replaces the pipeline schedule; a seq axis alongside
        # pipe would silently hold inert replicas instead of sharding sequence.
        raise ValueError(
            f"seq={mesh_cfg.seq} cannot combine with pipe={mesh_cfg.pipe}: "
            "ring attention and the pipeline schedule are alternative "
            "shardings of the layer loop — use one, with 'data'/'model' axes"
        )
# NOTE: sliding_window models mesh-TRAIN fine (the cache=None forward
# windows in position space directly); only the mesh DECODE adapters are
# guarded — see ParallelModel._guard_windowed_decode.
    return ParallelModel(
        cfg=cfg, mesh=mesh, num_microbatches=num_microbatches, kv_dtype=kv_dtype
    )
