"""Partition-spec rules: which mesh axis shards which param/activation axis.

Successor of the reference's shard assignment (round-robin shard->worker,
src/master/node.py:84-104): "distribution" here is `jax.device_put` with a
`NamedSharding` — weights go host->HBM once and XLA inserts the collectives
(Megatron-style all-reduce for tensor parallelism) instead of tensors
transiting a master over TCP (SURVEY §2.4).

Conventions:
- stacked layer axis L    -> 'pipe'  (pipeline stages own layer blocks)
- attention head axis     -> 'model' (tensor parallelism; KV heads only when
                                      divisible — GQA with few KV heads
                                      replicates KV, shards Q)
- MLP hidden axis F       -> 'model'
- vocab axis              -> 'model' (Megatron-style sharded embed/unembed)
- batch axis              -> 'data'
- sequence axis           -> 'seq'   (ring attention path, ops/ring.py)
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.config import ModelConfig

Params = Any


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape.get(name, 1)


def param_specs(cfg: ModelConfig, mesh: Mesh) -> Params:
    """PartitionSpec pytree matching models.model param trees."""
    if cfg.family == "hybrid":
        # No sharding rule yet for the stacks by kind, the expert stacks
        # or the convolution state: every leaf replicated (the engine and
        # the batcher refuse a mesh for this family: models/kv_cache.py
        # refuse_unpaged_state; ROADMAP Reach).
        import jax

        from ..models.model import init_params

        shapes = jax.eval_shape(
            lambda: init_params(jax.random.key(0), cfg))
        return jax.tree.map(lambda x: P(*(None,) * x.ndim), shapes)
    tp = _axis_size(mesh, "model")
    # The stacked layer axis shards over 'pipe' only when it divides evenly;
    # an uneven split (e.g. 3 layers over pipe=2) would leave XLA padding a
    # ragged shard on every block leaf — replicate instead and let the
    # staged pipeline path (parallel.api) do its own stage packing.
    # tools.graftcheck GC2 pins this for every preset x mesh.
    pipe_sz = _axis_size(mesh, "pipe")
    pipe = "pipe" if pipe_sz > 1 and cfg.num_layers % pipe_sz == 0 else None
    # Shard head axes only when divisible (e.g. GQA KV heads may be < tp).
    q_ax = "model" if cfg.num_heads % max(tp, 1) == 0 else None
    kv_ax = "model" if cfg.num_kv_heads % max(tp, 1) == 0 else None
    vocab_ax = "model" if cfg.vocab_size % max(tp, 1) == 0 else None
    f_ax = "model" if cfg.intermediate_size % max(tp, 1) == 0 else None

    specs: Params = {
        "embed": {"wte": P(vocab_ax, None)},
        "final_norm": {"scale": P(None)},
    }
    attn = {
        "wq": P(pipe, None, q_ax, None),
        "wk": P(pipe, None, kv_ax, None),
        "wv": P(pipe, None, kv_ax, None),
        "wo": P(pipe, q_ax, None, None),
    }
    if cfg.qkv_bias or cfg.family in ("gpt2", "opt", "neox"):
        # q/k/v biases shard with their head axes (gpt2/opt/neox always
        # carry them; llama only in the Qwen2-style qkv_bias layout).
        attn.update(
            bq=P(pipe, q_ax, None), bk=P(pipe, kv_ax, None),
            bv=P(pipe, kv_ax, None),
        )
    if cfg.family in ("gpt2", "opt", "neox"):
        if cfg.family != "neox":  # neox is rotary — no position table
            specs["embed"]["wpe"] = P(None, None)
        specs["final_norm"]["bias"] = P(None)
        attn["bo"] = P(pipe, None)
        mlp = {
            "w_in": P(pipe, None, f_ax), "b_in": P(pipe, f_ax),
            "w_out": P(pipe, f_ax, None), "b_out": P(pipe, None),
        }
        norm = {"scale": P(pipe, None), "bias": P(pipe, None)}
    elif cfg.num_experts > 0:
        # MoE: expert-stacked weights shard over 'expert' (expert
        # parallelism); the hidden axis can still shard over 'model'.
        ep_size = _axis_size(mesh, "expert")
        ep = "expert" if ep_size > 1 and cfg.num_experts % ep_size == 0 else None
        mlp = {
            "router": P(pipe, None, None),
            "w_gate": P(pipe, ep, None, f_ax), "w_up": P(pipe, ep, None, f_ax),
            "w_down": P(pipe, ep, f_ax, None),
        }
        norm = {"scale": P(pipe, None)}
    else:
        mlp = {
            "w_gate": P(pipe, None, f_ax), "w_up": P(pipe, None, f_ax),
            "w_down": P(pipe, f_ax, None),
        }
        norm = {"scale": P(pipe, None)}
    specs["blocks"] = {"ln1": dict(norm), "ln2": dict(norm), "attn": attn, "mlp": mlp}
    if not cfg.tie_embeddings:
        specs["lm_head"] = {"w": P(None, vocab_ax)}
    return specs


def shard_params(params: Params, cfg: ModelConfig, mesh: Mesh) -> Params:
    """Place a param tree onto the mesh (host -> HBM once, no sockets)."""
    specs = param_specs(cfg, mesh)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )


def batch_spec() -> P:
    return P("data", None)
