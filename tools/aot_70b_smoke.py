#!/usr/bin/env python
"""AOT compile-only smoke for BASELINE config 5: Llama-3-70B serving shapes
on a 16-device pp x tp mesh (VERDICT r3 next-step 9 contingency).

No hardware (and no 280 GB of weights) needed: params/cache are abstract
``ShapeDtypeStruct``s carrying the real NamedShardings, and
``jax.jit(...).lower(...).compile()`` runs the full GSPMD partitioner +
XLA pipeline — proving the 70B shardings compose (pipeline shard_map,
GQA TP guards, int8-resident quantized leaves) and letting us check the
per-device weight-memory math, without allocating a single parameter.

Run standalone (spawns nothing): ``python tools/aot_70b_smoke.py [n_dev]``.
The test suite drives it via subprocess (tests/parallel/test_aot_70b.py)
because the fake-device count must be set before JAX backend init.
"""
from __future__ import annotations

import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_DEV = int(sys.argv[1]) if len(sys.argv) > 1 else 16

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={N_DEV}"
)
import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_llms_tpu.checkpoint import quantize as quant_lib
from distributed_llms_tpu.core.config import MeshConfig
from distributed_llms_tpu.models import model as model_lib
from distributed_llms_tpu.models.kv_cache import KVCache
from distributed_llms_tpu.models.presets import get_preset
from distributed_llms_tpu.parallel import api as api_lib, pipeline as pipeline_lib
from distributed_llms_tpu.parallel.api import make_parallel_model

HBM_PER_CHIP = 16e9  # v5e


def abstract_sharded(tree, specs, mesh):
    """ShapeDtypeStructs carrying the placement NamedShardings — the same
    path-keyed spec lookup as api._place_tree, minus the device_put."""
    is_q = lambda x: isinstance(x, quant_lib.QuantizedTensor)  # noqa: E731
    spec_by_path = {
        jax.tree_util.keystr(kp): s
        for kp, s in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P)
        )[0]
    }

    def place(kp, leaf):
        spec = spec_by_path[jax.tree_util.keystr(kp)]
        if is_q(leaf):
            # _place_quantized's layout, minus the device_put (no scale is
            # refined at the 70B dims).
            from distributed_llms_tpu.parallel.api import quantized_layout

            data, scale, _ = quantized_layout(leaf, spec, mesh, "")
            return dataclasses.replace(
                leaf,
                data=jax.ShapeDtypeStruct(
                    leaf.data.shape, leaf.data.dtype,
                    sharding=NamedSharding(mesh, data),
                ),
                scale=jax.ShapeDtypeStruct(
                    leaf.scale.shape, leaf.scale.dtype,
                    sharding=NamedSharding(mesh, scale),
                ),
            )
        return jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec)
        )

    return jax.tree_util.tree_map_with_path(place, tree, is_leaf=is_q)


def leaf_bytes_per_device(tree, mesh) -> float:
    """Analytic per-device bytes of a sharded abstract tree."""
    total = 0.0
    for leaf in jax.tree.leaves(tree):
        shards = 1
        spec = getattr(leaf.sharding, "spec", None)
        if spec is not None:
            for ax in spec:
                if ax is None:
                    continue
                for name in (ax if isinstance(ax, tuple) else (ax,)):
                    shards *= mesh.shape.get(name, 1)
        total += leaf.size * leaf.dtype.itemsize / shards
    return total


def main() -> int:
    t0 = time.perf_counter()
    assert jax.default_backend() == "cpu", "refusing to smoke-compile on HW"
    # f32 elementwise math on the fake-CPU mesh (the dryrun's bf16
    # AllReducePromotion crash is a CPU-only XLA pass issue); weights are
    # int8-resident so the per-device memory math is the serving one.
    cfg = get_preset("llama-3-70b", dtype="float32")
    pipe, tp = 4, N_DEV // 4
    mesh_cfg = MeshConfig(pipe=pipe, model=tp)
    pm = make_parallel_model(cfg, mesh_cfg, num_microbatches=4)
    mesh = pm.mesh
    print(f"mesh: pipe={pipe} x model={tp} ({N_DEV} fake devices)")

    # Abstract int8-resident staged params: eval_shape runs init + quantize +
    # staging symbolically — zero bytes allocated.
    def init_staged(key):
        p = model_lib.init_params(key, cfg)
        p["blocks"] = quant_lib.quantize_tree(p["blocks"], bits=8)
        p["blocks"] = pipeline_lib.split_stages(p["blocks"], pipe)
        return p

    abs_params = jax.eval_shape(init_staged, jax.random.key(0))
    specs = api_lib.staged_param_specs(cfg, mesh)
    abs_params = abstract_sharded(abs_params, specs, mesh)
    w_bytes = leaf_bytes_per_device(abs_params, mesh)
    print(f"per-device weight bytes: {w_bytes / 1e9:.2f} GB "
          f"(budget {HBM_PER_CHIP / 1e9:.0f} GB)")
    assert w_bytes < HBM_PER_CHIP, "70B int8 weights do not fit the mesh"

    # Abstract KV cache with the pipeline placement (batch 4, 2048 slots).
    b, s = 4, 2048
    kvh, hd, l = cfg.num_kv_heads, cfg.head_dim_, cfg.num_layers
    kv_ax = "model" if kvh % tp == 0 else None
    cache_spec = P("pipe", None, None, None, kv_ax, None)
    cache_leaf = jax.ShapeDtypeStruct(
        (pipe, l // pipe, b, s, kvh, hd), jnp.dtype(cfg.dtype),
        sharding=NamedSharding(mesh, cache_spec),
    )
    abs_cache = KVCache(k=cache_leaf, v=cache_leaf)
    kv_bytes = leaf_bytes_per_device(abs_cache, mesh)
    print(f"per-device KV bytes (b={b}, s={s}): {kv_bytes / 1e9:.2f} GB")
    assert w_bytes + kv_bytes < HBM_PER_CHIP, "weights + KV exceed HBM"

    # 1) Prefill step (T=128 chunk) through the pipeline forward.
    def prefill(params, tokens, cache):
        return pm.forward(params, tokens, cache=cache,
                          cache_index=jnp.int32(0))

    toks = jax.ShapeDtypeStruct((b, 128), jnp.int32,
                                sharding=NamedSharding(mesh, P()))
    lowered = jax.jit(prefill).lower(abs_params, toks, abs_cache)
    compiled = lowered.compile()
    print(f"prefill compile OK [{time.perf_counter() - t0:.1f}s]")
    mem = compiled.memory_analysis()
    if mem is not None:
        print(f"  xla memory analysis: args "
              f"{getattr(mem, 'argument_size_in_bytes', 0) / 1e9:.2f} GB, "
              f"temps {getattr(mem, 'temp_size_in_bytes', 0) / 1e9:.2f} GB")

    # 2) One decode step (T=1, mid-cache write).
    def decode(params, tokens, cache):
        return pm.forward(params, tokens, cache=cache,
                          cache_index=jnp.int32(128))

    tok1 = jax.ShapeDtypeStruct((b, 1), jnp.int32,
                                sharding=NamedSharding(mesh, P()))
    jax.jit(decode).lower(abs_params, tok1, abs_cache).compile()
    print(f"decode compile OK [{time.perf_counter() - t0:.1f}s]")

    print(f"AOT_70B_SMOKE OK: llama-3-70b int8-resident pp{pipe} x tp{tp}, "
          f"{w_bytes / 1e9:.2f} GB weights + {kv_bytes / 1e9:.2f} GB KV "
          f"per chip [{time.perf_counter() - t0:.1f}s]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
