#!/usr/bin/env python
"""Compile the paged serving programs for one TPU v5e without a chip and
say what they do to the KV page pool and to the quantized weights.

``decode_chunk`` and the paged admission programs are compiled ahead of
time on the compile-only TPU client (abstract int8 weights, the served
shapes), and the report gives XLA's memory analysis next to every
instruction of the optimised HLO that produces an array shaped like one
layer of the pool or like the whole stack.  The pool must stay where it
lies: the only instructions allowed on that list are the in-place
scatters of a step's keys and values (tests/runtime/test_aot_pool.py
holds the decode program to it).  Beside it: every instruction shaped like
a layer's expert stack (``expert_shaped``) and like one layer of a
quantized weight or of its scales (``weight_shaped``); both lists are
empty when the kernels read the stacks where they lie.  And, for a model
whose windowed layers keep a ring a row beside the pool, every instruction
shaped like the rings (``ring_shaped``): the in-place writes alone.

    python tools/aot_decode.py qwen2-7b --slots 16 --max-len 4096 --pages 512
    python tools/aot_decode.py pythia-6.9b --slots 8 --max-len 2048 --pages 96 \
        --programs decode_chunk,admit_row_paged --hlo-dir /tmp/hlo
    (cd _chip/parent && python tools/aot_decode.py ...) and cmp the .noloc files
    python tools/aot_decode.py ax-k1-ep16 --slots 64 --pages 2176 --prompt-len 2048
    python tools/aot_decode.py k-exaone-ep8 --slots 64 --max-len 8192 \
        --pages 3712 --prompt-len 8192
    python tools/aot_decode.py brumby-pp4 --slots 16 --max-len 32768 \
        --pages 0 --prompt-len 16384

``--pages 0`` compiles the programs of a server WITHOUT a pool (a model of
retention layers, whose rows hold a float32 state and no key):
``decode_chunk`` over the contiguous cache and ``admit_row``, with every
instruction shaped like the slots' states (``state_shaped``): the decode
kernel's update where the stack lies and the admission's write of one row
into its slot, and nothing else; and with what XLA makes for the decode
kernel (``ret_fed``): its small operands, and no relayout of a layer's
products.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

PROGRAMS = ("decode_chunk", "admit_row_paged", "admit_row_auto_paged",
            "finish_chunked_admission_paged")


def v5e_devices(n: int = 1) -> list:
    """``n`` compile-only ``TPU v5 lite`` devices (raises where libtpu
    cannot describe the topology)."""
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    return list(topo.devices)[:n]


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _abstract_on_mesh(tree, specs, mesh):
    """``tree`` as ShapeDtypeStructs placed by ``specs`` (one
    PartitionSpec a weight): a quantized leaf's data and scale take what
    parallel.api.quantized_layout makes of the weight's spec (they divide
    at the served widths, so no scale is refined)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_llms_tpu.checkpoint.quantize import QuantizedTensor
    from distributed_llms_tpu.parallel.api import quantized_layout

    def sds(x, spec):
        full = tuple(spec) + (None,) * (x.ndim - len(tuple(spec)))
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, P(*full)))

    def place(leaf, spec):
        if not isinstance(leaf, QuantizedTensor):
            return sds(leaf, spec)
        data, scale, repeat = quantized_layout(leaf, spec, mesh, "")
        assert repeat == 1, (leaf, spec)
        return dataclasses.replace(
            leaf, data=sds(leaf.data, data), scale=sds(leaf.scale, scale))

    return jax.tree.map(
        place, tree, specs, is_leaf=lambda x: isinstance(x, QuantizedTensor)
    )


def lower_program(
    program: str, cfg, *, slots: int, max_len: int, pages: int,
    page_size: int = 64, chunk_steps: int = 8, kv_bits: int = 16,
    prompt_len: int = 512, mesh_model: int = 1,
):
    """``jax.stages.Lowered`` of one paged serving program at the given
    shapes (weights int8, as the benchmark serves), for one v5e device or,
    with ``mesh_model`` > 1, for that many under ``mesh.model``."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from distributed_llms_tpu.models import kv_cache, model as model_lib
    from distributed_llms_tpu.runtime import batcher as batcher_lib

    devices = v5e_devices(mesh_model)
    key_shape = jax.ShapeDtypeStruct((2,), jnp.uint32)
    if pages:
        pool = jax.eval_shape(lambda: kv_cache.make_pool(
            cfg, pages, page_size, kv_bits=kv_bits, slots=slots))
    else:  # no pool: the contiguous cache, a model of retention layers'
        pool = jax.eval_shape(
            lambda: kv_cache.init_cache(cfg, slots, max_len))
    if mesh_model > 1:
        from distributed_llms_tpu.core.config import MeshConfig
        from distributed_llms_tpu.parallel import specs as specs_lib
        from distributed_llms_tpu.parallel.api import make_parallel_model

        pm = make_parallel_model(
            cfg, MeshConfig(model=mesh_model), devices=devices)
        sh = NamedSharding(pm.mesh, P())
        params = _abstract_on_mesh(
            jax.eval_shape(lambda k: model_lib.init_params_quantized(
                k, cfg, 8, mesh=pm.mesh), key_shape),
            specs_lib.param_specs(cfg, pm.mesh), pm.mesh)
        cache = _abstract_on_mesh(
            pool, kv_cache.pool_specs(cfg, pm.mesh, pool), pm.mesh)
        on_mesh = {"pm": pm}
    else:
        sh = SingleDeviceSharding(devices[0])
        params = _abstract(jax.eval_shape(
            lambda k: model_lib.init_params_quantized(k, cfg, 8), key_shape),
            sh)
        cache = _abstract(pool, sh)
        on_mesh = {}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sh)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    p = max_len // page_size
    if not pages and program == "decode_chunk":
        return batcher_lib.decode_chunk.lower(
            params, cfg, cache, arr((slots,)), arr((slots,)),
            arr((slots, max_len), jnp.bool_), arr((slots,), jnp.bool_),
            arr((slots,)), key, chunk_steps,
        )
    if not pages and program == "admit_row":
        return batcher_lib.admit_row.lower(
            params, cfg, cache, arr(()), arr((prompt_len,)), arr(()), key)
    if program == "decode_chunk":
        cfg_decode = dataclasses.replace(cfg, ragged_decode=True)
        return batcher_lib.decode_chunk.lower(
            params, cfg_decode, cache, arr((slots,)), arr((slots,)),
            arr((slots, 1), jnp.bool_), arr((slots,), jnp.bool_),
            arr((slots,)), key, chunk_steps, tables=arr((slots, p)),
            **on_mesh,
        )
    if program == "admit_row_paged":
        # A hybrid model's admission is told the batch slot its
        # convolution state goes to.
        slot = {"slot": arr(())} if cfg.family == "hybrid" else {}
        return batcher_lib.admit_row_paged.lower(
            params, cfg, cache, arr((p,)), arr((prompt_len,)), arr(()), key,
            **on_mesh, **slot,
        )
    if program == "admit_row_auto_paged":
        return batcher_lib.admit_row_auto_paged.lower(
            params, cfg, cache, arr((p,)), arr((p,)), arr(()),
            arr((prompt_len,)), arr(()), key, **on_mesh,
        )
    if program == "finish_chunked_admission_paged":
        dt = kv_cache.row_dtype(cache)
        row = arr((cfg.num_layers, 1, max_len, cfg.num_kv_heads,
                   cfg.head_dim_), dt)
        return batcher_lib.finish_chunked_admission_paged.lower(
            cache, arr((p,)), row, row,
            arr((1, cfg.vocab_size), jnp.float32), key, **on_mesh,
        )
    raise ValueError(f"unknown program {program!r}; one of {PROGRAMS}")


_INSTR = re.compile(
    r"^\s*(ROOT\s+)?(%?[\w.\-]+)\s*=\s*(\([^=]*?\)|\S+)\s+([\w\-]+)\("
)
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%?[\w.\-]+)\s+\(.*\{\s*$")
_CALLS = re.compile(r"calls=(%?[\w.\-]+)")
_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition|branch_computations)=\{?([^,}\s]+(?:,\s*%?[\w.\-]+)*)\}?")
_BODY = re.compile(r"body=(%?[\w.\-]+)")


def pool_shaped(hlo_text: str, cfg, pages: int, page_size: int,
                shards: int = 1) -> list:
    """(opcode, name, result type) of every instruction of the optimised
    HLO whose result holds an array shaped like one layer of the pool,
    like the whole stack, or like either without its head-dim axis (the
    int8 pool's scales).  A fusion is reported with the opcode of its
    root, as ``fusion:scatter``.  Parameters, tuples and their plumbing
    are not instructions that move data and are left out.  ``shards`` is
    the size of ``mesh.model``: a device's program holds that share of the
    KV heads."""
    from distributed_llms_tpu.models.kv_cache import pages_are_private
    from distributed_llms_tpu.ops.decode_attn import pool_head_shape

    if cfg.kv_lora_rank:  # latent pages: one leaf, no head axis
        layer = f"{pages},{page_size},{cfg.latent_width}"
        return shaped_like(
            hlo_text, [f"[{layer}]", f"[{len(cfg.attn_layers)},{layer}]"])
    kvh, hd = pool_head_shape(cfg.num_kv_heads // shards, cfg.head_dim_,
                              fold_narrow=pages_are_private(cfg))
    layer = f"{pages},{page_size},{kvh},{hd}"
    stack = f"{len(cfg.attn_layers)},{layer}"
    shapes = [f"[{s}]" for s in (layer, stack)]
    shapes += [f"[{s.rsplit(',', 1)[0]}]" for s in (layer, stack)]
    if cfg.num_kv_heads == shards:  # one head a device: the axis may go
        shapes += [s.replace(",1,", ",") for s in shapes[:2]]
    return shaped_like(hlo_text, shapes)


def ring_shaped(hlo_text: str, cfg, slots: int) -> list:
    """Every instruction whose result is shaped like the windowed layers'
    rings, one layer's or the stack of all ([swa layers, slots, window,
    KVH, HD]): like the pool they are the scans' carry, so only the
    scatter of a step's keys and values (decode) or the write of a row's
    ring into its slot (admission) may be on the list.  Empty for a model
    without windowed layers."""
    if not cfg.swa_layers:
        return []
    layer = (f"{slots},{cfg.sliding_window},{cfg.num_kv_heads},"
             f"{cfg.head_dim_}")
    return shaped_like(
        hlo_text, [f"[{layer}]", f"[{len(cfg.swa_layers)},{layer}]"])


def state_shaped(hlo_text: str, cfg, slots: int) -> list:
    """Every instruction whose result is shaped like the retention layers'
    states: one row's in one layer, a layer's slots, or the stack of all
    ([ret layers, slots, KVH, 65, 128, 128]).  The stack is the decode
    scans' carry: only the kernel that updates it where it lies
    (``custom-call``) and an admission's write of one row into its slot
    may be on the list.  The same for a model of Mamba-2 layers, whose
    states ([ssm layers, slots, R, N, 128]) lie beside a pool, and of gated
    delta-rule layers ([gdn layers, slots, HV, dk, dv]).  Empty for a model
    without any."""
    if cfg.gdn_layers:
        from distributed_llms_tpu.ops.gdn import state_shape

        layers = len(cfg.gdn_layers)
        row = ",".join(str(n) for n in state_shape(
            cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim))
    elif cfg.ssm_layers:
        from distributed_llms_tpu.ops.ssm import state_shape

        layers = len(cfg.ssm_layers)
        row = ",".join(str(n) for n in state_shape(
            cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state))
    elif cfg.ret_layers:
        from distributed_llms_tpu.ops.retention import state_shapes

        layers = len(cfg.ret_layers)
        row = ",".join(str(n) for n in state_shapes(cfg.num_kv_heads)[0])
    else:
        return []
    return shaped_like(hlo_text, [
        f"[{row}]", f"[1,{row}]", f"[{slots},{row}]",
        f"[{layers},{slots},{row}]", f"[{layers},1,{row}]"])


_ARRAY = re.compile(r"\b(pred|[a-z]+(\d+)\w*)\[([\d,]*)\]")


def type_bytes(result_type: str) -> int:
    """Bytes of an instruction's result type as the HLO prints it (the
    arrays of a tuple summed)."""
    return sum(
        math.prod(int(d) for d in dims.split(",") if d)
        * max(int(bits or 8) // 8, 1)
        for _, bits, dims in _ARRAY.findall(result_type))


def ret_fed(hlo_text: str, cfg, slots: int) -> list:
    """(opcode, name, result type) of what XLA makes for the retention
    layers' decode kernel: every operand of ``retention_decode`` but the
    stack of states, and every ``copy`` or ``gather`` (inside fusions too)
    of as many bytes as a layer's products of the keys, float32 [slots,
    KVH, 65, 128], in a decode step.  The kernel is given q, k, v and the
    gate as rows and rotates the products out of them itself (PR 53), so
    the operands are small and no such relayout is left.  Empty for a model
    without retention layers."""
    if not cfg.ret_layers:
        return []
    from distributed_llms_tpu.ops.retention import DIAGS, HD, state_shapes

    row = ",".join(str(n) for n in state_shapes(cfg.num_kv_heads)[0])
    products = slots * cfg.num_kv_heads * DIAGS * HD * 4
    instrs = [(m.group(4), m.group(2), m.group(3), m) for m in map(
        _INSTR.match, hlo_text.splitlines()) if m]
    by_name = {name: (opcode, name, kind) for opcode, name, kind, _ in instrs}
    found = []
    for opcode, name, kind, m in instrs:
        if opcode == "custom-call" and "retention_decode" in name:
            operands = m.string[m.end():m.string.index(")", m.end())]
            found += [by_name[a] for a in re.findall(r"%[\w.\-]+", operands)
                      if f",{row}]" not in by_name[a][2]]
        elif opcode in ("copy", "gather") and type_bytes(kind) >= products:
            found.append((opcode, name, kind))
    return found


def expert_shaped(hlo_text: str, cfg) -> list:
    """Every instruction whose result is shaped like a layer's expert
    stack or like one expert's weights, at any dtype: an expert stack
    dequantized or copied whole would be on this list.  Empty for a model
    without experts."""
    if not cfg.num_experts or cfg.moe_capacity:
        return []
    d, f, e = cfg.hidden_size, cfg.expert_size, cfg.held_experts
    up = 2 * f
    if cfg.moe_latent_size:  # two matrices an expert, on the latent
        d, up = cfg.moe_latent_size, f
    shapes = []
    for one in (f"{d},{up}", f"{f},{d}"):
        shapes += [f"[{e},{one}]",
                   f"[{cfg.ffn_kinds.count('moe')},{e},{one}]"]
    # (one expert's [D, 2F] too; its [F, D] is left out: the dense FFN's
    # w_down, prefetched a quarter at a time, has that shape in LFM2)
    return shaped_like(hlo_text, shapes + [f"[{d},{up}]"])


_MOVES = {"copy", "copy-start", "copy-done", "slice", "slice-start",
          "slice-done", "dynamic-slice", "transpose", "custom-call",
          "bitcast", "concatenate"}


def weight_shapes(cfg, shards: int = 1) -> list:
    """The result types :func:`weight_shaped` looks for: an int8 array
    shaped like one layer of a quantized block weight's matrix, a float32
    one shaped like one layer of its scales in either order of their axes,
    or the whole stack of either.  ``shards`` is the size of
    ``mesh.model``: a device's program holds that share of a weight's rows
    or of its columns.  The expert stacks are :func:`expert_shaped`'s."""
    from distributed_llms_tpu.checkpoint.quantize import QuantizedTensor
    from distributed_llms_tpu.models import model as model_lib

    params = jax.eval_shape(
        lambda k: model_lib.init_params_quantized(k, cfg, 8),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    leaves = [q for q in jax.tree.leaves(
        params["blocks"], is_leaf=lambda x: isinstance(x, QuantizedTensor))
        if isinstance(q, QuantizedTensor) and q.block_axis == -1
        and q.data.ndim == 3]
    shapes = set()
    for q in leaves:
        layers, n, k = q.data.shape  # stored [L, N, K], K on the lanes
        nb = q.scale.shape[1]
        for kd, nd in {(1, 1), (shards, 1), (1, shards)}:
            if k % kd or nb % nd:
                continue
            # as it lies and turned: a [K, N] temporary would be the copy
            # PR 29 removed, back again
            for w in (f"{n // nd},{k // kd}", f"{k // kd},{n // nd}"):
                shapes |= {f"s8[{w}]", f"s8[{layers},{w}]"}
            if nb // nd > 1:  # one block a row is a vector like any other
                sc = (f"{nb // nd},{k // kd}", f"{k // kd},{nb // nd}")
                shapes |= {f"f32[{x}]" for x in sc}
                shapes |= {f"f32[{layers},{x}]" for x in sc}
    return sorted(shapes)


def weight_shaped(hlo_text: str, shapes: list, in_loops: bool | None = True,
                  prefetched: bool = False) -> list:
    """Every instruction inside the program's loops (the layer scan; in
    ``decode_chunk`` the step scan around it) whose result has one of
    :func:`weight_shapes`: a copy or a slice of a layer's int8 weights,
    or of its scales (a float32 array may be activations of the same
    shape, lfm2: 16 rows of the dense FFN's 7168, so for the scales only
    what moves data counts).  The kernel of ops/quant_matmul.py reads a
    layer's tiles out of the stacked leaves where they lie, so the list is
    empty when no call site slices a weight and nothing re-lays out a
    scale.  ``in_loops=False`` lists what runs ONCE a program instead.
    ``prefetched`` lists, in place of all that, XLA's own asynchronous
    copies of a stack (or of a quarter of one) into fast memory (``S(1)``
    in the result's layout): the compiler's choice for an operand of a
    custom call, made or not by its budget of fast memory, and no slice a
    call site cut."""
    found = [e for e in shaped_like(hlo_text, shapes, in_loops)
             if "s8[" in e[2] or e[0].split(":")[-1] in _MOVES]
    # (the -done of a copy that leaves fast memory names only where it lands)
    fast = {e[1].replace("-start", "-done") for e in found if "S(1)" in e[2]}
    return [e for e in found
            if ("S(1)" in e[2] or e[1] in fast) == prefetched]


def _loop_computations(lines: list) -> set:
    """Names of the computations that run inside a ``while`` loop: the
    loops' bodies and everything they call."""
    callees, bodies, computation = {}, set(), None
    for line in lines:
        c = _COMPUTATION.match(line)
        if c:
            computation = c.group(1)
        names = _CALLED.findall(line)
        callees.setdefault(computation, set()).update(
            n for group in names for n in re.findall(r"%?[\w.\-]+", group))
        bodies.update(_BODY.findall(line))
    inside, todo = set(), list(bodies)
    while todo:
        name = todo.pop()
        if name not in inside:
            inside.add(name)
            todo.extend(callees.get(name, ()))
    return inside


def shaped_like(hlo_text: str, shapes: list, in_loops: bool | None = None
                ) -> list:
    """(opcode, name, result type) of every instruction of the optimised
    HLO whose result type holds one of ``shapes`` (``"[a,b,c]"``).
    ``in_loops`` keeps only the instructions that run inside a ``while``
    loop, a layer scan or a step scan (True), or only those that run once
    a program (False)."""
    skip = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
            "conditional", "call", "opt-barrier"}
    roots, computation = {}, None
    lines = hlo_text.splitlines()
    for line in lines:
        c = _COMPUTATION.match(line)
        if c:
            computation = c.group(1)
        m = _INSTR.match(line)
        if m and m.group(1):
            roots[computation] = m.group(4)
    loops = _loop_computations(lines) if in_loops is not None else set()
    found = []
    for line in lines:
        c = _COMPUTATION.match(line)
        if c:
            computation = c.group(1)
        m = _INSTR.match(line)
        if not m or m.group(4) in skip:
            continue
        if in_loops is not None and (computation in loops) != in_loops:
            continue
        if any(s in m.group(3) for s in shapes):
            opcode = m.group(4)
            calls = _CALLS.search(line)
            if opcode == "fusion" and calls:
                opcode = f"fusion:{roots.get(calls.group(1), '?')}"
            found.append((opcode, m.group(2), m.group(3)))
    return found


def score_shaped(hlo_text: str, prompt_len: int, max_len: int) -> list:
    """(opcode, name, result type) of every instruction whose result is a
    float32 array ``[.., prompt_len, max_len]`` of three or more
    dimensions: a bucket's queries scored against every slot of the row
    cache, a head at a time.  Since PR 35 a fresh row attends among its
    own tokens and ``admit_row_paged`` holds none.  Nothing to tell where
    the two lengths are equal."""
    if prompt_len == max_len:
        return []
    tail = re.compile(rf"f32\[(\d+,)+{prompt_len},{max_len}\]")
    return [e for e in shaped_like(hlo_text, [f"{prompt_len},{max_len}]"])
            if tail.search(e[2])]


def in_place_scatter(entry: tuple) -> bool:
    """Whether a :func:`pool_shaped` entry is a scatter (bare or the root
    of a fusion): the one update the pool takes where it lies."""
    return entry[0] in ("scatter", "fusion:scatter")


_KERNEL_BODY = re.compile(r'("custom_call_config":\{"body":")([A-Za-z0-9+/=]+)"')


def without_locations(hlo_text: str) -> str:
    """The optimised HLO with everything that names a file, a function or a
    line taken out, so that the programs of two checkouts (a parent under
    ``_chip/``) can be compared with ``cmp``: the header's tables, every
    instruction's ``metadata``, and each Mosaic kernel's body, which is its
    MLIR serialized WITH locations, replaced by the hash of its text
    printed without them."""
    import base64
    import hashlib

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    ctx = jax_mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True  # the serialized `stable_mosaic`
    bodies: dict = {}

    def body(m):
        if m.group(2) not in bodies:
            with ctx:
                text = ir.Module.parse(base64.b64decode(m.group(2))
                                       ).operation.get_asm(enable_debug_info=False)
            bodies[m.group(2)] = hashlib.sha256(text.encode()).hexdigest()
        return f'{m.group(1)}mlir-sha256:{bodies[m.group(2)]}"'

    text = re.sub(r"(?ms)^(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n.*?\n\n", "", hlo_text)
    text = re.sub(r", metadata=\{[^{}]*\}", "", text)
    return _KERNEL_BODY.sub(body, text)


def analyse(program: str, cfg, **shape_kw) -> dict:
    compiled = lower_program(program, cfg, **shape_kw).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    shapes = weight_shapes(cfg, shape_kw.get("mesh_model", 1))
    found = pool_shaped(text, cfg, shape_kw["pages"],
                        shape_kw.get("page_size", 64),
                        shape_kw.get("mesh_model", 1)
                        ) if shape_kw["pages"] else []
    return {
        "state_shaped": [list(e) for e in state_shaped(
            text, cfg, shape_kw["slots"])],
        "ret_fed": [list(e) for e in ret_fed(text, cfg, shape_kw["slots"])]
        if program == "decode_chunk" else [],
        "ring_shaped": [list(e) for e in ring_shaped(
            text, cfg, shape_kw["slots"])],
        "expert_shaped": [list(e) for e in expert_shaped(text, cfg)],
        "weight_shaped": [list(e) for e in weight_shaped(text, shapes)],
        "weight_shaped_once": [
            list(e) for e in weight_shaped(text, shapes, in_loops=False)],
        "weight_prefetched": [list(e) for e in weight_shaped(
            text, shapes, in_loops=None, prefetched=True)],
        "score_shaped": [list(e) for e in score_shaped(
            text, shape_kw.get("prompt_len", 512), shape_kw["max_len"])]
        if program.startswith("admit_row") else [],
        "program": program,
        "argument_gb": mem.argument_size_in_bytes / 1e9,
        "output_gb": mem.output_size_in_bytes / 1e9,
        "alias_gb": mem.alias_size_in_bytes / 1e9,
        "temp_gb": mem.temp_size_in_bytes / 1e9,
        "pool_shaped": found,
        "hlo": text,
    }


def main() -> int:
    from distributed_llms_tpu.models.presets import get_preset

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("preset")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=4096)
    ap.add_argument("--pages", type=int, default=512)
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--chunk-steps", type=int, default=8)
    ap.add_argument("--kv-bits", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=512)
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="compile for this many chips under mesh.model")
    ap.add_argument("--programs", default=None,
                    help="default: all four; the two without a prefix "
                         "cache or a chunked prefill for a model with "
                         "convolution state, which refuses both; no "
                         "chunked prefill for latent pages")
    ap.add_argument("--hlo-dir", default=None,
                    help="write each program's optimised HLO here, and "
                         "beside it the same without locations (.noloc), "
                         "which two checkouts' programs can be compared by")
    a = ap.parse_args()
    try:
        v5e_devices()
    except Exception as e:  # no compile-only TPU client in this installation
        print(json.dumps({"no_client": str(e).splitlines()[0][:400]}))
        return 3
    cfg = get_preset(a.preset)
    if a.layers:
        cfg = dataclasses.replace(cfg, num_layers=a.layers)
    programs = a.programs or ",".join(
        ("decode_chunk", "admit_row") if not a.pages
        else PROGRAMS if cfg.family != "hybrid"
        # (latent pages serve the prefix cache; convolution state none)
        else PROGRAMS[:3] if cfg.kv_lora_rank else PROGRAMS[:2])
    for program in programs.split(","):
        try:
            r = analyse(
                program, cfg, slots=a.slots, max_len=a.max_len,
                pages=a.pages, page_size=a.page_size,
                chunk_steps=a.chunk_steps, kv_bits=a.kv_bits,
                prompt_len=a.prompt_len, mesh_model=a.mesh_model,
            )
        except Exception as e:  # the compiler's refusal IS the finding
            print(json.dumps({"program": program,
                              "error": str(e).splitlines()[0][:400]}))
            continue
        hlo = r.pop("hlo")
        if a.hlo_dir:
            os.makedirs(a.hlo_dir, exist_ok=True)
            name = f"{a.preset}.{program}.p{a.pages}.kv{a.kv_bits}.hlo"
            with open(os.path.join(a.hlo_dir, name), "w") as f:
                f.write(hlo)
            with open(os.path.join(a.hlo_dir, name + ".noloc"), "w") as f:
                f.write(without_locations(hlo))
        by_op: dict = {}
        for e in r["pool_shaped"]:
            by_op[e[0]] = by_op.get(e[0], 0) + 1
        r["pool_shaped_by_opcode"] = by_op
        r["pool_shaped"] = [list(e) for e in r["pool_shaped"]]
        print(json.dumps(r))
    return 0


if __name__ == "__main__":
    jax.config.update("jax_platforms", "cpu")
    # The default backend is the CPU here, so "auto" would trace the dense
    # fallbacks; the compiled kernels are what the chip runs.
    os.environ.setdefault("DLT_QUANT_MATMUL", "kernel")
    os.environ.setdefault("DLT_RAGGED_DECODE", "kernel")
    os.environ.setdefault("DLT_MOE_EXPERTS", "kernel")
    sys.exit(main())
