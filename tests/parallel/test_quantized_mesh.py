"""Quantized-resident serving ON A MESH (SURVEY §7 hard part 6, VERDICT r2
next-step 9, r3 next-step 7): mesh placement keeps QuantizedTensor leaves —
data and scale sharded under the plain weight's PartitionSpec, scale blocks
refined where a shard boundary would split a block — instead of rehydrating
to full dtype.  The GSPMD forward runs quantized contractions per shard
under shard_map whenever the kernel would run (per-shard Pallas tiles; the
bandwidth win applies to plain-TP serving), falling back to
dequantize+einsum on non-TPU backends.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distributed_llms_tpu.checkpoint import quantize as quant_lib
from distributed_llms_tpu.checkpoint import store as store_lib
from distributed_llms_tpu.core.config import MeshConfig, RuntimeConfig
from distributed_llms_tpu.models import model as model_lib, presets
from distributed_llms_tpu.parallel import api as api_lib
from distributed_llms_tpu.runtime.engine import InferenceEngine


def _qleaves(tree):
    return [
        x for x in jax.tree.leaves(
            tree, is_leaf=lambda x: isinstance(x, quant_lib.QuantizedTensor)
        )
        if isinstance(x, quant_lib.QuantizedTensor)
    ]


def test_scale_refinement_is_exact(devices8):
    """Sharding the blocked axis over more shards than block granularity
    allows refines scales (repeat) — dequantized values must be identical."""
    mesh = Mesh(np.array(devices8).reshape(8), ("model",))
    w = jax.random.normal(jax.random.key(0), (64, 256), jnp.float32)
    qt = quant_lib.quantize(w, bits=8, block=128)  # 2 blocks; 8 shards of 32
    placed = api_lib._place_quantized(qt, P(None, "model"), mesh, "w")
    assert placed.scale.shape[-2] == 8  # refined 128 -> 32-wide blocks
    np.testing.assert_array_equal(
        np.asarray(quant_lib.dequantize(qt)), np.asarray(quant_lib.dequantize(placed))
    )
    # data [N, K] really is sharded over 'model', rows beside scale rows
    assert placed.data.sharding.spec == P("model", None)
    assert placed.scale.sharding.spec == P("model", None)


def test_unshardable_leaf_replicates(devices8):
    """A spec that would shard the int4 pack axis at the last dim replicates
    (loudly) instead of corrupting."""
    mesh = Mesh(np.array(devices8).reshape(8), ("model",))
    w = jax.random.normal(jax.random.key(0), (64, 256), jnp.float32)
    qt = quant_lib.quantize(w, bits=4, block=128, pack_axis=-1)  # pairs along K
    placed = api_lib._place_quantized(qt, P("model", None), mesh, "w")
    assert placed.data.sharding.spec == P()
    np.testing.assert_array_equal(
        np.asarray(quant_lib.dequantize(qt)), np.asarray(quant_lib.dequantize(placed))
    )


def test_preset_weights_are_born_quantized_and_sharded(devices8):
    """InferenceEngine.from_preset under serve_quantized builds the block
    weights quantized leaf by leaf (models.model.init_params_quantized) —
    on a mesh already under param_specs' sharding, scale blocks refined
    like placement refines them — and, the generator's values not
    depending on the mesh, serves the single-device engine's tokens."""
    rt = RuntimeConfig(max_decode_steps=6, serve_quantized=True)
    kw = dict(rt=rt, quantization="int8", vocab_size=512)
    ref = InferenceEngine.from_preset("llama-tiny", **kw)
    eng = InferenceEngine.from_preset(
        "llama-tiny", mesh_cfg=MeshConfig(data=2, model=4), **kw
    )
    for e in (ref, eng):
        blocks = e.params["blocks"]
        assert len(_qleaves(blocks)) == 7  # wq wk wv wo + the three MLP
        assert not _qleaves({k: v for k, v in e.params.items() if k != "blocks"})
    w_gate = eng.params["blocks"]["mlp"]["w_gate"]
    assert w_gate.data.sharding.spec == P(None, "model", None)  # [L, N, K]
    # 176 columns over 4 shards = 44 a shard: the 16-wide blocks refine to 4.
    assert w_gate.scale.shape[-2] == 44
    assert w_gate.scale.sharding.spec == P(None, "model", None)
    assert ref.params["blocks"]["mlp"]["w_gate"].scale.shape[-2] == 11
    np.testing.assert_array_equal(
        np.asarray(quant_lib.dequantize(w_gate)),
        np.asarray(quant_lib.dequantize(ref.params["blocks"]["mlp"]["w_gate"])),
    )
    out_ref = ref.generate_text(["born quantized"], max_new_tokens=6)
    out = eng.generate_text(["born quantized"], max_new_tokens=6)
    assert out.tokens.tolist() == out_ref.tokens.tolist()
    with pytest.raises(ValueError, match="checkpoint.quantization"):
        InferenceEngine.from_preset("llama-tiny", rt=rt, vocab_size=512)


@pytest.mark.parametrize("quantization", ["int8", "int4"])
def test_tp_mesh_serves_quantized_resident(tmp_path, devices8, quantization):
    """data=2 x model=4 mesh: block weights stay quantized on the mesh and
    generation matches the single-device quantized engine token-for-token.
    model=4 over intermediate_size=176 with quant_block=32 forces scale
    refinement (per-shard 44 % 32 != 0 -> 4-wide blocks) in the real path."""
    cfg = presets.get_preset("llama-tiny", vocab_size=512)
    params = model_lib.init_params(jax.random.key(0), cfg)
    store_lib.save_shards(
        params, str(tmp_path), num_shards=2, model_config=cfg,
        quantization=quantization, quant_block=32,
    )
    rt = RuntimeConfig(max_decode_steps=6, serve_quantized=True)
    ref = InferenceEngine.from_store(str(tmp_path), rt=rt)
    eng = InferenceEngine.from_store(
        str(tmp_path), rt=rt, mesh_cfg=MeshConfig(data=2, model=4)
    )
    qleaves = _qleaves(eng.params["blocks"])
    assert qleaves, "mesh placement rehydrated the quantized tree"
    # Sharded, not replicated: at least one leaf's data spans the model axis.
    assert any(
        "model" in jax.tree_util.tree_leaves(
            [n for n in q.data.sharding.spec if n is not None]
        )
        for q in qleaves
    )
    out_ref = ref.generate_text(["hello quantized mesh"], max_new_tokens=6)
    out = eng.generate_text(["hello quantized mesh"], max_new_tokens=6)
    assert out.tokens.tolist() == out_ref.tokens.tolist()


@pytest.mark.parametrize(
    "case,wshape,wspec,xshape,xspec,k_lead,eq,shard,layers",
    [
        # Shapes chosen so the LOCAL shard is kernel-tileable (block=128,
        # local n a multiple of 128) — the Pallas program, not the dequant
        # fallback, is what runs per shard (asserted via the spy below).
        ("w_in N-sharded", (256, 1024), P(None, "model"), (4, 256),
         P("data", None), 1, "md,df->mf", "n", 0),
        ("wq head-sharded", (256, 4, 128), P(None, "model", None), (4, 256),
         P("data", None), 1, "md,dhk->mhk", "n", 0),
        ("wo K-sharded psum", (4, 128, 256), P("model", None, None),
         (4, 4, 128), P("data", "model", None), 2, "mhk,hkd->md", "k", 0),
        ("x batched 3d", (256, 1024), P(None, "model"), (2, 3, 256),
         P("data", None, None), 1, "btd,df->btf", "n", 0),
        # The stack of every layer's weight, read at an index: the layer
        # axis of data and scales unsharded, the index replicated.
        ("stacked wq N-sharded", (256, 4, 128), P(None, "model", None),
         (4, 256), P("data", None), 1, "md,dhk->mhk", "n", 3),
        ("stacked wo K-sharded psum", (4, 128, 256), P("model", None, None),
         (4, 4, 128), P("data", "model", None), 2, "mhk,hkd->md", "k", 3),
    ],
)
def test_sharded_kernel_partitions(
    devices8, monkeypatch, case, wshape, wspec, xshape, xspec, k_lead, eq,
    shard, layers, dispatched,
):
    """Under a tensor-parallel mesh the kernel program runs per shard
    inside shard_map (interpret mode on CPU) — N-sharded weights
    embarrassingly parallel, K-sharded wo with a psum — matching the dense
    dequant+einsum exactly."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from distributed_llms_tpu.checkpoint.quantize import dequantize, quantize
    from distributed_llms_tpu.ops import dispatch, quant_matmul as qm

    monkeypatch.setenv("DLT_QUANT_MATMUL", "interpret")
    kernel_calls = []
    orig = qm._quant_matmul_2d
    monkeypatch.setattr(
        qm, "_quant_matmul_2d",
        lambda *a, **kw: kernel_calls.append(1) or orig(*a, **kw),
    )
    mesh = Mesh(np.array(devices8).reshape(2, 4), ("data", "model"))
    lead = (layers,) if layers else ()
    w = jax.random.normal(jax.random.key(0), lead + wshape, jnp.float32)
    qt = quantize(w, bits=8, block=128, k_axes=k_lead,
                  n_axes=len(wshape) - k_lead)
    sharded = api_lib._place_quantized(
        qt, P(*(None,) * len(lead), *wspec), mesh, case)
    assert "model" in sharded.data.sharding.spec
    assert "model" in sharded.scale.sharding.spec
    x = jax.device_put(
        jax.random.normal(jax.random.key(1), xshape, jnp.float32),
        NamedSharding(mesh, xspec),
    )
    at = jnp.int32(layers - 1)
    with dispatch.sharded(mesh):
        f = jax.jit(lambda x_, q_, at_: qm.quant_contract(
            x_, q_.at(at_) if layers else q_, k_lead, eq, shard=shard))
        y = f(x, sharded, at)
    if layers:
        w, qt = w[layers - 1], jax.tree.map(lambda a: a[layers - 1], qt)
        assert dispatched()["quant_matmul.stacked"] == 1
    assert kernel_calls, "Pallas kernel program was not run per shard"
    ref = jnp.einsum(eq, x, dequantize(qt, x.dtype))
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("case,wshape,wspec,xtail,k_lead,eq,shard", [
    ("w_up N-sharded", (256, 1024), P(None, "model"), (256,), 1,
     "btd,df->btf", "n"),
    ("wo K-sharded psum", (4, 128, 256), P("model", None, None), (4, 128), 2,
     "bthk,hkd->btd", "k"),
])
@pytest.mark.parametrize("rows", [0, 257, 1024])
def test_sharded_kernel_skips_padding(
    devices8, monkeypatch, case, wshape, wspec, xtail, k_lead, eq, shard,
    rows, counted_kernels,
):
    """Under the mesh the count of an admission's real rows rides into every
    shard replicated, like the layer index: the row tiles that hold a real
    row equal the call without a count bit for bit, the others are zeros
    (a psum of zeros where K is split), and each shard's kernel stops at
    the real rows."""
    from distributed_llms_tpu.ops import dispatch, quant_matmul as qm

    monkeypatch.setenv("DLT_QUANT_MATMUL", "interpret")
    mesh = Mesh(np.array(devices8).reshape(2, 4), ("data", "model"))
    w = jax.random.normal(jax.random.key(0), (3, *wshape), jnp.float32)
    qt = quant_lib.quantize(w, bits=8, block=128, k_axes=k_lead,
                            n_axes=len(wshape) - k_lead)
    qt = api_lib._place_quantized(qt, P(None, *wspec), mesh, case)
    x = jax.random.normal(jax.random.key(1), (1, 1024, *xtail), jnp.bfloat16)

    def f(x_, q_, count=None):
        return qm.quant_contract(x_, q_.at(jnp.int32(2)), k_lead, eq,
                                 shard=shard, rows=count)

    count = jnp.array([rows], jnp.int32)
    with dispatch.sharded(mesh):
        want = np.asarray(jax.jit(f)(x, qt)[0].astype(jnp.float32))
        got = np.asarray(jax.jit(f)(x, qt, count)[0].astype(jnp.float32))
        assert counted_kernels(jax.make_jaxpr(f)(x, qt)) == [False]
        assert counted_kernels(jax.make_jaxpr(f)(x, qt, count)) == [True]
    live = -(-rows // 256) * 256
    np.testing.assert_array_equal(got[:live], want[:live])
    assert not got[live:].any()


@pytest.mark.parametrize("stacked_xs", [False, True, "at"])
def test_sharded_kernel_under_scan(devices8, monkeypatch, stacked_xs):
    """The per-shard kernel compiles and matches the dense reference INSIDE
    a ``lax.scan`` — with scan-invariant (closed-over) weights, the shape
    of the decode loop; with stacked weights scanned as xs; and with the
    stack closed over and read at the scan's index, the shape of the layer
    loop."""
    from jax.sharding import NamedSharding

    from distributed_llms_tpu.checkpoint.quantize import dequantize, quantize
    from distributed_llms_tpu.ops import dispatch, quant_matmul as qm

    monkeypatch.setenv("DLT_QUANT_MATMUL", "interpret")
    kernel_calls = []
    orig = qm._quant_matmul_2d
    monkeypatch.setattr(
        qm, "_quant_matmul_2d",
        lambda *a, **kw: kernel_calls.append(1) or orig(*a, **kw),
    )
    mesh = Mesh(np.array(devices8).reshape(2, 4), ("data", "model"))
    # Local N per 'model' shard must stay kernel-tileable (>=128, block 128)
    # or the per-shard dispatch takes its internal dequant branch
    # and the spy below would prove nothing.
    L, d = 3, 1024
    w = jax.random.normal(jax.random.key(0), (L, d, d), jnp.float32) * d**-0.5
    qt = quant_lib.quantize(w, bits=8, block=128)
    placed = api_lib._place_quantized(qt, P(None, None, "model"), mesh, "w")
    data, scale = placed.data, placed.scale
    assert scale.sharding.spec == P(None, "model", None)
    x = jax.device_put(
        jax.random.normal(jax.random.key(1), (4, d), jnp.float32),
        NamedSharding(mesh, P("data", None)),
    )

    def layer(c, d_, s_, at=None):
        q = dataclasses.replace(qt, data=d_, scale=s_, layer=at)
        return qm.quant_contract(c, q, 1, "md,df->mf", shard="n")

    if stacked_xs == "at":  # the stack closed over, read at the scan's index
        def f(x_, d_, s_):
            return jax.lax.scan(
                lambda c, at: (layer(c, d_, s_, at), None), x_,
                jnp.arange(L, dtype=jnp.int32),
            )[0]
    elif stacked_xs:
        def f(x_, d_, s_):
            return jax.lax.scan(
                lambda c, xs: (layer(c, *xs), None), x_, (d_, s_)
            )[0]
    else:
        def f(x_, d_, s_):
            def body(c, _):
                return layer(c, d_[0], s_[0]), None
            return jax.lax.scan(body, x_, None, length=L)[0]

    with dispatch.sharded(mesh):
        y = jax.jit(f)(x, data, scale)
    assert kernel_calls, "kernel program did not run under the scan"
    ref = np.asarray(x)
    wd = np.asarray(dequantize(qt, jnp.float32))
    if stacked_xs:
        for i in range(L):
            ref = ref @ wd[i]
    else:
        for _ in range(L):
            ref = ref @ wd[0]
    np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-4, atol=2e-4)


def test_tp_mesh_quantized_kernel_active(tmp_path, devices8, monkeypatch):
    """VERDICT r3 next-step 7 done-criterion: plain-TP (GSPMD) quantized
    serving dispatches the fused kernel program (spy on _quant_matmul_2d —
    the Pallas program itself, per shard under shard_map) under the
    layer scan AND the decode scan, and the tokens match fallback serving
    exactly."""
    from distributed_llms_tpu.ops import quant_matmul as qm

    cfg = presets.get_preset(
        "llama-tiny", vocab_size=512, hidden_size=256, intermediate_size=256,
        num_heads=2, num_kv_heads=2,  # hd=128: local TP shards stay tileable
    )
    params = model_lib.init_params(jax.random.key(0), cfg)
    store_dir = str(tmp_path / "s")
    store_lib.save_shards(
        params, store_dir, num_shards=1, model_config=cfg, quantization="int8",
        quant_block=128,
    )
    rt = RuntimeConfig(max_decode_steps=4, serve_quantized=True)
    monkeypatch.setenv("DLT_QUANT_MATMUL", "fallback")
    ref = InferenceEngine.from_store(store_dir, rt=rt)
    out_ref = ref.generate_text(["kernel under gspmd"], max_new_tokens=4)

    monkeypatch.setenv("DLT_QUANT_MATMUL", "interpret")
    kernel_calls = []
    orig = qm._quant_matmul_2d
    monkeypatch.setattr(
        qm, "_quant_matmul_2d",
        lambda *a, **kw: kernel_calls.append(1) or orig(*a, **kw),
    )
    eng = InferenceEngine.from_store(
        store_dir, rt=rt, mesh_cfg=MeshConfig(data=4, model=2)
    )
    assert _qleaves(eng.params["blocks"])
    out = eng.generate_text(["kernel under gspmd"], max_new_tokens=4)
    assert kernel_calls, "fused kernel was not dispatched under GSPMD serving"
    assert out.tokens.tolist() == out_ref.tokens.tolist()


@pytest.mark.parametrize("quantization", ["int8"])
def test_pipelined_mesh_serves_quantized_resident(tmp_path, devices8, quantization):
    """pipe=2 x model=2 (+data=2) mesh: staged quantized blocks flow through
    the shard_map pipeline and the wavefront decode, matching single-device."""
    cfg = presets.get_preset("llama-tiny", vocab_size=512)
    params = model_lib.init_params(jax.random.key(0), cfg)
    store_lib.save_shards(
        params, str(tmp_path), num_shards=2, model_config=cfg,
        quantization=quantization, quant_block=32,
    )
    rt = RuntimeConfig(max_decode_steps=6, serve_quantized=True, microbatches=2)
    ref = InferenceEngine.from_store(str(tmp_path), rt=rt)
    eng = InferenceEngine.from_store(
        str(tmp_path), rt=rt, mesh_cfg=MeshConfig(data=2, pipe=2, model=2)
    )
    assert _qleaves(eng.params["blocks"]), "pipeline staging rehydrated"
    prompts = ["hello quantized pipeline", "second row"]
    out_ref = ref.generate_text(prompts, max_new_tokens=6)
    out = eng.generate_text(prompts, max_new_tokens=6)
    assert out.tokens.tolist() == out_ref.tokens.tolist()


def test_pipelined_mesh_kernel_inside_shard_map(tmp_path, devices8, monkeypatch):
    """The PIPELINED mesh runs blocks inside its own vma-checked shard_map,
    where operands are already local — the fused kernel dispatch
    (_qmm_flat) runs under the layer scan there.  On CPU the Pallas interpreter loses vma, so the
    numerically-identical flat-dequant branch executes (same limitation and
    same answer as ops/flash.py's interpret path); on real TPU the kernel
    lowers with vma declared.  A spy proves the kernel dispatch path (not
    the einsum fallback) ran; tokens must match fallback serving exactly."""
    from distributed_llms_tpu.ops import quant_matmul as qm

    cfg = presets.get_preset(
        "llama-tiny", vocab_size=512, hidden_size=256, intermediate_size=256,
        num_heads=2, num_kv_heads=2,  # hd = 128
    )
    params = model_lib.init_params(jax.random.key(0), cfg)
    store_dir = str(tmp_path / "s")
    store_lib.save_shards(
        params, store_dir, num_shards=1, model_config=cfg, quantization="int8",
        quant_block=128,
    )
    rt = RuntimeConfig(max_decode_steps=4, serve_quantized=True, microbatches=2)
    monkeypatch.setenv("DLT_QUANT_MATMUL", "fallback")
    ref = InferenceEngine.from_store(store_dir, rt=rt)
    out_ref = ref.generate_text(["kernel in pipeline"], max_new_tokens=4)

    monkeypatch.setenv("DLT_QUANT_MATMUL", "interpret")
    dispatch_calls = []
    orig = qm._qmm_flat
    monkeypatch.setattr(
        qm, "_qmm_flat",
        lambda *a, **kw: dispatch_calls.append(1) or orig(*a, **kw),
    )
    eng = InferenceEngine.from_store(
        store_dir, rt=rt, mesh_cfg=MeshConfig(pipe=2, model=4)
    )
    assert _qleaves(eng.params["blocks"])
    out = eng.generate_text(["kernel in pipeline"], max_new_tokens=4)
    assert dispatch_calls, "kernel dispatch did not run inside the pipeline"
    assert out.tokens.tolist() == out_ref.tokens.tolist()
