"""Sharded checkpoint store: per-shard .npz files + JSON manifest.

Successor of the reference's shard store (`shard_<i>.pt` + `shard_info.json`
+ copied config.json, src/model/shard_manager.py:63-74) with its defects
fixed by construction: no pickle anywhere (npz + JSON), explicit param names
(no fragile layer-index parsing, D6), safetensors-native upstream (D5).

Layout on disk:
    <dir>/manifest.json   {params: {name: {shard, shape, dtype, quant...}},
                           arrays: {name: {shard[, offset, nbytes, crc32,
                           dtype, shape]}}, storage, num_shards,
                           model_config, quantization}
    <dir>/shard_<i>.bin   storage="raw" (default): tensors concatenated at
                          64-byte-aligned offsets; read by the native C++
                          parallel-pread tier (native/dlt_io.cpp) with
                          per-tensor CRC32 verification, Python fallback
    <dir>/shard_<i>.npz   storage="npz": numpy archives (v1 compatibility)

Packing uses the reference's greedy byte-balanced algorithm
(parallel.stages.pack_greedy).  ``load_shards`` can read a subset of shards
(a pipeline host loads only its stages' params) and ``reconstruct`` merges
everything back — the `reconstruct_model` parity point
(src/model/shard_manager.py:82-93).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any

import jax
import numpy as np

from ..core.config import ModelConfig
from ..parallel.stages import pack_greedy
from .. import native
from . import quantize as quant_lib
from .quantize import QuantizedTensor

SEP = "/"
MANIFEST = "manifest.json"
ALIGN = 64  # raw storage: tensor offsets aligned for mmap/DMA friendliness

# HF tokenizer files copied into the store so serving decodes with the
# model's real vocab (the reference tokenized with the HF tokenizer on the
# master, src/master/node.py:235-245; without this the cluster path fell
# back to byte-level ids — gibberish against a real checkpoint).
TOKENIZER_DIR = "tokenizer"
_TOKENIZER_FILES = (
    "tokenizer.json",
    "tokenizer_config.json",
    "vocab.json",
    "merges.txt",
    "special_tokens_map.json",
    "tokenizer.model",
    "added_tokens.json",
    "vocab.txt",
    "spiece.model",
)


def _flatten(params: Any) -> dict[str, Any]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, QuantizedTensor)
    )[0]:
        name = SEP.join(str(getattr(p, "key", p)) for p in path)
        flat[name] = leaf
    return flat


def _unflatten(flat: dict[str, Any]) -> dict[str, Any]:
    tree: dict[str, Any] = {}
    for name, leaf in flat.items():
        node = tree
        parts = name.split(SEP)
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return tree


def save_shards(
    params: Any,
    out_dir: str,
    num_shards: int = 1,
    model_config: ModelConfig | None = None,
    quantization: str | None = None,  # None | "int8" | "int4"
    quant_block: int = 128,
    storage: str = "raw",  # "raw" (native-IO blobs + CRC) | "npz" (v1)
    tokenizer_src: str | None = None,  # checkpoint dir whose tokenizer files
    #                                    are copied into the store
) -> dict:
    """Write params (optionally quantizing first) into a sharded store.
    Returns the manifest dict."""
    if storage not in ("raw", "npz"):
        raise ValueError(f"unknown storage {storage!r}; raw|npz")
    os.makedirs(out_dir, exist_ok=True)
    tokenizer_rel: str | None = None
    if tokenizer_src is not None:
        import shutil

        found = [
            f for f in _TOKENIZER_FILES
            if os.path.isfile(os.path.join(tokenizer_src, f))
        ]
        if found:
            tok_dir = os.path.join(out_dir, TOKENIZER_DIR)
            # A reused shard_dir may hold a previous model's tokenizer files;
            # stale ones (e.g. an old tokenizer.json next to a new
            # tokenizer.model) would win AutoTokenizer's file preference and
            # serve the wrong vocab — clear before copying.
            shutil.rmtree(tok_dir, ignore_errors=True)
            os.makedirs(tok_dir, exist_ok=True)
            for f in found:
                shutil.copy2(os.path.join(tokenizer_src, f), os.path.join(tok_dir, f))
            tokenizer_rel = TOKENIZER_DIR
        else:
            from ..core.observability import get_logger

            get_logger("store").warning(
                "tokenizer_src %r contains no recognized tokenizer files; "
                "store will fall back to byte-level ids at serve time",
                tokenizer_src,
            )
    if quantization:
        bits = {"int8": 8, "int4": 4}[quantization]
        params = quant_lib.quantize_tree(params, bits=bits, block=quant_block)

    flat = _flatten(params)
    sizes = {}
    for name, leaf in flat.items():
        if isinstance(leaf, QuantizedTensor):
            sizes[name] = leaf.data.size + leaf.scale.size * 4
        else:
            sizes[name] = int(np.asarray(leaf).nbytes)
    assignment = pack_greedy(sizes, num_shards)

    entries: dict[str, dict] = {}
    arrays_meta: dict[str, dict] = {}
    shard_arrays: list[dict[str, np.ndarray]] = [dict() for _ in range(num_shards)]
    for name, leaf in flat.items():
        shard = assignment[name]
        if isinstance(leaf, QuantizedTensor):
            shard_arrays[shard][name + ".q"] = np.asarray(leaf.data)
            shard_arrays[shard][name + ".scale"] = np.asarray(leaf.scale)
            entries[name] = {
                "shard": shard,
                "shape": list(leaf.orig_shape),
                "dtype": "quantized",
                "bits": leaf.bits,
                "pack_axis": leaf.pack_axis,
                # Stored as a matrix [N, K] with scales [N/block, K], K on
                # the lanes of both (checkpoint/quantize.py; the expert
                # stacks, block_axis -2, as [K, N] with [K/block, N]): how
                # the axes of "shape" flatten.
                "axes": [leaf.k_axes, leaf.n_axes],
                "block_axis": leaf.block_axis,
                "k_minor": leaf.block_axis == -1,
            }
        else:
            arr = np.asarray(leaf)
            # Neither npz nor numpy dtypes know bfloat16: store raw bytes
            # viewed as uint16.
            if arr.dtype == jax.numpy.bfloat16:
                shard_arrays[shard][name] = arr.view(np.uint16)
                entries[name] = {"shard": shard, "shape": list(arr.shape), "dtype": "bfloat16"}
            else:
                shard_arrays[shard][name] = arr
                entries[name] = {"shard": shard, "shape": list(arr.shape), "dtype": str(arr.dtype)}

    for i, arrays in enumerate(shard_arrays):
        if storage == "npz":
            np.savez(os.path.join(out_dir, f"shard_{i}.npz"), **arrays)
            for aname in arrays:
                arrays_meta[aname] = {"shard": i}
            continue
        # raw: concatenated tensors at 64-byte-aligned offsets + CRC32.
        path = os.path.join(out_dir, f"shard_{i}.bin")
        with open(path, "wb") as f:
            for aname, arr in arrays.items():
                arr = np.ascontiguousarray(arr)
                pad = (-f.tell()) % ALIGN
                f.write(b"\0" * pad)
                offset = f.tell()
                # Zero-copy: stream the array buffer and checksum it in
                # place (no tensor-sized bytes duplicate on the save path).
                arr.tofile(f)
                arrays_meta[aname] = {
                    "shard": i,
                    "offset": offset,
                    "nbytes": int(arr.nbytes),
                    "crc32": native.crc32(arr),
                    "dtype": str(arr.dtype),
                    "shape": list(arr.shape),
                }

    manifest = {
        "format_version": 2,
        "storage": storage,
        "num_shards": num_shards,
        "quantization": quantization,
        "params": entries,
        "arrays": arrays_meta,
        "model_config": dataclasses.asdict(model_config) if model_config else None,
        "tokenizer": tokenizer_rel,  # store-relative dir of HF tokenizer files
    }
    with open(os.path.join(out_dir, MANIFEST), "w") as f:
        json.dump(manifest, f, indent=1)
    return manifest


def load_manifest(store_dir: str) -> dict:
    with open(os.path.join(store_dir, MANIFEST)) as f:
        return json.load(f)


def _load_arrays_npz(
    store_dir: str, manifest: dict, wanted: set[int]
) -> dict[str, np.ndarray]:
    out: dict[str, np.ndarray] = {}
    for i in wanted:
        path = os.path.join(store_dir, f"shard_{i}.npz")
        if not os.path.exists(path):
            raise FileNotFoundError(f"manifest lists shard {i} but {path} is missing")
        z = np.load(path)
        for aname in z.files:
            out[aname] = z[aname]
    return out


def _load_arrays_raw(
    store_dir: str, manifest: dict, wanted: set[int], io_threads: int
) -> dict[str, np.ndarray]:
    """Raw storage: parallel native pread of every wanted tensor segment,
    CRC32-verified against the manifest."""
    names: list[str] = []
    tasks: list[tuple[str, int, int]] = []
    for aname, meta in manifest["arrays"].items():
        if meta["shard"] not in wanted:
            continue
        path = os.path.join(store_dir, f"shard_{meta['shard']}.bin")
        if not os.path.exists(path):
            raise FileNotFoundError(
                f"manifest lists shard {meta['shard']} but {path} is missing"
            )
        names.append(aname)
        tasks.append((path, meta["offset"], meta["nbytes"]))
    bufs, crcs = native.read_segments(tasks, threads=io_threads, with_crc=True)
    out: dict[str, np.ndarray] = {}
    for aname, buf, crc in zip(names, bufs, crcs):
        meta = manifest["arrays"][aname]
        if crc != meta["crc32"]:
            raise IOError(
                f"checksum mismatch for {aname!r} in shard {meta['shard']} "
                f"(expected {meta['crc32']:#010x}, got {crc:#010x}) — store corrupt?"
            )
        out[aname] = buf.view(np.dtype(meta["dtype"])).reshape(meta["shape"])
    return out


def load_shards(
    store_dir: str,
    shards: list[int] | None = None,
    dequantize: bool = False,
    dtype: Any = None,
    io_threads: int = 8,
) -> dict[str, Any]:
    """Load params from the store (optionally only some shards).  Returns the
    nested param tree containing only the params present in those shards."""
    manifest = load_manifest(store_dir)
    wanted = set(range(manifest["num_shards"])) if shards is None else set(shards)
    missing = wanted - set(range(manifest["num_shards"]))
    if missing:
        raise ValueError(f"store has {manifest['num_shards']} shards; no {sorted(missing)}")

    if manifest.get("storage", "npz") == "raw":
        arrays = _load_arrays_raw(store_dir, manifest, wanted, io_threads)
    else:
        arrays = _load_arrays_npz(store_dir, manifest, wanted)

    import jax.numpy as jnp

    flat: dict[str, Any] = {}
    for name, meta in manifest["params"].items():
        if meta["shard"] not in wanted:
            continue
        if meta["dtype"] == "quantized":
            if "axes" not in meta or (
                    meta["block_axis"] == -1 and not meta.get("k_minor")):
                raise ValueError(
                    f"store {store_dir} holds {name} in a quantized layout "
                    "of before PR 33 (weights with their model axes, or as "
                    "matrices [K, N]); this build reads matrices [N, K] "
                    "with scales [N/block, K], K on the lanes of both: "
                    "re-quantize it with save_shards"
                )
            qt = QuantizedTensor(
                data=jnp.asarray(arrays[name + ".q"]),
                scale=jnp.asarray(arrays[name + ".scale"]),
                bits=meta["bits"],
                orig_shape=tuple(meta["shape"]),
                pack_axis=meta["pack_axis"],
                block_axis=meta["block_axis"],
                k_axes=meta["axes"][0], n_axes=meta["axes"][1],
            )
            flat[name] = quant_lib.dequantize(qt, dtype or jnp.float32) if dequantize else qt
        elif meta["dtype"] == "bfloat16":
            arr = jnp.asarray(arrays[name].view(jnp.bfloat16))
            flat[name] = arr.astype(dtype) if dtype else arr
        else:
            arr = jnp.asarray(arrays[name])
            flat[name] = arr.astype(dtype) if dtype else arr
    return _unflatten(flat)


def reconstruct(store_dir: str, dtype: Any = None) -> dict[str, Any]:
    """Merge every shard back into a full (dequantized) param tree."""
    return load_shards(store_dir, shards=None, dequantize=True, dtype=dtype)
