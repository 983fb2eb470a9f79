#!/usr/bin/env python
"""Time ``_quant_matmul_2d`` alone on the chip: one matmul a layer under a
``lax.scan`` over a stack of 8 int8 layers, at every block weight's shape of
the cells' configurations, at M = 16, 64 and 256 rows (a decode step, a
bucket of one row tile) and at what an admission of the cells hands it:
M = 2,048 of which 1,100 rows are real, and M = 8,192 of which 5,000 are, in
blocks of 2,048 as ``models.model.run_layers`` runs K-EXAONE's FFNs
(``--ms M:REAL``; a tree whose kernel takes no count computes every row).

A row of the report is one (shape, M): the tile ``ops/quant_matmul._tiles``
chose, microseconds a call on the host's clock (``wall_us``: the scan's
iteration and the sum that keeps the call alive are in it), the kernel's own
median device time from a profiler trace of the same program
(``kernel_us``), the time the weight's and scales' bytes take at the chip's
819 GB/s (``bytes_us``: what is left of ``kernel_us`` is the dequantization,
the MXU at hundreds of rows, and a call's fixed cost) and the rate
(``gb_s``).  A row with a count of real rows has, beside them, the device
time of a whole call, every block of it (``call_us``), the row tiles that
hold a real row (``live_tiles``) and ``call_us`` over them
(``us_per_live_tile``): the kernel's time a live tile, apart from a cell.

    python tools/quant_matmul_bench.py --out chiprun_out/quant_matmul_bench.json
    python tools/quant_matmul_bench.py --tree _chip/parent   # another checkout
    python tools/quant_matmul_bench.py --tiles 18432x7168=1024x2048

``--tree`` names the checkout whose kernel is timed; a tree from before the
leaves lay K-minor ([L, K, N]: its ``_tiles`` gives four numbers) is handed
them so.  ``--tiles`` times other tiles than the kernel's own choice (an
experiment; the first is always the kernel's).  ``--pads-mb`` allocates
device memory ahead of the stack (where a stack lies has moved a kernel's
time before: PERF.md section 7).  No chip, no time: on the CPU the script
runs the interpreter at a toy size to check itself (``--rehearsal``).
"""
from __future__ import annotations

import argparse
import inspect
import json
import math
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PRESETS = ("qwen2-7b", "pythia-6.9b", "lfm2-8b-a1b", "ax-k1-ep16")
HBM_GB_S = 819.0
BLOCK = 128
LAYERS = 8
TOKEN_BLOCK = 2048  # models.model._TOKEN_BLOCK


def cell_shapes(presets) -> dict[tuple[int, int], list[str]]:
    """(K, N) of every 2-D-blocked quantized leaf of the presets' block
    stacks -> the leaves that have it."""
    import jax
    import jax.numpy as jnp

    from distributed_llms_tpu.checkpoint.quantize import QuantizedTensor
    from distributed_llms_tpu.models import model as model_lib
    from distributed_llms_tpu.models.presets import get_preset

    is_q = lambda x: isinstance(x, QuantizedTensor)  # noqa: E731
    shapes: dict[tuple[int, int], list[str]] = {}
    for preset in presets:
        params = jax.eval_shape(
            lambda k: model_lib.init_params_quantized(k, get_preset(preset), 8),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        for path, q in jax.tree_util.tree_flatten_with_path(
                params["blocks"], is_leaf=is_q)[0]:
            if is_q(q) and q.block_axis == -1:
                k, n = (math.prod(axes) for axes in q.tail_shape)
                shapes.setdefault((k, n), []).append(
                    preset + "/" + "/".join(str(p.key) for p in path))
    return shapes


def kernel_us(trace_dir: str) -> tuple[float, float] | None:
    """(median of the `_quant_matmul_2d` events' device times, sum of them
    and of the padding kernel's behind a counted call) of a trace,
    microseconds."""
    from benchmark import trace_reduce

    path = trace_reduce.find_xplane(trace_dir)
    events = [
        (e.name, e.dur_ns / 1e3)
        for e in (trace_reduce.load_xplane(path) if path else ())
        if e.plane.startswith("/device:TPU:0") and e.line == "XLA Ops"
        and e.name.startswith("_quant_matmul_2d")]
    durs = sorted(d for name, d in events if name == "_quant_matmul_2d")
    if not durs:
        return None
    return durs[len(durs) // 2], sum(d for _, d in events)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=HERE)
    ap.add_argument("--presets", default=",".join(PRESETS))
    ap.add_argument("--ms", default="16,64,256,2048:1100,8192:5000",
                    help="rows a call, M or M:REAL (REAL of them real; over "
                         "2,048 in blocks of 2,048)")
    ap.add_argument("--tiles", default="",
                    help="KxN=BNxBK[,KxN=BNxBK...]: other tiles to time")
    ap.add_argument("--pads-mb", default="0")
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()

    sys.path[:0] = [os.path.abspath(args.tree), HERE]
    import jax
    import jax.numpy as jnp

    from distributed_llms_tpu.ops import quant_matmul as qm

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.rehearsal:
        print("no TPU: a kernel's time comes from the chip (--rehearsal "
              "checks the script on the interpreter)", file=sys.stderr)
        return 2
    if args.rehearsal:
        shapes, ms, layers = {(256, 384): ["rehearsal"]}, "16,768:300,4096:2500", 2
    else:
        shapes, ms, layers = cell_shapes(args.presets.split(",")), args.ms, LAYERS
    # (M, real rows or None) a call
    ms = [(int(m), int(real) if real else None) for m, _, real in
          (item.partition(":") for item in ms.split(","))]
    counted = "real" in inspect.signature(
        qm._quant_matmul_2d.__wrapped__).parameters
    extra: dict[tuple[int, int], list[tuple[int, int]]] = {}
    for item in filter(None, args.tiles.split(",")):
        kn, tile = item.split("=")
        extra.setdefault(tuple(map(int, kn.split("x"))), []).append(
            tuple(map(int, tile.split("x"))))
    reps = 20 if on_tpu else 1
    dev = jax.devices()[0]
    k_minor = len(qm._tiles(512, 512, BLOCK, 8)) == 3
    report = {"device": {"platform": dev.platform,
                         "device_kind": dev.device_kind},
              "tree": os.path.abspath(args.tree), "k_minor": k_minor,
              "counted": counted, "rows": []}

    def timed(fn, *a):
        """(host microseconds a call, the kernel's own) of the jitted
        stack ``fn``: ``reps`` runs back to back, then three traced."""
        fn(*a).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*a)
        out.block_until_ready()
        wall = (time.perf_counter() - t0) / reps / layers * 1e6
        if not on_tpu:
            return wall, None, None
        with tempfile.TemporaryDirectory() as td:
            with jax.profiler.trace(td):
                for _ in range(3):
                    out = fn(*a)
                out.block_until_ready()
            median, total = kernel_us(td) or (None, None)
            return wall, median, total and total / (3 * layers)

    for (k, n), leaves in sorted(shapes.items()):
        own = qm._tiles(k, n, BLOCK, 8)
        if own is None:
            report["rows"].append({"k": k, "n": n, "leaves": leaves,
                                   "error": "untileable: the fallback"})
            continue
        for pad_mb in [int(x) for x in args.pads_mb.split(",")]:
            pad = jnp.zeros((pad_mb << 20,), jnp.int8).block_until_ready()
            keys = jax.random.split(jax.random.key(k + n), 3)
            # the stack as the tree stores it: [L, N, K], or [L, K, N]
            q = jax.random.randint(
                keys[0], (layers, n, k) if k_minor else (layers, k, n),
                -127, 128, jnp.int8).block_until_ready()
            s = jax.random.uniform(keys[1], (layers, n // BLOCK, k),
                                   jnp.float32, 1e-3, 3e-3)
            for m, real in ms:
                x = jax.random.normal(keys[2], (m, k), jnp.bfloat16)
                # an admission over TOKEN_BLOCK tokens: a block at a time
                mb = min(m, TOKEN_BLOCK)
                bm = min(qm._BM_MAX, mb)
                left = None if real is None else jnp.clip(
                    real - mb * jnp.arange(m // mb, dtype=jnp.int32)[:, None],
                    0, mb)  # [blocks, 1]: the real rows of each
                tiles = [own] + [(*t, own[2]) for t in extra.get((k, n), ())
                                 if k_minor]
                for tile in tiles:
                    def stack(x, q, s, tile=tile):
                        def block(i, xb, rows=None):
                            given = () if rows is None or not counted else (rows,)
                            return qm._quant_matmul_2d(
                                xb, q, s, i.reshape(1), *given, bits=8, bm=bm,
                                tiles=tile, interpret=not on_tpu)

                        def layer(acc, i):
                            if m == mb:
                                y = block(i, x, None if left is None else left[0])
                            else:
                                y = jax.lax.map(
                                    lambda a: block(i, *a),
                                    (x.reshape(m // mb, mb, k), left)
                                ).reshape(m, n)
                            return acc + y.astype(jnp.float32), None
                        return jax.lax.scan(
                            layer, jnp.zeros((m, n), jnp.float32),
                            jnp.arange(layers, dtype=jnp.int32))[0]

                    row = {"k": k, "n": n, "m": m, "tile": list(tile),
                           "pad_mb": pad_mb, "leaves": leaves}
                    try:
                        wall, kern, call = timed(jax.jit(stack), x, q, s)
                    except Exception as e:  # a tile too large for VMEM
                        row["error"] = f"{type(e).__name__}: {e}"[:300]
                    else:
                        bytes_us = k * n * (1 + 4 / BLOCK) / HBM_GB_S / 1e3
                        row.update(wall_us=wall, kernel_us=kern,
                                   bytes_us=bytes_us)
                        if kern:
                            row["gb_s"] = bytes_us / kern * HBM_GB_S
                        if real is not None:
                            live = sum(qm.live_rows(mb, int(r[0])) for r in left
                                       ) // bm if counted else m // bm
                            row.update(real_rows=real, live_tiles=live,
                                       call_us=call)
                            if call:
                                row["us_per_live_tile"] = call / live
                    report["rows"].append(row)
                    print(json.dumps(row), flush=True)
            del q, s, pad
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
