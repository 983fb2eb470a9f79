"""Device time of one ``admit_row_paged`` a bucket, by operation and by the
shape each operation writes: what an admission consists of, outside any
server.  PERF.md's tables of an admission's fusions by shape come from it.

    chiprun --chips 1 -- python tools/admit_dig.py qwen2-7b --slots 16 \
        --max-len 4096 --pages 512 --buckets 256,2048,2048:1100,128:100@1280 \
        --out chiprun_out/a.json

``BUCKET:LEN@PREFIX`` times one ``admit_row_auto_paged`` instead, a prefix
cache's hit: a suffix of LEN tokens in a bucket of BUCKET behind PREFIX
tokens that the pool's pages already hold (PR 47: ``by_part_ms`` splits it
into the matmuls, the head, the attention kernel and everything else).

Weights are ``init_params_quantized`` (int8) from seed 0, the pool and the
row's page list are the cell's (``max_len // page`` entries, the bucket's
own pages first, the scratch page after), the prompt random bytes: three
short of the bucket, or ``BUCKET:LEN`` tokens of it (since PR 39 the
quantized matmuls skip the row tiles past them, and since PR 46 the flash
kernel of a model of windowed and full layers the tiles of queries past
them: ``flash_attn_ms`` lists its calls singly, in the order they ran).
``--tree DIR`` profiles another checkout's programs (a parent under
``_chip/``) in the same call.  ``--rehearsal`` runs a tiny preset on the
CPU and reads no trace (there are no device lines to read).
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import shutil
import sys
import time

_SHAPE = re.compile(r"^\(?([a-z0-9]+\[[0-9,]*\])")


def _op(name: str) -> tuple[str, str]:
    """(stem, output shape) of an `XLA Ops` event's name, which is the HLO
    instruction's text: ``%fusion.12 = f32[1,28,256,4096]{...} fusion(...)``."""
    from benchmark import trace_reduce  # (the tree's own: main sets the path)

    m = _SHAPE.match(name.partition(" = ")[2])
    return trace_reduce.short_name(name), m.group(1) if m else ""


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("preset")
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=4096)
    ap.add_argument("--pages", type=int, default=512)
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--buckets", default="256,2048",
                    help="BUCKET or BUCKET:LEN (the prompt's real tokens), "
                         "or BUCKET:LEN@PREFIX (a suffix behind PREFIX "
                         "cached tokens)")
    ap.add_argument("--tree", default=None)
    ap.add_argument("--out", default="chiprun_out/admit_dig.json")
    ap.add_argument("--rehearsal", action="store_true")
    a = ap.parse_args()
    root = os.path.abspath(
        a.tree or os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sys.path.insert(0, root)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import ProfileData

    from benchmark import trace_reduce
    from distributed_llms_tpu.models import kv_cache, model as model_lib
    from distributed_llms_tpu.models.presets import get_preset
    from distributed_llms_tpu.runtime import batcher

    cfg = get_preset(a.preset)
    t0 = time.time()
    params = model_lib.init_params_quantized(jax.random.key(0), cfg, 8)
    jax.block_until_ready(params)
    print(f"weights {time.time() - t0:.1f} s on {jax.devices()[0].device_kind}",
          flush=True)
    pool = kv_cache.make_pool(cfg, a.pages, a.page_size, slots=a.slots)
    per_row = a.max_len // a.page_size
    report = {"preset": a.preset, "tree": root, "max_len": a.max_len,
              "device": jax.devices()[0].device_kind, "buckets": {}}
    tdir = os.path.join(os.path.dirname(os.path.abspath(a.out)), "_admit_trace")
    for item in a.buckets.split(","):
        spec, _, prefix = item.partition("@")
        bucket, _, plen = spec.partition(":")
        bucket, prefix = int(bucket), int(prefix or 0)
        plen = int(plen) if plen else bucket - 3
        # The row's pages: the cached run's first (whole pages: a hit is),
        # the bucket's own after them, the scratch page past those.
        held = -(-prefix // a.page_size)
        own = held + -(-bucket // a.page_size)
        prompt = jnp.asarray(
            np.random.RandomState(0).randint(0, 250, bucket), jnp.int32)
        pages = np.r_[1:1 + own, np.zeros(per_row - own)].astype(np.int32)
        page_list = jnp.asarray(pages)
        pages[:held] = 0  # (a shared page is read and never written)
        write_list = jnp.asarray(pages)

        def admit(pool):
            if prefix:
                out = batcher.admit_row_auto_paged(
                    params, cfg, pool, page_list, write_list,
                    jnp.int32(prefix), prompt, jnp.int32(plen),
                    jax.random.key(1))
            else:
                out = batcher.admit_row_paged(
                    params, cfg, pool, page_list, prompt, jnp.int32(plen),
                    jax.random.key(1), slot=jnp.int32(1))
            jax.block_until_ready(out[1])
            return out[0]

        for _ in range(2):
            pool = admit(pool)
        t1 = time.time()
        for _ in range(3):
            pool = admit(pool)
        entry = {"prompt_len": plen, "prefix_len": prefix,
                 "wall_ms": (time.time() - t1) / 3 * 1e3}
        if not a.rehearsal:
            shutil.rmtree(tdir, ignore_errors=True)
            jax.profiler.start_trace(tdir)
            pool = admit(pool)
            jax.profiler.stop_trace()
            path = trace_reduce.find_xplane(tdir)
            by = collections.defaultdict(lambda: [0, 0])
            flash = []  # (start, instruction, ns) of every flash_attn call
            for plane in ProfileData.from_file(path).planes:
                if not plane.name.startswith("/device:TPU:0"):
                    continue
                for line in plane.lines:
                    if line.name != "XLA Ops":
                        continue
                    for ev in line.events:
                        op = _op(ev.name)
                        if op[0] == "while":  # (a wrapper: its body's
                            continue  # operations are events of their own)
                        rec = by[op]
                        rec[0] += int(ev.duration_ns)
                        rec[1] += 1
                        if op[0] == "flash_attn":
                            flash.append((
                                int(ev.start_ns),
                                ev.name.split(" = ")[0].lstrip("%"),
                                int(ev.duration_ns)))
            shutil.rmtree(tdir, ignore_errors=True)
            kinds = collections.defaultdict(int)
            for (stem, _), (ns, _) in by.items():
                kinds[stem] += ns
            # An admission's parts: the head (what writes [.., vocab]), the
            # blocks' quantized matmuls, the attention kernel, and what XLA
            # wrote itself (the dense attention body, the gather of the row,
            # the splice, norms and rotations).
            parts = collections.defaultdict(int)
            for (stem, shape), (ns, _) in by.items():
                if re.search(rf"[\[,]{cfg.vocab_size}\]$", shape):
                    parts["head"] += ns  # (one row of logits: a fusion)
                elif stem.startswith("_quant_matmul_2d"):
                    parts["matmuls"] += ns
                elif stem.startswith("flash_attn"):
                    parts["flash_attn"] += ns
                else:
                    parts["other"] += ns
            entry.update(
                device_ms=sum(v[0] for v in by.values()) / 1e6,
                by_part_ms={k: round(v / 1e6, 3) for k, v in parts.items()},
                by_kind_ms={k: round(v / 1e6, 3) for k, v in sorted(
                    kinds.items(), key=lambda kv: -kv[1])[:12]},
                # The flash kernel's calls singly, in the order they ran: a
                # full and a windowed layer write the same shape, and only
                # their times tell them apart.
                flash_attn_ms=[[name, round(ns / 1e6, 3)]
                               for _, name, ns in sorted(flash)],
                # Operations XLA wrote itself (no Pallas call), by the shape
                # they write: the score matrices are the [.., T, S] ones.
                by_shape_ms=[
                    [stem, shape, round(ns / 1e6, 3), n]
                    for (stem, shape), (ns, n) in sorted(
                        by.items(), key=lambda kv: -kv[1][0])[:40]],
            )
        report["buckets"][item] = entry
        print(item, json.dumps(entry)[:7000], flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
