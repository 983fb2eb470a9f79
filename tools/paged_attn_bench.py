#!/usr/bin/env python
"""Time ``paged_decode_attn`` alone on the chip: one call a layer under a
``lax.scan`` over the stacked pool, at the cells' shapes, with rows as deep
as the cells' decode steps find them.

Rows are drawn from a traffic file: a request of the mix, weighted by the
decode steps it takes, at a uniform point of its answer.  Each shape is
timed at run lengths of 1, 2, 4, 8 and 16 pages and at the one the kernel
works out for itself, under pads of device memory allocated before the
pool (where the pool lies has moved a kernel's time before: PERF.md
section 7), and with every row at one depth, which tells a run's fixed
cost from its cost a page.

    python tools/paged_attn_bench.py --out chiprun_out/paged_attn_bench.json
    python tools/paged_attn_bench.py --tree _chip/parent   # another checkout

``--tree`` names the checkout whose kernel is timed: a tree from before the
kernel walked runs takes no run length and is timed as it is.  No chip, no
time: on the CPU the script runs the interpreter at a toy size to check
itself (``--rehearsal``).
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import random
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (rows, layers, pages in the pool, page, KV heads, head width, query heads,
# page slots a row) as the benchmark's cells serve them, and their traffic.
SHAPES = {
    "qwen2-7b": dict(b=16, layers=28, nb=512, blk=64, kvh=4, d=128, h=28,
                     p=64, traffic=("chat", "doc-qa")),
    "pythia-6.9b": dict(b=8, layers=32, nb=96, blk=64, kvh=32, d=128, h=32,
                        p=32, traffic=("chat-short",)),
    "lfm2-8b-a1b": dict(b=16, layers=6, nb=512, blk=64, kvh=8, d=64, h=32,
                        p=64, traffic=("chat",)),
}
REHEARSAL = dict(b=2, layers=2, nb=12, blk=8, kvh=2, d=128, h=4, p=5,
                 traffic=("rehearsal-chat",))


def drawn_lengths(traffic: str, rows: int, sets: int, cap: int,
                  seed: int) -> list[list[int]]:
    """``sets`` batches of ``rows`` resident lengths, as a decode step of
    the mix finds them (the BOS token counted)."""
    with open(os.path.join(HERE, "benchmark", "traffic",
                           f"{traffic}.json")) as f:
        spec = json.load(f)
    turns = [(s["shared"] + p, a) for s in spec["sessions"]
             for p, a in s["turns"]]
    rng = random.Random(f"{traffic}/{seed}")
    picks = rng.choices(turns, weights=[a for _, a in turns], k=rows * sets)
    depths = [min(1 + p + rng.randrange(a), cap) for p, a in picks]
    return [depths[i * rows:(i + 1) * rows] for i in range(sets)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=HERE)
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--runs", default="0,1,2,4,8,16",
                    help="pages a run; 0: what the kernel works out")
    ap.add_argument("--pads-mb", default="0,300,1000")
    ap.add_argument("--sets", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()

    sys.path.insert(0, os.path.abspath(args.tree))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llms_tpu.ops import decode_attn

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.rehearsal:
        print("no TPU: a kernel's time comes from the chip (--rehearsal "
              "checks the script on the interpreter)", file=sys.stderr)
        return 2
    mode = "kernel" if on_tpu else "interpret"
    takes_run = "run" in inspect.signature(decode_attn._paged_impl).parameters
    runs = [int(r) for r in args.runs.split(",")] if takes_run else [None]
    shapes = ({"rehearsal": REHEARSAL} if args.rehearsal
              else {n: SHAPES[n] for n in args.shapes.split(",")})
    reps = 20 if on_tpu else 1
    dev = jax.devices()[0]
    report = {"device": {"platform": dev.platform,
                         "device_kind": dev.device_kind},
              "tree": os.path.abspath(args.tree), "takes_run": takes_run,
              "rows": []}

    def timed(fn, *a):
        """Seconds a call of the jitted ``fn``: ``reps`` back to back."""
        fn(*a).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*a)
        out.block_until_ready()
        return (time.perf_counter() - t0) / reps

    for name, c in shapes.items():
        kvh, d = decode_attn.pool_head_shape(c["kvh"], c["d"], True)
        page_bytes = 2 * c["blk"] * kvh * d * 2
        cap = c["p"] * c["blk"]
        cases = {t: drawn_lengths(t, c["b"], args.sets, cap, args.seed)
                 for t in c["traffic"]}
        # Every row at one depth: 1, an eighth, a half, all of its slots.
        for pages in sorted({1, max(c["p"] // 8, 1), c["p"] // 2, c["p"]}):
            cases[f"all-{pages}-pages"] = [[pages * c["blk"]] * c["b"]]

        def make(run):
            kw = {} if run is None else {"run": run or None}

            def stack(q, k_pool, v_pool, lengths, tables):
                def layer(acc, i):
                    out = decode_attn._paged_impl(
                        q, k_pool, v_pool, lengths, tables, i.reshape(1),
                        mode=mode, **kw)
                    return acc + out.astype(jnp.float32), None
                return jax.lax.scan(
                    layer, jnp.zeros(q.shape, jnp.float32),
                    jnp.arange(c["layers"], dtype=jnp.int32))[0]
            return jax.jit(stack)

        fns = {run: make(run) for run in runs}
        for pad_mb in [int(x) for x in args.pads_mb.split(",")]:
            pad = jnp.zeros((pad_mb << 20,), jnp.int8).block_until_ready()
            key = jax.random.key(args.seed)
            pool_shape = (c["layers"], c["nb"], c["blk"], kvh, d)
            k_pool, v_pool = (
                jax.random.normal(kk, pool_shape, jnp.bfloat16)
                for kk in jax.random.split(key))
            q = jax.random.normal(
                jax.random.fold_in(key, 2), (c["b"], 1, c["h"], c["d"]),
                jnp.bfloat16)
            rs = np.random.RandomState(args.seed)
            tables = jnp.asarray(np.stack(
                [rs.permutation(c["nb"])[:c["p"]] for _ in range(c["b"])]),
                jnp.int32)
            for run, fn in fns.items():
                for case, sets in cases.items():
                    held = [sum(-(-n // c["blk"]) for n in ln) for ln in sets]
                    row = {"shape": name, "pad_mb": pad_mb, "run": run,
                           "lengths": case,
                           "pages_held_a_row": sum(held) / len(held) / c["b"]}
                    try:
                        secs = [timed(fn, q, k_pool, v_pool,
                                      jnp.asarray(ln, jnp.int32), tables)
                                for ln in sets]
                    except Exception as e:  # a run too long for VMEM
                        row["error"] = f"{type(e).__name__}: {e}"[:300]
                        report["rows"].append(row)
                        print(json.dumps(row), flush=True)
                        break
                    call_us = 1e6 * sum(secs) / len(secs) / c["layers"]
                    row["call_us"] = call_us
                    # the pages the rows hold over the time: what the call
                    # reaches of HBM's rate if it reads no more
                    row["held_gb_s"] = (sum(held) / len(held) * page_bytes
                                        / (call_us * 1e-6) / 1e9)
                    report["rows"].append(row)
                    print(json.dumps(row), flush=True)
            del k_pool, v_pool, pad
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
