#!/usr/bin/env python
"""Time ``paged_decode_attn`` (and, at ``ax-k1``, the latent kernel
``mla_paged_decode_attn``) alone on the chip: one call a layer under a
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
    python tools/paged_attn_bench.py --shapes ax-k1 --pads-mb 0 \
        --runs 0,1,2,4,8,12,16 --depths 1-36 --floors   # ISSUE 63's step 0

``--depths A-B`` puts every row at each depth of A..B pages (at the run the
kernel works out: a stair with a step a run says the time follows the runs
a row takes, a line the pages it holds); ``--floors`` times the walk with
its copies alone and with its products alone (the other half traced away);
``--blocks`` the latent body at other blocks than the kernel's own.

``--tree`` names the checkout whose kernel is timed: a tree from before the
kernel walked runs takes no run length and is timed as it is.  No chip, no
time: on the CPU the script runs the interpreter at a toy size to check
itself (``--rehearsal``).
"""
from __future__ import annotations

import argparse
import contextlib
import functools
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
    # Latent pages (a row of ``w`` lanes, its first ``latent`` the values):
    # one chip's 13 layers of A.X-K1, 64 heads.
    "ax-k1": dict(b=64, layers=13, nb=2176, blk=64, w=640, latent=512, h=64,
                  p=64, traffic=("long-answers",)),
}
REHEARSAL = {
    "rehearsal": dict(b=2, layers=2, nb=12, blk=8, kvh=2, d=128, h=4, p=5,
                      traffic=("rehearsal-chat",)),
    "rehearsal-latent": dict(b=2, layers=2, nb=12, blk=8, w=256, latent=128,
                             h=8, p=5, traffic=("rehearsal-chat",)),
}
# The functions a walk computes a run with, by the kernel that calls them
# (a tree has those of its day): traced away, the walk's copies are left.
BODIES = ("_softmax_all_heads", "_softmax_block", "_latent_update")


class _NoCopies:
    """``pltpu`` as the kernel sees it, its copies started and awaited by
    nobody: the walk's products on whatever the buffers hold."""

    class _Copy:
        def start(self):
            pass

        wait = start

    def __init__(self, real):
        self._real = real

    def __getattr__(self, name):
        return getattr(self._real, name)

    def make_async_copy(self, *_):
        return self._Copy()


@contextlib.contextmanager
def traced_as(decode_attn, floor: str | None, block: int | None):
    """The kernel's module while one variant of the walk is traced: whole
    (None), ``copies`` (no products), ``products`` (no copies), and the
    latent body at ``block`` pages where the tree has a block."""
    saved = {n: getattr(decode_attn, n)
             for n in (*BODIES, "pltpu", "_LATENT_BLOCK_PAGES")
             if hasattr(decode_attn, n)}
    try:
        if floor == "copies":
            for n in BODIES:
                if n in saved:
                    setattr(decode_attn, n, lambda *a, **k: None)
        if floor == "products":
            decode_attn.pltpu = _NoCopies(saved["pltpu"])
        if block:
            decode_attn._LATENT_BLOCK_PAGES = block
        yield
    finally:
        for n, v in saved.items():
            setattr(decode_attn, n, v)


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
    ap.add_argument("--depths", default="",
                    help="A-B: every row at each depth of A..B pages "
                    "(default: 1, an eighth, a half, all of a row's slots)")
    ap.add_argument("--floors", action="store_true",
                    help="also the walk's copies alone and products alone")
    ap.add_argument("--blocks", default="",
                    help="pages a block of the latent body, where the "
                    "tree's kernel has one (default: its own)")
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
    has_block = hasattr(decode_attn, "_LATENT_BLOCK_PAGES")
    blocks = ([int(x) for x in args.blocks.split(",")]
              if args.blocks and has_block else [None])
    shapes = (REHEARSAL if args.rehearsal
              else {n: SHAPES[n] for n in args.shapes.split(",")})
    reps = 20 if on_tpu else 1
    dev = jax.devices()[0]
    report = {"device": {"platform": dev.platform,
                         "device_kind": dev.device_kind},
              "tree": os.path.abspath(args.tree), "takes_run": takes_run,
              "latent_block_pages": getattr(
                  decode_attn, "_LATENT_BLOCK_PAGES", None),
              "rows": []}

    def timed(fn, *a):
        """Seconds a call of the compiled ``fn``: ``reps`` back to back."""
        fn(*a).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*a)
        out.block_until_ready()
        return (time.perf_counter() - t0) / reps

    for name, c in shapes.items():
        latent = c.get("latent")
        if latent:
            page_bytes = c["blk"] * c["w"] * 2
            pool_shape = (c["layers"], c["nb"], c["blk"], c["w"])
            q_shape = (c["b"], 1, c["h"], c["w"])
        else:
            kvh, d = decode_attn.pool_head_shape(c["kvh"], c["d"], True)
            page_bytes = 2 * c["blk"] * kvh * d * 2
            pool_shape = (c["layers"], c["nb"], c["blk"], kvh, d)
            q_shape = (c["b"], 1, c["h"], c["d"])
        cap = c["p"] * c["blk"]
        cases = {t: drawn_lengths(t, c["b"], args.sets, cap, args.seed)
                 for t in c["traffic"]}
        # Every row at one depth: 1, an eighth, a half, all of its slots.
        depths = sorted({1, max(c["p"] // 8, 1), c["p"] // 2, c["p"]})
        if args.depths:
            lo, hi = (int(x) for x in args.depths.split("-"))
            depths = range(lo, min(hi, c["p"]) + 1)
        uniform = {f"all-{pages}-pages": [[pages * c["blk"]] * c["b"]]
                   for pages in depths}

        def stack(run, q, k_pool, v_pool, lengths, tables):
            kw = {} if run is None else {"run": run or None}

            def layer(acc, i):
                if latent:
                    out = decode_attn._mla_paged_impl(
                        q, k_pool, lengths, tables, i.reshape(1),
                        latent=latent, scale=c["w"] ** -0.5, mode=mode, **kw)
                else:
                    out = decode_attn._paged_impl(
                        q, k_pool, v_pool, lengths, tables, i.reshape(1),
                        mode=mode, **kw)
                return acc + out.astype(jnp.float32), None
            return jax.lax.scan(
                layer, jnp.zeros((*q.shape[:3], latent or q.shape[3]),
                                 jnp.float32),
                jnp.arange(c["layers"], dtype=jnp.int32))[0]

        # The variants of the walk: whole at every run (and block); the two
        # floors at the run (and block) the kernel works out.
        variants = [(run, None, blk_) for run in runs
                    for blk_ in (blocks if latent else [None])]
        if args.floors:
            variants += [(0 if takes_run else None, floor, None)
                         for floor in ("copies", "products")]
        for pad_mb in [int(x) for x in args.pads_mb.split(",")]:
            pad = jnp.zeros((pad_mb << 20,), jnp.int8).block_until_ready()
            key = jax.random.key(args.seed)
            k_pool, v_pool = (
                jax.random.normal(kk, pool_shape, jnp.bfloat16)
                for kk in jax.random.split(key))
            if latent:
                v_pool = None
            q = jax.random.normal(
                jax.random.fold_in(key, 2), q_shape, jnp.bfloat16)
            rs = np.random.RandomState(args.seed)
            tables = jnp.asarray(np.stack(
                [rs.permutation(c["nb"])[:c["p"]] for _ in range(c["b"])]),
                jnp.int32)
            for run, floor, block in variants:
                # The uniform depths at the kernel's own run, whole and by
                # floors; the drawn rows at every variant.
                todo = dict(cases)
                if not run or floor:
                    todo.update(uniform)
                fn = None
                for case, sets in todo.items():
                    held = [sum(-(-n // c["blk"]) for n in ln) for ln in sets]
                    row = {"shape": name, "pad_mb": pad_mb, "run": run,
                           "lengths": case,
                           "pages_held_a_row": sum(held) / len(held) / c["b"]}
                    if floor:
                        row["floor"] = floor
                    if block:
                        row["block"] = block
                    try:
                        if fn is None:
                            first = jnp.asarray(sets[0], jnp.int32)
                            with traced_as(decode_attn, floor, block):
                                fn = jax.jit(
                                    functools.partial(stack, run)).lower(
                                    q, k_pool, v_pool, first, tables
                                ).compile()
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
                    row["row_us"] = call_us / c["b"]
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
