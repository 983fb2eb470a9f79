"""Coordinator CLI: REPL parity with the reference's run_master.py.

Reference REPL (run_master.py:28-42): assign / distribute / inference / exit.
Here (same verbs kept, mesh semantics):
  init <model_id_or_path> [num_shards]  - fetch + convert + write shard store
                                          (initialize_model parity, :54-82)
  assign [num_shards]                   - plan shard->worker assignment
  distribute                            - workers load their shards (place)
  inference                             - prompt for text, generate, print
  status / metrics                      - registry + counters
  exit
Plus ``--local N``: spawn N in-process workers (the reference's planned
multiprocessing local-simulation mode, snippets.md:835-846 / plan.md:225-233,
which never landed).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

from ..checkpoint import convert, store
from ..checkpoint.download import fetch_model
from ..cluster.coordinator import Coordinator
from ..cluster.worker import WorkerHost
from ..core.config import Config, load_config
from ..core.observability import METRICS, get_logger
from . import init_backend

log = get_logger("cli")


async def _ainput(prompt: str) -> str:
    return await asyncio.to_thread(input, prompt)


def init_store(model_id: str, num_shards: int, cfg: Config) -> str:
    """Fetch checkpoint, convert to param tree, write the shard store."""
    local = fetch_model(model_id, cache_dir=cfg.checkpoint.cache_dir)
    with open(os.path.join(local, "config.json")) as f:
        model_cfg = convert.config_from_hf(json.load(f))
    params = convert.convert_state_dict(convert.load_state_dict(local), model_cfg)
    out_dir = cfg.checkpoint.shard_dir
    store.save_shards(
        params, out_dir, num_shards=num_shards, model_config=model_cfg,
        quantization=cfg.checkpoint.quantization,
        quant_block=cfg.checkpoint.quant_block_size,
        tokenizer_src=local,  # ship the model's own tokenizer with the store
    )
    print(f"sharded {model_id} -> {out_dir} ({num_shards} shards)")
    return out_dir


async def repl(coord: Coordinator, cfg: Config) -> None:
    print("commands: init <model> [shards] | assign [shards] [policy] | "
          "distribute | rebalance | inference | batch | status | metrics | exit")
    store_dir: str | None = None
    while True:
        try:
            line = (await _ainput("> ")).strip()
        except (EOFError, KeyboardInterrupt):
            break
        if not line:
            continue
        cmd, *rest = line.split()
        try:
            if cmd == "init":
                model_id = rest[0] if rest else cfg.model_id
                shards = int(rest[1]) if len(rest) > 1 else cfg.checkpoint.num_shards
                store_dir = init_store(model_id, shards, cfg)
            elif cmd == "assign":
                shards = int(rest[0]) if rest else cfg.checkpoint.num_shards
                policy = rest[1] if len(rest) > 1 else "capacity"
                plan = coord.plan_shards(
                    shards, store_dir=store_dir or cfg.checkpoint.shard_dir,
                    policy=policy,
                )
                print(json.dumps({str(k): v for k, v in plan.items()}, indent=1))
            elif cmd == "distribute":
                print(json.dumps(await coord.place_shards(), indent=1))
            elif cmd == "rebalance":
                plan = await coord.rebalance()
                print(json.dumps({str(k): v for k, v in plan.items()}, indent=1))
            elif cmd == "inference":
                text = await _ainput("prompt: ")
                out = await coord.generate([text])
                print(out["text"][0])
                print(f"[{out['generated_tokens']} tokens, "
                      f"{out['tokens_per_second']:.1f} tok/s]")
            elif cmd == "batch":
                # Mixed-budget batch: N lines of "<max_new_tokens> <prompt>",
                # blank line ends; served via continuous batching.
                print("one request per line: <max_new_tokens> <prompt>; "
                      "blank line runs the batch")
                reqs = []
                while True:
                    line2 = (await _ainput("req: ")).strip()
                    if not line2:
                        break
                    n_str, _, ptext = line2.partition(" ")
                    try:
                        n_new = int(n_str)
                    except ValueError:
                        n_new = 0
                    if n_new < 1 or not ptext.strip():
                        # Don't let one malformed line discard the batch.
                        print(f"expected '<max_new_tokens> <prompt>' with a "
                              f"positive budget, got {line2!r}; line skipped")
                        continue
                    reqs.append({"prompt": ptext, "max_new_tokens": n_new})
                if reqs:
                    out = await coord.generate_requests(reqs)
                    for i, t in enumerate(out["text"]):
                        print(f"[{i}] {t}")
                    print(f"[{out['generated_tokens']} tokens, "
                          f"{out['tokens_per_second']:.1f} tok/s]")
            elif cmd == "status":
                print(json.dumps(coord.status(), indent=1))
            elif cmd == "metrics":
                print(json.dumps(METRICS.snapshot(), indent=1))
            elif cmd in ("exit", "quit"):
                break
            else:
                print(f"unknown command {cmd!r}")
        except Exception as e:
            print(f"error: {e}")


async def amain(args: argparse.Namespace) -> None:
    import dataclasses

    cfg = load_config(args.config, args.override)
    ccfg = dataclasses.replace(
        cfg.cluster,
        coordinator_host=args.host or cfg.cluster.coordinator_host,
        coordinator_port=args.port if args.port is not None else cfg.cluster.coordinator_port,
        metrics_port=args.metrics_port if args.metrics_port is not None
        else cfg.cluster.metrics_port,
    )
    coord = Coordinator(ccfg)
    await coord.start()
    local_tasks = []
    procs = []
    if args.local:
        rt = cfg.runtime
        for _ in range(args.local):
            w = WorkerHost("127.0.0.1", coord.port, cfg=ccfg, rt=rt, mesh_cfg=cfg.mesh)
            local_tasks.append(asyncio.create_task(w.run()))
        log.info("spawned %d local in-process workers", args.local)
    if args.local_proc:
        # True process isolation (the reference's planned multiprocessing
        # local-simulation mode, plan.md:225-233): each worker is a separate
        # interpreter running the host entry point.
        import subprocess

        for i in range(args.local_proc):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "distributed_llms_tpu.cli.host_main",
                 "--host", "127.0.0.1", "--port", str(coord.port),
                 *(["--platform", args.platform] if args.platform else []),
                 *(["--config", args.config] if args.config else []),
                 *(x for ov in args.override for x in ("--override", ov))],
            ))
        log.info("spawned %d local worker processes", args.local_proc)
    expected = args.local + args.local_proc
    if expected:
        # Don't hand the REPL to the user (or a piped script) until the local
        # workers are actually registered — otherwise the first `assign`
        # races the registrations.
        for _ in range(600):
            if len(coord.workers) >= expected:
                break
            await asyncio.sleep(0.1)
        else:
            log.warning(
                "only %d/%d local workers registered", len(coord.workers), expected
            )
    try:
        if args.serve:
            # Headless daemon mode: containers/K8s have no interactive
            # stdin, and a REPL there would hit EOF and exit immediately.
            # As PID 1, Python's default SIGTERM action would kill the
            # interpreter before the finally-cleanup runs; catch it.
            import signal

            stop = asyncio.Event()
            loop = asyncio.get_running_loop()
            for sig in (signal.SIGTERM, signal.SIGINT):
                loop.add_signal_handler(sig, stop.set)
            log.info("serving headless (no REPL); SIGTERM/Ctrl-C stops")
            await stop.wait()
            log.info("stop signal received; shutting down")
        else:
            await repl(coord, cfg)
    finally:
        for t in local_tasks:
            t.cancel()
        for p in procs:
            p.terminate()
        await coord.stop()


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description="distributed-llms-tpu coordinator")
    ap.add_argument("--config", default=None, help="JSON/YAML config file")
    ap.add_argument("--override", action="append", default=[], metavar="K=V",
                    help="dotted config override, e.g. mesh.pipe=2")
    ap.add_argument("--host", default=None)
    ap.add_argument("--port", type=int, default=None)
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus /metrics (+/healthz, /status) here")
    ap.add_argument("--serve", action="store_true",
                    help="headless daemon mode (no REPL) — for containers/K8s "
                         "where stdin is closed; default is the interactive "
                         "REPL, which also accepts piped command scripts")
    ap.add_argument("--local", type=int, default=0, metavar="N",
                    help="spawn N in-process workers (local simulation)")
    ap.add_argument("--local-proc", type=int, default=0, metavar="N",
                    help="spawn N worker *processes* (isolated local simulation)")
    ap.add_argument("--platform", default=None, choices=["cpu", "tpu"],
                    help="force a JAX platform (e.g. cpu for a CPU-only host)")
    args = ap.parse_args(argv)
    if args.local_proc and (
        args.platform or os.environ.get("JAX_PLATFORMS")
    ) != "cpu":
        # Every worker process initialises JAX, and so does this one (a
        # checkpoint conversion, in-process workers).  An accelerator
        # belongs to one process at a time: the second to ask hangs or
        # fails, so refuse here instead.
        raise SystemExit(
            "--local-proc starts worker processes that each initialise "
            "JAX, and a chip belongs to one process at a time: run one "
            "dlt-host per chip, use --local N for in-process workers, or "
            "pin the simulation to the CPU with --platform cpu"
        )
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    init_backend()
    try:
        asyncio.run(amain(args))
    except KeyboardInterrupt:
        sys.exit(0)


if __name__ == "__main__":
    main()
