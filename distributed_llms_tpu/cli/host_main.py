"""Host-runner CLI: parity with the reference's run_worker.py (:12-23) —
connect to the coordinator, serve commands, clean stop on Ctrl-C."""

from __future__ import annotations

import argparse
import asyncio
import os
import sys

from ..cluster.distributed import initialize_distributed
from ..cluster.worker import WorkerHost
from ..core.config import load_config
from . import init_backend


async def amain(args: argparse.Namespace) -> None:
    cfg = load_config(args.config, args.override)
    initialize_distributed(cfg.cluster)
    init_backend()
    # CLI flags win when given; otherwise the config file decides.
    host = args.host if args.host is not None else cfg.cluster.coordinator_host
    port = args.port if args.port is not None else cfg.cluster.coordinator_port
    if host == "0.0.0.0":  # bind-any is not a connect address
        host = "localhost"
    worker = WorkerHost(host, port, cfg=cfg.cluster, rt=cfg.runtime, mesh_cfg=cfg.mesh)
    if args.worker_id:
        # Stable identity across restarts (e.g. the StatefulSet pod name):
        # the coordinator re-registers the same id, so shard assignment and
        # pinned tasks survive a host bounce.
        worker.worker_id = args.worker_id
    await worker.run()


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description="distributed-llms-tpu host runner")
    ap.add_argument("--config", default=None)
    ap.add_argument("--override", action="append", default=[], metavar="K=V")
    ap.add_argument("--host", default=None,
                    help="coordinator host (default: from config)")
    ap.add_argument("--port", type=int, default=None,
                    help="coordinator port (default: from config)")
    ap.add_argument("--platform", default=None, choices=["cpu", "tpu"],
                    help="force a JAX platform (e.g. cpu for a CPU-only host)")
    ap.add_argument("--worker-id", default=os.environ.get("DLT_WORKER_ID"),
                    help="stable worker identity to register under (default: "
                         "$DLT_WORKER_ID; unset -> coordinator assigns one)")
    args = ap.parse_args(argv)
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    try:
        asyncio.run(amain(args))
    except KeyboardInterrupt:
        print("stopping worker")
        sys.exit(0)


if __name__ == "__main__":
    main()
