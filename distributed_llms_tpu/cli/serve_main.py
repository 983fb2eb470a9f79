"""Standalone HTTP serving entry point.

``python -m distributed_llms_tpu.cli.serve_main --store ./store_7b --port 8000``
boots an InferenceEngine from a shard store, wraps its continuous batcher in
the OpenAI-style HTTP gateway (runtime/server.py), and serves until SIGTERM/
SIGINT.  This is the single-process serving front door; the cluster path
(cli/coordinator_main.py --serve) remains the multi-worker one.

The reference has no serving entry point at all — its user interface is the
master REPL (run_master.py:28-42).
"""

from __future__ import annotations

import argparse
import asyncio
import os
import signal

from ..core.config import load_config
from ..core.observability import get_logger
from . import init_backend
from ..runtime.engine import InferenceEngine
from ..runtime.server import InferenceServer

log = get_logger("serve_main")

# Flag <-> config contract, pinned by graftlint (GL303): every dlt-serve
# flag is declared in exactly one of these two tables.  _RUNTIME_FLAGS
# maps a flag to the RuntimeConfig field it shadows (the flag wins when
# given; the field is the config-file/--override spelling) — field
# existence is checked against core/config.py, so a rename there breaks
# the gate here instead of silently orphaning the flag.
_RUNTIME_FLAGS: dict[str, str] = {
    "max-len": "max_seq_len",
    "paged-pages": "paged_pages",
    "page-size": "page_size",
    "prefix-cache": "prefix_cache",
    "kv-bits": "kv_bits",
    "host-pages": "host_pages",
    "overlap": "overlap",
    "schedule": "schedule",
    "token-budget": "token_budget",
    "request-timeout": "request_timeout_s",
    "shed-cost-factor": "shed_cost_factor",
    "constrained": "constrained_decoding",
    "constrain-cache": "constrain_cache_size",
    "spec-decode": "spec_decode",
    "spec-k": "spec_k",
    "spec-adaptive-k": "spec_adaptive_k",
    "tenant-weights": "tenant_weights",
    "tenant-quota-tps": "tenant_quota_tps",
    "tenant-max-rows": "tenant_max_rows",
    "fault": "faults",
}
# Server plumbing with no RuntimeConfig twin (transport, process, and
# batcher-shape knobs that only make sense per serving process).
_SERVER_ONLY_FLAGS = frozenset({
    "store", "preset", "config", "override", "host", "port", "model-name",
    "slots", "chunk-steps", "prefill-chunk", "prefill-concurrency",
    "max-pending", "drain-timeout", "watchdog-timeout", "platform",
    "replicas", "probe-interval", "failover-retries",
    "disaggregate", "prefill-replicas", "decode-replicas",
    "prefill-replicas-max", "decode-replicas-max",
    "replicas-min", "replicas-max", "autoscale-interval",
    "autoscale-up-load", "autoscale-down-load", "autoscale-cooldown",
    "autoscale-hysteresis",
})


def _build_engine(args):
    """Shared boot: config + fault plane + engine.  Returns
    (engine, default model name, runtime config, fault plane, fault
    spec) — fleet mode re-parses the spec into a plane PER REPLICA."""
    cfg = load_config(args.config, args.override)
    rt = cfg.runtime
    # Speculative knobs must land on the RuntimeConfig BEFORE the engine
    # builds: the engine attaches its self-draft at construction from
    # rt.spec_decode (flag wins when given; the field is the config-file
    # spelling, like every _RUNTIME_FLAGS entry).
    spec_overrides = {
        field: val for field, val in (
            ("spec_decode", args.spec_decode),
            ("spec_k", args.spec_k),
            ("spec_adaptive_k", args.spec_adaptive_k),
        ) if val is not None
    }
    if spec_overrides:
        import dataclasses

        rt = dataclasses.replace(rt, **spec_overrides)
    # Parse the fault spec BEFORE the (slow) engine build: an operator's
    # typo'd site must fail the boot in milliseconds, not after a full
    # model load.  strict=True checks sites against FAULT_SITES — a rule
    # that could never fire is config drift, not a no-op.
    faults = None
    fault_spec = ",".join(args.fault or []) or rt.faults
    if fault_spec:
        from ..runtime.faults import FaultPlane

        faults = FaultPlane.parse(fault_spec, strict=True)
        log.warning("fault injection armed: %s", faults.describe())
    if args.store:
        engine = InferenceEngine.from_store(args.store, rt=rt, mesh_cfg=cfg.mesh)
        default_name = os.path.basename(os.path.normpath(args.store))
    elif args.preset:
        # Random weights from a seed (no checkpoint needed): exercises the
        # full HTTP/batcher/decode path with a byte-level tokenizer, at
        # full width when runtime.serve_quantized +
        # checkpoint.quantization say to generate the blocks quantized.
        # Tiny presets (vocab 256) cannot hold the byte tokenizer's
        # specials (259 ids) — widen to a lane-aligned 512.
        from ..models.presets import get_preset
        from ..runtime.tokenizer import ByteTokenizer

        overrides = (
            {"vocab_size": 512}
            if get_preset(args.preset).vocab_size < ByteTokenizer.vocab_size
            else {}
        )
        engine = InferenceEngine.from_preset(
            args.preset, rt=rt, mesh_cfg=cfg.mesh,
            quantization=cfg.checkpoint.quantization, **overrides,
        )
        default_name = args.preset
    else:
        raise SystemExit("one of --store or --preset is required")
    return engine, default_name, rt, faults, fault_spec


def _server_factory(args, engine, default_name, rt, faults, *,
                    host=None, port=None, role="colocated",
                    backstop_x=None):
    """() -> a fresh, unstarted InferenceServer over a fresh batcher.
    Replicas share the engine's weights by reference; each gets its own
    pool/caches/supervisor."""

    def make_batcher():
        # Called at boot and again by the supervisor after an engine
        # crash: a respawn must share the already-armed fault plane (rules
        # that fired stay fired) while rebuilding pool + caches fresh.
        return engine.continuous_batcher(
            batch_slots=args.slots,
            max_len=args.max_len,
            chunk_steps=args.chunk_steps,
            prefill_chunk=args.prefill_chunk,
            prefill_concurrency=args.prefill_concurrency,
            paged_pages=args.paged_pages,
            page_size=args.page_size,
            prefix_cache=args.prefix_cache,
            kv_bits=args.kv_bits,
            host_pages=args.host_pages,
            overlap=(None if args.overlap is None
                     else args.overlap == "on"),
            schedule=args.schedule,
            token_budget=args.token_budget,
            tenant_weights=args.tenant_weights,
            tenant_max_rows=args.tenant_max_rows,
            faults=faults,
        )

    # Size the compiled-constraint LRU once per serving process (the
    # cache is module-level: replicas and respawns share remembered
    # automata by design).
    from ..runtime import constrain as constrain_lib

    constrain_lib.configure_cache(
        args.constrain_cache if args.constrain_cache is not None
        else rt.constrain_cache_size
    )

    # Tenant QoS (the gateway half): flag wins, config-file field is the
    # fallback, exactly like every _RUNTIME_FLAGS knob.  Weights parse
    # ONCE here so a typo'd spec fails the boot in milliseconds.
    from ..runtime.scheduler import parse_tenant_weights

    tenant_weights = parse_tenant_weights(
        args.tenant_weights if args.tenant_weights is not None
        else rt.tenant_weights
    )
    tenant_quota_tps = (args.tenant_quota_tps
                        if args.tenant_quota_tps is not None
                        else rt.tenant_quota_tps)

    def make_server():
        return InferenceServer(
            make_batcher(),
            model_name=args.model_name or default_name,
            host=args.host if host is None else host,
            port=args.port if port is None else port,
            max_pending=args.max_pending,
            batcher_factory=make_batcher,
            request_timeout_s=(args.request_timeout
                               if args.request_timeout is not None
                               else rt.request_timeout_s),
            watchdog_timeout_s=args.watchdog_timeout,
            shed_cost_factor=(args.shed_cost_factor
                              if args.shed_cost_factor is not None
                              else rt.shed_cost_factor),
            role=role,
            constrained=(args.constrained if args.constrained is not None
                         else rt.constrained_decoding),
            tenant_weights=tenant_weights,
            tenant_quota_tps=tenant_quota_tps,
            tenant_backstop_x=backstop_x,
        )

    return make_server


def build_server(args) -> InferenceServer:
    engine, default_name, rt, faults, _spec = _build_engine(args)
    return _server_factory(args, engine, default_name, rt, faults)()


def build_fleet(args):
    """``--replicas N`` (N >= 2) or ``--disaggregate``: full
    server/batcher stacks on ephemeral local ports behind a health-aware
    ReplicaRouter on --host/--port — exact failover, rolling
    drain/respawn (SIGHUP), and replica-scoped chaos via the --fault
    spec.  ``--disaggregate`` builds --prefill-replicas prefill-role +
    --decode-replicas decode-role stacks instead of N colocated ones;
    the router hands prompts to the prefill tier and ships finished KV
    pages to the decode replica before forwarding (degrading to
    colocated prefill whenever the handoff cannot complete).
    ``--replicas-min/--replicas-max`` boot an ELASTIC colocated fleet:
    replicas-min stacks now, a signal-driven autoscaler
    (cluster/autoscale.py) growing to replicas-max on router
    committed-token load and shrinking back via graceful drain only.
    With ``--disaggregate``, ``--replicas-max`` (or the per-tier
    ``--prefill-replicas-max``/``--decode-replicas-max``) arms the
    TIERED autoscaler instead: each tier scales independently between
    its boot count and its ceiling — prefill on handoff queue depth,
    decode on committed-token mass.  In every fleet mode the ROUTER
    owns the tenant rate ledger (one admission-commit point, so a
    fleet of N admits 1x quota); replica gateways keep a loose 2x
    backstop so a bypassed router gate never leaves an unmetered path.
    Returns (fleet, router, autoscaler-or-None)."""
    from ..cluster.autoscale import Autoscaler, TieredAutoscaler, TierPolicy
    from ..cluster.fleet import ReplicaFleet
    from ..runtime.router import ReplicaRouter
    from ..runtime.scheduler import parse_tenant_weights

    engine, default_name, rt, faults, fault_spec = _build_engine(args)

    def replica_factory(role="colocated"):
        # Each replica gets its OWN plane parsed from the same spec: the
        # batcher.*/server-side rule counters are traversed by that
        # replica's engine thread alone (FaultPlane's thread contract),
        # and @N windows count per replica — sharing the fleet's plane
        # across N engine threads would race the counters and let a
        # replica-scoped stall drill wedge whichever replica decodes
        # next.  The shared ``faults`` plane keeps the replica.*/router.*
        # sites, which only the event loop traverses.
        plane = None
        if fault_spec:
            from ..runtime.faults import FaultPlane

            plane = FaultPlane.parse(fault_spec, strict=True)
        # backstop_x: behind a router the replica gateway is NOT the
        # admission-commit point — the router's fleet ledger is.  The
        # replica keeps a loose ~2x-fair-share backstop so a drilled or
        # bypassed router gate still meters (never a silent unmetered
        # path), without double-shedding honest traffic the router
        # already admitted.
        return _server_factory(args, engine, default_name, rt, plane,
                               host="127.0.0.1", port=0, role=role,
                               backstop_x=2.0)()

    if args.disaggregate:
        if args.prefill_replicas < 1 or args.decode_replicas < 1:
            raise SystemExit(
                "--disaggregate needs --prefill-replicas >= 1 and "
                "--decode-replicas >= 1"
            )
        paged = args.paged_pages if args.paged_pages is not None \
            else rt.paged_pages
        cache_on = args.prefix_cache if args.prefix_cache is not None \
            else rt.prefix_cache
        if not paged or not cache_on:
            raise SystemExit(
                "--disaggregate ships content-addressed KV pool pages: it "
                "needs --paged-pages and --prefix-cache on every replica"
            )
        import functools

        factories = (
            [functools.partial(replica_factory, "prefill")]
            * args.prefill_replicas
            + [functools.partial(replica_factory, "decode")]
            * args.decode_replicas
        )
        names = [f"p{i}" for i in range(args.prefill_replicas)] \
            + [f"d{i}" for i in range(args.decode_replicas)]
    else:
        n = args.replicas_min if args.replicas_max else args.replicas
        factories = [replica_factory] * n
        names = None
    fleet = ReplicaFleet(
        factories, names=names,
        probe_interval_s=args.probe_interval,
        faults=faults,
    )
    # The router is the fleet's one admission-commit point: the tenant
    # rate ledger lives HERE (quota conserved at any fleet size), with
    # the same flag-wins-else-config resolution the gateways use.
    tenant_weights = parse_tenant_weights(
        args.tenant_weights if args.tenant_weights is not None
        else rt.tenant_weights
    )
    tenant_quota_tps = (args.tenant_quota_tps
                        if args.tenant_quota_tps is not None
                        else rt.tenant_quota_tps)
    router = ReplicaRouter(
        fleet, host=args.host, port=args.port,
        tokenizer=engine.tokenizer,
        page_size=(args.page_size or rt.page_size or 64),
        max_failover_retries=args.failover_retries,
        faults=faults,
        handoff=bool(args.disaggregate),
        # Affinity/handoff digests must match the fleet's pool digests,
        # which are salted by the KV width (--kv-bits) — a mismatched
        # salt would read as a digest mismatch on every handoff.
        kv_bits=(args.kv_bits if args.kv_bits is not None else rt.kv_bits),
        tenant_weights=tenant_weights,
        tenant_quota_tps=tenant_quota_tps,
    )
    autoscaler = None
    if args.disaggregate:
        # Tier ceilings: the per-tier flag wins, --replicas-max is the
        # shared spelling, the boot count means "fixed tier".
        p_max = (args.prefill_replicas_max or args.replicas_max
                 or args.prefill_replicas)
        d_max = (args.decode_replicas_max or args.replicas_max
                 or args.decode_replicas)
        if p_max < args.prefill_replicas or d_max < args.decode_replicas:
            raise SystemExit(
                f"tier ceiling below its boot count: prefill "
                f"{args.prefill_replicas}..{p_max}, decode "
                f"{args.decode_replicas}..{d_max}"
            )
        if p_max > args.prefill_replicas or d_max > args.decode_replicas:
            import functools

            autoscaler = TieredAutoscaler(
                fleet,
                prefill=TierPolicy(
                    min_replicas=args.prefill_replicas,
                    max_replicas=p_max,
                    up_load=args.autoscale_up_load,
                    down_load=args.autoscale_down_load,
                    hysteresis=args.autoscale_hysteresis,
                    cooldown_s=args.autoscale_cooldown,
                ),
                decode=TierPolicy(
                    min_replicas=args.decode_replicas,
                    max_replicas=d_max,
                    up_load=args.autoscale_up_load,
                    down_load=args.autoscale_down_load,
                    hysteresis=args.autoscale_hysteresis,
                    cooldown_s=args.autoscale_cooldown,
                ),
                prefill_factory=functools.partial(replica_factory,
                                                  "prefill"),
                decode_factory=functools.partial(replica_factory,
                                                 "decode"),
                interval_s=args.autoscale_interval,
                drain_timeout_s=args.drain_timeout,
                faults=faults,
            )
    elif args.replicas_max:
        if args.replicas_max < args.replicas_min:
            raise SystemExit(
                f"--replicas-max {args.replicas_max} < --replicas-min "
                f"{args.replicas_min}"
            )
        if args.replicas != 1:
            raise SystemExit(
                "--replicas fixes the fleet size; an elastic fleet is "
                "sized by --replicas-min/--replicas-max"
            )
        autoscaler = Autoscaler(
            fleet,
            min_replicas=args.replicas_min,
            max_replicas=args.replicas_max,
            interval_s=args.autoscale_interval,
            up_load=args.autoscale_up_load,
            down_load=args.autoscale_down_load,
            hysteresis=args.autoscale_hysteresis,
            cooldown_s=args.autoscale_cooldown,
            drain_timeout_s=args.drain_timeout,
            faults=faults,
        )
    return fleet, router, autoscaler


async def _serve(args) -> None:
    stop = asyncio.Event()
    force = asyncio.Event()
    loop = asyncio.get_running_loop()

    def on_signal():
        # First signal: graceful drain.  Second: cut the drain short.
        (force if stop.is_set() else stop).set()

    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, on_signal)
    if args.replicas > 1 or args.disaggregate or args.replicas_max:
        fleet, router, autoscaler = build_fleet(args)
        await fleet.start()
        host, port = await router.start()
        # Replicas boot in state "starting" and only a healthy probe makes
        # them routable — announce ready only once the fleet can actually
        # place work, or the first requests shed 503 off an idle fleet.
        if not await fleet.wait_healthy(timeout_s=60.0):
            log.warning(
                "not every replica probed healthy within 60s; serving with "
                "%d routable", fleet.report()["healthy"],
            )
        # SIGHUP: zero-downtime rolling restart of the whole fleet, one
        # replica at a time (config reload drills, binary swaps).  One
        # restart at a time: a second SIGHUP mid-walk would interleave
        # two drain/respawn passes over the same handles — overwriting
        # h.server orphans a freshly-booted replica (leaked socket +
        # engine thread + pool) and can leave every replica draining at
        # once.  Failures must surface, not die as unretrieved task
        # exceptions.
        restart_task: list[asyncio.Task | None] = [None]

        def on_hup():
            t = restart_task[0]
            if t is not None and not t.done():
                log.warning("SIGHUP ignored: a rolling restart is "
                            "already in progress")
                return

            async def run():
                try:
                    await fleet.rolling_restart(
                        drain_timeout_s=args.drain_timeout
                    )
                    log.info("rolling restart complete")
                except Exception:
                    log.exception("rolling restart failed")

            restart_task[0] = asyncio.ensure_future(run())

        loop.add_signal_handler(signal.SIGHUP, on_hup)
        if autoscaler is not None:
            # Flat or tiered — each logs its own bounds in start().
            await autoscaler.start()
        placed = {
            h.name: h.server.device["weights_on"] for h in fleet.replicas
        }
        log.info("replica weights on devices: %s", placed)
        n_dev = fleet.replicas[0].server.device["count"]
        if n_dev > 1 and len({tuple(d) for d in placed.values()}) < len(placed):
            # Replicas share the engine's weights by reference, so they
            # all compute on the same device(s): N replicas here are not
            # N chips, and a number taken from them is not an N-chip one.
            log.warning(
                "%d replicas share devices on a %d-device host (%s): the "
                "other devices idle unless a mesh spans them",
                len(placed), n_dev, placed,
            )
        log.info("fleet of %d ready on http://%s:%s (SIGHUP = rolling "
                 "restart; Ctrl-C to stop)", len(fleet.replicas), host, port)
        await stop.wait()
        log.info("shutting down fleet...")
        if autoscaler is not None:
            await autoscaler.stop()
        await router.stop()
        await fleet.stop()
        return
    server = build_server(args)
    host, port = await server.start()
    log.info("weights on devices %s", server.device["weights_on"])
    log.info("ready on http://%s:%s (Ctrl-C to stop)", host, port)
    await stop.wait()
    log.info("shutting down (draining up to %.0fs; signal again to force)...",
             args.drain_timeout)
    drain = asyncio.create_task(server.stop(drain_timeout=args.drain_timeout))
    forcer = asyncio.create_task(force.wait())
    await asyncio.wait({drain, forcer}, return_when=asyncio.FIRST_COMPLETED)
    if not drain.done():
        log.info("second signal: forcing immediate shutdown")
        server.force_stop()
    await drain
    forcer.cancel()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--store", default=None, help="shard store directory")
    ap.add_argument("--preset", default=None,
                    help="serve a random-weight preset (smoke testing)")
    ap.add_argument("--config", default=None, help="JSON/YAML config file")
    ap.add_argument("--override", action="append", default=[],
                    help="dotted config override, e.g. runtime.temperature=0.7")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--model-name", default=None,
                    help="name reported by /v1/models (default: store/preset)")
    ap.add_argument("--slots", type=int, default=8,
                    help="continuous-batching row slots")
    ap.add_argument("--max-len", type=int, default=None,
                    help="per-row cache length (default: runtime.max_seq_len)")
    ap.add_argument("--chunk-steps", type=int, default=8,
                    help="decode steps per scheduling chunk")
    ap.add_argument("--paged-pages", type=int, default=None,
                    help="paged KV: size of the shared page pool (pages); "
                         "rows allocate only what prompt+budget need and a "
                         "dry pool back-pressures admission (default: "
                         "runtime.paged_pages; 0 forces contiguous)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="paged KV: tokens per page (default: "
                         "runtime.page_size)")
    ap.add_argument("--kv-bits", type=int, default=None, choices=[16, 8],
                    help="KV pool width: 8 stores pages as int8 with "
                         "blockwise absmax scales (~1.9x concurrent rows "
                         "per pool byte; greedy outputs parity-bounded, "
                         "not bit-exact).  Needs --paged-pages.  Default: "
                         "runtime.kv_bits (16)")
    ap.add_argument("--host-pages", type=int, default=None,
                    help="host-RAM KV tier size in pages: preemption swaps "
                         "rows out (byte-exact restore) and cold cached "
                         "pages spill before eviction.  Needs "
                         "--paged-pages.  Default: runtime.host_pages (0)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="automatic prefix caching over the paged pool: "
                         "full prompt pages are content-hashed and reused "
                         "copy-free across requests (refcounted pages, LRU "
                         "eviction under pool pressure); needs --paged-pages."
                         "  Per-request opt-out: \"prefix_cache\": false.  "
                         "(default: runtime.prefix_cache)")
    ap.add_argument("--overlap", choices=["on", "off"], default=None,
                    help="dispatch-ahead engine loop: while no scheduling "
                         "work is pending, decode chunk N+1 dispatches "
                         "from the device-resident carry and chunk N's "
                         "host work (delivery, digest hashing, metrics) "
                         "overlaps its device execution.  Temp-0 bytes "
                         "identical on or off; gauges under "
                         "batcher_overlap_* on /metrics (default: "
                         "runtime.overlap, on)")
    ap.add_argument("--schedule", choices=["mixed", "alternate"],
                    default=None,
                    help="scheduling policy (runtime/scheduler.py): "
                         "'mixed' fuses pending prefill-chunk bites into "
                         "the decode step as one token-budget program so "
                         "decode rows never stall for a serialized "
                         "prefill forward; 'alternate' keeps the classic "
                         "serialized rounds.  Temp-0 bytes identical "
                         "either way (default: runtime.schedule, mixed)")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="per-step token budget of the mixed schedule: "
                         "each fused step runs one decode leg per active "
                         "slot plus up to budget - n_active prefill "
                         "tokens; prompts longer than the budget "
                         "auto-chunk.  0 = prefill-chunk-sized bites "
                         "(default: runtime.token_budget)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill: admit at most this many prompt "
                         "tokens per scheduling round per pending prefill, "
                         "so long prompts never stall in-flight decodes "
                         "(default: monolithic admission)")
    ap.add_argument("--prefill-concurrency", type=int, default=2,
                    help="chunked prefills in flight at once — two long "
                         "prompts interleave their admissions instead of "
                         "serializing (1 restores the old one-at-a-time "
                         "limit; per-round prefill work is bounded by "
                         "prefill-chunk x this)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="replica-fleet serving: run N independent "
                         "server/batcher stacks (each with its own "
                         "supervisor and KV pool) behind a health-aware "
                         "router on --port — exact failover on replica "
                         "crash/stall/partition, SIGHUP = zero-downtime "
                         "rolling restart (1 = single-server mode)")
    ap.add_argument("--disaggregate", action="store_true",
                    help="disaggregated serving: dedicated prefill-role "
                         "replicas run admission/chunked prefill and ship "
                         "finished KV pages to decode-role replicas over "
                         "the verified KV-handoff plane; any handoff "
                         "failure (prefill crash/stall, digest mismatch, "
                         "retry exhaustion) degrades to colocated prefill "
                         "on the decode replica, byte-exact either way.  "
                         "Requires --paged-pages and --prefix-cache; "
                         "ignores --replicas")
    ap.add_argument("--prefill-replicas", type=int, default=1,
                    help="prefill-role replicas under --disaggregate "
                         "(the tier's floor when a ceiling arms the "
                         "tiered autoscaler)")
    ap.add_argument("--decode-replicas", type=int, default=2,
                    help="decode-role replicas under --disaggregate "
                         "(the tier's floor when a ceiling arms the "
                         "tiered autoscaler)")
    ap.add_argument("--prefill-replicas-max", type=int, default=None,
                    help="elastic prefill-tier ceiling under "
                         "--disaggregate: the tiered autoscaler grows "
                         "the tier on handoff queue depth and shrinks "
                         "it via graceful drain, never below "
                         "--prefill-replicas (default: --replicas-max, "
                         "else fixed at the boot count)")
    ap.add_argument("--decode-replicas-max", type=int, default=None,
                    help="elastic decode-tier ceiling under "
                         "--disaggregate: scales on committed-token "
                         "mass over tier KV capacity, never below "
                         "--decode-replicas (default: --replicas-max, "
                         "else fixed at the boot count)")
    ap.add_argument("--replicas-min", type=int, default=1,
                    help="elastic fleet floor: boot this many colocated "
                         "replicas and never drain below it (used with "
                         "--replicas-max; the autoscaler scales between "
                         "the two on router committed-token load)")
    ap.add_argument("--replicas-max", type=int, default=None,
                    help="elastic fleet ceiling: arm the autoscaler "
                         "(cluster/autoscale.py) to grow the colocated "
                         "fleet up to this many replicas under load and "
                         "shrink back via graceful drain — in-flight "
                         "requests finish byte-exact, stragglers migrate "
                         "through the router's exact failover.  With "
                         "--disaggregate this is the PER-TIER ceiling "
                         "(each tier scales independently between its "
                         "boot count and this; --prefill/--decode-"
                         "replicas-max override per tier).  Unset = "
                         "fixed-size fleet")
    ap.add_argument("--autoscale-interval", type=float, default=1.0,
                    help="autoscaler tick cadence in seconds")
    ap.add_argument("--autoscale-up-load", type=float, default=0.8,
                    help="scale up when committed-token load (fraction "
                         "of aggregate KV capacity) stays above this")
    ap.add_argument("--autoscale-down-load", type=float, default=0.25,
                    help="scale down when load stays below this")
    ap.add_argument("--autoscale-hysteresis", type=int, default=3,
                    help="consecutive ticks past a threshold before the "
                         "autoscaler acts (noise filter)")
    ap.add_argument("--autoscale-cooldown", type=float, default=10.0,
                    help="quiet seconds after every scale action (or "
                         "failed attempt) before the next one")
    ap.add_argument("--tenant-weights", default=None,
                    help="multi-tenant weighted-fair serving: "
                         "\"gold:4,free:1\"-style shares (\"*\" sets the "
                         "default weight).  Requests carry X-Tenant (or "
                         "a \"tenant\" body field); admission serves "
                         "tenants by virtual token counter — a flooding "
                         "tenant cannot crowd out a lighter one's share "
                         "(default: runtime.tenant_weights)")
    ap.add_argument("--tenant-quota-tps", type=float, default=None,
                    help="per-tenant token-rate quota at the gateway: "
                         "admitted prompt+budget tokens/s per unit "
                         "weight; a tenant over its rate sheds 429 with "
                         "its OWN Retry-After (0 disables; default: "
                         "runtime.tenant_quota_tps)")
    ap.add_argument("--tenant-max-rows", type=int, default=None,
                    help="per-tenant resident-row cap in the batcher: a "
                         "tenant at the cap defers admission while "
                         "others admit past it (0 = uncapped; default: "
                         "runtime.tenant_max_rows)")
    ap.add_argument("--probe-interval", type=float, default=0.25,
                    help="fleet health-probe interval in seconds "
                         "(replica /healthz polling cadence)")
    ap.add_argument("--failover-retries", type=int, default=2,
                    help="router failover budget: how many other replicas "
                         "a zero-streamed request may be re-sent to after "
                         "a replica failure before answering 503 + "
                         "Retry-After")
    ap.add_argument("--max-pending", type=int, default=256,
                    help="in-flight request cap before 429s")
    ap.add_argument("--drain-timeout", type=float, default=30.0,
                    help="graceful shutdown: seconds to let in-flight "
                         "requests finish before cancelling (0 = immediate)")
    ap.add_argument("--request-timeout", type=float, default=None,
                    help="default per-request deadline in seconds: an "
                         "expired request cancels at the next chunk and "
                         "returns finish_reason \"timeout\" with its "
                         "partial output; a request's own timeout_s field "
                         "wins (default: runtime.request_timeout_s)")
    ap.add_argument("--shed-cost-factor", type=float, default=None,
                    help="estimated-cost admission gate: 429 (with "
                         "Retry-After) once queued + resident token mass "
                         "exceeds this multiple of KV capacity — overload "
                         "sheds at the front door instead of queueing "
                         "doomed work (0 disables; default: "
                         "runtime.shed_cost_factor)")
    ap.add_argument("--constrained", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="grammar-constrained structured output: the "
                         "response_format={\"type\": \"json_schema\"|"
                         "\"regex\"} request fields plus logit_bias / "
                         "banned_tokens, served as token-mask automata "
                         "fused into the shared decode step.  "
                         "--no-constrained answers every constrained "
                         "request 400 (default: "
                         "runtime.constrained_decoding, on)")
    ap.add_argument("--spec-decode", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="speculative decoding: the engine drafts spec-k "
                         "tokens per row with its own int-quantized "
                         "self-draft and verifies them in one target "
                         "forward — temp-0 bytes identical with it on or "
                         "off.  Composes with --paged-pages (the "
                         "draft/verify window writes through the page "
                         "tables), --prefix-cache, --kv-bits 8, and the "
                         "host tier; rejected with --prefill-chunk and "
                         "on meshes (default: runtime.spec_decode)")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="draft tokens per speculative round "
                         "(default: runtime.spec_k)")
    ap.add_argument("--spec-adaptive-k",
                    action=argparse.BooleanOptionalAction, default=None,
                    help="adaptive per-row spec_k downshift from the "
                         "acceptance-rate EMA + token budget "
                         "(default: runtime.spec_adaptive_k)")
    ap.add_argument("--constrain-cache", type=int, default=None,
                    help="LRU capacity of the compiled (constraint, "
                         "tokenizer) automaton cache (default: "
                         "runtime.constrain_cache_size)")
    ap.add_argument("--watchdog-timeout", type=float, default=30.0,
                    help="engine watchdog: /healthz flips unhealthy when "
                         "in-flight work exists but no chunk was delivered "
                         "for this many seconds")
    ap.add_argument("--fault", action="append", default=[],
                    help="deterministic fault injection spec "
                         "(runtime/faults.py grammar, repeatable): e.g. "
                         "'batcher.decode:raise@3' crashes the 3rd decode "
                         "chunk, 'batcher.page_alloc:exhaust@1+' dries the "
                         "KV pool, 'batcher.decode:stall@2:1.5' wedges a "
                         "chunk for the watchdog.  Operator drills / CI "
                         "only — the supervisor restart is the tested path")
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu): the flag "
                         "spelling of jax_platforms / JAX_PLATFORMS, set "
                         "before backend init")
    args = ap.parse_args(argv)
    if args.replicas_max is not None and args.replicas_max < 1:
        raise SystemExit(f"--replicas-max must be >= 1, got "
                         f"{args.replicas_max}")
    for k in ("prefill_replicas_max", "decode_replicas_max"):
        v = getattr(args, k)
        flag = f"--{k.replace('_', '-')}"
        if v is not None and v < 1:
            raise SystemExit(f"{flag} must be >= 1, got {v}")
        if v is not None and not args.disaggregate:
            # Tier ceilings without tiers is config drift — reject in
            # milliseconds, before the model loads.
            raise SystemExit(f"{flag} needs --disaggregate")
    if args.replicas_max is None and args.prefill_replicas_max is None \
            and args.decode_replicas_max is None:
        # A max ceiling is THE elastic-fleet switch: the floor and every
        # autoscale knob mean nothing without one — reject loudly instead
        # of booting a fixed fleet the operator believes is elastic.
        stray = [f"--{k.replace('_', '-')}" for k in (
            "replicas_min", "autoscale_interval", "autoscale_up_load",
            "autoscale_down_load", "autoscale_hysteresis",
            "autoscale_cooldown",
        ) if getattr(args, k) != ap.get_default(k)]
        if stray:
            raise SystemExit(
                f"{', '.join(stray)} need --replicas-max (or a "
                "--prefill/--decode-replicas-max tier ceiling)"
            )
    if args.disaggregate and args.replicas_min != ap.get_default(
            "replicas_min"):
        raise SystemExit(
            "--replicas-min sizes the colocated elastic fleet; "
            "--disaggregate tiers floor at --prefill-replicas/"
            "--decode-replicas"
        )
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    init_backend()
    asyncio.run(_serve(args))


if __name__ == "__main__":
    main()
