"""Structured logging + metrics.

The reference logs with bare ``print()`` throughout (src/master/node.py:36,
197, 206, 215) and its Prometheus/ELK plans (implementation.md:34-41,
:146-157) never landed.  Here: std ``logging`` with an optional JSON
formatter, and an in-process metrics registry (counters, gauges, histogram
summaries) that the coordinator exports over its control-plane endpoint —
tokens/s, p50/p95 hop latency, HBM occupancy, per-stage step time.
"""

from __future__ import annotations

import bisect
import itertools
import json
import logging
import threading
from collections import defaultdict
from dataclasses import dataclass, field


class JsonFormatter(logging.Formatter):
    def format(self, record: logging.LogRecord) -> str:
        out = {
            "ts": record.created,
            "level": record.levelname,
            "logger": record.name,
            "msg": record.getMessage(),
        }
        if record.exc_info:
            out["exc"] = self.formatException(record.exc_info)
        extra = getattr(record, "fields", None)
        if extra:
            out.update(extra)
        return json.dumps(out)


def get_logger(name: str, json_format: bool = False, level: int = logging.INFO) -> logging.Logger:
    logger = logging.getLogger(name)
    if not logger.handlers:
        handler = logging.StreamHandler()
        if json_format:
            handler.setFormatter(JsonFormatter())
        else:
            handler.setFormatter(
                logging.Formatter("%(asctime)s %(levelname)s %(name)s: %(message)s")
            )
        logger.addHandler(handler)
        logger.setLevel(level)
        logger.propagate = False
    return logger


# The one ladder of upper edges, in seconds, for the latency histograms a
# scrape can take percentiles of over a WINDOW: twenty edges a decade from
# 1 ms to 125.9 s, each a whole number of microseconds (the series' names
# carry it), neighbours 1.122 apart.  A percentile interpolated inside a
# bucket is good to a few percent where the values spread; the gaps of a
# steady decode are a SPIKE one chunk long, which reads as the middle of
# its bucket, so a bucket's half width (6%) is what a median can be off by:
# at ten edges a decade (1.26 apart) that was 13%, too coarse to hold a
# median against chunk_steps x the engine's step.
LATENCY_EDGES_US: tuple[int, ...] = tuple(
    round(1000 * 10 ** (k / 20)) for k in range(103))
LATENCY_EDGES_S: tuple[float, ...] = tuple(e / 1e6 for e in LATENCY_EDGES_US)

# The histograms that count on that ladder, by name, and only these: every
# other one exports what it always did (some 40 histograms x 103 edges would
# be 4,000 lines a scrape that nobody reads).
BUCKETED: tuple[str, ...] = (
    "batcher.row.gap_seconds",
    "server.ttft_seconds",
    "batcher.queue_wait_seconds",
)


@dataclass
class _Histogram:
    values: list[float] = field(default_factory=list)
    max_keep: int = 4096
    # Cumulative across the full lifetime (Prometheus summary semantics);
    # the percentile window above slides, these never reset.
    total_count: int = 0
    total_sum: float = 0.0
    # A bucketed histogram's ladder of upper edges and the observations
    # that fell in each bucket ((edges[i-1], edges[i]]; the last entry is
    # what lay over the top edge), never reset either: a scrape exports the
    # running sums, ``count of observations <= edge``, and the difference
    # of two scrapes is the window's distribution.
    edges: tuple[float, ...] | None = None
    buckets: list[int] = field(default_factory=list)

    def __post_init__(self) -> None:
        if self.edges is not None:
            self.buckets = [0] * (len(self.edges) + 1)

    def observe(self, v: float) -> None:
        if len(self.values) >= self.max_keep:
            # Keep a sliding window: drop oldest half.
            self.values = self.values[self.max_keep // 2 :]
        self.values.append(v)
        self.total_count += 1
        self.total_sum += v
        if self.edges is not None:
            self.buckets[bisect.bisect_left(self.edges, v)] += 1

    def cumulative(self) -> list[int]:
        """Observations <= each edge, in the ladder's order."""
        return list(itertools.accumulate(self.buckets[:-1]))

    def summary(self) -> dict[str, float]:
        if not self.values:
            return {"count": 0}
        vs = sorted(self.values)
        n = len(vs)

        def pct(p: float) -> float:
            return vs[min(n - 1, int(p * n))]

        return {
            "count": n,
            "mean": sum(vs) / n,
            "p50": pct(0.50),
            "p95": pct(0.95),
            "p99": pct(0.99),
            "min": vs[0],
            "max": vs[-1],
        }


class _Histograms(dict):
    """name -> histogram, made on first use: on the latency ladder where
    ``BUCKETED`` names it, plain otherwise."""

    def __missing__(self, name: str) -> _Histogram:
        h = self[name] = _Histogram(
            edges=LATENCY_EDGES_S if name in BUCKETED else None)
        return h


class Metrics:
    """Thread-safe in-process metrics registry."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: dict[str, float] = defaultdict(float)  # guarded-by: self._lock
        self._gauges: dict[str, float] = {}  # guarded-by: self._lock
        self._hists: dict[str, _Histogram] = _Histograms()  # guarded-by: self._lock

    def inc(self, name: str, value: float = 1.0) -> None:
        with self._lock:
            self._counters[name] += value

    def set_gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = value

    def set_gauges(self, values: dict[str, float]) -> None:
        """Set a family of gauges under one lock acquisition — occupancy
        views (e.g. the KV pool's batcher_pool_* snapshot) publish several
        numbers that should land atomically for a scrape."""
        with self._lock:
            self._gauges.update(values)

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            self._hists[name].observe(value)

    def observe_many(self, name: str, values) -> None:
        """Observe a batch under one lock acquisition: what one delivery
        call of the batcher saw, a value a delivering row."""
        with self._lock:
            h = self._hists[name]
            for v in values:
                h.observe(v)

    def get_counter(self, name: str) -> float:
        """Point read of one counter (0.0 when never incremented) — the
        supervisor's restart accounting and tests read through this
        instead of snapshotting the whole registry."""
        with self._lock:
            return self._counters.get(name, 0.0)

    def get_histogram(self, name: str) -> tuple[int, float]:
        """Point read of one histogram's lifetime (count, sum) — what a
        scrape exports as ``_count``/``_sum``; (0, 0.0) when never
        observed.  Window differences of these are how spans are read."""
        with self._lock:
            h = self._hists.get(name)
            return (h.total_count, h.total_sum) if h else (0, 0.0)

    def get_gauge(self, name: str, default: float = 0.0) -> float:
        with self._lock:
            return self._gauges.get(name, default)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "histograms": {k: h.summary() for k, h in self._hists.items()},
            }

    def prometheus_text(self) -> str:
        """Render the registry in Prometheus exposition format (text/plain
        version 0.0.4).  Histograms export as summaries: quantile series plus
        cumulative _count/_sum.  A histogram of ``BUCKETED`` adds one
        LABEL-FREE series an edge of its ladder, ``<name>_le_us_<edge in
        whole microseconds> <observations <= edge>``: a scraper that keeps
        only label-free lines (``benchmark/client.py``) can then difference
        the distribution over a window, which the lifetime quantiles do not
        allow.  The reference planned a Prometheus endpoint
        (implementation.md:34-37, :146-157) but never built one."""

        def name_of(raw: str) -> str:
            # Prometheus names: [a-zA-Z_:][a-zA-Z0-9_:]*
            out = "".join(c if c.isalnum() or c == "_" else "_" for c in raw)
            return out if out[:1].isalpha() or out[:1] == "_" else "_" + out

        lines: list[str] = []
        with self._lock:
            for raw, v in sorted(self._counters.items()):
                n = name_of(raw)
                lines.append(f"# TYPE {n} counter")
                lines.append(f"{n} {v}")
            for raw, v in sorted(self._gauges.items()):
                n = name_of(raw)
                lines.append(f"# TYPE {n} gauge")
                lines.append(f"{n} {v}")
            for raw, h in sorted(self._hists.items()):
                n = name_of(raw)
                s = h.summary()
                lines.append(f"# TYPE {n} summary")
                for q in ("p50", "p95", "p99"):
                    if q in s:
                        lines.append(f'{n}{{quantile="0.{q[1:]}"}} {s[q]}')
                lines.append(f"{n}_count {h.total_count}")
                lines.append(f"{n}_sum {h.total_sum}")
                if h.edges is not None:
                    lines.extend(
                        f"{n}_le_us_{edge} {count}" for edge, count
                        in zip(LATENCY_EDGES_US, h.cumulative()))
        return "\n".join(lines) + "\n"


METRICS = Metrics()

# THE registry of metric names this package emits.  Every name passed to
# METRICS.inc/set_gauge/set_gauges/observe must appear here (or match
# a declared ``*`` pattern — f-string names register VERBATIM as their
# pattern, e.g. ``faults.fired.*``).  graftlint's GL302 pins emission
# sites to this dict, GL305 flags dead entries, and the README metric
# table is generated from it — dashboards can't find what the registry
# doesn't name.
METRIC_DOCS: dict[str, str] = {
    # -- continuous batcher (runtime/batcher.py) --
    "batcher.admitted": "requests admitted into a batch row (counter)",
    "batcher.completed": "requests that finished and published results",
    "batcher.cancelled": "requests cancelled while queued or resident",
    "batcher.shed_total": "queued requests shed at deadline expiry",
    "batcher.preemptions_total": "rows preempted for KV pool pressure",
    "batcher.pages_grown": "KV pages allocated by on-demand row growth",
    "batcher.prefill_chunks": "chunked-prefill bites consumed",
    "batcher.sched.budget_tokens": "per-step token budget available to "
        "fused mixed-schedule dispatches (cumulative; runtime/"
        "scheduler.py)",
    "batcher.sched.prefill_tokens": "prompt tokens consumed by prefill "
        "bites, fused (mixed) and serialized (alternate) alike",
    "batcher.sched.decode_tokens": "decode-token legs dispatched "
        "(span-start live rows x chunk_steps per plain decode/mixed "
        "step — an upper bound on committed tokens: rows finishing "
        "mid-span still occupy their legs until the carry sync)",
    "batcher.sched.stall_rounds": "serialized prefill bites that ran "
        "while decode rows were live — the alternating schedule's "
        "latency spike; the mixed schedule keeps this at zero",
    "batcher.sched.budget_utilization": "per-step token budget fill of "
        "the latest fused dispatch (gauge: (n_active + bite) / "
        "token_budget; exceeds 1.0 when the active decode legs alone "
        "over-subscribe the budget — the floor-1 bite keeps the prefill "
        "progressing)",
    "batcher.prefix_cache.lookups": "automatic prefix-cache lookups",
    "batcher.prefix_cache.hits": "lookups that matched >= 1 cached page",
    "batcher.prefix_cache.hit_tokens": "prompt tokens served from cache",
    "batcher.prefix_cache.miss_tokens": "prompt tokens prefilled fresh, by "
                                        "every admission: a lookup's misses, "
                                        "and the whole prompt where no "
                                        "lookup is made (no cache, opt-out, "
                                        "named prefix); no bucket padding",
    "batcher.prefix_cache.hit_rate": "cumulative hit_tokens fraction (gauge)",
    "batcher.prefix_cache.evicted_pages": "cached pages evicted under pressure",
    "batcher.pool.*": "KV page-pool occupancy gauges (free/cached/held/"
                      "total pages, min_available + peak_held watermarks)",
    "batcher.kv_pages_exported": "KV pages gathered for handoff to a "
                                 "decode-role engine",
    "batcher.kv_pages_imported": "handed-off KV pages adopted into the "
                                 "pool (decode-role engine)",
    # -- dispatch-ahead engine loop (overlap) --
    "batcher.overlap.dispatched_ahead": "decode chunks dispatched from the "
                                        "device-resident carry while the "
                                        "previous chunk's host work ran",
    "batcher.overlap.carry_syncs": "decode spans ended by syncing the "
                                   "device carry into the host mirrors "
                                   "(scheduling work was pending)",
    "batcher.overlap.depth": "current dispatch depth: 1 while a chunk is "
                             "dispatched ahead of its predecessor's host "
                             "work, 0 at a carry sync (gauge)",
    # -- engine-thread spans (core.profiling.span: each is a profiler
    #    annotation of the same name AND this histogram).  The thread is
    #    serial, so the batcher.loop.* sums partition its wall time. --
    "batcher.loop.admit_seconds": "one admission round (_admit_pending): "
                                  "host work plus the device's prefill — "
                                  "resident rows wait this long "
                                  "(histogram)",
    "batcher.admit.row_seconds": "one request's admission, inside "
                                 "batcher.loop.admit: its operands and "
                                 "its program's dispatch, then (overlap "
                                 "on) the fetch and the activation of the "
                                 "admission launched BEFORE it, then the "
                                 "selection and the pages of the next; "
                                 "its own fetch where nothing follows at "
                                 "once (histogram; the annotation carries "
                                 "rid, prompt_tokens, cached_tokens, "
                                 "bucket, live_rows: the bucket's rows "
                                 "the quantized matmuls compute, "
                                 "attn_pairs_live: the (query, key) pairs "
                                 "the flash kernel's visited tiles hold, "
                                 "key_slots: the keys a query of it is "
                                 "scored against, fetched_rid: the "
                                 "earlier admission whose outputs it "
                                 "fetched)",
    "batcher.admit.wait_device_seconds": "the engine thread blocked in an "
                                         "admission's ONE device_get "
                                         "(first token, logprob, expert "
                                         "counts), inside a "
                                         "batcher.admit.row (overlap on: "
                                         "the NEXT admission's, after its "
                                         "launch; the round's last in its "
                                         "own): prefill on the chip; the "
                                         "row spans minus this is the "
                                         "host's part of the admissions "
                                         "(histogram)",
    "batcher.admit.overlapped": "admissions launched while the admission "
                                "before them was still unfetched (the "
                                "pipelined ones; beside batcher.admitted: "
                                "admissions less pipelined runs, a round "
                                "being one unless a swap restore or a "
                                "chunked start parts it)",
    "batcher.admit.self_attention": "admissions whose attention read their "
                                    "own bucket of tokens: a fresh row, "
                                    "whose start the model sees while "
                                    "tracing",
    "batcher.admit.row_cache_attention": "admissions whose attention read "
                                         "every slot of the row cache: a "
                                         "suffix behind a named or cached "
                                         "prefix, whose start is traced",
    "batcher.admit.matmul_rows": "rows the admissions handed the quantized "
                                 "matmul kernel: each one's bucket",
    "batcher.admit.matmul_rows_live": "of those, the rows of the row tiles "
                                      "that held a real token; the tiles "
                                      "past them are skipped "
                                      "(ops.quant_matmul.live_rows)",
    "batcher.admit.attn_pairs": "(query, key) pairs held by the tiles of "
                                "the flash kernel that are live by the "
                                "causal band and the window over a fresh "
                                "admission's bucket, summed over the "
                                "attention layers by kind, each tile "
                                "counted by its area; 0 for an admission "
                                "that does not take the kernel (a suffix "
                                "behind a prefix, a mesh, heads that fill "
                                "a register in part)",
    "batcher.admit.attn_pairs_live": "of those, the pairs of the tiles of "
                                     "queries that held a real token; the "
                                     "tiles past them are not visited "
                                     "(ops.flash.live_tiles; equal to "
                                     "attn_pairs where no count reaches "
                                     "the kernel: the families whose "
                                     "layers are all alike)",
    "batcher.admit.cont_keys": "slots of the row cache that a layer scored "
                               "for the admissions behind a named or "
                               "cached prefix, a row's continuation: the "
                               "key tiles the flash kernel fetched "
                               "(ops.flash.live_keys), every slot of the "
                               "row for the dense body",
    "batcher.admit.cont_keys_live": "of those, the slots that held a key: "
                                    "the prefix's tokens and the prompt's",
    "batcher.loop.grow_seconds": "chunk-boundary page growth, preemption "
                                 "included (histogram)",
    "batcher.loop.plan_seconds": "span planning and the per-chunk "
                                 "dispatch-ahead decision (histogram)",
    "batcher.loop.dispatch_seconds": "enqueueing one decode chunk — host "
                                     "cost, not device time (histogram)",
    "batcher.loop.wait_device_seconds": "the engine thread blocked in the "
                                        "chunk's device_get: decode on the "
                                        "chip (histogram)",
    "batcher.loop.deliver_seconds": "per-chunk delivery callbacks, result "
                                    "publishing and digest pre-hashing "
                                    "(histogram)",
    "batcher.queue_wait_seconds": "submit (or the requeue after a "
                                  "preemption) to admission start: the "
                                  "request's selection or, launched "
                                  "behind an admission still on the "
                                  "chip, that one's fetch; one sample "
                                  "per admission (histogram, bucketed)",
    "batcher.queue_wait_seconds.le_us.*": "of those waits, how many were no "
                                          "longer than the edge the name "
                                          "gives in microseconds (a counter "
                                          "an edge of the latency ladder; "
                                          "the difference of two scrapes is "
                                          "the window's distribution)",
    # -- what a resident request waits between two deliveries, stamped
    #    where tokens are delivered (_collect), on the batcher's clock --
    "batcher.row.gap_seconds": "the interval between two deliveries of "
                               "tokens to one request: from its admission's "
                               "first token, or its last chunk's tokens, to "
                               "the next chunk's that brought it at least "
                               "one, or to the token a re-admission after a "
                               "preemption samples (the requeue wait lies "
                               "inside that one gap); the interval a cancel "
                               "or a deadline cuts is dropped; its sum over "
                               "batcher.decode.committed_tokens is the gap "
                               "a token (histogram, bucketed; its count is "
                               "the deliveries less the first tokens)",
    "batcher.row.gap_seconds.le_us.*": "of those gaps, how many were no "
                                       "longer than the edge the name gives "
                                       "in microseconds (a counter an edge "
                                       "of the latency ladder)",
    "batcher.row.gap_admit_seconds": "of batcher.row.gap_seconds' sum, the "
                                     "seconds the engine thread was inside "
                                     "batcher.loop.admit: what resident "
                                     "requests waited through OTHER "
                                     "requests' admission rounds (counter)",
    # -- the time no model program was in flight (from a blocking fetch
    #    that returned the newest one's output to the next dispatch call),
    #    charged to the batcher.loop.* span it fell in: a lower bound of
    #    the device's idle time, over the whole window.  Time parked with
    #    no request is not counted. --
    "batcher.starved.admit_seconds": "seconds the device had nothing to "
                                     "run inside admission rounds: host "
                                     "work before an admission's dispatch "
                                     "and after its fetch (counter)",
    "batcher.starved.grow_seconds": "... inside chunk-boundary page "
                                    "growth (counter)",
    "batcher.starved.plan_seconds": "... inside span planning: the turn "
                                    "into a decode span (counter; 0 for "
                                    "dispatched-ahead chunks)",
    "batcher.starved.dispatch_seconds": "... inside batcher.loop.dispatch, "
                                        "up to the call that dispatches "
                                        "the chunk (counter)",
    "batcher.starved.deliver_seconds": "... inside delivery after a span's "
                                       "sync (counter; 0 while a chunk is "
                                       "dispatched ahead)",
    "batcher.decode.chunks": "decode / speculative / mixed chunks "
                             "dispatched: the loop's wait_device + plan + "
                             "dispatch + deliver + grow seconds over "
                             "chunks x chunk_steps is the engine's step",
    "batcher.decode.slot_steps": "decode legs the dispatched chunks had "
                                 "room for (slots x chunk_steps per "
                                 "chunk): decode_tokens / slot_steps is "
                                 "the row fill",
    "batcher.decode.committed_tokens": "tokens the decode chunks delivered "
                                       "to callers (admission tokens "
                                       "excluded): committed / "
                                       "decode_tokens is the useful share "
                                       "of the legs run",
    # -- paged speculative decoding (batcher spec_chunk) --
    "batcher.spec.rounds": "speculative draft/verify rounds dispatched",
    "batcher.spec.accepted_tokens": "drafted tokens the verify pass "
                                    "committed (bonus/correction tokens "
                                    "excluded)",
    "batcher.spec.rejected_tokens": "drafted tokens the verify pass "
                                    "rejected (rolled back by the "
                                    "pos/length clamp)",
    "batcher.spec.k_downshifts": "rounds dispatched with at least one "
                                 "row's draft length adaptively clamped "
                                 "below spec_k (budget or acceptance-EMA "
                                 "downshift)",
    "batcher.spec.acceptance": "cumulative accepted/(accepted+rejected) "
                               "draft fraction (gauge; per-round "
                               "fractions feed the engine.spec_acceptance "
                               "histogram)",
    # -- grammar-constrained structured output (runtime/constrain.py) --
    "batcher.constrain.rows": "constrained/biased rows admitted (token-mask "
                              "automaton engaged in the decode step)",
    "batcher.constrain.cache_hits": "constraint compiles served from the "
                                    "(constraint, tokenizer) LRU cache",
    "batcher.constrain.cache_misses": "schema/regex -> token-DFA compiles "
                                      "actually built",
    "batcher.constrain.compile_seconds": "wall time of one token-mask "
                                         "automaton compile (histogram)",
    # -- KV memory tiering (int8 pages + host-RAM tier) --
    "batcher.kv_swaps.out": "preemption victims swapped to the host tier "
                            "(raw pages parked instead of recomputed)",
    "batcher.kv_swaps.in": "swapped rows restored to device pages "
                           "(byte-exact, no recompute)",
    "batcher.kv_swaps.fallback": "swap/restore attempts degraded to exact "
                                 "recompute (host budget dry, drop drill, "
                                 "or checksum mismatch)",
    "batcher.host_tier.spilled_pages": "cold cached pages captured to host "
                                       "RAM ahead of LRU eviction",
    "batcher.host_tier.restored_pages": "host-spilled pages scattered back "
                                        "into the pool on a prefix-cache "
                                        "hit",
    "batcher.host_tier.hits": "prefix-cache lookups extended by a "
                              "host-tier restore",
    "batcher.host_tier.spill_evictions": "host-spilled pages dropped for "
                                         "tier budget pressure",
    "batcher.host_tier.*": "host-tier occupancy gauges (budget/used pages, "
                           "swap parcels, spill entries)",
    # -- serving gateway (runtime/server.py) --
    "server.requests": "completion requests accepted past the shed gates",
    "server.disconnects": "requests whose client went away mid-serve",
    "server.request_seconds": "request latency, receipt to close (histogram)",
    "server.ttft_seconds": "time to first token, from receipt (histogram, "
                           "bucketed)",
    "server.ttft_seconds.le_us.*": "of those first tokens, how many came no "
                                   "later than the edge the name gives in "
                                   "microseconds (a counter an edge of the "
                                   "latency ladder)",
    "server.pre_submit_seconds": "receipt to batcher.submit: parsing, "
                                 "tokenizing, the shed gates (histogram)",
    "server.engine.idle_seconds": "the engine thread parked with no "
                                  "request to serve (histogram; a span)",
    "server.request_timeouts": "requests that hit their deadline mid-flight",
    "server.requests_shed_total": "requests answered 429/503 unworked",
    "server.requests_shed.*": "shed requests by reason (queue_full, "
                              "cost_gate, queue_deadline)",
    "server.engine_restarts": "supervised engine respawns after a crash",
    "server.requests_retried": "zero-streamed requests re-admitted on restart",
    "server.recovery_seconds": "crash to tokens-flowing-again (histogram)",
    "server.engine_last_chunk_age_s": "watchdog: seconds since last delivery",
    "server.prefill_requests": "prefill-role handoff requests served "
                               "(/v1/prefill)",
    # -- engine / sessions / profiling --
    "engine.generated_tokens": "tokens generated by engine entry points",
    "engine.generate_seconds": "wall seconds per generate call (histogram; "
                               "the engine.generate span)",
    "engine.spec_acceptance": "speculative decoding acceptance fraction",
    "kv_spill.spills": "session KV caches spilled to host DRAM",
    "kv_spill.restores": "session KV caches restored to device",
    "kv_spill.host_bytes": "bytes of session KV resident on host (gauge)",
    "kv_spill.resident_sessions": "session caches resident in HBM (gauge)",
    "kv_spill.spilled_sessions": "session caches parked on host (gauge)",
    "runtime.compiles_total": "trips through the backend compiler (a compile "
                              "or a load from the persistent cache), from "
                              "jax.monitoring; the program's name is logged "
                              "at INFO — a recompile in a live server is a "
                              "counter to alarm on",
    "runtime.compile_seconds": "wall seconds spent in those trips "
                               "(counter: the sum)",
    # -- replica fleet router (runtime/router.py + cluster/fleet.py) --
    "router.requests": "requests through the router front door",
    "router.placements": "placement decisions onto a replica",
    "router.affinity_hits": "placements that followed prefix-cache affinity",
    "router.failovers": "zero-streamed requests re-placed after a replica "
                        "failure (crash/stall/partition/drain straggler)",
    "router.failover_seconds": "replica failure observed to the re-placed "
                               "request answered (histogram)",
    "router.retries_exhausted": "requests 503'd after the failover budget",
    "router.failed_streamed": "partially-streamed requests failed with "
                              "engine_error (deltas cannot be retracted)",
    "router.replicas_healthy": "replicas currently routable (gauge)",
    "router.committed_tokens.*": "router-side committed token mass per "
                                 "replica (gauge; placement load signal)",
    "router.replica_kills": "replicas killed (chaos or real death observed)",
    "router.drains": "replica drains started (rolling restart)",
    "router.respawns": "replica respawns completed",
    # -- disaggregated prefill/decode (router + cluster/kv_transfer.py) --
    "router.handoffs": "prefill handoffs attempted (disaggregated mode)",
    "router.handoff_skips": "handoffs skipped because the decode replica "
                            "already holds the prompt's page run "
                            "(epoch-valid affinity)",
    "router.handoff_fallbacks": "handoffs degraded to colocated prefill",
    "router.handoff_fallbacks.*": "handoff fallbacks by reason (timeout, "
                                  "error, rejected, digest_mismatch, "
                                  "no_prefill_replica, no_kv_target)",
    "router.handoff_seconds": "prefill + verified transfer latency, "
                              "handoff start to pages landed (histogram)",
    "router.handoff_bytes": "KV payload bytes shipped by completed "
                            "handoffs",
    "xfer.sends": "KV transfer attempts (sender side)",
    "xfer.retries": "KV transfer attempts retried after timeout/NACK",
    "xfer.bytes": "KV transfer frame bytes written to the wire",
    "xfer.send_seconds": "one transfer's send->ack latency incl. retries "
                         "(histogram)",
    "xfer.verify_failures": "KV payloads rejected by checksum/digest "
                            "verification",
    "xfer.dup_deliveries": "duplicate KV deliveries absorbed idempotently",
    # -- cluster control plane --
    "worker.errors": "commands answered with a structured ERROR reply "
                     "(the coordinator's task-retry trigger)",
    "coordinator.workers": "registered workers (gauge)",
    "coordinator.evictions": "workers evicted (heartbeat/connection loss)",
    "coordinator.tasks_dispatched": "tasks sent to workers",
    "coordinator.tasks_completed": "tasks answered with RESULT",
    "coordinator.tasks_retried": "tasks requeued after worker failure",
    "coordinator.tasks_failed": "tasks failed after max attempts",
    "coordinator.shards_reassigned": "shards moved off evicted workers",
    # -- multi-tenant QoS (runtime/scheduler.py + runtime/server.py) --
    "tenant.requests.*": "requests accepted past every shed gate, per "
                         "tenant",
    "tenant.admitted_tokens.*": "admission-time token mass (prompt + "
                                "budget) accepted per tenant — the "
                                "rate-quota gate's currency",
    "tenant.shed.*": "requests shed 429 by the per-tenant token-rate "
                     "quota gate (each carries the tenant's own "
                     "Retry-After)",
    "tenant.vtc.*": "weighted-fair virtual token counter per tenant "
                    "(gauge; runtime/scheduler.py TenantScheduler — "
                    "admission serves the lowest counter first)",
    "tenant.resident_rows.*": "batch rows currently resident per tenant "
                              "(gauge; capped by tenant_max_rows)",
    # -- elastic fleet autoscaling (cluster/autoscale.py) --
    "autoscale.replicas": "live (non-dead) replicas in the fleet (gauge)",
    "autoscale.load": "committed token mass over aggregate routable KV "
                      "capacity — the scale signal (gauge)",
    "autoscale.queue_depth": "router in-flight proxies summed over "
                             "routable replicas (gauge)",
    "autoscale.scale_ups": "replicas added by the autoscaler",
    "autoscale.scale_downs": "replicas drained away by the autoscaler",
    "autoscale.scale_failures": "scale actions that failed or were "
                                "vetoed (injected or real provision "
                                "failure) — the fleet kept its size",
    "autoscale.scale_seconds": "wall time of one scale action, decision "
                               "to done (histogram; up = boot + first "
                               "healthy wait, down = graceful drain)",
    "autoscale.replicas_added": "replicas registered by "
                                "ReplicaFleet.add_replica",
    "autoscale.replicas_removed": "replicas drained away by "
                                  "ReplicaFleet.remove_replica",
    # -- fleet control plane (runtime/router.py, ISSUE 18) --
    "router.ledger.charges": "admissions charged to the router's fleet "
                             "tenant ledger at placement (the one "
                             "admission-commit point)",
    "router.ledger.charged_tokens": "token mass (prompt + budget) charged "
                                    "to the fleet ledger",
    "router.ledger.refunds": "fleet-ledger charges refunded — the request "
                             "shed or failed without service rendered",
    "router.ledger.sheds": "requests shed 429 by the fleet-ledger gate "
                           "(each carries the tenant's own fleet-ledger "
                           "Retry-After)",
    "router.ledger.shed.*": "fleet-ledger sheds per tenant",
    "router.ledger.bypasses": "requests that bypassed the fleet-ledger "
                              "gate (the router.ledger drop drill) — the "
                              "replica gateways' loose backstop still "
                              "meters them, never a silent unmetered path",
    "router.ledger.tenants": "tenants live in the fleet ledger map "
                             "(gauge; cardinality-capped)",
    "directory.lookups": "fleet prefix-digest directory lookups at "
                         "placement (cold replica, warm sibling?)",
    "directory.hits": "lookups that found an epoch-valid sibling holding "
                      "a cached run the placed replica lacks",
    "directory.stale_drops": "directory entries dropped lazily at lookup "
                             "(epoch mismatch — the holder drained or "
                             "respawned since recording)",
    "directory.pulls": "cross-replica KV pulls attempted (sibling cache "
                       "-> placed replica over the checksummed KV_PAGES "
                       "plane)",
    "directory.pulled_pages": "KV pages landed on the placed replica by "
                              "completed cross-replica pulls",
    "directory.pull_bytes": "KV payload bytes shipped by completed pulls",
    "directory.pull_seconds": "one pull's cached-export + verified "
                              "transfer latency (histogram)",
    "directory.pull_fallbacks": "pulls degraded to local recompute "
                                "(byte-exact, just slower)",
    "directory.pull_fallbacks.*": "pull fallbacks by reason (stale, "
                                  "not_cached, error, timeout, rejected, "
                                  "no_kv_target)",
    # -- disaggregated autoscaling (cluster/autoscale.py, per tier) --
    "autoscale.*.replicas": "live replicas in the tier (gauge; * = "
                            "prefill/decode)",
    "autoscale.*.load": "the tier's scale signal (gauge): decode = "
                        "committed-token mass over tier KV capacity, "
                        "prefill = in-flight handoffs per replica",
    "autoscale.*.scale_ups": "replicas added to the tier by the "
                             "autoscaler",
    "autoscale.*.scale_downs": "replicas drained away from the tier "
                               "(graceful-only)",
    "autoscale.*.scale_failures": "tier scale actions that failed or "
                                  "were vetoed — the tier kept its size",
    "batcher.conv_state_bytes": "bytes of the state a hybrid model keeps "
                                "beside its pages: each convolution "
                                "layer's last gated inputs, one entry a "
                                "batch slot (gauge)",
    # -- expert layers (models/layers.py moe_dropless; real tokens only,
    #    carried out of each admission and decode chunk, added at delivery) --
    "batcher.window_state_bytes": "bytes of the rings a model of windowed "
                                  "and full attention layers keeps beside "
                                  "its pages: each windowed layer's last "
                                  "sliding_window keys and values, one ring "
                                  "a batch slot, whatever the rows hold "
                                  "(gauge)",
    "batcher.pool_token_bytes": "bytes a resident token costs the page "
                                "pool, every paged layer's (page_bytes over "
                                "the page size; gauge)",
    "batcher.ret_state_bytes": "bytes of the float32 state a model of "
                               "power-retention layers keeps, one entry a "
                               "layer, a batch slot and a key/value head "
                               "(state and normaliser; its rows hold no "
                               "key and it is served without a pool; gauge)",
    # -- retention layers (models.model.retention_counts; real tokens
    #    only, carried out of each admission and decode chunk, added at
    #    delivery; a layer's, not summed over the layers) --
    "ret.admit.tokens": "real prompt tokens the retention layers' chunked "
                        "scan took in, summed over admissions",
    "ret.admit.chunks": "chunks of ret_chunk tokens that scan walked: those "
                        "that hold a real token",
    "ret.decode.row_steps": "rows that took a recurrence step, summed over "
                            "decode steps: each reads and writes its state "
                            "once a layer",
    "ret.decode.resident_tokens": "tokens those rows held, summed over "
                                  "decode steps: what keys and values "
                                  "would have had to be read for them",
    "batcher.ssm_state_bytes": "bytes of the state a model of Mamba-2 "
                               "layers keeps BESIDE its page pool, one "
                               "entry a layer and a batch slot: the float32 "
                               "state of every head and the convolution's "
                               "last inputs, whatever the rows hold (gauge)",
    # -- state-space layers (models.model.ssm_counts; real tokens only,
    #    carried out of each admission and decode chunk behind the experts'
    #    counts, added at delivery; a layer's, not summed over the layers) --
    "ssm.admit.tokens": "real prompt tokens the Mamba-2 layers' chunked "
                        "scan took in, summed over admissions",
    "ssm.admit.chunks": "chunks of ssm_chunk tokens that scan walked: those "
                        "that hold a real token",
    "ssm.decode.row_steps": "rows that took a recurrence step, summed over "
                            "decode steps: each reads and writes its state "
                            "once a layer",
    "batcher.gdn_state_bytes": "bytes of the state a model of gated "
                               "delta-rule layers keeps BESIDE its page "
                               "pool, one entry a layer and a batch slot: "
                               "the float32 state of every value head and "
                               "the convolution's last inputs, whatever the "
                               "rows hold (gauge)",
    # -- delta-rule layers (models.model.ssm_counts with gdn_chunk; real
    #    tokens only, behind the experts' counts; a layer's) --
    "gdn.admit.tokens": "real prompt tokens the delta-rule layers' chunked "
                        "scan took in, summed over admissions",
    "gdn.admit.chunks": "chunks of gdn_chunk tokens that scan walked (a "
                        "triangle solved in each): those that hold a real "
                        "token",
    "gdn.decode.row_steps": "rows that took a recurrence step, summed over "
                            "decode steps: each reads and writes its state "
                            "once a layer",
    "attn.decode.resident_tokens": "tokens the decoding rows held, summed "
                                   "over decode steps: what the paged decode "
                                   "kernel read a full attention layer (a "
                                   "model with windowed layers beside them)",
    "swa.decode.window_tokens": "the same sum of min(tokens held, "
                                "sliding_window): what the rings' decode "
                                "kernel read a windowed layer, never more "
                                "than the window a row a step",
    "swa.decode.ring_tokens": "sliding_window times the rows that decoded, "
                              "summed over decode steps: the tokens their "
                              "rings hold room for a windowed layer "
                              "(window_tokens over it is the share of the "
                              "rings' bytes that are live)",
    "batcher.latent_page_bytes": "bytes of one page of a latent (MLA) pool, "
                                 "every layer's rows (kv_cache.page_bytes)",
    "moe.held_pairs": "routed pairs that fell on an expert this chip holds "
                      "(ModelConfig.experts_held), real tokens only",
    "moe.combine_rows": "of moe.held_pairs, those whose rows the expert "
                        "layer's combine fetched singly (the kernel "
                        "moe_combine: an admission block whose grouped list "
                        "outgrows fast memory); the rest, and every pair of "
                        "a model that holds all its experts, came by the "
                        "gather of every routed pair's row",
    "mla.decode.resident_tokens": "tokens the decoding rows held, summed "
                                  "over decode steps: what the latent decode "
                                  "kernel read a layer",
    "mla.decode.scored_keys": "keys the latent decode kernel's products "
                              "covered a layer for those rows, summed over "
                              "decode steps: their pages in whole blocks of "
                              "the kernel's body (resident_tokens over it is "
                              "the share of the products that falls on a key "
                              "a row holds)",
    "moe.routed_pairs": "(token, expert) pairs routed, summed over the "
                        "expert layers: tokens x experts a token x layers",
    "moe.layer_passes": "expert-layer passes that had a real token (22 a "
                        "forward pass in LFM2-8B-A1B)",
    "moe.experts_touched": "experts with at least one real token, summed "
                           "over layer passes: over experts x layer_passes "
                           "it is the share of expert weights a pass reads",
    "moe.max_load_tokens": "the fullest expert's tokens, summed over layer "
                           "passes: x experts over routed_pairs is the "
                           "load imbalance (1.0 is even)",
    # -- kernel dispatch (ops/dispatch.py) --
    "ops.dispatch.*.*": "trace-time dispatches of a Pallas op (quant_matmul, "
                        "paged_decode, ragged_decode, flash, moe_experts, "
                        "moe_combine) "
                        "by the path "
                        "taken: kernel (compiled), interpret (Pallas "
                        "interpreter) or fallback (dense jax.numpy)",
    "ops.dispatch.quant_matmul.stacked": "of quant_matmul's kernel (or "
                                         "interpret) traces, those handed a "
                                         "stack of layers and the index of "
                                         "the one to read; in a served dense "
                                         "model it equals them, and a value "
                                         "below says a call site slices a "
                                         "layer out (a copy a step)",
    "ops.dispatch.quant_matmul.k_minor": "of quant_matmul's kernel (or "
                                         "interpret) traces, those that "
                                         "took the lane-dense leg: weight "
                                         "[N, K] and scales [N/block, K] "
                                         "both with K on the lanes, a "
                                         "block's scales one row; since PR "
                                         "33 it equals them",
    "ops.dispatch.paged_decode.run_pages": "pages of a row the paged decode "
                                           "kernel walks at a time, as its "
                                           "last trace worked it out from "
                                           "the pool's shapes (a gauge; "
                                           "ops/decode_attn._run_pages)",
    "ops.gdn_prefill.bf16_operands": "of gdn_prefill's traces (whatever "
                                     "the path), those handed q and k in "
                                     "bfloat16: K K^T and Q K^T are ONE MXU "
                                     "pass over the raw rows, [K ; Q] S and "
                                     "the state's update three (a float32 "
                                     "operand's bfloat16 pieces), where "
                                     "float32 rows take HIGHEST's six; a "
                                     "served model's admissions all count",
    "ops.gdn_prefill.paired_heads": "of gdn_prefill's traces, those whose "
                                    "key heads carry an even count of value "
                                    "heads: a chunk's triangles are "
                                    "inverted two side by side in 128 "
                                    "lanes, ten matmuls for both",
    "ops.dispatch.*.shard_map": "of those, dispatches traced inside the "
                                "per-shard shard_map body of a "
                                "tensor-parallel mesh (the kernel then "
                                "runs on every shard)",
    # -- fault injection (runtime/faults.py) --
    "faults.fired": "injected faults triggered, total",
    "faults.fired.*": "injected faults triggered, by action",
}
