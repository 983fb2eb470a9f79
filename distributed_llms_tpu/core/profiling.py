"""Profiling: jax.profiler capture + step timing + HBM occupancy.

The reference has no profiler or timing instrumentation of any kind — only
``print()`` logging (SURVEY §5.1; benchmarking was a plan item,
plan.md:297-300).  Here:

- :func:`trace` captures a TensorBoard/Perfetto trace of everything run
  inside it (XLA ops, host callbacks, transfers) via ``jax.profiler``;
- :func:`annotate` labels host-side regions so they show up on the trace;
- :class:`span` is the one span mechanism of the serving path: an
  annotation on the profiler's clock AND a ``<name>_seconds`` histogram in
  the global METRICS registry, from one ``with`` block;
- :func:`count_compiles` turns every backend compile into a counter;
- :func:`record_memory_stats` snapshots per-device HBM occupancy gauges;
- :func:`device_report` names the backend a process actually runs on.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator

import jax

from .observability import METRICS, get_logger

log = get_logger("profiling")


@contextlib.contextmanager
def trace(log_dir: str, host_tracer_level: int = 2) -> Iterator[None]:
    """Capture a profiler trace into ``log_dir`` (view with TensorBoard's
    profile plugin or Perfetto).  Usage:

        with profiling.trace("/tmp/trace"):
            engine.generate_text([...])
    """
    with jax.profiler.trace(log_dir, create_perfetto_trace=True):
        yield
    log.info("profiler trace written to %s", log_dir)


def annotate(name: str, **attrs):
    """Label a host-side region on the profiler timeline.  ``attrs`` ride
    the event as metadata (``rid=7``), not in its name."""
    return jax.profiler.TraceAnnotation(name, **attrs)


class span:
    """One host span, two sinks: a ``TraceAnnotation`` named ``name`` (so
    the region sits on the profiler's clock beside the device operations
    it caused) and, on exit, ``METRICS.observe(name + "_seconds", dt)`` (so
    /metrics and the benchmark read the same interval as a window
    difference).  ``clock`` is injectable: the batcher passes its lockstep
    clock, tests a fake one.  ``on_exit`` is called with the clock reading
    the histogram closes on: accounting that must split at the span's own
    boundary (the batcher charges the time no program was in flight to the
    loop span it fell in) hangs there and reads no second clock.  With no
    profiler session the annotation is a flag test; the histogram is one
    lock and one append.

        with span("batcher.loop.admit"):
            ...

    Call-site rule: never inside a per-token or per-row Python loop, and
    nothing but ``jax.named_scope`` inside a jitted function.  Names are
    literals registered in METRIC_DOCS as ``<name>_seconds`` (graftlint
    GL302 reads ``span("...")`` calls as emitters of that histogram)."""

    __slots__ = ("_name", "_clock", "_on_exit", "_ann", "_t0")

    def __init__(self, name: str, clock=time.perf_counter, on_exit=None,
                 **attrs) -> None:
        self._name = name
        self._clock = clock
        self._on_exit = on_exit
        self._ann = annotate(name, **attrs)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = self._clock()
        return self

    def __exit__(self, *exc) -> None:
        now = self._clock()
        self._ann.__exit__(*exc)
        if self._on_exit is not None:
            self._on_exit(now)
        # graftlint: ignore[GL302](the name is the span's: GL302 checks every span("...") call site against METRIC_DOCS as "<name>_seconds")
        METRICS.observe(self._name + "_seconds", now - self._t0)


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_counting_compiles = False


def count_compiles() -> None:
    """Count trips through the backend compiler from inside the process: a
    ``jax.monitoring`` listener (registered once, however often this is
    called) that increments ``runtime.compiles_total`` and
    ``runtime.compile_seconds`` and logs the program's name.  The event
    wraps compile-or-load, so a load from the persistent cache counts too;
    a cached jit call fires nothing."""
    global _counting_compiles
    if _counting_compiles:
        return
    _counting_compiles = True

    def on_duration(event: str, duration: float, **kw) -> None:
        if event != _COMPILE_EVENT:
            return
        METRICS.inc("runtime.compiles_total")
        METRICS.inc("runtime.compile_seconds", duration)
        log.info("compiled %s in %.2f s", kw.get("fun_name", "?"), duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def record_memory_stats(prefix: str = "device") -> dict[str, float]:
    """Snapshot per-device memory occupancy into gauges (HBM on TPU).
    Returns {gauge_name: bytes}; devices without stats are skipped."""
    out: dict[str, float] = {}
    for i, dev in enumerate(jax.local_devices()):
        stats = getattr(dev, "memory_stats", lambda: None)()
        if not stats:
            continue
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            if key in stats:
                name = f"{prefix}{i}.{key}"
                # graftlint: ignore[GL302](gauge names are per-device — "<prefix><i>.bytes_in_use" — an open-ended family no registry entry can enumerate)
                METRICS.set_gauge(name, float(stats[key]))
                out[name] = float(stats[key])
    return out


def device_report(params=None) -> dict:
    """The devices JAX reports (``platform``, ``device_kind``, ``count``)
    and, given a param tree, ``weights_on``: the ids of the devices that
    hold it.  With JAX_PLATFORMS unset a failed TPU init is a silent CPU
    run, so /healthz, the boot log and every benchmark row carry this."""
    devs = jax.devices()
    out = {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "count": len(devs),
    }
    if params is not None:
        out["weights_on"] = sorted({
            d.id for leaf in jax.tree.leaves(params) for d in leaf.devices()
        })
    return out
