"""Profiling: jax.profiler capture + step timing + HBM occupancy.

The reference has no profiler or timing instrumentation of any kind — only
``print()`` logging (SURVEY §5.1; benchmarking was a plan item,
plan.md:297-300).  Here:

- :func:`trace` captures a TensorBoard/Perfetto trace of everything run
  inside it (XLA ops, host callbacks, transfers) via ``jax.profiler``;
- :func:`annotate` labels host-side regions so they show up on the trace;
- :class:`StepTimer` measures wall-per-step and derived throughput into the
  global METRICS registry (tokens/s, p50/p95 step time — the BASELINE.md
  north-star metrics);
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


def annotate(name: str):
    """Label a host-side region on the profiler timeline (and in nested
    StepTimer logs)."""
    return jax.profiler.TraceAnnotation(name)


class StepTimer:
    """Times steps and feeds METRICS.

        timer = StepTimer("train")
        for batch in data:
            with timer.step(tokens=batch.size):
                run_step(batch)

    Records ``<name>.step_seconds`` (histogram -> p50/p95) and a
    ``<name>.tokens_per_second`` gauge over a sliding window.
    """

    def __init__(self, name: str, window: int = 32,
                 clock=time.perf_counter) -> None:
        self.name = name
        self._window = window
        self._samples: list[tuple[float, int]] = []  # (seconds, tokens)
        self.steps = 0
        # Injectable clock: tests drive a fake monotonic counter instead of
        # sleeping wall-clock time to make dt nonzero (graftlint GL501).
        self._clock = clock

    @contextlib.contextmanager
    def step(self, tokens: int = 0) -> Iterator[None]:
        t0 = self._clock()
        with annotate(f"{self.name}.step"):
            yield
        dt = self._clock() - t0
        self.steps += 1
        METRICS.observe(f"{self.name}.step_seconds", dt)
        if tokens:
            self._samples.append((dt, tokens))
            if len(self._samples) > self._window:
                self._samples = self._samples[-self._window :]
            total_t = sum(s for s, _ in self._samples)
            total_tok = sum(n for _, n in self._samples)
            METRICS.set_gauge(
                f"{self.name}.tokens_per_second", total_tok / max(total_t, 1e-9)
            )

    @property
    def tokens_per_second(self) -> float:
        return METRICS.snapshot()["gauges"].get(f"{self.name}.tokens_per_second", 0.0)


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
