"""From a profiler trace to device busy time, kernel times and idle gaps.

The reduction works on plain events ``(plane, line, name, start_ns,
dur_ns)`` so that it can be checked on a small recorded list
(``tests/benchmark/data/``).  :func:`load_xplane` makes that list from the
``.xplane.pb`` a ``jax.profiler`` trace leaves; it is the only function here
that needs JAX, and it runs in the parent under ``JAX_PLATFORMS=cpu`` after
the child has exited.

Device planes are those named ``/device:TPU:<n>``.  On each, the line
``XLA Ops`` holds one event per executed operation and ``XLA Modules`` one
per executed program (``jit_decode_chunk(...)``).  Busy time is the union of
the operations' intervals; a gap is the idle time between two of them, and
is named by the host event that overlaps it longest.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def load_xplane(path: str) -> list[Event]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                # An operation's name is its whole HLO text: keep the stem.
                out.append(Event(plane.name, line.name, short_name(ev.name),
                                 int(ev.start_ns), int(ev.duration_ns)))
    return out


def short_name(name: str) -> str:
    """``%fusion.123 = ...`` -> ``fusion``; ``jit_decode_chunk(4817...)``
    -> ``jit_decode_chunk``: the part that survives a recompile."""
    name = name.split(" = ")[0].lstrip("%")
    name = re.sub(r"\(.*\)$", "", name)
    return re.sub(r"(\.\d+)+$", "", name)


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Sorted, disjoint intervals covering the same points."""
    out: list[tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


class _HostIndex:
    """Host events, for "which one overlaps this gap longest"."""

    def __init__(self, events: list[Event]):
        self.events = sorted(events, key=lambda e: e.start_ns)
        self.starts = [e.start_ns for e in self.events]
        self.max_end = []
        m = 0
        for e in self.events:
            m = max(m, e.end_ns)
            self.max_end.append(m)

    def covering(self, a: int, b: int) -> str:
        best, best_key = "unattributed", (0, 0)
        j = bisect.bisect_left(self.starts, b) - 1
        while j >= 0 and self.max_end[j] > a:
            e = self.events[j]
            overlap = min(b, e.end_ns) - max(a, e.start_ns)
            # Longest overlap; of equals, the shortest (innermost) event.
            key = (overlap, -e.dur_ns)
            if overlap > 0 and key > best_key:
                best, best_key = short_name(e.name), key
            j -= 1
        return best


def reduce(events: list[Event]) -> dict | None:
    """The summary the per-layer readers and the result line use, or None
    when no operation ran on a device plane."""
    planes = sorted({e.plane for e in events if DEVICE_PLANE.search(e.plane)})
    if not planes:
        return None
    # The window is the span of the device's own events: the profiler's
    # start and stop on the host are no part of the steady state.
    on_device = [e for e in events if e.plane in planes]
    t0 = min(e.start_ns for e in on_device)
    t1 = max(e.end_ns for e in on_device)
    busy_ns = []
    op_ns: dict[str, int] = defaultdict(int)
    op_count: dict[str, int] = defaultdict(int)
    module_ns: dict[str, int] = defaultdict(int)
    module_count: dict[str, int] = defaultdict(int)
    gaps: list[tuple[int, int]] = []
    for n, plane in enumerate(planes):
        ops = [e for e in events if e.plane == plane and e.line == OPS_LINE]
        merged = union([(e.start_ns, e.end_ns) for e in ops])
        busy_ns.append(sum(b - a for a, b in merged))
        if n == 0:
            gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
        for e in ops:
            op_ns[short_name(e.name)] += e.dur_ns
            op_count[short_name(e.name)] += 1
        for e in events:
            if e.plane == plane and e.line == MODULES_LINE:
                module_ns[short_name(e.name)] += e.dur_ns
                module_count[short_name(e.name)] += 1
    if not any(busy_ns):
        return None
    host = _HostIndex([e for e in events
                       if not e.plane.startswith("/device:") and e.dur_ns > 0])
    gap_ns: dict[str, int] = defaultdict(int)
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:2000]:
        gap_ns[host.covering(a, b)] += b - a
    n_dev = len(planes)

    def top(d):
        return [[k, v / n_dev / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "devices": n_dev,
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "op_s": {k: v / n_dev / 1e9 for k, v in op_ns.items()},
        "op_count": {k: v / n_dev for k, v in op_count.items()},
        "module_s": {k: v / n_dev / 1e9 for k, v in module_ns.items()},
        "module_count": {k: v / n_dev for k, v in module_count.items()},
        "gap_count": len(gaps),
        "gap_total_s": sum(b - a for a, b in gaps) / 1e9,
        "breakdown": {
            "device_ops": top(module_ns)[:TOP // 2] + top(op_ns)[:TOP // 2],
            "idle_gaps": [[k, v / 1e9] for k, v in
                          sorted(gap_ns.items(), key=lambda kv: -kv[1])[:TOP]],
        },
    }
