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

**Tokens against device time** (PR 52; benchmark/README.md has the section).
A count of prompt tokens is set against device time only of the admissions
whose device time it is, both read from the trace: :func:`pair_admissions`
pairs each ``batcher.admit.row`` span (whose attributes ride the host event
as stats) with the ``jit_admit_row*`` program it launched, and
:func:`reduce` hands out, beside the sums over everything, the paired
admissions one by one and the whole decode programs, each with the kernels'
seconds INSIDE it.  A counter read on the host around the trace is not the
device's window: an admission launched just before ``trace_stop`` is
counted whole and gives no device time.
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
ROW_SPAN = "batcher.admit.row"       # the one span an admission is launched in
ADMISSIONS = ("jit_admit_row",)      # ... and the programs it launches
DECODE = ("jit_decode_chunk", "jit_mixed_step")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int
    # ``batcher.admit.row``: the span's attributes (``rid``,
    # ``prompt_tokens``, ``cached_tokens``, ``bucket``, ``live_rows``,
    # ``fetched_rid`` where an admission was in flight ahead of it, ...).
    # ``XLA Modules``: ``{"program": <the event's full name>}``, whose
    # fingerprint tells one compiled program from another of the same
    # function.  None elsewhere, and in lists recorded before PR 52.
    stats: dict | None = None

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
            modules = line.name == MODULES_LINE
            for ev in line.events:
                # An operation's name is its whole HLO text: keep the stem.
                name = short_name(ev.name)
                stats = None
                if modules:
                    stats = {"program": ev.name}
                elif name == ROW_SPAN:
                    stats = dict(ev.stats)
                out.append(Event(plane.name, line.name, name,
                                 int(ev.start_ns), int(ev.duration_ns), stats))
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


def fresh_tokens(span: Event) -> int:
    """What the admission a row span launched prefilled fresh: what
    ``batcher.prefix_cache.miss_tokens`` adds for it."""
    return span.stats["prompt_tokens"] - span.stats["cached_tokens"]


def pair_admissions(spans: list[Event], programs: list[Event]
                    ) -> list[tuple[Event, Event]] | None:
    """Each ``batcher.admit.row`` span of the trace with the admission
    program it launched, or None where the trace does not bear the pairing
    out.  ``programs`` are ALL the ``jit_admit_row*`` events of one device
    plane, cut ones too: the device runs admissions in launch order, so the
    pairing is by order and a cut program holds its place in it.

    The hazards are the edges.  A span that began before the trace is not
    in it, but its program may be: pairing starts at the first span with no
    ``fetched_rid`` (nothing was in flight ahead of it, so every program
    launched before it had ended when it began) and with the first program
    that starts at or after that span does.  From there first in, first
    out; what is left over at the end (a span whose program the trace's end
    cut away, a program whose span was still open at ``trace_stop`` and so
    was never recorded) is in neither side.

    The guards, from what the trace itself knows: a program starts no
    earlier than its span; it has ended when the admission is settled,
    which is by the END of the next span where that one names it
    ``fetched_rid`` and by its START otherwise; and all runs of one
    compiled program (the event's full name, fingerprint and all) were
    launched by spans of one ``bucket``.  Where one fails the answer is
    None: a pairing that may be shifted by one is not read."""
    spans = sorted(spans, key=lambda e: e.start_ns)
    programs = sorted(programs, key=lambda e: e.start_ns)
    if any(s.stats is None or "bucket" not in s.stats for s in spans):
        return None if programs else []
    first = next((i for i, s in enumerate(spans)
                  if "fetched_rid" not in s.stats), None)
    if first is None:
        return []
    spans = spans[first:]
    programs = [p for p in programs if p.start_ns >= spans[0].start_ns]
    pairs = list(zip(spans, programs))
    bucket_of: dict[str, int] = {}
    for k, (span, prog) in enumerate(pairs):
        if prog.start_ns < span.start_ns:
            return None
        if k + 1 < len(spans):
            then = spans[k + 1]
            behind = then.stats.get("fetched_rid") == span.stats.get("rid")
            if prog.end_ns > (then.end_ns if behind else then.start_ns):
                return None
        fingerprint = (prog.stats or {}).get("program", prog.name)
        if bucket_of.setdefault(fingerprint, span.stats["bucket"]) != \
                span.stats["bucket"]:
            return None
    return pairs


def _inside(ops: list[Event], programs: list[Event]) -> list[dict[str, int]]:
    """For each of ``programs`` (of one device, so disjoint; in order of
    their start), the nanoseconds by name of the operations whose interval
    lies inside it."""
    starts = [p.start_ns for p in programs]
    out: list[dict[str, int]] = [defaultdict(int) for _ in programs]
    for e in ops:
        j = bisect.bisect_right(starts, e.start_ns) - 1
        if j >= 0 and e.end_ns <= programs[j].end_ns:
            out[j][short_name(e.name)] += e.dur_ns
    return out


def paired_programs(events: list[Event], plane: str) -> dict:
    """``admissions``, ``decode`` and ``row_spans`` of :func:`reduce`, from
    one device plane.  A program is WHOLE when it touches neither edge of
    that plane's own window: the profiler clips a program that runs across
    its start or its stop to it (the first and the last
    ``jit_decode_chunk`` of a trace last 208 and 47 ms of 237: my chip run,
    PR 52)."""
    on_plane = [e for e in events if e.plane == plane]
    t0 = min(e.start_ns for e in on_plane)
    t1 = max(e.end_ns for e in on_plane)
    modules = sorted((e for e in on_plane if e.line == MODULES_LINE),
                     key=lambda e: e.start_ns)
    ops = [e for e in on_plane if e.line == OPS_LINE]

    def whole(e: Event) -> bool:
        return e.start_ns > t0 and e.end_ns < t1

    decode = [e for e in modules
              if short_name(e.name).startswith(DECODE) and whole(e)]
    inside = _inside(ops, decode)
    decode_ns: dict[str, int] = defaultdict(int)
    for d in inside:
        for k, v in d.items():
            decode_ns[k] += v
    out = {"decode": {
        "count": len(decode), "seconds": sum(e.dur_ns for e in decode) / 1e9,
        "op_s": {k: v / 1e9 for k, v in decode_ns.items()}}}
    spans = [e for e in events
             if not e.plane.startswith("/device:") and e.name == ROW_SPAN]
    out["row_spans"] = {
        "count": len(spans),
        "tokens": sum(fresh_tokens(s) for s in spans
                      if s.stats and "bucket" in s.stats)}
    pairs = pair_admissions(spans, [
        e for e in modules if short_name(e.name).startswith(ADMISSIONS)])
    if pairs is None:
        return {**out, "admissions": None}
    pairs = [(s, p) for s, p in pairs if whole(p)]
    inside = _inside(ops, [p for _, p in pairs])
    out["admissions"] = [{
        "rid": s.stats.get("rid"), "program": short_name(p.name),
        "seconds": p.dur_ns / 1e9, "tokens": fresh_tokens(s),
        "bucket": s.stats["bucket"], "live_rows": s.stats.get("live_rows"),
        "op_s": {k: v / 1e9 for k, v in d.items()},
    } for (s, p), d in zip(pairs, inside)]
    return out


def _is_paired(trace: dict) -> bool:
    return trace.get("admissions") is not None and "decode" in trace


def inside_s(trace: dict, kernel: str) -> float | None:
    """Seconds of ``kernel`` inside the paired admissions and the whole
    decode programs of a reduced trace: the device time the tokens and the
    steps read from those same programs are set against.  None where the
    admissions could not be paired."""
    if not _is_paired(trace):
        return None
    return trace["decode"]["op_s"].get(kernel, 0.0) + sum(
        a["op_s"].get(kernel, 0.0) for a in trace["admissions"])


def least_s(trace: dict, steps: int, step_s: float, admission_s
            ) -> float | None:
    """The least time of the programs a roofline reads: the whole decode
    programs (each ``steps`` steps of ``step_s`` seconds) plus, a paired
    admission at a time, ``admission_s(tokens)``.  None where the
    admissions could not be paired."""
    if not _is_paired(trace):
        return None
    return trace["decode"]["count"] * steps * step_s + sum(
        admission_s(a["tokens"]) for a in trace["admissions"])


def paired_share(trace: dict, kernel: str, least) -> float | None:
    """``kernel`` against its roofline, in percent: ``least`` seconds
    (:func:`least_s`) over the kernel's seconds inside the same programs
    (:func:`inside_s`).  None where there is nothing to read.  Nothing is
    clamped."""
    kernel_s = inside_s(trace, kernel)
    if not kernel_s or not least:
        return None
    return 100.0 * least / kernel_s


def reduce(events: list[Event]) -> dict | None:
    """The summary the per-layer readers and the result line use, or None
    when no operation ran on a device plane.  Beside the sums over
    everything the trace holds (``op_s``, ``module_s``, ``module_count``,
    ``breakdown``): ``admissions``, the admission programs that lie WHOLE
    inside the trace and are paired with their span, each with its
    ``seconds``, its kernels' seconds by name (``op_s``) and its span's
    ``tokens`` (``prompt_tokens - cached_tokens``: what the admission
    prefilled fresh), ``bucket`` and ``live_rows``, or None where the
    pairing does not hold (:func:`pair_admissions`); and ``decode``, the
    ``count``, ``seconds`` and kernels' seconds of the whole
    ``jit_decode_chunk`` / ``jit_mixed_step`` programs.  Both are of the
    first device plane.  ``row_spans`` counts every row span the trace
    holds and its tokens, paired or not: what a host counter of the same
    window would have said, for the eye."""
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
        **paired_programs(events, planes[0]),
        "breakdown": {
            "device_ops": top(module_ns)[:TOP // 2] + top(op_ns)[:TOP // 2],
            "idle_gaps": [[k, v / 1e9] for k, v in
                          sorted(gap_ns.items(), key=lambda kv: -kv[1])[:TOP]],
        },
    }
