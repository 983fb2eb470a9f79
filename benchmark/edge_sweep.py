#!/usr/bin/env python
"""What the trace's end does to a reading: an offline sweep over ONE kept
trace (PR 52).

    python3 benchmark/run.py --workload <cell> --seed <n> --trace 1 --keep-trace
    python3 benchmark/edge_sweep.py --run-dir chiprun_out/benchmark/<cell>-s<n>-t1 \\
        [--save-events events.json.gz]
    python3 benchmark/edge_sweep.py --events events.json.gz --config <name>

Cuts the event list's end at every ``--step-ms`` over its last
``--cuts x step`` (as a ``trace_stop`` that came earlier would have:
:func:`cut`) and reads
``quant_matmul_roofline`` and ``prefill_ms_per_ktok`` at each cut twice: by
the rule the readers hold (tokens and device time of the admissions the
trace pairs and holds whole: ``trace_reduce.reduce``) and by the OLD rule,
kept here as the control and nowhere else: the tokens of every
``batcher.admit.row`` span LAUNCHED before the cut, which is what a host
counter read just before ``trace_stop`` counts, against everything the
trace holds.  The old column steps when a launch straddles the cut; the
new one stays in a band.  It needs no chip and no JAX but to read an
``.xplane.pb`` (``--run-dir``, ``--xplane``); ``--save-events`` leaves the
list in a form that needs neither (a few MB: a trace is too large to bring
back from the chip's machine).
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import kernel_bytes, metrics, trace_reduce  # noqa: E402
from benchmark.trace_reduce import Event  # noqa: E402

KERNEL = "_quant_matmul_2d"


def pack(events: list[Event]) -> dict:
    """The device planes' events and the row spans, names in a table."""
    names: dict[tuple, int] = {}
    rows = []
    for e in events:
        if not (e.plane.startswith("/device:")
                or e.name == trace_reduce.ROW_SPAN):
            continue
        key = names.setdefault((e.plane, e.line, e.name), len(names))
        rows.append([key, e.start_ns, e.dur_ns] + ([e.stats] if e.stats else []))
    return {"names": [list(k) for k in names], "events": rows}


def unpack(data: dict) -> list[Event]:
    names = data["names"]
    return [Event(*names[r[0]], r[1], r[2], r[3] if len(r) > 3 else None)
            for r in data["events"]]


def save_events(events: list[Event], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(pack(events), f, separators=(",", ":"))


def load_events(path: str) -> list[Event]:
    with gzip.open(path, "rt") as f:
        return unpack(json.load(f))


def cut(events: list[Event], start_ns: int | None = None,
        end_ns: int | None = None) -> list[Event]:
    """The list a trace that started at ``start_ns`` and stopped at
    ``end_ns`` would have left, as the chip's profiler leaves it (my chip
    run, PR 52: the first and the last ``jit_decode_chunk`` of a trace last
    208 and 47 ms where a whole one lasts 237): a PROGRAM that straddles an
    edge is clipped to it; an operation that does, and a host span (which is
    recorded when it ends, and only if the trace was on when it began), is
    not in the list."""
    lo = min(e.start_ns for e in events) if start_ns is None else start_ns
    hi = max(e.end_ns for e in events) if end_ns is None else end_ns
    out = []
    for e in events:
        if lo <= e.start_ns and e.end_ns <= hi:
            out.append(e)
        elif (e.line == trace_reduce.MODULES_LINE
              and e.start_ns < hi and e.end_ns > lo):
            a, b = max(e.start_ns, lo), min(e.end_ns, hi)
            out.append(e._replace(start_ns=a, dur_ns=b - a))
    return out


def launched_tokens(events: list[Event], cut_ns: int) -> int:
    """What ``batcher.prefix_cache.miss_tokens`` adds between the trace's
    start and ``cut_ns``: every row span that BEGAN by then, whether or not
    the device has taken its program up."""
    return sum(trace_reduce.fresh_tokens(e) for e in events
               if e.name == trace_reduce.ROW_SPAN and e.stats
               and e.start_ns <= cut_ns)


def old_rule(trace: dict, tokens: float, config: dict, peaks: dict) -> dict:
    """The readings as the readers made them before PR 52 (the control):
    ``tokens`` of a host counter against every program and every second of
    the kernel the trace holds."""
    steps = config["serve"]["chunk_steps"]
    per_pass = kernel_bytes.quant_matmul_bytes_per_pass(config)
    weights = kernel_bytes.quant_matmul_weights(config)
    decode = sum(n * steps for name, n in trace["module_count"].items()
                 if name.startswith(trace_reduce.DECODE))
    admits = sum(n for name, n in trace["module_count"].items()
                 if name.startswith(trace_reduce.ADMISSIONS))
    least_s = decode * per_pass / peaks["hbm_bytes_per_s"] + max(
        admits * per_pass / peaks["hbm_bytes_per_s"],
        2.0 * tokens * weights / peaks["bf16_flops_per_s"])
    secs = sum(v for k, v in trace["module_s"].items()
               if k.startswith(trace_reduce.ADMISSIONS))
    kernel_s = trace["op_s"].get(KERNEL)
    return {
        "quant_matmul_roofline":
            100.0 * least_s / kernel_s if kernel_s and least_s else None,
        "prefill_ms_per_ktok": 1e6 * secs / tokens if tokens and secs else None,
    }


def new_rule(trace: dict, config: dict, peaks: dict) -> dict:
    """The same two readings by the readers themselves."""
    ctx = {"trace": trace, "config": config, "peaks": peaks}
    out = {}
    for name in ("quant_matmul_roofline", "prefill_ms_per_ktok"):
        got = metrics.read_layer_metric(name, ctx)
        out[name] = got[0] if got else None
    return out


def sweep(events: list[Event], config: dict, peaks: dict, cuts: int,
          step_ns: int) -> list[dict]:
    end = max(e.end_ns for e in events if e.plane.startswith("/device:"))
    rows = []
    for k in range(cuts):
        stop = end - k * step_ns
        trace = trace_reduce.reduce(cut(events, end_ns=stop))
        if trace is None:
            break
        tokens = launched_tokens(events, stop)
        adm = trace["admissions"]
        rows.append({
            "cut_ms": k * step_ns / 1e6,
            "paired": None if adm is None else len(adm),
            "paired_tokens": None if adm is None else sum(
                a["tokens"] for a in adm),
            "launched_tokens": tokens,
            "new": new_rule(trace, config, peaks),
            "old": old_rule(trace, tokens, config, peaks),
        })
    return rows


def table(rows: list[dict]) -> str:
    def f(v):
        return "   None" if v is None else f"{v:7.2f}"

    lines = ["cut ms | paired (tokens) | launched tokens | "
             "quant_matmul_roofline new | old | prefill_ms_per_ktok new | old"]
    for r in rows:
        lines.append(
            f"{r['cut_ms']:6.0f} | {r['paired']} ({r['paired_tokens']}) | "
            f"{r['launched_tokens']} | {f(r['new']['quant_matmul_roofline'])}"
            f" | {f(r['old']['quant_matmul_roofline'])} | "
            f"{f(r['new']['prefill_ms_per_ktok'])} | "
            f"{f(r['old']['prefill_ms_per_ktok'])}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--run-dir", help="a --keep-trace run's directory under "
                                      "chiprun_out/benchmark/")
    ap.add_argument("--xplane", help="an .xplane.pb")
    ap.add_argument("--events", help="a list --save-events left")
    ap.add_argument("--config", help="the configuration (with --run-dir: the "
                                     "cell's, from BENCHMARK.json)")
    ap.add_argument("--save-events", help="write the list here (.json.gz)")
    ap.add_argument("--device-kind", default="TPU v5 lite",
                    help="the key of peaks.json")
    ap.add_argument("--cuts", type=int, default=21)
    ap.add_argument("--step-ms", type=float, default=100.0)
    ap.add_argument("--out", help="write the rows here as JSON")
    args = ap.parse_args(argv)

    config_name = args.config
    if args.run_dir:
        cell = os.path.basename(os.path.normpath(args.run_dir)).rsplit(
            "-s", 1)[0]
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            cells = {w["name"]: w for w in json.load(f)["workloads"]}
        config_name = config_name or cells[cell]["config"]
        args.xplane = args.xplane or trace_reduce.find_xplane(
            os.path.join(args.run_dir, "trace"))
    if args.events:
        events = load_events(args.events)
    elif args.xplane:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        events = trace_reduce.load_xplane(args.xplane)
    else:
        ap.error("one of --run-dir, --xplane, --events")
    if args.save_events:
        save_events(events, args.save_events)
    if not config_name:
        return 0
    with open(os.path.join(HERE, "configs", config_name + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)[args.device_kind]
    rows = sweep(events, config, peaks, args.cuts, int(args.step_ms * 1e6))
    print(table(rows))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
