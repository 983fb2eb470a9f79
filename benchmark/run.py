#!/usr/bin/env python
"""One run of one benchmark cell.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process is the load generator and the arithmetic; it never imports JAX
on the accelerator.  It starts one child (``serve_child.py``: ``dlt-serve``
itself, under ``JAX_PLATFORMS=tpu``), sets the server up (boot, probes
against the golden, one warm-up per admission shape of the cell's traffic,
the pre-roll), measures for ``--seconds``, stops the child and prints one
JSON object as the last line of standard output.  No TPU, no result: there
is no CPU fallback.  ``--rehearsal`` runs the same code on the CPU with a
rehearsal configuration (``--rehearsal-config``) and prints counts only,
never a metric.

What a run holds a cell to beyond its requests is the configuration's to
say (:func:`held_to`): the kernels it must have taken, whether its rows lie
in a page pool, and the probes compared with the golden.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import client, metrics, trace_reduce, traffic  # noqa: E402

BOOT_TIMEOUT_S = 900.0
TRACE_S = 6.0            # the traced part of a --trace 1 window
TRACE_AFTER = 0.4        # ... which starts this share of the window in
GOLDEN_TOL = 0.05
PROBE_TOKENS = 8
# What a configuration that says nothing is held to: what every cell was
# held to before a configuration could say (PR 49).
PROBE_BYTES = (32, 200, 700, 1500)
MUST_DISPATCH = ("quant_matmul", "paged_decode")
COMPILE_LOG = re.compile(
    r"PERSISTENT COMPILATION CACHE MISS for '(\w+)'"
    r"|Persistent compilation cache hit for '(\w+)'"
)


class Failed(Exception):
    """The run cannot give a result."""


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def probe_prompt(n: int) -> str:
    """An n-byte prompt that depends on nothing but n."""
    words = f"probe {n}: the quick brown fox jumps over the lazy dog; "
    return (words * (n // len(words) + 1))[:n]


def serve_argv(config: dict, port: int) -> list[str]:
    s = config["serve"]
    argv = ["--preset", config["preset"], "--host", "127.0.0.1",
            "--port", str(port), "--slots", str(s["slots"]),
            "--max-len", str(s["max_len"]), "--page-size", str(s["page_size"]),
            "--paged-pages", str(s["paged_pages"]),
            "--chunk-steps", str(s["chunk_steps"])]
    return argv + list(s.get("extra_argv", []))


class Server:
    """The child that holds the chip, its log and its trace switch."""

    def __init__(self, config: dict, out_dir: str, platform: str):
        self.port = free_port()
        self.gw = client.Gateway(f"http://127.0.0.1:{self.port}")
        self.out_dir = out_dir
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = platform
        # Every trip through the compiler is logged by program name (that
        # is how compiles inside the window are counted), and every program
        # is cached, however quickly it compiled.
        env["JAX_DEBUG_LOG_MODULES"] = "jax._src.compiler"
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        cmd = [sys.executable, os.path.join(HERE, "serve_child.py"), out_dir,
               "--", *serve_argv(config, self.port)]
        self.log_path = os.path.join(out_dir, "server.log")
        self.log = open(self.log_path, "wb")
        say("$ " + " ".join(cmd[1:]))
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, env=env, stdin=subprocess.PIPE, stdout=self.log,
            stderr=subprocess.STDOUT,
        )

    def wait_ready(self) -> None:
        t0 = time.monotonic()
        while not self.gw.ready():
            if self.proc.poll() is not None:
                raise Failed(f"server exited with code {self.proc.returncode}"
                             " before it was ready")
            if time.monotonic() - t0 > BOOT_TIMEOUT_S:
                raise Failed(f"server not ready after {BOOT_TIMEOUT_S:.0f} s")
            time.sleep(0.25)

    def command(self, line: str, timeout: float = 120.0) -> None:
        """Send one trace command and wait for its acknowledgement."""
        ack = os.path.join(self.out_dir, line.split()[0])
        self.proc.stdin.write((line + "\n").encode())
        self.proc.stdin.flush()
        t0 = time.monotonic()
        while not os.path.exists(ack):
            if time.monotonic() - t0 > timeout or self.proc.poll() is not None:
                raise Failed(f"no acknowledgement of {line!r}")
            time.sleep(0.01)

    def log_size(self) -> int:
        self.log.flush()
        return os.path.getsize(self.log_path)

    def log_text(self, start: int = 0, end: int | None = None) -> str:
        with open(self.log_path, "rb") as f:
            f.seek(start)
            data = f.read() if end is None else f.read(end - start)
        return data.decode(errors="replace")

    def stop(self) -> int | None:
        code = self.proc.poll()
        if code is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                code = self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        if not self.log.closed:
            self.log.close()
        return code


# -- correctness --------------------------------------------------------
def held_to(serve: dict) -> dict:
    """What a run holds a cell to, read from its configuration's ``serve``:
    ``must_dispatch``, the operations whose compiled kernel a run must have
    taken; ``paged_pages``, where 0 is the server's word for no pool; and
    ``probe_bytes``, the lengths of the probes compared with the golden."""
    held = {"must_dispatch": list(serve.get("must_dispatch", MUST_DISPATCH)),
            "paged_pages": serve["paged_pages"],
            "probe_bytes": list(serve.get("probe_bytes", PROBE_BYTES))}
    if not held["must_dispatch"] or not all(
            isinstance(op, str) and op for op in held["must_dispatch"]):
        raise Failed("'must_dispatch' names at least one operation")
    if not held["probe_bytes"] or not all(
            isinstance(n, int) and n >= 1 for n in held["probe_bytes"]):
        raise Failed("'probe_bytes' lists at least one length in bytes")
    return held


def run_probes(gw: client.Gateway, max_len: int, probe_bytes) -> list[dict]:
    """The golden probes, sent alone with the prefix cache off."""
    out = []
    for n in probe_bytes:
        n = min(n, max_len - PROBE_TOKENS - 8)
        rec = client.send_alone(gw, probe_prompt(n), PROBE_TOKENS,
                                prefix_cache=False)
        if rec.failed:
            raise Failed(f"probe of {n} bytes failed: {rec.status} "
                         f"{rec.finish} {rec.error}")
        out.append({"bytes": n, "logprobs": rec.logprobs})
    return out


def check_golden(probes: list[dict], golden: dict | None) -> list[str]:
    """What is wrong with the probes against the golden (nothing: [])."""
    if golden is None:
        return ["no golden is recorded for this configuration"]
    faults = []
    if len(probes) != len(golden["probes"]):
        faults.append(f"{len(probes)} probes sent, "
                      f"{len(golden['probes'])} in the golden")
    for got, want in zip(probes, golden["probes"]):
        if got["bytes"] != want["bytes"]:
            faults.append(f"probe sizes differ: {got['bytes']} / {want['bytes']}")
            continue
        same = 0
        for a, b in zip(got["logprobs"], want["logprobs"]):
            if abs(a - b) > GOLDEN_TOL:
                break
            same += 1
        say(f"  probe {got['bytes']}: {same} of {len(want['logprobs'])} "
            "logprobs within the tolerance of the golden")
        if same == 0:
            faults.append(
                f"probe {got['bytes']}: first-token logprob "
                f"{got['logprobs'][0]} against golden {want['logprobs'][0]}")
    return faults


def check_dispatch(m: dict[str, float], must_dispatch) -> list[str]:
    """What is wrong with the dispatch record (nothing: []): an operation
    of ``must_dispatch`` that never took its compiled kernel and, whatever
    the list says, any fallback or interpreter leg that was taken."""
    disp = {k[len("ops_dispatch_"):]: v for k, v in m.items()
            if k.startswith("ops_dispatch_")}
    say(f"  dispatch record: {disp}")
    faults = [f"{op} did not take the compiled kernel"
              for op in must_dispatch
              if not disp.get(f"{op}_kernel", 0) > 0]
    faults += [f"{k} = {v}" for k, v in disp.items()
               if k.endswith(("_fallback", "_interpret")) and v]
    return faults


def warm_up(gw: client.Gateway, spec: dict, config: dict) -> list[str]:
    """One request per admission shape of the mix, alone; for a shared run
    a second question behind it, which is served from the prefix cache and
    must agree with a fresh send of itself.  A server without a pool has no
    run of pages to serve it from: there a shared run is sent like any
    other bytes."""
    rng = random.Random("warm-up")
    n_new = config["serve"]["chunk_steps"] + 1   # admission and one chunk
    pool = config["serve"]["paged_pages"] != 0
    faults = []
    for shared, prompt, answer in traffic.warmup_turns(
            spec, config["serve"]["page_size"]):
        asked = min(answer, n_new)
        shared = shared if pool else 0
        doc = traffic.text(rng, shared)
        first = doc + traffic.text(rng, prompt - shared)
        recs = [client.send_alone(gw, first, asked, shared=shared)]
        if shared:
            second = doc + traffic.text(rng, prompt - shared)
            cached = client.send_alone(gw, second, asked, shared=shared)
            fresh = client.send_alone(gw, second, asked, prefix_cache=False)
            recs += [cached, fresh]
            gap = max((abs(a - b) for a, b in
                       zip(cached.logprobs, fresh.logprobs)), default=math.nan)
            say(f"  warm-up {shared}+{prompt - shared}: {cached.cached_tokens}"
                f" tokens from the cache; first logprob cached "
                f"{cached.logprobs[:1]} fresh {fresh.logprobs[:1]}; "
                f"max |difference| {gap:.3g}")
            if cached.cached_tokens < (shared // config["serve"]["page_size"]
                                       ) * config["serve"]["page_size"]:
                faults.append(f"a {shared}-token shared run was served "
                              f"{cached.cached_tokens} tokens from the cache")
            if cached.logprobs and fresh.logprobs and abs(
                    cached.logprobs[0] - fresh.logprobs[0]) > GOLDEN_TOL:
                faults.append("cached and fresh first-token logprobs differ "
                              f"by more than {GOLDEN_TOL}")
        faults += [f"warm-up request failed: {r.status} {r.finish} {r.error}"
                   for r in recs if r.failed]
    return faults


# -- one run ------------------------------------------------------------
def run(args) -> dict:
    manifest = load_json(ROOT, "BENCHMARK.json")
    if args.rehearsal:
        cell = {"name": args.workload, "config": args.rehearsal_config,
                "traffic": args.workload, "chips": 1}
        layer_names = sorted(
            os.path.splitext(f)[0] for f in os.listdir(metrics.LAYER_DIR)
            if f.endswith((".json", ".py")))
    else:
        cells = {w["name"]: w for w in manifest["workloads"]}
        if args.workload not in cells:
            raise Failed(f"no workload {args.workload!r} in BENCHMARK.json")
        cell = cells[args.workload]
        layer_names = [
            m["name"] for m in manifest["per_layer"]
            if args.workload in m.get("workloads", [args.workload])]
    config = load_json(HERE, "configs", cell["config"] + ".json")
    if bool(config.get("rehearsal")) != args.rehearsal:
        raise Failed("a rehearsal configuration runs under --rehearsal, and "
                     "nothing else does")
    platform = "cpu" if args.rehearsal else "tpu"
    spec = traffic.load(cell["traffic"])
    held = held_to(config["serve"])
    say(f"held to: {json.dumps(held)}")
    unfit = traffic.pool_fits(spec, config["serve"], held["must_dispatch"])
    if unfit:
        raise Failed("the mix does not fit the server: " + "; ".join(unfit))
    peaks = load_json(HERE, "peaks.json")
    golden_path = os.path.join(HERE, "golden", cell["config"] + ".json")
    golden = load_json(golden_path) if os.path.exists(golden_path) else None

    out_dir = os.path.join(
        ROOT, "chiprun_out", "benchmark",
        f"{args.workload}-s{args.seed}-t{args.trace}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    trace_dir = os.path.join(out_dir, "trace")
    faults: list[str] = []
    srv = Server(config, out_dir, platform)
    try:
        srv.wait_ready()
        say(f"ready after {time.monotonic() - T_START:.1f} s")
        dev = srv.gw.health()["device"]
        say(f"device: {dev}")
        if dev["platform"] != platform or dev["count"] < cell["chips"]:
            raise Failed(f"the cell needs {cell['chips']} {platform} "
                         f"device(s); the server reports {dev}")
        if not args.rehearsal and dev["device_kind"] not in peaks:
            raise Failed(f"no peaks for device kind {dev['device_kind']!r}")

        probes = run_probes(srv.gw, config["serve"]["max_len"],
                            held["probe_bytes"])
        with open(os.path.join(out_dir, "probes.json"), "w") as f:
            json.dump({"probes": probes, "device_kind": dev["device_kind"],
                       "tolerance": GOLDEN_TOL}, f, indent=1)
        if golden is not None or not args.rehearsal:
            faults += check_golden(probes, golden)
        faults += warm_up(srv.gw, spec, config)
        say(f"warm after {time.monotonic() - T_START:.1f} s")

        snap: dict = {}

        def at_open() -> None:
            snap["setup_s"] = time.monotonic() - T_START
            snap["m0"] = srv.gw.metrics()
            snap["log0"] = srv.log_size()

        load = client.Load(srv.gw, spec, args.seed, args.seconds, at_open)
        tracer = None
        if args.trace:
            def trace_part() -> None:
                while math.isnan(load.t_open):
                    time.sleep(0.05)
                time.sleep(max(0.0, load.t_open + TRACE_AFTER * args.seconds
                               - time.monotonic()))
                srv.command(f"trace_start {trace_dir}")
                snap["tm0"] = srv.gw.metrics()
                time.sleep(min(TRACE_S, args.seconds / 2))
                snap["tm1"] = srv.gw.metrics()
                srv.command("trace_stop")

            tracer = threading.Thread(target=trace_part, name="tracer")
            tracer.start()
        records = load.run()
        say(f"window closed {time.monotonic() - load.t_open:.2f} s after it "
            "opened")
        m1 = srv.gw.metrics()
        log1 = srv.log_size()
        if tracer:
            tracer.join()

        again = run_probes(srv.gw, config["serve"]["max_len"],
                           held["probe_bytes"][:1])
        if again[0]["logprobs"] != probes[0]["logprobs"]:
            faults.append("the first probe answers differently after the "
                          "window than before it")
        m_end = srv.gw.metrics()
        health = srv.gw.health()
        code = srv.stop()
        if code != 0:
            faults.append(f"the server exited with code {code} on SIGTERM")
    except BaseException:
        srv.stop()
        sys.stderr.write("---- server log (tail) ----\n"
                         + srv.log_text()[-6000:] + "\n")
        raise

    # -- arithmetic ----------------------------------------------------
    m0 = snap["m0"]
    delta = {k: m1[k] - m0.get(k, 0.0) for k in m1}
    window_log = srv.log_text(snap["log0"], log1)
    compiled = [a or b for a, b in COMPILE_LOG.findall(window_log)]
    trace = None
    if args.trace:
        path = trace_reduce.find_xplane(trace_dir)
        if path is None:
            raise Failed("the trace left no .xplane.pb")
        os.environ["JAX_PLATFORMS"] = "cpu"
        trace = trace_reduce.reduce(trace_reduce.load_xplane(path))
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if trace is None and not args.rehearsal:
            raise Failed("no operation ran on a device plane of the trace")
    ctx = {
        "records": records, "seconds": args.seconds, "t_open": load.t_open,
        "setup_s": snap["setup_s"], "counters": delta, "gauges": m1,
        "trace": trace, "compiled_in_window": compiled, "config": config,
        "trace_counters": ({k: v - snap["tm0"].get(k, 0.0)
                            for k, v in snap["tm1"].items()}
                           if "tm1" in snap else None),
        "traffic": spec, "peaks": peaks.get(dev["device_kind"]),
    }
    attempted, failed = metrics.attempted_failed(records)
    if failed:
        bad = [r for r in metrics.window(records) if r.failed][:5]
        faults.append(f"{failed} failed requests, e.g. "
                      + "; ".join(f"{r.status} {r.finish} {r.n_tokens}/"
                                  f"{r.asked} {r.error}" for r in bad))
    if not args.rehearsal:
        faults += check_dispatch(m_end, held["must_dispatch"])
    if health.get("engine_restarts", 0) or delta.get(
            "server_engine_restarts", 0):
        faults.append("the engine restarted")
    if compiled:
        faults.append(f"compiled or loaded inside the window: {compiled}")
    if delta.get("batcher_preemptions_total", 0):
        faults.append(f"{delta['batcher_preemptions_total']:.0f} preemptions")
    early = sum(1 for r in metrics.window(records)
                if r.complete and r.finish == "stop")
    say(f"window: {attempted} requests, {failed} failed, "
        f"{metrics.tokens_in_window(records)} tokens, {early} ended early on "
        f"the end-of-sequence token, "
        f"{sum(r.cut for r in metrics.window(records))} cut by its end")

    values: dict[str, dict] = {}
    if args.trace and not args.rehearsal:
        for name in layer_names:
            got = metrics.read_layer_metric(name, ctx)
            if got is not None:
                values[name] = {"value": got[0], "unit": got[1]}
    elif not args.rehearsal:
        for m in manifest["end_to_end"]:
            if args.workload in m.get("workloads", [args.workload]):
                values[m["name"]] = {
                    "value": metrics.END_TO_END[m["name"]](ctx),
                    "unit": m["unit"]}
    peak = max((v for k, v in m_end.items()
                if re.fullmatch(r"device\d+_peak_bytes_in_use", k)), default=0)
    device = {"platform": dev["platform"], "kind": dev["device_kind"],
              "count": dev["count"], "memory_peak_bytes": int(peak)}
    with open(os.path.join(out_dir, "records.jsonl"), "w") as f:
        for r in records:
            f.write(json.dumps(r.to_json()) + "\n")
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump({"faults": faults, "counters": delta, "trace": trace,
                   "trace_counters": ctx["trace_counters"],
                   "setup_s": snap["setup_s"], "compiled": compiled,
                   "t_open": load.t_open, "held_to": held}, f, indent=1)
    for fault in faults:
        say(f"NOT CORRECT: {fault}")
    if args.rehearsal:
        # Counts only: a CPU run has no time, rate or share to report.
        layer = {n: metrics.read_layer_metric(n, ctx) for n in layer_names}
        return {"rehearsal": True, "correct": not faults,
                "attempted": attempted, "failed": failed,
                "counts": {"tokens": metrics.tokens_in_window(records),
                           "layer_metrics_read": sorted(
                               n for n, v in layer.items() if v)},
                "device": device, "held_to": held}
    result = {"correct": not faults, "attempted": attempted, "failed": failed,
              "metrics": values, "device": device, "held_to": held}
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = trace["breakdown"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a cell of BENCHMARK.json; under --rehearsal, a "
                         "traffic file")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="the same code on the CPU with a rehearsal "
                         "configuration; prints counts, never a metric")
    ap.add_argument("--rehearsal-config", default="rehearsal-tiny",
                    help="the configuration a --rehearsal runs: a file of "
                         "configs/ with \"rehearsal\": true")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the .xplane.pb under chiprun_out/")
    args = ap.parse_args(argv)
    if args.seconds is None:
        args.seconds = float(load_json(ROOT, "BENCHMARK.json")["run_seconds"])
    try:
        result = run(args)
    except Failed as e:
        print(f"benchmark: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
