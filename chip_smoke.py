#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

Boots ``dlt-serve`` (``python -m distributed_llms_tpu.cli.serve_main``) as a
child process at the full published width and depth of ``qwen2-7b`` — block
weights int8, embeddings bf16, random weights from a seed, byte tokenizer,
paged KV pool, prefix cache, mixed schedule and overlap at their defaults —
sends a dozen requests over HTTP and checks what comes back, then boots it
a second time to show that the compilation cache spares the recompile.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # the same model on mesh.model=4
    python chip_smoke.py --rehearsal   # the same script on the CPU, tiny preset

This process imports no JAX: a chip belongs to one process, and that
process is the server.  The child runs under ``JAX_PLATFORMS=tpu``, so a
missing or failed TPU is an error there and can never become a CPU run.
On success the last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``,
with the device as the server's JAX reports it; any failed check exits 1
without that line.  The rehearsal checks everything except the platform,
the kernel paths and the cache, and never prints that line.

Times printed here (seconds to ready, first request) are set-up times of a
boot, not speeds of the system.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
BOOT_TIMEOUT_S = 600.0
REQUEST_TIMEOUT_S = 420.0  # the first request of a cold boot compiles

# What is served, per mode.  The chip shape compiles for one v5e with
# 15.75 GB usable: 8.9 GB of weights, a 1.9 GB pool of 512 pages, and the
# decode step's temporaries beside them.
CHIP = dict(
    preset="qwen2-7b", slots=16, max_len=4096, page_size=64, pages=512,
    max_tokens=16,
)
REHEARSAL = dict(
    preset="llama-tiny", slots=4, max_len=128, page_size=16, pages=40,
    max_tokens=6,
)
# ``--preset lfm2-8b-a1b``: the hybrid model (short convolutions beside six
# attention layers, 32 int8 experts), which serves without the prefix cache
# (the server refuses the pair) and has a third main-path kernel.
DENSE_KERNELS = ("quant_matmul", "paged_decode")
HYBRID = dict(prefix_cache=False, kernels=DENSE_KERNELS + ("moe_experts",))
# ``--preset ax-k1-ep16``: one chip's share of A.X-K1 (latent attention on
# latent pages, 12 of 192 int8 experts), as its benchmark cell serves it:
# 64 slots, 2,176 pages, the prefix cache ON, and a fourth kernel.  It also
# sends a long prompt again with a new suffix (``suffix_check``): the
# suffix is admitted behind the cached run, which is expanded from latent
# rows, and must give the first-token logprob a fresh admission gives.
LATENT = dict(kernels=DENSE_KERNELS + ("moe_experts", "mla_paged_decode"),
              suffix_check=True)
# ``--preset k-exaone-ep8``: one chip's share of K-EXAONE (windowed and full
# attention layers mixed: pages for the full layers, a ring a row for the
# rest; 16 of 128 int8 experts), as its benchmark cell serves it: 64 slots,
# --max-len 8192, 3,712 pages, no prefix cache (the server refuses the
# pair), and the rings' kernel on the dispatch record.  It also sends a
# prompt of 6,000 bytes (``long_check``): admitted at the 8,192 bucket in
# blocks, 94 pages deep, 46 wraps of the ring.
WINDOWED = dict(prefix_cache=False, long_check=True,
                kernels=DENSE_KERNELS + ("moe_experts", "swa_decode"))
# ``--preset smallthinker-pp4``: stage 0 of SmallThinker-21BA3B (a full
# layer without rotation then three with a window of 4,096, their rings
# walked block by block; 64 ReLU-gated int8 experts a layer routed on the
# block's input; the whole 151,936-row vocabulary), as its benchmark cell
# serves it: 32 slots, --max-len 16384, 3,712 pages, no prefix cache.  The
# 6,000-byte prompt wraps the ring once, in the admission.
SHAPES = {
    "qwen2-7b": (CHIP, REHEARSAL),
    "lfm2-8b-a1b": (dict(CHIP, preset="lfm2-8b-a1b", **HYBRID),
                    dict(REHEARSAL, preset="lfm2-tiny", **HYBRID)),
    "ax-k1-ep16": (dict(CHIP, preset="ax-k1-ep16", slots=64, pages=2176,
                        **LATENT),
                   dict(REHEARSAL, preset="ax-k1-tiny", **LATENT)),
    "k-exaone-ep8": (dict(CHIP, preset="k-exaone-ep8", slots=64,
                          max_len=8192, pages=3712, **WINDOWED),
                     dict(REHEARSAL, preset="k-exaone-tiny", **WINDOWED)),
    "smallthinker-pp4": (dict(CHIP, preset="smallthinker-pp4", slots=32,
                              max_len=16384, pages=3712, **WINDOWED),
                         dict(REHEARSAL, preset="smallthinker-tiny",
                              **WINDOWED)),
}


class Failed(Exception):
    """A check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)
    print(f"  ok: {what}", flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """One ``dlt-serve`` child and the HTTP calls made to it."""

    def __init__(self, shape: dict, chips: int, rehearsal: bool, tag: str):
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu" if rehearsal else "tpu"
        # JAX then logs every compilation-cache hit and miss by program
        # name, which is how boot 2 is read below; and it caches every
        # program, so that the reading does not depend on which side of
        # JAX's one-second threshold a compile happened to fall (on four
        # chips one decode_chunk variant compiles in 0.9 s).
        env["JAX_DEBUG_LOG_MODULES"] = "jax._src.compiler"
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        if rehearsal and chips > 1:
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={chips}"
            )
        cmd = [
            sys.executable, "-m", "distributed_llms_tpu.cli.serve_main",
            "--preset", shape["preset"],
            "--host", "127.0.0.1", "--port", str(self.port),
            "--slots", str(shape["slots"]),
            "--max-len", str(shape["max_len"]),
            "--page-size", str(shape["page_size"]),
            "--paged-pages", str(shape["pages"]),
            "--override", "runtime.serve_quantized=true",
            "--override", "checkpoint.quantization=int8",
        ]
        if shape.get("prefix_cache", True):
            cmd.append("--prefix-cache")
        if chips > 1:
            cmd += ["--override", f"mesh.model={chips}"]
        os.makedirs(OUT_DIR, exist_ok=True)
        self.log_path = os.path.join(OUT_DIR, f"server_{tag}.log")
        self.log = open(self.log_path, "wb")
        print("$ " + " ".join(cmd[1:]), flush=True)
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            cmd, cwd=HERE, env=env, stdout=self.log, stderr=subprocess.STDOUT,
        )

    def __enter__(self) -> "Server":
        return self

    def __exit__(self, exc_type, *_) -> None:
        """Always stop the child; on a failure show the end of its log."""
        self.stop()
        if exc_type is not None:
            print("---- server log (tail) ----\n" + self.log_tail(),
                  file=sys.stderr, flush=True)

    def get(self, path: str, timeout: float = 10.0):
        with urllib.request.urlopen(self.base + path, timeout=timeout) as r:
            return r.status, r.read()

    def wait_ready(self) -> float:
        """Seconds from spawn to the first 200 on /healthz."""
        while True:
            if self.proc.poll() is not None:
                raise Failed(
                    f"server exited with code {self.proc.returncode} "
                    "before it was ready"
                )
            if time.monotonic() - self.t0 > BOOT_TIMEOUT_S:
                raise Failed(f"server not ready after {BOOT_TIMEOUT_S:.0f} s")
            try:
                if self.get("/healthz", timeout=5.0)[0] == 200:
                    return time.monotonic() - self.t0
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(0.5)

    def health(self) -> dict:
        return json.loads(self.get("/healthz")[1])

    def metrics(self) -> dict[str, float]:
        out = {}
        for line in self.get("/metrics")[1].decode().splitlines():
            m = re.fullmatch(r"([A-Za-z_:][\w:]*) (\S+)", line)
            if m:
                out[m.group(1)] = float(m.group(2))
        return out

    def complete(self, prompt: str, **fields) -> dict:
        """POST /v1/completions; the parsed body (streams reassembled into
        the same shape, with ``usage.completion_tokens`` counted from the
        streamed logprobs)."""
        body = {"prompt": prompt, "temperature": 0, **fields}
        req = urllib.request.Request(
            self.base + "/v1/completions", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=REQUEST_TIMEOUT_S) as r:
                status, raw = r.status, r.read()
        except urllib.error.HTTPError as e:
            raise Failed(f"HTTP {e.code} for {body}: {e.read()[:300]!r}")
        check(status == 200, f"200 for a {len(prompt)}-byte prompt {fields}")
        if not fields.get("stream"):
            return json.loads(raw)
        events = [
            line[len(b"data: "):] for line in raw.split(b"\n")
            if line.startswith(b"data: ")
        ]
        check(events and events[-1] == b"[DONE]", "stream ends with [DONE]")
        text, lps, reason = "", [], None
        for ev in events[:-1]:
            choice = json.loads(ev)["choices"][0]
            text += choice["text"]
            lps += (choice["logprobs"] or {}).get("token_logprobs", [])
            reason = choice["finish_reason"] or reason
        return {
            "choices": [{"text": text, "finish_reason": reason,
                         "logprobs": {"token_logprobs": lps}}],
            "usage": {"completion_tokens": len(lps)},
        }

    def stop(self) -> int | None:
        """SIGTERM, then the exit code (None if it had to be killed)."""
        code = self.proc.poll()
        if code is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                code = self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self.log.close()
        return code

    def log_text(self) -> str:
        if not self.log.closed:
            self.log.flush()
        with open(self.log_path, "rb") as f:
            return f.read().decode(errors="replace")

    def log_tail(self, n: int = 4000) -> str:
        lines = [ln for ln in self.log_text().splitlines()
                 if "jax._src.compiler" not in ln]
        return "\n".join(lines)[-n:]


def check_answer(out: dict, what: str) -> tuple:
    """What every answer must satisfy; returns (text, chosen-token
    logprobs or None).  At a 152k vocabulary random weights rarely pick a
    byte token, so the decoded text is mostly empty: where two answers are
    compared, their logprobs say whether the same tokens came out."""
    choice = out["choices"][0]
    check(out["usage"]["completion_tokens"] >= 1, f"{what}: >= 1 token")
    check(choice["finish_reason"] in ("length", "stop"),
          f"{what}: finish_reason {choice['finish_reason']!r}")
    lps = (choice.get("logprobs") or {}).get("token_logprobs")
    return choice["text"], lps


def prompt_of(n: int, salt: str) -> str:
    """An n-byte ASCII prompt (one token a byte) that no other shares a
    first page with."""
    words = f"{salt} the quick brown fox jumps over the lazy dog; "
    return (words * (n // len(words) + 1))[:n]


def serve_requests(srv: Server, shape: dict) -> None:
    """The dozen requests and what must hold of each."""
    page, n_new = shape["page_size"], shape["max_tokens"]
    cap = shape["max_len"] - n_new - 8

    print("first request (a cold boot compiles here):", flush=True)
    t0 = time.monotonic()
    check_answer(srv.complete(prompt_of(24, "warm"), max_tokens=n_new),
                 "first request")
    print(f"  first request answered in {time.monotonic() - t0:.1f} s "
          "(set-up, not a speed)", flush=True)

    print("five at once, prompts in different length buckets:", flush=True)
    lens = [min(n, cap) for n in (9, 40, 3 * page // 2, 5 * page, 9 * page)]
    outs: list = [None] * len(lens)

    def one(i: int) -> None:
        try:
            outs[i] = srv.complete(prompt_of(lens[i], f"c{i}"),
                                   max_tokens=n_new)
        except Exception as e:  # reported below, on the main thread
            outs[i] = e

    threads = [threading.Thread(target=one, args=(i,)) for i in range(len(lens))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(REQUEST_TIMEOUT_S + 30)
    for n, out in zip(lens, outs):
        if isinstance(out, Exception):
            raise Failed(f"concurrent {n}-byte request: {out}")
        check(out is not None, f"concurrent {n}-byte request returned")
        check_answer(out, f"concurrent {n}-byte request")

    print("one streamed:", flush=True)
    check_answer(
        srv.complete(prompt_of(30, "stream"), max_tokens=n_new, stream=True,
                     logprobs=True),
        "streamed request",
    )

    print("one with logprobs:", flush=True)
    out = srv.complete(prompt_of(30, "lp"), max_tokens=n_new, logprobs=True)
    _, lps = check_answer(out, "logprobs request")
    check(len(lps) == out["usage"]["completion_tokens"]
          and all(isinstance(x, float) and math.isfinite(x) for x in lps),
          f"{len(lps)} logprobs, all finite")

    print("one prompt longer than a page, sent twice:", flush=True)
    long_prompt = prompt_of(min(2 * page + page // 2, cap), "long")
    first = srv.complete(long_prompt, max_tokens=n_new, logprobs=True)
    second = srv.complete(long_prompt, max_tokens=n_new, logprobs=True)
    (t1, l1), (t2, l2) = (check_answer(first, "long prompt"),
                          check_answer(second, "long prompt again"))
    cached = second["usage"].get("prompt_tokens_details", {}).get("cached_tokens", 0)
    if shape.get("prefix_cache", True):
        check(cached >= page, f"second send served {cached} tokens from "
                              f"cache (>= page size {page})")
    else:
        check(cached == 0, "no prefix cache: nothing served from it")
    # Compared, not asserted: the cached run and the fresh prefill round
    # differently in bf16, and with random weights the top logit is close.
    gap = (max(abs(a - b) for a, b in zip(l1, l2))
           if len(l1) == len(l2) else float("nan"))
    print(f"  cached against fresh: texts "
          f"{'equal' if t1 == t2 else 'DIFFER'} ({t1!r} / {t2!r}), logprobs "
          f"{'equal' if l1 == l2 else 'differ'} (max |difference| {gap:.3g})",
          flush=True)

    print("one request twice with the prefix cache off:", flush=True)
    p = prompt_of(min(page + 7, cap), "nocache")
    a = check_answer(
        srv.complete(p, max_tokens=n_new, prefix_cache=False, logprobs=True),
        "uncached request")
    b = check_answer(
        srv.complete(p, max_tokens=n_new, prefix_cache=False, logprobs=True),
        "uncached request again")
    check(a == b, f"identical text and logprobs both times ({a[0]!r}, "
                  f"{len(a[1])} logprobs)")

    if shape.get("long_check"):
        print("one long prompt, twice:", flush=True)
        p = prompt_of(6000 if cap > 6100 else cap - 20, "sixk")
        a, b = (check_answer(srv.complete(p, max_tokens=n_new, logprobs=True),
                             f"{len(p)}-byte prompt") for _ in range(2))
        check(a == b, f"identical text and logprobs both times ({a[0]!r})")

    if shape.get("suffix_check"):
        print("a long prompt again with a new suffix:", flush=True)
        n_base = min(1500, cap - 100) if cap > 400 else cap - 20
        n_suffix = 100 if cap > 400 else 10
        base = prompt_of(n_base, "base")
        check_answer(srv.complete(base + prompt_of(n_suffix, "s1"),
                                  max_tokens=n_new), "base + first suffix")
        again = base + prompt_of(n_suffix, "s2")
        hit = srv.complete(again, max_tokens=n_new, logprobs=True)
        cold = srv.complete(again, max_tokens=n_new, logprobs=True,
                            prefix_cache=False)
        (_, lh), (_, lc) = (check_answer(hit, "base + second suffix"),
                            check_answer(cold, "the same, cache off"))
        cached = hit["usage"].get("prompt_tokens_details", {}).get(
            "cached_tokens", 0)
        check(cached >= (n_base // page) * page,
              f"the suffix was admitted behind {cached} cached tokens "
              f"(>= {(n_base // page) * page})")
        check(abs(lh[0] - lc[0]) <= 0.05,
              f"first-token logprob behind the cached run {lh[0]:.6f}, "
              f"fresh {lc[0]:.6f}: within 0.05")


def check_dispatch(metrics: dict[str, float], chips: int,
                   kernels=DENSE_KERNELS) -> None:
    """The dispatch record: every main-path kernel compiled, nothing on a
    fallback or on the interpreter; on a mesh, every kernel trace inside
    the per-shard body (so it ran on every shard)."""
    disp = {k[len("ops_dispatch_"):]: v for k, v in metrics.items()
            if k.startswith("ops_dispatch_")}
    print(f"  dispatch record: {disp}", flush=True)
    for op in kernels:
        check(disp.get(f"{op}_kernel", 0) > 0, f"{op} took the compiled kernel")
        if chips > 1:
            check(disp.get(f"{op}_shard_map") == disp[f"{op}_kernel"],
                  f"every {op} kernel trace ran per shard")
    if "quant_matmul" in kernels:
        check(disp.get("quant_matmul_k_minor") == disp["quant_matmul_kernel"],
              "every quant_matmul kernel trace took the lane-dense leg")
    bad = {k: v for k, v in disp.items()
           if k.endswith(("_fallback", "_interpret")) and v}
    check(not bad, f"no op on fallback or interpret ({bad or 'none'})")


def check_memory(metrics: dict[str, float], n_dev: int, chips: int) -> None:
    used = [metrics.get(f"device{i}_bytes_in_use") for i in range(n_dev)]
    peak = [metrics.get(f"device{i}_peak_bytes_in_use") for i in range(n_dev)]
    check(all(x is not None for x in used[:chips] + peak[:chips]),
          "memory_stats() reported for every device in use")
    for i in range(chips):
        print(f"  device {i}: bytes_in_use {used[i] / 1e9:.2f} GB, "
              f"peak_bytes_in_use {peak[i] / 1e9:.2f} GB", flush=True)
    if chips > 1:
        total = sum(used[:chips])
        share = [u / total for u in used[:chips]]
        check(max(share) < 1.5 / chips and min(share) > 0.5 / chips,
              f"each device holds about 1/{chips} of the bytes in use "
              f"({[round(s, 3) for s in share]}), none the whole")


def run(args) -> dict:
    shape = SHAPES[args.preset][args.rehearsal]
    with Server(shape, args.chips, args.rehearsal, "boot1") as srv:
        ready = srv.wait_ready()
        print(f"boot 1 ready in {ready:.1f} s (set-up, not a speed)", flush=True)
        dev = srv.health()["device"]
        print(f"device: platform={dev['platform']} "
              f"device_kind={dev['device_kind']} count={dev['count']} "
              f"weights_on={dev['weights_on']}", flush=True)
        if not args.rehearsal:
            check(dev["platform"] == "tpu", "the server runs on a TPU")
        check(dev["count"] >= args.chips and
              len(dev["weights_on"]) == args.chips,
              f"weights on {args.chips} device(s)")
        serve_requests(srv, shape)
        health = srv.health()
        check(health["engine_restarts"] == 0, "engine_restarts == 0")
        metrics = srv.metrics()
        if not args.rehearsal:
            check_dispatch(metrics, args.chips,
                           shape.get("kernels", DENSE_KERNELS))
            check_memory(metrics, dev["count"], args.chips)
        else:
            check(any(k.startswith("ops_dispatch_") for k in metrics),
                  "/metrics exports the dispatch record")
        check(srv.stop() == 0, "server exits 0 on SIGTERM")
    if args.rehearsal:
        return dev  # a CPU run keeps no compilation cache: nothing to show

    print("second boot, on the compilation cache the first one filled:",
          flush=True)
    with Server(shape, args.chips, False, "boot2") as srv:
        ready2 = srv.wait_ready()
        t0 = time.monotonic()
        check_answer(srv.complete(prompt_of(24, "warm"),
                                  max_tokens=shape["max_tokens"]),
                     "first request of boot 2")
        first2 = time.monotonic() - t0
        print(f"boot 2 ready in {ready2:.1f} s, first request in "
              f"{first2:.1f} s (set-up, not a speed)", flush=True)
        log = srv.log_text()
        hits = set(re.findall(r"cache hit for '(\w+)'", log))
        misses = set(re.findall(r"CACHE MISS for '(\w+)'", log))
        print(f"  from the cache: {sorted(hits)}\n  compiled anew: "
              f"{sorted(misses)}", flush=True)
        for program in ("jit_admit_row_paged", "jit_decode_chunk"):
            check(program in hits and program not in misses,
                  f"boot 2 took {program} from the cache, with no fresh "
                  "compile")
        check(srv.stop() == 0, "server exits 0 on SIGTERM")
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="devices the model spans: above 1, the server "
                         "boots on --override mesh.model=CHIPS")
    ap.add_argument("--preset", choices=sorted(SHAPES), default="qwen2-7b",
                    help="the model served (under --rehearsal its tiny "
                         "stand-in)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="run the same script on JAX_PLATFORMS=cpu with a "
                         "tiny preset; proves the script, not the chip")
    args = ap.parse_args(argv)
    if args.rehearsal:
        print("chip_smoke: REHEARSAL on the CPU with a tiny preset — this "
              "is not a chip run", flush=True)
    else:
        print(f"chip_smoke: {args.preset} int8 through dlt-serve on "
              f"{args.chips} chip(s)", flush=True)
    try:
        dev = run(args)
    except Failed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    device = {"platform": dev["platform"], "kind": dev["device_kind"],
              "count": dev["count"]}
    if args.rehearsal:
        print(json.dumps({"rehearsal": "passed", "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
