"""Device time per decode step by operation name, from a trace the
benchmark kept (``benchmark/run.py --trace 1 --keep-trace``): the `XLA Ops`
events inside WHOLE `jit_decode_chunk` events (the trace's edges cut some),
over the steps of a chunk, and the same per admission program.  PERF.md's
per-step tables come from it.  Run where the trace lies, on the CPU:

    JAX_PLATFORMS=cpu python tools/step_dig.py <run-dir> <out.json> [steps-per-chunk]
"""
import collections, glob, json, os, re, statistics, sys

from jax.profiler import ProfileData

run_dir, out_path = sys.argv[1], sys.argv[2]
steps = int(sys.argv[3]) if len(sys.argv) > 3 else 8
(path,) = glob.glob(os.path.join(run_dir, "trace", "plugins", "profile", "*", "*.xplane.pb"))
data = ProfileData.from_file(path)
modules, ops = [], []
for plane in data.planes:
    if not plane.name.startswith("/device:TPU:0"):
        continue
    for line in plane.lines:
        for ev in line.events:
            if line.name == "XLA Modules":
                modules.append((ev.name.split("(")[0], int(ev.start_ns), int(ev.duration_ns)))
            elif line.name == "XLA Ops":
                stem = re.sub(r"(\.\d+)+$", "", re.sub(r"\(.*\)$", "", ev.name.split(" = ")[0].lstrip("%")))
                ops.append((stem, int(ev.start_ns), int(ev.duration_ns)))
chunks = sorted((m for m in modules if m[0].startswith("jit_decode_chunk")), key=lambda m: m[1])
top = max(m[2] for m in chunks)
whole = [m for m in chunks if m[2] > 0.9 * top]
by_op = collections.defaultdict(int)
ops.sort(key=lambda o: o[1])
for name, start, dur in whole:
    for stem, s, d in ops:
        if s >= start and s + d <= start + dur:
            by_op[stem] += d
n = len(whole) * steps
admits = [m for m in modules if m[0].startswith("jit_admit_row")]
adm_op = collections.defaultdict(int)
for name, start, dur in admits:
    for stem, s, d in ops:
        if s >= start and s + d <= start + dur:
            adm_op[stem] += d
out = {
    "whole_chunks": len(whole), "chunks_seen": len(chunks),
    "chunk_ms": [round(m[2] / 1e6, 2) for m in whole],
    "step_ms": statistics.median(m[2] for m in whole) / 1e6 / steps,
    "per_step_ms": {k: round(v / 1e6 / n, 3) for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:14]},
    "admissions": len(admits),
    "admit_ms": [round(m[2] / 1e6, 1) for m in admits],
    "per_admission_ms": {k: round(v / 1e6 / max(len(admits), 1), 3) for k, v in sorted(adm_op.items(), key=lambda kv: -kv[1])[:10]},
    "modules_s": {k: round(sum(m[2] for m in modules if m[0] == k) / 1e9, 3) for k in sorted({m[0] for m in modules})},
}
json.dump(out, open(out_path, "w"), indent=1)
print(json.dumps(out))
