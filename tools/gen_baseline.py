#!/usr/bin/env python
"""Regenerate BASELINE.md's ladder-of-record section from BENCH_LADDER.json.

VERDICT r3 weak #2 / next-step 8: BASELINE.md's performance claims must come
from the measured artifact, not hand-maintained prose — a config that is
merely *instrumented* must read NOT YET MEASURED until a row with a
``measured_on`` stamp exists.  This script rewrites everything between the
AUTOGEN markers in BASELINE.md from the JSON; run it after every ladder run.
"""
from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BEGIN = "<!-- BEGIN AUTOGEN LADDER (tools/gen_baseline.py) -->"
END = "<!-- END AUTOGEN LADDER -->"


def _result_cell(row: dict) -> str:
    if "skipped" in row:
        return f"SKIPPED — {row['skipped']}"
    cells = []
    if "tok_per_s" in row:
        cells.append(f"**{row['tok_per_s']:.1f} tok/s**")
    for k, label in (
        ("mfu_2N", "MFU_2N"), ("hbm_util", "hbm_util"),
        ("weight_stream_gb_per_s", "weight-stream GB/s"),
        ("ttft_p50_ms", "TTFT p50 ms"), ("ttft_p95_ms", "TTFT p95 ms"),
        ("tpot_ms", "TPOT ms"), ("tok_per_s_steady", "steady tok/s"),
        ("tok_per_s_continuous", "continuous tok/s"),
        ("tok_per_s_grouped", "grouped tok/s"),
        ("tok_per_s_paged", "paged tok/s"),
        ("tok_per_s_contiguous", "contiguous tok/s"),
        ("kv_memory_ratio", "paged/contiguous KV bytes"),
        ("dense_chunk_ms", "dense ms"), ("ragged_chunk_ms", "ragged ms"),
        ("speedup", "speedup"),
        ("flash_ms", "flash ms"), ("dot_ms", "dot ms"),
        ("p50_us", "p50 µs"), ("p95_us", "p95 µs"),
        ("tok_per_s_end_to_end", "end-to-end tok/s"),
        ("tok_per_s_in_engine", "in-engine tok/s"),
        ("cluster_overhead_pct", "cluster overhead %"),
        ("rtt_1tok_p50_ms", "1-tok RTT p50 ms"),
        ("short_done_ms_monolithic", "short-req ms (monolithic)"),
        ("short_done_ms_chunked", "short-req ms (chunked)"),
        ("ttft_ms_cache_off", "TTFT ms cache-off"),
        ("ttft_ms_cache_on", "TTFT ms cache-on"),
        ("ttft_ms_shared_off", "shared-prefix TTFT ms off"),
        ("ttft_ms_shared_on", "shared-prefix TTFT ms on"),
        ("prefill_tokens_saved", "prefill tokens saved"),
        ("hit_rate", "hit rate"),
        ("recovery_ms", "recovery ms"),
        ("completed_frac", "completed frac"),
        ("engine_restarts", "engine restarts"),
        ("requests_retried", "requests retried"),
        ("replicas", "replicas"),
        ("exact", "byte-exact"),
        ("failovers", "failovers"),
        ("short_ms_colocated", "short-req ms (colocated)"),
        ("short_ms_disagg", "short-req ms (disagg)"),
        ("interference_speedup", "interference speedup"),
        ("handoff_ms_p50", "handoff p50 ms"),
        ("fallback_recovery_ms", "prefill-kill fallback ms"),
        ("goodput_tok_per_s", "goodput tok/s"),
        ("aggressor_offered_x", "aggressor offered x quota"),
        ("victim_goodput_off", "victim goodput tok/s (QoS off)"),
        ("victim_goodput_on", "victim goodput tok/s (QoS on)"),
        ("victim_goodput_gain", "victim goodput gain x"),
        ("victim_slo_off", "victim SLO attainment (off)"),
        ("victim_slo_on", "victim SLO attainment (on)"),
        ("victim_itl_p95_ms_off", "victim ITL p95 ms (off)"),
        ("victim_itl_p95_ms_on", "victim ITL p95 ms (on)"),
        ("aggressor_shed_frac", "aggressor shed frac"),
        ("scale_up_s", "scale-up s"),
        ("scale_down_s", "scale-down s"),
        ("goodput_tok_per_s_colocated", "goodput tok/s (colocated)"),
        ("goodput_tok_per_s_disagg", "goodput tok/s (disagg)"),
        ("exact_disagg", "byte-exact (disagg)"),
        ("handoffs", "handoffs"),
        ("directory_hit_rate", "directory hit rate"),
        ("pulled_pages", "pages pulled"),
        ("pull_fallbacks", "pull fallbacks"),
        ("pull_ttft_ms", "pull TTFT ms"),
        ("reprefill_ttft_ms", "re-prefill TTFT ms"),
        ("pull_ttft_speedup", "pull TTFT speedup"),
        ("offered_x", "offered load x"),
        ("shed_frac", "shed frac"),
        ("preemptions", "preemptions"),
        ("rows_bf16", "rows @bf16"),
        ("rows_int8", "rows @int8"),
        ("capacity_factor_int8", "int8 capacity factor"),
        ("swap_restore_ms", "swap restore ms"),
        ("recompute_restore_ms", "recompute restore ms"),
        ("swap_speedup", "swap speedup"),
        ("spill_hit_ttft_ms", "spill-hit TTFT ms"),
        ("cold_ttft_ms", "cold TTFT ms"),
        ("rows_per_chip_tp1", "rows/chip @tp1"),
        ("rows_per_chip_tp2", "rows/chip @tp2"),
        ("capacity_factor_tp2", "tp2 capacity factor"),
        ("tok_per_s_tp1", "tok/s @tp1"),
        ("tok_per_s_tp2", "tok/s @tp2"),
        ("per_chip_pool_kb", "per-chip pool KB"),
        ("tok_per_s_overlap_off", "tok/s overlap-off"),
        ("tok_per_s_overlap_on", "tok/s overlap-on"),
        ("dfa_compile_ms", "DFA compile ms"),
        ("tok_per_s_free", "free tok/s"),
        ("tok_per_s_constrained", "constrained tok/s"),
        ("mask_overhead_pct", "mask overhead %"),
        ("parse_valid_frac", "parse-valid frac"),
        ("device_gap_ms_off", "device-gap ms off"),
        ("device_gap_ms_on", "device-gap ms on"),
        ("gap_reduction", "gap reduction x"),
        ("dispatched_ahead_frac", "dispatched-ahead frac"),
        ("exact_spec_vs_plain", "spec byte-exact"),
        ("tok_per_s_plain", "tok/s spec-off"),
        ("tok_per_s_spec", "tok/s spec-on"),
        ("itl_p50_ms_plain", "ITL p50 ms spec-off"),
        ("itl_p50_ms_spec", "ITL p50 ms spec-on"),
        ("acceptance_frac", "acceptance frac"),
        ("spec_rounds", "spec rounds"),
        ("k_downshifts", "k downshifts"),
        ("rows_contig_spec", "rows @contiguous-spec"),
        ("rows_paged_spec", "rows @paged-spec"),
        ("capacity_factor", "capacity factor"),
        ("pool_kib", "pool KiB"),
        ("itl_p95_ms_alternate", "ITL p95 ms (alternate)"),
        ("itl_p95_ms_mixed", "ITL p95 ms (mixed)"),
        ("itl_p95_gain", "ITL p95 gain x"),
        ("ttft_first_s_alternate", "long-prompt TTFT s (alternate)"),
        ("ttft_first_s_mixed", "long-prompt TTFT s (mixed)"),
        ("ttft_ratio", "TTFT ratio (mixed/alternate)"),
        ("ttft_last_s_mixed", "last-prefill TTFT s (mixed)"),
        ("stall_rounds_alternate", "stall bites (alternate)"),
        ("stall_rounds_mixed", "stall bites (mixed)"),
        ("admit_row_keys", "admit compile keys"),
        ("admit_row_declared", "of declared"),
        ("decode_chunk_keys", "decode compile keys"),
        ("decode_chunk_declared", "of declared"),
        ("decode_chunk_overlap_keys", "overlap decode compile keys"),
        ("decode_chunk_overlap_declared", "of declared"),
        ("decode_chunk_constrained_keys", "constrained decode compile keys"),
        ("decode_chunk_constrained_declared", "of declared"),
        ("generate_tokens_keys", "generate compile keys"),
        ("generate_tokens_declared", "of declared"),
        ("trace_wall_ms", "trace wall ms"),
        ("graftlint_wall_ms", "graftlint ms"),
        ("graftcheck_wall_ms", "graftcheck ms"),
        ("graftflow_wall_ms", "graftflow ms"),
        ("graftsync_wall_ms", "graftsync ms"),
        ("graftmodel_wall_ms", "graftmodel ms"),
        ("analysis_wall_ms", "combined analysis ms"),
    ):
        if row.get(k) is not None:
            v = row[k]
            cells.append(f"{label} {v:.3g}" if isinstance(v, float) else f"{label} {v}")
    if row.get("degraded"):
        cells.append(f"DEGRADED: {row['degraded']}")
    return ", ".join(cells) or json.dumps(
        {k: v for k, v in row.items() if k not in ("config", "measured_on")}
    )[:120]


def generate(ladder_path: str) -> str:
    import bench  # repo-root bench.py — the ladder definition of record

    with open(ladder_path) as f:
        rows = {str(r.get("config")): r for r in json.load(f)["rows"]}
    lines = [
        BEGIN,
        "",
        "## Ladder of record (auto-generated from BENCH_LADDER.json)",
        "",
        "A config with no `measured on` stamp has **never produced a "
        "number** — treat every claim about it as design intent, not data.",
        "",
        "| Config | Preset | Result | Measured on |",
        "|--------|--------|--------|-------------|",
    ]
    listed = [str(e["config"]) for e in bench.LADDER] + [
        # Aux rows run_ladder appends after the decode configs.
        "serving-latency", "continuous-batching", "local-proc-batching",
        "chunked-prefill", "prefix-cache-ttft", "fault-recovery",
        "overload-goodput", "tenant-qos", "kv-tiering", "decode-overlap",
        "mixed-step", "spec-paged",
        "constrained-decode", "mesh-paged", "replica-failover",
        "fleet-goodput", "disagg-handoff", "compile-stability",
        "analysis-wall",
        "ragged-decode-8k", "ragged-decode-win-8k", "quant-matmul-bw",
        "spec-decode", "spec-decode-7b-int8", "spec-batching",
        "paged-batching", "prefill-flash-2048", "prefill-flash-8192",
        "prefill-flash-win-8192", "hop-latency",
    ]
    extras = [c for c in rows if c not in listed]
    for cfg_id in listed + extras:
        row = rows.get(cfg_id)
        entry = next(
            (e for e in bench.LADDER if str(e["config"]) == cfg_id), {}
        )
        preset = (row or {}).get("preset", entry.get("preset", "—"))
        if row is None:
            lines.append(
                f"| {cfg_id} | {preset} | NOT YET MEASURED (instrumented in "
                f"bench.py; no row in the artifact) | — |"
            )
            continue
        stamp = row.get("measured_on", "pre-r4 artifact (no stamp)")
        if "skipped" in row:
            stamp = "—"
        lines.append(f"| {cfg_id} | {preset} | {_result_cell(row)} | {stamp} |")
    lines += ["", END]
    return "\n".join(lines)


def main() -> int:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ladder = os.path.join(repo, "BENCH_LADDER.json")
    baseline = os.path.join(repo, "BASELINE.md")
    section = generate(ladder)
    with open(baseline) as f:
        text = f.read()
    if BEGIN in text and END in text:
        pattern = re.escape(BEGIN) + r".*?" + re.escape(END)
        text = re.sub(pattern, lambda _m: section, text, flags=re.DOTALL)
    else:
        text = text.rstrip() + "\n\n" + section + "\n"
    with open(baseline, "w") as f:
        f.write(text)
    print(f"BASELINE.md ladder section regenerated from {ladder}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
