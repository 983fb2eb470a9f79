"""Fresh-process runner for tests XLA:CPU cannot compile reliably in a
long-lived process.

The speculative while_loop programs (two model scans inlined into one
loop) nondeterministically SEGFAULT the XLA:CPU compiler when compiled
after ~150 other tests have run in the same process — 5/5 full-suite runs
on 2026-07-31 crashed there, on five different members of the family
(int4-draft, engine-level, lax.map-batched) and at three different stages
(backend_compile_and_load, persistent-cache serialize, deserialize) —
while every fresh-process run passes.  The whole speculative test family
is therefore marked skip-unless-DLT_RUN_ISOLATED in its home files
(module-level pytestmark) and executed here in fresh processes — full
coverage, crash domain isolated, and a real failure in those tests still
fails the suite loudly through this runner.

The list runs over WORKERS fresh processes at once (an inner ``pytest -n``,
a file a worker at a time): each compiles at most some 25 of the 90 cases,
far under the ~150 the crash needs, and the runner is no longer the suite's
longest job (304 s as one process, PR 47's run).  A worker that dies
fails the run: its test is reported failed, and the "node down" lines are
counted as the driver's command counts them.
"""

import os
import re
import subprocess
import sys

WORKERS = 6

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ISOLATED = [
    "tests/runtime/test_speculative.py",
    "tests/runtime/test_spec_batcher.py",
    # (The four longest files stand first: the inner run hands files out in
    # this order, and a long one handed out last is the run's tail.)
    # Paged speculative decoding (round 17): every composition leg
    # compiles paged spec_chunk programs — same crash class as
    # test_spec_batcher.
    "tests/runtime/test_spec_paged.py",
    # Stall-free mixed batching (round 16): every fused-step composition
    # compiles mixed_step programs per pool/bucket config — the policy
    # hook tests at the top of the file are model-free and also run in
    # the main process.
    "tests/runtime/test_mixed_step.py",
    # Every OTHER test that compiles a speculative while_loop program —
    # grep for speculative_generate_tokens when adding tests outside the
    # speculative files above.
    "tests/models/test_sliding_window.py::"
    "test_ragged_windowed_speculative_matches_generate",
    # Cluster engine compiles at the suite TAIL (same crash class, plain
    # generate_text programs — see the marker in test_tokenizer_store.py).
    "tests/runtime/test_tokenizer_store.py::"
    "test_cluster_mixed_budget_requests_via_continuous_batching",
    "tests/runtime/test_tokenizer_store.py::"
    "test_cluster_path_decodes_real_words",
    # Round-5 compile-heavy additions: the crash budget is CUMULATIVE
    # (2026-07-31 round-5 runs died at whatever module compiled last —
    # tokenizer_store once, then train_ckpt once it was isolated), so new
    # big programs must not grow the main process past the round-4 green
    # budget.  These five compile pipelined/mesh/speculative programs.
    "tests/models/test_sliding_window.py::"
    "test_mesh_windowed_decode_matches_single_device",
    "tests/models/test_sliding_window.py::"
    "test_pipelined_windowed_decode_matches_single_device",
    "tests/runtime/test_batcher_sampling.py::"
    "test_speculative_logprobs_match_plain",
    "tests/runtime/test_batcher_sampling.py::"
    "test_speculative_penalties_match_plain",
    "tests/parallel/test_mesh_batcher.py::"
    "test_mesh_batcher_penalties_match_single_device",
    # Round-5 windowed-kernel additions (flash window band + windowed
    # ragged decode): each parametrization compiles fresh programs.
    "tests/ops/test_flash.py::test_windowed_static_matches_dense",
    "tests/ops/test_flash.py::test_windowed_dynamic_matches_dense",
    "tests/ops/test_flash.py::test_windowed_grad_matches_dot",
    "tests/ops/test_decode_attn.py::test_windowed_kernel_matches_dense",
    "tests/ops/test_decode_attn.py::test_batcher_windowed_ragged_matches_solo",
    "tests/models/test_sliding_window.py::test_flash_impl_matches_windowed_dot",
    # Chunked prefill (round 5): prefill_chunk_step compiles per bucket.
    "tests/runtime/test_chunked_prefill.py",
    # Dispatch-ahead overlap (round 13): the speculative leg compiles
    # spec_chunk programs — same crash class as test_spec_batcher.
    "tests/runtime/test_overlap.py::test_speculative_exact_on_vs_off",
    # The explicit admission fetch (PR 36): its speculative leg compiles
    # paged spec_chunk programs.
    "tests/runtime/test_tracing.py::"
    "test_the_explicit_fetch_changes_no_token[speculative]",
    # Pipelined admissions (PR 44): the speculative leg of the on-vs-off
    # matrix compiles paged spec_chunk programs.
    "tests/runtime/test_admit_pipeline.py::"
    "test_the_two_orders_serve_the_same_tokens[speculative]",
    # The gap between deliveries (PR 57): the speculative schedule's leg.
    "tests/runtime/test_tracing.py::"
    "test_every_schedule_delivers_through_the_same_stamps[speculative]",
]


def test_fragile_xla_cpu_tests_in_fresh_process():
    env = {**os.environ, "DLT_RUN_ISOLATED": "1"}
    # Never let an opted-in persistent compile cache reach the fragile
    # family: executable (de)serialization of these exact programs is 2 of
    # the 5 documented crash sites (tests/conftest.py).
    env.pop("DLT_TEST_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "xdist", "-n", str(WORKERS), "--dist", "loadfile", *ISOLATED],
        env=env, capture_output=True, text=True, timeout=3300, cwd=REPO,
    )
    down = len(re.findall(r"\[gw\d+\] node down", r.stdout + r.stderr))
    assert r.returncode == 0 and not down, (
        f"isolated fragile tests failed (rc={r.returncode}, "
        f"{down} workers down):\n"
        f"{r.stdout[-3000:]}\n{r.stderr[-2000:]}"
    )
