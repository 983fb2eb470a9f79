"""graftcheck self-tests: every rule family proven to fire on a seeded
violation, the GC4 gate pinned to the declared bucket ladder, and THE
tier-1 gate — the repo's real contracts must hold modulo the (empty)
checked-in baseline."""

from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tools.graftcheck import (  # noqa: E402
    read_baseline, run_all, split_new, write_baseline,
)
from tools.graftcheck import (  # noqa: E402
    donation, dtypes, recompile, shapes, sharding,
)
from tools.graftcheck.contracts import (  # noqa: E402
    DonationContract, HotFnContract, OpCase, OpContract, RecompileScenario,
    SpecAudit, CollectiveAudit, fake_mesh, sds,
)
from tools.graftcheck.core import jaxpr_hash  # noqa: E402


def _rules(findings):
    return sorted(f.rule for f in findings)


# -- GC1 shape/dtype contracts --------------------------------------------

def test_gc1_fires_on_shape_and_dtype_drift():
    contract = OpContract(
        "seeded.op", "pkg/op.py", "seeded", lambda: [
            # Declared f32 [2, 4] but the op returns bf16 [2, 4]: dtype drift.
            OpCase("dtype", lambda x: x.astype(jnp.bfloat16),
                   (sds((2, 4), jnp.float32),), (((2, 4), "float32"),)),
            # Declared [2, 4] but the op transposes: shape drift.
            OpCase("shape", lambda x: x.T,
                   (sds((2, 4), jnp.float32),), (((2, 4), "float32"),)),
            # Contract holds: no finding from this case.
            OpCase("ok", lambda x: x + 1,
                   (sds((2, 4), jnp.float32),), (((2, 4), "float32"),)),
        ])
    findings = shapes.check([contract])
    assert _rules(findings) == ["GC101", "GC101"]
    assert all("seeded.op" in f.message for f in findings)


def test_gc1_trace_failure_is_a_finding():
    def boom(x):
        raise ValueError("shapes the op claims to support")

    contract = OpContract(
        "seeded.broken", "pkg/op.py", "seeded", lambda: [
            OpCase("case", boom, (sds((2,), jnp.float32),),
                   (((2,), "float32"),))])
    findings = shapes.check([contract])
    assert _rules(findings) == ["GC102"]


# -- GC2 sharding-spec audit ----------------------------------------------

def _audit(build):
    return SpecAudit("seeded@mesh", "pkg/specs.py", build)


def test_gc2_structure_drift():
    from jax.sharding import PartitionSpec as P

    findings = sharding.check_specs([_audit(lambda: (
        {"a": sds((4, 4), jnp.float32), "b": sds((4,), jnp.float32)},
        {"a": P(None, None)},          # 'b' missing: tree drift
        fake_mesh(model=2),
    ))])
    assert _rules(findings) == ["GC201"]
    assert "'b'" in findings[0].message


def test_gc2_unknown_axis_rank_and_divisibility():
    from jax.sharding import PartitionSpec as P

    findings = sharding.check_specs([_audit(lambda: (
        {"w": sds((5, 4), jnp.float32), "v": sds((4,), jnp.float32),
         "u": sds((8, 4), jnp.float32)},
        {"w": P("model", None),        # 5 % 2 != 0 -> GC204
         "v": P(None, None, "model"),  # rank 3 > rank 1 -> GC203
         "u": P("bogus", None)},       # no such axis -> GC202
        fake_mesh(model=2),
    ))])
    assert _rules(findings) == ["GC202", "GC203", "GC204"]


def test_gc2_catches_the_unguarded_pipe_shard_regression():
    """The in-tree bug this rule forced fixed: param_specs used to shard
    the stacked layer axis over 'pipe' without a divisibility check (3
    neox-tiny layers over pipe=2).  Seed the pre-fix behavior and prove
    the audit fails it; the repo-clean gate proves the fix holds."""
    from jax.sharding import PartitionSpec as P

    findings = sharding.check_specs([_audit(lambda: (
        {"blocks": {"wq": sds((3, 64, 4, 16), jnp.float32)}},
        {"blocks": {"wq": P("pipe", None, None, None)}},  # unguarded
        fake_mesh(pipe=2),
    ))])
    assert _rules(findings) == ["GC204"]
    assert "'pipe'" in findings[0].message


def test_gc2_collective_axis_must_exist_on_mesh():
    from jax.sharding import PartitionSpec as P

    def build():
        trace_mesh = fake_mesh(seq=2)

        def fn(x):
            return jax.shard_map(
                lambda x: jax.lax.psum(x, "seq"),
                mesh=trace_mesh, in_specs=P("seq"), out_specs=P(),
                axis_names={"seq"},
            )(x)

        # The audit DECLARES the op runs on a mesh without a 'seq' axis at
        # all: the traced psum's axis is missing there -> GC205.
        from jax.sharding import AbstractMesh

        return fn, (sds((4,), jnp.float32),), AbstractMesh((2,), ("model",))

    findings = sharding.check_collectives(
        [CollectiveAudit("seeded.psum", "pkg/op.py", "seeded", build)])
    assert "GC205" in _rules(findings)
    assert any("'seq'" in f.message for f in findings)


# -- GC3 dtype promotion --------------------------------------------------

def test_gc3_unallowlisted_bf16_upcast_fires():
    def sneaky_upcast(x):  # np.float32 scalar promotes bf16 -> f32
        return (x * np.float32(2.0)).sum()

    contract = HotFnContract(
        "seeded.hot", "pkg/hot.py", "seeded",
        lambda: (sneaky_upcast, (sds((8,), jnp.bfloat16),)),
        frozenset())
    findings = dtypes.check([contract])
    assert _rules(findings) == ["GC302"]
    assert "sneaky_upcast" in findings[0].message
    # The same trace passes once the site is allowlisted.
    blessed = HotFnContract(
        "seeded.hot", "pkg/hot.py", "seeded",
        lambda: (sneaky_upcast, (sds((8,), jnp.bfloat16),)),
        frozenset({"sneaky_upcast"}))
    assert dtypes.check([blessed]) == []


def test_gc3_float64_fires_under_x64():
    def widens(x):
        return x.astype("float64").sum()

    contract = HotFnContract(
        "seeded.x64", "pkg/hot.py", "seeded",
        lambda: (widens, (sds((8,), jnp.float32),)), frozenset())
    with jax.enable_x64(True):
        findings = dtypes.check([contract])
    assert "GC301" in _rules(findings)
    assert any("widens" in f.message for f in findings)


# -- GC4 recompilation ----------------------------------------------------

def _identity_trace(width: int) -> str:
    return jaxpr_hash(lambda x: x + 1, sds((width,), jnp.float32))


def test_gc4_unbucketed_widths_fire_both_rules():
    """The classic bug seeded verbatim: padding to the RAW request length.
    Off-ladder widths fire GC402 and the (per-width-compiling) trace
    blows the declared key budget -> GC401."""
    sc = RecompileScenario(
        name="seeded.raw-pad", path="pkg/engine.py", doc="seeded",
        ladder=(1, 2, 3, 5, 7, 9, 11),
        width_of=lambda n: n,                 # no bucketing
        allowed_widths=(1, 2, 3, 5, 7, 9, 11),  # ladder "allows" raw widths
        max_keys=2,                           # but declares 2 programs
        trace=_identity_trace,
    )
    findings = recompile.check([sc])
    assert _rules(findings) == ["GC401"]
    sc_off = RecompileScenario(
        name="seeded.off-ladder", path="pkg/engine.py", doc="seeded",
        ladder=(1, 9), width_of=lambda n: n, allowed_widths=(8, 16),
        max_keys=2, trace=_identity_trace,
    )
    findings = recompile.check([sc_off])
    assert set(_rules(findings)) == {"GC402"}


def test_gc4_bucketed_widths_pass():
    from distributed_llms_tpu.runtime import shapes as shapes_lib

    sc = RecompileScenario(
        name="seeded.bucketed", path="pkg/engine.py", doc="seeded",
        ladder=tuple(range(1, 65)),
        width_of=lambda n: shapes_lib.bucket_length(n),
        allowed_widths=tuple(shapes_lib.bucket_ladder(64)),
        max_keys=shapes_lib.bucket_count(64),
        trace=_identity_trace,
    )
    assert recompile.check([sc]) == []


def test_bucket_ladder_is_closed_under_the_policy():
    from distributed_llms_tpu.runtime import shapes as shapes_lib

    cap = 128
    ladder = set(shapes_lib.bucket_ladder(cap))
    for n in range(1, cap + 1):
        assert min(shapes_lib.bucket_length(n), cap) in ladder
        assert shapes_lib.generate_pad_len(n, 8, cap) in (
            ladder | {min(shapes_lib.bucket_length(n), cap - 8),
                      max(cap - 8, n)}
        )
    assert len(ladder) == shapes_lib.bucket_count(cap)


def test_engine_generate_pads_up_the_bucket_ladder():
    """The in-tree GC4 bug this gate forced fixed: generate_text used to
    pad T to the batch's raw max prompt length (one compile per novel
    length).  The engine must route through shapes.generate_pad_len."""
    from distributed_llms_tpu.core.config import RuntimeConfig
    from distributed_llms_tpu.runtime.engine import InferenceEngine

    eng = InferenceEngine.from_preset(
        "llama-tiny", vocab_size=512,
        rt=RuntimeConfig(max_decode_steps=8, max_seq_len=128))
    assert eng._bucket_prompt(jnp.zeros((2, 13), jnp.int32), 8).shape[1] == 16
    assert eng._bucket_prompt(jnp.zeros((2, 97), jnp.int32), 8).shape[1] == 120
    # An over-budget prompt keeps its raw width so the sequence-budget
    # check raises exactly as it did before bucketing.
    assert eng._bucket_prompt(jnp.zeros((1, 125), jnp.int32), 8).shape[1] == 125


# -- GC5 donation ---------------------------------------------------------

def test_gc5_missing_donation_fires():
    import functools

    @functools.partial(jax.jit)  # donate_argnames FORGOTTEN
    def step(params, cache, x):
        return x + 1, jax.tree.map(lambda c: c + 1, cache)

    big = sds((1024, 64), jnp.float32)  # 256 KiB leaves

    contract = DonationContract(
        "seeded.step", "pkg/step.py", "seeded",
        lambda: (step, [
            ("params", {"w": sds((8, 8), jnp.float32)}),
            ("cache", {"k": big, "v": big}),
            ("x", sds((4,), jnp.float32)),
        ], {}),
        must_donate=("cache",), may_keep=("params",), static_args=())
    findings = donation.check([contract])
    assert _rules(findings) == ["GC501"]
    assert "cache" in findings[0].message


def test_gc5_large_undeclared_buffer_fires():
    import functools

    @functools.partial(jax.jit, donate_argnames=("cache",))
    def step(params, cache, stash, x):
        return x + stash.sum(), jax.tree.map(lambda c: c + 1, cache)

    big = sds((1024, 64), jnp.float32)
    contract = DonationContract(
        "seeded.step", "pkg/step.py", "seeded",
        lambda: (step, [
            ("params", {"w": sds((8, 8), jnp.float32)}),
            ("cache", {"k": big, "v": big}),
            ("stash", big),                  # large, kept, undeclared
            ("x", sds((4,), jnp.float32)),
        ], {}),
        must_donate=("cache",), may_keep=("params",), static_args=())
    findings = donation.check([contract])
    assert _rules(findings) == ["GC502"]
    assert "stash" in findings[0].message


# -- THE tier-1 gate ------------------------------------------------------

def test_repo_is_clean():
    """Zero non-baselined semantic findings over the real registries: every
    op shape/dtype contract, every preset x mesh spec audit, the dtype
    allowlist, the compile-key budgets, and the donation flags."""
    findings = run_all(root=ROOT)
    new, _accepted = split_new(findings, read_baseline(ROOT))
    assert not new, "new graftcheck findings:\n" + "\n".join(
        f.render() for f in new)


def test_checked_in_baseline_is_empty():
    assert read_baseline(ROOT) == {}, (
        "graftcheck_baseline.txt must stay empty — fix contract violations "
        "instead of baselining them")


def test_gc4_gate_pins_decode_compile_keys():
    """Acceptance pin: the decode-step scenario's measured compile keys
    equal its declared bucket count exactly (1), and the admission ladder
    stays within shapes.bucket_count."""
    from tools.graftcheck.contracts import recompile_scenarios

    by_name = {s.name: s for s in recompile_scenarios()}
    decode = by_name["batcher.decode_chunk"]
    assert len(recompile.measure_keys(decode)) == decode.max_keys == 1
    admit = by_name["batcher.admit_row"]
    measured = recompile.measure_keys(admit)
    assert 1 < len(measured) <= admit.max_keys
    assert set(measured.values()) <= set(admit.allowed_widths)


# -- baseline + CLI -------------------------------------------------------

def test_baseline_roundtrip(tmp_path):
    from tools.graftlint.core import Finding

    f1 = Finding("GC101", "pkg/op.py", 0, "seeded contract violation")
    write_baseline(tmp_path, [f1, f1])
    baseline = read_baseline(tmp_path)
    assert sum(baseline.values()) == 2  # [x2] multiset round-trip
    new, accepted = split_new([f1, f1, f1], baseline)
    assert len(accepted) == 2 and len(new) == 1


def test_cli_docs_drift_and_write(tmp_path):
    (tmp_path / "README.md").write_text(
        "# x\n<!-- graftcheck:contracts:begin -->\nstale\n"
        "<!-- graftcheck:contracts:end -->\n", encoding="utf-8")
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftcheck", "--root", str(tmp_path),
         "--only", "GCD"],
        capture_output=True, text=True, cwd=ROOT)
    assert r.returncode == 1
    assert "GCD01" in r.stdout
    subprocess.run(
        [sys.executable, "-m", "tools.graftcheck", "--root", str(tmp_path),
         "--write-docs"],
        capture_output=True, text=True, cwd=ROOT, check=True)
    r2 = subprocess.run(
        [sys.executable, "-m", "tools.graftcheck", "--root", str(tmp_path),
         "--only", "GCD"],
        capture_output=True, text=True, cwd=ROOT)
    assert r2.returncode == 0, r2.stdout + r2.stderr


def test_front_door_escalates_stale_baseline_entries(tmp_path, capsys):
    """python -m tools.check: a baseline entry whose finding no longer
    occurs (fixed debt) is an ERROR at the front door, not a warning —
    the prune must land in the same change."""
    from tools import check as front_door

    (tmp_path / "graftlint_baseline.txt").write_text(
        "ghost.py: GL501 wall-clock sleep that was fixed long ago\n",
        encoding="utf-8")
    rc = front_door.main(["--root", str(tmp_path)])
    err = capsys.readouterr().err
    assert rc == 1
    assert "STALE graftlint baseline entry" in err


@pytest.mark.slow
def test_cli_full_run_is_clean():
    """End-to-end CLI over the real repo (subprocess, fresh jax)."""
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftcheck"],
        capture_output=True, text=True, cwd=ROOT, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
