"""chip_smoke.py (the proof that the system starts on the chip) and the
rule it enforces elsewhere: no path turns a missing accelerator into a CPU
run that exits 0.  The suite has no chip, so it drives the script's
rehearsal mode and the refusals."""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _smoke(*args, cwd=REPO, script=SMOKE):
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, capture_output=True,
        text=True, timeout=600,
    )


def test_rehearsal_passes_on_the_cpu_and_cannot_pass_for_a_chip_run():
    r = _smoke("--rehearsal")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert "REHEARSAL" in lines[0]
    assert '"rehearsal": "passed"' in lines[-1] and '"platform": "cpu"' in lines[-1]
    assert '"ok"' not in r.stdout


def test_chip_mode_without_a_chip_fails_and_prints_no_result():
    r = _smoke()
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and "FAILED" in r.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    """The script proves the repository it sits in: with nothing of the
    repository beside it there is no server to start."""
    script = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _smoke("--rehearsal", cwd=tmp_path, script=str(script))
    assert r.returncode != 0 and '"rehearsal": "passed"' not in r.stdout


def test_local_proc_refuses_unless_pinned_to_the_cpu(monkeypatch):
    """dlt-coordinator --local-proc would start worker processes that each
    initialise JAX; on a chip host that hangs, so it refuses at once."""
    from distributed_llms_tpu.cli import coordinator_main

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit, match="one process at a time"):
        coordinator_main.main(["--local-proc", "2"])
