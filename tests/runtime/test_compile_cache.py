"""Where the persistent XLA compilation cache goes (cli.init_backend):
JAX_COMPILATION_CACHE_DIR places it and the program then sets no directory
in code; unset, a TPU run uses one fixed git-ignored path inside the
checkout and a CPU run keeps none.  Each case runs in a fresh process: the
cache directory is process-wide JAX configuration, and the suite's own
process must stay without one (tests/conftest.py)."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CHILD = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
from distributed_llms_tpu import cli
from distributed_llms_tpu.core import profiling

set_in_code = []
real_update = jax.config.update
def spy(name, value):
    if name == "jax_compilation_cache_dir":
        set_in_code.append(value)
    real_update(name, value)
jax.config.update = spy
if {as_tpu!r}:
    real_report = profiling.device_report
    profiling.device_report = lambda *a: {{**real_report(*a), "platform": "tpu"}}
cli.init_backend()
if {run_jit!r}:
    jax.jit(lambda x: (x @ x.T).sum())(jax.numpy.ones((64, 64))).block_until_ready()
print("RESULT " + json.dumps({{
    "set_in_code": set_in_code,
    "configured": jax.config.jax_compilation_cache_dir,
}}))
"""


def _child(env_extra: dict, as_tpu: bool = False, run_jit: bool = False) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env.update(env_extra)
    r = subprocess.run(
        [sys.executable, "-c",
         CHILD.format(repo=REPO, as_tpu=as_tpu, run_jit=run_jit)],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    return json.loads(r.stdout.split("RESULT ")[1])


def test_cpu_run_keeps_no_cache():
    out = _child({})
    assert out["set_in_code"] == [] and out["configured"] is None


def test_tpu_run_uses_one_fixed_ignored_path_in_the_checkout():
    from distributed_llms_tpu import cli

    out = _child({}, as_tpu=True)
    assert out["set_in_code"] == [cli.COMPILE_CACHE_DIR] == [out["configured"]]
    assert cli.COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_environment_variable_places_the_cache(tmp_path):
    """With the variable set the program sets nothing, on any platform, and
    a restarted process reads back what the first one compiled."""
    cache = str(tmp_path / "cc")
    env = {
        "JAX_COMPILATION_CACHE_DIR": cache,
        # Cache even this test's tiny program.
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1",
    }
    cold = _child(env, as_tpu=True, run_jit=True)
    assert cold["set_in_code"] == [] and cold["configured"] == cache
    written = sorted(os.listdir(cache))
    assert written
    warm = _child(env, run_jit=True)
    assert warm["set_in_code"] == []
    assert sorted(os.listdir(cache)) == written  # nothing compiled anew
