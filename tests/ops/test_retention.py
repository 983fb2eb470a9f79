"""The two retention kernels' dispatch records and what the TPU compiler
makes of the programs that hold them (ops/retention.py).

On the CPU the operators take their ``jax.numpy`` bodies and say so
(``ops.dispatch.retention_*.fallback``); told ``kernel`` they lower the
Pallas kernels and record ``.kernel`` at every shape the benchmark's cell
and its probes reach, the 32-byte probe's bucket of 64 included (padded to
one chunk of 128 inside the wrapper), and never a fallback: the harness
reads any ``*_fallback`` above zero as a fault.  Compiled ahead of time for
one v5e on the compile-only TPU client (tools/aot_decode.py; no chip) at
Brumby's widths with 2 layers: ``decode_chunk`` keeps the slots' states
where they lie (the kernel's aliased update is the only instruction that
produces a state-shaped array) and feeds the kernel small operands alone
(``ret_fed``), and an admission writes one row's state into its slot.
Skips where the installation has no such client.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from distributed_llms_tpu.core.observability import METRICS
from distributed_llms_tpu.ops import retention

H, KVH = 40, 8


def _took(before, name):
    return METRICS.snapshot()["counters"].get(name, 0) - before.get(name, 0)


def _lower(monkeypatch, mode, t):
    """Trace both operators at Brumby's widths (the record is written
    while tracing; the CPU cannot lower a compiled kernel)."""
    monkeypatch.setenv("DLT_RAGGED_DECODE", mode)
    sds = jax.ShapeDtypeStruct
    jax.eval_shape(lambda q, k, v, lg, n: retention.retention_prefill(
        q, k, v, lg, n, chunk=256),
        sds((t, H, 128), jnp.bfloat16), sds((t, KVH, 128), jnp.bfloat16),
        sds((t, KVH, 128), jnp.bfloat16), sds((t, KVH), jnp.float32),
        sds((), jnp.int32))
    jax.eval_shape(lambda q, k, v, lg, s, z, live: retention.retention_decode(
        q, k, v, lg, s, z, 1, live),
        sds((16, H, 128), jnp.bfloat16), sds((16, KVH, 128), jnp.bfloat16),
        sds((16, KVH, 128), jnp.bfloat16), sds((16, KVH), jnp.float32),
        sds((2, 16, KVH, 65, 128, 128), jnp.float32),
        sds((2, 16, KVH, 128, 128), jnp.float32), sds((16,), jnp.bool_))


@pytest.mark.parametrize("t", [64, 256, 2048, 8192, 16384])
def test_told_kernel_both_operators_record_the_kernel_at_the_cells_shapes(
        monkeypatch, t):
    """The probes' buckets (64 to 8,192) and the traffic's (4,096 to
    16,384): each lowers its Pallas call and records ``.kernel``."""
    before = METRICS.snapshot()["counters"]
    _lower(monkeypatch, "kernel", t)
    for op in ("retention_prefill", "retention_decode"):
        assert _took(before, f"ops.dispatch.{op}.kernel") == 1
        assert _took(before, f"ops.dispatch.{op}.fallback") == 0
        assert _took(before, f"ops.dispatch.{op}.interpret") == 0


def test_on_the_cpu_the_dense_bodies_say_so(monkeypatch):
    monkeypatch.delenv("DLT_RAGGED_DECODE", raising=False)
    before = METRICS.snapshot()["counters"]
    _lower(monkeypatch, "auto", 64)
    for op in ("retention_prefill", "retention_decode"):
        assert _took(before, f"ops.dispatch.{op}.fallback") == 1
        assert _took(before, f"ops.dispatch.{op}.kernel") == 0


def test_the_state_is_8320_rows_where_the_symmetric_count_is_8256():
    s, z = retention.state_shapes(KVH)
    assert s == (8, 65, 128, 128) and z == (8, 128, 128)
    assert 65 * 128 == 8320 and 128 * 129 // 2 == 8256
    assert retention.state_bytes(KVH) == 34_603_008
    # every unordered pair once, weighed as the square weighs it
    x = jnp.arange(1.0, 129.0)
    total = jnp.sum(retention.phi_k(x))
    assert float(total) == pytest.approx(float(jnp.sum(x) ** 2), rel=1e-6)


@pytest.fixture(scope="module")
def compiled():
    from distributed_llms_tpu.models.presets import get_preset
    from tools import aot_decode

    try:
        aot_decode.v5e_devices()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cfg = dataclasses.replace(get_preset("brumby-pp4"), num_layers=2,
                              layer_types=("ret",) * 2, vocab_size=4096)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DLT_QUANT_MATMUL", "kernel")
        mp.setenv("DLT_RAGGED_DECODE", "kernel")
        return {p: aot_decode.analyse(
            p, cfg, slots=16, max_len=4096, pages=0, prompt_len=2048)
            for p in ("decode_chunk", "admit_row")}


def test_decode_chunk_updates_the_states_where_they_lie(compiled):
    r = compiled["decode_chunk"]
    state = 2 * 16 * retention.state_bytes(KVH)
    assert [e[0] for e in r["state_shaped"]] == ["custom-call"]
    assert "retention_decode" in r["state_shaped"][0][1]
    assert r["alias_gb"] * 1e9 >= state  # donated and aliased, whole
    # no second copy, not of one layer's slots (half the two layers')
    assert r["temp_gb"] * 1e9 < 0.5 * state / 2
    assert r["weight_shaped"] == []


def test_xla_makes_nothing_large_for_the_decode_kernel(compiled):
    """The kernel is given q, k, v and the gate as rows of one small block
    and rotates phi(q) and phi(k) out of them itself: beside the stack its
    operands are under 1 MB together, and the optimised program holds no
    ``copy`` and no ``gather`` (inside fusions too) as large as a layer's
    products of the keys, 16 x 8 x 65 x 128 (the parent built 34.5 MB a
    layer a step through one gather and two transposing copies)."""
    from tools import aot_decode

    fed = compiled["decode_chunk"]["ret_fed"]
    assert fed and {e[0] for e in fed}.isdisjoint({"copy", "gather"})
    assert sum(aot_decode.type_bytes(e[2]) for e in fed) < 1 << 20
    hlo = compiled["decode_chunk"]["hlo"]
    # ... and nothing shaped like phi(q) or phi(k), however it is made
    assert not aot_decode.shaped_like(hlo, [
        "[16,8,5,65,128]", "[16,8,65,128]", "[16,8,5,5,13,128]",
        "[16,8,5,13,128]"])


def test_three_turns_of_heads_fit_the_decode_calls_vmem(compiled):
    """The decode kernel leaves the stack in HBM and copies four of a row's
    heads a turn through three buffers (PR 54): the TPU's compiler took the
    call, so they fit, and what they come to beside the scratch lies inside
    the scoped memory the call asks for, ``vmem_limit_bytes``, as the
    compiled program states it; the call still writes the stack it is
    given (the two tests above hold the rest of that program)."""
    import re

    r = compiled["decode_chunk"]
    (call,) = [line for line in r["hlo"].splitlines()
               if "custom-call(" in line and "%retention_decode" in line]
    assert "output_to_operand_aliasing={{0}: (2, {})}" in call
    scoped = [int(n) for n in re.findall(
        r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"\d+",'
        r'"size":"(\d+)"', call)]
    assert scoped == [retention._VMEM_LIMIT]
    turn = retention._turn(KVH)
    assert turn == 4
    buffers = 3 * turn * retention.DIAGS * 128 * 128 * 4
    scratch = (H // KVH + 1) * 128 * 128 * 4 + retention._RUN * 8 * 128 * 4
    small = 2 * 2 * 16 * KVH * 8 * 128 * 4  # x and the numerators, whole
    assert 51e6 < buffers < buffers + scratch + small < scoped[0]


def test_an_admission_writes_one_rows_state_into_its_slot(compiled):
    r = compiled["admit_row"]
    stack = "f32[2,16,8,65,128,128]"
    whole = [e for e in r["state_shaped"] if stack in e[2]]
    assert whole and all("dynamic-update-slice" in e[0] for e in whole)
    assert any("retention_prefill" in e[1] for e in r["state_shaped"])
    assert r["alias_gb"] * 1e9 >= 2 * 16 * retention.state_bytes(KVH)
