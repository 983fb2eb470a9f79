"""What the TPU compiler makes of paged ``decode_chunk``: the pool and the
quantized weights stay where they lie.

Compiled ahead of time for one v5e on the compile-only TPU client
(tools/aot_decode.py; no chip), at qwen2-7b's widths with 2 layers and the
benchmark's 512 pages.  Before PR 26 the layer scan took the pool as
scanned inputs and gave it back as stacked outputs, and the compiled step
sliced a layer out for the Pallas call, copied it, wrote it into a fresh
pool-sized stack and copied that stack into the carry: four passes over
the pool a decode step.  This is the guard against the copy coming back
with a JAX upgrade; it skips where the installation has no such client.
Before PR 29 the same program sliced every layer's int8 weights out of
their stacks for ``quant_matmul`` (a copy each) and re-laid out every
layer's scales, a third of a decode step: ``weight_shaped`` holds both
families' programs to none of it.
"""

import dataclasses

import re

import pytest

LAYERS, PAGES, BLK, KVH, HD = 2, 512, 64, 4, 128
POOL_BYTES = 2 * LAYERS * PAGES * BLK * KVH * HD * 2  # k + v, bf16


@pytest.fixture(scope="module")
def compiled():
    """The report of tools.aot_decode on ``decode_chunk``, compiled in
    this process (the TPU's library is one process's at a time) and only
    once a test of this file runs."""
    from distributed_llms_tpu.models.presets import get_preset
    from tools import aot_decode

    try:
        aot_decode.v5e_devices()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cfg = dataclasses.replace(get_preset("qwen2-7b"), num_layers=LAYERS)
    with pytest.MonkeyPatch.context() as mp:
        # The default backend is the CPU: "auto" would trace the dense
        # fallbacks, and the chip runs the kernels.
        mp.setenv("DLT_QUANT_MATMUL", "kernel")
        mp.setenv("DLT_RAGGED_DECODE", "kernel")
        return aot_decode.analyse(
            "decode_chunk", cfg, slots=16, max_len=4096, pages=PAGES,
            page_size=BLK,
        )


def test_only_in_place_scatters_produce_a_pool_shaped_array(compiled):
    """No instruction but the step's two scatters (K and V, each with the
    fusion around it) produces a [512,64,4,128] or [2,512,64,4,128] array:
    no slice of a layer, no copy, no ``AllocateBuffer`` of the stack's
    shape (a second pool), no dynamic-update-slice into a fresh stack."""
    found = compiled["pool_shaped"]
    assert {e[0] for e in found} == {"scatter", "fusion:scatter"}, found
    assert all(f"[{LAYERS},{PAGES},{BLK},{KVH},{HD}]" in e[2] for e in found)
    assert len(found) == 4, found


def test_temporaries_hold_no_second_pool(compiled):
    """Half the pool plus what the step needs beside it (0.106 GB of
    temporaries in all at these shapes; with the pool sliced per layer,
    0.388 GB: AOT compiles for the v5e, PR 26)."""
    assert compiled["temp_gb"] * 1e9 < POOL_BYTES / 2 + 120e6
    # The donated pool is written in place: the output aliases it whole.
    assert compiled["alias_gb"] * 1e9 >= POOL_BYTES


@pytest.fixture(scope="module")
def compiled_full_depth():
    """``decode_chunk`` of qwen2-7b at its 28 layers: the layout the device
    gives a stack depends on its depth (two layers of scales are stored
    layer-minor and turned once a program), and a scan compiles as fast
    at any depth."""
    from distributed_llms_tpu.models.presets import get_preset
    from tools import aot_decode

    try:
        aot_decode.v5e_devices()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DLT_QUANT_MATMUL", "kernel")
        mp.setenv("DLT_RAGGED_DECODE", "kernel")
        return aot_decode.analyse(
            "decode_chunk", get_preset("qwen2-7b"), slots=16, max_len=4096,
            pages=PAGES, page_size=BLK,
        )


def test_no_layer_of_a_weight_or_of_its_scales_is_copied(compiled_full_depth):
    """No instruction, inside the loops or before them, produces an int8
    array shaped like one layer of wq/wk/wv/wo or of the FFN's matrices
    ([3584,18944], [18944,3584], [3584,3584], [3584,512]), a float32 one
    shaped like a layer's scales in either order ([148,3584] and
    [3584,148], ...), or a whole stack of either: the kernel takes its
    tiles out of the stacked leaves where they lie."""
    assert compiled_full_depth["weight_shaped"] == []
    assert compiled_full_depth["weight_shaped_once"] == []
    # ...so the step needs next to nothing beside its arguments (0.6 MB;
    # 0.557 GB with a layer's weights and scales copied out, PR 28).
    assert compiled_full_depth["temp_gb"] < 0.05
    assert 10.7 < compiled_full_depth["argument_gb"] < 10.9


@pytest.fixture(scope="module")
def compiled_hybrid():
    """``decode_chunk`` of lfm2-8b-a1b at full depth and the cell's shapes
    (three scanned runs: some 10 s to lower and compile)."""
    from distributed_llms_tpu.models.presets import get_preset
    from tools import aot_decode

    try:
        aot_decode.v5e_devices()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DLT_QUANT_MATMUL", "kernel")
        mp.setenv("DLT_RAGGED_DECODE", "kernel")
        mp.setenv("DLT_MOE_EXPERTS", "kernel")
        return aot_decode.analyse(
            "decode_chunk", get_preset("lfm2-8b-a1b"), slots=16,
            max_len=4096, pages=PAGES, page_size=BLK,
        )


def test_hybrid_pool_of_six_layers_is_written_in_place(compiled_hybrid):
    """PR 26's property for a pool that counts the 6 attention layers and
    keeps heads of 64 two to a row: nothing but in-place scatters produces
    a [6,512,64,4,128] array or a layer of it."""
    found = compiled_hybrid["pool_shaped"]
    assert found and {e[0] for e in found} == {"scatter", "fusion:scatter"}
    assert all("[6,512,64,4,128]" in e[2] for e in found), found
    pool_bytes = 2 * 6 * PAGES * BLK * 8 * 64 * 2
    assert compiled_hybrid["alias_gb"] * 1e9 >= pool_bytes
    assert compiled_hybrid["temp_gb"] < 0.15


def test_no_expert_stack_is_copied_or_dequantized(compiled_hybrid):
    """No instruction produces an array shaped like the expert stacks
    ([22,32,2048,3584], [22,32,1792,2048]), like one layer's, or like one
    expert's [2048,3584], at any dtype: the stacks go into the kernel
    whole and are read there a tile at a time."""
    assert compiled_hybrid["expert_shaped"] == []
    # Weights (8.73 GB) and the pool (0.40 GB) are all the program is given.
    assert 9.0 < compiled_hybrid["argument_gb"] < 9.3


def test_no_layer_of_the_other_weights_is_copied_either(compiled_hybrid):
    """in_proj, out_proj, wq..wo and the dense FFN, stacked by kind of
    layer, reach ``quant_matmul`` as stacks read at ``at[kind]``: no
    instruction is shaped like one layer of any of them or of its scales
    (0.93 GB a step were sliced out before PR 29)."""
    assert compiled_hybrid["weight_shaped"] == []


@pytest.fixture(scope="module")
def compiled_latent():
    """``decode_chunk`` of ax-k1-ep16 at its 13 layers and the cell's
    shapes (64 slots, 2,176 latent pages; two runs, one scanned: some 10 s
    to lower and compile)."""
    from distributed_llms_tpu.models.presets import get_preset
    from tools import aot_decode

    try:
        aot_decode.v5e_devices()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DLT_QUANT_MATMUL", "kernel")
        mp.setenv("DLT_RAGGED_DECODE", "kernel")
        mp.setenv("DLT_MOE_EXPERTS", "kernel")
        return aot_decode.analyse(
            "decode_chunk", get_preset("ax-k1-ep16"), slots=64,
            max_len=4096, pages=2176, page_size=BLK,
        )


def test_latent_pool_is_one_leaf_written_in_place(compiled_latent):
    """The latent pool is ONE stack [13,2176,64,640] (no value pages, no
    head axis): nothing but the step's in-place scatter of the new rows
    produces an array of that shape or a layer of it, and the donated pool
    is the output's alias."""
    found = compiled_latent["pool_shaped"]
    assert found and {e[0] for e in found} == {"scatter", "fusion:scatter"}
    assert all("[13,2176,64,640]" in e[2] for e in found), found
    pool_bytes = 13 * 2176 * BLK * 640 * 2
    assert compiled_latent["alias_gb"] * 1e9 >= pool_bytes
    # 0.43 GB of temporaries (the gathered rows of 704 x 7168 a layer, the
    # absorbed queries), a fifth of the pool: no second pool among them.
    assert compiled_latent["temp_gb"] * 1e9 < pool_bytes / 4


def test_a_chips_share_of_the_experts_is_read_where_it_lies(compiled_latent):
    """No instruction is shaped like the held experts' stacks
    ([12,12,7168,4096], [12,12,2048,7168]), one layer's or one expert's;
    weights (9.66 GB) and the pool (2.32 GB) are all the program is given,
    with 3.3 GB of the chip to spare."""
    assert compiled_latent["expert_shaped"] == []
    assert 11.9 < compiled_latent["argument_gb"] < 12.05
    assert compiled_latent["argument_gb"] + compiled_latent["temp_gb"] < 12.6


def test_latent_attentions_and_the_shared_experts_weights_stay_stacked(
        compiled_latent):
    """W_qa, W_qb, W_o, the dense layer and the shared expert reach
    ``quant_matmul`` as stacks read at an index: no instruction inside the
    loops is shaped like one layer of any of them or of its scales."""
    assert compiled_latent["weight_shaped"] == []


@pytest.fixture(scope="module")
def compiled_windowed():
    """``decode_chunk`` and ``admit_row_paged`` at the 8,192 bucket of
    k-exaone-ep8 at its 12 layers and the cell's shapes (64 slots, 3,712
    pages of the 3 full layers, the 9 windowed layers' rings beside them;
    some 35 s to lower and compile the two)."""
    from distributed_llms_tpu.models.presets import get_preset
    from tools import aot_decode

    try:
        aot_decode.v5e_devices()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DLT_QUANT_MATMUL", "kernel")
        mp.setenv("DLT_RAGGED_DECODE", "kernel")
        mp.setenv("DLT_MOE_EXPERTS", "kernel")
        return {
            program: aot_decode.analyse(
                program, get_preset("k-exaone-ep8"), slots=64, max_len=8192,
                pages=3712, page_size=BLK, prompt_len=8192)
            for program in ("decode_chunk", "admit_row_paged")}


def test_pool_of_the_full_layers_and_the_rings_are_written_in_place(
        compiled_windowed):
    """The pool is the 3 full layers' [3,3712,64,8,128] and the rings
    [9,64,128,8,128]: nothing but the step's in-place scatters produces an
    array of either shape or a layer of it, and both are the output's
    alias (2 x 2.919 GB + 2 x 0.151 GB)."""
    decode = compiled_windowed["decode_chunk"]
    found = decode["pool_shaped"]
    assert found and {e[0] for e in found} == {"scatter", "fusion:scatter"}
    assert all("[3,3712,64,8,128]" in e[2] for e in found), found
    rings = decode["ring_shaped"]
    assert rings and {e[0] for e in rings} == {"scatter", "fusion:scatter"}
    assert all("[9,64,128,8,128]" in e[2] for e in rings), rings
    carried = 2 * 3 * 3712 * BLK * 8 * 128 * 2 + 301_989_888
    assert decode["alias_gb"] * 1e9 >= carried
    assert decode["temp_gb"] < 0.05  # 8 MB: no second pool, no second ring


def test_the_windowed_models_experts_and_weights_stay_where_they_lie(
        compiled_windowed):
    """No instruction of the decode step is shaped like the held experts'
    stacks ([11,16,6144,4096], [11,16,2048,6144]) or like a layer of the
    other int8 weights; weights (9.54 GB), pool and rings (3.22 GB) are all
    the program is given."""
    decode = compiled_windowed["decode_chunk"]
    assert decode["expert_shaped"] == []
    assert decode["weight_shaped"] == []
    assert 12.7 < decode["argument_gb"] < 12.8


def test_an_admission_at_the_8192_bucket_leaves_half_a_gigabyte(
        compiled_windowed):
    """Attention through the flash kernel (no score matrix), FFNs in blocks
    of 2,048 tokens: 1.4 GB of temporaries beside 12.76 GB of weights, pool
    and rings, inside the chip's 15.75 GB with more than 0.5 GB to spare;
    the rings are written where they lie (a dynamic-update-slice into the
    slot), the pool a page at a time; no expert stack is copied."""
    admit = compiled_windowed["admit_row_paged"]
    assert admit["argument_gb"] + admit["temp_gb"] < 15.75 - 0.5
    assert admit["temp_gb"] < 1.8
    assert admit["expert_shaped"] == []
    assert admit["weight_shaped"] == []  # the counted kernel too (PR 39)
    assert {e[0] for e in admit["pool_shaped"]} <= {
        "dynamic-update-slice", "fusion:dynamic-update-slice"}
    assert {e[0] for e in admit["ring_shaped"]} <= {
        "dynamic-update-slice", "fusion:dynamic-update-slice"}


def _kernels(hlo: str) -> set:
    return set(re.findall(r"%(\w+?)(?:\.\d+)? = [^\n]*custom_call_target="
                          r'"tpu_custom_call"', hlo))


def _gathers(hlo: str, shape: str) -> list:
    """Instructions that gather (alone or inside a fusion) an array of
    ``shape``."""
    from tools import aot_decode

    return [e for e in aot_decode.shaped_like(hlo, [shape])
            if "gather" in e[0]]


def test_an_admissions_combine_gathers_no_row_for_an_absent_expert(
        compiled_windowed):
    """K-EXAONE's admission at the 8,192 bucket walks its expert layers in
    blocks of 2,048 tokens, 16,384 pairs of which one in eight falls on a
    held expert: the operand of the sum over k is made by the kernel
    ``moe_combine`` (Mosaic takes its copies of a tile of 8 bf16 rows, the
    held pairs' bits scalar-prefetched) and no ``gather`` of [16384, 6144]
    is left in the program (PR 58); the decode step's 512 pairs, whose list
    lies in fast memory, keep the gather and hold no such kernel."""
    admit = compiled_windowed["admit_row_paged"]["hlo"]
    assert "moe_combine" in _kernels(admit)
    assert _gathers(admit, "[16384,6144]") == []
    decode = compiled_windowed["decode_chunk"]["hlo"]
    assert "moe_combine" not in _kernels(decode)
    assert _gathers(decode, "[512,6144]")


def test_the_8192_admissions_flash_kernel_ends_with_the_real_tokens(
        compiled_windowed):
    """Mosaic takes the flash kernel with a traced bound on its axis of
    query tiles at 64 heads over 8, tiles of 1,024 and the band of 128 in
    tiles of 512, and the kernel that zeros the tiles not visited (PR 46)."""
    assert {"flash_attn", "flash_attn_padding"} <= _kernels(
        compiled_windowed["admit_row_paged"]["hlo"])
    assert "flash_attn" not in _kernels(
        compiled_windowed["decode_chunk"]["hlo"])


@pytest.fixture(scope="module")
def compiled_state_beside_pool():
    """``decode_chunk`` and ``admit_row_paged`` at the 512 and the 8,192
    bucket of nemotron3-super-ep4 at the cell's shapes (64 slots, 5,184
    pages of the 2 attention layers, the 10 Mamba-2 layers' float32 states
    beside them; some 50 s to lower and compile the three)."""
    from distributed_llms_tpu.models.presets import get_preset
    from tools import aot_decode

    try:
        aot_decode.v5e_devices()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    shapes = dict(slots=64, max_len=16384, pages=5184, page_size=BLK)
    cfg = get_preset("nemotron3-super-ep4")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DLT_QUANT_MATMUL", "kernel")
        mp.setenv("DLT_RAGGED_DECODE", "kernel")
        mp.setenv("DLT_MOE_EXPERTS", "kernel")
        return {
            "decode_chunk": aot_decode.analyse("decode_chunk", cfg, **shapes),
            **{bucket: aot_decode.analyse(
                "admit_row_paged", cfg, prompt_len=bucket, **shapes)
               for bucket in (512, 8192)}}


STATES = "[10,64,64,128,128]"  # every layer's and slot's, 2.68 GB


def test_the_states_beside_the_pool_are_updated_where_they_lie(
        compiled_state_beside_pool):
    """A decode step: nothing but the kernel ``ssm_decode`` produces an
    array shaped like the stack of states, a layer of it or a row of it
    (the stack is aliased through the kernel whole), nothing but the step's
    scatters one shaped like the pool [2,5184,64,2,128]; no expert stack
    ([10,128,1024,2688], [10,128,2688,1024]) and no layer of another int8
    weight is copied; weights (9.63 GB), states and taps (2.72) and pool
    (0.68) are all the program is given, and 0.13 GB of temporaries."""
    decode = compiled_state_beside_pool["decode_chunk"]
    states = decode["state_shaped"]
    assert states and {e[0] for e in states} == {"custom-call"}
    assert all("ssm_decode" in e[1] and STATES in e[2] for e in states)
    found = decode["pool_shaped"]
    assert found and {e[0] for e in found} == {"scatter", "fusion:scatter"}
    assert all("[2,5184,64,2,128]" in e[2] for e in found), found
    assert decode["expert_shaped"] == []
    assert decode["weight_shaped"] == []
    assert 13.0 < decode["argument_gb"] < 13.1
    assert decode["alias_gb"] * 1e9 >= 2_723_676_160 + 5184 * 131_072
    assert decode["temp_gb"] < 0.2
    assert {"ssm_decode", "moe_experts"} <= _kernels(decode["hlo"])


@pytest.mark.parametrize("bucket,temp_gb", [(512, 0.5), (8192, 2.0)])
def test_an_admission_writes_one_rows_state_into_its_slot(
        compiled_state_beside_pool, bucket, temp_gb):
    """The 512 and the 8,192 bucket: the stack of states takes one
    dynamic-update-slice of the row's slot, no instruction holds a layer's
    64 slots, and what is shaped like ONE row's state (4 MB a layer, 42 MB
    the row cache's ten) is the scan's output and its way into the row
    cache; the pool is written a page at a time; no expert stack is copied;
    1.89 GB of temporaries at 8,192 beside 13.04 GB of arguments, inside
    the chip's 15.75 GB with more than 0.5 GB to spare."""
    admit = compiled_state_beside_pool[bucket]
    whole = [e for e in admit["state_shaped"] if STATES in e[2]]
    assert whole and {e[0] for e in whole} <= {
        "dynamic-update-slice", "fusion:dynamic-update-slice"}
    assert not [e for e in admit["state_shaped"]
                if "[64,64,128,128]" in e[2]]
    assert admit["expert_shaped"] == []
    assert admit["weight_shaped"] == []
    assert {e[0] for e in admit["pool_shaped"]} <= {
        "dynamic-update-slice", "fusion:dynamic-update-slice"}
    assert admit["temp_gb"] < temp_gb
    assert admit["argument_gb"] + admit["temp_gb"] < 15.75 - 0.5
    assert {"ssm_prefill", "moe_experts", "flash_attn"} <= _kernels(
        admit["hlo"])


@pytest.fixture(scope="module")
def compiled_delta_state_beside_pool():
    """``decode_chunk`` and ``admit_row_paged`` at the 8,192 bucket of
    qwen3-next-ep4 at the cell's shapes (64 slots, 5,184 pages of the 3
    gated attention layers' 256-wide heads, the 9 delta-rule layers' float32
    states beside them; some 80 s to lower and compile the two)."""
    from distributed_llms_tpu.models.presets import get_preset
    from tools import aot_decode

    try:
        aot_decode.v5e_devices()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    shapes = dict(slots=64, max_len=16384, pages=5184, page_size=BLK)
    cfg = get_preset("qwen3-next-ep4")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DLT_QUANT_MATMUL", "kernel")
        mp.setenv("DLT_RAGGED_DECODE", "kernel")
        mp.setenv("DLT_MOE_EXPERTS", "kernel")
        return {
            "decode_chunk": aot_decode.analyse("decode_chunk", cfg, **shapes),
            8192: aot_decode.analyse(
                "admit_row_paged", cfg, prompt_len=8192, **shapes)}


DELTA_STATES = "[9,64,32,128,128]"  # every layer's and slot's, 1.21 GB


def test_the_delta_states_are_updated_where_they_lie_and_no_pool_is_copied(
        compiled_delta_state_beside_pool):
    """A decode step: nothing but the kernel ``gdn_decode`` produces an array
    shaped like the stack of states; no expert stack ([12,128,2048,1024],
    [12,128,512,2048]) and no layer of another int8 weight is copied;
    weights (5.78 GB), states and taps (1.24) and pool (2.04) are all the
    program is given.  The pool keeps both 256-wide heads in ONE row of 512
    lanes (ops.decode_attn.pool_head_shape): as [.., 2, 256] the paged
    kernel's view of it was a copy of the pool a layer a step, 2.06 GB of
    temporaries where there are 0.005."""
    decode = compiled_delta_state_beside_pool["decode_chunk"]
    states = decode["state_shaped"]
    assert states and {e[0] for e in states} == {"custom-call"}
    assert all("gdn_decode" in e[1] and DELTA_STATES in e[2] for e in states)
    assert decode["expert_shaped"] == []
    assert decode["weight_shaped"] == []
    assert 9.0 < decode["argument_gb"] < 9.1
    assert decode["alias_gb"] * 1e9 >= 1_236_271_104 + 5184 * 393_216
    assert decode["temp_gb"] < 0.1
    assert {"gdn_decode", "moe_experts", "paged_decode_attn"} <= _kernels(
        decode["hlo"])


def test_an_8192_admission_solves_its_triangles_in_a_gigabyte_and_a_half(
        compiled_delta_state_beside_pool):
    """The stack of states takes one dynamic-update-slice of the row's slot
    and no instruction holds a layer's 64 slots; the pool is written a page
    at a time; no expert stack is copied; 1.50 GB of temporaries beside 9.06
    GB of arguments."""
    admit = compiled_delta_state_beside_pool[8192]
    whole = [e for e in admit["state_shaped"] if DELTA_STATES in e[2]]
    assert whole and {e[0] for e in whole} <= {
        "dynamic-update-slice", "fusion:dynamic-update-slice"}
    assert not [e for e in admit["state_shaped"]
                if "[64,32,128,128]" in e[2]]
    assert admit["expert_shaped"] == []
    assert admit["weight_shaped"] == []
    assert {e[0] for e in admit["pool_shaped"]} <= {
        "dynamic-update-slice", "fusion:dynamic-update-slice"}
    assert admit["temp_gb"] < 1.7
    assert {"gdn_prefill", "moe_experts", "flash_attn"} <= _kernels(
        admit["hlo"])


@pytest.fixture(scope="module")
def compiled_blocked_rings():
    """``decode_chunk`` and ``admit_row_paged`` at the 16,384 bucket of
    smallthinker-pp4 at its 12 layers and the cell's shapes (32 slots,
    3,712 pages of the 3 full layers, the 9 windowed layers' rings of 4,096
    tokens beside them; some 50 s to lower and compile the two)."""
    from distributed_llms_tpu.models.presets import get_preset
    from tools import aot_decode

    try:
        aot_decode.v5e_devices()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DLT_QUANT_MATMUL", "kernel")
        mp.setenv("DLT_RAGGED_DECODE", "kernel")
        mp.setenv("DLT_MOE_EXPERTS", "kernel")
        return {
            program: aot_decode.analyse(
                program, get_preset("smallthinker-pp4"), slots=32,
                max_len=16384, pages=3712, page_size=BLK, prompt_len=16384)
            for program in ("decode_chunk", "admit_row_paged")}


def test_rings_of_4096_tokens_are_walked_by_the_kernel_and_written_in_place(
        compiled_blocked_rings):
    """The pool is the 3 full layers' [3,3712,64,4,128] and the rings
    [9,32,4096,4,128]: nothing but the step's in-place scatters produces an
    array of either shape, both are the output's alias (2 x 0.730 GB + 2 x
    1.208 GB), no expert stack ([12,64,2560,1536], [12,64,768,2560]) and no
    layer of an int8 weight is copied, and the rings' read is the compiled
    kernel (a dense body would gather [32,4096,4,128] a layer)."""
    decode = compiled_blocked_rings["decode_chunk"]
    found = decode["pool_shaped"]
    assert found and {e[0] for e in found} == {"scatter", "fusion:scatter"}
    assert all("[3,3712,64,4,128]" in e[2] for e in found), found
    rings = decode["ring_shaped"]
    assert rings and {e[0] for e in rings} == {"scatter", "fusion:scatter"}
    assert all("[9,32,4096,4,128]" in e[2] for e in rings), rings
    carried = 2 * 3 * 3712 * BLK * 4 * 128 * 2 + 2_415_919_104
    assert decode["alias_gb"] * 1e9 >= carried
    assert decode["expert_shaped"] == [] and decode["weight_shaped"] == []
    assert 10.3 < decode["argument_gb"] < 10.45
    assert decode["temp_gb"] < 0.3
    assert {"swa_decode_attn", "paged_decode_attn", "moe_experts",
            "_quant_matmul_2d"} <= _kernels(decode["hlo"])
    assert not re.search(r"\[32,4096,4,128\]", decode["hlo"])


def test_an_admission_at_the_16384_bucket_holds_one_row_of_logits(
        compiled_blocked_rings):
    """The head reads the last real position alone: no array of the
    compiled admission has the bucket AND the vocabulary among its
    dimensions (float32 logits of 16,384 positions would be 9.96 GB), and
    11.5 GB of arguments and temporaries leave 4 GB of the chip's 15.75."""
    admit = compiled_blocked_rings["admit_row_paged"]
    assert not re.search(r"\[[\d,]*16384,151936\]", admit["hlo"])
    assert re.search(r"f32\[1,151936\]", admit["hlo"])
    assert admit["temp_gb"] < 1.3
    assert admit["argument_gb"] + admit["temp_gb"] < 15.75 - 0.5
    assert admit["expert_shaped"] == [] and admit["weight_shaped"] == []
    assert admit["score_shaped"] == []
    assert {e[0] for e in admit["pool_shaped"]} <= {
        "dynamic-update-slice", "fusion:dynamic-update-slice"}
    assert {e[0] for e in admit["ring_shaped"]} <= {
        "dynamic-update-slice", "fusion:dynamic-update-slice"}


def test_the_16384_admissions_flash_kernel_ends_with_the_real_tokens(
        compiled_blocked_rings):
    """... and at 28 heads over 4 with the band of 4,096 (9 tiles of 512) on
    a row of 32 tiles."""
    assert {"flash_attn", "flash_attn_padding"} <= _kernels(
        compiled_blocked_rings["admit_row_paged"]["hlo"])


# (preset, slots, max_len, pages, bucket, GB of temporaries held to; what
# tools/aot_decode.py printed for the parent of PR 35, for PR 35 and, since
# the head reads the last real position alone, for PR 45: qwen2's float32
# logits of 2,048 positions, 1.25 GB, are gone.)
ADMISSIONS = [
    ("qwen2-7b", 16, 4096, 512, 2048, 0.75),  # 1.746 -> 1.716 -> 0.701
    ("pythia-6.9b", 8, 2048, 96, 512, 1.15),  # 1.343 -> 1.075 -> 1.075
    ("lfm2-8b-a1b", 16, 4096, 512, 2048, 0.45),  # 1.325 -> 0.550 -> 0.410
    ("ax-k1-ep16", 64, 4096, 2176, 2048, 0.80),  # 0.941 -> 0.757 -> 0.757
]


@pytest.mark.parametrize("preset,slots,max_len,pages,bucket,temp_gb",
                         ADMISSIONS, ids=[a[0] for a in ADMISSIONS])
def test_a_fresh_rows_admission_holds_no_scores_over_the_row_cache(
        preset, slots, max_len, pages, bucket, temp_gb):
    """``admit_row_paged`` of the four configurations whose admissions
    scored a bucket's queries against every slot of the row cache before
    PR 35, at the cell's shapes and full depth: no float32 ``[.., bucket,
    max_len]`` is left in the compiled program, and its temporaries are
    what an admission among its own tokens needs."""
    from distributed_llms_tpu.models.presets import get_preset
    from tools import aot_decode

    try:
        aot_decode.v5e_devices()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DLT_QUANT_MATMUL", "kernel")
        mp.setenv("DLT_RAGGED_DECODE", "kernel")
        mp.setenv("DLT_MOE_EXPERTS", "kernel")
        admit = aot_decode.analyse(
            "admit_row_paged", get_preset(preset), slots=slots,
            max_len=max_len, pages=pages, page_size=BLK, prompt_len=bucket)
    assert admit["score_shaped"] == []
    # ...and since PR 39 the matmuls of these buckets are told the count of
    # real rows: the kernel whose grid ends with them still reads every
    # weight where it lies, and the temporaries are no larger.
    # (lfm2's routing weights of 2,048 tokens, [2048, 4], are shaped like
    # wk's scales turned, at the parent too: what has the bucket among its
    # dimensions is an activation.)
    assert [e for e in admit["weight_shaped"]
            if not re.search(rf"[\[,]{bucket}[,\]]", e[2])] == []
    assert admit["temp_gb"] < temp_gb
    assert admit["argument_gb"] + admit["temp_gb"] < 15.75 - 0.5
    # A chip's share of the experts at a 2,048-token block: the pairs' rows
    # are fetched by the kernel, not gathered for all 16,384 pairs (PR 58);
    # a model that holds every expert keeps the gather.
    if preset == "ax-k1-ep16":
        assert "moe_combine" in _kernels(admit["hlo"])
        assert not _gathers(admit["hlo"], "[16384,7168]")
    if preset == "lfm2-8b-a1b":
        assert "moe_combine" not in _kernels(admit["hlo"])
        assert _gathers(admit["hlo"], "[8192,2048]")


@pytest.mark.parametrize("preset,slots,max_len,pages,bucket,group", [
    ("qwen2-7b", 16, 4096, 512, 128, 7), ("qwen2-7b", 16, 4096, 512, 2048, 7),
    ("pythia-6.9b", 8, 2048, 96, 128, 1)])
def test_a_continuation_is_the_flash_kernel_over_the_row_as_it_lies(
        preset, slots, max_len, pages, bucket, group):
    """``admit_row_auto_paged`` of the dense configurations that serve a
    prefix cache, compiled for the v5e at the cell's shapes (PR 47): the
    continuation's attention is the Pallas kernel (Mosaic takes the shifted
    diagonal's prefetched scalar, a KV group's heads in one tile of queries
    and K and V as lane blocks of the row), and neither the row's keys at
    the query heads nor a score matrix over the row is left in the
    program."""
    from distributed_llms_tpu.models.presets import get_preset
    from tools import aot_decode

    try:
        aot_decode.v5e_devices()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cfg = get_preset(preset)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DLT_QUANT_MATMUL", "kernel")
        mp.setenv("DLT_RAGGED_DECODE", "kernel")
        admit = aot_decode.analyse(
            "admit_row_auto_paged", cfg, slots=slots, max_len=max_len,
            pages=pages, page_size=BLK, prompt_len=bucket)
    calls = re.findall(r"%(flash_attn[\w.]*) = (\S+) custom-call", admit["hlo"])
    assert calls, "no flash kernel in the program"
    kvh, hd = cfg.num_kv_heads, cfg.head_dim_
    rows = group * bucket
    assert all(shape.startswith(f"bf16[{kvh},{rows},{hd}]")
               for _, shape in calls), calls
    assert admit["score_shaped"] == []
    repeated = f"{max_len},{kvh},{group},{hd}]"  # repeat_kv over the row
    assert aot_decode.shaped_like(admit["hlo"], [repeated]) == []
    assert admit["argument_gb"] + admit["temp_gb"] < 15.75 - 0.5


def test_score_shaped_finds_a_score_matrix():
    from tools import aot_decode

    hlo = "\n".join([
        "HloModule m", "ENTRY %main {",
        "  %a.1 = f32[28,2048,4096]{2,1,0} fusion(%p), kind=kLoop, calls=%f",
        "  %b.2 = f32[2048,4096]{1,0} copy(%q)",  # a stack of scales
        "  %c.3 = bf16[28,2048,4096]{2,1,0} copy(%r)", "}"])
    assert [e[1] for e in aot_decode.score_shaped(hlo, 2048, 4096)] == ["%a.1"]
    assert aot_decode.score_shaped(hlo, 4096, 4096) == []
