"""The counts a hybrid model's programs hand ``ContinuousBatcher._note_moe``
beside their tokens, which reads them BY POSITION: the experts' four, a
share's fifth and sixth (the held pairs; those of them whose rows the
combine fetched singly, PR 58), a decode chunk's one or two of the tokens
its rows held (against latent pages a third, the keys the kernel's products
covered, in the NINTH place behind an empty eighth, so that eight counts stay
the rings': PR 63), a state-space model's three last of all.  Every family's
layout in both kinds of program, pinned before anyone moves a slot."""

import dataclasses

import jax
import numpy as np
import pytest

from distributed_llms_tpu.core.observability import METRICS
from distributed_llms_tpu.models import model as model_lib
from distributed_llms_tpu.models.presets import get_preset
from distributed_llms_tpu.runtime.batcher import ContinuousBatcher

MOE = ["moe.routed_pairs", "moe.layer_passes", "moe.experts_touched",
       "moe.max_load_tokens"]
SHARE = ["moe.held_pairs", "moe.combine_rows"]
RINGS = ["attn.decode.resident_tokens", "swa.decode.window_tokens",
         "swa.decode.ring_tokens"]
SSM = ["ssm.admit.tokens", "ssm.admit.chunks", "ssm.decode.row_steps"]
# family -> (preset, experts held of its experts, an admission's counters in
# the order of its array, a decode chunk's)
FAMILIES = {
    "whole": ("lfm2-tiny", None, MOE, MOE),
    "share": ("lfm2-tiny", 4, MOE + SHARE, MOE + SHARE),
    "latent-pages": (
        "ax-k1-tiny", 4, MOE + SHARE,
        MOE + SHARE + ["mla.decode.resident_tokens", None,
                       "mla.decode.scored_keys"]),
    "pages-and-rings": (
        "k-exaone-tiny", 4, MOE + SHARE,
        MOE + SHARE + ["attn.decode.resident_tokens",
                       "swa.decode.window_tokens"]),
    "state-space": (
        "nemotron3-super-tiny", 8, MOE + SHARE + SSM, MOE + SHARE + SSM),
}


def _batcher(family):
    name, held, *_ = FAMILIES[family]
    cfg = get_preset(name)
    params = model_lib.init_params(jax.random.key(0), cfg)
    if held is not None and cfg.experts_held is None:
        blocks = dict(params["blocks"])
        blocks["moe"] = dict(blocks["moe"], experts=jax.tree.map(
            lambda a: a[:, :held], blocks["moe"]["experts"]))
        params = dict(params, blocks=blocks)
        cfg = dataclasses.replace(cfg, experts_held=held, experts_offset=0)
    return ContinuousBatcher(cfg, params, batch_slots=3, max_len=64,
                             chunk_steps=4, paged_pages=24, page_size=8)


def _delta(before, names):
    after = METRICS.snapshot()["counters"]
    return {n: after.get(n, 0) - before.get(n, 0) for n in names}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_every_familys_layout_in_an_admission_and_a_decode_chunk(family):
    _, _, admission, chunk = FAMILIES[family]
    b = _batcher(family)
    seen = []
    note = b._note_moe

    def spy(stats=None):
        if stats is not None:
            seen.append(len(stats))
        note(stats)

    b._note_moe = spy
    b.submit(list(range(40, 59)), max_new_tokens=6)
    b.run()
    assert set(seen) == {len(admission), len(chunk)}
    # Each slot lands on its own counter: a number a slot, read back.
    for names in (admission, chunk):
        before = METRICS.snapshot()["counters"]
        values = [1000 * (i + 1) for i in range(len(names))]
        if "swa.decode.window_tokens" in names:
            values[0] = 0  # (ring_tokens is reckoned from the routed pairs)
        note(np.asarray(values, np.int32))
        counted = [n for n in names if n]  # (None: a place nobody reads)
        got = _delta(before, counted + RINGS)
        assert [got[n] for n in counted] == [
            v for n, v in zip(names, values) if n]
        # ... and on no other family's: the rings' counters move for pages
        # and rings alone, whatever the others hand out.
        assert all(got[n] == 0 for n in RINGS if n not in names)



@pytest.mark.parametrize("family", [f for f in FAMILIES if f != "whole"])
def test_the_rows_the_combine_fetched_are_held_pairs(family):
    """``moe.combine_rows`` counts, of the held pairs, those whose rows the
    kernel ``moe_combine`` fetched: never more than ``moe.held_pairs``, which
    is never more than ``moe.routed_pairs``, and here none, since these
    batchers hold float stacks and a float stack's pairs are gathered (the
    kernel's own count is held in tests/ops/test_moe_experts.py)."""
    names = ["moe.routed_pairs", "moe.held_pairs", "moe.combine_rows"]
    before = METRICS.snapshot()["counters"]
    b = _batcher(family)
    b.submit(list(range(40, 59)), max_new_tokens=6)
    b.run()
    got = _delta(before, names)
    assert 0 == got["moe.combine_rows"] <= got["moe.held_pairs"]
    assert 0 < got["moe.held_pairs"] < got["moe.routed_pairs"]
