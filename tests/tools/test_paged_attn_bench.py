"""tools/paged_attn_bench.py, which times the paged decode kernels alone on
the chip: its rehearsal on the interpreter at a toy size (the GQA shape and
the latent one, the run and depth sweeps, the two floors, a block of the
latent body), and the rule that no time comes from a CPU."""

import json
import os
import sys

import pytest

from distributed_llms_tpu.ops import decode_attn
from tools import paged_attn_bench

SHAPES = ["rehearsal", "rehearsal-latent"]


def _main(monkeypatch, *args):
    monkeypatch.setattr(sys, "argv", ["paged_attn_bench.py", *args])
    monkeypatch.setattr(sys, "path", list(sys.path))
    return paged_attn_bench.main()


@pytest.fixture(scope="module")
def report(tmp_path_factory):
    out = tmp_path_factory.mktemp("bench") / "b.json"
    with pytest.MonkeyPatch.context() as mp:
        assert _main(mp, "--rehearsal", "--pads-mb", "0", "--runs", "0,2",
                     "--sets", "1", "--depths", "1-3", "--floors",
                     "--blocks", "1,2", "--out", str(out)) == 0
    return json.loads(out.read_text())


def _rows(report, shape, **keys):
    return [r for r in report["rows"] if r["shape"] == shape
            and all(r.get(k) == v for k, v in keys.items())]


def test_the_report_names_the_device_and_the_tree(report):
    assert report["device"]["platform"] == "cpu"
    assert os.path.samefile(report["tree"], paged_attn_bench.HERE)
    assert report["latent_block_pages"] == decode_attn._LATENT_BLOCK_PAGES
    assert not [r for r in report["rows"] if "error" in r]


@pytest.mark.parametrize("shape", SHAPES)
def test_the_drawn_rows_are_timed_at_every_run(report, shape):
    block = {"block": 1} if shape == "rehearsal-latent" else {}
    for run in (0, 2):
        rows = _rows(report, shape, run=run, lengths="rehearsal-chat", **block)
        assert len([r for r in rows if "floor" not in r]) == 1
        assert rows[0]["call_us"] > 0 and rows[0]["pages_held_a_row"] > 1
        assert rows[0]["row_us"] == rows[0]["call_us"] / 2  # two rows


@pytest.mark.parametrize("floor", [None, "copies", "products"])
@pytest.mark.parametrize("shape", SHAPES)
def test_every_depth_at_the_kernels_own_run_whole_and_by_floors(
        report, shape, floor):
    """``--depths 1-3``: every row at 1, 2 and 3 pages, at run 0 alone (a
    named run gets the drawn rows only), whole and with each half of the
    walk traced away."""
    rows = [r for r in _rows(report, shape, run=0)
            if r.get("floor") == floor and r.get("block") in (None, 1)]
    assert [r["lengths"] for r in rows if r["lengths"].startswith("all-")] \
        == ["all-1-pages", "all-2-pages", "all-3-pages"]
    assert not [r for r in _rows(report, shape, run=2)
                if r["lengths"].startswith("all-")]


def test_the_latent_body_is_timed_at_each_block_named(report):
    assert {r.get("block") for r in _rows(report, "rehearsal-latent")
            if "floor" not in r} == {1, 2}
    assert {r.get("block") for r in _rows(report, "rehearsal")} == {None}


@pytest.mark.parametrize("floor", [None, "copies", "products"])
def test_a_variant_is_traced_and_the_module_left_as_it_was(floor):
    before = {n: getattr(decode_attn, n) for n in
              (*paged_attn_bench.BODIES, "pltpu", "_LATENT_BLOCK_PAGES")
              if hasattr(decode_attn, n)}
    with paged_attn_bench.traced_as(decode_attn, floor, 2):
        assert decode_attn._LATENT_BLOCK_PAGES == 2
        if floor == "copies":
            assert decode_attn._latent_update(1, x=2) is None
        if floor == "products":
            copy = decode_attn.pltpu.make_async_copy(None, None, None)
            assert copy.start() is None and copy.wait() is None
            assert decode_attn.pltpu.VMEM is before["pltpu"].VMEM
    assert {n: getattr(decode_attn, n) for n in before} == before


def test_without_a_chip_it_times_nothing(monkeypatch, capsys):
    assert _main(monkeypatch, "--shapes", "ax-k1") == 2
    assert "no TPU" in capsys.readouterr().err


def test_the_latent_shape_is_the_cells():
    c = paged_attn_bench.SHAPES["ax-k1"]
    with open(os.path.join(paged_attn_bench.HERE, "benchmark", "configs",
                           "ax-k1-int8-ep16.json")) as f:
        served = json.load(f)["serve"]
    assert (c["b"], c["blk"], c["nb"]) == (
        served["slots"], served["page_size"], served["paged_pages"])
    assert c["p"] * c["blk"] == served["max_len"]
    assert c["traffic"] == ("long-answers",)
