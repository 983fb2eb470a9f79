"""What a run holds a cell to is its configuration's to say (PR 49): the
kernels it must have taken, whether its rows lie in a pool, the probes
compared with the golden.  A file that says nothing is held to what every
cell was held to before the keys existed."""

import glob
import json
import os

import pytest

from benchmark import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIGS = sorted(glob.glob(os.path.join(ROOT, "benchmark", "configs", "*.json")))
SERVE = {"slots": 16, "max_len": 4096, "page_size": 64, "paged_pages": 512,
         "chunk_steps": 8}
# A dispatch record as /metrics gives it at the end of a sound run of a cell
# with a pool, and of one whose rows hold a state and no page.
PAGED = {"ops_dispatch_quant_matmul_kernel": 49.0,
         "ops_dispatch_quant_matmul_fallback": 0.0,
         "ops_dispatch_paged_decode_kernel": 2.0,
         "ops_dispatch_paged_decode_run_pages": 8.0,
         "ops_dispatch_flash_kernel": 7.0, "ops_dispatch_flash_fallback": 0.0,
         "server_requests_total": 400.0}
STATE = {"ops_dispatch_quant_matmul_kernel": 70.0,
         "ops_dispatch_state_decode_kernel": 2.0,
         "ops_dispatch_state_scan_kernel": 6.0,
         "ops_dispatch_state_decode_fallback": 0.0}
OWN = ["quant_matmul", "state_decode"]


def test_a_file_that_says_nothing_is_held_to_what_every_cell_was():
    held = run.held_to(SERVE)
    assert held == {"must_dispatch": ["quant_matmul", "paged_decode"],
                    "paged_pages": 512, "probe_bytes": [32, 200, 700, 1500]}
    assert json.loads(json.dumps(held)) == held     # it goes into the line


def test_a_file_that_says_is_held_to_what_it_says():
    serve = {**SERVE, "paged_pages": 0, "must_dispatch": OWN,
             "probe_bytes": [32, 5000]}
    assert run.held_to(serve) == {"must_dispatch": OWN, "paged_pages": 0,
                                  "probe_bytes": [32, 5000]}


@pytest.mark.parametrize("key, value", [
    ("must_dispatch", []), ("must_dispatch", [""]), ("must_dispatch", [3]),
    ("probe_bytes", []), ("probe_bytes", [0]), ("probe_bytes", [32, "200"]),
    ("probe_bytes", [1.5])])
def test_an_empty_or_ill_typed_list_is_refused(key, value):
    with pytest.raises(run.Failed, match=key):
        run.held_to({**SERVE, key: value})


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_every_configuration_says_what_it_is_held_to(path):
    with open(path) as f:
        serve = json.load(f)["serve"]
    held = run.held_to(serve)
    assert ("paged_decode" in held["must_dispatch"]) == \
        (held["paged_pages"] != 0)
    assert "quant_matmul" in held["must_dispatch"]


@pytest.mark.parametrize("record, must, faults", [
    # The default list on a sound record, and its own list on a state's.
    (PAGED, run.MUST_DISPATCH, []),
    (STATE, OWN, []),
    # A required kernel at 0, or never recorded, is a fault ...
    ({**PAGED, "ops_dispatch_paged_decode_kernel": 0.0}, run.MUST_DISPATCH,
     ["paged_decode did not take the compiled kernel"]),
    (STATE, run.MUST_DISPATCH,
     ["paged_decode did not take the compiled kernel"]),
    (PAGED, OWN, ["state_decode did not take the compiled kernel"]),
    ({}, OWN, ["quant_matmul did not take the compiled kernel",
               "state_decode did not take the compiled kernel"]),
    # ... and so is any fallback or interpreter leg, of an operation the
    # list names or of one it does not, whatever the list says.
    ({**PAGED, "ops_dispatch_flash_fallback": 3.0}, run.MUST_DISPATCH,
     ["flash_fallback = 3.0"]),
    ({**STATE, "ops_dispatch_state_decode_fallback": 1.0}, OWN,
     ["state_decode_fallback = 1.0"]),
    ({**STATE, "ops_dispatch_state_scan_interpret": 2.0}, OWN,
     ["state_scan_interpret = 2.0"]),
    ({**STATE, "ops_dispatch_flash_fallback": 1.0}, ["quant_matmul"],
     ["flash_fallback = 1.0"]),
    ({**PAGED, "ops_dispatch_paged_decode_kernel": 0.0,
      "ops_dispatch_paged_decode_interpret": 2.0}, run.MUST_DISPATCH,
     ["paged_decode did not take the compiled kernel",
      "paged_decode_interpret = 2.0"]),
])
def test_check_dispatch(record, must, faults):
    assert run.check_dispatch(record, must) == faults


class Gateway:
    """Answers every probe; remembers what it was sent."""

    def __init__(self):
        self.sent = []

    def complete(self, rec, prompt, *, timeout_s, prefix_cache=True):
        self.sent.append((len(prompt), rec.asked, prefix_cache))
        rec.status, rec.finish = 200, "length"
        rec.n_tokens, rec.logprobs = rec.asked, [-1.0] * rec.asked
        return rec


@pytest.mark.parametrize("serve, max_len, sent", [
    (SERVE, 4096, [32, 200, 700, 1500]),
    ({**SERVE, "probe_bytes": [32, 200, 700, 1500, 6000]}, 16384,
     [32, 200, 700, 1500, 6000]),
    # The cut at max_len less the probe's answer and 8 stays.
    ({**SERVE, "probe_bytes": [32, 90]}, 128, [32, 90]),
    (SERVE, 128, [32, 112, 112, 112])])
def test_the_probes_sent_are_the_configurations(serve, max_len, sent):
    gw = Gateway()
    probes = run.run_probes(gw, max_len, run.held_to(serve)["probe_bytes"])
    assert [p["bytes"] for p in probes] == sent
    assert gw.sent == [(n, run.PROBE_TOKENS, False) for n in sent]
    assert all(run.probe_prompt(n).startswith(f"probe {n}: ") for n in sent)


def test_a_golden_of_other_lengths_than_the_probes_is_not_correct():
    probes = [{"bytes": n, "logprobs": [-1.0] * 8} for n in (32, 5000)]
    same = {"probes": [dict(p) for p in probes]}
    assert run.check_golden(probes, same) == []
    old = {"probes": [{"bytes": n, "logprobs": [-1.0] * 8}
                      for n in (32, 200)]}
    assert run.check_golden(probes, old) == ["probe sizes differ: 5000 / 200"]
    # A golden recorded before the configuration listed its last probe
    # compares the others and is refused for the one it lacks.
    short = {"probes": same["probes"][:1]}
    assert run.check_golden(probes, short) == [
        "2 probes sent, 1 in the golden"]
