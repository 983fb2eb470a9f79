"""The traffic files: the same work under every seed, and work that fits."""

import collections
import glob
import json
import os

import pytest

from benchmark import run, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MIXES = sorted(
    os.path.splitext(os.path.basename(p))[0]
    for p in glob.glob(os.path.join(ROOT, "benchmark", "traffic", "*.json"))
)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def shape(turns):
    return sorted((t.shared, len(t.prompt), t.max_tokens) for t in turns)


@pytest.mark.parametrize("mix", MIXES)
def test_two_seeds_same_work_other_bytes_and_callers(mix):
    spec = traffic.load(mix)
    a = [t for s in traffic.cycle(spec, 1, 0) for t in s]
    b = [t for s in traffic.cycle(spec, 2**31 + 7, 0) for t in s]
    # The same lengths in the same order: the work does not follow the seed.
    assert [(t.shared, len(t.prompt), t.max_tokens) for t in a] == \
        [(t.shared, len(t.prompt), t.max_tokens) for t in b]
    assert shape(a) == traffic.lengths(spec)
    assert [t.prompt for t in a] != [t.prompt for t in b]
    # The same seed gives the same bytes; a later cycle other bytes.
    assert a == [t for s in traffic.cycle(spec, 1, 0) for t in s]
    later = [t for s in traffic.cycle(spec, 1, 1) for t in s]
    assert shape(later) == shape(a) and later != a
    # The scripts are dealt to the callers in an order the seed draws.
    n = spec["clients"]
    deals = {tuple(traffic.script_of_caller(spec, seed)) for seed in range(8)}
    assert all(sorted(d) == list(range(n)) for d in deals)
    assert len(deals) > 1


@pytest.mark.parametrize("mix", MIXES)
def test_scripts_cover_each_cycle_once(mix):
    """The scripts are the cycles dealt out: together, over the first two
    cycles, they hold the file's multiset twice, less the turns a script
    skips in its very first session."""
    spec = traffic.load(mix)
    n = spec["clients"]

    def skipped(index, k, turns):
        return (k % n) % len(turns) if index == 0 and k < n else 0

    want, mine = collections.Counter(), [0] * n
    for index in (0, 1):
        for k, turns in enumerate(traffic.cycle(spec, 5, index)):
            kept = turns[skipped(index, k, turns):]
            want.update((t.shared, len(t.prompt), t.max_tokens) for t in kept)
            mine[k % n] += len(kept)
    sent = collections.Counter()
    for j in range(n):
        script = traffic.client_script(spec, 5, j)
        for _ in range(mine[j]):
            t = next(script)
            sent[(t.shared, len(t.prompt), t.max_tokens)] += 1
    assert sent == want


@pytest.mark.parametrize(
    "cell", [w["name"] for w in manifest()["workloads"]])
def test_worst_case_fits_the_pool(cell):
    """Every cell's mix fits the server its configuration asks for, by the
    one function the run itself asks before it boots."""
    w = {w["name"]: w for w in manifest()["workloads"]}[cell]
    serve = config(w["config"])["serve"]
    spec = traffic.load(w["traffic"])
    must = run.held_to(serve)["must_dispatch"]
    assert traffic.pool_fits(spec, serve, must) == []


def mix(clients, longest, answer=1):
    """``clients`` callers who each send ``longest`` bytes and ask for
    ``answer`` tokens, beside as many short requests."""
    return {"clients": clients, "sessions": [
        {"shared": 0, "turns": [[n, answer]]}
        for n in (longest, 100) for _ in range(clients)]}


POOL = {"slots": 16, "max_len": 4096, "page_size": 64, "paged_pages": 512}
NO_POOL = {"slots": 16, "max_len": 32768, "page_size": 64, "paged_pages": 0}
PAGED = ["quant_matmul", "paged_decode"]
STATE = ["quant_matmul", "state_decode"]


@pytest.mark.parametrize("spec, serve, must, faults", [
    # A pool: 16 rows of 1 + 2,000 + 43 tokens are 16 x 32 = 512 pages, one
    # more than 512 less the scratch page hold; 15 such rows fit.
    (mix(16, 2000, 43), POOL, PAGED, ["worst case of 512 pages"]),
    (mix(15, 2000, 43), POOL, PAGED, []),
    (mix(16, 1900, 83), POOL, PAGED, []),
    (mix(17, 100), POOL, PAGED, ["17 callers for 16 slots"]),
    (mix(2, 4000, 96), POOL, PAGED, ["holds 4097 tokens, a row 4096"]),
    (mix(2, 100), POOL, STATE, ["does not name paged_decode"]),
    # No pool: no page is counted, so 16 rows of 30,721 tokens fit where
    # they fit the rows (7,681 pages of 64 would fit no pool declared) ...
    (mix(16, 28672, 2048), NO_POOL, STATE, []),
    (mix(16, 28672, 2048), {**NO_POOL, "paged_pages": 7681}, PAGED,
     ["worst case of 7696 pages"]),
    (mix(17, 100), NO_POOL, STATE, ["17 callers for 16 slots"]),
    (mix(16, 30720, 2048), NO_POOL, STATE,
     ["holds 32769 tokens, a row 32768"]),
    # ... and a file without a pool may ask for nothing that needs one.
    (mix(2, 100), NO_POOL, PAGED, ["must_dispatch names paged_decode"]),
    (mix(2, 100), {**NO_POOL, "extra_argv": ["--prefix-cache"]}, STATE,
     ["and --prefix-cache"]),
    (mix(17, 100), {**NO_POOL, "extra_argv": ["--prefix-cache"]}, PAGED,
     ["17 callers", "names paged_decode", "and --prefix-cache"]),
])
def test_pool_fits(spec, serve, must, faults):
    traffic.check(spec)
    got = traffic.pool_fits(spec, serve, must)
    assert len(got) == len(faults), got
    for said, want in zip(got, faults):
        assert want in said


def test_doc_qa_asks_each_document_four_times_the_first_cold():
    spec = traffic.load("doc-qa")
    assert all(len(s["turns"]) == 4 and s["shared"] >= 768
               for s in spec["sessions"])
    docs = collections.defaultdict(list)
    for turns in traffic.cycle(spec, 9, 0):
        for t in turns:
            docs[t.prompt[: t.shared]].append(t)
    assert len(docs) == len(spec["sessions"])
    for doc, turns in docs.items():
        assert [t.turn for t in turns] == [0, 1, 2, 3]
        # No other document shares even the first page with this one, so
        # the first question finds nothing cached.
        assert sum(d[:64] == doc[:64] for d in docs) == 1
        assert len({t.prompt for t in turns}) == 4


def test_open_loop_schedule_is_the_same_under_every_seed():
    spec = traffic.load("rehearsal-open")
    times = traffic.arrival_times(spec, 4.0)
    assert times == sorted(times) and times[0] == 0.0
    # 4 a second, doubled for the first half second of every two.
    assert len(times) == 4 * 4 + 2 * 2
    script = traffic.arrival_script(spec, 3)
    first = [next(script) for _ in range(len(spec["sessions"]))]
    assert shape(first) == traffic.lengths(spec)


def test_warmups_cover_every_admission_bucket():
    spec = traffic.load("doc-qa")
    ups = traffic.warmup_turns(spec, 64)
    assert 1 <= len(ups) <= 8
    want = {traffic.bucket(p + 1) for _, p, _ in traffic.lengths(spec)}
    assert {traffic.bucket(p + 1) for _, p, _ in ups} == want
    assert [traffic.bucket(n) for n in (1, 8, 9, 1024, 1025)] == \
        [8, 8, 16, 1024, 2048]
