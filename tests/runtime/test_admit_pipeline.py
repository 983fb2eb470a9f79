"""A round's admissions, pipelined one deep (runtime/batcher.py,
``ContinuousBatcher._admit_pending``).

With ``overlap`` on, admission k+1 is selected, prepared and launched while
admission k runs, and k's one fetch and its activation come after.  Pinned
here: the two orders serve the same tokens and logprobs, request for request,
in every family and on every admission path; every way out of a round settles
the admission in flight first, hands no slot out twice and leaves the pool as
the serial order leaves it; the engine's account of the device charges the
round's first preparation and nothing between a launch and the fetch before
it; ``batcher.admit.overlapped`` counts the admissions launched behind an
unfetched one; a cancel that arrives for the admission in flight takes it,
and one for the request picked next (whose pick a settle fell into: a
chunked start, a swap restore, a dry pool) is the serial order's cancel in
the queue; a request launched behind another leaves the queue when that
one's fetch returns.
"""

import jax
import numpy as np
import pytest

from distributed_llms_tpu.core.observability import METRICS
from distributed_llms_tpu.models import model as model_lib, presets
from distributed_llms_tpu.runtime import batcher as batcher_mod
from distributed_llms_tpu.runtime.batcher import ContinuousBatcher
from distributed_llms_tpu.runtime.faults import FaultPlane

PAGED = dict(paged_pages=24, page_size=8)
DOC = list(range(40, 75))                     # four pages of 8 and a bit


def ids(n, seed):
    return [int(x) for x in np.random.RandomState(seed).randint(1, 250, n)]


_MODELS: dict = {}


def model(preset, **kw):
    """(cfg, params) of a tiny preset, built once a module run."""
    key = (preset, tuple(sorted(kw.items())))
    if key not in _MODELS:
        cfg = presets.get_preset(preset, **kw)
        _MODELS[key] = cfg, model_lib.init_params(jax.random.key(0), cfg)
    return _MODELS[key]


def mk(preset="llama-tiny", overlap=True, preset_kw=None, **kw):
    cfg, params = model(preset, **(preset_kw or {"vocab_size": 512}))
    kw = {"batch_slots": 3, "max_len": 64, "chunk_steps": 4, **kw}
    return ContinuousBatcher(cfg, params, overlap=overlap, **kw)


def counted(b):
    """Watch ``b`` serve: after every admission round nothing is in flight,
    no admission is activated into a slot that holds a row, and the rounds
    that admitted are counted.  -> the dict of counts."""
    seen = {"rounds": 0}
    admit, activate = b._admit_pending, b._activate_row

    def round_():
        before = METRICS.get_counter("batcher.admitted")
        try:
            admit()
        finally:
            assert b._admit_inflight is None
        seen["rounds"] += METRICS.get_counter("batcher.admitted") > before

    def activating(i, req, *a, **k):
        assert (b.rows[i].rid is None or b.rows[i].prefilling) \
            and not b.active[i], f"slot {i} handed out twice"
        return activate(i, req, *a, **k)

    b._admit_pending, b._activate_row = round_, activating
    return seen


def serve(b, jobs, **submit_kw):
    """-> (tokens, logprobs, prefix-cached tokens) request for request."""
    rids = [b.submit(p, max_new_tokens=n, **submit_kw) for p, n in jobs]
    out = b.run()
    return ([out[r] for r in rids], [b.result_logprobs[r] for r in rids],
            [b.prefix_cached_tokens.get(r, 0) for r in rids])


JOBS = [(ids(5, 1), 9), (ids(19, 2), 6), (ids(11, 3), 7), (ids(3, 4), 5),
        (ids(26, 5), 4)]


def _spec_kw():
    dcfg, dparams = model("llama-tiny", vocab_size=512, num_layers=2)
    return dict(paged_pages=24, page_size=16, prefix_cache=True, spec_k=3,
                draft_params=dparams, draft_cfg=dcfg)


# kind -> (batcher keywords, jobs, submit keywords)
KINDS = {
    "llama-paged": (lambda: dict(PAGED), JOBS, {}),
    "neox-paged": (lambda: dict(PAGED, preset="neox-tiny"), JOBS, {}),
    "hybrid": (lambda: dict(PAGED, preset="lfm2-tiny", preset_kw={}),
               JOBS, {}),
    "latent-pool": (lambda: dict(PAGED, preset="ax-k1-tiny", preset_kw={},
                                 prefix_cache=True, batch_slots=4),
                    JOBS, {}),
    "windowed-rings": (lambda: dict(PAGED, preset="k-exaone-tiny",
                                    preset_kw={}), JOBS, {}),
    # the second and third hit pages the first published in the SAME round
    "prefix-hit-same-round": (
        lambda: dict(PAGED, prefix_cache=True),
        [(DOC + [3], 4), (DOC + [5, 6], 9), (DOC + [8], 7),
         (ids(3, 9), 6)], {}),
    "constrained": (
        lambda: dict(PAGED), JOBS,
        {"response_format": {"type": "regex", "regex": "[a-z]{4,12}"}}),
    "sampled-penalized": (
        lambda: dict(PAGED, seed=5), JOBS,
        {"temperature": 1.1, "top_k": 40, "presence_penalty": 0.5}),
    "contiguous": (lambda: {}, JOBS, {}),
    "speculative": (_spec_kw, JOBS, {}),
}


@pytest.mark.parametrize("kind", [
    pytest.param(k, marks=pytest.mark.fragile_xla_cpu)
    if k == "speculative" else k for k in KINDS])
def test_the_two_orders_serve_the_same_tokens(kind):
    """Overlap on against off, the same queue: tokens, logprobs and the
    prefix cache's hits request for request, and with it on most admissions
    are launched behind an unfetched one."""
    make_kw, jobs, submit_kw = KINDS[kind]
    if kind == "constrained":
        from distributed_llms_tpu.runtime.tokenizer import ByteTokenizer
        tok = ByteTokenizer()
        extra = dict(tokenizer=tok, eos_id=tok.eos_id, pad_id=tok.pad_id)
    else:
        extra = {}
    got = {}
    for overlap in (False, True):
        b = mk(overlap=overlap, **make_kw(), **extra)
        seen = counted(b)
        c0 = {n: METRICS.get_counter(n)
              for n in ("batcher.admitted", "batcher.admit.overlapped")}
        got[overlap] = serve(b, jobs, **submit_kw)
        d = {n: METRICS.get_counter(n) - v for n, v in c0.items()}
        assert d["batcher.admitted"] == len(jobs)
        # (4) the counter: admissions less the rounds that admitted
        assert d["batcher.admit.overlapped"] == \
            (len(jobs) - seen["rounds"] if overlap else 0)
        assert not overlap or d["batcher.admit.overlapped"] >= 2
        if b.paged:
            b.assert_pool_consistent()
    assert got[True] == got[False]
    assert [len(t) for t in got[True][0]] == [n for _, n in jobs] \
        or kind == "constrained"
    if kind == "prefix-hit-same-round":
        assert got[True][2][:3] == [0, 32, 32]


# -- every way out of a round settles the admission in flight ---------------


def _pool_accounts(b):
    s = b.pool.stats()
    return {k: s[k] for k in ("free_pages", "cached_pages", "held_pages")}


def _swap_scenario(overlap):
    """A swap-preempted resume queued behind two requests of higher
    priority: the round launches those, then meets the parcel."""
    b = mk(overlap=overlap, paged_pages=16, page_size=8, prefix_cache=True,
           host_pages=16)
    seen = counted(b)
    first = b.submit(ids(12, 1), max_new_tokens=9)
    b._admit_pending()
    swaps = METRICS.get_counter("batcher.kv_swaps.in")
    b._preempt_row(0, "growth")
    assert b.queue[0].swap_handle is not None
    others = [b.submit(ids(7, s), max_new_tokens=5, priority=3)
              for s in (2, 3)]
    out = b.run()
    assert METRICS.get_counter("batcher.kv_swaps.in") == swaps + 1
    return b, seen, [out[r] for r in [first] + others]


def _plain(jobs, **kw):
    def scenario(overlap):
        b = mk(overlap=overlap, **kw)
        seen = counted(b)
        return b, seen, serve(b, jobs)[0]
    return scenario


EXITS = {
    # two requests, three slots: the queue runs empty with one in flight
    "empty-queue": _plain(JOBS[:2], **PAGED),
    # five requests, two slots: no slot is free with one in flight
    "no-slot": _plain(JOBS, batch_slots=2, **PAGED),
    # a pool of 9 pages, 4 a row: the third reservation finds it dry
    "back-pressure": _plain([(ids(17, 1), 6), (ids(18, 2), 6),
                             (ids(19, 3), 6)], paged_pages=10, page_size=8),
    # the dry pool is an injected one, at the second reservation
    "exhaust-fault": lambda overlap: _plain(
        JOBS[:3], faults=FaultPlane.parse("batcher.page_alloc/admit:exhaust@2"),
        **PAGED)(overlap),
    # the third request is long enough to start chunked
    "chunked-start": _plain([(ids(5, 1), 9), (ids(9, 4), 7), (ids(30, 2), 6),
                             (ids(4, 3), 5)], batch_slots=4,
                            prefill_chunk=16, prefix_cache=True, **PAGED),
    "swap-restore": _swap_scenario,
}


@pytest.mark.parametrize("exit_", list(EXITS))
def test_every_way_out_of_a_round_settles_the_admission_in_flight(exit_):
    """``counted`` holds every round to: nothing in flight when it ends, no
    slot twice.  The pool's accounts and the tokens are the serial run's."""
    runs = {}
    for overlap in (False, True):
        ahead = METRICS.get_counter("batcher.admit.overlapped")
        b, seen, toks = EXITS[exit_](overlap)
        b.assert_pool_consistent()
        runs[overlap] = (toks, _pool_accounts(b), b.preemptions)
        ahead = METRICS.get_counter("batcher.admit.overlapped") - ahead
        assert (ahead > 0) == overlap         # the pipeline did engage
    assert runs[True] == runs[False]


@pytest.mark.parametrize("overlap", [True, False])
def test_an_exception_at_the_next_launch_settles_the_one_before(
        monkeypatch, overlap):
    """The second admission's program raises as it is dispatched: the first
    is resident and streamed, nothing is in flight, and its slot and the
    pool's accounts are the serial order's."""
    b = mk(overlap=overlap, **PAGED)
    counted(b)
    real, calls = batcher_mod.admit_row_paged, []

    def failing(*a, **k):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("boom at the next launch")
        return real(*a, **k)

    monkeypatch.setattr(batcher_mod, "admit_row_paged", failing)
    rids = [b.submit(p, max_new_tokens=n) for p, n in JOBS[:3]]
    streamed = []
    with pytest.raises(RuntimeError, match="boom"):
        b.run(on_tokens=lambda rid, toks, done, lps: streamed.append(rid))
    assert b._admit_inflight is None
    assert [r.rid for r in b.rows] == [rids[0], None, None]
    assert bool(b.active[0]) and streamed == [rids[0]]
    assert [q.rid for q in b.queue] == [rids[2]]
    # the failed launch's reservation is lost in either order (the
    # supervisor respawns the batcher): held = both reservations
    assert _pool_accounts(b)["held_pages"] == len(b.rows[0].pages) + 4


# -- the engine's account of the device --------------------------------------


def _ticking(b, monkeypatch, now, cost):
    """Each named piece of the batcher's work moves the clock by its cost."""
    def ticking(fn, dt):
        def wrapped(*a, **k):
            now[0] += dt
            return fn(*a, **k)
        return wrapped

    for name, dt in cost.items():
        target = (batcher_mod.jax if name == "device_get"
                  else b if hasattr(b, name) else batcher_mod)
        monkeypatch.setattr(target, name, ticking(getattr(target, name), dt))


@pytest.mark.parametrize("overlap", [True, False])
def test_starved_time_is_a_rounds_first_preparation_and_last_activation(
        monkeypatch, overlap):
    """(3) On a clock that moves only in a reservation (2), an admission
    program's dispatch (64), a fetch (16) and an activation (4): two rounds
    of two admissions.  A run's first dispatch follows no fetch, so round
    one charges its last activation (and, serial, everything between its
    two admissions); round two its first reservation too.  Pipelined,
    nothing between the launch of k+1 and the fetch of k is charged."""
    now = [0.0]
    b = mk(overlap=overlap, batch_slots=2, clock=lambda: now[0], **PAGED)
    _ticking(b, monkeypatch, now, {
        "_alloc_pages": 2.0, "admit_row_paged": 64.0, "device_get": 16.0,
        "_activate_row": 4.0})
    for seed in range(4):                     # 12 tokens in 2 pages: no growth
        b.submit(ids(4, seed), max_new_tokens=8)
    seen = counted(b)
    s0 = METRICS.get_counter("batcher.starved.admit_seconds")
    row0 = METRICS.get_histogram("batcher.admit.row_seconds")
    wait0 = METRICS.get_histogram("batcher.admit.wait_device_seconds")
    b.run()
    assert seen["rounds"] == 2
    got = METRICS.get_counter("batcher.starved.admit_seconds") - s0
    # serial: round one 4 + (2 + 4), round two (2 + 4) + (2 + 4)
    assert got == pytest.approx(4.0 + (2.0 + 4.0) if overlap else 22.0)
    row1 = METRICS.get_histogram("batcher.admit.row_seconds")
    wait1 = METRICS.get_histogram("batcher.admit.wait_device_seconds")
    # one row span and one fetch an admission, and the row spans hold
    # every launch, fetch and activation and the reservations of the
    # second admission of each round (the first's is made before its span)
    assert row1[0] - row0[0] == wait1[0] - wait0[0] == 4
    assert wait1[1] - wait0[1] == pytest.approx(4 * 16.0)
    assert row1[1] - row0[1] == pytest.approx(4 * (64.0 + 16.0 + 4.0) + 2 * 2.0)


def test_every_fetch_lies_inside_a_row_span_that_names_it(monkeypatch):
    """k's fetch is inside k+1's row span (``fetched_rid``), after k+1's
    launch; the round's last inside its own."""
    b = mk(**PAGED)
    events, span = [], b._span

    class noted:
        def __init__(self, name, **attrs):
            self.name, self.attrs, self.inner = name, attrs, span(name, **attrs)

        def __enter__(self):
            events.append(("open", self.name, self.attrs))
            return self.inner.__enter__()

        def __exit__(self, *exc):
            events.append(("close", self.name, self.attrs))
            return self.inner.__exit__(*exc)

    monkeypatch.setattr(b, "_span", noted)
    launch = b._launch
    monkeypatch.setattr(b, "_launch", lambda prog, *a, **k: (
        events.append(("launch", prog.__name__, {})), launch(prog, *a, **k))[1])
    rids = [b.submit(p, max_new_tokens=n) for p, n in JOBS[:3]]
    b.run()
    round_ = [(what, name.rsplit(".", 1)[-1], attrs.get("rid"),
               attrs.get("fetched_rid"))
              for what, name, attrs in events
              if name.startswith("batcher.admit.") or name == "admit_row_paged"]
    r0, r1, r2 = rids
    assert round_ == [
        ("open", "row", r0, None), ("launch", "admit_row_paged", None, None),
        ("close", "row", r0, None),
        ("open", "row", r1, r0), ("launch", "admit_row_paged", None, None),
        ("open", "wait_device", None, None),
        ("close", "wait_device", None, None), ("close", "row", r1, r0),
        ("open", "row", r2, r1), ("launch", "admit_row_paged", None, None),
        ("open", "wait_device", None, None),
        ("close", "wait_device", None, None),
        ("open", "wait_device", None, None),
        ("close", "wait_device", None, None), ("close", "row", r2, r1)]


# -- a cancel for the admission in flight ------------------------------------


def test_a_cancel_from_the_callback_before_it_takes_the_admission_in_flight():
    """The callback of admission k cancels k+1, which is launched and not
    yet a row: it is taken (True, no token of it delivered, its pages back,
    its slot free for the next round) and the others are served as if it
    had been cancelled in the queue."""
    want = serve(mk(**PAGED), [JOBS[0], JOBS[2]])[0]
    b = mk(**PAGED)
    counted(b)
    rids = [b.submit(p, max_new_tokens=n) for p, n in JOBS[:3]]
    delivered, took = {}, []

    def on_tokens(rid, toks, done, lps):
        delivered.setdefault(rid, []).extend(toks)
        if rid == rids[0] and not took:
            assert b._admit_inflight.req.rid == rids[1]
            took.append(b.cancel_row(rids[1]))
            assert not b.cancel_row(rids[1])        # once

    cancelled = METRICS.get_counter("batcher.cancelled")
    out = b.run(on_tokens=on_tokens)
    assert took == [True] and rids[1] not in delivered
    assert METRICS.get_counter("batcher.cancelled") == cancelled + 1
    assert out[rids[1]] == [] and [out[rids[0]], out[rids[2]]] == want
    assert [delivered[rids[0]], delivered[rids[2]]] == want
    assert all(r.rid is None for r in b.rows)
    b.assert_pool_consistent()
    assert _pool_accounts(b)["held_pages"] == 0


# -- a cancel for the request picked next -------------------------------------


def _cancelling(b, canceller, victims):
    """-> (on_tokens, delivered, took): the first delivery of ``canceller``
    cancels ``victims``."""
    delivered, took = {}, []

    def on_tokens(rid, toks, done, lps):
        delivered.setdefault(rid, []).extend(toks)
        if rid == canceller and not took:
            took.extend(b.cancel_row(v) for v in victims)

    return on_tokens, delivered, took


def _cancel_before_chunked(overlap):
    """Two short prompts, then one long enough to start chunked: the
    callback of the second cancels it."""
    b = mk(overlap=overlap, batch_slots=4, prefill_chunk=16,
           prefix_cache=True, **PAGED)
    rids = [b.submit(p, max_new_tokens=n) for p, n in
            [(ids(5, 1), 9), (ids(9, 4), 7), (ids(30, 2), 6), (ids(4, 3), 5)]]
    return b, rids, rids[1], [rids[2]]


def _cancel_before_swap(overlap):
    """A swap-preempted resume behind two requests of higher priority: the
    callback of the second cancels the parcel's request."""
    b = mk(overlap=overlap, paged_pages=16, page_size=8, prefix_cache=True,
           host_pages=16)
    first = b.submit(ids(12, 1), max_new_tokens=9)
    b._admit_pending()
    b._preempt_row(0, "growth")
    assert b.queue[0].swap_handle is not None
    others = [b.submit(ids(7, s), max_new_tokens=5, priority=3)
              for s in (2, 3)]
    return b, [first] + others, others[1], [first]


def _cancel_under_page_pressure(overlap):
    """A pool of 9 pages, 4 a row: the third reservation finds it dry and
    settles the second admission, whose callback cancels the first (a row:
    its pages come back, so the reservation goes through) AND the third
    (the one being reserved for).  The fourth takes the pages."""
    b = mk(overlap=overlap, batch_slots=4, paged_pages=10, page_size=8)
    rids = [b.submit(ids(n, s), max_new_tokens=6)
            for s, n in enumerate((17, 18, 19, 20))]
    return b, rids, rids[1], [rids[0], rids[2]]


CANCELS = {"chunked-start": _cancel_before_chunked,
           "swap-restore": _cancel_before_swap,
           "page-pressure": _cancel_under_page_pressure}


@pytest.mark.parametrize("exit_", list(CANCELS))
def test_a_cancel_from_the_callback_before_it_takes_the_request_picked_next(
        exit_):
    """The pick that cannot pipeline is made behind the settle, so the
    callback's cancel finds the request in the queue, as in the serial
    order: no token of it, no page, no slot, and the rest served alike."""
    runs = {}
    for overlap in (False, True):
        b, rids, canceller, victims = CANCELS[exit_](overlap)
        counted(b)
        on_tokens, delivered, took = _cancelling(b, canceller, victims)
        swaps = METRICS.get_counter("batcher.kv_swaps.in")
        out = b.run(on_tokens=on_tokens)
        assert took == [True] * len(victims)
        assert victims[-1] not in delivered
        assert METRICS.get_counter("batcher.kv_swaps.in") == swaps
        assert all(r.rid is None for r in b.rows) and not b.queue
        b.assert_pool_consistent()
        runs[overlap] = ([out[r] for r in rids], delivered,
                         _pool_accounts(b), b.preemptions)
        assert _pool_accounts(b)["held_pages"] == 0
        if b.host_tier is not None:
            assert b.host_tier.stats()["swap_parcels"] == 0
    assert runs[True] == runs[False]


# -- the queue wait of an admission launched behind another -------------------


@pytest.mark.parametrize("overlap", [True, False])
def test_a_request_launched_behind_another_leaves_the_queue_at_its_fetch(
        monkeypatch, overlap):
    """On the clock of the starved test, one round of two: the first
    request's wait ends at its selection (2, its reservation), the second's
    when the first's fetch returns (2 + 64 + 2 + 64 + 16), which is when the
    chip takes it up, and not at its launch; serial, after the first's
    activation and its own reservation (2 + 64 + 16 + 4 + 2).  Its admission
    part runs from there to its activation."""
    now = [0.0]
    b = mk(overlap=overlap, batch_slots=2, clock=lambda: now[0], **PAGED)
    _ticking(b, monkeypatch, now, {
        "_alloc_pages": 2.0, "admit_row_paged": 64.0, "device_get": 16.0,
        "_activate_row": 4.0})
    rids = [b.submit(ids(4, seed), max_new_tokens=8) for seed in range(2)]
    wait0 = METRICS.get_histogram("batcher.queue_wait_seconds")
    b._admit_pending()
    wait1 = METRICS.get_histogram("batcher.queue_wait_seconds")
    second = 148.0 if overlap else 88.0
    assert wait1[0] - wait0[0] == 2
    assert wait1[1] - wait0[1] == pytest.approx(2.0 + second)
    tl = b.rows[1].req.timeline
    assert b.rows[1].rid == rids[1] and tl.queue_s == pytest.approx(second)
    # fetch and activation pipelined (16 + 4 behind the first's activation
    # 4); serial its launch too
    assert tl.admit_s == pytest.approx(24.0 if overlap else 84.0)
