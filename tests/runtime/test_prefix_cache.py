"""Automatic prefix caching: hash-block KV reuse in the paged pool
(runtime/pages.py PrefixCache + refcounted page allocator).

Invariants pinned here:
- exact tokens: at temperature 0 every request served with the automatic
  prefix cache ON — hit or miss — equals its solo generate_tokens run
  (extends tests/runtime/test_paged_batcher.py's pinned invariant);
- refcounting: a page shared by live rows is never freed or rewritten
  while any of them reads it; page accounting is conserved;
- LRU: unreferenced cached pages persist (later requests hit them) and
  are evicted oldest-first only under pool pressure;
- accounting: hit/miss/eviction counters (batcher-local and the METRICS
  registry the gateway exports at /metrics) say what actually happened;
- plumbing: per-request opt-out, the engine/config knob, and the named
  register_prefix path coexisting with the automatic cache.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_llms_tpu.core.observability import METRICS
from distributed_llms_tpu.models import model as model_lib, presets
from distributed_llms_tpu.runtime import generate as gen_lib
from distributed_llms_tpu.runtime.batcher import ContinuousBatcher


@pytest.fixture(scope="module")
def tiny():
    cfg = presets.get_preset("llama-tiny", vocab_size=512)
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


def solo(cfg, params, ids, n_new):
    out = gen_lib.generate_tokens(
        params, cfg, jnp.asarray([ids], jnp.int32),
        jnp.asarray([len(ids)], jnp.int32), jax.random.key(9),
        max_new_tokens=n_new,
    )
    return np.asarray(out)[0].tolist()


def _cached(cfg, params, **kw):
    kw.setdefault("batch_slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("chunk_steps", 4)
    kw.setdefault("page_size", 16)
    kw.setdefault("paged_pages", 16)
    kw.setdefault("prefix_cache", True)
    return ContinuousBatcher(cfg, params, **kw)


SHARED = list(np.random.RandomState(7).randint(1, 500, size=40))


def test_cache_hits_match_solo_and_count_honestly(tiny):
    """Shared-prefix traffic: later requests hit the first one's full
    prompt pages, prefill only their suffix, and still emit exactly their
    solo tokens; the counters record per-token hits/misses."""
    cfg, params = tiny
    reqs = [
        (SHARED + [7, 1, 9], 6),
        (SHARED + [4, 4], 5),
        (SHARED + [9, 9, 9, 9], 4),
        ([3, 2, 1], 5),  # unrelated: pure miss
    ]
    b = _cached(cfg, params, paged_pages=24)
    rids = [b.submit(ids, max_new_tokens=n) for ids, n in reqs]
    res = b.run()
    for rid, (ids, n) in zip(rids, reqs):
        assert res[rid] == solo(cfg, params, ids, n), f"req {rid} diverged"
    pc = b.prefix_cache
    # 40-token shared prefix at page 16 -> 2 full pages (32 tokens) are
    # cacheable; requests 2 and 3 hit them.
    assert pc.lookups == 4 and pc.hits == 2
    assert pc.hit_tokens == 64
    assert b.prefix_cached_tokens[rids[1]] == 32
    assert b.prefix_cached_tokens[rids[3]] == 0

    # After the batch drains, the cached pages park in the LRU (not the
    # free list) and a second wave still hits them.
    assert len(pc.lru) > 0 and not b.page_refs
    rid2 = b.submit(SHARED + [5, 5], max_new_tokens=4)
    res2 = b.run()
    assert res2[rid2] == solo(cfg, params, SHARED + [5, 5], 4)
    assert pc.hit_tokens == 96


def test_refcount_never_frees_a_live_page(tiny):
    """Two live rows share cached pages; one finishing must not free them
    (the other still reads them through its page table), and total page
    accounting is conserved at every step."""
    cfg, params = tiny
    b = _cached(cfg, params, paged_pages=24, batch_slots=2)
    n_usable = 23  # pages 1..23; page 0 is scratch

    def accounted():
        lru = len(b.prefix_cache.lru)
        held = len(b.page_refs)
        free = len(b.free_pages)
        assert free + lru + held == n_usable, (free, lru, held)

    r1 = b.submit(SHARED + [7, 1, 9], max_new_tokens=12)
    r2 = b.submit(SHARED + [4, 4], max_new_tokens=2)
    b._admit_pending()  # both admit this round; row 2 hits row 1's pages
    accounted()
    shared_pages = [p for p, r in b.page_refs.items() if r == 2]
    assert len(shared_pages) == 2, "rows do not share the prefix pages"
    assert set(shared_pages) <= set(b.tables[0]) & set(b.tables[1])

    checked = {}

    def cb(rid, new, done, lps):
        # on_tokens fires between device chunks — the documented safe
        # point to inspect batcher state.  When the SHORT row finishes
        # (budget 2 vs 12, so first), the long row still reads the shared
        # pages: they must stay referenced, never on the free list.
        accounted()
        if done and rid == r2:
            for p in shared_pages:
                assert p not in b.free_pages
                assert b.page_refs.get(p) == 1
            checked["r2_done_first"] = True

    res = b.run(on_tokens=cb)
    assert checked.get("r2_done_first"), "short row did not finish first"
    assert res[r1] == solo(cfg, params, SHARED + [7, 1, 9], 12)
    assert res[r2] == solo(cfg, params, SHARED + [4, 4], 2)
    accounted()
    assert not b.page_refs  # everything released; cached pages in the LRU
    # The full allocator audit (partition + refcount-vs-row-holds) agrees.
    b.assert_pool_consistent()


def test_lru_eviction_under_pool_pressure(tiny):
    """A pool too small to keep every cached page resident evicts the
    coldest entries (counted) instead of back-pressuring admission, and
    serving stays exact throughout."""
    cfg, params = tiny
    b = _cached(cfg, params, paged_pages=5, batch_slots=1)
    p1 = list(np.random.RandomState(1).randint(1, 500, size=40))
    p2 = list(np.random.RandomState(2).randint(1, 500, size=40))
    r1 = b.submit(p1, max_new_tokens=4)
    assert b.run()[r1] == solo(cfg, params, p1, 4)
    assert len(b.prefix_cache.lru) == 2 and b.prefix_cache.evictions == 0
    # p2 needs 3 pages; only 2 are free -> the coldest cached page goes.
    r2 = b.submit(p2, max_new_tokens=4)
    assert b.run()[r2] == solo(cfg, params, p2, 4)
    assert b.prefix_cache.evictions >= 1
    # The evicted digest is gone; hash map and LRU stay consistent.
    pc = b.prefix_cache
    assert set(pc.by_hash.values()) == set(pc.page_hash)
    assert set(pc.lru) <= set(pc.page_hash)
    # p1 again: partially evicted prefix still serves exact tokens.
    r3 = b.submit(p1, max_new_tokens=4)
    assert b.run()[r3] == solo(cfg, params, p1, 4)


def test_per_request_optout_and_metrics_export(tiny):
    """prefix_cache=False skips both lookup and publication; the METRICS
    registry (what the gateway's /metrics renders) mirrors the batcher's
    own counters."""
    cfg, params = tiny
    before = METRICS.snapshot()["counters"]
    b = _cached(cfg, params, paged_pages=24)
    ids = SHARED + [1, 2, 3]
    r1 = b.submit(ids, max_new_tokens=4, prefix_cache=False)
    assert b.run()[r1] == solo(cfg, params, ids, 4)
    pc = b.prefix_cache
    assert pc.lookups == 0 and not pc.by_hash  # nothing published either
    assert b.prefix_cached_tokens[r1] == 0
    # Opted-in traffic populates and hits as usual.
    r2 = b.submit(ids, max_new_tokens=4)
    r3 = b.submit(ids, max_new_tokens=4)
    res = b.run()
    assert res[r2] == res[r3] == solo(cfg, params, ids, 4)
    assert pc.lookups == 2 and pc.hits == 1 and pc.hit_tokens == 32
    after = METRICS.snapshot()
    delta = lambda k: after["counters"].get(k, 0) - before.get(k, 0)  # noqa: E731
    assert delta("batcher.prefix_cache.lookups") == 2
    assert delta("batcher.prefix_cache.hits") == 1
    assert delta("batcher.prefix_cache.hit_tokens") == 32
    assert "batcher.prefix_cache.hit_rate" in after["gauges"]
    # The Prometheus rendering the gateway serves includes the family.
    assert "batcher_prefix_cache_hit_tokens" in METRICS.prometheus_text()


def test_named_prefix_and_sampling_compose(tiny):
    """register_prefix requests keep the legacy contiguous-prefix path on
    a cache-enabled batcher, and per-request sampled rows admit through
    the hit path without disturbing greedy neighbors."""
    cfg, params = tiny
    b = _cached(cfg, params, paged_pages=24)
    b.register_prefix("sys", SHARED[:10])
    r_named = b.submit([6, 6, 6], max_new_tokens=5, prefix="sys")
    r_seed = b.submit(SHARED + [8], max_new_tokens=4)
    res = b.run()
    assert res[r_named] == solo(cfg, params, SHARED[:10] + [6, 6, 6], 5)
    assert res[r_seed] == solo(cfg, params, SHARED + [8], 4)
    # A hot-sampled request admits through the cache-hit path; the greedy
    # neighbor submitted alongside stays exact.
    r_hot = b.submit(SHARED + [2, 2], max_new_tokens=5, temperature=1.5,
                     top_p=0.9)
    r_cold = b.submit(SHARED + [3, 3], max_new_tokens=5)
    res = b.run()
    assert len(res[r_hot]) == 5
    assert res[r_cold] == solo(cfg, params, SHARED + [3, 3], 5)
    assert b.prefix_cached_tokens[r_hot] == 32


def test_guards_and_engine_config_plumbing(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="paged"):
        ContinuousBatcher(cfg, params, max_len=64, prefix_cache=True)
    with pytest.raises(ValueError, match="prefix_cache"):
        b = _cached(cfg, params)
        b.submit([1, 2], max_new_tokens=2, prefix_cache="yes")

    from distributed_llms_tpu.core.config import RuntimeConfig
    from distributed_llms_tpu.runtime.engine import InferenceEngine

    rt = RuntimeConfig(max_seq_len=64, paged_pages=16, page_size=16,
                       prefix_cache=True)
    eng = InferenceEngine(cfg, rt, params)
    b = eng.continuous_batcher(batch_slots=2)
    assert b.prefix_cache is not None
    r1 = b.submit(SHARED + [5], max_new_tokens=3)
    r2 = b.submit(SHARED + [6], max_new_tokens=3)
    res = b.run()
    assert res[r1] == solo(cfg, params, SHARED + [5], 3)
    assert res[r2] == solo(cfg, params, SHARED + [6], 3)
    assert b.prefix_cache.hit_tokens == 32

    # Explicit request without a paged pool errors; a config-inherited
    # flag on a contiguous engine degrades silently (shared configs must
    # not error contiguous workers).
    rt_contig = RuntimeConfig(max_seq_len=64, prefix_cache=True)
    eng2 = InferenceEngine(cfg, rt_contig, params)
    assert eng2.continuous_batcher(batch_slots=2).prefix_cache is None
    with pytest.raises(ValueError, match="paged"):
        eng2.continuous_batcher(batch_slots=2, prefix_cache=True)
