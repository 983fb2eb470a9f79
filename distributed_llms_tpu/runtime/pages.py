"""The host side of the KV page pool: which page holds what, and who holds
it.  :class:`PagePool` is the refcounted allocator of paged mode (with the
audit the serving supervisor runs after an engine restart),
:class:`PrefixCache` the content-addressed index of automatic prefix
caching.  Host bookkeeping only: no JAX is imported, so the router and the
gateway take ``PrefixCache.page_digests`` from here without an engine.
How a page is stored on the device is models/kv_cache.py's; when pages
move is the batcher's.
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict

import numpy as np

from ..core.observability import METRICS
from .kv_tier import HostTier


class PrefixCache:
    """Content-addressed index of pool pages for AUTOMATIC prefix caching
    (vLLM/SGLang-style): every FULL page of an admitted prompt is keyed by
    a chained content digest (a page's digest commits to every token before
    it, so equal digests mean equal full prefixes), and later admissions
    reuse the longest cached page-run copy-free through their page tables.

    Ownership model: refcounts live with the batcher's pool allocator; this
    class only maps digests <-> pages and keeps the LRU of UNREFERENCED
    pages whose cached content is still resident — those are reclaimable
    (evicted oldest-first under pool pressure) but serve hits until then.
    Stats are cumulative per batcher and mirrored into the process-wide
    METRICS registry (gateway /metrics)."""

    def __init__(self) -> None:
        self.by_hash: dict[bytes, int] = {}
        self.page_hash: dict[int, bytes] = {}
        self.lru: OrderedDict[int, None] = OrderedDict()  # oldest first
        self.hit_tokens = 0
        self.miss_tokens = 0
        self.lookups = 0
        self.hits = 0
        self.evictions = 0

    @staticmethod
    def page_digests(ids: list[int], page_size: int, n_pages: int,
                     kv_bits: int = 16) -> list[bytes]:
        """Chained blake2b digests of the first ``n_pages`` full pages:
        digest_i = H(digest_{i-1} || tokens of page i).  ``kv_bits`` salts
        the chain seed: a page's stored bytes are a deterministic function
        of (token prefix, kv width), so folding the width into the digest
        keeps sharing content-addressed over the QUANTIZED bytes — an int8
        page can never alias a bf16 page (locally, across a handoff, or in
        router affinity), while all default-width digests stay unchanged."""
        digests: list[bytes] = []
        prev = (b"dlt-prefix-cache-v1" if kv_bits == 16
                else b"dlt-prefix-cache-v1:kv%d" % kv_bits)
        # ONE token-id conversion for the whole prompt, sliced per page —
        # the old per-page np.asarray paid a fresh list->array
        # materialization inside every blake2b update; the chain bytes
        # are identical (tests/runtime/test_overlap.py pins equality
        # against the per-page construction).
        flat = np.asarray(ids[: n_pages * page_size], np.int64)
        for i in range(n_pages):
            h = hashlib.blake2b(prev, digest_size=16)
            h.update(flat[i * page_size: (i + 1) * page_size].tobytes())
            prev = h.digest()
            digests.append(prev)
        return digests

    def match(self, digests: list[bytes]) -> list[int]:
        """Pages of the longest cached run from the start (maybe empty)."""
        pages: list[int] = []
        for d in digests:
            p = self.by_hash.get(d)
            if p is None:
                break
            pages.append(p)
        return pages

    def register(self, page: int, digest: bytes) -> None:
        """Publish ``page`` as the holder of ``digest``.  First writer wins:
        if another page already holds this content, the new page stays
        private (it frees normally when its row releases it)."""
        if digest not in self.by_hash:
            self.by_hash[digest] = page
            self.page_hash[page] = digest

    def forget(self, page: int) -> None:
        """Drop a page's cache entry (eviction): its content is no longer
        addressable and the page returns to plain-allocator life."""
        d = self.page_hash.pop(page, None)
        if d is not None:
            self.by_hash.pop(d, None)
        self.lru.pop(page, None)

    def record_lookup(self, hit_tokens: int, miss_tokens: int) -> None:
        self.lookups += 1
        self.hits += hit_tokens > 0
        self.hit_tokens += hit_tokens
        self.miss_tokens += miss_tokens
        METRICS.inc("batcher.prefix_cache.lookups")
        if hit_tokens > 0:
            METRICS.inc("batcher.prefix_cache.hits")
        METRICS.inc("batcher.prefix_cache.hit_tokens", hit_tokens)
        METRICS.inc("batcher.prefix_cache.miss_tokens", miss_tokens)
        total = self.hit_tokens + self.miss_tokens
        if total:
            METRICS.set_gauge(
                "batcher.prefix_cache.hit_rate", self.hit_tokens / total
            )



class PagePool:
    """Refcounted KV page allocator for paged mode.  Owns the free list and
    per-page refcounts, and cooperates with an optional :class:`PrefixCache`
    whose LRU parks unreferenced-but-content-cached pages (still serving
    hits, reclaimable under pressure).  Page 0 is the permanent scratch
    page: never allocated, never freed, never read by a live row.

    Extracted from the batcher so the invariants have one owner and one
    audit (:meth:`assert_consistent`) — the recovery path's leak class
    (dangling refcounts / pinned cache pages after a crashed ``run``) is
    exactly a violation of these invariants, and the serving supervisor
    runs the audit after every engine restart."""

    def __init__(self, num_pages: int,
                 prefix_cache: "PrefixCache | None" = None,
                 host_tier: "HostTier | None" = None) -> None:
        self.num_pages = num_pages
        # Optional host-RAM tier BEHIND the pool (KV tiering): the batcher
        # spills eviction candidates into it before alloc reclaims them,
        # and swap-preemption parks whole rows there.  The pool itself
        # only audits and reports it — all data movement is the batcher's
        # (device calls never run under the allocator lock).
        self.host_tier = host_tier
        # Allocator lock: mutation happens on the engine thread, but the
        # occupancy view (stats/publish_gauges behind /metrics, the
        # supervisor's audit) reads from the serving loop thread — PR 3
        # published those gauges off GIL-atomic len() reads, the pattern
        # graftlint's GL101 now rejects.  The PrefixCache LRU is covered by
        # THIS lock too: every lru mutation goes through alloc/retain/
        # release (engine thread), every cross-thread read through stats().
        self._lock = threading.Lock()
        self.free_pages: list[int] = list(range(1, num_pages))  # guarded-by: self._lock
        # Refcounts of allocated pages (prefix-cache hits share pages
        # across rows; a page returns to free/LRU only at refcount 0).
        self.page_refs: dict[int, int] = {}  # guarded-by: self._lock
        self.prefix_cache = prefix_cache
        # Watermarks: the least headroom an admission has ever seen and the
        # most pages rows have ever held at once — the two numbers that say
        # whether a production pool is sized right (a min_available of 0
        # means admissions back-pressured or preempted; a peak_held far
        # under num_pages means the pool is over-provisioned).
        self.min_available = num_pages - 1  # guarded-by: self._lock
        self.peak_held = 0  # guarded-by: self._lock

    # graftlint: holds(self._lock)
    def _note_watermarks(self) -> None:
        avail = self._available_locked()
        if avail < self.min_available:
            self.min_available = avail
        held = len(self.page_refs)
        if held > self.peak_held:
            self.peak_held = held

    def stats(self) -> dict[str, int]:
        """Occupancy snapshot: every usable page is exactly one of free /
        LRU-cached / row-held (the partition assert_consistent audits).
        Safe from any thread (the /metrics scrape path)."""
        pc = self.prefix_cache
        with self._lock:
            return {
                "total_pages": self.num_pages - 1,  # page 0 is scratch
                "free_pages": len(self.free_pages),
                "cached_pages": len(pc.lru) if pc is not None else 0,
                "held_pages": len(self.page_refs),
                "min_available": self.min_available,
                "peak_held": self.peak_held,
            }

    def publish_gauges(self) -> None:
        """Mirror the occupancy view into the process-wide METRICS registry
        (rendered as batcher_pool_* on the gateway's /metrics); the host
        tier's occupancy rides along as batcher_host_tier_*."""
        METRICS.set_gauges({
            f"batcher.pool.{k}": float(v) for k, v in self.stats().items()
        })
        if self.host_tier is not None:
            METRICS.set_gauges({
                f"batcher.host_tier.{k}": float(v)
                for k, v in self.host_tier.stats().items()
            })

    def eviction_candidates(self, n: int) -> list[tuple[int, bytes]]:
        """The (page, digest) pairs :meth:`alloc`\\ (n) would evict from
        the LRU, oldest first — the spill plane reads these BEFORE the
        alloc so their content can move to the host tier.  Engine thread
        only: nothing may mutate the pool between this and the alloc."""
        pc = self.prefix_cache
        with self._lock:
            if pc is None:
                return []
            m = max(0, n - len(self.free_pages))
            out: list[tuple[int, bytes]] = []
            for p in pc.lru:
                if len(out) >= m:
                    break
                out.append((p, pc.page_hash[p]))
            return out

    # graftlint: holds(self._lock)
    def _available_locked(self) -> int:
        pc = self.prefix_cache
        return len(self.free_pages) + (len(pc.lru) if pc else 0)

    def available(self) -> int:
        """Pages an admission could obtain: the free list plus every
        LRU-parked cached page (reclaimable under pressure)."""
        with self._lock:
            return self._available_locked()

    def alloc(self, n: int) -> list[int]:
        """Allocate ``n`` pages at refcount 1, evicting LRU-cold cached
        pages when the free list runs dry (the caller checked
        :meth:`available` first)."""
        pc = self.prefix_cache
        out: list[int] = []
        with self._lock:
            for _ in range(n):
                if self.free_pages:
                    p = self.free_pages.pop()
                else:
                    p, _ = pc.lru.popitem(last=False)  # the coldest entry
                    pc.forget(p)
                    pc.evictions += 1
                    METRICS.inc("batcher.prefix_cache.evicted_pages")
                self.page_refs[p] = 1
                out.append(p)
            self._note_watermarks()
        return out

    def retain(self, p: int) -> None:
        """Take a reference on a cached page (a prefix-cache hit): pages
        referenced by live rows bump their refcount; LRU-parked ones come
        back referenced (their content stays addressable)."""
        with self._lock:
            if p in self.page_refs:
                self.page_refs[p] += 1
            else:
                del self.prefix_cache.lru[p]
                self.page_refs[p] = 1
            self._note_watermarks()

    def release(self, pages: list[int]) -> None:
        """Drop one reference per page.  At refcount 0 a content-cached
        page parks at the LRU's most-recently-used end — still serving
        hits until pool pressure reclaims it — while an uncached page
        returns straight to the free list."""
        pc = self.prefix_cache
        with self._lock:
            for p in pages:
                left = self.page_refs[p] - 1
                if left:
                    self.page_refs[p] = left
                    continue
                del self.page_refs[p]
                if pc is not None and p in pc.page_hash:
                    pc.lru[p] = None
                else:
                    self.free_pages.append(p)

    def publish_prefix(self, page: int, digest: bytes) -> None:
        """Publish a page's cached content (:meth:`PrefixCache.register`)
        under the allocator lock: the hash maps are engine-thread-written,
        but :meth:`assert_consistent` snapshots them from any thread —
        every cross-thread-visible PrefixCache mutation rides this lock
        (``forget`` runs inside the locked :meth:`alloc`)."""
        with self._lock:
            self.prefix_cache.register(page, digest)

    def assert_consistent(self, live_rows=(), swap_handles=()) -> None:
        """Audit the allocator's partition invariants; AssertionError on
        the first violation.  ``live_rows`` is the page lists of currently
        resident rows — every reference comes from exactly one row hold,
        so per-page refcounts must EQUAL the row-hold counts (a dangling
        ref or a pinned cache page after a crashed run fails here).
        With a host tier attached the audit extends across tiers:
        ``swap_handles`` is the swap handles of queued resume requests,
        and every parked parcel must be owned by exactly one of them
        (:meth:`HostTier.assert_consistent`) — a stranded handle is the
        host-RAM analogue of a dangling refcount.
        Takes one consistent snapshot under the allocator lock; callable
        from any thread."""
        if self.host_tier is not None:
            self.host_tier.assert_consistent(swap_handles)
        pc = self.prefix_cache
        with self._lock:
            lru = set(pc.lru) if pc is not None else set()
            hashed = set(pc.page_hash) if pc is not None else set()
            free_list = list(self.free_pages)
            refs = dict(self.page_refs)
        free = set(free_list)
        refed = set(refs)
        assert len(free) == len(free_list), (
            f"free list holds duplicates: {sorted(free_list)}"
        )
        assert 0 not in (free | refed | lru), "scratch page 0 escaped the pool"
        for a, b, what in ((free, refed, "free and refcounted"),
                           (free, lru, "free and LRU-parked"),
                           (refed, lru, "refcounted and LRU-parked")):
            assert not (a & b), f"pages both {what}: {sorted(a & b)}"
        accounted = free | refed | lru
        expect = set(range(1, self.num_pages))
        assert accounted == expect, (
            f"pages leaked (neither free, refcounted, nor LRU-parked): "
            f"{sorted(expect - accounted)}; "
            f"foreign pages: {sorted(accounted - expect)}"
        )
        assert all(v >= 1 for v in refs.values()), (
            f"non-positive refcounts: {refs}"
        )
        holds: dict[int, int] = {}
        for pages in live_rows:
            for p in pages:
                holds[p] = holds.get(p, 0) + 1
        assert holds == refs, (
            f"refcounts diverge from live-row holds: refs={refs} "
            f"holds={holds}"
        )
        for p in lru:
            assert p in hashed, (
                f"LRU-parked page {p} has no cached content"
            )
