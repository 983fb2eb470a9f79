"""Host-RAM KV page tier (``--host-pages``) behind the paged pool.

Extracted from runtime/batcher.py (PR 9 introduced it inline; the round-16
scheduler extraction moved it here): the tier is STORAGE mechanism — swap
parcels for preemption victims and spilled prefix-cache pages, with a
single-worker D2H pipeline and checksum verification — while batcher.py
keeps the batching mechanism and runtime/scheduler.py the policy.  See
:class:`HostTier` for the contract; tests/runtime/test_kv_tiering.py pins
it.
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any

import numpy as np

from ..core.observability import METRICS, get_logger

log = get_logger("kv_tier")


# Machine-readable transition system for host-tier parcel ownership —
# the contract ``park_swap`` / ``take_swap`` / ``drop_swap`` and the
# spill plane implement, declared next to the code it models
# (PROTOCOL_MODELS["kv.parcels"], runtime/faults.py).  ``python -m
# tools.graftmodel`` explores every interleaving of three swap parcels
# and two spill slots over a shared page budget under the declared
# kv.swap_out / kv.swap_in / kv.spill fault actions, checking GM2 on
# every reachable state: a parked parcel is owned by exactly one queued
# resume, a settled parcel by none, and the budget equals the parked
# bytes exactly (released even when verification fails).  Swap phases:
# 0 victim about to swap, 1 parked (owned), 2 restored byte-exact,
# 3 degraded to exact recompute, 4 dropped (cancel/shed).  Spill
# phases: 0 cold pages ahead of eviction, 1 spilled, 2 restored,
# 3 evicted (plain eviction — correct, just slower).
PARCEL_MODEL = {
    "name": "kv.parcels",
    "doc": "host-tier swap/spill parcels: exactly-one-owner while "
           "parked, budget conserved, verify failure degrades to "
           "exact recompute",
    "params": {"PAGES": 2},
    "state": {"w0": 0, "w1": 0, "w2": 0, "own0": 0, "own1": 0, "own2": 0,
              "bad0": 0, "bad1": 0,
              "s0": 0, "s1": 0, "sbad0": 0, "sbad1": 0, "used": 0},
    "actions": [
        {"name": "park0", "guard": "w0 == 0 and used < PAGES",
         "update": {"w0": "1", "own0": "own0 + 1", "used": "used + 1"}},
        {"name": "park1", "guard": "w1 == 0 and used < PAGES",
         "update": {"w1": "1", "own1": "own1 + 1", "used": "used + 1"}},
        {"name": "park2", "guard": "w2 == 0 and used < PAGES",
         "update": {"w2": "1", "own2": "own2 + 1", "used": "used + 1"}},
        # Budget dry: park_swap returns None and the victim recomputes.
        {"name": "park_dry0", "guard": "w0 == 0 and used >= PAGES",
         "update": {"w0": "3"}},
        {"name": "park_dry1", "guard": "w1 == 0 and used >= PAGES",
         "update": {"w1": "3"}},
        {"name": "park_dry2", "guard": "w2 == 0 and used >= PAGES",
         "update": {"w2": "3"}},
        {"name": "take_ok0", "guard": "w0 == 1 and own0 == 1 and bad0 == 0",
         "update": {"w0": "2", "own0": "own0 - 1", "used": "used - 1"}},
        {"name": "take_ok1", "guard": "w1 == 1 and own1 == 1 and bad1 == 0",
         "update": {"w1": "2", "own1": "own1 - 1", "used": "used - 1"}},
        # Parcel 2 carries no fault edges: the plain path, kept in the
        # composition so faulted and clean parcels interleave.
        {"name": "take_ok2", "guard": "w2 == 1 and own2 == 1",
         "update": {"w2": "2", "own2": "own2 - 1", "used": "used - 1"}},
        # Checksum verify fails at take time: budget released anyway,
        # the request recomputes exactly.
        {"name": "take_bad0", "guard": "w0 == 1 and own0 == 1 and bad0 == 1",
         "update": {"w0": "3", "own0": "own0 - 1", "used": "used - 1",
                    "bad0": "0"}},
        {"name": "take_bad1", "guard": "w1 == 1 and own1 == 1 and bad1 == 1",
         "update": {"w1": "3", "own1": "own1 - 1", "used": "used - 1",
                    "bad1": "0"}},
        # Cancel/shed: drop_swap frees the parcel and its budget.
        {"name": "cancel0", "guard": "w0 == 1 and own0 == 1",
         "update": {"w0": "4", "own0": "own0 - 1", "used": "used - 1"}},
        {"name": "cancel1", "guard": "w1 == 1 and own1 == 1",
         "update": {"w1": "4", "own1": "own1 - 1", "used": "used - 1"}},
        {"name": "spill0", "guard": "s0 == 0 and used < PAGES",
         "update": {"s0": "1", "used": "used + 1"}},
        {"name": "spill1", "guard": "s1 == 0 and used < PAGES",
         "update": {"s1": "1", "used": "used + 1"}},
        # Spills are best-effort cache: evictable any time swaps need
        # room (oldest-first in code; order-free here).
        {"name": "spill_evict0", "guard": "s0 == 1 and sbad0 == 0",
         "update": {"s0": "3", "used": "used - 1"}},
        {"name": "spill_evict1", "guard": "s1 == 1 and sbad1 == 0",
         "update": {"s1": "3", "used": "used - 1"}},
        {"name": "spill_restore0", "guard": "s0 == 1 and sbad0 == 0",
         "update": {"s0": "2", "used": "used - 1"}},
        {"name": "spill_restore1", "guard": "s1 == 1 and sbad1 == 0",
         "update": {"s1": "2", "used": "used - 1"}},
        # Restore verification rejects a corrupt spill: cold prefill.
        {"name": "spill_restore_bad0", "guard": "s0 == 1 and sbad0 == 1",
         "update": {"s0": "3", "used": "used - 1", "sbad0": "0"}},
        {"name": "spill_restore_bad1", "guard": "s1 == 1 and sbad1 == 1",
         "update": {"s1": "3", "used": "used - 1", "sbad1": "0"}},
    ],
    "faults": [
        {"name": "swapout_drop0", "site": "kv.swap_out", "action": "drop",
         "metric": "batcher.kv_swaps.fallback",
         "guard": "w0 == 0", "update": {"w0": "3"}},
        {"name": "swapout_drop1", "site": "kv.swap_out", "action": "drop",
         "metric": "batcher.kv_swaps.fallback",
         "guard": "w1 == 0", "update": {"w1": "3"}},
        {"name": "swapout_corrupt0", "site": "kv.swap_out",
         "action": "corrupt", "metric": "batcher.kv_swaps.fallback",
         "guard": "w0 == 1 and bad0 == 0", "update": {"bad0": "1"}},
        {"name": "swapout_corrupt1", "site": "kv.swap_out",
         "action": "corrupt", "metric": "batcher.kv_swaps.fallback",
         "guard": "w1 == 1 and bad1 == 0", "update": {"bad1": "1"}},
        {"name": "swapin_drop0", "site": "kv.swap_in", "action": "drop",
         "metric": "batcher.kv_swaps.fallback",
         "guard": "w0 == 1 and own0 == 1",
         "update": {"w0": "3", "own0": "own0 - 1", "used": "used - 1"}},
        {"name": "swapin_drop1", "site": "kv.swap_in", "action": "drop",
         "metric": "batcher.kv_swaps.fallback",
         "guard": "w1 == 1 and own1 == 1",
         "update": {"w1": "3", "own1": "own1 - 1", "used": "used - 1"}},
        {"name": "swapin_corrupt0", "site": "kv.swap_in", "action": "corrupt",
         "metric": "batcher.kv_swaps.fallback",
         "guard": "w0 == 1 and bad0 == 0", "update": {"bad0": "1"}},
        {"name": "swapin_corrupt1", "site": "kv.swap_in", "action": "corrupt",
         "metric": "batcher.kv_swaps.fallback",
         "guard": "w1 == 1 and bad1 == 0", "update": {"bad1": "1"}},
        {"name": "spill_drop0", "site": "kv.spill", "action": "drop",
         "metric": "batcher.host_tier.spill_evictions",
         "guard": "s0 == 0", "update": {"s0": "3"}},
        {"name": "spill_drop1", "site": "kv.spill", "action": "drop",
         "metric": "batcher.host_tier.spill_evictions",
         "guard": "s1 == 0", "update": {"s1": "3"}},
        {"name": "spill_corrupt0", "site": "kv.spill", "action": "corrupt",
         "metric": "batcher.kv_swaps.fallback",
         "guard": "s0 == 1 and sbad0 == 0", "update": {"sbad0": "1"}},
        {"name": "spill_corrupt1", "site": "kv.spill", "action": "corrupt",
         "metric": "batcher.kv_swaps.fallback",
         "guard": "s1 == 1 and sbad1 == 0", "update": {"sbad1": "1"}},
    ],
    "invariants": [
        {"rule": "GM2", "name": "parked-implies-exactly-one-owner",
         "expr": "(w0 != 1 or own0 == 1) and (w1 != 1 or own1 == 1) "
                 "and (w2 != 1 or own2 == 1)"},
        {"rule": "GM2", "name": "settled-implies-zero-owners",
         "expr": "(w0 == 1 or own0 == 0) and (w1 == 1 or own1 == 0) "
                 "and (w2 == 1 or own2 == 0)"},
        {"rule": "GM2", "name": "never-multi-owned",
         "expr": "own0 <= 1 and own1 <= 1 and own2 <= 1"},
        {"rule": "GM2", "name": "budget-equals-parked-bytes",
         "expr": "used == (w0 == 1) + (w1 == 1) + (w2 == 1) "
                 "+ (s0 == 1) + (s1 == 1)"},
        {"rule": "GM2", "name": "budget-never-oversubscribed",
         "expr": "0 <= used <= PAGES"},
    ],
    # Stuck only once every parcel settled (restored / recomputed /
    # dropped) and every spill slot resolved (restored / evicted) —
    # a parcel parked forever with no owner is a stranded parcel.
    "terminal": "w0 in (2, 3, 4) and w1 in (2, 3, 4) and w2 in (2, 3, 4) "
                "and s0 in (2, 3) and s1 in (2, 3)",
}


@dataclass
class _HostEntry:
    """One host-tier parcel: ``future`` resolves (on the tier's worker
    thread) to ``(arrays, checksum)`` — an INDEPENDENT host-numpy copy of
    a raw page export plus its blake2b checksum.  Swap parcels hold a
    whole row (``index`` None); a spill entry holds exactly one page
    (``index`` records which slice of the gathered stack it copied out —
    every entry owns its own bytes, so eviction frees them)."""

    n_pages: int
    future: Any
    index: int | None = None


class HostTier:
    """Host-RAM KV page tier behind the :class:`PagePool` (``--host-pages``).

    Two kinds of parcels, one page budget:

    - **swap parcels**: a preempted row's pages, raw pool bytes, keyed by
      an opaque handle carried on the requeued request — restore scatters
      them back instead of recomputing the prefix;
    - **spilled pages**: cold prefix-cache pages captured just before LRU
      eviction, keyed by content digest — a later cache hit restores them
      instead of re-prefilling.

    Swaps outrank spills: parking a swap may evict spilled pages (they are
    only a cache), never the other way.  Device-to-host copies and
    checksumming run on a single worker thread (``park_*`` merely submits
    the already-dispatched device gather), so the engine loop never blocks
    on a D2H transfer at preemption time; ``take_*`` joins the future and
    VERIFIES the checksum — a corrupted parcel degrades to exact recompute
    / cold prefill rather than poisoning the cache.

    Thread contract: park/take/drop run under ``_lock`` (engine thread,
    plus the serving thread's cancel path); the worker thread touches only
    its own future's payload."""

    def __init__(self, pages: int) -> None:
        if pages < 1:
            raise ValueError(f"host tier needs >= 1 page, got {pages}")
        self.pages = pages
        self._lock = threading.Lock()
        # graftflow: cleanup-required
        self._swaps: dict[int, _HostEntry] = {}  # guarded-by: self._lock
        self._spills: OrderedDict[bytes, _HostEntry] = OrderedDict()  # guarded-by: self._lock
        self.used = 0  # guarded-by: self._lock
        self._next_handle = 0  # guarded-by: self._lock
        self._workers = None  # lazy single-thread executor

    # graftlint: holds(self._lock)
    def _executor(self):
        if self._workers is None:
            import concurrent.futures

            self._workers = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="kv-host-tier"
            )
        return self._workers

    @staticmethod
    def _checksum(arrays) -> bytes:
        h = hashlib.blake2b(digest_size=16)
        for a in arrays:
            h.update(np.ascontiguousarray(a).tobytes())
        return h.digest()

    @staticmethod
    def _flip_byte(arrays) -> tuple:
        """Corrupt a parcel in host storage (the ``corrupt`` fault drill):
        flip the first byte of the first array — checksum verification at
        take time must catch it."""
        raw = bytearray(np.ascontiguousarray(arrays[0]).tobytes())
        raw[0] ^= 0xFF
        bad = np.frombuffer(bytes(raw), dtype=arrays[0].dtype).reshape(
            arrays[0].shape
        )
        return (bad,) + tuple(arrays[1:])

    @classmethod
    def _to_host(cls, payload, corrupt: bool):
        """WORKER THREAD: device arrays -> host numpy + checksum.  The
        np.asarray calls are the actual D2H transfers."""
        arrays = tuple(np.asarray(a) for a in payload)
        checksum = cls._checksum(arrays)
        if corrupt:
            arrays = cls._flip_byte(arrays)
        return arrays, checksum

    @classmethod
    def _to_host_page(cls, payload, i: int, corrupt: bool):
        """WORKER THREAD: spill variant — ONE page's slices copied out
        independently (np.ascontiguousarray detaches from the stacked
        gather), so each spill entry owns exactly its own bytes: evicting
        it frees them, and the `pages` budget really bounds host RAM."""
        arrays = tuple(
            np.ascontiguousarray(np.asarray(a[:, i])) for a in payload
        )
        checksum = cls._checksum(arrays)
        if corrupt:
            arrays = cls._flip_byte(arrays)
        return arrays, checksum

    # graftlint: holds(self._lock)
    def _fit_locked(self, n: int) -> bool:
        """Make room for ``n`` pages, evicting spilled pages (oldest
        first) if needed — spills are only a cache.  Swap parcels are
        never evicted: their content is the ONLY copy of a live request's
        KV."""
        while self.pages - self.used < n and self._spills:
            self._spills.popitem(last=False)
            self.used -= 1
            METRICS.inc("batcher.host_tier.spill_evictions")
        return self.pages - self.used >= n

    def can_fit(self, n: int) -> bool:
        """Whether ``n`` pages could be parked right now (spills count as
        evictable).  Engine-thread advisory — the authoritative check is
        park's own."""
        with self._lock:
            return self.pages - self.used + len(self._spills) >= n

    def park_swap(self, payload, n_pages: int,
                  corrupt: bool = False) -> int | None:
        """Park a preempted row's raw page export; returns the handle the
        resume request carries, or None when the budget cannot fit it
        (the caller falls back to exact recompute)."""
        with self._lock:
            if not self._fit_locked(n_pages):
                return None
            fut = self._executor().submit(self._to_host, payload, corrupt)
            handle = self._next_handle
            self._next_handle += 1
            self.used += n_pages
            self._swaps[handle] = _HostEntry(n_pages, fut)
        return handle

    def take_swap(self, handle: int, corrupt: bool = False):
        """Resolve and REMOVE a swap parcel: returns the raw page arrays,
        or None when the handle is unknown or the checksum fails (the
        caller falls back to exact recompute either way).  Budget is
        released even on verification failure — the parcel is gone."""
        with self._lock:
            entry = self._swaps.pop(handle, None)
            if entry is None:
                return None
            self.used -= entry.n_pages
        try:
            arrays, checksum = entry.future.result()
        except Exception:
            # A failed D2H (host OOM, device error surfacing on the copy)
            # must degrade to exact recompute, not crash the engine —
            # the same contract as a checksum mismatch.
            log.exception("host-tier swap parcel %d copy failed", handle)
            return None
        if corrupt:
            arrays = self._flip_byte(arrays)
        if self._checksum(arrays) != checksum:
            log.warning("host-tier swap parcel %d failed verification", handle)
            return None
        return arrays

    def drop_swap(self, handle: int) -> None:
        """Free a swap parcel whose request will never resume (cancelled
        or shed while queued)."""
        with self._lock:
            entry = self._swaps.pop(handle, None)
            if entry is not None:
                self.used -= entry.n_pages

    def park_spill(self, digests: list[bytes], payload,
                   corrupt: bool = False) -> int:
        """Park soon-to-be-evicted cached pages (stacked raw export, one
        digest per page).  Best-effort: parks the prefix that fits after
        evicting older spills; returns how many pages were parked.  Each
        page gets its OWN worker task and host copy (never a shared
        stack), so the budget bounds actual host bytes: evicting an
        entry frees its pages."""
        with self._lock:
            room = 0
            for _ in digests:
                if not self._fit_locked(1):
                    break
                self.used += 1
                room += 1
            for i, d in enumerate(digests[:room]):
                fut = self._executor().submit(
                    self._to_host_page, payload, i, corrupt and i == 0
                )
                # Re-spilling content already parked would double-count
                # its budget page: drop the stale entry (its budget page
                # transfers to the fresh one reserved above).
                if d in self._spills:
                    self._spills.pop(d)
                    self.used -= 1
                self._spills[d] = _HostEntry(1, fut, index=i)
        return room

    def has_spill(self, digest: bytes) -> bool:
        with self._lock:
            return digest in self._spills

    def take_spill(self, digest: bytes):
        """Resolve and REMOVE one spilled page: returns its raw arrays
        ([L, BLK, ...] slices), or None when absent or corrupted (the
        caller prefillls cold — correct, just slower)."""
        with self._lock:
            entry = self._spills.pop(digest, None)
            if entry is None:
                return None
            self.used -= 1
        try:
            page, checksum = entry.future.result()
        except Exception:
            log.exception("host-tier spilled page copy failed")
            return None
        if self._checksum(page) != checksum:
            log.warning("host-tier spilled page failed verification")
            return None
        return page

    def stats(self) -> dict[str, int]:
        # Key names become batcher.host_tier.* GAUGES on /metrics
        # (publish_gauges): none may collide with a same-named counter —
        # "spill_entries" here vs the "spilled_pages" cumulative counter,
        # or the exposition renders one series under two TYPEs and the
        # whole scrape fails to parse.
        with self._lock:
            return {
                "pages": self.pages,
                "used": self.used,
                "swap_parcels": len(self._swaps),
                "spill_entries": len(self._spills),
            }

    def assert_consistent(self, swap_handles=()) -> None:
        """Audit the tier: budget accounting must equal the parcels held,
        and every parked swap handle must be owned by exactly one queued
        resume request (``swap_handles``) — a handle nobody will ever
        restore or free is a host-RAM leak, the tier's analogue of the
        pool's dangling refcount."""
        with self._lock:
            swaps = {h: e.n_pages for h, e in self._swaps.items()}
            spills = len(self._spills)
            used = self.used
        expect = set(swap_handles)
        held = set(swaps)
        assert used == sum(swaps.values()) + spills, (
            f"host tier budget diverged: used={used}, swaps={swaps}, "
            f"spilled={spills}"
        )
        assert used <= self.pages, (
            f"host tier over budget: {used} > {self.pages}"
        )
        assert held == expect, (
            f"host-tier swap handles diverge from queued resume requests: "
            f"parked={sorted(held)} expected={sorted(expect)}"
        )

