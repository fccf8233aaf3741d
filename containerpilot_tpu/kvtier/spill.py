"""Host-RAM KV spill tier: the floor under the prefix cache's LRU.

A replica's ``PrefixCache`` holds completed prompts' KV caches in
device memory, and device memory is the scarcest resource on the box
— so the LRU is small, and under multi-tenant chat traffic entries
are evicted while their sessions are still alive. Re-prefilling an
evicted prefix costs a full quadratic pass; copying it back from host
RAM costs one ``jax.device_put``. Following the CPU-GPU-coupled
characterization (PAPERS.md), this tier keeps evicted entries in host
memory instead of dropping them:

- **Spill**: on LRU eviction the cache dict (a pytree of device
  arrays) is fetched to host numpy (``jax.device_get``) and stored in
  a byte-budgeted OrderedDict LRU of its own. Entries larger than the
  whole budget are refused (counted), and inserts evict
  least-recently-used spilled entries until the budget holds.
- **Readmit**: ``take()`` pops the host copy and ``jax.device_put``\\ s
  it back. The roundtrip is byte-exact — device_get/device_put
  preserve dtype and contents bit-for-bit — so the rewind+extend
  reuse path and its byte-parity test discipline are untouched; the
  readmitted entry re-enters the device LRU as most-recently-used.
- **Deferred spill**: the slot engine's admission does not wait for
  that fetch. ``defer()`` only hands the evicted row to the tier's
  one worker thread (OS name ``kv-spill``), which runs the very
  ``jax.device_get`` that ``put()`` runs, in eviction order, and then
  inserts the host copy. A row in flight is still in the cache:
  ``keys``/``candidates`` list it, ``take`` hands back its device
  arrays themselves (they never left; no ``device_put``) exactly once
  and the copy's result is thrown away, ``peek`` waits for it to
  land. A pending row keeps its device memory alive, so at most
  ``MAX_IN_FLIGHT`` rows are in flight: at the limit ``defer`` waits
  for a landing (timed and counted as back-pressure), it never drops
  a row and never queues without bound. A failing copy costs that
  one row (``failed``); the worker lives on. ``flush()`` waits until
  nothing is pending, for whoever reads the tier's state for good.

Thread safety: rows are handed over on the slot engine's thread,
copied out on the ``kv-spill`` worker, matched on the event loop's
threads and exported on its executor's, so the index and the pending
rows are locked; the device transfers themselves happen OUTSIDE the
lock (they take tens of milliseconds, and a transfer must not block a
concurrent ``best_match`` scan). ``take`` pops atomically, so two
concurrent readmits of one key cannot double-serve it, pending or
landed. The counters in ``phases`` have one writer each: the worker
(or a direct ``put``'s caller) the spill's, the taker the readmit's.

Single-host placement only: the pod mirror's replicated repin gives
its cache entries multi-device shardings that a plain ``device_put``
would collapse, so the pod path does not attach a spill tier.
"""
from __future__ import annotations

import logging
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Deque, Dict, List, Optional, Set, Tuple

from ..telemetry.goodput import EnginePhases, name_os_thread
from .digest import prefix_fingerprint

log = logging.getLogger("containerpilot.kvtier.spill")

#: rows handed to the worker and not yet landed. Each keeps its device
#: memory alive, so the limit is small and fixed: a copy takes ~60 ms
#: and admissions come further apart than that, so the second place is
#: already slack
MAX_IN_FLIGHT = 2


def tree_nbytes(host_tree: Any) -> int:
    """Total bytes of a pytree's array leaves (host or device: an
    array knows its size without a transfer)."""
    import jax

    return sum(
        int(getattr(leaf, "nbytes", 0))
        for leaf in jax.tree_util.tree_leaves(host_tree)
    )


def latent_nbytes(tree: Any) -> int:
    """The bytes of a cache entry that are latent rows: its ``ckv``
    and ``kpe`` leaves (models/mla_moe.py); 0 for any other tree."""
    if not isinstance(tree, dict):
        return 0
    return sum(tree_nbytes(tree[name])
               for name in ("ckv", "kpe") if name in tree)


class _Pending:
    """One row between ``defer`` and its landing."""

    __slots__ = ("key", "cache", "landed")

    def __init__(self, key: Tuple[int, ...], cache: Any,
                 landed: Optional[Callable[[bool], None]]) -> None:
        self.key = key
        #: the device tree; None once the row landed, or a ``take`` (or
        #: a newer row of the key) got here first: the copy, if it
        #: still runs, is then thrown away
        self.cache = cache
        #: called on the worker with whether the tier accepted the row
        self.landed = landed


class HostSpillTier:
    """Byte-budgeted host-RAM LRU of evicted KV cache entries."""

    def __init__(self, max_bytes: int) -> None:
        if max_bytes < 1:
            raise ValueError("spill tier max_bytes must be >= 1")
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        #: signalled at every landing: wakes ``defer`` at the limit,
        #: ``peek`` of a pending key, ``flush``, and the idle worker
        self._changed = threading.Condition(self._lock)
        #: key -> (host pytree, nbytes)
        self._store: "OrderedDict[Tuple[int, ...], Tuple[Any, int]]" = (
            OrderedDict()
        )
        #: key -> its row in flight: what ``MAX_IN_FLIGHT`` bounds
        #: (``take`` pops it: the device LRU holds that row again)
        self._pending: Dict[Tuple[int, ...], _Pending] = {}
        #: the rows handed over, in eviction order, until the worker
        #: is done with each (a taken row keeps its place, and is
        #: skipped when the worker reaches it)
        self._queue: Deque[_Pending] = deque()
        self._worker: Optional[threading.Thread] = None
        #: prefix fingerprint -> keys sharing it, landed or pending. A
        #: usable reuse match shares at least MIN_REUSE == FP_TOKENS
        #: leading ids, i.e. the same fingerprint — so the per-request
        #: match scan compares only this bucket (a few collision
        #: candidates) instead of every spilled key, and stays
        #: O(device LRU) however large the host budget grows. Keys too
        #: short to fingerprint can never match >= MIN_REUSE and are
        #: not indexed (PrefixCache doesn't spill them).
        self._by_fp: Dict[int, Set[Tuple[int, ...]]] = {}
        self._bytes = 0
        self.stats = {
            "spilled": 0,       # entries accepted into the tier
            "readmitted": 0,    # entries handed back by a device_put
            "evicted": 0,       # entries dropped for budget
            "refused": 0,       # entries larger than the whole budget
            "misses": 0,        # take() of a key not (or no longer) here
            "deferred": 0,      # rows handed to the worker (defer)
            "pending_hits": 0,  # take() served from a row in flight
            "failed": 0,        # rows lost to a failing copy
            # the handing thread made to wait at MAX_IN_FLIGHT
            "backpressure_n": 0,
            "backpressure_s": 0.0,
        }
        #: where the two transfers below are accounted as
        #: ``kvtier.spill`` and ``kvtier.readmit`` with their bytes:
        #: the slot engine's accumulator once a prefix cache under an
        #: engine attached it (PrefixCache.attach_phases), until then
        #: the tier's own
        self.phases = EnginePhases()

    def __len__(self) -> int:
        """Entries a ``take`` would find: landed and in flight."""
        with self._lock:
            return len(self._store) + len(self._pending)

    @property
    def bytes_used(self) -> int:
        """Host bytes held (a row in flight holds none yet)."""
        with self._lock:
            return self._bytes

    def keys(self) -> List[Tuple[int, ...]]:
        """Snapshot of spilled keys, landed then in flight, for digest
        publication (keys are immutable tuples; the list is safe to
        scan lock-free)."""
        with self._lock:
            return list(self._store) + [
                k for k in self._pending if k not in self._store
            ]

    def candidates(
        self, fp: Optional[int]
    ) -> List[Tuple[int, ...]]:
        """Spilled keys that could match a row with prefix
        fingerprint ``fp`` at >= MIN_REUSE tokens (same-fingerprint
        bucket; collisions cost one exact compare, never a wrong
        answer). None — a row too short to fingerprint — can't reach
        the reuse floor at all."""
        if fp is None:
            return []
        with self._lock:
            bucket = self._by_fp.get(fp)
            return list(bucket) if bucket else []

    def _index(self, key: Tuple[int, ...]) -> None:
        fp = prefix_fingerprint(key)
        if fp is not None:
            self._by_fp.setdefault(fp, set()).add(key)

    def _unindex(self, key: Tuple[int, ...]) -> None:
        """Drop ``key`` from its bucket once neither a landed nor a
        pending row holds it."""
        if key in self._store or key in self._pending:
            return
        fp = prefix_fingerprint(key)
        bucket = self._by_fp.get(fp)
        if bucket is not None:
            bucket.discard(key)
            if not bucket:
                del self._by_fp[fp]

    def _fetch(self, cache: Any) -> Tuple[Any, int]:
        """device -> host, OUTSIDE the lock: a multi-ms transfer must
        not block concurrent match scans. The one copy both ``put``
        and the worker run: the whole tree in one ``device_get``,
        which starts every leaf's transfer before it waits for the
        first (fetched leaf by leaf the same row took half as long
        again on the v5e, and the tier fell behind the evictions:
        PERF.md section 6, PR 32)."""
        import jax

        with self.phases.span("kvtier.spill"):
            host = jax.device_get(cache)
        return host, tree_nbytes(host)

    def _insert(self, key: Tuple[int, ...], host: Any, nbytes: int,
                copied: bool) -> bool:
        """Make ``host`` the tier's entry for ``key`` as its
        most-recently-used and evict for budget; False when it is
        larger than the whole budget (refused). ``copied`` says the
        bytes came over the bus here (not ``put_host``'s) and count
        as ``spill_bytes``. Call with the lock held."""
        if copied:
            self.phases.spill_bytes += nbytes
            self.phases.latent_spill_bytes += latent_nbytes(host)
        if nbytes > self.max_bytes:
            self.stats["refused"] += 1
            return False
        self._supersede(key)
        old = self._store.pop(key, None)
        if old is not None:
            self._bytes -= old[1]
        self._store[key] = (host, nbytes)
        self._index(key)
        self._bytes += nbytes
        while self._bytes > self.max_bytes and self._store:
            evicted, (_, dropped) = self._store.popitem(last=False)
            self._unindex(evicted)
            self._bytes -= dropped
            self.stats["evicted"] += 1
        self.stats["spilled"] += 1
        return True

    def _supersede(self, key: Tuple[int, ...]) -> None:
        """A newer row of ``key`` arrives while an older one is still
        in flight: the older is let go of, as if taken."""
        stale = self._pending.pop(key, None)
        if stale is not None:
            stale.cache = None

    def put(self, key: Tuple[int, ...], cache: Any) -> bool:
        """Spill one evicted entry on the calling thread. Returns True
        when it was accepted; False when it exceeds the whole budget
        (refused)."""
        host, nbytes = self._fetch(cache)
        with self._lock:
            return self._insert(key, host, nbytes, copied=True)

    def put_host(self, key: Tuple[int, ...], host_tree: Any) -> int:
        """Insert an entry that is ALREADY host-side (a handed-off KV
        prefix rebuilt from the wire — kvtier/handoff.py) without any
        device round-trip. Returns the bytes stored, 0 when refused
        for budget. The entry then readmits through the exact
        ``take``/``reuse_admission`` path a locally-spilled one
        takes, which is what makes handoff byte-parity hold by
        construction."""
        nbytes = tree_nbytes(host_tree)
        with self._lock:
            accepted = self._insert(key, host_tree, nbytes, copied=False)
        return nbytes if accepted else 0

    def defer(self, key: Tuple[int, ...], cache: Any,
              landed: Optional[Callable[[bool], None]] = None) -> None:
        """Hand one evicted entry to the ``kv-spill`` worker and
        return without waiting for its transfer; from here on the key
        is found as a landed one is. ``landed(accepted)`` is called on
        the worker once the row is in the tier (or refused, or its
        copy failed). With ``MAX_IN_FLIGHT`` rows in flight this WAITS
        for a landing first (``backpressure_n``/``backpressure_s``).

        Books: a row that lands counts as ``put`` counts it. A row
        taken while pending counts as ``pending_hits`` alone: it is
        neither ``spilled`` nor ``readmitted`` and adds no bytes to
        ``spill_bytes`` or ``readmit_bytes`` (its copy, if it ran,
        leaves only its seconds in ``kvtier.spill``), so after a
        ``flush`` ``spill_bytes == bytes_used + readmit_bytes`` holds
        as it does for ``put``."""
        row = _Pending(key, cache, landed)
        with self._changed:
            if len(self._pending) >= MAX_IN_FLIGHT:
                t0 = time.perf_counter()
                while len(self._pending) >= MAX_IN_FLIGHT:
                    self._changed.wait()
                self.stats["backpressure_n"] += 1
                self.stats["backpressure_s"] += time.perf_counter() - t0
            self._supersede(key)
            self._pending[key] = row
            self._index(key)
            self._queue.append(row)
            self.stats["deferred"] += 1
            if self._worker is None:
                self._worker = threading.Thread(
                    target=self._land_rows, name="kv-spill", daemon=True
                )
                self._worker.start()
            self._changed.notify_all()

    def _land_rows(self) -> None:
        """The worker: copy out and insert the rows in flight, oldest
        first, for the tier's life."""
        # the profiler names this thread's line by its OS name, taken
        # at the thread's first event: before any jax call
        name_os_thread("kv-spill")
        while True:
            with self._changed:
                while not self._queue:
                    self._changed.wait()
                row = self._queue[0]
                cache = row.cache
            host = None
            if cache is not None:
                try:
                    host, nbytes = self._fetch(cache)
                except Exception:  # noqa: BLE001 — costs the row, not the worker
                    log.exception("kv spill of a %d-token prefix failed; "
                                  "dropping the entry", len(row.key))
            accepted = False
            with self._changed:
                cache = row.cache = None  # the device memory goes free here
                # not so for a row taken, or replaced, in the meantime
                landing = self._pending.get(row.key) is row
                if landing:
                    del self._pending[row.key]
                    if host is None:
                        self.stats["failed"] += 1
                    else:
                        accepted = self._insert(
                            row.key, host, nbytes, copied=True
                        )
                    if not accepted:
                        self._unindex(row.key)
                    self._changed.notify_all()
            if landing and row.landed is not None:
                try:
                    row.landed(accepted)
                except Exception:  # noqa: BLE001
                    log.exception("kv spill landing callback failed")
            # the place in the queue is given up last: a flush that
            # returns has seen the callback's books too
            with self._changed:
                self._queue.popleft()
                self._changed.notify_all()

    def flush(self, timeout: Optional[float] = None) -> bool:
        """Wait until the worker is done with every row handed over;
        False when ``timeout`` seconds did not suffice."""
        with self._changed:
            return self._changed.wait_for(
                lambda: not self._queue, timeout
            )

    def peek(self, key: Tuple[int, ...]) -> Optional[Any]:
        """Non-destructive host-side read for EXPORT (the handoff
        send path): the stored host tree itself, no device ops, no
        LRU movement, the entry stays readmittable; a key in flight
        is waited for. Callers only serialize from it (leaves are
        effectively immutable)."""
        with self._changed:
            self._changed.wait_for(lambda: key not in self._pending)
            entry = self._store.get(key)
            return entry[0] if entry is not None else None

    def take(self, key: Tuple[int, ...]) -> Optional[Any]:
        """Pop one entry and readmit it to the device, or None when
        the key isn't spilled (evicted for budget, never spilled, or
        already taken by a concurrent readmit). A key in flight is
        served its device arrays themselves."""
        import jax

        with self._changed:
            row = self._pending.pop(key, None)
            if row is not None:
                cache, row.cache = row.cache, None
                self.stats["pending_hits"] += 1
                # an older landed row of the key goes with it, as this
                # one's landing would have replaced it: one take, one row
                old = self._store.pop(key, None)
                if old is not None:
                    self._bytes -= old[1]
                self._unindex(key)
                self._changed.notify_all()  # a place in flight is free
                return cache
            entry = self._store.pop(key, None)
            if entry is not None:
                self._bytes -= entry[1]
                self._unindex(key)
        if entry is None:
            self.stats["misses"] += 1
            return None
        self.stats["readmitted"] += 1
        self.phases.readmit_bytes += entry[1]
        self.phases.latent_readmit_bytes += latent_nbytes(entry[0])
        # host -> device outside the lock, same rationale as _fetch()
        with self.phases.span("kvtier.readmit"):
            return jax.device_put(entry[0])

    def snapshot(self) -> Dict[str, Any]:
        """Stats + size for surfaces (``/v1/model``). ``deferred`` is
        ``pending`` (rows in flight now) plus the rows that landed
        (``spilled`` less ``put``'s and ``put_host``'s, or
        ``refused``), were taken first (``pending_hits``), were
        replaced by a newer row of their key, or ``failed``."""
        with self._lock:
            return {
                "max_bytes": self.max_bytes,
                "bytes": self._bytes,
                "entries": len(self._store),
                "pending": len(self._pending),
                **self.stats,
                "backpressure_s": round(self.stats["backpressure_s"], 6),
            }
