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

Thread safety: spills run on the inference executor thread while
matching runs on the event-loop thread, so the index is locked; the
device transfers themselves happen OUTSIDE the lock (they can take
milliseconds, and a transfer must not block a concurrent
``best_match`` scan). ``take`` pops atomically, so two concurrent
readmits of one key cannot double-serve it.

Single-host placement only: the pod mirror's replicated repin gives
its cache entries multi-device shardings that a plain ``device_put``
would collapse, so the pod path does not attach a spill tier.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Set, Tuple

from ..telemetry.goodput import EnginePhases
from .digest import prefix_fingerprint


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


class HostSpillTier:
    """Byte-budgeted host-RAM LRU of evicted KV cache entries."""

    def __init__(self, max_bytes: int) -> None:
        if max_bytes < 1:
            raise ValueError("spill tier max_bytes must be >= 1")
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        #: key -> (host pytree, nbytes)
        self._store: "OrderedDict[Tuple[int, ...], Tuple[Any, int]]" = (
            OrderedDict()
        )
        #: prefix fingerprint -> keys sharing it. A usable reuse
        #: match shares at least MIN_REUSE == FP_TOKENS leading ids,
        #: i.e. the same fingerprint — so the per-request match scan
        #: compares only this bucket (a few collision candidates)
        #: instead of every spilled key, and stays O(device LRU)
        #: however large the host budget grows. Keys too short to
        #: fingerprint can never match >= MIN_REUSE and are not
        #: indexed (PrefixCache doesn't spill them).
        self._by_fp: Dict[int, Set[Tuple[int, ...]]] = {}
        self._bytes = 0
        self.stats = {
            "spilled": 0,       # entries accepted into the tier
            "readmitted": 0,    # entries handed back to the device
            "evicted": 0,       # entries dropped for budget
            "refused": 0,       # entries larger than the whole budget
            "misses": 0,        # take() of a key not (or no longer) here
        }
        #: where the two transfers below are accounted as
        #: ``kvtier.spill`` and ``kvtier.readmit`` with their bytes:
        #: the slot engine's accumulator once a prefix cache under an
        #: engine attached it (PrefixCache.attach_phases), until then
        #: the tier's own
        self.phases = EnginePhases()

    def __len__(self) -> int:
        with self._lock:
            return len(self._store)

    @property
    def bytes_used(self) -> int:
        with self._lock:
            return self._bytes

    def keys(self) -> List[Tuple[int, ...]]:
        """Snapshot of spilled keys, for digest publication (keys are
        immutable tuples; the list is safe to scan lock-free)."""
        with self._lock:
            return list(self._store)

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
        fp = prefix_fingerprint(key)
        bucket = self._by_fp.get(fp)
        if bucket is not None:
            bucket.discard(key)
            if not bucket:
                del self._by_fp[fp]

    def put(self, key: Tuple[int, ...], cache: Any) -> bool:
        """Spill one evicted entry. Returns True when it was
        accepted; False when it exceeds the whole budget (refused)."""
        import jax

        # device -> host OUTSIDE the lock: a multi-ms transfer must
        # not block concurrent match scans
        with self.phases.span("kvtier.spill"):
            host = jax.device_get(cache)
        nbytes = tree_nbytes(host)
        self.phases.spill_bytes += nbytes
        self.phases.latent_spill_bytes += latent_nbytes(host)
        if nbytes > self.max_bytes:
            self.stats["refused"] += 1
            return False
        with self._lock:
            old = self._store.pop(key, None)
            if old is not None:
                self._bytes -= old[1]
            else:
                self._index(key)
            self._store[key] = (host, nbytes)
            self._bytes += nbytes
            while self._bytes > self.max_bytes and self._store:
                evicted, (_, dropped) = self._store.popitem(last=False)
                self._unindex(evicted)
                self._bytes -= dropped
                self.stats["evicted"] += 1
        self.stats["spilled"] += 1
        return True

    def put_host(self, key: Tuple[int, ...], host_tree: Any) -> int:
        """Insert an entry that is ALREADY host-side (a handed-off KV
        prefix rebuilt from the wire — kvtier/handoff.py) without any
        device round-trip. Returns the bytes stored, 0 when refused
        for budget. The entry then readmits through the exact
        ``take``/``reuse_admission`` path a locally-spilled one
        takes, which is what makes handoff byte-parity hold by
        construction."""
        nbytes = tree_nbytes(host_tree)
        if nbytes > self.max_bytes:
            self.stats["refused"] += 1
            return 0
        with self._lock:
            old = self._store.pop(key, None)
            if old is not None:
                self._bytes -= old[1]
            else:
                self._index(key)
            self._store[key] = (host_tree, nbytes)
            self._bytes += nbytes
            while self._bytes > self.max_bytes and self._store:
                evicted, (_, dropped) = self._store.popitem(last=False)
                self._unindex(evicted)
                self._bytes -= dropped
                self.stats["evicted"] += 1
        self.stats["spilled"] += 1
        return nbytes

    def peek(self, key: Tuple[int, ...]) -> Optional[Any]:
        """Non-destructive host-side read for EXPORT (the handoff
        send path): the stored host tree itself, no device ops, no
        LRU movement, the entry stays readmittable. Callers only
        serialize from it (leaves are effectively immutable)."""
        with self._lock:
            entry = self._store.get(key)
            return entry[0] if entry is not None else None

    def take(self, key: Tuple[int, ...]) -> Optional[Any]:
        """Pop one entry and readmit it to the device, or None when
        the key isn't spilled (evicted for budget, never spilled, or
        already taken by a concurrent readmit)."""
        import jax

        with self._lock:
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
        # host -> device outside the lock, same rationale as put()
        with self.phases.span("kvtier.readmit"):
            return jax.device_put(entry[0])

    def snapshot(self) -> Dict[str, int]:
        """Stats + size for surfaces (``/v1/model``)."""
        with self._lock:
            return {
                "max_bytes": self.max_bytes,
                "bytes": self._bytes,
                "entries": len(self._store),
                **self.stats,
            }
