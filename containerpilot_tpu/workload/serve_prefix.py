"""Prefix KV reuse for the inference server.

Completed prompts' KV caches, keyed by their token tuple, LRU-bounded.
An admission to the slot engine reuses the longest common prefix and
only prefills the (bucketed) suffix — the chat/agent regime where
every turn re-sends a long shared history.

Thread safety: the digest, the KV export/pull verbs and the drain
migration read the cache from the event loop's executor threads while
the store/evict side runs on the slot engine's thread and an evicted
row lands in the spill tier on the tier's ``kv-spill`` thread, so
every OrderedDict access holds ``_lock`` (round-2 review: a concurrent
request could previously hit "OrderedDict mutated during iteration"
and surface as a 500), and so does the one stat two threads write
(``spill_bytes``).

With a **spill tier** attached (``kvtier.HostSpillTier``), LRU
eviction moves the entry's KV to byte-budgeted host RAM instead of
dropping it, and a later match readmits it through the SAME
``get``/``reuse_admission`` path — the slot engines and the rewind+
extend protocol never see the difference, only the stats do
(``spilled``/``readmitted``/``spill_bytes``, zeroed when the tier is
disabled so the ``/v1/model`` schema stays stable either way). The
eviction only HANDS the row to the tier (``HostSpillTier.defer``): the
copy to the host runs behind the engine's back, the row is matched and
readmitted at every instant in between, and ``spilled`` counts it when
it lands. ``flush()`` waits for the rows in flight.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Any, List, Optional, Tuple

# import-light by design (no jax): just the fingerprint/codec helpers
from ..kvtier import digest as kvdigest
from ..kvtier.spill import latent_nbytes, tree_nbytes
from ..telemetry.goodput import EnginePhases

#: shorter matches aren't worth a device call. Tied to the digest's
#: FP_TOKENS BY CONSTRUCTION: the spill tier indexes keys by their
#: first-FP_TOKENS fingerprint, and that bucket lookup finds every
#: >= MIN_REUSE match only while FP_TOKENS <= MIN_REUSE — tune the
#: floor in kvtier/digest.py, not by breaking the tie here
MIN_REUSE = kvdigest.FP_TOKENS
BUCKET = 16      # suffix lengths compile in these steps


class PrefixCache:
    def __init__(self, entries: int, spill: Optional[Any] = None) -> None:
        self.entries = entries
        #: optional kvtier.HostSpillTier catching LRU evictions
        self.spill = spill
        self._cache: "OrderedDict[Tuple[int, ...], Any]" = OrderedDict()
        self._lock = threading.Lock()
        self.stats = {
            "hits": 0, "misses": 0, "tokens_reused": 0,
            # spill-tier accounting; stays zeroed when no tier is
            # attached so the /v1/model schema is identical either way
            "spilled": 0, "readmitted": 0, "spill_bytes": 0,
        }
        #: seconds the LAST admission spent readmitting from spill —
        #: reset/read by the slot engines around reuse_admission to
        #: stamp the trace's ``kv`` stage (single inference thread per
        #: engine, so a plain float is race-free in practice)
        self.readmit_seconds = 0.0
        #: bumped on any contents change; versions the published
        #: digest so readers can tell fresh from stale
        self.version = 0
        self._digest_memo: Tuple[int, str] = (-1, "")
        # stores add their bytes, and the tier its transfers, to the
        # slot engine's phase accumulator once an engine attaches it;
        # until then to one of the cache's own
        self.attach_phases(EnginePhases())

    def attach_phases(self, phases: Any) -> None:
        """Account this cache's stores, and its spill tier's
        transfers, in the engine's phase accumulator."""
        self.phases = phases
        if self.spill is not None:
            self.spill.phases = phases

    def __len__(self) -> int:
        with self._lock:
            return len(self._cache)

    def best_match(
        self, row: List[int]
    ) -> Tuple[int, Optional[Tuple[int, ...]]]:
        """Longest common prefix over device-resident AND spilled
        keys. Device keys scan first, so on equal match length the
        cheaper (no-readmit) base wins. The spill tier is consulted
        by fingerprint bucket, not scanned: a usable (>= MIN_REUSE)
        match shares the row's first-MIN_REUSE ids, so only
        same-fingerprint keys can qualify — the scan stays O(device
        LRU) however large the host budget grows."""
        with self._lock:
            keys: List[Tuple[int, ...]] = list(self._cache)
        if self.spill is not None:
            keys.extend(
                self.spill.candidates(
                    kvdigest.prefix_fingerprint(row)
                )
            )
        best_len, best_key = 0, None
        for stored in keys:
            n = min(len(stored), len(row))
            i = 0
            while i < n and stored[i] == row[i]:
                i += 1
            if i > best_len:
                best_len, best_key = i, stored
        return best_len, best_key

    def get(self, key: Tuple[int, ...]) -> Optional[Any]:
        """Fetch a stored cache and mark it most-recently-used,
        readmitting from the spill tier when the device LRU evicted
        it. Returns None if it is gone from both tiers (evicted
        between match and fetch)."""
        with self._lock:
            cache = self._cache.get(key)
            if cache is not None:
                self._cache.move_to_end(key)
                return cache
        if self.spill is None:
            return None
        t0 = time.monotonic()
        cache = self.spill.take(key)
        if cache is None:
            return None
        # back from the tier: by a device_put, or the device arrays
        # themselves where the row was still in flight
        self.stats["readmitted"] += 1
        self.readmit_seconds += time.monotonic() - t0
        # back into the device LRU as MRU (which may spill another
        # entry in turn); the caller sees a plain device-tier hit
        self.store(key, cache)
        return cache

    def device_entry(self, key: Tuple[int, ...]) -> Optional[Any]:
        """The device-tier entry for ``key``, untouched: no readmit,
        no MRU bump — the handoff EXPORT path's read (a fresh
        prefill's entry lives here, and serializing it for a peer
        must not disturb LRU order or the spill tier)."""
        with self._lock:
            return self._cache.get(key)

    def adopt_host(self, key: Tuple[int, ...], host_tree: Any) -> int:
        """Inject a handed-off HOST-side entry (kvtier/handoff.py)
        into the spill tier and republish the digest. Returns the
        bytes adopted, 0 without a spill tier or when the budget
        refuses it. The entry readmits through the SAME
        ``get``/``reuse_admission`` path a locally-spilled one takes
        — which is what makes handoff byte-parity hold by
        construction."""
        if self.spill is None:
            return 0
        adopted = self.spill.put_host(key, host_tree)
        if adopted:
            self._tier_changed()
        return adopted

    def _tier_changed(self) -> None:
        """The spill tier's contents moved: republish the digest and
        the tier's size (read and written under the lock: the engine's
        thread and the ``kv-spill`` thread both come here)."""
        with self._lock:
            self.version += 1
            self.stats["spill_bytes"] = self.spill.bytes_used

    def _spill_landed(self, accepted: bool) -> None:
        """On the ``kv-spill`` thread, once a deferred row is in the
        tier (or was refused, or its copy failed)."""
        if accepted:
            self.stats["spilled"] += 1
        self._tier_changed()

    def flush(self, timeout: Optional[float] = None) -> bool:
        """Wait until no evicted row is still on its way to the host
        (``HostSpillTier.flush``); True without a tier."""
        return self.spill is None or self.spill.flush(timeout)

    def store(self, key: Tuple[int, ...], cache: Any) -> None:
        self.phases.store_bytes += tree_nbytes(cache)
        self.phases.latent_store_bytes += latent_nbytes(cache)
        evicted: List[Tuple[Tuple[int, ...], Any]] = []
        with self._lock:
            self._cache[key] = cache
            self._cache.move_to_end(key)
            while len(self._cache) > self.entries:
                evicted.append(self._cache.popitem(last=False))
            self.version += 1
        if self.spill is None:
            return
        for k, c in evicted:
            if len(k) < MIN_REUSE:
                # below the reuse floor it can never match again —
                # not worth the host RAM or the transfer
                continue
            # hand-over only: device->host happens on the tier's
            # thread, and the key stays matchable all the while
            self.spill.defer(k, c, self._spill_landed)
        # a take() before this store, or the hand-over, moved the tier
        self._tier_changed()

    def export_keys(self) -> List[Tuple[int, ...]]:
        """Every migratable prompt key this cache holds, device tier
        first in MRU order, then spilled keys — the drain-migration
        enumeration (kvtier.plan_migration's input). Read-only: no
        MRU bump, no readmit, nothing below the reuse floor (it can
        never match again, so it is not worth moving)."""
        with self._lock:
            keys = list(reversed(self._cache))
        if self.spill is not None:
            seen = set(keys)
            keys.extend(
                k for k in self.spill.keys() if k not in seen
            )
        return [k for k in keys if len(k) >= MIN_REUSE]

    def digest(self, max_bytes: Optional[int] = None) -> str:
        """Versioned fingerprint digest of every reusable prefix this
        cache holds (device + spill tiers), for gateway routing —
        memoized per version, so steady state costs a tuple compare."""
        version = self.version
        memo_version, memo = self._digest_memo
        if memo_version == version:
            return memo
        with self._lock:
            keys = list(self._cache)
        if self.spill is not None:
            keys.extend(self.spill.keys())
        fps = []
        for key in keys:
            fp = kvdigest.prefix_fingerprint(key)
            if fp is not None:
                fps.append(fp)
        encoded = kvdigest.encode_fingerprints(
            version, fps, max_bytes or kvdigest.DIGEST_MAX_BYTES
        )
        self._digest_memo = (version, encoded)
        return encoded


def plan_reuse(pc: "PrefixCache", row: List[int], quantum: int = 1):
    """The ONE reuse plan the slot engines' admissions apply:
    longest cached match, suffix
    bucketed (a little of the matched prefix re-prefills so jit
    compiles one extend program per BUCKET, not per suffix length).
    ``quantum`` (a configuration's ``reuse_quantum``) is the block a
    cached position's keys depend on: the reuse is cut down to a
    multiple of it, and since it never passes the match, only whole
    matched blocks are reused.
    Returns (reuse_len, base_cache_or_None); counts a miss when no
    usable base exists."""
    plen = len(row)
    best_len, best_key = pc.best_match(row)
    reuse = 0
    if best_len >= MIN_REUSE:
        suffix = plen - best_len
        bucket = max(1, -(-suffix // BUCKET) * BUCKET) if suffix > 0 else 1
        reuse = plen - min(bucket, plen)
        reuse -= reuse % quantum
    base = pc.get(best_key) if reuse > 0 and best_key is not None else None
    return (reuse, base) if base is not None else (0, None)


def reuse_admission(pc: "PrefixCache", row_tokens: List[int], cfg,
                    params, chunk_len: int = 0):
    """The ONE admission-side reuse protocol both slot engines apply
    (workload/serve_slots.py and the pod's serve_dist mirror): plan
    the reuse, rewind the cached base (same arrays, earlier pos),
    extend the bucketed suffix — in bounded pieces when ``chunk_len``
    says the configured activation bound applies — and count the
    hit/miss stats. Returns (logits, cache) on a hit, None on a miss.
    Callers store the completed prompt's cache afterwards (with any
    placement transform of their own, e.g. the pod's replicated
    repin)."""
    import jax.numpy as jnp

    from ..models.decode import _jitted_extend, extend_pieces

    reuse, base = plan_reuse(
        pc, row_tokens, getattr(cfg, "reuse_quantum", 1))
    if base is None:
        pc.stats["misses"] += 1
        return None
    cache = {**base, "pos": jnp.asarray(reuse, jnp.int32)}
    suffix = jnp.asarray([row_tokens[reuse:]], jnp.int32)
    if chunk_len > 0 and suffix.shape[1] > chunk_len:
        # a huge cached-hit suffix honors the SAME O(chunk)
        # activation bound as a cold prompt
        logits, cache = extend_pieces(
            params, cache, suffix, cfg, chunk_len
        )
    else:
        logits, cache = _jitted_extend(cfg)(params, cache, suffix)
    pc.stats["hits"] += 1
    pc.stats["tokens_reused"] += reuse
    return logits, cache
