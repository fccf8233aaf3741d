"""Warm-standby pool + peer weight transfer: scale-up as *promotion*.

PR 12's ledger put numbers on the cold-start tax: a scale event pays
process boot + weight init + XLA compile (measured 0.4-5.4s
time-to-first-routed-token on the lab box) before the new replica
serves its first token — which is why ``burst_10x`` survives by
shedding, not by growing. This module collapses that tax from three
directions, composed:

- **Warm-standby pool** (``StandbyLauncher``): the autoscaler keeps
  ``standby_count`` replicas fully booted — weights loaded,
  warmup-compiled, registered in the catalog under the ``standby``
  role (heartbeating, never routed to; the gateway excludes them from
  ``_pick`` and admission capacity). A scale event *promotes* one
  (``POST /v3/standby/promote`` flips the role and ``/health``
  semantics in one assignment) instead of launching, and the pool is
  refilled in the background with equal-jitter backoff. Kill-repair
  rides the same path: the autoscaler's below-min relaunch goes
  through ``launch()``, which promotes when a standby is warm.
- **Peer weight transfer over cp-mux/1** (``fetch_params``): a fresh
  standby fetches model weights from an already-warm peer replica as
  a framed mux stream (``GET /v1/weights``) — digest-verified chunks,
  resume-at-chunk-boundary with ONE transparent redial per the pool's
  stale-connection discipline — instead of re-reading a checkpoint or
  re-initializing. ANY failure (declined upgrade, digest mismatch,
  second connection death, shape mismatch) returns None and the
  caller falls back to its disk/init load: transfer is an
  accelerator, never a new failure mode.
- **Shared compile cache** (workload/modelcfg.py): every launch on
  a host resolves the same XLA compile-cache dir
  (``JAX_COMPILATION_CACHE_DIR``, else the checkout's fixed one) and
  skips warmup buckets its marker already carries, so
  ``compile_warmup`` seconds collapse release-over-release; replicas
  advertise the dir and the marker's digest through heartbeat notes
  (``cc=``). The marker helpers live in modelcfg next to
  ``enable_compile_cache``; this module only defines the roles and
  the transfer wire.

Wire format for ``GET /v1/weights`` (one close-delimited stream,
preferably carried as a cp-mux/1 stream so the transfer interleaves
with the peer's live traffic):

    u64 manifest_len | manifest JSON | chunk bytes back-to-back

The manifest names every leaf (flattened in ``jax.tree_util`` order:
path, dtype, shape, byte length) and every chunk (owning leaf, offset,
length, blake2b-8 digest). ``?chunk=K`` re-serves from flat chunk
index K — the resume point after a connection death is simply the
number of fully verified chunks already received. Serialization is
deterministic (numpy ``tobytes`` of the device-fetched leaf), so a
resumed stream's digests match the first attempt's manifest.
"""
from __future__ import annotations

import asyncio
import hashlib
import json
import logging
import random
from typing import Any, Dict, List, Optional, Set, Tuple

from ..utils.tasks import spawn
from .pool import ConnectionPool, UpstreamError

log = logging.getLogger("containerpilot.fleet")

#: replica roles as they ride catalog heartbeat notes (``role=``);
#: an absent field means active, so promotion is visible the moment
#: the first post-promote beat lands
ROLE_ACTIVE = "active"
ROLE_STANDBY = "standby"
#: phase-specialized roles for a disaggregated fleet (docs/60):
#: routing ADVICE, not a serving restriction — a prefill replica
#: takes fresh prompts and ships the KV prefix to a decode peer
#: (kvtier/handoff.py), a decode replica generates off handed-off
#: prefixes, and either serves anything when the other pool is empty
ROLE_PREFILL = "prefill"
ROLE_DECODE = "decode"

#: path a peer serves its weights on (and the standby fetches from)
WEIGHTS_PATH = "/v1/weights"

#: bytes per manifest chunk: big enough to amortize per-chunk digest
#: and frame overhead, small enough that a resume never re-ships much
WEIGHT_CHUNK = 256 * 1024

_MANIFEST_LEN_BYTES = 8


class WeightTransferError(RuntimeError):
    """The peer transfer failed in a way a redial cannot fix (digest
    mismatch, manifest drift, shape/dtype disagreement): fall back to
    the disk/init load, do not retry the peer."""


def equal_jitter(
    backoff: float, rng: random.Random, fraction: float = 0.5
) -> float:
    """The fleet's ONE retry-delay shape (the gateway's request
    retries, the autoscaler's launch retries, and the standby
    refill all call this): a deterministic floor plus a uniform
    random slice of ``fraction`` of the backoff — failures retried
    by many actors at once spread out instead of re-arriving as one
    synchronized wave."""
    spread = backoff * fraction
    return backoff - spread + rng.random() * spread


# -- serialization (pure helpers; callers executor-wrap them) ---------


def _chunk_digest(data: bytes) -> str:
    return hashlib.blake2b(data, digest_size=8).hexdigest()


def leaf_bytes(leaf: Any) -> bytes:
    """One leaf's deterministic host-side byte image (numpy
    ``tobytes`` of the device-fetched array). Blocking (device_get):
    call it from an executor, never on the loop."""
    import jax
    import numpy as np

    return np.asarray(jax.device_get(leaf)).tobytes()


def weights_manifest(
    params: Any, chunk_bytes: int = WEIGHT_CHUNK
) -> Dict[str, Any]:
    """The transfer manifest: every leaf (name/dtype/shape/bytes) and
    every chunk (leaf index, offset, length, digest) in flat
    ``tree_util`` order. Blocking (device_get per leaf): executor-wrap
    it. Built once per server and cached — the manifest is small; the
    chunk bytes themselves are re-derived lazily at serve time so the
    server never holds a second full copy of the params."""
    import jax
    import numpy as np

    flat, _treedef = jax.tree_util.tree_flatten_with_path(params)
    leaves: List[Dict[str, Any]] = []
    chunks: List[Dict[str, Any]] = []
    for index, (path, leaf) in enumerate(flat):
        arr = np.asarray(jax.device_get(leaf))
        data = arr.tobytes()
        leaves.append(
            {
                "name": jax.tree_util.keystr(path),
                "dtype": arr.dtype.name,
                "shape": list(arr.shape),
                "bytes": len(data),
            }
        )
        for offset in range(0, len(data) or 1, chunk_bytes):
            piece = data[offset:offset + chunk_bytes]
            chunks.append(
                {
                    "leaf": index,
                    "offset": offset,
                    "len": len(piece),
                    "digest": _chunk_digest(piece),
                }
            )
    return {
        "version": 1,
        "total_bytes": sum(entry["bytes"] for entry in leaves),
        "leaves": leaves,
        "chunks": chunks,
    }


def encode_manifest(manifest: Dict[str, Any]) -> bytes:
    """Length-prefixed manifest blob — the stream's first bytes."""
    body = json.dumps(manifest, sort_keys=True).encode()
    return len(body).to_bytes(_MANIFEST_LEN_BYTES, "big") + body


def rebuild_params(
    manifest: Dict[str, Any], chunks: List[bytes], like: Any
) -> Any:
    """Reassemble a host-side params tree from verified chunks,
    shaped like ``like`` (the fetcher's own freshly-initialized or
    restored tree — it provides the treedef the wire cannot carry).
    Raises WeightTransferError on any structural disagreement; the
    caller falls back. Blocking-ish (numpy assembly): executor-wrap
    for big models."""
    import jax
    import numpy as np

    leaves, treedef = jax.tree_util.tree_flatten(like)
    specs = manifest["leaves"]
    if len(specs) != len(leaves):
        raise WeightTransferError(
            f"peer serves {len(specs)} leaves, local model has "
            f"{len(leaves)} — config mismatch"
        )
    if len(chunks) != len(manifest["chunks"]):
        raise WeightTransferError(
            f"{len(chunks)} chunks received, manifest names "
            f"{len(manifest['chunks'])}"
        )
    by_leaf: List[List[bytes]] = [[] for _ in specs]
    for chunk_spec, data in zip(manifest["chunks"], chunks):
        by_leaf[chunk_spec["leaf"]].append(data)
    rebuilt: List[Any] = []
    for spec, pieces, local in zip(specs, by_leaf, leaves):
        arr = np.frombuffer(
            b"".join(pieces), dtype=np.dtype(spec["dtype"])
        ).reshape(spec["shape"])
        local_shape = tuple(getattr(local, "shape", arr.shape))
        if local_shape != tuple(arr.shape):
            raise WeightTransferError(
                f"leaf {spec['name']}: peer shape {tuple(arr.shape)} "
                f"!= local {local_shape} — config mismatch"
            )
        rebuilt.append(arr)
    return jax.tree_util.tree_unflatten(treedef, rebuilt)


# -- the fetch client (standby side) ----------------------------------


class _Peer:
    """The minimal replica shape ConnectionPool.acquire_mux needs."""

    def __init__(self, address: str, port: int) -> None:
        self.id = f"peer@{address}:{port}"
        self.address = address
        self.port = port
        self.authority = f"{address}:{port}"


class _ChunkedReader:
    """Reassemble exact-length reads off a mux stream's arbitrary
    DATA-frame boundaries."""

    def __init__(self, stream: Any, timeout: float) -> None:
        self._stream = stream
        self._timeout = timeout
        self._buf = bytearray()

    async def read_exact(self, n: int) -> bytes:
        while len(self._buf) < n:
            piece = await self._stream.read_chunk(self._timeout)
            if not piece:
                raise UpstreamError(
                    "peer weight stream ended "
                    f"{n - len(self._buf)} bytes early"
                )
            self._buf += piece
        out = bytes(self._buf[:n])
        del self._buf[:n]
        return out


async def _read_manifest(
    reader: _ChunkedReader,
) -> Dict[str, Any]:
    raw_len = await reader.read_exact(_MANIFEST_LEN_BYTES)
    length = int.from_bytes(raw_len, "big")
    if not 0 < length <= 64 * 1024 * 1024:
        raise UpstreamError(f"implausible manifest length {length}")
    try:
        manifest = json.loads((await reader.read_exact(length)).decode())
    except (ValueError, UnicodeDecodeError) as exc:
        raise UpstreamError(f"malformed weight manifest: {exc}") from None
    if not isinstance(manifest, dict) or not isinstance(
        manifest.get("chunks"), list
    ):
        raise UpstreamError("weight manifest missing its chunk table")
    return manifest


async def fetch_weight_chunks(
    address: str,
    port: int,
    *,
    connect_timeout: float = 5.0,
    read_timeout: float = 120.0,
) -> Tuple[Dict[str, Any], List[bytes]]:
    """Fetch a peer's full weight stream over cp-mux/1: returns
    (manifest, verified chunks). ONE transparent redial on connection
    death, resuming at the first unverified chunk boundary — mirroring
    the pool's stale-connection discipline (the peer served none of
    the missing bytes, so re-requesting them cannot double-apply
    anything). Digest mismatches and manifest drift raise
    WeightTransferError immediately (corruption is not a connection
    problem; a redial cannot fix it)."""
    pool = ConnectionPool(mux=True)
    peer = _Peer(address, port)
    got: List[bytes] = []
    manifest: Optional[Dict[str, Any]] = None
    redialed = False
    try:
        while True:
            try:
                conn = await pool.acquire_mux(peer, connect_timeout)
                if conn is None:
                    raise UpstreamError(
                        f"{peer.authority} declined the cp-mux/1 "
                        f"upgrade"
                    )
                stream = await conn.open_stream(
                    "GET", f"{WEIGHTS_PATH}?chunk={len(got)}"
                )
                status, _headers = await stream.response_head(
                    read_timeout
                )
                if status != 200:
                    raise UpstreamError(
                        f"weights fetch answered {status}"
                    )
                reader = _ChunkedReader(stream, read_timeout)
                fresh = await _read_manifest(reader)
                if manifest is None:
                    manifest = fresh
                elif fresh != manifest:
                    # the peer's params changed between attempts (it
                    # reloaded): the already-verified prefix belongs
                    # to a different tree
                    raise WeightTransferError(
                        "peer manifest changed across the redial"
                    )
                specs = manifest["chunks"]
                while len(got) < len(specs):
                    spec = specs[len(got)]
                    data = await reader.read_exact(int(spec["len"]))
                    if _chunk_digest(data) != spec["digest"]:
                        raise WeightTransferError(
                            f"chunk {len(got)} digest mismatch"
                        )
                    got.append(data)
                return manifest, got
            except WeightTransferError:
                raise
            except UpstreamError:
                if redialed:
                    raise
                redialed = True
                # drop the dead shared connection so the next acquire
                # dials fresh; fully-verified chunks stay counted
                pool.close_all()
                log.warning(
                    "standby: peer weight stream died at chunk %d; "
                    "redialing once to resume", len(got),
                )
    finally:
        pool.close_all()


async def fetch_params(
    address: str,
    port: int,
    like: Any,
    *,
    connect_timeout: float = 5.0,
    read_timeout: float = 120.0,
) -> Optional[Any]:
    """Fetch a warm peer's weights and return them as a device-put
    tree shaped like ``like``, or None on ANY failure — the caller
    falls back to its disk/init load (the transfer is an accelerator,
    never a new way to fail a boot)."""
    try:
        manifest, chunks = await fetch_weight_chunks(
            address, port,
            connect_timeout=connect_timeout,
            read_timeout=read_timeout,
        )
    except (WeightTransferError, UpstreamError, OSError) as exc:
        log.warning(
            "standby: peer weight transfer from %s:%d failed (%s); "
            "falling back to local load", address, port, exc,
        )
        return None

    def assemble() -> Any:
        import jax

        host_tree = rebuild_params(manifest, chunks, like)
        # land each leaf HOW ``like``'s leaf lives — but only when
        # that placement is a real multi-device mesh sharding: a
        # tp/cp server's load path sharded ``like`` onto its mesh,
        # and the fetched replacements must follow or the ring/decode
        # programs see a params/mesh mismatch. Single-device likes
        # take the plain default placement (an explicit
        # SingleDeviceSharding would commit the arrays and fork the
        # jit cache a warm process already holds).
        host_leaves, treedef = jax.tree_util.tree_flatten(host_tree)

        def put(arr, ref):
            sharding = getattr(ref, "sharding", None)
            mesh = getattr(sharding, "mesh", None)
            if mesh is not None and getattr(mesh, "size", 1) > 1:
                return jax.device_put(arr, sharding)
            return jax.device_put(arr)

        placed = [
            put(arr, ref)
            for arr, ref in zip(
                host_leaves, jax.tree_util.tree_leaves(like)
            )
        ]
        return jax.tree_util.tree_unflatten(treedef, placed)

    loop = asyncio.get_event_loop()
    try:
        return await loop.run_in_executor(None, assemble)
    except (WeightTransferError, ValueError, TypeError) as exc:
        log.warning(
            "standby: fetched weights did not match the local model "
            "(%s); falling back to local load", exc,
        )
        return None


# -- the pool (autoscaler side) ---------------------------------------


class StandbyLauncher:
    """Autoscaler launcher that turns scale-up into PROMOTION.

    Wraps an inner launcher speaking the plain duck type plus three
    standby verbs::

        count() -> int / ids() -> list[str]   ACTIVE replicas only
        async launch() -> str                 cold active launch
        async retire(id)                      drain + stop
        async launch_standby() -> str         boot one standby replica
        async promote(id) -> bool             standby -> active; False
                                              when the standby is gone
                                              or already promoted

    ``launch()`` claims a warm standby (popped BEFORE any await, so
    two concurrent launches can never promote the same one — the
    promotion-race invariant) and promotes it; a dead/contended
    standby is dropped and the next tried; an empty pool falls back
    to the inner cold launch. Every launch — promoted or cold —
    schedules a background refill that boots standbys until the pool
    holds ``standby_count`` again, retrying failures with the
    fleet's equal-jitter backoff discipline. The autoscaler's
    kill-repair path calls the same ``launch()``, so crash recovery
    promotes too."""

    def __init__(
        self,
        inner: Any,
        standby_count: int = 1,
        *,
        refill_backoff: float = 0.25,
        refill_backoff_cap: float = 4.0,
        jitter_seed: Optional[int] = None,
    ) -> None:
        if standby_count < 0:
            raise ValueError("standby_count must be >= 0")
        self.inner = inner
        self.standby_count = standby_count
        self.refill_backoff = refill_backoff
        self.refill_backoff_cap = refill_backoff_cap
        self._rng = random.Random(jitter_seed)
        self._pool: List[str] = []
        self.promotions = 0
        self.promote_failures = 0
        self.cold_launches = 0
        self.refill_failures = 0
        #: how the LAST successful launch happened ("promoted"/"cold")
        #: — the autoscaler stamps it into its scale log so the TTFRT
        #: report can separate the promoted path from the cold one
        self.last_launch: Dict[str, str] = {}
        self._refill_task: Optional["asyncio.Task[None]"] = None
        self._tasks: Set["asyncio.Task"] = set()

    # -- the autoscaler duck type -------------------------------------

    def count(self) -> int:
        return self.inner.count()

    def ids(self) -> List[str]:
        return self.inner.ids()

    def standby_ids(self) -> List[str]:
        return list(self._pool)

    async def launch(self) -> str:
        """Promote a warm standby when one exists; cold-launch
        otherwise. Either way the pool refills in the background."""
        while self._pool:
            # claim BEFORE the await: concurrent launches pop
            # different standbys, so exactly one promoter ever
            # targets each — the loser of a pool race simply gets
            # the next standby (or the cold path), never a 409
            standby_id = self._pool.pop(0)
            try:
                promoted = await self.inner.promote(standby_id)
            except Exception as exc:
                log.warning(
                    "standby: promote %s raised (%s); trying next",
                    standby_id, exc,
                )
                promoted = False
            if promoted:
                self.promotions += 1
                self.last_launch = {
                    "mode": "promoted", "replica": standby_id,
                }
                self._ensure_refill()
                return standby_id
            # the standby died (or someone else promoted it) between
            # joining the pool and now: drop it and keep going
            self.promote_failures += 1
        self.last_launch = {"mode": "cold"}
        self._ensure_refill()
        replica_id = await self.inner.launch()
        # counted AFTER the await: a raising launcher is the
        # autoscaler's launch_failures, not a cold launch that never
        # happened skewing the promoted-vs-cold split
        self.cold_launches += 1
        return replica_id

    async def retire(self, replica_id: str) -> None:
        await self.inner.retire(replica_id)

    # -- pool maintenance ---------------------------------------------

    async def prefill(self) -> None:
        """Boot the initial standby set synchronously (the fleet-boot
        path; refills after that are background)."""
        while len(self._pool) < self.standby_count:
            self._pool.append(await self.inner.launch_standby())

    def _ensure_refill(self) -> None:
        if self.standby_count <= 0:
            return
        if self._refill_task is not None and not self._refill_task.done():
            return
        self._refill_task = spawn(
            self._refill_loop(), name="standby-refill",
            owner=self._tasks,
        )

    async def _refill_loop(self) -> None:
        """Boot standbys until the pool is full again. A standby that
        crashes mid-boot counts a failure and retries after an
        equal-jitter backoff (doubling, capped) — the same discipline
        the gateway's retry path uses, so a broken launcher can't
        storm the host with boot attempts."""
        backoff = self.refill_backoff
        while len(self._pool) < self.standby_count:
            try:
                standby_id = await self.inner.launch_standby()
            except Exception as exc:
                self.refill_failures += 1
                delay = equal_jitter(backoff, self._rng)
                log.warning(
                    "standby: refill launch failed (%s); retrying "
                    "in %.2fs", exc, delay,
                )
                await asyncio.sleep(delay)
                backoff = min(backoff * 2, self.refill_backoff_cap)
                continue
            self._pool.append(standby_id)
            backoff = self.refill_backoff
        log.info(
            "standby: pool refilled to %d (%s)",
            len(self._pool), self._pool,
        )

    async def stop(self) -> None:
        """Cancel the background refill (shutdown path)."""
        task = self._refill_task
        self._refill_task = None
        if task is not None and not task.done():
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                log.debug("standby: refill task cancelled at stop")

    def standby_stats(self) -> Dict[str, Any]:
        """The pool's surface on /fleet (via the autoscaler stats)."""
        return {
            "standby_count": self.standby_count,
            "pool": list(self._pool),
            "promotions": self.promotions,
            "promote_failures": self.promote_failures,
            "cold_launches": self.cold_launches,
            "refill_failures": self.refill_failures,
        }
