"""Fleet-wide KV reuse units: the host-RAM spill tier, the prefix
digest codec, and the ``reuse_admission`` edge cases the tier must
not break (serve_prefix.py's match-then-evicted window, readmit under
concurrent evictions, byte-budget enforcement).

The spill tier moves real device arrays through
``jax.device_get``/``device_put``, so this module rides the workload
tier (conftest pins the CPU platform before jax imports).
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from containerpilot_tpu.kvtier import (
    DIGEST_MAX_BYTES,
    FP_TOKENS,
    HostSpillTier,
    encode_fingerprints,
    parse_digest,
    parse_kv_counters,
    parse_kv_note,
    prefix_fingerprint,
)
from containerpilot_tpu.kvtier.spill import tree_nbytes
from containerpilot_tpu.workload.serve_prefix import (
    BUCKET,
    MIN_REUSE,
    PrefixCache,
    plan_reuse,
)


def _entry(tag: int, rows: int = 8) -> dict:
    """A fake KV pytree: deterministic contents, predictable bytes
    (PrefixCache/HostSpillTier treat entries as opaque)."""
    base = jnp.full((rows, 16), tag, jnp.float32)
    return {"k": base, "v": base + 1, "pos": jnp.asarray(rows, jnp.int32)}


def _entry_bytes(rows: int = 8) -> int:
    return 2 * rows * 16 * 4 + 4


#: seconds any wait on another thread may take before the test fails
#: instead of hanging the suite
WAIT = 30.0


# -- digest codec (pure host) -------------------------------------------


def test_prefix_fingerprint_contract():
    row = list(range(100, 100 + FP_TOKENS))
    fp = prefix_fingerprint(row)
    assert fp is not None and 0 <= fp <= 0xFFFFFFFF
    # stable across calls and processes (blake2b, not hash())
    assert prefix_fingerprint(row) == fp
    # the tail doesn't matter: only the first FP_TOKENS ids hash
    assert prefix_fingerprint(row + [1, 2, 3]) == fp
    # a different prefix fingerprint differs
    assert prefix_fingerprint([7] + row[1:]) != fp
    # too short to ever be reused -> never advertised
    assert prefix_fingerprint(row[: FP_TOKENS - 1]) is None
    # FP_TOKENS tracks the reuse floor by design
    assert FP_TOKENS == MIN_REUSE


def test_digest_roundtrip_and_truncation():
    fps = {1, 0xFFFFFFFF, 0xDEADBEEF, 42}
    raw = encode_fingerprints(7, fps)
    version, parsed = parse_digest(raw)
    assert version == 7 and parsed == frozenset(fps)
    # equal sets encode identically (sorted)
    assert raw == encode_fingerprints(7, reversed(sorted(fps)))
    # size bound: a huge set truncates to whole fingerprints
    big = encode_fingerprints(1, range(10_000))
    assert len(big) <= DIGEST_MAX_BYTES
    v, kept = parse_digest(big)
    assert v == 1 and 0 < len(kept) < 10_000


@pytest.mark.parametrize("raw", [
    None, 17, "", "x", "v:", "v1", "v1:abc",          # malformed head/body
    "v١:00000001",                                # unicode digit
    "v1:zzzzzzzz",                                     # non-hex body
    "v1:" + "0" * (DIGEST_MAX_BYTES + 8),              # oversized body
])
def test_digest_parse_rejects_garbage(raw):
    assert parse_digest(raw) == (None, frozenset())


def test_kv_note_parsing_is_tolerant():
    note = "ok occ=0.50 kv=3,4,120,2,1 pd=v2:0000002a"
    fields = parse_kv_note(note)
    assert fields["occ"] == "0.50" and fields["pd"] == "v2:0000002a"
    assert parse_kv_counters(fields["kv"]) == {
        "hits": 3, "misses": 4, "tokens_reused": 120,
        "spilled": 2, "readmitted": 1,
    }
    # short / torn values keep the fields that did parse, zero-filled
    assert parse_kv_counters("7,2")["hits"] == 7
    assert parse_kv_counters("7,2")["tokens_reused"] == 0
    assert parse_kv_counters("7,x,9")["misses"] == 0
    assert parse_kv_counters(None) == parse_kv_counters("")
    assert parse_kv_note(None) == {}
    assert parse_kv_note("just words no pairs") == {}


# -- host spill tier ----------------------------------------------------


def test_spill_roundtrip_is_byte_exact():
    tier = HostSpillTier(1 << 20)
    entry = _entry(3)
    assert tier.put((1, 2, 3), entry)
    back = tier.take((1, 2, 3))
    assert back is not None
    for leaf, ref in zip(
        jax.tree_util.tree_leaves(back),
        jax.tree_util.tree_leaves(entry),
    ):
        assert leaf.dtype == ref.dtype
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(ref))
    assert tier.stats["spilled"] == 1 and tier.stats["readmitted"] == 1


def test_spill_byte_budget_evicts_lru_and_refuses_oversize():
    per = _entry_bytes()
    tier = HostSpillTier(2 * per)  # room for exactly two entries
    for tag in range(4):
        assert tier.put((tag,), _entry(tag))
    assert len(tier) == 2
    assert tier.bytes_used <= tier.max_bytes
    assert tier.stats["evicted"] == 2
    # LRU: the two NEWEST keys survived
    assert tier.take((0,)) is None and tier.take((1,)) is None
    assert tier.take((2,)) is not None and tier.take((3,)) is not None
    # an entry larger than the whole budget is refused, not stored
    big = HostSpillTier(per - 1)
    assert not big.put((9,), _entry(9))
    assert big.stats["refused"] == 1 and len(big) == 0
    # re-putting an existing key replaces, never double-counts bytes
    tier.put((5,), _entry(5))
    tier.put((5,), _entry(6))
    assert len(tier) == 1 and tier.bytes_used == per


def test_spill_candidates_bucket_by_fingerprint():
    """The match scan consults the tier by fingerprint bucket, not a
    full key scan: only keys sharing the row's first-FP_TOKENS ids
    (the reuse floor) come back, and the index tracks every insert,
    take, replacement, and budget eviction."""
    tier = HostSpillTier(1 << 20)
    key_a = tuple(range(FP_TOKENS)) + (1, 2)
    key_a2 = tuple(range(FP_TOKENS)) + (9,)   # same first-16 ids
    key_b = tuple(range(50, 50 + FP_TOKENS))  # different prefix
    for key in (key_a, key_a2, key_b):
        assert tier.put(key, _entry(1))
    fp_a = prefix_fingerprint(list(key_a))
    assert set(tier.candidates(fp_a)) == {key_a, key_a2}
    assert tier.candidates(prefix_fingerprint(list(key_b))) == [key_b]
    assert tier.candidates(None) == []
    assert tier.candidates(0x12345678) == []
    # take unindexes
    assert tier.take(key_a) is not None
    assert set(tier.candidates(fp_a)) == {key_a2}
    # budget eviction unindexes the LRU victim
    per = _entry_bytes()
    tight = HostSpillTier(per)
    tight.put(key_a, _entry(1))
    tight.put(key_b, _entry(2))  # evicts key_a
    assert tight.candidates(fp_a) == []
    assert tight.candidates(prefix_fingerprint(list(key_b))) == [key_b]


def test_spill_take_serves_a_key_exactly_once():
    tier = HostSpillTier(1 << 20)
    tier.put((1,), _entry(1))
    assert tier.take((1,)) is not None
    # a second take (concurrent readmit racing this one) misses
    assert tier.take((1,)) is None
    assert tier.stats["misses"] == 1
    assert tier.take((404,)) is None
    assert tier.stats["misses"] == 2


# -- the deferred spill: rows leave the device behind the caller's back --
#
# Every test here gates the ``kv-spill`` worker's ``jax.device_get``
# with an Event (conftest's ``spill_gate``), so "in flight" is a state
# the test holds, not a race it hopes to win; no sleeps, and every
# wait has a timeout.


def _latent_tree(tag: int) -> dict:
    """A latent row as models/mla_moe.py keeps it: a leaf per layer."""
    ckv = [jnp.full((1, 8, 12), tag + i, jnp.bfloat16) for i in range(2)]
    kpe = [jnp.full((1, 8, 4), tag - i, jnp.bfloat16) for i in range(2)]
    return {"ckv": ckv, "kpe": kpe, "pos": jnp.asarray(8, jnp.int32)}


TREES = pytest.mark.parametrize(
    "tree", [_entry, _latent_tree], ids=["keys_values", "latent"]
)


def _same_bytes(got, ref) -> None:
    got_leaves = jax.tree_util.tree_leaves(got)
    ref_leaves = jax.tree_util.tree_leaves(ref)
    assert len(got_leaves) == len(ref_leaves)
    for leaf, want in zip(got_leaves, ref_leaves):
        assert leaf.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(want))


def _key(tag: int, extra: int = 0):
    return tuple(range(tag * 100, tag * 100 + MIN_REUSE + extra))


def _on_thread(fn, *args):
    """Run ``fn`` on a thread named ``waiter``; (thread, result box)."""
    box = {}

    def run():
        box["value"] = fn(*args)

    t = threading.Thread(target=run, name="waiter", daemon=True)
    t.start()
    return t, box


def _waiting(tier, monkeypatch) -> threading.Event:
    """Set once the ``waiter`` thread blocks on the tier's condition:
    how a test knows a call is WAITING without sleeping on it."""
    blocked = threading.Event()
    real = tier._changed.wait

    def wait(timeout=None):
        if threading.current_thread().name == "waiter":
            blocked.set()
        return real(timeout)

    monkeypatch.setattr(tier._changed, "wait", wait)
    return blocked


@TREES
def test_a_pending_key_is_found_as_a_landed_one_is(tree, spill_gate):
    tier = HostSpillTier(1 << 20)
    pc = PrefixCache(1, spill=tier)
    key_a, key_b = _key(1, extra=4), _key(2)
    pc.store(key_a, tree(1))
    pc.store(key_b, tree(2))  # evicts A: handed over, copy gated shut
    assert spill_gate.reached.wait(WAIT)

    def seen():
        return (
            pc.best_match(list(key_a) + [1, 2]),
            tier.candidates(prefix_fingerprint(key_a)),
            key_a in tier.keys(),
            pc.export_keys(),
            parse_digest(pc.digest())[1],
            len(tier),
        )

    in_flight = seen()
    assert in_flight == (
        (len(key_a), key_a), [key_a], True, [key_b, key_a],
        {prefix_fingerprint(key_a), prefix_fingerprint(key_b)}, 1,
    )
    snap = tier.snapshot()
    assert (snap["pending"], snap["deferred"], snap["spilled"]) == (1, 1, 0)
    assert snap["entries"] == 0 and snap["bytes"] == 0
    assert pc.stats["spilled"] == 0
    spill_gate.open()
    assert pc.flush(timeout=WAIT)
    assert seen() == in_flight  # landing changes nothing a match sees
    snap = tier.snapshot()
    assert (snap["pending"], snap["deferred"], snap["spilled"]) == (0, 1, 1)
    assert snap["entries"] == 1 and snap["bytes"] == tier.bytes_used > 0
    assert pc.stats["spilled"] == 1
    assert pc.stats["spill_bytes"] == tier.bytes_used


@TREES
def test_take_of_a_pending_key_hands_back_the_row_itself_once(
    tree, spill_gate
):
    """A row in flight never left the device: ``take`` hands back its
    very arrays (no ``device_put``), once, whether the worker is in
    the middle of its copy or has not reached it, and what the copy
    brings is thrown away. The bytes are a landed row's."""
    landed = HostSpillTier(1 << 20)
    assert landed.put(_key(1), tree(1))
    reference = landed.take(_key(1))

    tier = HostSpillTier(1 << 20)
    row_a, row_b = tree(1), tree(2)
    tier.defer(_key(1), row_a)
    assert spill_gate.reached.wait(WAIT)  # A's copy has started
    tier.defer(_key(2), row_b)      # B waits its turn behind it
    for key, row in ((_key(1), row_a), (_key(2), row_b)):
        got = tier.take(key)
        for leaf, mine in zip(jax.tree_util.tree_leaves(got),
                              jax.tree_util.tree_leaves(row)):
            assert leaf is mine
        assert tier.take(key) is None  # exactly once
        assert tier.candidates(prefix_fingerprint(key)) == []
    _same_bytes(row_a, reference)
    assert tier.stats["pending_hits"] == 2 and tier.stats["misses"] == 2
    spill_gate.open()
    assert tier.flush(timeout=WAIT)
    # neither row became part of the tier, and the books say so
    assert len(tier) == 0 and tier.bytes_used == 0 and tier.keys() == []
    assert tier.stats["spilled"] == 0 and tier.stats["readmitted"] == 0
    assert tier.phases.spill_bytes == tier.phases.readmit_bytes == 0
    # B's copy never ran: the worker found it taken
    assert tier.phases.phase_n["kvtier.spill"] == 1
    assert tier.phases.phase_n["kvtier.readmit"] == 0


def test_take_of_a_key_landed_and_pending_serves_the_newer_row_alone(
    spill_gate,
):
    """A newer row of a landed key, taken while in flight, takes the
    older one with it, as its landing would have replaced it (what
    ``put`` does at once): the key is served once, not twice."""
    tier = HostSpillTier(1 << 20)
    older, newer = _entry(1), _entry(5)
    assert tier.put(_key(1), older)
    tier.defer(_key(1), newer)
    assert spill_gate.reached.wait(WAIT)
    assert tier.keys() == [_key(1)] and len(tier) == 2
    got = tier.take(_key(1))
    assert got["k"] is newer["k"] and got["v"] is newer["v"]
    assert tier.take(_key(1)) is None
    assert tier.bytes_used == 0 and tier.keys() == []
    assert tier.candidates(prefix_fingerprint(_key(1))) == []
    spill_gate.open()
    assert tier.flush(timeout=WAIT)
    assert len(tier) == 0 and tier.bytes_used == 0
    assert tier.stats["pending_hits"] == 1 and tier.stats["spilled"] == 1


def test_store_waits_at_the_in_flight_limit_and_drops_nothing(
    spill_gate, monkeypatch
):
    from containerpilot_tpu.kvtier.spill import MAX_IN_FLIGHT

    tier = HostSpillTier(1 << 20)
    pc = PrefixCache(1, spill=tier)
    blocked = _waiting(tier, monkeypatch)
    for tag in range(MAX_IN_FLIGHT + 1):  # MAX_IN_FLIGHT evictions
        pc.store(_key(tag), _entry(tag))
    assert tier.snapshot()["pending"] == MAX_IN_FLIGHT
    assert tier.stats["backpressure_n"] == 0  # the limit itself is free
    over = MAX_IN_FLIGHT + 1
    storing, _ = _on_thread(pc.store, _key(over), _entry(over))
    assert blocked.wait(WAIT)  # one eviction too many: store WAITS
    assert storing.is_alive()
    assert tier.stats["deferred"] == MAX_IN_FLIGHT
    spill_gate.open()
    storing.join(WAIT)
    assert not storing.is_alive()
    assert pc.flush(timeout=WAIT)
    assert tier.stats["backpressure_n"] == 1
    assert tier.stats["backpressure_s"] > 0.0
    snap = tier.snapshot()
    assert snap["backpressure_n"] == 1 and snap["backpressure_s"] > 0.0
    # every evicted row reached the tier, in eviction order
    assert tier.stats["deferred"] == tier.stats["spilled"] == over
    assert tier.keys() == [_key(tag) for tag in range(over)]
    assert tier.stats["failed"] == 0


@TREES
def test_a_failing_copy_costs_one_row_and_the_worker_lives_on(
    tree, spill_gate, monkeypatch
):
    tier = HostSpillTier(1 << 20)
    pc = PrefixCache(1, spill=tier)
    blocked = _waiting(tier, monkeypatch)
    spill_gate.fail = 1
    pc.store(_key(0), tree(0))
    pc.store(_key(1), tree(1))  # evicts 0, whose copy will raise
    assert spill_gate.reached.wait(WAIT)
    peeking, peeked = _on_thread(tier.peek, _key(0))
    assert blocked.wait(WAIT)   # an export waits for the landing
    spill_gate.open()
    peeking.join(WAIT)
    assert not peeking.is_alive() and peeked["value"] is None
    assert pc.flush(timeout=WAIT)
    assert tier.stats["failed"] == 1 and tier.stats["spilled"] == 0
    assert pc.stats["spilled"] == 0 and len(tier) == 0
    assert tier.candidates(prefix_fingerprint(_key(0))) == []
    assert tier.take(_key(0)) is None
    pc.store(_key(2), tree(2))  # evicts 1: the next spill still lands
    assert pc.flush(timeout=WAIT)
    assert tier.stats["failed"] == 1 and tier.stats["spilled"] == 1
    assert pc.stats["spilled"] == 1 and tier.keys() == [_key(1)]
    _same_bytes(tier.take(_key(1)), tree(1))


@TREES
def test_peek_of_a_pending_key_waits_and_reads_the_landed_row(
    tree, spill_gate, monkeypatch
):
    tier = HostSpillTier(1 << 20)
    blocked = _waiting(tier, monkeypatch)
    tier.defer(_key(1), tree(1))
    peeking, peeked = _on_thread(tier.peek, _key(1))
    assert blocked.wait(WAIT)
    assert peeking.is_alive()
    spill_gate.open()
    peeking.join(WAIT)
    assert not peeking.is_alive()
    _same_bytes(peeked["value"], jax.device_get(tree(1)))
    assert len(tier) == 1  # an export leaves the entry readmittable
    _same_bytes(tier.take(_key(1)), tree(1))


@TREES
@pytest.mark.parametrize("budget_rows", [2, 64], ids=["tight", "roomy"])
def test_deferred_spills_leave_the_books_a_synchronous_put_leaves(
    tree, budget_rows
):
    """The same sequence through ``put`` and through ``defer`` +
    ``flush``: budget eviction, LRU order, the counters and the bytes
    come out the same, and what was spilled is in the tier or came
    back (where the budget dropped nothing)."""
    per = tree_nbytes(tree(0))

    def drive(spill):
        tier = HostSpillTier(budget_rows * per)
        for tag in range(4):
            spill(tier, _key(tag), tree(tag))
        tier.flush(timeout=WAIT)
        back = tier.take(_key(3))
        spill(tier, _key(4), tree(4))
        spill(tier, _key(2), tree(9))  # a newer row of a held key
        assert tier.flush(timeout=WAIT)
        return tier, back

    sync, sync_back = drive(lambda t, k, row: t.put(k, row))
    late, late_back = drive(lambda t, k, row: t.defer(k, row))
    _same_bytes(late_back, sync_back)
    assert list(late._store) == list(sync._store)  # the host LRU's order
    assert late.keys() == sync.keys()
    assert late.bytes_used == sync.bytes_used <= late.max_bytes
    for name in ("spilled", "readmitted", "evicted", "refused", "misses"):
        assert late.stats[name] == sync.stats[name], name
    assert late.stats["evicted"] == (2 if budget_rows == 2 else 0)
    assert late.stats["deferred"] == 6 and sync.stats["deferred"] == 0
    for name in ("spill_bytes", "readmit_bytes", "latent_spill_bytes",
                 "latent_readmit_bytes"):
        assert getattr(late.phases, name) == getattr(sync.phases, name), name
    assert late.phases.phase_n["kvtier.spill"] == 6
    for key in sync.keys():
        _same_bytes(late.peek(key), sync.peek(key))
    if budget_rows == 64:
        # a replaced row's bytes left the tier without coming back
        assert late.phases.spill_bytes == (
            late.bytes_used + late.phases.readmit_bytes + per
        )
    oversize = HostSpillTier(per - 1)
    oversize.defer(_key(7), tree(7))
    assert oversize.flush(timeout=WAIT)
    assert oversize.stats["refused"] == 1 and len(oversize) == 0
    assert oversize.candidates(prefix_fingerprint(_key(7))) == []


@TREES
def test_spilled_bytes_are_in_the_tier_or_came_back_after_a_flush(tree):
    """``spill_bytes == bytes_used + readmit_bytes``, through a prefix
    cache whose every eviction is deferred, a landed readmit and a
    readmit of a row still in flight among them."""
    tier = HostSpillTier(1 << 20)
    pc = PrefixCache(1, spill=tier)
    for tag in range(3):
        pc.store(_key(tag), tree(tag))
    assert pc.flush(timeout=WAIT)
    _same_bytes(pc.get(_key(0)), tree(0))   # landed: a device_put
    got = pc.get(_key(2))                   # evicted a moment ago
    _same_bytes(got, tree(2))
    assert pc.flush(timeout=WAIT)
    phases = pc.phases
    assert phases.spill_bytes == tier.bytes_used + phases.readmit_bytes
    assert phases.readmit_bytes > 0
    assert pc.stats["readmitted"] == 2
    assert pc.stats["readmitted"] == (
        tier.stats["readmitted"] + tier.stats["pending_hits"]
    )
    assert pc.stats["spill_bytes"] == tier.bytes_used
    assert tier.stats["deferred"] == (
        tier.stats["spilled"] + tier.stats["pending_hits"]
    )


# -- prefix cache + spill integration -----------------------------------


def test_prefix_cache_spills_on_eviction_and_readmits():
    pc = PrefixCache(1, spill=HostSpillTier(1 << 20))
    key_a = tuple(range(MIN_REUSE + 4))
    key_b = tuple(range(100, 100 + MIN_REUSE))
    pc.store(key_a, _entry(1))
    pc.store(key_b, _entry(2))  # device LRU (1 entry) evicts A -> spill
    assert pc.flush(timeout=WAIT)  # the copy runs behind the store
    assert pc.stats["spilled"] == 1
    assert pc.stats["spill_bytes"] > 0
    # the spilled key still matches (best_match scans both tiers)
    n, key = pc.best_match(list(key_a) + [1, 2])
    assert key == key_a and n == len(key_a)
    # fetch readmits it to the device LRU as MRU (spilling B in turn)
    got = pc.get(key_a)
    assert got is not None
    assert pc.stats["readmitted"] == 1
    assert pc.readmit_seconds > 0.0
    with pc._lock:
        assert list(pc._cache) == [key_a]
    # byte parity through the spill roundtrip
    np.testing.assert_array_equal(
        np.asarray(got["k"]), np.asarray(_entry(1)["k"])
    )


def test_match_then_evicted_between_match_and_fetch():
    """The serve_prefix.py get() contract: a key evicted from BOTH
    tiers after the match scan but before the fetch returns None —
    the caller re-prefills cold instead of crashing or double-using
    a freed entry."""
    pc = PrefixCache(1, spill=HostSpillTier(1 << 20))
    key = tuple(range(MIN_REUSE))
    pc.store(key, _entry(1))
    n, matched = pc.best_match(list(key))
    assert matched == key
    # the race window: another request's store pushes it to spill...
    pc.store(tuple(range(50, 50 + MIN_REUSE)), _entry(2))
    assert pc.flush(timeout=WAIT)
    # ...and a concurrent readmit drains it from the spill tier too
    assert pc.spill.take(key) is not None
    assert pc.get(matched) is None
    # the cold path then counts a miss through plan_reuse
    reuse, base = plan_reuse(pc, list(key) + [1] * BUCKET)
    assert (reuse, base) == (0, None)


def test_reuse_admission_counts_miss_when_base_vanishes():
    """reuse_admission must answer None (cold prefill) when the
    matched base disappears between match and fetch — the eviction
    window with a spill tier attached is the same contract as
    without one."""
    from containerpilot_tpu.workload.serve_prefix import reuse_admission

    class RacingCache(PrefixCache):
        """Simulates a concurrent eviction winning the window: every
        fetch finds both tiers already drained."""

        def get(self, key):
            with self._lock:
                self._cache.pop(key, None)
            if self.spill is not None:
                self.spill.take(key)
            return super().get(key)

    pc = RacingCache(2, spill=HostSpillTier(1 << 20))
    key = tuple(range(MIN_REUSE + BUCKET))
    pc.store(key, _entry(1))
    hit = reuse_admission(
        pc, list(key) + [3] * BUCKET, cfg=None, params=None
    )
    assert hit is None
    assert pc.stats["misses"] == 1 and pc.stats["hits"] == 0


def test_readmit_under_concurrent_evictions():
    """Stores (spilling under a tight budget) race gets (readmitting)
    across threads — the locked index must neither corrupt nor
    double-serve; every get returns the key's own bytes or None."""
    per = _entry_bytes()
    pc = PrefixCache(1, spill=HostSpillTier(3 * per))
    hot = tuple(range(MIN_REUSE))
    pc.store(hot, _entry(7))
    stop = threading.Event()
    errors = []

    def churn():
        tag = 100
        try:
            while not stop.is_set():
                tag += 1
                pc.store(
                    tuple(range(tag * 50, tag * 50 + MIN_REUSE)),
                    _entry(tag % 50),
                )
        except Exception as exc:  # pragma: no cover - the assertion
            errors.append(exc)

    t = threading.Thread(target=churn, daemon=True)
    t.start()
    served = 0
    try:
        for _ in range(200):
            got = pc.get(hot)
            if got is not None:
                served += 1
                np.testing.assert_array_equal(
                    np.asarray(got["k"]), np.asarray(_entry(7)["k"])
                )
                pc.store(hot, got)  # keep it in play
            else:
                # gone from both tiers (churn outran the budget):
                # the cold path re-prefills and re-stores, exactly
                # what a real miss does
                pc.store(hot, _entry(7))
    finally:
        stop.set()
        t.join(timeout=10)
    assert not errors
    assert served > 0
    # accounting stayed coherent under the churn
    assert pc.flush(timeout=WAIT)
    assert pc.spill.bytes_used <= pc.spill.max_bytes
    # the cache counts a row as back whether the tier handed it a
    # device_put or, still in flight, the device arrays themselves
    assert pc.stats["readmitted"] == (
        pc.spill.stats["readmitted"] + pc.spill.stats["pending_hits"]
    )


def test_digest_is_versioned_and_memoized():
    pc = PrefixCache(2, spill=HostSpillTier(1 << 20))
    assert parse_digest(pc.digest()) == (0, frozenset())
    key = tuple(range(MIN_REUSE))
    pc.store(key, _entry(1))
    v1, fps1 = parse_digest(pc.digest())
    assert fps1 == {prefix_fingerprint(key)}
    assert pc.digest() is pc.digest()  # memoized per version
    # a spilled entry stays advertised (it is still warm, host-side)
    pc.store(tuple(range(60, 60 + MIN_REUSE)), _entry(2))
    pc.store(tuple(range(90, 90 + MIN_REUSE)), _entry(3))
    v2, fps2 = parse_digest(pc.digest())
    assert v2 > v1 and prefix_fingerprint(key) in fps2
    assert len(fps2) == 3
    # short keys (< FP_TOKENS) are never advertised
    short = PrefixCache(2)
    short.store((1, 2, 3), _entry(1))
    assert parse_digest(short.digest())[1] == frozenset()


def test_spill_disabled_keeps_stats_schema_zeroed():
    """/v1/model schema stability: without a tier the spill fields
    exist and stay zero (the PR 1 pod-boot discipline)."""
    pc = PrefixCache(1)
    for tag in range(3):
        pc.store(tuple(range(tag * 40, tag * 40 + MIN_REUSE)), _entry(tag))
    assert pc.stats["spilled"] == 0
    assert pc.stats["readmitted"] == 0
    assert pc.stats["spill_bytes"] == 0
    assert pc.get(tuple(range(MIN_REUSE))) is None  # dropped, not spilled


def test_reuse_admission_readmits_from_spill_byte_parity():
    """End to end on a real model: an engine whose device LRU holds
    ONE entry + a spill tier produces byte-identical tokens to an
    engine with a big device LRU — the host roundtrip must be
    invisible to the rewind+extend protocol."""
    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )
    from containerpilot_tpu.workload.serve_slots import SlotEngine

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=128, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    caches = {
        "spilling": PrefixCache(1, spill=HostSpillTier(1 << 20)),
        "roomy": PrefixCache(4),
    }

    turn_a = list(range(1, 33))          # 32-token history A
    turn_b = [9] * 32                    # unrelated history B
    turn_a2 = turn_a + [50] * 16         # A's next turn

    outs = {}
    for name, pc in caches.items():
        engine = SlotEngine(
            cfg, params, 128, slots=1, chunk=4, prefix_cache=pc
        )
        try:
            outs[name] = [
                engine.submit(turn, 8).result(timeout=120)
                for turn in (turn_a, turn_b, turn_a2)
            ]
        finally:
            engine.stop()
    assert outs["spilling"] == outs["roomy"]
    stats = caches["spilling"].stats
    # A was evicted to host RAM by B, then readmitted for turn 2
    assert stats["spilled"] >= 1, stats
    assert stats["readmitted"] == 1, stats
    assert stats["hits"] == 1, stats
    assert stats["tokens_reused"] >= 16, stats
    # the roomy engine reused straight from device: same hit account
    assert caches["roomy"].stats["hits"] == 1
    assert caches["roomy"].stats["readmitted"] == 0
