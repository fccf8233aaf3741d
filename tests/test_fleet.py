"""Fleet subsystem tests: routing units, connection-pool behavior,
drain hook, catalog robustness, control-plane drain, and the
two-replica gateway integration scenario (drain mid-traffic, zero
client-visible 5xx).

The gateway unit tests run against stub HTTP servers (no JAX); the
integration test boots two real tiny InferenceServers behind a
FleetGateway on the CPU backend.
"""
import asyncio
import json
import socket
import time
import urllib.error
import urllib.request

import pytest

from containerpilot_tpu.discovery import (
    FileCatalogBackend,
    NoopBackend,
    ServiceRegistration,
)
from containerpilot_tpu.fleet import FleetGateway, FleetMember
from containerpilot_tpu.fleet.gateway import Replica
from containerpilot_tpu.utils.http import (
    HTTPServer,
    Response,
    StreamingResponse,
)


def _counter(metric, label: str) -> float:
    return metric.labels(label)._value.get()  # noqa: SLF001


def _post(port, path, payload, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read().decode(), dict(resp.headers)
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read().decode(), dict(exc.headers)


def _get(port, path, timeout=30):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read().decode(), dict(resp.headers)
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read().decode(), dict(exc.headers)


def _register(backend, instance_id, port, name="svc"):
    backend.service_register(
        ServiceRegistration(
            id=instance_id, name=name, port=port, ttl=60,
            address="127.0.0.1",
        ),
        status="passing",
    )


# -- routing units (no servers, no JAX) ---------------------------------


def test_least_outstanding_pick_is_deterministic():
    gw = FleetGateway(NoopBackend(), "svc")
    gw._replicas = {
        "a": Replica("a", "h", 1, outstanding=2),
        "b": Replica("b", "h", 2, outstanding=0),
        "c": Replica("c", "h", 3, outstanding=1),
    }
    assert gw._pick().id == "b"
    assert gw._pick(exclude={"b"}).id == "c"
    assert gw._pick(exclude={"a", "b", "c"}) is None
    # ties break on id, so equal load routes reproducibly
    gw._replicas["b"].outstanding = 1
    assert gw._pick().id == "b"


def test_sticky_affinity_and_drained_away_accounting():
    gw = FleetGateway(NoopBackend(), "svc", affinity="session")
    gw._replicas = {
        "a": Replica("a", "h", 1),
        "b": Replica("b", "h", 2),
    }
    first = gw._route("s:user1")
    # load elsewhere must not move a sticky key
    other_id = "b" if first.id == "a" else "a"
    gw._replicas[other_id].outstanding = 0
    gw._replicas[first.id].outstanding = 5
    assert gw._route("s:user1").id == first.id
    # a pin excluded by one request's retry re-routes THAT request
    # but keeps the pin (warm prefix cache survives a transient
    # failure) and does NOT count as drained_away
    assert gw._route("s:user1", exclude={first.id}).id == other_id
    assert gw._sticky["s:user1"] == first.id
    assert _counter(gw._m_drained, first.id) == 0
    # …but a replica that LEFT the fleet re-pins and counts
    del gw._replicas[first.id]
    rerouted = gw._route("s:user1")
    assert rerouted.id == other_id
    assert gw._sticky["s:user1"] == other_id
    assert _counter(gw._m_drained, first.id) == 1
    # keyless requests never stick
    assert gw._route(None).id == other_id


def test_affinity_key_extraction_modes():
    from containerpilot_tpu.utils.http import Request

    def req(headers=None):
        return Request("POST", "/v1/generate", {}, headers or {}, b"")

    session_gw = FleetGateway(NoopBackend(), "svc", affinity="session")
    prefix_gw = FleetGateway(NoopBackend(), "svc", affinity="prefix")
    none_gw = FleetGateway(NoopBackend(), "svc", affinity="none")

    body = {"session_id": "u1", "tokens": [[1, 2, 3]]}
    assert session_gw._affinity_key(req(), body) == "s:u1"
    assert none_gw._affinity_key(req(), body) is None
    # header beats prompt-derived keys, loses to session_id
    assert session_gw._affinity_key(
        req({"x-affinity-key": "k9"}), {}
    ) == "h:k9"
    # prefix mode: same token prefix -> same key; different -> different
    k1 = prefix_gw._affinity_key(req(), {"tokens": [[1, 2, 3]]})
    k2 = prefix_gw._affinity_key(req(), {"tokens": [[1, 2, 3]]})
    k3 = prefix_gw._affinity_key(req(), {"tokens": [[9, 9, 9]]})
    assert k1 == k2 and k1 != k3 and k1.startswith("p:")
    # session mode does NOT key on prompts (every unique prompt would
    # otherwise occupy a sticky slot)
    assert session_gw._affinity_key(req(), {"tokens": [[1, 2, 3]]}) is None


def test_cache_aware_pick_prefers_warm_within_slack():
    """A replica advertising the request's prefix fingerprint wins
    the pick — but only within cache_slack of the least load, so a
    warm-but-loaded replica never beats a healthy cold one."""
    gw = FleetGateway(NoopBackend(), "svc", cache_slack=2)
    fp = 0xBEEF
    gw._replicas = {
        "a": Replica("a", "h", 1, outstanding=0),
        "b": Replica("b", "h", 2, outstanding=2, digest=frozenset({fp})),
        "c": Replica("c", "h", 3, outstanding=1, digest=frozenset({fp})),
    }
    # no fingerprint: plain least-outstanding
    assert gw._pick().id == "a"
    # warm within slack: least-loaded WARM candidate wins
    assert gw._pick(fp=fp).id == "c"
    assert gw.hint_hits == 1
    # every warm candidate beyond slack: cold pick, counted as a miss
    gw._replicas["b"].outstanding = 3
    gw._replicas["c"].outstanding = 3
    assert gw._pick(fp=fp).id == "a"
    assert gw.hint_misses == 1
    # slack 0 still lets warmth break exact load ties
    tie = FleetGateway(NoopBackend(), "svc", cache_slack=0)
    tie._replicas = {
        "a": Replica("a", "h", 1, outstanding=1),
        "b": Replica("b", "h", 2, outstanding=1, digest=frozenset({fp})),
    }
    assert tie._pick(fp=fp).id == "b"
    # an unknown fingerprint in a digest-publishing fleet is a miss;
    # in a fleet with NO digests at all it is not counted (nothing
    # was in play)
    assert tie._pick(fp=0x1234).id == "a"
    assert tie.hint_misses == 1
    bare = FleetGateway(NoopBackend(), "svc")
    bare._replicas = {"a": Replica("a", "h", 1)}
    assert bare._pick(fp=fp).id == "a"
    assert bare.hint_misses == 0


def test_request_fingerprint_token_rows_only():
    """The gateway fingerprints single token-row bodies exactly the
    way replicas fingerprint cached keys; text prompts and malformed
    bodies keep plain routing (None)."""
    from containerpilot_tpu.kvtier import FP_TOKENS, prefix_fingerprint

    gw = FleetGateway(NoopBackend(), "svc")
    row = list(range(5, 5 + FP_TOKENS + 4))
    assert gw._request_fingerprint(
        {"tokens": [row]}
    ) == prefix_fingerprint(row)
    assert gw._request_fingerprint({"prompt": "text"}) is None
    assert gw._request_fingerprint({"tokens": row}) is None  # flat
    assert gw._request_fingerprint({"tokens": [row, row]}) is None
    assert gw._request_fingerprint({"tokens": [["a"] * 20]}) is None
    assert gw._request_fingerprint(
        {"tokens": [row[: FP_TOKENS - 1]]}
    ) is None
    off = FleetGateway(NoopBackend(), "svc", cache_routing=False)
    assert off._request_fingerprint({"tokens": [row]}) is None


def test_sticky_lru_bound_and_eviction_counter():
    """The sticky table is CAPPED: the oldest pin falls out when a
    new session pins past capacity (it used to grow one entry per
    session forever), and evictions are counted."""
    gw = FleetGateway(NoopBackend(), "svc", sticky_capacity=2)
    gw._replicas = {
        "a": Replica("a", "h", 1),
        "b": Replica("b", "h", 2),
    }
    for n in range(4):
        gw._route(f"s:u{n}")
    assert len(gw._sticky) == 2
    assert gw.sticky_evicted == 2
    assert gw._m_sticky_evicted._value.get() == 2  # noqa: SLF001
    # the survivors are the two newest pins
    assert set(gw._sticky) == {"s:u2", "s:u3"}
    # routing an evicted key simply re-pins (possibly elsewhere);
    # no crash, no drained_away accounting
    assert gw._route("s:u0") is not None
    assert len(gw._sticky) == 2
    with pytest.raises(ValueError):
        FleetGateway(NoopBackend(), "svc", sticky_capacity=0)


def test_apply_notes_updates_kv_state_tolerantly():
    """Heartbeat notes feed routing state: kv= counters and the pd=
    digest parse tolerantly, same-version digests don't churn, and a
    torn note never blanks a warm advertisement."""
    from containerpilot_tpu.kvtier import encode_fingerprints

    gw = FleetGateway(NoopBackend(), "svc")
    r = Replica("a", "h", 1)
    digest = encode_fingerprints(3, {0xAA, 0xBB})
    gw._apply_notes(r, f"ok occ=0.50 kv=4,2,96,1,1 pd={digest}")
    assert r.kv["tokens_reused"] == 96 and r.kv["hits"] == 4
    assert r.digest == frozenset({0xAA, 0xBB})
    assert r.digest_version == 3 and r.digest_at > 0
    stamp = r.digest_at
    # same version: no re-parse churn, stamp untouched
    gw._apply_notes(r, f"ok kv=5,2,97,1,1 pd={digest}")
    assert r.digest_at == stamp and r.kv["hits"] == 5
    # a digest-free or garbage note keeps the previous advertisement,
    # and a torn/malformed kv= must NOT regress the cumulative
    # counters (a zeroed tokens_reused parked by a departure would
    # permanently drop the replica from the fleet-wide gauge)
    gw._apply_notes(r, "ok occ=0.75")
    gw._apply_notes(r, "ok pd=garbage kv=nonsense")
    gw._apply_notes(r, "ok kv=5,2,")      # torn mid-value
    gw._apply_notes(r, "ok kv=5,2,9,1,1")  # truncated digit: 97 -> 9
    assert r.digest == frozenset({0xAA, 0xBB})
    assert r.kv == {
        "hits": 5, "misses": 2, "tokens_reused": 97,
        "spilled": 1, "readmitted": 1,
    }
    # a new version replaces the set
    gw._apply_notes(r, f"ok pd={encode_fingerprints(4, {0xCC})}")
    assert r.digest == frozenset({0xCC}) and r.digest_version == 4


def test_pick_excludes_standby_role():
    """A standby-role replica is warm, catalog-visible capacity that
    the router must NEVER choose — even when it is the least loaded —
    until its post-promotion beat drops the role field."""
    gw = FleetGateway(NoopBackend(), "svc")
    gw._replicas = {
        "a": Replica("a", "h", 1, outstanding=5),
        "sb": Replica("sb", "h", 2, outstanding=0, role="standby"),
    }
    assert gw._pick().id == "a"  # idle standby loses to loaded active
    gw._replicas["a"].role = "standby"
    assert gw._pick() is None    # all-standby fleet routes nowhere
    # promotion (role field absent from the next note) restores it
    gw._apply_notes(gw._replicas["sb"], "ok occ=0.00")
    assert gw._pick().id == "sb"


def test_apply_notes_parses_role_and_compile_cache():
    """role= rides every standby beat and is absent from active
    beats (promotion flips by omission); cc= is kept raw for /fleet
    and adoption; garbage roles default to active."""
    gw = FleetGateway(NoopBackend(), "svc")
    r = Replica("a", "h", 1)
    assert r.role == "active"
    gw._apply_notes(r, "ok occ=0.00 role=standby cc=ab12:%2Ftmp%2Fcc")
    assert r.role == "standby"
    assert r.compile_cache == "ab12:%2Ftmp%2Fcc"
    # a TORN/empty note must keep the previous role: flipping a
    # standby routable off a half-written record would route a poll
    # interval of traffic into its 503s
    gw._apply_notes(r, "")
    gw._apply_notes(r, "ok")
    assert r.role == "standby"
    # the first post-promotion beat has no role field but DID parse
    # (a real beat always carries occ=): active by omission
    gw._apply_notes(r, "ok occ=0.10")
    assert r.role == "active"
    assert r.compile_cache == "ab12:%2Ftmp%2Fcc"  # sticky until replaced
    gw._apply_notes(r, "ok role=gibberish")
    assert r.role == "active"


def test_standby_member_note_and_gateway_capacity(run, tmp_path):
    """Live wiring: a FleetMember fronting a standby-role stub
    advertises role=standby (and cc=) through its TTL beat; the
    gateway's poll excludes it from admission capacity and routing
    while listing it on /fleet — and promotion (role attr flip +
    next beat) brings capacity and routability back."""
    backend = FileCatalogBackend(str(tmp_path / "catalog"))

    class _RoleStub(_StubReplica):
        def __init__(self):
            super().__init__()
            self.role = "standby"

        def compile_cache_note(self):
            return "beef:%2Ftmp%2Fcc"

    async def scenario():
        active = _StubReplica()
        standby = _RoleStub()
        m1 = FleetMember(
            active, backend, "svc", ttl=5, heartbeat_interval=0.05,
            instance_id="r-active",
        )
        m2 = FleetMember(
            standby, backend, "svc", ttl=5, heartbeat_interval=0.05,
            instance_id="r-standby",
        )
        await m1.start()
        await m2.start()
        gw = FleetGateway(
            backend, "svc", "127.0.0.1", 0, poll_interval=0.05,
            admission={"per_replica_inflight": 2},
        )
        await gw.run()
        for _ in range(100):
            if (
                gw.replica_count == 2
                and gw._replicas.get("r-standby") is not None
                and gw._replicas["r-standby"].role == "standby"
            ):
                break
            await asyncio.sleep(0.05)
        assert gw._replicas["r-standby"].role == "standby"
        assert gw._replicas["r-standby"].compile_cache.startswith(
            "beef:"
        )
        # routing: only the active replica is ever picked
        assert gw._pick().id == "r-active"
        # admission capacity: 1 active x 2 inflight, standby excluded
        assert gw._admission.capacity == 2
        # /fleet shows the parked capacity
        status = json.loads(
            (await gw._fleet_status(None)).body
        )
        assert status["standby"] == {
            "count": 1, "ids": ["r-standby"],
        }
        roles = {
            r["id"]: r["role"] for r in status["replicas"]
        }
        assert roles == {
            "r-active": "active", "r-standby": "standby",
        }
        # promote: flip the role; the next beat drops the field and
        # the next poll folds the capacity in
        standby.role = "active"
        for _ in range(100):
            if gw._admission.capacity == 4:
                break
            await asyncio.sleep(0.05)
        assert gw._admission.capacity == 4
        assert gw._replicas["r-standby"].role == "active"
        await gw.stop()
        await m1.stop()
        await m2.stop()

    run(scenario(), timeout=60)


def test_fleet_tokens_reused_survives_replica_departure(run, tmp_path):
    """The fleet-wide tokens_reused gauge folds a departed replica's
    final advertised counter into _reuse_departed instead of
    forgetting it when the record leaves the catalog."""
    backend = FileCatalogBackend(str(tmp_path))

    async def scenario():
        gw = FleetGateway(
            backend, "svc", poll_interval=0.05, empty_poll_threshold=1
        )
        for rid, port in (("r1", 1001), ("r2", 1002)):
            backend.service_register(
                ServiceRegistration(
                    id=rid, name="svc", port=port, ttl=60,
                    address="127.0.0.1",
                ),
                status="passing",
            )
            backend.update_ttl(rid, "ok occ=0.10 kv=1,0,50,0,0", "pass")
        await gw._poll_once()
        assert gw._fleet_tokens_reused() == 100
        assert gw._replicas["r1"].kv["tokens_reused"] == 50
        # r1 leaves the fleet (drain/crash): its contribution stays
        backend.service_deregister("r1")
        backend.update_ttl("r2", "ok occ=0.10 kv=2,0,75,0,0", "pass")
        await gw._poll_once()
        assert set(gw._replicas) == {"r2"}
        assert gw._fleet_tokens_reused() == 50 + 75
        # r1 FLAPS BACK (wedge heal / TTL-starved heartbeat) with its
        # cumulative counter intact: the parked departed copy must be
        # reclaimed, not double-counted
        backend.service_register(
            ServiceRegistration(
                id="r1", name="svc", port=1001, ttl=60,
                address="127.0.0.1",
            ),
            status="passing",
        )
        backend.update_ttl("r1", "ok occ=0.10 kv=1,0,50,0,0", "pass")
        await gw._poll_once()
        assert set(gw._replicas) == {"r1", "r2"}
        assert gw._fleet_tokens_reused() == 50 + 75
        return True

    assert run(scenario())


def test_hedge_threshold_is_learned_per_endpoint():
    """Millisecond /v1/score samples must not set the hedge deadline
    for second-long /v1/generate requests (and vice versa)."""
    from collections import deque

    gw = FleetGateway(NoopBackend(), "svc", hedge_min_ms=1.0)
    gw._replicas = {
        "a": Replica("a", "h", 1),
        "b": Replica("b", "h", 2),
    }
    gw._latencies["score"] = deque([0.002] * 30)
    # no generate samples yet -> no basis to hedge generate
    assert gw._hedge_threshold("generate") is None
    gw._latencies["generate"] = deque([0.5] * 30)
    assert gw._hedge_threshold("generate") >= 0.5
    assert gw._hedge_threshold("score") < 0.01
    # hedging needs somewhere to hedge TO
    del gw._replicas["b"]
    assert gw._hedge_threshold("generate") is None


# -- gateway behavior against stub replicas (no JAX) --------------------


def test_gateway_retries_on_a_different_replica(run, tmp_path):
    """A 503 from the first-picked replica (draining/warming) moves
    the request to another replica; the client sees only the 200."""
    backend = FileCatalogBackend(str(tmp_path))
    calls = {"aaa": 0, "bbb": 0}

    async def scenario():
        draining, healthy = HTTPServer(), HTTPServer()

        async def handler_draining(_req):
            calls["aaa"] += 1
            return Response(
                503, b"draining\n", headers={"Retry-After": "1"}
            )

        async def handler_healthy(_req):
            calls["bbb"] += 1
            return Response(
                200, json.dumps({"tokens": [[9]]}).encode(),
                content_type="application/json",
            )

        draining.route("POST", "/v1/generate", handler_draining)
        healthy.route("POST", "/v1/generate", handler_healthy)
        await draining.start_tcp("127.0.0.1", 0)
        await healthy.start_tcp("127.0.0.1", 0)
        # ids chosen so the load tie breaks to the draining replica
        _register(backend, "aaa", draining.bound_port)
        _register(backend, "bbb", healthy.bound_port)
        gw = FleetGateway(
            backend, "svc", "127.0.0.1", 0,
            poll_interval=0.2, hedge=False, retry_backoff=0.01,
        )
        await gw.run()
        assert gw.replica_count == 2
        status, text, _ = await asyncio.get_event_loop().run_in_executor(
            None, _post, gw.port, "/v1/generate",
            {"tokens": [[1]], "max_new_tokens": 2},
        )
        retried = _counter(gw._m_retried, "aaa")
        await gw.stop()
        await draining.stop()
        await healthy.stop()
        return status, text, retried

    status, text, retried = run(scenario(), timeout=60)
    assert status == 200 and json.loads(text)["tokens"] == [[9]]
    assert calls == {"aaa": 1, "bbb": 1}
    assert retried == 1


def test_gateway_exhausted_retries_surface_503_with_retry_after(
    run, tmp_path
):
    backend = FileCatalogBackend(str(tmp_path))

    async def scenario():
        gw = FleetGateway(
            backend, "svc", "127.0.0.1", 0, poll_interval=5.0,
        )
        await gw.run()  # catalog is empty: no replicas at all
        status, _text, headers = (
            await asyncio.get_event_loop().run_in_executor(
                None, _post, gw.port, "/v1/generate", {"tokens": [[1]]},
            )
        )
        health = await asyncio.get_event_loop().run_in_executor(
            None, _get, gw.port, "/health"
        )
        await gw.stop()
        return status, headers, health

    status, headers, health = run(scenario(), timeout=60)
    assert status == 503
    assert {k.lower(): v for k, v in headers.items()}["retry-after"]
    assert health[0] == 503


def test_gateway_hedges_slow_replica_and_takes_the_fast_result(
    run, tmp_path
):
    """A request still unanswered at the hedge deadline races a second
    replica; the fast replica's answer wins and the slow dispatch is
    cancelled (its connection drops)."""
    backend = FileCatalogBackend(str(tmp_path))

    async def scenario():
        slow, fast = HTTPServer(), HTTPServer()

        async def handler_slow(_req):
            await asyncio.sleep(1.0)
            return Response(200, b'{"who": "slow"}',
                            content_type="application/json")

        async def handler_fast(_req):
            return Response(200, b'{"who": "fast"}',
                            content_type="application/json")

        slow.route("POST", "/v1/generate", handler_slow)
        fast.route("POST", "/v1/generate", handler_fast)
        await slow.start_tcp("127.0.0.1", 0)
        await fast.start_tcp("127.0.0.1", 0)
        _register(backend, "aaa", slow.bound_port)  # tie -> slow first
        _register(backend, "bbb", fast.bound_port)
        gw = FleetGateway(
            backend, "svc", "127.0.0.1", 0,
            poll_interval=5.0, retries=0, hedge_after_ms=80.0,
        )
        await gw.run()
        t0 = time.perf_counter()
        status, text, _ = await asyncio.get_event_loop().run_in_executor(
            None, _post, gw.port, "/v1/generate", {"tokens": [[1]]},
        )
        elapsed = time.perf_counter() - t0
        hedged = _counter(gw._m_hedged, "aaa")
        routed_fast = _counter(gw._m_routed, "bbb")
        await gw.stop()
        await slow.stop()
        await fast.stop()
        return status, text, elapsed, hedged, routed_fast

    status, text, elapsed, hedged, routed_fast = run(
        scenario(), timeout=60
    )
    assert status == 200 and json.loads(text)["who"] == "fast"
    assert elapsed < 0.8, f"hedge did not preempt the slow replica: {elapsed}"
    assert hedged == 1 and routed_fast == 1


# -- gateway connection pool (stub replicas, no JAX) --------------------


def test_gateway_pool_reuses_connections_across_requests(run, tmp_path):
    """Sequential buffered requests ride ONE upstream connection: the
    replica accepts a single connection, the pool counts one miss and
    the rest hits, and /fleet + /metrics expose the counters."""
    backend = FileCatalogBackend(str(tmp_path))

    async def scenario():
        replica = HTTPServer()

        async def handler(_req):
            return Response(
                200, json.dumps({"tokens": [[7]]}).encode(),
                content_type="application/json",
            )

        replica.route("POST", "/v1/generate", handler)
        await replica.start_tcp("127.0.0.1", 0)
        _register(backend, "aaa", replica.bound_port)
        # mux=False: this suite pins the CLASSIC pooled discipline,
        # which stays the fallback for replicas that decline the
        # cp-mux upgrade (the mux paths have their own suite)
        gw = FleetGateway(
            backend, "svc", "127.0.0.1", 0, poll_interval=5.0,
            hedge=False, mux=False,
        )
        await gw.run()
        loop = asyncio.get_event_loop()
        for _ in range(4):
            status, _text, _ = await loop.run_in_executor(
                None, _post, gw.port, "/v1/generate", {"tokens": [[1]]},
            )
            assert status == 200
        fleet_view = await loop.run_in_executor(
            None, _get, gw.port, "/fleet"
        )
        metrics = await loop.run_in_executor(
            None, _get, gw.port, "/metrics"
        )
        stats = gw._pool.stats("aaa")  # noqa: SLF001
        accepted = replica.connections_accepted
        served = replica.requests_served
        await gw.stop()
        await replica.stop()
        return stats, accepted, served, fleet_view, metrics

    stats, accepted, served, fleet_view, metrics = run(
        scenario(), timeout=60
    )
    assert accepted == 1 and served == 4  # one dial, four requests
    assert stats["misses"] == 1 and stats["hits"] == 3
    assert stats["idle"] == 1  # the warm connection went back
    pool_view = {
        r["id"]: r["pool"]
        for r in json.loads(fleet_view[1])["replicas"]
    }
    assert pool_view["aaa"]["hits"] == 3
    assert (
        'containerpilot_gateway_pool_hit_total{replica="aaa"} 3.0'
        in metrics[1]
    )
    assert (
        'containerpilot_gateway_pool_miss_total{replica="aaa"} 1.0'
        in metrics[1]
    )


def test_gateway_pool_evicts_on_deregister(run, tmp_path):
    """Pooled connections to a replica that left the healthy set
    (drain deregisters it) are evicted at the next poll, never
    reused."""
    backend = FileCatalogBackend(str(tmp_path))

    async def scenario():
        replica = HTTPServer()

        async def handler(_req):
            return Response(200, b"{}", content_type="application/json")

        replica.route("POST", "/v1/generate", handler)
        await replica.start_tcp("127.0.0.1", 0)
        _register(backend, "aaa", replica.bound_port)
        gw = FleetGateway(
            backend, "svc", "127.0.0.1", 0, poll_interval=0.1,
            hedge=False, mux=False,
        )
        await gw.run()
        loop = asyncio.get_event_loop()
        status, _, _ = await loop.run_in_executor(
            None, _post, gw.port, "/v1/generate", {"tokens": [[1]]},
        )
        assert status == 200
        assert gw._pool.idle_count("aaa") == 1  # noqa: SLF001
        backend.service_deregister("aaa")
        for _ in range(100):
            if gw.replica_count == 0:
                break
            await asyncio.sleep(0.05)
        idle = gw._pool.idle_count("aaa")  # noqa: SLF001
        evicted = gw._pool.evicted.get("aaa", 0)  # noqa: SLF001
        await gw.stop()
        await replica.stop()
        return idle, evicted

    idle, evicted = run(scenario(), timeout=60)
    assert idle == 0 and evicted == 1


def test_gateway_pool_redials_stale_connection_transparently(
    run, tmp_path
):
    """A pooled connection the replica reaped while idle is detected
    and redialed without the client seeing a failure."""
    backend = FileCatalogBackend(str(tmp_path))

    async def scenario():
        replica = HTTPServer()
        replica.KEEPALIVE_IDLE_TIMEOUT = 0.15

        async def handler(_req):
            return Response(200, b"{}", content_type="application/json")

        replica.route("POST", "/v1/generate", handler)
        await replica.start_tcp("127.0.0.1", 0)
        _register(backend, "aaa", replica.bound_port)
        gw = FleetGateway(
            backend, "svc", "127.0.0.1", 0, poll_interval=5.0,
            hedge=False, mux=False,
        )
        await gw.run()
        loop = asyncio.get_event_loop()
        first, _, _ = await loop.run_in_executor(
            None, _post, gw.port, "/v1/generate", {"tokens": [[1]]},
        )
        await asyncio.sleep(0.4)  # let the replica reap the idle conn
        second, _, _ = await loop.run_in_executor(
            None, _post, gw.port, "/v1/generate", {"tokens": [[1]]},
        )
        stats = gw._pool.stats("aaa")  # noqa: SLF001
        retried = _counter(gw._m_retried, "aaa")  # noqa: SLF001
        await gw.stop()
        await replica.stop()
        return first, second, stats, retried

    first, second, stats, retried = run(scenario(), timeout=60)
    assert first == 200 and second == 200
    # the reap voided the pooled connection: two dials total, the
    # stale one evicted, and NO routing-level retry was consumed
    assert stats["misses"] == 2 and stats["hits"] == 0
    assert stats["evicted"] >= 1
    assert retried == 0


def test_hedge_legs_take_distinct_connections(run, tmp_path):
    """The losing hedge leg's connection is discarded (it may carry a
    half-written response), never pooled; the winner's goes back."""
    backend = FileCatalogBackend(str(tmp_path))

    async def scenario():
        slow, fast = HTTPServer(), HTTPServer()

        async def handler_slow(_req):
            await asyncio.sleep(1.0)
            return Response(200, b'{"who": "slow"}',
                            content_type="application/json")

        async def handler_fast(_req):
            return Response(200, b'{"who": "fast"}',
                            content_type="application/json")

        slow.route("POST", "/v1/generate", handler_slow)
        fast.route("POST", "/v1/generate", handler_fast)
        await slow.start_tcp("127.0.0.1", 0)
        await fast.start_tcp("127.0.0.1", 0)
        _register(backend, "aaa", slow.bound_port)  # tie -> slow first
        _register(backend, "bbb", fast.bound_port)
        gw = FleetGateway(
            backend, "svc", "127.0.0.1", 0,
            poll_interval=5.0, retries=0, hedge_after_ms=80.0,
            mux=False,
        )
        await gw.run()
        status, text, _ = await asyncio.get_event_loop().run_in_executor(
            None, _post, gw.port, "/v1/generate", {"tokens": [[1]]},
        )
        idle_slow = gw._pool.idle_count("aaa")  # noqa: SLF001
        idle_fast = gw._pool.idle_count("bbb")  # noqa: SLF001
        dials = (slow.connections_accepted, fast.connections_accepted)
        await gw.stop()
        await slow.stop()
        await fast.stop()
        return status, text, idle_slow, idle_fast, dials

    status, text, idle_slow, idle_fast, dials = run(
        scenario(), timeout=60
    )
    assert status == 200 and json.loads(text)["who"] == "fast"
    assert dials == (1, 1)  # one private connection per leg
    assert idle_slow == 0  # cancelled leg: discarded, not pooled
    assert idle_fast == 1  # winning leg: released for reuse


# -- satellite bugfixes: upstream response parsing ----------------------


def test_content_length_parsed_strictly():
    """int() and str.isdigit() both accept Unicode digits; the parser
    must not — and garbage must raise instead of silently switching
    to read-to-EOF framing."""
    from containerpilot_tpu.fleet.gateway import (
        UpstreamError,
        _parse_content_length,
    )

    assert _parse_content_length({"content-length": "42"}) == 42
    assert _parse_content_length({}) is None
    for bad in ("١٢٣", "12abc", "-1", "+5", "", "4 2"):
        with pytest.raises(UpstreamError):
            _parse_content_length({"content-length": bad})


async def _raw_replica(respond: bytes):
    """A server that reads one full request, writes ``respond``
    verbatim, and closes — for malformed-upstream scenarios a real
    HTTPServer can't produce."""
    hits = []

    async def handle(reader, writer):
        head = await reader.readuntil(b"\r\n\r\n")
        length = 0
        for line in head.split(b"\r\n"):
            if line.lower().startswith(b"content-length:"):
                length = int(line.split(b":")[1])
        if length:
            await reader.readexactly(length)
        hits.append(1)
        writer.write(respond)
        await writer.drain()
        writer.close()

    server = await asyncio.start_server(handle, "127.0.0.1", 0)
    return server, server.sockets[0].getsockname()[1], hits


def test_replica_dying_after_status_line_is_retried(run, tmp_path):
    """EOF inside the response header block is an UpstreamError (not
    an empty-header 'success'), so the retry path fires and the
    client still gets a 200 from the healthy replica."""
    backend = FileCatalogBackend(str(tmp_path))

    async def scenario():
        broken, broken_port, hits = await _raw_replica(
            b"HTTP/1.1 200 OK\r\n"  # dies mid-header-block
        )
        healthy = HTTPServer()

        async def handler(_req):
            return Response(
                200, json.dumps({"tokens": [[9]]}).encode(),
                content_type="application/json",
            )

        healthy.route("POST", "/v1/generate", handler)
        await healthy.start_tcp("127.0.0.1", 0)
        _register(backend, "aaa", broken_port)  # tie -> broken first
        _register(backend, "bbb", healthy.bound_port)
        gw = FleetGateway(
            backend, "svc", "127.0.0.1", 0,
            poll_interval=5.0, hedge=False, retry_backoff=0.01,
            mux=False,  # pins the HTTP/1.1 response-parsing path
        )
        await gw.run()
        status, text, _ = await asyncio.get_event_loop().run_in_executor(
            None, _post, gw.port, "/v1/generate", {"tokens": [[1]]},
        )
        retried = _counter(gw._m_retried, "aaa")
        await gw.stop()
        broken.close()
        await broken.wait_closed()
        await healthy.stop()
        return status, text, retried, len(hits)

    status, text, retried, hits = run(scenario(), timeout=60)
    assert status == 200 and json.loads(text)["tokens"] == [[9]]
    assert hits == 1 and retried == 1


def test_malformed_content_length_is_retried(run, tmp_path):
    """Garbage Content-Length fails the leg (UpstreamError) instead
    of silently mis-framing the body as read-to-EOF."""
    backend = FileCatalogBackend(str(tmp_path))

    async def scenario():
        broken, broken_port, hits = await _raw_replica(
            b"HTTP/1.1 200 OK\r\nContent-Length: 12abc\r\n\r\nhello"
        )
        healthy = HTTPServer()

        async def handler(_req):
            return Response(
                200, json.dumps({"tokens": [[9]]}).encode(),
                content_type="application/json",
            )

        healthy.route("POST", "/v1/generate", handler)
        await healthy.start_tcp("127.0.0.1", 0)
        _register(backend, "aaa", broken_port)
        _register(backend, "bbb", healthy.bound_port)
        gw = FleetGateway(
            backend, "svc", "127.0.0.1", 0,
            poll_interval=5.0, hedge=False, retry_backoff=0.01,
            mux=False,  # pins the HTTP/1.1 response-parsing path
        )
        await gw.run()
        status, text, _ = await asyncio.get_event_loop().run_in_executor(
            None, _post, gw.port, "/v1/generate", {"tokens": [[1]]},
        )
        retried = _counter(gw._m_retried, "aaa")
        await gw.stop()
        broken.close()
        await broken.wait_closed()
        await healthy.stop()
        return status, text, retried

    status, text, retried = run(scenario(), timeout=60)
    assert status == 200 and json.loads(text)["tokens"] == [[9]]
    assert retried == 1


# -- satellite: filecatalog robustness ----------------------------------


def test_filecatalog_listing_survives_torn_and_leftover_records(tmp_path):
    """Torn JSON (partial NFS write), writer scratch files, and
    records missing required keys are skipped as critical — never an
    exception that hides the healthy peers next to them."""
    backend = FileCatalogBackend(str(tmp_path))
    _register(backend, "good", 8001)
    sdir = tmp_path / "services" / "svc"
    (sdir / "torn.json").write_text('{"id": "torn", "na')
    (sdir / "scratch.json.tmp").write_text("{}")
    (sdir / "nokeys.json").write_text(
        json.dumps({"status": "passing", "expires": time.time() + 60})
    )
    (sdir / "notdict.json").write_text("[1, 2, 3]")
    (sdir / "badport.json").write_text(json.dumps({
        "id": "badport", "name": "svc", "port": "eighty",
        "status": "passing", "expires": time.time() + 60,
    }))
    instances = backend.instances("svc")
    assert [i.id for i in instances] == ["good"]
    did_change, healthy = backend.check_for_upstream_changes("svc")
    assert healthy


# -- member lifecycle (stub server, no JAX) -----------------------------


class _StubReplica:
    """Duck-types the InferenceServer drain surface."""

    def __init__(self):
        self.ready = True
        self.draining = False
        self.inflight = 0
        self.port = 4242

    def enter_maintenance(self):
        self.draining = True

    def exit_maintenance(self):
        self.draining = False


def test_member_heartbeats_and_ttl_expiry(run, tmp_path):
    backend = FileCatalogBackend(str(tmp_path))

    async def scenario():
        stub = _StubReplica()
        member = FleetMember(
            stub, backend, "svc", ttl=1, heartbeat_interval=0.05,
            instance_id="r1",
        )
        await member.start()
        for _ in range(100):
            if backend.instances("svc"):
                break
            await asyncio.sleep(0.02)
        assert [i.id for i in backend.instances("svc")] == ["r1"]
        # a replica that stops being ready stops beating; the record
        # flips critical by TTL expiry, like a wedged job
        stub.ready = False
        await asyncio.sleep(1.3)
        assert backend.instances("svc") == []
        # recovery: ready again -> next heartbeat revives the record
        stub.ready = True
        for _ in range(100):
            if backend.instances("svc"):
                break
            await asyncio.sleep(0.02)
        assert [i.id for i in backend.instances("svc")] == ["r1"]
        await member.stop()
        assert backend.instances("svc") == []

    run(scenario(), timeout=60)


def test_member_drains_via_control_plane(run, tmp_path):
    """POST /v3/maintenance/enable on the control socket drains the
    replica: maintenance flag set, catalog record gone; disable
    resumes and the next heartbeat re-registers."""
    from containerpilot_tpu.client import ControlClient
    from containerpilot_tpu.control import ControlConfig, ControlServer
    from containerpilot_tpu.events import EventBus

    socket_path = str(tmp_path / "cp.sock")
    backend = FileCatalogBackend(str(tmp_path / "catalog"))

    async def scenario():
        bus = EventBus()
        control = ControlServer(ControlConfig({"socket": socket_path}))
        await control.run(bus)
        stub = _StubReplica()
        member = FleetMember(
            stub, backend, "svc", ttl=2, heartbeat_interval=0.05,
            instance_id="r1",
        )
        await member.start()
        member.attach_bus(bus)
        loop = asyncio.get_event_loop()
        client = ControlClient(socket_path)
        for _ in range(100):
            if backend.instances("svc"):
                break
            await asyncio.sleep(0.02)
        assert backend.instances("svc")

        await loop.run_in_executor(None, client.set_maintenance, True)
        for _ in range(100):
            if stub.draining and not backend.instances("svc"):
                break
            await asyncio.sleep(0.02)
        assert stub.draining
        assert backend.instances("svc") == []
        assert await loop.run_in_executor(
            None, client.get_maintenance_status
        )

        await loop.run_in_executor(None, client.set_maintenance, False)
        for _ in range(100):
            if not stub.draining and backend.instances("svc"):
                break
            await asyncio.sleep(0.02)
        assert not stub.draining
        assert backend.instances("svc")

        await member.stop()
        await control.stop()

    run(scenario(), timeout=60)


# -- serve.py drain hook (tiny model, CPU) ------------------------------


def test_inference_server_drain_hook(run):
    import jax
    import jax.numpy as jnp

    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=32, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    server = InferenceServer(cfg, params, "127.0.0.1", 0, max_len=32)

    async def scenario():
        loop = asyncio.get_event_loop()
        await server.run()
        body = {"tokens": [[1, 2, 3]], "max_new_tokens": 4}
        before = await loop.run_in_executor(
            None, _post, server.port, "/v1/generate", body
        )
        server.enter_maintenance()
        health = await loop.run_in_executor(
            None, _get, server.port, "/health"
        )
        rejected = await loop.run_in_executor(
            None, _post, server.port, "/v1/generate", body
        )
        # reads stay up for the replica's last consumers
        model = await loop.run_in_executor(
            None, _get, server.port, "/v1/model"
        )
        score = await loop.run_in_executor(
            None, _post, server.port, "/v1/score",
            {"tokens": [[1, 2, 3, 4]]},
        )
        server.exit_maintenance()
        after = await loop.run_in_executor(
            None, _post, server.port, "/v1/generate", body
        )
        await server.stop()
        return before, health, rejected, model, score, after

    before, health, rejected, model, score, after = run(
        scenario(), timeout=300
    )
    assert before[0] == 200
    assert health[0] == 503 and "draining" in health[1]
    assert rejected[0] == 503
    assert {k.lower(): v for k, v in rejected[2].items()}["retry-after"]
    assert model[0] == 200 and json.loads(model[1])["draining"] is True
    assert score[0] == 200
    assert after[0] == 200
    assert server.inflight == 0


# -- the tier-1 integration scenario ------------------------------------


def test_fleet_gateway_drain_mid_traffic_zero_5xx(run, tmp_path):
    """Two replicas behind the gateway; one drains mid-traffic. Every
    client request completes 200 (the drain 503s are absorbed by
    retry-on-another-replica), the drained replica leaves the healthy
    set immediately, and SSE streaming keeps working through the
    gateway afterwards."""
    import jax
    import jax.numpy as jnp

    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=64, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    replica1 = InferenceServer(
        cfg, params, "127.0.0.1", 0, max_len=64, slots=2, slot_chunk=4
    )
    replica2 = InferenceServer(
        cfg, params, "127.0.0.1", 0, max_len=64, slots=2, slot_chunk=4
    )
    backend = FileCatalogBackend(str(tmp_path))

    async def scenario():
        loop = asyncio.get_event_loop()
        await replica1.run()
        await replica2.run()
        member1 = FleetMember(
            replica1, backend, "inference", ttl=5,
            heartbeat_interval=0.1, instance_id="replica-1",
        )
        member2 = FleetMember(
            replica2, backend, "inference", ttl=5,
            heartbeat_interval=0.1, instance_id="replica-2",
        )
        await member1.start()
        await member2.start()
        gateway = FleetGateway(
            backend, "inference", "127.0.0.1", 0,
            poll_interval=0.2, hedge=False, retry_backoff=0.01,
        )
        await gateway.run()
        for _ in range(100):
            if gateway.replica_count == 2:
                break
            await asyncio.sleep(0.05)
        assert gateway.replica_count == 2

        results = []

        async def client_loop(worker, n):
            for i in range(n):
                status, text, _ = await loop.run_in_executor(
                    None, _post, gateway.port, "/v1/generate",
                    {
                        "tokens": [[1, 2, 3, 4]],
                        "max_new_tokens": 16,
                        "seed": worker * 100 + i,
                    },
                )
                results.append((status, text))

        clients = [
            asyncio.ensure_future(client_loop(w, 6)) for w in range(3)
        ]
        await asyncio.sleep(0.1)  # let traffic get in flight

        drained = await member1.drain()
        assert drained is True
        assert replica1.draining
        # the drained replica is out of the healthy set immediately
        # (deregistration, not TTL decay): well within one gateway
        # poll interval
        instances = await loop.run_in_executor(
            None, backend.instances, "inference"
        )
        assert [i.id for i in instances] == ["replica-2"]

        await asyncio.gather(*clients)
        assert len(results) == 18
        assert all(status == 200 for status, _ in results), [
            status for status, _ in results
        ]
        for _status, text in results:
            out = json.loads(text)["tokens"]
            assert len(out) == 1 and 1 <= len(out[0]) <= 16

        # the gateway's routing set converges to the one survivor
        for _ in range(50):
            if gateway.replica_count == 1:
                break
            await asyncio.sleep(0.05)
        assert gateway.replica_count == 1

        # SSE streaming through the gateway still works post-drain
        stream_status, stream_text, stream_headers = (
            await loop.run_in_executor(
                None, _post, gateway.port, "/v1/generate",
                {
                    "tokens": [[1, 2, 3, 4]],
                    "max_new_tokens": 8,
                    "stream": True,
                },
            )
        )
        # proxied /v1/model answers from a healthy replica
        model = await loop.run_in_executor(
            None, _get, gateway.port, "/v1/model"
        )
        fleet_view = await loop.run_in_executor(
            None, _get, gateway.port, "/fleet"
        )
        metrics = await loop.run_in_executor(
            None, _get, gateway.port, "/metrics"
        )

        await gateway.stop()
        await member1.stop()
        await member2.stop()
        await replica1.stop()
        await replica2.stop()
        return (
            stream_status, stream_text, stream_headers, model,
            fleet_view, metrics,
        )

    (
        stream_status, stream_text, stream_headers, model,
        fleet_view, metrics,
    ) = run(scenario(), timeout=600)

    assert stream_status == 200
    content_type = {
        k.lower(): v for k, v in stream_headers.items()
    }["content-type"]
    assert "text/event-stream" in content_type
    events = [
        json.loads(line[len("data: "):])
        for line in stream_text.splitlines()
        if line.startswith("data: ")
    ]
    assert events and events[-1].get("done") is True
    streamed = [t for e in events if "tokens" in e for t in e["tokens"]]
    assert len(streamed) == events[-1]["count"] and streamed

    assert model[0] == 200 and "vocab_size" in model[1]
    fleet = json.loads(fleet_view[1])
    assert [r["id"] for r in fleet["replicas"]] == ["replica-2"]
    assert metrics[0] == 200
    # the metrics pipeline recorded the traffic: dispatches to both
    # replicas and the client-visible 200s
    assert 'containerpilot_gateway_routed_total{replica="replica-1"}' in metrics[1]
    assert 'containerpilot_gateway_routed_total{replica="replica-2"}' in metrics[1]
    assert (
        'containerpilot_gateway_requests_total'
        '{code="200",endpoint="generate"}'
    ) in metrics[1]


# -- mux transport through the gateway (stub replicas, no JAX) ----------


def test_mux_hedge_loser_cancelled_not_torn_down(run, tmp_path):
    """PR 8's headline cancel semantics: the losing hedge leg becomes
    a CANCEL frame — counter-pinned — and the slow replica's shared
    connection stays in service for the next request instead of being
    discarded (pre-mux, every hedge loss burned a pooled conn)."""
    backend = FileCatalogBackend(str(tmp_path))

    async def scenario():
        slow, fast = HTTPServer(), HTTPServer()

        async def handler_slow(_req):
            await asyncio.sleep(1.0)
            return Response(200, b'{"who": "slow"}',
                            content_type="application/json")

        async def handler_fast(_req):
            return Response(200, b'{"who": "fast"}',
                            content_type="application/json")

        slow.route("POST", "/v1/generate", handler_slow)
        fast.route("POST", "/v1/generate", handler_fast)
        await slow.start_tcp("127.0.0.1", 0)
        await fast.start_tcp("127.0.0.1", 0)
        _register(backend, "aaa", slow.bound_port)  # tie -> slow first
        _register(backend, "bbb", fast.bound_port)
        gw = FleetGateway(
            backend, "svc", "127.0.0.1", 0,
            poll_interval=5.0, retries=0, hedge_after_ms=80.0,
        )
        await gw.run()
        loop = asyncio.get_event_loop()
        status, text, _ = await loop.run_in_executor(
            None, _post, gw.port, "/v1/generate", {"tokens": [[1]]},
        )
        cancels = _counter(gw._m_mux_cancels, "aaa")  # noqa: SLF001
        saved = _counter(gw._m_conns_saved, "aaa")  # noqa: SLF001
        conns_after_race = slow.connections_accepted
        # the cancelled leg's connection went BACK to service: a
        # follow-up request to the slow replica rides the same socket
        gw._sticky.clear()  # noqa: SLF001
        backend.service_deregister("bbb")
        await gw._poll_once()  # noqa: SLF001
        status2, _text2, _ = await loop.run_in_executor(
            None, _post, gw.port, "/v1/generate", {"tokens": [[1]]},
        )
        conns_after_reuse = slow.connections_accepted
        await gw.stop()
        await slow.stop()
        await fast.stop()
        return (
            status, text, cancels, saved,
            conns_after_race, status2, conns_after_reuse,
        )

    (status, text, cancels, saved, conns_race, status2, conns_reuse) = (
        run(scenario(), timeout=60)
    )
    assert status == 200 and json.loads(text)["who"] == "fast"
    assert cancels == 1 and saved == 1  # the loss was a CANCEL frame
    assert conns_race == 1  # one mux conn carried the losing leg
    assert status2 == 200
    assert conns_reuse == 1  # ...and SURVIVED to carry the next request


def test_dead_mux_conn_fails_streams_once_each_arming_retry(run, tmp_path):
    """A mux connection dying with streams in flight fails each
    exactly once: every request retries to the healthy replica and
    the dead replica saw each body exactly once — no double-dispatch
    of a request the server might have started."""
    backend = FileCatalogBackend(str(tmp_path))

    async def scenario():
        doomed, healthy = HTTPServer(), HTTPServer()
        gate = asyncio.Event()
        hits = {"doomed": 0, "healthy": 0}

        async def handler_doomed(_req):
            hits["doomed"] += 1
            await gate.wait()  # never answers
            return Response(200, b"{}")

        async def handler_healthy(_req):
            hits["healthy"] += 1
            return Response(200, b'{"tokens": [[9]]}',
                            content_type="application/json")

        doomed.route("POST", "/v1/generate", handler_doomed)
        healthy.route("POST", "/v1/generate", handler_healthy)
        await doomed.start_tcp("127.0.0.1", 0)
        await healthy.start_tcp("127.0.0.1", 0)
        # the doomed replica is the whole fleet while the two
        # requests dispatch (least-loaded routing would split a
        # two-replica fleet: the first dispatch already counts)
        _register(backend, "aaa", doomed.bound_port)
        gw = FleetGateway(
            backend, "svc", "127.0.0.1", 0, poll_interval=5.0,
            hedge=False, retry_backoff=0.01, affinity="none",
        )
        await gw.run()
        loop = asyncio.get_event_loop()
        posts = [
            loop.run_in_executor(
                None, _post, gw.port, "/v1/generate",
                {"tokens": [[1]], "i": i},
            )
            for i in range(2)
        ]
        # both streams in flight on the doomed replica's ONE conn
        for _ in range(200):
            if hits["doomed"] == 2:
                break
            await asyncio.sleep(0.01)
        _register(backend, "bbb", healthy.bound_port)
        await gw._poll_once()  # noqa: SLF001 — the retry's target
        await doomed.abort()  # SIGKILL semantics: RST, flush nothing
        results = await asyncio.gather(*posts)
        retried = _counter(gw._m_retried, "aaa")  # noqa: SLF001
        await gw.stop()
        await healthy.stop()
        return results, dict(hits), retried

    results, hits, retried = run(scenario(), timeout=60)
    assert [status for status, _t, _h in results] == [200, 200]
    # each stream failed ONCE and was dispatched exactly once to each
    # side: no silent redispatch onto the dead conn, no double-serve
    assert hits == {"doomed": 2, "healthy": 2}
    assert retried == 2


def test_mux_cold_burst_shares_one_dial(run, tmp_path):
    """N concurrent requests against a COLD gateway share one
    upgrade dial: the replica sees a single connection, not a
    stampede of N sockets racing to become the shared conn."""
    backend = FileCatalogBackend(str(tmp_path))

    async def scenario():
        replica = HTTPServer()

        async def handler(_req):
            await asyncio.sleep(0.05)  # keep the burst overlapping
            return Response(200, b"{}", content_type="application/json")

        replica.route("POST", "/v1/generate", handler)
        await replica.start_tcp("127.0.0.1", 0)
        _register(backend, "aaa", replica.bound_port)
        gw = FleetGateway(
            backend, "svc", "127.0.0.1", 0, poll_interval=5.0,
            hedge=False,
        )
        await gw.run()
        loop = asyncio.get_event_loop()
        results = await asyncio.gather(*[
            loop.run_in_executor(
                None, _post, gw.port, "/v1/generate", {"tokens": [[1]]},
            )
            for _ in range(8)
        ])
        conns = replica.connections_accepted
        streams = replica.mux_streams_served
        await gw.stop()
        await replica.stop()
        return [s for s, _t, _h in results], conns, streams

    statuses, conns, streams = run(scenario(), timeout=60)
    assert statuses == [200] * 8
    assert conns == 1  # one shared dial, no cold-start stampede
    assert streams == 8


def test_mux_stale_connection_redialed_transparently(run, tmp_path):
    """A mux connection the replica reaped while idle is replaced
    without the client seeing a failure and WITHOUT consuming a
    routing retry — the mux mirror of the classic pooled
    stale-redial discipline."""
    backend = FileCatalogBackend(str(tmp_path))

    async def scenario():
        replica = HTTPServer()
        replica.KEEPALIVE_IDLE_TIMEOUT = 0.15

        async def handler(_req):
            return Response(200, b"{}", content_type="application/json")

        replica.route("POST", "/v1/generate", handler)
        await replica.start_tcp("127.0.0.1", 0)
        _register(backend, "aaa", replica.bound_port)
        gw = FleetGateway(
            backend, "svc", "127.0.0.1", 0, poll_interval=5.0,
            hedge=False,
        )
        await gw.run()
        loop = asyncio.get_event_loop()
        first, _, _ = await loop.run_in_executor(
            None, _post, gw.port, "/v1/generate", {"tokens": [[1]]},
        )
        await asyncio.sleep(0.6)  # idle-reap the warm mux conn
        second, _, _ = await loop.run_in_executor(
            None, _post, gw.port, "/v1/generate", {"tokens": [[1]]},
        )
        retried = _counter(gw._m_retried, "aaa")  # noqa: SLF001
        mux_conns = replica.mux_connections
        await gw.stop()
        await replica.stop()
        return first, second, retried, mux_conns

    first, second, retried, mux_conns = run(scenario(), timeout=60)
    assert first == 200 and second == 200
    assert retried == 0  # transparent: no routing-level retry consumed
    assert mux_conns == 2  # the reaped conn was replaced by a redial


def test_mux_sse_abandon_cancels_stream_keeps_connection(run, tmp_path):
    """A downstream client abandoning an SSE relay becomes an
    upstream CANCEL frame: the replica's generator cleanup runs, the
    stream id is freed, and the SAME connection serves the next
    request (pre-mux, a stream always burned its close-delimited
    connection)."""
    backend = FileCatalogBackend(str(tmp_path))

    async def scenario():
        replica = HTTPServer()
        cleaned = asyncio.Event()

        async def sse(_req):
            async def gen():
                try:
                    while True:
                        yield b"data: {\"tick\": 1}\n\n"
                        await asyncio.sleep(0.01)
                finally:
                    cleaned.set()

            return StreamingResponse(gen())

        async def buffered(_req):
            return Response(200, b"{}", content_type="application/json")

        replica.route("POST", "/v1/generate", sse)
        replica.route("POST", "/v1/score", buffered)
        await replica.start_tcp("127.0.0.1", 0)
        _register(backend, "aaa", replica.bound_port)
        gw = FleetGateway(
            backend, "svc", "127.0.0.1", 0, poll_interval=5.0,
            hedge=False,
        )
        await gw.run()
        loop = asyncio.get_event_loop()

        def abandoning_client():
            sock = socket.create_connection(
                ("127.0.0.1", gw.port), timeout=10
            )
            body = b'{"tokens": [[1]], "stream": true}'
            sock.sendall(
                b"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                b"Content-Type: application/json\r\n"
                b"Content-Length: " + str(len(body)).encode()
                + b"\r\n\r\n" + body
            )
            got = b""
            while b"tick" not in got:
                got += sock.recv(65536)
            sock.close()  # hang up mid-stream
            return got

        got = await loop.run_in_executor(None, abandoning_client)
        await asyncio.wait_for(cleaned.wait(), 10)
        for _ in range(200):  # relay close runs after the disconnect
            if _counter(gw._m_mux_cancels, "aaa") > 0:  # noqa: SLF001
                break
            await asyncio.sleep(0.01)
        cancels = _counter(gw._m_mux_cancels, "aaa")  # noqa: SLF001
        # the shared conn survived the abandon: a buffered request
        # rides the same socket
        status, _t, _h = await loop.run_in_executor(
            None, _post, gw.port, "/v1/score", {"tokens": [[1]]},
        )
        conns = replica.connections_accepted
        await gw.stop()
        await replica.stop()
        return got, cancels, status, conns

    got, cancels, status, conns = run(scenario(), timeout=60)
    assert b"tick" in got
    assert cancels == 1  # the abandon became a CANCEL frame
    assert status == 200
    assert conns == 1  # one connection through stream AND next request


def test_member_drain_cycle_racecheck_clean(run, tmp_path):
    """Run the full control-plane drain/resume cycle with the
    racecheck harness watching the bus: no maintenance-path publish
    may happen while an application lock is held (the dynamic analog
    of cpcheck's CP-LOCKPUB, which PRs must keep true as the drain
    path grows)."""
    from containerpilot_tpu.analysis import RaceCheck
    from containerpilot_tpu.events import (
        EventBus,
        GLOBAL_ENTER_MAINTENANCE,
        GLOBAL_EXIT_MAINTENANCE,
    )

    backend = FileCatalogBackend(str(tmp_path / "catalog"))

    async def scenario():
        rc = RaceCheck()
        bus = rc.wrap_bus(EventBus())
        stub = _StubReplica()
        member = FleetMember(
            stub, backend, "svc", ttl=2, heartbeat_interval=0.05,
            instance_id="r1",
        )
        # instrument the REAL locks the drain path crosses, so the
        # harness actually has something to catch: the discovery
        # FIFO-queue lock (taken on both the loop thread and the
        # catalog pool threads) and the bus's internal lock
        member.service._lock = rc.lock("service-queue")  # noqa: SLF001
        bus._lock = rc.rlock("bus-internal")  # noqa: SLF001
        await member.start()
        member.attach_bus(bus)
        for _ in range(100):
            if backend.instances("svc"):
                break
            await asyncio.sleep(0.02)
        assert backend.instances("svc")

        bus.publish(GLOBAL_ENTER_MAINTENANCE)
        for _ in range(100):
            if stub.draining and not backend.instances("svc"):
                break
            await asyncio.sleep(0.02)
        assert stub.draining and backend.instances("svc") == []

        bus.publish(GLOBAL_EXIT_MAINTENANCE)
        for _ in range(100):
            if not stub.draining and backend.instances("svc"):
                break
            await asyncio.sleep(0.02)
        assert not stub.draining and backend.instances("svc")

        await member.stop()
        rc.unwrap()
        rc.assert_clean()

    run(scenario(), timeout=60)


def test_member_heartbeat_survives_transient_exception(run, tmp_path):
    """An exception thrown synchronously inside one beat (here: the
    server's drain-surface property glitching) must not kill the
    heartbeat task — a dead loop would silently TTL-expire a healthy
    replica out of every gateway's routing set."""

    class _GlitchyReplica:
        """Drain surface whose `draining` property raises a few times."""

        def __init__(self):
            self.ready = True
            self.inflight = 0
            self.port = 4242
            self.glitches = 0

        @property
        def draining(self):
            if self.glitches > 0:
                self.glitches -= 1
                raise RuntimeError("transient state glitch")
            return False

    backend = FileCatalogBackend(str(tmp_path / "catalog"))

    async def scenario():
        stub = _GlitchyReplica()
        member = FleetMember(
            stub, backend, "svc", ttl=2, heartbeat_interval=0.05,
            instance_id="r1",
        )
        await member.start()
        for _ in range(100):
            if backend.instances("svc"):
                break
            await asyncio.sleep(0.02)
        assert backend.instances("svc")

        stub.glitches = 3  # three beats in a row blow up
        await asyncio.sleep(0.3)
        assert stub.glitches == 0  # the loop kept beating through them
        assert member._beat_task is not None  # noqa: SLF001
        assert not member._beat_task.done()  # noqa: SLF001 — loop alive
        assert backend.instances("svc")  # replica never left the catalog
        await member.stop()
        assert backend.instances("svc") == []

    run(scenario(), timeout=60)
