"""Cold-start collapse tests (fleet/standby.py + the serve seams):
weight-transfer wire roundtrip + resume + corruption fallback, the
standby role / promote-verb semantics (incl. the promote-racing-drain
race), warm-bucket marker skip, and the slow-boot chaos seam — tiny
model on the CPU backend, plus pure host-side units.
"""
import asyncio
import http.client
import json
import time
import urllib.error
import urllib.request

import pytest

from containerpilot_tpu.fleet.standby import (
    StandbyLauncher,
    WeightTransferError,
    fetch_params,
    fetch_weight_chunks,
    rebuild_params,
    weights_manifest,
)
from containerpilot_tpu.workload.modelcfg import (
    compile_cache_note,
    load_warm_buckets,
    mark_warm_buckets,
    parse_compile_cache_note,
    warmup_fingerprint,
)


def _get(port, path, timeout=30):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout
        ) as resp:
            return resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read(), dict(exc.headers)


def _post(port, path, payload=None, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload or {}).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read(), dict(exc.headers)


def _tiny_model():
    import jax
    import jax.numpy as jnp

    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=64, dtype=jnp.float32,
    )
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _server(cfg, params, **kwargs):
    from containerpilot_tpu.workload.serve import InferenceServer

    return InferenceServer(
        cfg, params, "127.0.0.1", 0, max_len=64,
        slots=2, slot_chunk=4, **kwargs,
    )


# -- weight wire (pure) -------------------------------------------------


def test_weights_manifest_rebuild_roundtrip():
    """Serialize -> chunk -> rebuild is byte-identical, and the
    manifest's accounting (total bytes, per-chunk digests) is
    self-consistent with small chunks forcing multi-chunk leaves."""
    import jax
    import numpy as np

    from containerpilot_tpu.fleet.standby import (
        _chunk_digest,
        leaf_bytes,
    )

    cfg, params = _tiny_model()
    manifest = weights_manifest(params, chunk_bytes=1000)
    leaves = jax.tree_util.tree_leaves(params)
    assert len(manifest["leaves"]) == len(leaves)
    assert manifest["total_bytes"] == sum(
        np.asarray(leaf).nbytes for leaf in leaves
    )
    # some leaf must span multiple chunks at this chunk size
    owners = [c["leaf"] for c in manifest["chunks"]]
    assert any(owners.count(i) > 1 for i in set(owners))
    # materialize the chunk bytes the way the server does
    chunks = []
    for spec in manifest["chunks"]:
        data = leaf_bytes(leaves[spec["leaf"]])
        piece = data[spec["offset"]:spec["offset"] + spec["len"]]
        assert _chunk_digest(piece) == spec["digest"]
        chunks.append(piece)
    like = jax.tree_util.tree_map(np.zeros_like, params)
    rebuilt = rebuild_params(manifest, chunks, like)
    for a, b in zip(leaves, jax.tree_util.tree_leaves(rebuilt)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_rebuild_rejects_structural_mismatch():
    import numpy as np

    cfg, params = _tiny_model()
    manifest = weights_manifest(params)
    import jax

    leaves = jax.tree_util.tree_leaves(params)
    chunks = []
    for spec in manifest["chunks"]:
        data = np.asarray(leaves[spec["leaf"]]).tobytes()
        chunks.append(
            data[spec["offset"]:spec["offset"] + spec["len"]]
        )
    # wrong leaf count
    with pytest.raises(WeightTransferError):
        rebuild_params(manifest, chunks, {"just_one": leaves[0]})
    # wrong shape in `like`
    bad = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params),
        [np.zeros((3, 3), np.float32) for _ in leaves],
    )
    with pytest.raises(WeightTransferError):
        rebuild_params(manifest, chunks, bad)


# -- the live transfer (mux) --------------------------------------------


def test_fetch_params_over_mux_and_resume_endpoint(run):
    """End to end against a live replica: fetch_params returns a
    byte-identical tree over cp-mux/1, and ``?chunk=K`` re-serves
    exactly the suffix (the resume contract a mid-transfer redial
    relies on)."""
    import jax
    import numpy as np

    cfg, params = _tiny_model()

    async def scenario():
        server = _server(cfg, params)
        await server.run()
        like = jax.tree_util.tree_map(np.zeros_like, params)
        fetched = await fetch_params("127.0.0.1", server.port, like)
        assert fetched is not None
        for a, b in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(fetched),
        ):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        # the resume surface: a plain keep-alive read of ?chunk=K
        # yields manifest + exactly the chunk suffix
        manifest, chunks = await fetch_weight_chunks(
            "127.0.0.1", server.port
        )
        resume_at = len(chunks) - 2
        loop = asyncio.get_event_loop()

        def read_stream():
            conn = http.client.HTTPConnection(
                "127.0.0.1", server.port, timeout=30
            )
            try:
                conn.request(
                    "GET", f"/v1/weights?chunk={resume_at}"
                )
                resp = conn.getresponse()
                assert resp.status == 200
                return resp.read()
            finally:
                conn.close()

        raw = await loop.run_in_executor(None, read_stream)
        mlen = int.from_bytes(raw[:8], "big")
        assert json.loads(raw[8:8 + mlen]) == manifest
        assert raw[8 + mlen:] == b"".join(chunks[resume_at:])
        await server.stop()

    run(scenario(), timeout=300)


def test_fetch_params_corruption_falls_back_to_none(run):
    """A digest mismatch (peer reloaded/bit-rot) is NOT retried: the
    fetch returns None and the caller takes the disk/init path."""
    cfg, params = _tiny_model()

    async def scenario():
        server = _server(cfg, params)
        await server.run()
        # poison one advertised digest AFTER the manifest caches: the
        # served bytes recompute honestly and can never match it
        await server._ensure_weights_manifest()  # noqa: SLF001
        manifest = server._weights_manifest_cache  # noqa: SLF001
        manifest["chunks"][0]["digest"] = "0" * 16
        from containerpilot_tpu.fleet.standby import encode_manifest

        server._weights_manifest_bytes = (  # noqa: SLF001
            encode_manifest(manifest)
        )
        fetched = await fetch_params("127.0.0.1", server.port, params)
        assert fetched is None
        await server.stop()

    run(scenario(), timeout=300)


# -- standby role + promote verb ----------------------------------------


def test_standby_role_health_refusal_and_promote_verb(run):
    """A warm standby: /health 503 standby, generate refused 503,
    score/model reads stay up; POST /v3/standby/promote flips it in
    one call (second promote 409s — the exactly-one-winner half the
    replica enforces); generate then serves."""
    cfg, params = _tiny_model()

    async def scenario():
        loop = asyncio.get_event_loop()
        server = _server(cfg, params, role="standby")
        await server.run()
        body = {"tokens": [[1, 2, 3]], "max_new_tokens": 4}
        health = await loop.run_in_executor(
            None, _get, server.port, "/health"
        )
        refused = await loop.run_in_executor(
            None, _post, server.port, "/v1/generate", body
        )
        score = await loop.run_in_executor(
            None, _post, server.port, "/v1/score",
            {"tokens": [[1, 2, 3, 4]]},
        )
        first = await loop.run_in_executor(
            None, _post, server.port, "/v3/standby/promote"
        )
        second = await loop.run_in_executor(
            None, _post, server.port, "/v3/standby/promote"
        )
        served = await loop.run_in_executor(
            None, _post, server.port, "/v1/generate", body
        )
        health_after = await loop.run_in_executor(
            None, _get, server.port, "/health"
        )
        await server.stop()
        return health, refused, score, first, second, served, health_after

    health, refused, score, first, second, served, health_after = run(
        scenario(), timeout=300
    )
    assert health[0] == 503 and b"standby" in health[1]
    assert refused[0] == 503 and b"standby" in refused[1]
    assert {k.lower(): v for k, v in refused[2].items()}["retry-after"]
    assert score[0] == 200
    assert first[0] == 200 and json.loads(first[1])["promoted"]
    assert second[0] == 409
    assert served[0] == 200
    assert health_after[0] == 200


def test_promote_racing_drain_409s_until_resume(run):
    """Promote racing drain: a DRAINING standby refuses promotion
    (409) — capacity leaving the fleet must not be promoted into it —
    and promotes cleanly once maintenance exits."""
    cfg, params = _tiny_model()

    async def scenario():
        loop = asyncio.get_event_loop()
        server = _server(cfg, params, role="standby")
        await server.run()
        server.enter_maintenance()
        refused = await loop.run_in_executor(
            None, _post, server.port, "/v3/standby/promote"
        )
        assert not server.promote()  # the in-process verb agrees
        server.exit_maintenance()
        accepted = await loop.run_in_executor(
            None, _post, server.port, "/v3/standby/promote"
        )
        await server.stop()
        return refused, accepted

    refused, accepted = run(scenario(), timeout=300)
    assert refused[0] == 409 and b"draining" in refused[1]
    assert accepted[0] == 200


# -- warm-bucket marker + warmup skip -----------------------------------


def test_warm_bucket_marker_roundtrip_and_tolerance(tmp_path):
    cfg, _ = _tiny_model()
    fp = warmup_fingerprint(cfg, 64, slots=2, slot_chunk=4)
    other = warmup_fingerprint(cfg, 128, slots=2, slot_chunk=4)
    assert fp != other  # max_len shapes the program set
    assert load_warm_buckets(str(tmp_path), fp) == set()
    mark_warm_buckets(str(tmp_path), fp, {"p4"})
    mark_warm_buckets(str(tmp_path), fp, {"p16", "slots"})
    assert load_warm_buckets(str(tmp_path), fp) == {
        "p4", "p16", "slots"
    }
    assert load_warm_buckets(str(tmp_path), other) == set()
    # garbage marker: tolerant empty read, and marking heals it
    (tmp_path / "cp_warm_buckets.json").write_text("{not json")
    assert load_warm_buckets(str(tmp_path), fp) == set()
    mark_warm_buckets(str(tmp_path), fp, {"p4"})
    assert load_warm_buckets(str(tmp_path), fp) == {"p4"}
    # the cc= advertisement VALUE roundtrips through the tolerant
    # parser (the "cc=" name itself is owned by fleet/notes.py)
    note = compile_cache_note(str(tmp_path))
    assert ":" in note and " " not in note
    digest, cache_dir = parse_compile_cache_note(note)
    assert digest and cache_dir == str(tmp_path)
    assert parse_compile_cache_note("garbage") == ("", "")
    assert parse_compile_cache_note(None) == ("", "")
    assert compile_cache_note("") == ""


@pytest.mark.parametrize(
    "marked,warms",
    [
        # no marker yet: warm, mark, and the next launch skips
        (None, [1, 0]),
        # a marker an older build wrote, which also listed the
        # one-shot programs' buckets: read without error, skipped
        (["p16", "p4", "slots"], [0]),
        # one that lists nothing this build compiles: warm, then mark
        (["p16", "p4"], [1, 0]),
    ],
    ids=["unmarked", "older-build-warm", "older-build-one-shot-only"],
)
def test_warmup_skips_marked_buckets(
    run, tmp_path, monkeypatch, marked, warms
):
    """Same-shaped servers sharing a warm-bucket marker dir: a launch
    that finds its ``slots`` bucket marked drives ZERO admissions
    through the engine (the marker skip — its compile_warmup seconds
    collapse, which is the cold-start lever the shared cache exists
    for), any other launch drives exactly warmup's one dummy request
    and marks. Warmup compiles what requests run: it never calls the
    one-shot ``generate``, and /health turns 200 without it. The
    marker dir here is the test's own, kept apart from jax's compile
    cache: the server never sets a cache directory."""
    import jax

    from containerpilot_tpu.models import decode as decode_mod
    from containerpilot_tpu.workload.modelcfg import WARM_MARKER
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg, params = _tiny_model()

    def no_generate(*_args, **_kwargs):
        raise AssertionError("warmup called the one-shot generate")

    monkeypatch.setattr(decode_mod, "generate", no_generate)
    cache_in_force = jax.config.jax_compilation_cache_dir

    async def scenario():
        for launch, admissions in enumerate(warms):
            server = InferenceServer(
                cfg, params, "127.0.0.1", 0, max_len=64,
                compile_cache_dir=str(tmp_path),
            )
            fingerprint = server._warmup_fingerprint()
            if marked is not None and launch == 0:
                (tmp_path / WARM_MARKER).write_text(
                    json.dumps({fingerprint: marked})
                )
            await server.run()
            health = await server._health(None)
            await server.stop()
            assert server.slot_engine.phases.admissions == admissions
            assert server.ready and health.status == 200
            assert load_warm_buckets(str(tmp_path), fingerprint) == (
                {"slots"} | set(marked or ())
            )
            # the cc= advertisement was computed once at warmup end
            _digest, adv_dir = parse_compile_cache_note(
                server.compile_cache_note()
            )
            assert adv_dir == str(tmp_path)

    run(scenario(), timeout=300)
    # the constructor argument placed the marker, not jax's cache
    assert jax.config.jax_compilation_cache_dir == cache_in_force


def test_slow_boot_hook_parks_warmup_as_compile_badput(run):
    """The chaos_hook("warmup") seam: an injected slow boot delays
    ready AND lands in the ledger's compile_warmup stage — the
    cold-start badput the standby pool masks."""
    cfg, params = _tiny_model()

    async def scenario():
        server = _server(cfg, params)

        async def hook(endpoint):
            if endpoint == "warmup":
                await asyncio.sleep(0.4)

        server.chaos_hook = hook
        t0 = time.monotonic()
        await server.run()
        boot_s = time.monotonic() - t0
        totals = server.ledger.totals()
        await server.stop()
        assert boot_s >= 0.4
        assert totals["compile_warmup"] >= 0.4

    run(scenario(), timeout=300)


# -- StandbyLauncher units (pure asyncio) --------------------------------


class _FakeStandbyInner:
    """Programmable inner launcher for StandbyLauncher units."""

    def __init__(self):
        self._next = 0
        self._active = []
        self.standbys = {}  # id -> alive
        self.promote_calls = []
        self.standby_failures = 0  # launch_standby raises this many times

    def ids(self):
        return list(self._active)

    def count(self):
        return len(self._active)

    async def launch(self):
        rid = f"cold-{self._next}"
        self._next += 1
        self._active.append(rid)
        return rid

    async def retire(self, rid):
        self._active.remove(rid)

    async def launch_standby(self):
        if self.standby_failures > 0:
            self.standby_failures -= 1
            raise RuntimeError("standby crashed mid-boot")
        rid = f"sb-{self._next}"
        self._next += 1
        self.standbys[rid] = True
        return rid

    async def promote(self, rid):
        self.promote_calls.append(rid)
        await asyncio.sleep(0)  # a real promote awaits the wire
        if not self.standbys.get(rid, False):
            return False
        del self.standbys[rid]
        self._active.append(rid)
        return True


def test_standby_launcher_promotes_then_refills(run):
    async def scenario():
        inner = _FakeStandbyInner()
        pool = StandbyLauncher(inner, standby_count=1,
                               refill_backoff=0.01)
        await pool.prefill()
        assert len(pool.standby_ids()) == 1
        rid = await pool.launch()
        assert rid.startswith("sb-") and rid in inner.ids()
        assert pool.promotions == 1 and pool.cold_launches == 0
        assert pool.last_launch["mode"] == "promoted"
        for _ in range(100):
            if len(pool.standby_ids()) == 1:
                break
            await asyncio.sleep(0.01)
        assert len(pool.standby_ids()) == 1  # background refill landed
        await pool.stop()

    run(scenario(), timeout=30)


def test_standby_launcher_promote_race_single_winner(run):
    """Two concurrent launches against a one-standby pool: exactly
    one promotes it (claimed before any await), the other cold-
    launches — the standby is never promoted twice."""

    async def scenario():
        inner = _FakeStandbyInner()
        pool = StandbyLauncher(inner, standby_count=1,
                               refill_backoff=0.01)
        await pool.prefill()
        first, second = await asyncio.gather(
            pool.launch(), pool.launch()
        )
        modes = sorted(
            rid.split("-")[0] for rid in (first, second)
        )
        assert modes == ["cold", "sb"]
        assert pool.promotions == 1 and pool.cold_launches == 1
        # the standby saw exactly ONE promote call
        sb = [rid for rid in (first, second) if rid.startswith("sb-")]
        assert inner.promote_calls.count(sb[0]) == 1
        await pool.stop()

    run(scenario(), timeout=30)


def test_standby_launcher_dead_standby_falls_back_cold(run):
    """A standby that died between pooling and promotion is dropped
    (promote -> False) and the launch proceeds — next standby or the
    cold path — without surfacing an error."""

    async def scenario():
        inner = _FakeStandbyInner()
        pool = StandbyLauncher(inner, standby_count=1,
                               refill_backoff=0.01)
        await pool.prefill()
        dead = pool.standby_ids()[0]
        inner.standbys[dead] = False  # crashed in the pool
        rid = await pool.launch()
        assert rid.startswith("cold-")
        assert pool.promote_failures == 1
        assert pool.last_launch["mode"] == "cold"
        await pool.stop()

    run(scenario(), timeout=30)


def test_standby_crash_mid_refill_retries_with_backoff(run):
    """launch_standby raising mid-refill counts a failure and the
    loop retries (equal-jitter backoff) until the pool converges —
    a crashing standby boot never strands the pool empty."""

    async def scenario():
        inner = _FakeStandbyInner()
        inner.standby_failures = 2  # first two boots crash
        pool = StandbyLauncher(
            inner, standby_count=1,
            refill_backoff=0.01, refill_backoff_cap=0.02,
        )
        pool._ensure_refill()  # noqa: SLF001 — the background path
        for _ in range(200):
            if len(pool.standby_ids()) == 1:
                break
            await asyncio.sleep(0.01)
        assert len(pool.standby_ids()) == 1
        assert pool.refill_failures == 2
        await pool.stop()

    run(scenario(), timeout=30)
