"""Slot-based continuous decode (models/slots.py +
workload/serve_slots.py): per-request byte-parity with solo generate,
staggered admission, eos handling, and pool churn."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from containerpilot_tpu.models.decode import generate
from containerpilot_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)
from containerpilot_tpu.workload.serve_slots import SlotEngine

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
    max_seq_len=64, dtype=jnp.float32,
)
MAX_LEN = 48


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture()
def engine(params):
    eng = SlotEngine(CFG, params, MAX_LEN, slots=2, chunk=3)
    yield eng
    eng.stop()


def _solo(params, tokens, max_new, cfg=CFG, **kw):
    """Reference: solo generate with the SERVER's key convention (row
    i of a request samples from fold_in(PRNGKey(seed), i), so seeded
    output is identical across serving configs; ``row`` is that i),
    trimmed the way the server trims (keep eos, drop the pads after
    it)."""
    seed = kw.pop("seed", 0)
    row_idx = kw.pop("row", 0)
    eos = kw.pop("eos_id", -1)
    out = generate(
        params, jnp.asarray([tokens], jnp.int32), cfg, max_new,
        MAX_LEN,
        rng=jnp.stack(
            [jax.random.fold_in(jax.random.PRNGKey(seed), row_idx)]
        ),
        eos_id=eos, **kw,
    )
    row = [int(t) for t in np.asarray(out)[0]]
    if eos >= 0 and eos in row:
        row = row[: row.index(eos) + 1]
    return row


def test_single_request_matches_generate_greedy(params, engine):
    tokens = [1, 2, 3, 4]
    got = engine.submit(tokens, max_new=7).result(timeout=120)
    assert got == _solo(params, tokens, 7)


def test_single_request_matches_generate_sampled(params, engine):
    tokens = [5, 6, 7]
    kw = dict(temperature=0.9, top_k=12, top_p=0.8, seed=11)
    got = engine.submit(tokens, max_new=9, **kw).result(timeout=120)
    assert got == _solo(params, tokens, 9, **kw)


def test_staggered_admission_is_isolated(params, engine):
    """A request admitted mid-flight (different prompt, different
    sampling, different arrival chunk) changes nothing for either
    row — both match their solo runs exactly."""
    a = engine.submit([1, 2, 3, 4, 5], max_new=12, temperature=0.7,
                      seed=3)
    # b arrives while a decodes (submission order is the only
    # coupling; the queue guarantees b joins at a later chunk)
    b = engine.submit([9, 8], max_new=5)
    assert a.result(timeout=180) == _solo(
        params, [1, 2, 3, 4, 5], 12, temperature=0.7, seed=3
    )
    assert b.result(timeout=180) == _solo(params, [9, 8], 5)


def test_eos_trims_like_generate(params, engine):
    """Force an early eos by finding the greedy second token, then
    asking for it as eos: the engine output must keep the eos and
    stop, matching the trimmed solo run."""
    tokens = [2, 4, 6]
    free = _solo(params, tokens, 6)
    eos = free[1]  # greedy decode is deterministic; token 1 will recur
    got = engine.submit(tokens, max_new=6, eos_id=eos).result(
        timeout=120
    )
    assert got == _solo(params, tokens, 6, eos_id=eos)
    # the chosen token may ALSO be the greedy first draw (numerics
    # vary across backends), so derive the expected stop point from
    # the free-running output instead of assuming position 1
    assert got[-1] == eos and len(got) == free.index(eos) + 1


def test_more_requests_than_slots_all_complete(params, engine):
    prompts = [[i + 1, i + 2] for i in range(5)]  # 5 reqs, 2 slots
    futs = [
        engine.submit(p, max_new=4, seed=i)
        for i, p in enumerate(prompts)
    ]
    for i, (p, f) in enumerate(zip(prompts, futs)):
        assert f.result(timeout=300) == _solo(params, p, 4, seed=i)


def test_submit_validation(params, engine):
    with pytest.raises(ValueError, match="prompt"):
        engine.submit([], max_new=4)
    with pytest.raises(ValueError, match="exceeds"):
        engine.submit([1] * 40, max_new=20)
    with pytest.raises(ValueError, match="max_new"):
        engine.submit([1, 2], max_new=0)


def test_chunk_failure_recovers_pool(params):
    """A failed chunk donates the pool buffer; the engine must
    rebuild it and keep serving instead of failing forever. The
    decode call lives in the step program now (models/stepprog.py),
    so that is where the fault injects; the first dispatch after an
    admission is always the single-chunk program, so the patch
    intercepts round one."""
    eng = SlotEngine(CFG, params, MAX_LEN, slots=2, chunk=2)
    try:
        import containerpilot_tpu.models.stepprog as mod

        original = mod.decode_slots_chunk
        calls = {"n": 0}

        def boom(*args, **kw):
            calls["n"] += 1
            if calls["n"] == 1:
                # donate like the real call would, then fail
                for leaf in args[1]["k"]:  # one leaf per layer
                    leaf.delete()
                raise RuntimeError("injected chunk failure")
            return original(*args, **kw)

        mod.decode_slots_chunk = boom
        try:
            failed = eng.submit([1, 2, 3], max_new=5)
            with pytest.raises(RuntimeError, match="injected"):
                failed.result(timeout=120)
        finally:
            mod.decode_slots_chunk = original
        # the pool was rebuilt: the next request serves normally
        ok = eng.submit([1, 2, 3], max_new=5)
        assert ok.result(timeout=120) == _solo(params, [1, 2, 3], 5)
    finally:
        eng.stop()


@pytest.fixture(scope="module")
def window_setup():
    """A sliding-window engine (ring caches, decode.py) plus params
    for the SAME windowed config — the solo reference must run the
    identical ring-cache path."""
    import dataclasses

    cfg = dataclasses.replace(CFG, window=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = SlotEngine(cfg, params, MAX_LEN, slots=2, chunk=3)
    yield cfg, params, eng
    eng.stop()


def test_window_long_prompt_and_decode_cross_the_ring(window_setup):
    """Prompt longer than the window AND decode past the wrap point:
    every ring overwrite the engine performs matches solo generate."""
    cfg, params, eng = window_setup
    tokens = list(range(1, 13))  # 12 > window 8
    got = eng.submit(tokens, max_new=9).result(timeout=180)
    assert got == _solo(params, tokens, 9, cfg=cfg)


def test_window_slot_reuse_carries_no_stale_context(window_setup):
    """The historical hazard: a freed ring slot's cache rows are NOT
    zeroed, so re-admission must prove the wholesale row overwrite
    (insert_row) leaves nothing of the previous occupant. Fill both
    slots, finish them, then reuse with fresh prompts."""
    cfg, params, eng = window_setup
    first = [
        eng.submit([1, 2, 3, 4, 5, 6, 7, 8, 9], max_new=6, seed=1),
        eng.submit([9, 8, 7], max_new=6, seed=2),
    ]
    for fut in first:
        fut.result(timeout=180)
    reused = [
        ([5, 4, 3, 2], dict(max_new=10, seed=7)),
        ([2, 2], dict(max_new=10, temperature=0.8, top_k=16, seed=4)),
    ]
    futs = [eng.submit(p, **kw) for p, kw in reused]
    for (p, kw), fut in zip(reused, futs):
        assert fut.result(timeout=180) == _solo(
            params, p, kw.pop("max_new"), cfg=cfg, **kw
        )


def test_chunked_admission_matches_generate(params):
    """--prefill-chunk composes with the pool: admissions longer than
    the chunk prefill in fixed-size pieces (chunked_prefill) and the
    decode still byte-matches solo generate — long and short prompts,
    greedy and sampled, plus slot reuse over the chunked path."""
    eng = SlotEngine(CFG, params, MAX_LEN, slots=2, chunk=3,
                     prefill_chunk=4)
    try:
        long_p = [(i * 3 + 1) % 64 for i in range(11)]  # 11 > 4
        got = eng.submit(long_p, max_new=7).result(timeout=180)
        assert got == _solo(params, long_p, 7)
        # short prompts skip the chunked path entirely
        got = eng.submit([5, 6], max_new=5).result(timeout=180)
        assert got == _solo(params, [5, 6], 5)
        # sampled + reuse of the chunk-admitted slot
        kw = dict(temperature=0.9, top_k=12, seed=11)
        got = eng.submit(long_p, max_new=6, **kw).result(timeout=180)
        assert got == _solo(params, long_p, 6, **kw)
    finally:
        eng.stop()


def test_stats_and_stop(params):
    eng = SlotEngine(CFG, params, MAX_LEN, slots=3, chunk=2)
    stats = eng.stats
    assert stats["slots"] == 3 and stats["chunk"] == 2
    fut = eng.submit([1, 2], max_new=3)
    assert fut.result(timeout=120)
    eng.stop()
    with pytest.raises(RuntimeError):
        eng.submit([1, 2], max_new=3)


def test_inference_server_slot_engine(run, params):
    """Server-level: concurrent /v1/generate requests through --slots
    match sequential solo answers; /v1/model reports the engine."""
    import json
    import urllib.request

    from containerpilot_tpu.workload.serve import InferenceServer

    server = InferenceServer(
        CFG, params, "127.0.0.1", 0, max_len=MAX_LEN, slots=2,
        slot_chunk=4,
    )

    def fetch(path, body=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}{path}",
            data=json.dumps(body).encode() if body is not None else None,
            headers={"Content-Type": "application/json"} if body else {},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            return json.loads(resp.read().decode())

    async def scenario():
        import asyncio

        await server.run()
        loop = asyncio.get_event_loop()
        info = await loop.run_in_executor(None, lambda: fetch("/v1/model"))
        reqs = [
            {"tokens": [[1, 2, 3]], "max_new_tokens": 6,
             "temperature": 0.8, "seed": 5},
            {"tokens": [[7, 8]], "max_new_tokens": 4},
            {"tokens": [[4, 5, 6, 7]], "max_new_tokens": 5, "seed": 2,
             "temperature": 0.5, "top_k": 10},
        ]
        outs = await asyncio.gather(*[
            loop.run_in_executor(None, lambda r=r: fetch("/v1/generate", r))
            for r in reqs
        ])
        await server.stop()
        return info, outs

    info, outs = run(scenario())
    stats = dict(info["slot_engine"])
    # cumulative dispatch/token accounting (the goodput ledger's
    # dispatches/token pair): present, monotone, and bounded below
    # one dispatch per token for chunked decode
    assert stats.pop("dispatches") >= 1
    assert stats.pop("tokens_out") >= 1
    # decode rounds by the sampler's arm: warm-up's request is greedy
    sampler = stats.pop("sampler")
    assert sampler.pop("rounds_argmax") >= 1
    assert sampler == {"rounds_draw": 0, "rounds_filter": 0}
    # how far the dispatches read the pool's rows: a pool this short
    # has the one rung, and every counted dispatch ran it
    read_len = stats.pop("read_len")
    assert read_len["ladder"] == [MAX_LEN]
    assert read_len["dispatches"][str(MAX_LEN)] >= 1
    assert stats == {
        "slots": 2, "chunk": 4, "window": 4, "active": 0,
        "queued": 0,
    }
    assert outs[0]["tokens"][0] == _solo(
        params, [1, 2, 3], 6, temperature=0.8, seed=5
    )
    assert outs[1]["tokens"][0] == _solo(params, [7, 8], 4)
    assert outs[2]["tokens"][0] == _solo(
        params, [4, 5, 6, 7], 5, seed=2, temperature=0.5, top_k=10
    )


def _serve(run, server, scenario, timeout=120):
    """Boot ``server``, await ``scenario(post)`` where ``post(body)``
    POSTs /v1/generate from a thread and returns (status, text), stop
    the server, return what the scenario returned."""
    import asyncio
    import json
    import urllib.error
    import urllib.request

    def post_sync(body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=120) as resp:
                return resp.status, resp.read().decode()
        except urllib.error.HTTPError as exc:
            return exc.code, exc.read().decode()

    async def whole():
        await server.run()
        loop = asyncio.get_event_loop()
        try:
            return await scenario(
                lambda body: loop.run_in_executor(None, post_sync, body)
            )
        finally:
            await server.stop()

    return run(whole(), timeout=timeout)


def _default_server(params, **kwargs):
    """A server built with NO ``slots`` argument."""
    from containerpilot_tpu.workload.serve import InferenceServer

    return InferenceServer(
        CFG, params, "127.0.0.1", 0, max_len=MAX_LEN, **kwargs
    )


@pytest.mark.parametrize(
    "n_rows,sampling",
    [
        (2, {}),
        (2, {"temperature": 0.8, "top_k": 8, "seed": 5}),
        (3, {}),
        (3, {"temperature": 0.8, "top_k": 8, "seed": 5}),
        # more rows than the default pool's 4 slots: the rest queue
        # and the answer still comes back in the request's order
        (6, {"temperature": 0.8, "top_k": 8, "seed": 9}),
    ],
    ids=["2-greedy", "2-sampled", "3-greedy", "3-sampled",
         "6-sampled-over-4-slots"],
)
def test_multi_row_request_matches_generate_per_row(
    run, params, n_rows, sampling
):
    """A /v1/generate request with several rows rides the engine row
    by row: row i equals ``generate`` run alone on that row with key
    fold_in(PRNGKey(seed), i), greedy and sampled alike."""
    import json

    rows = [[(7 * i + j) % 64 for j in range(1, 4)] for i in range(n_rows)]
    server = _default_server(params)

    async def scenario(post):
        return await post(
            {"tokens": rows, "max_new_tokens": 7, **sampling}
        )

    status, text = _serve(run, server, scenario)
    assert status == 200, text
    assert json.loads(text)["tokens"] == [
        _solo(params, row, 7, row=i, **sampling)
        for i, row in enumerate(rows)
    ]
    assert server.slot_engine.slots == 4  # the constructor's default


def test_n_samples_are_the_rows_keys(run, params):
    """``n`` = 3 sampled: three different rows, each equal to a
    one-row engine request under the same key (row i of the seed)."""
    import asyncio
    import json

    body = {"tokens": [[1, 2, 3]], "max_new_tokens": 8, "n": 3,
            "temperature": 0.9, "seed": 11}
    server = _default_server(params)

    async def scenario(post):
        answer = await post(body)
        alone = [
            await asyncio.wrap_future(server.slot_engine.submit(
                [1, 2, 3], 8, temperature=0.9, seed=11, row=i
            ))
            for i in range(3)
        ]
        return answer, alone

    (status, text), alone = _serve(run, server, scenario)
    assert status == 200, text
    samples = json.loads(text)["tokens"]
    assert samples == alone
    assert len({tuple(row) for row in samples}) == 3
    assert samples == [
        _solo(params, [1, 2, 3], 8, temperature=0.9, seed=11, row=i)
        for i in range(3)
    ]


def test_default_server_long_prompt_and_prefix_hit_match_generate(
    run, params
):
    """What the one-shot chunked and prefix paths existed for, on a
    server built with no ``slots`` argument: a prompt longer than
    --prefill-chunk (cold, in pieces) and the next turn's prefix-cache
    hit (rewind + extend, in pieces) both equal ``generate``."""
    import json

    server = _default_server(
        params, prefill_chunk=4, prefix_cache_entries=2
    )
    history = [(i * 5 + 2) % 64 for i in range(20)]  # >= MIN_REUSE
    turn2 = history + [9, 9, 5]

    async def scenario(post):
        cold = await post({"tokens": [history], "max_new_tokens": 6})
        hit = await post({"tokens": [turn2], "max_new_tokens": 6,
                          "temperature": 0.7, "seed": 3})
        return cold, hit, dict(server.prefix_cache.stats)

    cold, hit, stats = _serve(run, server, scenario)
    assert cold[0] == 200 and hit[0] == 200, (cold, hit)
    assert json.loads(cold[1])["tokens"] == [_solo(params, history, 6)]
    assert json.loads(hit[1])["tokens"] == [
        _solo(params, turn2, 6, temperature=0.7, seed=3)
    ]
    assert stats["misses"] == 1 and stats["hits"] == 1, stats
    assert stats["tokens_reused"] > 0, stats


@pytest.mark.parametrize("where", ["constructor", "cli"])
def test_zero_slots_refused(params, where, capsys):
    """``slots`` is a capacity, never a switch: 0 (the old "no slot
    engine" mode) is refused where it is given."""
    if where == "constructor":
        with pytest.raises(ValueError, match="slots must be >= 1"):
            _default_server(params, slots=0)
        return
    from containerpilot_tpu.workload.serve_cli import build_arg_parser

    assert build_arg_parser().parse_args([]).slots == 4
    with pytest.raises(SystemExit):
        build_arg_parser().parse_args(["--slots", "0"])
    assert "--slots: must be >= 1" in capsys.readouterr().err


def test_stream_deltas_concatenate_to_result(params, engine):
    """on_tokens deltas, concatenated, ARE the final result — the
    streaming surface can't drift from the non-streamed one."""
    deltas = []
    got = engine.submit(
        [1, 2, 3], max_new=8, temperature=0.7, seed=11,
        on_tokens=deltas.append,
    ).result(timeout=120)
    assert sum(deltas, []) == got
    assert got == _solo(params, [1, 2, 3], 8, temperature=0.7, seed=11)
    # the first delta is the admission sample: streaming starts
    # before the row's decode finishes, not after
    assert len(deltas) >= 2 and len(deltas[0]) == 1


def test_cancel_frees_slot_mid_generation(params, engine):
    """A cancelled request releases its slot at the next chunk
    boundary with a partial emission; the pool keeps serving."""
    import threading

    cancel = threading.Event()
    first = threading.Event()
    partial = []

    def on_tokens(delta):
        partial.extend(delta)
        first.set()

    max_new = MAX_LEN - 3
    fut = engine.submit(
        [5, 6, 7], max_new=max_new, on_tokens=on_tokens, cancel=cancel,
    )
    assert first.wait(timeout=120), "no first token"
    cancel.set()
    got = fut.result(timeout=120)
    assert 0 < len(got) < max_new, (
        f"cancel did not stop decode early ({len(got)}/{max_new})"
    )
    # the slot is back in the pool and byte-parity still holds
    deadline = __import__("time").monotonic() + 30
    while engine.stats["active"]:
        assert __import__("time").monotonic() < deadline
        __import__("time").sleep(0.05)
    after = engine.submit([1, 2, 3, 4], max_new=7).result(timeout=120)
    assert after == _solo(params, [1, 2, 3, 4], 7)


def _read_sse(port, body, abort_after=None, path="/v1/generate"):
    """POST with stream:true and read SSE events as they arrive;
    abort_after closes the socket after that many events (a client
    disconnect mid-stream)."""
    import http.client
    import json as json_mod

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request(
        "POST", path, json_mod.dumps(body),
        {"Content-Type": "application/json"},
    )
    resp = conn.getresponse()
    assert resp.status == 200, resp.read()
    assert resp.getheader("Content-Type") == "text/event-stream"
    events = []
    buf = b""
    while True:
        chunk = resp.read1(65536)
        if not chunk:
            break
        buf += chunk
        while b"\n\n" in buf:
            raw, buf = buf.split(b"\n\n", 1)
            assert raw.startswith(b"data: "), raw
            events.append(json_mod.loads(raw[len(b"data: "):]))
            if abort_after is not None and len(events) >= abort_after:
                # hard disconnect: closing the response closes the
                # underlying socket (Connection: close responses own
                # it), which is the server's EOF signal
                resp.close()
                conn.close()
                return events
    conn.close()
    return events


def test_server_stream_matches_non_streamed(run, params):
    """Streamed tokens byte-match the non-streamed response, greedy
    and sampled; the terminal event reports the count."""
    import asyncio
    import json as json_mod
    import urllib.request

    from containerpilot_tpu.workload.serve import InferenceServer

    server = InferenceServer(
        CFG, params, "127.0.0.1", 0, max_len=MAX_LEN, slots=2,
        slot_chunk=3,
    )

    def fetch(path, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}{path}",
            data=json_mod.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            return json_mod.loads(resp.read().decode())

    async def scenario():
        await server.run()
        loop = asyncio.get_event_loop()
        reqs = [
            {"tokens": [[1, 2, 3]], "max_new_tokens": 7},
            {"tokens": [[4, 5]], "max_new_tokens": 6,
             "temperature": 0.9, "top_k": 12, "seed": 3},
        ]
        results = []
        for body in reqs:
            plain = await loop.run_in_executor(
                None, lambda b=body: fetch("/v1/generate", b)
            )
            events = await loop.run_in_executor(
                None, lambda b=body: _read_sse(
                    server.port, dict(b, stream=True)
                )
            )
            results.append((plain, events))
        await server.stop()
        return results

    for plain, events in run(scenario()):
        assert events[-1]["done"] is True
        streamed = sum(
            (e["tokens"] for e in events if "tokens" in e), []
        )
        assert streamed == plain["tokens"][0]
        assert events[-1]["count"] == len(streamed)


def test_server_stream_disconnect_frees_slot(run, params):
    """Closing the connection mid-stream cancels the request: the
    slot returns to the pool well before the requested length could
    have decoded, and the server keeps serving."""
    import asyncio
    import json as json_mod
    import urllib.request

    from containerpilot_tpu.workload.serve import InferenceServer

    server = InferenceServer(
        CFG, params, "127.0.0.1", 0, max_len=MAX_LEN, slots=2,
        slot_chunk=2,
    )

    def fetch(path, body=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}{path}",
            data=json_mod.dumps(body).encode() if body else None,
            headers={"Content-Type": "application/json"} if body else {},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            return json_mod.loads(resp.read().decode())

    async def scenario():
        import time as time_mod

        await server.run()
        loop = asyncio.get_event_loop()
        max_new = MAX_LEN - 3
        events = await loop.run_in_executor(
            None, lambda: _read_sse(
                server.port,
                {"tokens": [[7, 8, 9]], "max_new_tokens": max_new,
                 "stream": True},
                abort_after=1,
            )
        )
        assert len(events) == 1  # we left after the first token
        # the slot must come back without the row decoding to the end
        deadline = time_mod.monotonic() + 60
        while True:
            info = await loop.run_in_executor(
                None, lambda: fetch("/v1/model")
            )
            if info["slot_engine"]["active"] == 0:
                break
            assert time_mod.monotonic() < deadline, info
            await asyncio.sleep(0.1)
        # cancellation kept the token counter well under the request
        metrics = await loop.run_in_executor(
            None,
            lambda: urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics", timeout=30
            ).read().decode(),
        )
        token_lines = [
            line for line in metrics.splitlines()
            if line.startswith("containerpilot_serve_generated_tokens_total")
        ]
        assert token_lines, "token counter missing from /metrics"
        for line in token_lines:
            assert float(line.split()[-1]) < max_new, line
        # and the pool still answers correctly
        after = await loop.run_in_executor(
            None, lambda: fetch(
                "/v1/generate",
                {"tokens": [[1, 2, 3]], "max_new_tokens": 5},
            )
        )
        await server.stop()
        return after

    after = run(scenario())
    assert after["tokens"][0] == _solo(params, [1, 2, 3], 5)


def test_stream_decoder_holds_back_split_multibyte():
    """Deterministic coverage of the holdback path the server test
    can't force (it depends on what the model happens to emit): a
    multibyte char split across deltas is buffered until complete,
    and a dangling prefix at stream end flushes as the SAME
    replacement char the one-shot decode produces."""
    from containerpilot_tpu.workload.text import (
        ByteTokenizer,
        stream_decoder,
    )

    tok = ByteTokenizer(512)
    e_acute = tok.encode("é", bos=False)  # 2 ids: 0xC3 0xA9
    assert len(e_acute) == 2

    # split across two deltas: nothing until the char completes
    delta_event, tail_events = stream_decoder(tok)
    first = delta_event([e_acute[0]])
    second = delta_event([e_acute[1]])
    assert first["text"] == "" and second["text"] == "é"
    assert tail_events() == []  # nothing dangling

    # dangling prefix at stream end: the flush event carries exactly
    # what decode() makes of the same ids
    delta_event, tail_events = stream_decoder(tok)
    assert delta_event([e_acute[0]])["text"] == ""
    (flush,) = tail_events()
    assert flush["tokens"] == []
    assert flush["text"] == tok.decode([e_acute[0]]) == "�"
    assert tail_events() == []  # flush is one-shot

    # specials interleaved: filtered identically to decode()
    delta_event, tail_events = stream_decoder(tok)
    parts = [
        delta_event([tok.EOS, e_acute[0]])["text"],
        delta_event([e_acute[1], tok.PAD])["text"],
    ]
    assert "".join(parts) == tok.decode(
        [tok.EOS, e_acute[0], e_acute[1], tok.PAD]
    ) == "é"


def test_server_completions_stream_matches_non_streamed(run):
    """Text SSE on /v1/completions: per-event text rides UTF-8
    partial-byte holdback, so concatenated event text equals the
    non-streamed 'text' and concatenated ids equal its 'tokens' —
    whatever byte sequences the model emits."""
    import asyncio
    import json as json_mod
    import urllib.request

    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=512, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=64, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    server = InferenceServer(
        cfg, params, "127.0.0.1", 0, max_len=48, text=True, slots=2,
        slot_chunk=3,
    )

    def fetch(body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/completions",
            data=json_mod.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            return json_mod.loads(resp.read().decode())

    async def scenario():
        await server.run()
        loop = asyncio.get_event_loop()
        results = []
        for body in (
            {"prompt": "hé", "max_new_tokens": 9},  # multibyte prompt
            {"prompt": "ab", "max_new_tokens": 7,
             "temperature": 0.9, "seed": 4},
        ):
            plain = await loop.run_in_executor(
                None, lambda b=body: fetch(b)
            )
            events = await loop.run_in_executor(
                None, lambda b=body: _read_sse(
                    server.port, dict(b, stream=True),
                    path="/v1/completions",
                )
            )
            results.append((plain, events))
        await server.stop()
        return results

    for plain, events in run(scenario()):
        assert events[-1]["done"] is True
        toks = sum((e["tokens"] for e in events if "tokens" in e), [])
        text = "".join(e.get("text", "") for e in events[:-1])
        assert toks == plain["tokens"]
        assert text == plain["text"]
        assert events[-1]["count"] == len(toks)


def test_server_stream_rejects_bad_compositions(run, params):
    """stream + stop, + logprobs, + several rows fail with clean 422s
    before any decode starts; a server built with no ``slots``
    argument streams like any other."""
    server = _default_server(params)
    base = {"tokens": [[1, 2]], "max_new_tokens": 4, "stream": True}

    async def scenario(post):
        import asyncio

        bad = [
            await post({**base, "stop": [[3]]}),
            await post({**base, "logprobs": True}),
            await post({**base, "tokens": [[1, 2], [3, 4]]}),
        ]
        loop = asyncio.get_event_loop()
        events = await loop.run_in_executor(
            None, _read_sse, server.port, base
        )
        return bad, events

    (with_stop, with_logprobs, two_rows), events = _serve(
        run, server, scenario
    )
    assert with_stop[0] == 422 and "stop" in with_stop[1]
    assert with_logprobs[0] == 422 and "logprobs" in with_logprobs[1]
    assert two_rows[0] == 422 and "single row" in two_rows[1]
    streamed = sum((e["tokens"] for e in events if "tokens" in e), [])
    assert streamed == _solo(params, [1, 2], 4)


def test_prefix_cache_admission_matches_generate(params):
    """--prefix-cache composes with the pool: an admission with a
    cached prefix rewinds + bucket-extends instead of full prefill,
    every admission seeds the cache, and output stays byte-identical
    to solo generate — cold miss, exact-repeat hit, and the
    chat-turn partial hit (extended prompt)."""
    from containerpilot_tpu.workload.serve_prefix import PrefixCache

    pc = PrefixCache(entries=4)
    # prefill_chunk too: the cold miss takes chunked_prefill and the
    # chat-turn hit's bucketed suffix (16 > 4) takes extend_pieces —
    # the prefix path honors the same O(chunk) activation bound
    eng = SlotEngine(CFG, params, MAX_LEN, slots=2, chunk=3,
                     prefix_cache=pc, prefill_chunk=4)
    try:
        base_p = [(i * 5 + 2) % 64 for i in range(20)]  # >= MIN_REUSE
        got = eng.submit(base_p, max_new=6).result(timeout=180)
        assert got == _solo(params, base_p, 6)
        assert pc.stats["misses"] == 1 and len(pc) == 1

        # exact repeat (sampled): rewind + bucketed extend, same bytes
        got = eng.submit(base_p, max_new=6, temperature=0.7,
                         seed=3).result(timeout=180)
        assert got == _solo(params, base_p, 6, temperature=0.7, seed=3)
        assert pc.stats["hits"] == 1 and pc.stats["tokens_reused"] > 0

        # the chat-turn shape: history + a new suffix
        turn2 = base_p + [9, 9, 5]
        got = eng.submit(turn2, max_new=6).result(timeout=180)
        assert got == _solo(params, turn2, 6)
        assert pc.stats["hits"] == 2 and len(pc) == 2
    finally:
        eng.stop()


def test_prefix_cache_rejects_cp_and_window(params):
    """The fundamental non-compositions still refuse at construction:
    cached prefixes bypass the ring, and a ring cache's stale rows
    are live window context."""
    import dataclasses

    from containerpilot_tpu.parallel import MeshPlan, make_mesh
    from containerpilot_tpu.workload.serve_prefix import PrefixCache

    mesh = make_mesh(
        jax.devices()[:2], plan=MeshPlan(data=1, model=1, seq=2)
    )
    with pytest.raises(ValueError, match="bypass the ring"):
        SlotEngine(CFG, params, MAX_LEN, slots=2, chunk=3,
                   cp_mesh=mesh, prefix_cache=PrefixCache(2))
    win_cfg = dataclasses.replace(CFG, window=8)
    with pytest.raises(ValueError, match="window"):
        SlotEngine(win_cfg, params, MAX_LEN, slots=2, chunk=3,
                   prefix_cache=PrefixCache(2))


def test_slots_reject_max_len_too_small_for_warmup(params):
    """A legal but tiny --max-len must fail at construction with a
    clean message — not after the port is bound, when warmup()'s
    dummy request (4 prompt ids + chunk+1 new tokens) would hit
    submit()'s ValueError and kill the server mid-startup."""
    from containerpilot_tpu.workload.serve import InferenceServer

    with pytest.raises(ValueError, match="max_len must be >= slot_chunk"):
        InferenceServer(
            CFG, params, "127.0.0.1", 0, max_len=8, slots=2,
            slot_chunk=8,
        )
    # the boundary itself is fine: 4 + chunk + 1 == max_len
    InferenceServer(
        CFG, params, "127.0.0.1", 0, max_len=9, slots=1, slot_chunk=4,
    )


def test_slot_engine_composes_with_tensor_parallel():
    """The slot pool rides TP-sharded params: the vmapped decode and
    the insert/chunk programs partition under GSPMD, and output stays
    byte-identical to the single-device solo run."""
    import dataclasses

    from containerpilot_tpu.parallel import (
        MeshPlan,
        make_mesh,
        shard_params,
    )

    cfg = dataclasses.replace(CFG, d_model=64, n_heads=8, d_ff=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh(jax.devices()[:8], plan=MeshPlan(data=1, model=8))
    sharded = shard_params(params, mesh, cfg)

    eng = SlotEngine(cfg, sharded, MAX_LEN, slots=2, chunk=3)
    try:
        a = eng.submit([1, 2, 3], max_new=6, temperature=0.8, seed=4)
        b = eng.submit([5, 6], max_new=4)
        assert a.result(timeout=180) == _solo(
            params, [1, 2, 3], 6, cfg=cfg, temperature=0.8, seed=4
        )
        assert b.result(timeout=180) == _solo(params, [5, 6], 4, cfg=cfg)
    finally:
        eng.stop()


def test_min_new_matches_generate(params, engine):
    """min_new through the slot engine equals solo generate with the
    same floor (the mask applies at the same sample indices)."""
    tokens = [2, 4, 6]
    free = _solo(params, tokens, 6)
    eos = free[1]
    got = engine.submit(
        tokens, max_new=6, eos_id=eos, min_new=4
    ).result(timeout=120)
    assert got == _solo(
        params, tokens, 6, eos_id=eos, min_new_tokens=4
    )
    assert eos not in got[:4]
    with pytest.raises(ValueError, match="min_new"):
        engine.submit(tokens, max_new=4, min_new=5)


def test_penalties_match_generate(params, engine):
    """Penalties through the slot engine equal solo generate — the
    counts buffer reproduces the scan's bookkeeping exactly."""
    tokens = [1, 2, 3]
    kw = dict(frequency_penalty=50.0, temperature=0.7, seed=8)
    got = engine.submit(tokens, max_new=8, **kw).result(timeout=120)
    assert got == _solo(
        params, tokens, 8, temperature=0.7, seed=8,
        frequency_penalty=50.0,
    )
    assert len(set(got)) == len(got)


@pytest.mark.parametrize("m", [1, 3])
@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "kv_int8"])
@pytest.mark.parametrize("ring", [False, True], ids=["linear", "ring"])
def test_pool_rows_decode_at_their_own_positions(ring, kv_int8, m):
    """One decode_chunk over a pool whose rows stand at DIFFERENT
    positions gives, row for row, the logits and the cache row of
    one-row decode_chunk calls. A linear row that has reached its end
    (a dead slot) writes nothing and disturbs no other row; a ring row
    past its length wraps like its one-row call. And insert_row into
    one slot leaves every other slot's leaves bit-equal."""
    from containerpilot_tpu.models.decode import decode_chunk, prefill
    from containerpilot_tpu.models.slots import insert_row, slot_cache

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=64, max_seq_len=32, window=8 if ring else 0, kv_int8=kv_int8,
    )
    max_len = 16
    params = init_params(jax.random.PRNGKey(0), cfg)
    lengths = [3, 7, 12, max_len]  # the last fills a linear cache
    prompts = [
        jax.random.randint(
            jax.random.PRNGKey(10 + i), (1, n), 0, cfg.vocab_size, jnp.int32)
        for i, n in enumerate(lengths)
    ]
    rows = [prefill(params, p, cfg, max_len)[1] for p in prompts]

    def host(pool):
        return jax.tree.map(np.array, pool)

    pool = slot_cache(cfg, len(rows), max_len)
    for slot, row in enumerate(rows):
        before = host(pool)
        pool = insert_row(pool, row, slot, cfg)
        after = host(pool)
        others = [s for s in range(len(rows)) if s != slot]
        for was, now in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
            np.testing.assert_array_equal(was[others], now[others])
    assert list(np.asarray(pool["pos"])) == lengths
    filled = host(pool)

    tokens = jax.random.randint(
        jax.random.PRNGKey(2), (len(rows), m), 0, cfg.vocab_size, jnp.int32)
    step = jax.jit(lambda p, c, t: decode_chunk(p, c, t, cfg))
    logits, new = step(params, pool, tokens)
    assert list(np.asarray(new["pos"])) == [n + m for n in lengths]
    names = [name for name in new if name != "pos"]
    for slot, row in enumerate(rows):
        if not ring and lengths[slot] + m > max_len:
            # past the end of a linear cache: nothing written
            for name in names:
                for layer in range(cfg.n_layers):
                    np.testing.assert_array_equal(
                        np.asarray(new[name][layer][slot]),
                        filled[name][layer][slot])
            continue
        want_logits, want = step(params, row, tokens[slot:slot + 1])
        np.testing.assert_allclose(
            np.asarray(logits[slot], np.float32),
            np.asarray(want_logits[0], np.float32), rtol=1e-5, atol=1e-5)
        assert int(want["pos"]) == lengths[slot] + m
        for name in names:
            for layer in range(cfg.n_layers):
                np.testing.assert_array_equal(
                    np.asarray(new[name][layer][slot]),
                    np.asarray(want[name][layer, 0]))


# --- an admission as ONE program (ISSUE 43): ``admit_row`` over one
# packed host row against the three-call sequence it stands for

def _three_calls(pool, state, slot, logits, row_cache, cfg, req):
    """The admission as it was issued before ``admit_row``: the row
    key on the host, ``first_sample``, a fetch of the token for
    ``done``, ``insert_row``, ``admit_slot_state``."""
    from containerpilot_tpu.models.slots import (
        admit_slot_state,
        first_sample,
        insert_row,
    )

    key = jax.random.fold_in(jax.random.PRNGKey(req["seed"]), req["row"])
    first = first_sample(
        logits, key, req["temperature"], req["top_k"], req["top_p"], cfg,
        eos_id=req["eos_id"], min_new=req["min_new"],
        bias_idx=req["bias_idx"], bias_val=req["bias_val"],
    )
    first_host = int(jax.device_get(first))
    pool = insert_row(pool, row_cache, slot, cfg)
    state = admit_slot_state(
        state, slot, cfg, last=first, key=key,
        done=first_host == req["eos_id"] or req["max_new"] <= 1,
        **{k: req[k] for k in (
            "temperature", "top_k", "top_p", "eos_id", "pad_id",
            "min_new", "presence", "frequency", "bias_idx", "bias_val")},
    )
    return pool, state, first_host


def _request(cfg, logit_bias=None, **kw):
    from containerpilot_tpu.models.decode import (
        BIAS_SLOTS_MAX,
        normalize_logit_bias,
    )

    idx, val = normalize_logit_bias(cfg, 1, logit_bias, slots=BIAS_SLOTS_MAX)
    req = dict(
        seed=0, row=0, temperature=0.0, top_k=0, top_p=0.0, eos_id=-1,
        pad_id=0, min_new=0, max_new=8, presence=0.0, frequency=0.0,
        bias_idx=idx[0], bias_val=val[0],
    )
    req.update(kw)
    return req


def _assert_admissions_agree(cfg, params, max_len, slots, prompt, req,
                             neighbour):
    """Both ways admit ``neighbour`` at slot 0 (so that the pool and
    the state hold something to leave alone), then ``req`` at the last
    slot; first token, every pool leaf and every state leaf must be
    the same bits. Returns the admitted state's row and the token."""
    from containerpilot_tpu.models.decode import _jitted_prefill
    from containerpilot_tpu.models.slots import (
        admit_row,
        init_slot_state,
        pack_admission,
        slot_cache,
    )

    prefill = _jitted_prefill(cfg, max_len)
    near_logits, near_row = prefill(
        params, np.asarray([[3, 1, 4, 1, 5, 9]], np.int32))
    logits, row = prefill(params, np.asarray([prompt], np.int32))
    slot = slots - 1
    got = []
    for one_program in (False, True):
        pool, state, _ = _three_calls(
            slot_cache(cfg, slots, max_len), init_slot_state(cfg, slots),
            0, near_logits, near_row, cfg, neighbour)
        if one_program:
            pool, state, first = admit_row(
                pool, state, logits, row,
                pack_admission(slot=slot, **req), cfg)
            first = int(jax.device_get(first))
        else:
            pool, state, first = _three_calls(
                pool, state, slot, logits, row, cfg, req)
        got.append((first, jax.device_get(pool), jax.device_get(state)))
    (want_first, want_pool, want_state), (first, pool, state) = got
    assert first == want_first
    assert set(state) == set(want_state)
    for name in want_state:
        np.testing.assert_array_equal(state[name], want_state[name], name)
        assert state[name].dtype == want_state[name].dtype, name
    assert jax.tree.structure(pool) == jax.tree.structure(want_pool)
    for mine, theirs in zip(jax.tree.leaves(pool), jax.tree.leaves(want_pool)):
        assert mine.dtype == theirs.dtype
        np.testing.assert_array_equal(
            np.asarray(mine, np.float32), np.asarray(theirs, np.float32))
    return {name: leaf[slot] for name, leaf in state.items()}, first


def _greedy_first(params, prompt):
    from containerpilot_tpu.models.decode import _jitted_prefill

    logits, _ = _jitted_prefill(CFG, MAX_LEN)(
        params, np.asarray([prompt], np.int32))
    return int(np.argmax(np.asarray(logits)[0]))


ADMIT_PROMPT = [7, 8, 9, 10, 11]
ADMISSIONS = {
    "greedy": dict(),
    "temperature": dict(temperature=0.9, seed=11),
    "top-k-top-p": dict(temperature=0.8, top_k=7, top_p=0.85, seed=5, row=2),
    # seeds past 31 and 32 bits, and a negative one: PRNGKey on the
    # host keeps their low 32 bits, and so does the packed row
    "seed-past-31-bits": dict(temperature=1.1, seed=2147484001, row=1),
    "seed-past-32-bits": dict(temperature=1.1, seed=2 ** 40 + 9),
    "seed-negative": dict(temperature=1.1, seed=-3),
    # eos is what greedy would draw first: under the floor it is masked
    "min-new-eos": dict(eos_id="greedy", min_new=2),
    # ...and without the floor the row ends at token 0
    "eos-at-once": dict(eos_id="greedy", pad_id=63),
    "max-new-1": dict(max_new=1),
    "logit-bias": dict(logit_bias={40: 100.0, 3: -50.0}),
    "logit-bias-sampled": dict(
        logit_bias={n: 2.5 for n in range(5, 45)}, temperature=0.7,
        top_p=0.9, seed=21),
    "penalties": dict(presence=0.4, frequency=0.3, temperature=0.6, seed=2),
}


@pytest.mark.parametrize("case", sorted(ADMISSIONS))
def test_one_program_admission_is_the_three_calls_bit_for_bit(params, case):
    """``admit_row`` over ``pack_admission``'s row gives the first
    token, the pool and every state leaf (``counts``, ``done`` and
    ``keys`` included) that ``first_sample`` -> ``insert_row`` ->
    ``admit_slot_state`` give with the key and ``done`` computed on
    the host, for every kind of request."""
    kw = dict(ADMISSIONS[case])
    greedy = _greedy_first(params, ADMIT_PROMPT)
    if kw.get("eos_id") == "greedy":
        kw["eos_id"] = greedy
    req = _request(CFG, **kw)
    neighbour = _request(CFG, temperature=0.5, top_k=3, seed=1, presence=0.2)
    row, first = _assert_admissions_agree(
        CFG, params, MAX_LEN, 3, ADMIT_PROMPT, req, neighbour)
    # what each case is there for happened
    ended = case in ("eos-at-once", "max-new-1")
    assert bool(row["done"]) == ended
    assert int(row["step_idx"]) == 1 and int(row["last"]) == first
    assert row["counts"].sum() == (0.0 if case == "eos-at-once" else 1.0)
    if case == "min-new-eos":
        assert first != greedy
    if case == "eos-at-once":
        assert first == greedy
    if case == "logit-bias":
        assert first == 40
    if case == "penalties":
        assert (float(row["presence"]), float(row["frequency"])) == (
            np.float32(0.4), np.float32(0.3))


@pytest.mark.parametrize("toy", ["toy-axk1", "toy-granite", "toy-ouro"])
def test_one_program_admission_writes_a_familys_pool_as_the_three_calls(toy):
    """The same over the pools the families bring (latents and
    counters, recurrent state beside keys, a plane per pass and
    layer): the row enters through the family's ``insert_row`` in both
    ways."""
    import os

    from containerpilot_tpu.workload import modelcfg

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "tests", "toy", toy + ".json")
    cfg = modelcfg.load_model_file(path, MAX_LEN)
    weights = cfg.family.init_params(jax.random.PRNGKey(0), cfg)
    req = _request(cfg, temperature=0.8, top_k=9, top_p=0.9, seed=17, row=1,
                   eos_id=2, min_new=1, presence=0.1,
                   logit_bias={5: 1.5, 11: -2.0})
    row, first = _assert_admissions_agree(
        cfg, weights, MAX_LEN, 3, [21, 22, 23, 24, 25, 26, 27], req,
        _request(cfg))
    assert not bool(row["done"]) and int(row["last"]) == first


def test_packed_row_has_one_static_shape_whatever_the_request():
    from containerpilot_tpu.models.slots import (
        ADMIT_ROW_WIDTH,
        pack_admission,
    )

    plain = _request(CFG)
    biased = _request(CFG, logit_bias={n: 1.0 for n in range(40)},
                      temperature=0.3, seed=2 ** 33)
    for req in (plain, biased):
        packed = pack_admission(slot=1, **req)
        assert isinstance(packed, np.ndarray)  # nothing on the device yet
        assert packed.shape == (ADMIT_ROW_WIDTH,) and packed.dtype == np.int32
    no_bias = dict(plain, bias_idx=None, bias_val=None)
    np.testing.assert_array_equal(
        pack_admission(slot=1, **no_bias), pack_admission(slot=1, **plain))
