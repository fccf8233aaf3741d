"""Multi-host pod serving (workload/serve_dist.py): real OS processes
rendezvous through a live catalog server, shard the model over a
global mesh — pure TP at 2 processes, a 2x2 dp x tp mesh at 4 — and
answer HTTP byte-identically to a single-host server of the same
config. Failure detection: a wedged follower trips every process's
decode-progress watchdog (exit 86), and under supervision the pod
restarts, re-rendezvouses, and serves again."""
import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODEL_FLAGS = [
    "--max-len", "48", "--d-model", "64", "--n-layers", "1",
    "--n-heads", "2", "--vocab", "128",
]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _sub_env() -> dict:
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # exactly 1 CPU device per process
    # pod boots across this suite recompile the same tiny-model
    # program sets; conftest exported the ONE compile cache dir
    # (JAX_COMPILATION_CACHE_DIR), which the workload CLIs'
    # enable_compile_cache honours, so every boot after the first is
    # a cache re-warm — exactly the crash->restart path the cache
    # exists for, and minutes off the suite on one core
    return env


def _default_cfg():
    """The config MODEL_FLAGS describes — the ONE copy every parity
    check derives from."""
    from containerpilot_tpu.models.transformer import TransformerConfig
    from containerpilot_tpu.workload.modelcfg import derive_d_ff

    return TransformerConfig(
        vocab_size=128, d_model=64, n_heads=2, n_layers=1,
        d_ff=derive_d_ff(64), max_seq_len=48,
    )


def _reference(tokens, max_new, cfg=None, params=None, row=0, **kw):
    """Single-device generate with the server key convention — the
    ONE copy of the fold_in(PRNGKey(seed), row) + _trim parity recipe
    every pod test compares against (row i of an n-sample request
    draws from fold_in(seed, i))."""
    from containerpilot_tpu.models.decode import generate
    from containerpilot_tpu.models.transformer import init_params

    if cfg is None:
        cfg = _default_cfg()
    if params is None:
        params = init_params(jax.random.PRNGKey(0), cfg)
    seed = kw.pop("seed", 0)
    eos = kw.pop("eos_id", -1)
    out = generate(
        params, jnp.asarray([tokens], jnp.int32), cfg, max_new,
        cfg.max_seq_len,
        rng=jnp.stack(
            [jax.random.fold_in(jax.random.PRNGKey(seed), row)]
        ),
        eos_id=eos, **kw,
    )
    from containerpilot_tpu.workload.serve import InferenceServer

    out_row = [int(t) for t in np.asarray(out)[0]]
    return InferenceServer._trim([out_row], max_new, eos)[0]


#: the pod member's entry point as a user types it; _sub_env() pins
#: the child to the CPU platform (JAX_PLATFORMS=cpu) and puts the repo
#: on its path
POD_MAIN = ["-m", "containerpilot_tpu.workload.serve_dist"]


def _wait_catalog(catalog_port):
    deadline = time.monotonic() + 30
    while True:
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{catalog_port}/v1/health/service/x",
                timeout=1,
            )
            return
        except Exception:
            if time.monotonic() > deadline:
                pytest.fail("catalog never became ready")
            time.sleep(0.2)


def _wait_pod_healthy(base, procs, tmp_path, n_procs, deadline_s,
                      log_prefix="pod"):
    deadline = time.monotonic() + deadline_s
    while True:
        try:
            urllib.request.urlopen(f"{base}/health", timeout=2)
            return
        except Exception:
            for i, proc in enumerate(procs):
                assert proc.poll() is None, (
                    tmp_path / f"{log_prefix}{i}.log"
                ).read_text()[-3000:]
            if time.monotonic() > deadline:
                pytest.fail(
                    "pod never became healthy:\n" + "\n".join(
                        (tmp_path / f"{log_prefix}{i}.log")
                        .read_text()[-2000:]
                        for i in range(n_procs)
                    )
                )
            time.sleep(0.5)


@pytest.mark.parametrize(
    "n_procs,dp", [(2, 1), (4, 2)], ids=["tp2", "dp2xtp2"]
)
def test_pod_serves_http(tmp_path, n_procs, dp):
    catalog_port, coord_port, http_port = (
        _free_port(), _free_port(), _free_port()
    )
    env = _sub_env()
    catalog = subprocess.Popen(
        [sys.executable, "-m", "containerpilot_tpu",
         "-catalog-server", f"127.0.0.1:{catalog_port}"],
        cwd=REPO, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    procs = []
    logs = []
    try:
        _wait_catalog(catalog_port)
        for pid in range(n_procs):
            fh = open(tmp_path / f"pod{pid}.log", "w")
            logs.append(fh)
            procs.append(subprocess.Popen(
                [sys.executable, "-u", *POD_MAIN,
                 "--process-id", str(pid),
                 "--num-processes", str(n_procs),
                 "--catalog", f"127.0.0.1:{catalog_port}",
                 "--coordinator-port", str(coord_port),
                 "--advertise-address", "127.0.0.1",
                 "--host", "127.0.0.1", "--port", str(http_port),
                 "--dp", str(dp)]
                # tp2 also proves pod prefix reuse (lockstep LRU on
                # every process) AND chunked admission (the 20-token
                # history cold-prefills in 4-token pieces; the turn-2
                # hit's bucketed suffix takes extend_pieces under the
                # same bound); dp2xtp2 stays on one-shot admission
                + (["--prefix-cache", "2", "--prefill-chunk", "4"]
                   if n_procs == 2 else [])
                + MODEL_FLAGS,
                cwd=REPO, env=env, stdout=fh, stderr=subprocess.STDOUT,
            ))

        base = f"http://127.0.0.1:{http_port}"
        # the single-core box serializes n_procs startup compiles
        _wait_pod_healthy(
            base, procs, tmp_path, n_procs, 240 * max(1, n_procs // 2)
        )

        def post(body):
            req = urllib.request.Request(
                f"{base}/v1/generate",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=240) as resp:
                return json.loads(resp.read().decode())

        greedy = post({"tokens": [[1, 2, 3]], "max_new_tokens": 6})
        assert greedy["tokens"][0] == _reference([1, 2, 3], 6)

        sampled = post({
            "tokens": [[5, 6]], "max_new_tokens": 5,
            "temperature": 0.8, "top_k": 20, "seed": 9,
        })
        assert sampled["tokens"][0] == _reference(
            [5, 6], 5, temperature=0.8, top_k=20, seed=9
        )

        # the newer sampling knobs ride the broadcast payload too
        knobs = post({
            "tokens": [[7, 8, 9]], "max_new_tokens": 6,
            "min_new_tokens": 3, "frequency_penalty": 30.0,
            "logit_bias": {"11": -100},
        })
        assert knobs["tokens"][0] == _reference(
            [7, 8, 9], 6, min_new_tokens=3, frequency_penalty=30.0,
            logit_bias={11: -100.0},
        )
        assert 11 not in knobs["tokens"][0]

        # the per-knob parity matrix (n/stop/bias/logprobs/beam) is
        # topology-independent — prove it once at tp2; the dp2xtp2
        # boot proves what IS topology-bound (lockstep parity,
        # co-batching, streams, score) without re-paying ~6 request
        # rounds of 4-process collectives on this one-core box
        knob_matrix = n_procs == 2
        from containerpilot_tpu.workload.serve import InferenceServer

        ref = _reference([1, 2, 3], 6)
        if knob_matrix:
            # n > 1: one prompt, n samples as n pool slots — row i
            # draws from fold_in(seed, i), the batcher's convention
            two = post({"tokens": [[1, 2, 3]], "max_new_tokens": 6,
                        "n": 2})
            assert two["tokens"][0] == ref
            assert two["tokens"][1] == two["tokens"][0]  # greedy twins
            sampled2 = post({
                "tokens": [[5, 6]], "max_new_tokens": 5,
                "temperature": 0.8, "top_k": 20, "seed": 9, "n": 2,
            })
            assert sampled2["tokens"][0] == sampled["tokens"][0]
            assert sampled2["tokens"][1] == _reference(
                [5, 6], 5, temperature=0.8, top_k=20, seed=9, row=1,
            )

            # stop sequences: OpenAI exclusive trim, identical to the
            # single-host server's whole-row trim of the same output
            stop_seq = ref[2:4]
            stopped = post({"tokens": [[1, 2, 3]],
                            "max_new_tokens": 6,
                            "stop": [stop_seq]})
            assert stopped["tokens"][0] == \
                InferenceServer._trim_stops(
                    [list(ref)], [stop_seq]
                )[0]
            assert len(stopped["tokens"][0]) < len(ref)

            # logit_bias beyond the 16-slot fast path (the OpenAI-300
            # wide table): 20 bans hold, byte-parity with generate
            wb = post({
                "tokens": [[1, 2, 3]], "max_new_tokens": 6,
                "logit_bias": {str(i): -100.0 for i in range(20)},
            })
            assert wb["tokens"][0] == _reference(
                [1, 2, 3], 6,
                logit_bias={i: -100.0 for i in range(20)},
            )
            assert all(t >= 20 for t in wb["tokens"][0])

            # pod prefix reuse: turn 1 (>= MIN_REUSE) misses and
            # seeds every process's identical LRU; turn 2 extends the
            # shared history through the cached rows — byte parity
            # with the single-host reference either way, and the
            # frontend's stats show exactly one miss + one hit
            history = [(i * 5 + 2) % 128 for i in range(20)]
            t1 = post({"tokens": [history], "max_new_tokens": 5})
            assert t1["tokens"][0] == _reference(history, 5)
            turn2 = history + [7, 3]
            t2 = post({"tokens": [turn2], "max_new_tokens": 5,
                       "temperature": 0.6, "seed": 5})
            assert t2["tokens"][0] == _reference(
                turn2, 5, temperature=0.6, seed=5
            )
            with urllib.request.urlopen(
                f"{base}/v1/model", timeout=30
            ) as resp:
                pc_info = json.loads(resp.read().decode())
            assert pc_info["prefix_cache"]["entries"] == 2
            assert pc_info["prefix_cache"]["misses"] == 1
            assert pc_info["prefix_cache"]["hits"] == 1
            assert pc_info["prefix_cache"]["tokens_reused"] > 0

        # /v1/score rides the broadcast too: teacher-forced logprobs
        # match the single-host formula bit-for-bit
        req = urllib.request.Request(
            f"{base}/v1/score",
            data=json.dumps({"tokens": [[1, 2, 3, 4]]}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=240) as resp:
            scored = json.loads(resp.read().decode())
        from containerpilot_tpu.models.transformer import init_params
        from containerpilot_tpu.workload.modelcfg import (
            score_logprobs_fn,
        )

        s_cfg = _default_cfg()
        s_params = init_params(jax.random.PRNGKey(0), s_cfg)
        # pad to the pod's 16-multiple width convention, slice back —
        # the same function the endpoint jits
        toks = jnp.asarray([[1, 2, 3, 4] + [0] * 12], jnp.int32)
        want = [
            round(float(x), 6)
            for x in np.asarray(
                score_logprobs_fn(s_cfg)(s_params, toks)
            )[0][:3]
        ]
        assert scored["logprobs"][0] == want

        if knob_matrix:
            # logprobs echo: per-token logprobs of the trimmed output
            # via lockstep score rounds — the single-host echo numbers
            lp = post({"tokens": [[1, 2, 3]], "max_new_tokens": 6,
                       "logprobs": True})
            assert lp["tokens"][0] == ref
            echo_row = [1, 2, 3] + ref
            width = -(-len(echo_row) // 16) * 16
            picked = np.asarray(score_logprobs_fn(s_cfg)(
                s_params,
                jnp.asarray(
                    [echo_row + [0] * (width - len(echo_row))],
                    jnp.int32,
                ),
            ))[0]
            assert lp["logprobs"][0] == [
                round(float(x), 6) for x in picked[2:2 + len(ref)]
            ]

            # beam search: a one-shot lockstep round, byte-identical
            # to the single-host deterministic beam program
            from containerpilot_tpu.models.beam import beam_search

            beam = post({"tokens": [[1, 2, 3]], "max_new_tokens": 6,
                         "beam_width": 2})
            bt, _sc = beam_search(
                s_params, jnp.asarray([[1, 2, 3]], jnp.int32), s_cfg,
                max_new_tokens=6, max_len=48, beam_width=2,
            )
            assert beam["tokens"][0] == [
                int(t) for t in np.asarray(bt)
            ]

        # SSE streaming over the chunked lockstep rounds: deltas
        # concatenate to the non-streamed answer for the same request
        import http.client

        conn = http.client.HTTPConnection(
            "127.0.0.1", http_port, timeout=240
        )
        conn.request(
            "POST", "/v1/generate",
            json.dumps({"tokens": [[1, 2, 3]], "max_new_tokens": 6,
                        "stream": True}),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Content-Type") == "text/event-stream"
        events, buf = [], b""
        while True:
            data = resp.read1(65536)
            if not data:
                break
            buf += data
            while b"\n\n" in buf:
                raw, buf = buf.split(b"\n\n", 1)
                events.append(json.loads(raw[len(b"data: "):]))
        conn.close()
        assert events[-1]["done"] is True
        streamed = sum(
            (e["tokens"] for e in events if "tokens" in e), []
        )
        assert streamed == greedy["tokens"][0]
        assert events[-1]["count"] == len(streamed)

        # CONTINUOUS BATCHING across the pod: a non-streamed request
        # lands mid-flight next to a running stream (it joins the
        # pool at a chunk boundary instead of queueing behind the
        # whole generation), and BOTH outputs stay byte-identical to
        # their solo references
        conn2 = http.client.HTTPConnection(
            "127.0.0.1", http_port, timeout=240
        )
        conn2.request(
            "POST", "/v1/generate",
            json.dumps({"tokens": [[5, 6]], "max_new_tokens": 40,
                        "temperature": 0.8, "top_k": 20, "seed": 9,
                        "stream": True}),
            {"Content-Type": "application/json"},
        )
        resp2 = conn2.getresponse()
        assert resp2.status == 200
        buf2 = b""
        while b"\n\n" not in buf2:  # the stream is live
            buf2 += resp2.read1(65536)
        mid = post({"tokens": [[1, 2, 3]], "max_new_tokens": 6})
        assert mid["tokens"][0] == greedy["tokens"][0]
        while True:  # drain the co-batched stream to its end
            data = resp2.read1(65536)
            if not data:
                break
            buf2 += data
        conn2.close()
        events2 = [
            json.loads(raw[len(b"data: "):])
            for raw in buf2.split(b"\n\n")
            if raw.startswith(b"data: ")
        ]
        assert events2[-1]["done"] is True
        streamed2 = sum(
            (e["tokens"] for e in events2 if "tokens" in e), []
        )
        assert streamed2 == _reference(
            [5, 6], 40, temperature=0.8, top_k=20, seed=9
        )

        # disconnect mid-stream: the frontend evicts the slot at the
        # next round, the pool keeps serving everyone else
        conn = http.client.HTTPConnection(
            "127.0.0.1", http_port, timeout=240
        )
        conn.request(
            "POST", "/v1/generate",
            json.dumps({"tokens": [[5, 6]], "max_new_tokens": 40,
                        "stream": True}),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        assert resp.status == 200
        buf = b""
        while b"\n\n" not in buf:
            buf += resp.read1(65536)
        resp.close()
        conn.close()
        again = post({"tokens": [[1, 2, 3]], "max_new_tokens": 6})
        assert again["tokens"][0] == greedy["tokens"][0]

        # observability parity: /v1/model reports the pod topology
        # and pool shape, /metrics carries the request/token counters
        info = json.loads(urllib.request.urlopen(
            f"{base}/v1/model", timeout=30
        ).read().decode())
        assert info["pod"]["num_processes"] == n_procs
        assert info["pod"]["mesh"] == {
            "data": dp, "seq": 1, "model": n_procs // dp,
        }
        assert info["slot_engine"]["slots"] == 4
        time.sleep(1)  # let the disconnected stream's close land
        metrics = urllib.request.urlopen(
            f"{base}/metrics", timeout=30
        ).read().decode()
        # plain 200s + 3 streamed 200s (the disconnected stream
        # still counts its 200); the knob matrix adds 6 at tp2 and
        # the prefix-reuse pair adds 2 more
        n_200 = 16.0 if knob_matrix else 8.0
        assert (
            'containerpilot_pod_requests_total'
            '{endpoint="generate",status="200"} %s' % n_200
        ) in metrics
        n_model = 2.0 if knob_matrix else 1.0
        assert (
            'containerpilot_pod_requests_total'
            '{endpoint="model",status="200"} %s' % n_model
        ) in metrics
        assert "containerpilot_pod_generated_tokens_total" in metrics


        # graceful pod shutdown: TERM on the frontend broadcasts the
        # stop; ALL processes exit 0
        procs[0].send_signal(15)
        for i, proc in enumerate(procs):
            assert proc.wait(timeout=60 * max(1, n_procs // 2)) == 0, (
                tmp_path / f"pod{i}.log"
            ).read_text()[-3000:]
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        catalog.terminate()
        catalog.wait(timeout=10)
        for fh in logs:
            fh.close()


def test_pod_frontend_parse_never_leaks_exceptions():
    """Adversarial bodies against the frontend's parse layer: every
    malformed request must raise the ValueError family the handlers
    turn into 422s — anything else would reach the broadcast loop,
    where an exception is deliberately pod-fatal."""
    import random

    from containerpilot_tpu.workload.serve_dist import _Frontend

    f = _Frontend("127.0.0.1", 0, max_len=48, vocab=512)
    rng = random.Random(0)
    atoms = [
        None, True, False, 0, 1, -1, 2**40, -2**40, 1.5, float("nan"),
        float("inf"), "x", "", [], {}, [None], [[]], [[1]], [[-1]],
        [[1, "a"]], [[True]], [[2**40]], {"1": 1}, [[1], [2]],
        [[1, 2, 3]],
    ]
    keys = [
        "tokens", "max_new_tokens", "temperature", "top_k", "top_p",
        "eos_id", "seed", "min_new_tokens", "presence_penalty",
        "frequency_penalty", "logit_bias", "n", "stop", "stream",
        "logprobs", "beam_width",
    ]
    ok = 0
    for _ in range(300):
        body = {
            k: rng.choice(atoms)
            for k in rng.sample(keys, rng.randrange(1, 6))
        }
        try:
            tokens = f._parse_single_row(body)
            f._parse_work(body, tokens)
            ok += 1
        except (ValueError, KeyError, TypeError, OverflowError):
            pass  # the 422 family the handlers catch
    # some random bodies are legal; the point is nothing ELSE raised
    assert ok >= 0


def test_pod_warmup_covers_serve_path():
    """The pod's no-post-grace-compiles invariant, in-process: after
    ``warm_pod``, serving a request at the warmed shapes — a plen-4
    admission with ARBITRARY sampling knobs (they are operands, not
    compile keys), the (slots, chunk) chunk program, the width-16
    scorer — compiles NOTHING. Post-grace compiles are what eat a
    production pod's watchdog deadline, so a regression that adds an
    un-warmed shape to the serve path must fail a test instead of
    wedging a pod. The detector is proven non-vacuous by an unwarmed
    prompt length compiling."""
    import logging

    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.workload import serve_dist as sd

    cfg = _default_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    mirror = sd._SlotMirror(cfg, params, 48, 4, 8)
    sd.warm_pod(mirror)

    records = []
    handler = logging.Handler()
    handler.emit = records.append
    jax_logger = logging.getLogger("jax")
    old_level = jax_logger.level
    jax.config.update("jax_log_compiles", True)
    jax_logger.addHandler(handler)
    jax_logger.setLevel(logging.DEBUG)

    def compiles():
        return [
            r.getMessage() for r in records
            if "ompil" in r.getMessage()
        ]

    def admit_round(tokens, slot, **knobs):
        work = {
            "tokens": tokens, "max_new": 9, "temperature": 0.0,
            "top_k": 0, "top_p": 0.0, "eos_id": -1, "seed": 0,
            "min_new": 0, "presence": 0.0, "frequency": 0.0,
            "logit_bias": {},
        }
        work.update(knobs)
        p = sd._payload_zeros(48, 4)
        p["op"] = np.asarray(sd.OP_ROUND, np.int32)
        sd._fill_admission(p, work, row_idx=0, slot=slot)
        p["run_chunk"] = np.asarray(1, np.int32)
        p["done"][slot] = 0
        sd._apply_round(mirror, p)

    try:
        # warmed shapes + aggressively different KNOB VALUES: zero
        # compiles (temperature/top_k/bias/penalties are operands of
        # the one chunk program, not compile keys)
        admit_round(
            [1, 2, 3, 4], slot=1, temperature=0.7, top_k=5, seed=3,
            min_new=2, presence=0.5, frequency=0.25,
            logit_bias={7: -5.0},
        )
        sc = sd._payload_zeros(48, 4)
        sc["prompt"][:9] = 1
        sc["plen"] = np.asarray(9, np.int32)
        np.asarray(jax.device_get(sd._score_pod(params, cfg, sc, 48)))
        assert not compiles(), compiles()
        # non-vacuous: an UNwarmed prompt length (11 — no other
        # in-process test prefills it) does compile and IS caught
        records.clear()
        admit_round(list(range(1, 12)), slot=2)
        assert compiles()
    finally:
        jax.config.update("jax_log_compiles", False)
        jax_logger.removeHandler(handler)
        jax_logger.setLevel(old_level)


def test_mirror_rounds_match_generate():
    """In-process parity for the device-resident slot mirror: the
    exact per-round device ops every pod process replays — admission
    row-writes into the state dict, chunk rounds under a churning
    broadcast done mask, retirement, and slot REUSE — byte-match solo
    generate. This is the single-process half of the 2-process
    co-batch parity story, and it pins the refactor that removed the
    per-round knob uploads and the torn-state barriers: a request
    admitted mid-flight must change nothing for the row already
    decoding, and a reused slot must carry nothing of its previous
    occupant."""
    from containerpilot_tpu.models.slots import append_chunk
    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.workload import serve_dist as sd

    cfg = _default_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    S, chunk = 4, 8
    mirror = sd._SlotMirror(cfg, params, 48, S, chunk)
    sd.warm_pod(mirror)

    def round_payload(mask, admit_work=None, slot=0, row_idx=0):
        p = sd._payload_zeros(48, S)
        p["op"] = np.asarray(sd.OP_ROUND, np.int32)
        if admit_work is not None:
            sd._fill_admission(p, admit_work, row_idx=row_idx,
                               slot=slot)
        p["run_chunk"] = np.asarray(1, np.int32)
        p["done"] = np.asarray(mask, np.int32)
        return p

    def work(tokens, max_new, **kw):
        w = {
            "tokens": tokens, "max_new": max_new, "temperature": 0.0,
            "top_k": 0, "top_p": 0.0, "eos_id": -1, "seed": 0,
            "min_new": 0, "presence": 0.0, "frequency": 0.0,
            "logit_bias": {},
        }
        w.update(kw)
        return w

    # A (slot 0, greedy, 20 new) decodes alone for one round...
    a_work = work([1, 2, 3, 4], 20)
    em_a: list = []
    first, toks = sd._apply_round(
        mirror, round_payload([0, 1, 1, 1], a_work, slot=0)
    )
    em_a.append(first)
    append_chunk(em_a, toks[0], 20, -1)
    # ...then B (slot 1, SAMPLED — different knobs mid-flight) joins
    b_work = work([5, 6, 7, 8], 12, temperature=0.8, top_k=20, seed=9)
    em_b: list = []
    first, toks = sd._apply_round(
        mirror, round_payload([0, 0, 1, 1], b_work, slot=1)
    )
    em_b.append(first)
    append_chunk(em_a, toks[0], 20, -1)
    append_chunk(em_b, toks[1], 12, -1)
    # third co-batched round finishes A (20 = 1 + 8 + 8 + 3)
    _f, toks = sd._apply_round(mirror, round_payload([0, 0, 1, 1]))
    append_chunk(em_a, toks[0], 20, -1)
    append_chunk(em_b, toks[1], 12, -1)
    assert len(em_a) == 20
    # A retired (mask flips its slot dead); B finishes alone
    _f, toks = sd._apply_round(mirror, round_payload([1, 0, 1, 1]))
    append_chunk(em_b, toks[1], 12, -1)
    assert len(em_b) == 12
    assert em_a == _reference([1, 2, 3, 4], 20)
    assert em_b == _reference(
        [5, 6, 7, 8], 12, temperature=0.8, top_k=20, seed=9
    )
    # slot 0 REUSED: the admission row-write + pool insert must leave
    # nothing of A (and the sampled knobs of B must not leak into a
    # greedy neighbor)
    c_work = work([9, 8, 7, 6], 9, seed=3)
    em_c: list = []
    first, toks = sd._apply_round(
        mirror, round_payload([0, 0, 1, 1], c_work, slot=0)
    )
    em_c.append(first)
    append_chunk(em_c, toks[0], 9, -1)
    _f, toks = sd._apply_round(mirror, round_payload([0, 1, 1, 1]))
    append_chunk(em_c, toks[0], 9, -1)
    assert len(em_c) == 9
    assert em_c == _reference([9, 8, 7, 6], 9, seed=3)


def test_pod_model_prefix_schema_stable_across_boot(run):
    """/v1/model's prefix_cache block must carry the SAME keys during
    the boot window (before warm_pod hands the mirror's live cache to
    the frontend) as after it — a client polling at startup must not
    see the schema change shape."""
    from containerpilot_tpu.workload.serve_dist import _Frontend
    from containerpilot_tpu.workload.serve_prefix import PrefixCache

    f = _Frontend(
        "127.0.0.1", 0, max_len=48, vocab=128,
        pod_info={"prefix_cache": {"entries": 2}}, prefix_entries=2,
    )
    before = json.loads(run(f._model(None)).body.decode())
    # the four counted values a client has always read; the block has
    # since gained the spill tier's (zeroed without a tier), so the
    # keys are compared as a set below, not spelled out here
    counted = ("entries", "hits", "misses", "tokens_reused")
    assert {k: before["prefix_cache"][k] for k in counted} == {
        "entries": 2, "hits": 0, "misses": 0, "tokens_reused": 0,
    }
    # after warm: the live cache (with counted traffic) — same keys
    pc = PrefixCache(2)
    pc.stats["misses"] = 1
    f.prefix_cache = pc
    after = json.loads(run(f._model(None)).body.decode())
    assert set(after["prefix_cache"]) == set(before["prefix_cache"])
    assert {k: after["prefix_cache"][k] for k in counted} == {
        "entries": 2, "hits": 0, "misses": 1, "tokens_reused": 0,
    }
    # unconfigured cache: no block at all, before or after (the
    # single-host server's contract)
    bare = _Frontend("127.0.0.1", 0, max_len=48, vocab=128)
    none = json.loads(run(bare._model(None)).body.decode())
    assert "prefix_cache" not in none


def test_pod_text_completions(tmp_path):
    """--text on the pod: /v1/completions encodes through the byte
    tokenizer, rides the broadcast decode, and byte-matches the
    single-host text contract — streamed (UTF-8 holdback) and not,
    with stop strings plumbed through the shared parser. The pod also
    runs --draft-layers here: the greedy non-streamed completion
    routes through the one-shot lockstep SPECULATIVE round (idle
    pool), and the streamed one through the slot chunks — both must
    byte-match the same reference, proving spec output identity on
    the pod."""
    catalog_port, coord_port, http_port = (
        _free_port(), _free_port(), _free_port()
    )
    env = _sub_env()
    # spec output is byte-identical to plain greedy BY DESIGN, so
    # parity alone can't prove the route; the debug round log pins it
    env["CONTAINERPILOT_POD_DEBUG"] = "1"
    catalog = subprocess.Popen(
        [sys.executable, "-m", "containerpilot_tpu",
         "-catalog-server", f"127.0.0.1:{catalog_port}"],
        cwd=REPO, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    procs = []
    logs = []
    try:
        _wait_catalog(catalog_port)
        for pid in (0, 1):
            fh = open(tmp_path / f"pod{pid}.log", "w")
            logs.append(fh)
            procs.append(subprocess.Popen(
                [sys.executable, "-u", *POD_MAIN,
                 "--process-id", str(pid), "--num-processes", "2",
                 "--catalog", f"127.0.0.1:{catalog_port}",
                 "--coordinator-port", str(coord_port),
                 "--advertise-address", "127.0.0.1",
                 "--host", "127.0.0.1", "--port", str(http_port),
                 "--text", "--vocab", "512", "--max-len", "48",
                 "--d-model", "64", "--n-layers", "2",
                 "--n-heads", "2",
                 "--draft-layers", "1", "--speculate", "2"],
                cwd=REPO, env=env, stdout=fh, stderr=subprocess.STDOUT,
            ))
        base = f"http://127.0.0.1:{http_port}"
        _wait_pod_healthy(base, procs, tmp_path, 2, 240)

        def post(path, body):
            req = urllib.request.Request(
                f"{base}{path}",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"},
            )
            try:
                with urllib.request.urlopen(req, timeout=240) as resp:
                    return resp.status, json.loads(resp.read().decode())
            except urllib.error.HTTPError as exc:
                return exc.code, exc.read().decode()

        status, comp = post(
            "/v1/completions", {"prompt": "hi", "max_new_tokens": 6}
        )
        assert status == 200

        # single-host reference: same encode, eos default, decode
        from containerpilot_tpu.models.transformer import (
            TransformerConfig,
        )
        from containerpilot_tpu.workload.modelcfg import derive_d_ff
        from containerpilot_tpu.workload.text import ByteTokenizer

        t_cfg = TransformerConfig(
            vocab_size=512, d_model=64, n_heads=2, n_layers=2,
            d_ff=derive_d_ff(64), max_seq_len=48,
        )
        tok = ByteTokenizer(512)
        want = _reference(
            tok.encode("hi"), 6, cfg=t_cfg, eos_id=tok.EOS
        )
        assert comp["tokens"] == want
        assert comp["text"] == tok.decode(comp["tokens"])
        # that greedy request ran the speculative path (idle pool,
        # no sampling knobs): BOTH processes log the SPEC round —
        # parity alone couldn't distinguish spec from the slot pool,
        # since their outputs are identical by design
        time.sleep(0.5)
        for pid in (0, 1):
            assert "SPEC plen=" in (
                tmp_path / f"pod{pid}.log"
            ).read_text(), f"pod{pid} never ran the spec round"
        info = json.loads(urllib.request.urlopen(
            f"{base}/v1/model", timeout=30
        ).read().decode())
        assert info["speculative"] == {
            "draft_layers": 1, "speculate": 2,
        }

        # stop strings plumb through the shared parser: a never-
        # matching stop leaves the completion untouched (200, not the
        # round-4 422 carve-out)
        s1, with_stop = post(
            "/v1/completions",
            {"prompt": "hi", "max_new_tokens": 6, "stop": ["\x00zz"]},
        )
        assert s1 == 200 and with_stop["tokens"] == want

        # streamed text: UTF-8-holdback deltas concatenate to the
        # non-streamed text AND ids (the single-host contract,
        # pod-shaped)
        import http.client

        conn = http.client.HTTPConnection(
            "127.0.0.1", http_port, timeout=240
        )
        conn.request(
            "POST", "/v1/completions",
            json.dumps({"prompt": "hi", "max_new_tokens": 6,
                        "stream": True}),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Content-Type") == "text/event-stream"
        buf = b""
        while True:
            data = resp.read1(65536)
            if not data:
                break
            buf += data
        conn.close()
        events = [
            json.loads(raw[len(b"data: "):])
            for raw in buf.split(b"\n\n")
            if raw.startswith(b"data: ")
        ]
        assert events[-1]["done"] is True
        streamed_ids = sum(
            (e["tokens"] for e in events if "tokens" in e), []
        )
        streamed_text = "".join(
            e["text"] for e in events if "text" in e
        )
        assert streamed_ids == comp["tokens"]
        assert streamed_text == comp["text"]

        procs[0].send_signal(15)
        for i, proc in enumerate(procs):
            assert proc.wait(timeout=60) == 0, (
                tmp_path / f"pod{i}.log"
            ).read_text()[-3000:]
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        catalog.terminate()
        catalog.wait(timeout=10)
        for fh in logs:
            fh.close()


def test_pod_restores_checkpoint_in_lockstep(tmp_path):
    """--checkpoint-dir on the pod: every process restores the SAME
    trained weights through orbax's global barriers onto the pod
    mesh (saved on a DIFFERENT, single-process topology — the
    restore re-shards), and answers change accordingly: byte-parity
    with a single-device restore of the same checkpoint. The pod
    also serves ``--kv-int8`` here: every process quantizes the KV
    cache identically, so the lockstep answer byte-matches a
    single-device kv-int8 decode of the same weights (the int8-KV
    serving accelerator composed with the pod)."""
    import numpy as np

    # train a couple of steps single-process to produce the artifact
    ck = tmp_path / "ck"
    worker = os.path.join(REPO, "tests", "capstone_worker.py")
    env = _sub_env()
    trained = subprocess.run(
        [sys.executable, worker, "--process-id", "0",
         "--num-processes", "1", "--tp", "1", "--steps", "2",
         "--global-batch", "4", "--checkpoint-dir", str(ck),
         "--out", str(tmp_path / "t.json")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert trained.returncode == 0, trained.stderr[-2000:]

    # the capstone worker's model config, serving-shaped
    model_flags = [
        "--max-len", "48", "--d-model", "32", "--n-layers", "1",
        "--n-heads", "2", "--vocab", "64",
    ]
    catalog_port, coord_port, http_port = (
        _free_port(), _free_port(), _free_port()
    )
    catalog = subprocess.Popen(
        [sys.executable, "-m", "containerpilot_tpu",
         "-catalog-server", f"127.0.0.1:{catalog_port}"],
        cwd=REPO, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    procs = []
    logs = []
    try:
        _wait_catalog(catalog_port)
        for pid in (0, 1):
            fh = open(tmp_path / f"pod{pid}.log", "w")
            logs.append(fh)
            procs.append(subprocess.Popen(
                [sys.executable, "-u", *POD_MAIN,
                 "--process-id", str(pid), "--num-processes", "2",
                 "--catalog", f"127.0.0.1:{catalog_port}",
                 "--coordinator-port", str(coord_port),
                 "--advertise-address", "127.0.0.1",
                 "--host", "127.0.0.1", "--port", str(http_port),
                 "--checkpoint-dir", str(ck), "--kv-int8"]
                + model_flags,
                cwd=REPO, env=env, stdout=fh, stderr=subprocess.STDOUT,
            ))
        base = f"http://127.0.0.1:{http_port}"
        _wait_pod_healthy(base, procs, tmp_path, 2, 240)

        req = urllib.request.Request(
            f"{base}/v1/generate",
            data=json.dumps(
                {"tokens": [[1, 2, 3]], "max_new_tokens": 6}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=240) as resp:
            got = json.loads(resp.read().decode())["tokens"][0]
        assert "pod serving checkpoint step 2" in (
            tmp_path / "pod0.log"
        ).read_text()

        # reference: single-device restore of the same checkpoint,
        # through the module's ONE parity recipe
        from containerpilot_tpu.models.transformer import (
            TransformerConfig,
        )
        from containerpilot_tpu.parallel import MeshPlan, make_mesh
        from containerpilot_tpu.workload.modelcfg import (
            derive_d_ff,
            restore_params_only,
        )

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=2, n_layers=1,
            d_ff=derive_d_ff(32), max_seq_len=48, kv_int8=True,
        )
        one_dev = make_mesh(
            jax.devices()[:1], plan=MeshPlan(data=1, model=1)
        )
        params, step = restore_params_only(cfg, one_dev, str(ck))
        assert int(step) == 2
        assert got == _reference([1, 2, 3], 6, cfg=cfg, params=params)
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        catalog.terminate()
        catalog.wait(timeout=10)
        for fh in logs:
            fh.close()


def test_pod_serves_int8_lora_window(tmp_path):
    """The load-time model knobs compose on the pod in ONE boot:
    ``--lora-dir`` (adapter restored through orbax's
    global barriers and merged before quantization), ``--int8``
    (weight-only; every process quantizes its shards identically),
    and ``--window`` (sliding-window attention: the pod's slot pool
    runs per-slot ring caches). The greedy request below decodes past
    the window boundary (3 prompt + 6 new > window 8), so the ring
    actually wraps. Byte parity against a single-device reference
    that applies the SAME transforms in the same order to the same
    PRNGKey(0) init."""
    from containerpilot_tpu.models.transformer import (
        TransformerConfig, init_params,
    )
    from containerpilot_tpu.parallel import (
        MeshPlan,
        make_lora_train_step,
        make_mesh,
        restore_params,
        save_checkpoint,
    )
    from containerpilot_tpu.workload.modelcfg import derive_d_ff

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1,
        d_ff=derive_d_ff(32), max_seq_len=48, window=8,
    )
    one_dev = make_mesh(jax.devices()[:1], plan=MeshPlan(1, 1))

    # train a tiny adapter so the merge provably changes the weights
    lora_dir = tmp_path / "lora"
    init_fn, step_fn, abstract = make_lora_train_step(
        cfg, one_dev, rank=4, learning_rate=1e-2
    )
    state = init_fn(jax.random.PRNGKey(3))
    base = init_params(jax.random.PRNGKey(0), cfg)  # the pod's init
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (4, 17), 0, cfg.vocab_size, jnp.int32
    )
    for _ in range(3):
        state, _loss = step_fn(state, base, tokens)
    save_checkpoint(str(lora_dir), 3, state)

    model_flags = [
        "--max-len", "48", "--d-model", "32", "--n-layers", "1",
        "--n-heads", "2", "--vocab", "64",
        "--int8", "--lora-dir", str(lora_dir), "--lora-rank", "4",
        "--window", "8",
    ]
    catalog_port, coord_port, http_port = (
        _free_port(), _free_port(), _free_port()
    )
    env = _sub_env()
    catalog = subprocess.Popen(
        [sys.executable, "-m", "containerpilot_tpu",
         "-catalog-server", f"127.0.0.1:{catalog_port}"],
        cwd=REPO, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    procs = []
    logs = []
    try:
        _wait_catalog(catalog_port)
        for pid in (0, 1):
            fh = open(tmp_path / f"pod{pid}.log", "w")
            logs.append(fh)
            procs.append(subprocess.Popen(
                [sys.executable, "-u", *POD_MAIN,
                 "--process-id", str(pid), "--num-processes", "2",
                 "--catalog", f"127.0.0.1:{catalog_port}",
                 "--coordinator-port", str(coord_port),
                 "--advertise-address", "127.0.0.1",
                 "--host", "127.0.0.1", "--port", str(http_port)]
                + model_flags,
                cwd=REPO, env=env, stdout=fh, stderr=subprocess.STDOUT,
            ))
        base_url = f"http://127.0.0.1:{http_port}"
        _wait_pod_healthy(base_url, procs, tmp_path, 2, 240)

        log0 = (tmp_path / "pod0.log").read_text()
        assert "pod merged lora adapter (rank 4, step 3)" in log0
        assert "pod int8 weight-only params" in log0

        with urllib.request.urlopen(
            f"{base_url}/v1/model", timeout=30
        ) as resp:
            info = json.loads(resp.read().decode())
        assert info["int8"] is True
        assert info["lora"] == {"rank": 4}
        assert info["window"] == 8

        def post(body):
            req = urllib.request.Request(
                f"{base_url}/v1/generate",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=240) as resp:
                return json.loads(resp.read().decode())

        # reference: same init -> same adapter merge -> same int8
        from containerpilot_tpu.models.lora import apply_lora
        from containerpilot_tpu.models.quantized import (
            quantize_model_params,
        )

        adapter, step_n = restore_params(str(lora_dir), abstract)
        assert int(step_n) == 3
        ref_params = quantize_model_params(
            apply_lora(base, adapter, cfg)
        )

        greedy = post({"tokens": [[1, 2, 3]], "max_new_tokens": 6})
        assert greedy["tokens"][0] == _reference(
            [1, 2, 3], 6, cfg=cfg, params=ref_params
        )
        sampled = post({
            "tokens": [[5, 6]], "max_new_tokens": 5,
            "temperature": 0.7, "top_k": 12, "seed": 4,
        })
        assert sampled["tokens"][0] == _reference(
            [5, 6], 5, cfg=cfg, params=ref_params,
            temperature=0.7, top_k=12, seed=4,
        )
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        catalog.terminate()
        catalog.wait(timeout=10)
        for fh in logs:
            fh.close()


def test_pod_serves_cp_long_prompt(tmp_path):
    """``--sp``: context-parallel admission on the pod. Long prompts
    ring their prefill over a 2-process seq axis (each process holds
    half the prompt's activations) and then decode on the replicated
    slot pool; short prompts take the plain path. The reference for
    the cp path is ``cp_generate`` on an IN-PROCESS seq=2 mesh — ring
    numerics against ring numerics, so parity is exact (plain-prefill
    references would differ by the ring's softmax reassociation under
    bf16). Also covered: the non-axis-divisible remainder (one extend
    chunk), /v1/model topology, and the --sp composition rejections."""
    from containerpilot_tpu.models.decode import generate_from_cache
    from containerpilot_tpu.models.transformer import (
        TransformerConfig, init_params,
    )
    from containerpilot_tpu.parallel import MeshPlan, make_mesh
    from containerpilot_tpu.parallel.context import (
        cp_head_buckets,
        cp_prefill_with_remainder,
        pick_cp_head,
    )
    from containerpilot_tpu.workload.modelcfg import derive_d_ff
    from containerpilot_tpu.workload.serve import InferenceServer

    max_len = 96
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1,
        d_ff=derive_d_ff(32), max_seq_len=max_len,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    ref_mesh = make_mesh(
        jax.devices()[:2], plan=MeshPlan(data=1, model=1, seq=2)
    )
    # the pod's exact recipe: startup-bucketed ring head + local
    # remainder extend + decode from the gathered cache (ring numerics
    # against ring numerics — a plain-prefill reference would differ
    # by the ring's softmax reassociation under bf16)
    buckets = cp_head_buckets(24, max_len, 2)
    assert buckets == [24, 48]

    def cp_ref(tokens, max_new, seed=0, **kw):
        head = pick_cp_head(len(tokens), buckets)
        assert head > 0
        logits, cache = cp_prefill_with_remainder(
            params, np.asarray([tokens], np.int32), cfg, ref_mesh,
            max_len, head=head,
        )
        out = generate_from_cache(
            params, cache, logits, cfg, max_new, pos=len(tokens),
            rng=jnp.stack(
                [jax.random.fold_in(jax.random.PRNGKey(seed), 0)]
            ),
            **kw,
        )
        rows = [[int(t) for t in np.asarray(out)[0]]]
        return InferenceServer._trim(rows, max_new, -1)[0]

    model_flags = [
        "--max-len", str(max_len), "--d-model", "32",
        "--n-layers", "1", "--n-heads", "2", "--vocab", "64",
        "--sp", "2", "--cp-min-len", "24",
    ]
    catalog_port, coord_port, http_port = (
        _free_port(), _free_port(), _free_port()
    )
    env = _sub_env()
    catalog = subprocess.Popen(
        [sys.executable, "-m", "containerpilot_tpu",
         "-catalog-server", f"127.0.0.1:{catalog_port}"],
        cwd=REPO, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    procs = []
    logs = []
    try:
        _wait_catalog(catalog_port)
        for pid in (0, 1):
            fh = open(tmp_path / f"pod{pid}.log", "w")
            logs.append(fh)
            procs.append(subprocess.Popen(
                [sys.executable, "-u", *POD_MAIN,
                 "--process-id", str(pid), "--num-processes", "2",
                 "--catalog", f"127.0.0.1:{catalog_port}",
                 "--coordinator-port", str(coord_port),
                 "--advertise-address", "127.0.0.1",
                 "--host", "127.0.0.1", "--port", str(http_port)]
                + model_flags,
                cwd=REPO, env=env, stdout=fh, stderr=subprocess.STDOUT,
            ))
        base_url = f"http://127.0.0.1:{http_port}"
        _wait_pod_healthy(base_url, procs, tmp_path, 2, 240)

        with urllib.request.urlopen(
            f"{base_url}/v1/model", timeout=30
        ) as resp:
            info = json.loads(resp.read().decode())
        assert info["cp"] == {"seq": 2, "min_len": 24}
        assert info["pod"]["mesh"] == {"data": 1, "seq": 2, "model": 1}

        def post(body):
            req = urllib.request.Request(
                f"{base_url}/v1/generate",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=240) as resp:
                return json.loads(resp.read().decode())

        # 40 tokens with buckets [24, 48]: head 24 rings, the
        # 16-token remainder extends locally in one 16-chunk
        long_even = [(i * 7 + 3) % 64 for i in range(40)]
        got = post({"tokens": [long_even], "max_new_tokens": 8})
        assert got["tokens"][0] == cp_ref(long_even, 8)

        # 41 tokens: head 24 rings, remainder 17 extends as 16 + 1
        # (the power-of-two decomposition's < axis tail)
        long_odd = long_even + [11]
        got = post({"tokens": [long_odd], "max_new_tokens": 8})
        assert got["tokens"][0] == cp_ref(long_odd, 8)

        # the sampling contract rides the cp admission unchanged
        sampled = post({
            "tokens": [long_even], "max_new_tokens": 6,
            "temperature": 0.8, "top_k": 12, "seed": 9,
        })
        assert sampled["tokens"][0] == cp_ref(
            long_even, 6, seed=9, temperature=0.8, top_k=12,
        )

        # short prompts stay on the plain replicated path
        short = post({"tokens": [[1, 2, 3]], "max_new_tokens": 6})
        assert short["tokens"][0] == _reference(
            [1, 2, 3], 6, cfg=cfg, params=params
        )
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        catalog.terminate()
        catalog.wait(timeout=10)
        for fh in logs:
            fh.close()

    # composition rejections fail fast, before any rendezvous
    for extra, msg in (
        (["--window", "8"], b"--sp does not compose with --window"),
        (["--draft-layers", "1"],
         b"--sp does not compose with --draft-layers"),
    ):
        res = subprocess.run(
            [sys.executable, *POD_MAIN,
             "--process-id", "0", "--num-processes", "2",
             "--catalog", "127.0.0.1:1", "--sp", "2"] + extra
            + ["--max-len", "96", "--d-model", "32", "--n-layers",
               "2", "--n-heads", "2", "--vocab", "64"],
            cwd=REPO, env=_sub_env(), capture_output=True, timeout=120,
        )
        assert res.returncode != 0
        assert msg in res.stderr + res.stdout


def test_pod_watchdog_turns_wedged_follower_into_exit(tmp_path):
    """A follower that stops making progress WITHOUT dying used to
    hang the frontend's collectives forever (the serve_dist docstring
    conceded as much in round 3). With --watchdog, the idle-heartbeat
    broadcast bounds every process's cycle time, so the wedge trips
    EVERY pod member's decode-progress deadline: all processes
    hard-exit 86 for a supervisor to restart."""
    catalog_port, coord_port, http_port = (
        _free_port(), _free_port(), _free_port()
    )
    wedge = tmp_path / "wedge"
    env = _sub_env()
    catalog = subprocess.Popen(
        [sys.executable, "-m", "containerpilot_tpu",
         "-catalog-server", f"127.0.0.1:{catalog_port}"],
        cwd=REPO, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    procs = []
    logs = []
    try:
        _wait_catalog(catalog_port)
        for pid in (0, 1):
            fh = open(tmp_path / f"pod{pid}.log", "w")
            logs.append(fh)
            procs.append(subprocess.Popen(
                [sys.executable, "-u", *POD_MAIN,
                 "--process-id", str(pid), "--num-processes", "2",
                 "--catalog", f"127.0.0.1:{catalog_port}",
                 "--coordinator-port", str(coord_port),
                 "--advertise-address", "127.0.0.1",
                 "--host", "127.0.0.1", "--port", str(http_port),
                 "--watchdog", "6", "--startup-grace", "240",
                 "--wedge-file", str(wedge)]
                + MODEL_FLAGS,
                cwd=REPO, env=env, stdout=fh, stderr=subprocess.STDOUT,
            ))
        base = f"http://127.0.0.1:{http_port}"
        _wait_pod_healthy(base, procs, tmp_path, 2, 240)

        wedge.write_text("1")  # the follower consumes this and wedges
        for i, proc in enumerate(procs):
            rc = proc.wait(timeout=120)
            assert rc == 86, (
                f"pod{i} rc={rc}:\n"
                + (tmp_path / f"pod{i}.log").read_text()[-3000:]
            )
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        catalog.terminate()
        catalog.wait(timeout=10)
        for fh in logs:
            fh.close()


def _pod_supervisor_config(
    tmp_path, idx, n_procs, catalog_port, coord_port, http_port,
    wedge,
):
    exec_argv = [
        sys.executable, "-u", *POD_MAIN,
        "--process-id", str(idx), "--num-processes", str(n_procs),
        "--catalog", f"127.0.0.1:{catalog_port}",
        "--coordinator-port", str(coord_port),
        "--advertise-address", "127.0.0.1",
        "--host", "127.0.0.1", "--port", str(http_port),
        "--dp", "2",
        # the deadline must exceed the slowest LEGITIMATE cycle; the
        # test's requests reuse the warmed (plen 4, bucket 16) shape
        # so no cycle carries a compile, but 4 processes share one
        # core here — keep slack
        "--watchdog", "20", "--startup-grace", "420",
    ] + MODEL_FLAGS
    if idx == 1:  # exactly one follower carries the fault injector
        exec_argv += ["--wedge-file", str(wedge)]
    config = {
        "stopTimeout": "15s",
        # four supervisors on one box: the default control-socket
        # path would collide
        "control": {"socket": str(tmp_path / f"cp{idx}.socket")},
        "logging": {"level": "INFO", "format": "default",
                    "output": "stdout"},
        "jobs": [
            {
                "name": f"pod{idx}",
                "exec": exec_argv,
                # absorbs: the watchdog exit plus rendezvous races
                # while the pod re-forms
                "restarts": 6,
            }
        ],
    }
    path = tmp_path / f"pod{idx}.json5"
    path.write_text(json.dumps(config))
    return str(path)


def test_supervised_pod_recovers_from_wedged_follower(tmp_path):
    """The serving capstone at n=4 on a 2x2 dp x tp mesh: a follower
    wedges mid-flight; every pod member's watchdog exits 86; the four
    supervisors apply restart budgets; the reincarnated pod
    re-rendezvouses through the catalog (process 0 re-registers the
    coordinator) and serves byte-identical answers again."""
    n_procs = 4
    catalog_port, coord_port, http_port = (
        _free_port(), _free_port(), _free_port()
    )
    wedge = tmp_path / "wedge"
    env = _sub_env()
    # restart speed is the point of the shared compile cache
    # (serve_dist calls enable_compile_cache): the reincarnated pod
    # re-warms from cached executables, shrinking exactly the window
    # this test measures
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla-cache")
    catalog = subprocess.Popen(
        [sys.executable, "-m", "containerpilot_tpu",
         "-catalog-server", f"127.0.0.1:{catalog_port}"],
        cwd=REPO, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    sups = []
    logs = []
    try:
        _wait_catalog(catalog_port)
        for idx in range(n_procs):
            cfg = _pod_supervisor_config(
                tmp_path, idx, n_procs, catalog_port, coord_port,
                http_port, wedge,
            )
            fh = open(tmp_path / f"sup{idx}.log", "w")
            logs.append(fh)
            sups.append(subprocess.Popen(
                [sys.executable, "-m", "containerpilot_tpu",
                 "-config", cfg],
                cwd=REPO, env=env, stdout=fh, stderr=subprocess.STDOUT,
            ))
        base = f"http://127.0.0.1:{http_port}"
        _wait_pod_healthy(base, sups, tmp_path, n_procs, 600,
                          log_prefix="sup")

        def post(body, timeout=240):
            req = urllib.request.Request(
                f"{base}/v1/generate",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return json.loads(resp.read().decode())

        # 4-token prompts ride the warmed (plen 4, bucket 16) decode
        # program: no request-triggered compile can outlast the
        # watchdog deadline on this single-core box
        before = post({"tokens": [[1, 2, 3, 4]], "max_new_tokens": 6})
        assert before["tokens"][0] == _reference([1, 2, 3, 4], 6)

        # inject the wedge; the pod must go DOWN (health unreachable
        # or 503) as the watchdogs fire...
        wedge.write_text("1")
        deadline = time.monotonic() + 180
        while True:
            try:
                urllib.request.urlopen(f"{base}/health", timeout=2)
                if time.monotonic() > deadline:
                    pytest.fail("pod never went unhealthy after wedge")
                time.sleep(0.5)
            except Exception:
                break

        # ...and come BACK: supervisors restart the members, the pod
        # re-rendezvouses, warms, and serves the same answer
        _wait_pod_healthy(base, sups, tmp_path, n_procs, 600,
                          log_prefix="sup")
        # greedy again: the sampled-path compile belongs to the
        # non-watchdog pod tests; here every cycle must stay far
        # under the deadline
        after = post({"tokens": [[5, 6, 7, 8]], "max_new_tokens": 5})
        assert after["tokens"][0] == _reference([5, 6, 7, 8], 5)

        # graceful teardown: stop every supervisor; each stops its pod
        # member (the frontend broadcasts shutdown) without burning a
        # restart, and exits 0
        for proc in sups:
            proc.send_signal(15)
        for i, proc in enumerate(sups):
            rc = proc.wait(timeout=120)
            assert rc == 0, (
                f"sup{i} rc={rc}:\n"
                + (tmp_path / f"sup{i}.log").read_text()[-3000:]
            )
    finally:
        for proc in sups:
            if proc.poll() is None:
                proc.terminate()
        for proc in sups:
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()
        catalog.terminate()
        catalog.wait(timeout=10)
        for fh in logs:
            fh.close()
