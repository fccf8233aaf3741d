#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user types, at
the widths of a 1.2B-class serving model and a d1024 trainer (the
module's constants), and checks what comes out:

  probe      a child prints what jax runs on; no TPU ends the run here
  server     supervisor -> `serve` job (FleetMember, slot engine, prefix
             cache, spill tier) + `fleet` gateway job against a file
             catalog; requests go through the GATEWAY over cp-mux/1
  reference  a child runs models.decode.generate with the same weights
             and the greedy rows are compared token by token
  trainer    supervisor -> `train` job: steps, a checkpoint, SIGKILL,
             the supervisor's restart resumes from the checkpoint, two
             more steps, SIGTERM, exit 0
  kernels    a child compiles the serving prefill (>= 1024 tokens) and
             the training step and finds the pallas kernels in them

The chip belongs to one process at a time: this parent NEVER imports
jax, the phases run strictly one after another, and every child that
touched the chip has exited before the next starts. Every phase prints
one JSON line; the LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the exit code is 0 only then. `--chips 4` runs ONLY what exists
across chips (`serve --tp 4` against a `--tp 1` server, and
`train --tensor-parallel 2` against the one-device loss).

Logs of every child (whole, not tails) land in chiprun_out/chip_smoke/.
"""
from __future__ import annotations

import argparse
import http.client
import json
import os
import random
import shutil
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
#: small files the chip tool brings back: logs, configs, the catalog
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
#: big files that must NOT ride back (checkpoints); git-ignored
WORK = os.path.join(ROOT, ".chip_smoke_work")

#: the platform every chip child must report
PLATFORM = "tpu"
SEED = 0

# -- sizes: a 1.2B-class decode model as the serve flags express it
# (d_model 2048, 16 heads, 16 layers, vocab 32768; d_ff from
# derive_d_ff) and a d1024 / 8-layer / 2048-token training
# configuration at batch 8.
# Module constants so the CPU rehearsal test can shrink them.
SERVE_MODEL = {
    "vocab": 32768, "d_model": 2048, "n_heads": 16, "n_layers": 16,
    "max_len": 2048,
}
SERVE_ENGINE = ["--slots", "8", "--prefix-cache", "4", "--kv-spill-mb", "512"]
PROMPT_LEN = 128        # the "few greedy requests" prompt
LONG_PROMPT_LEN = 1024  # reaches the flash forward's crossover
SHORT_PROMPT_LEN = 4    # below the prefix-reuse floor: one cold path
MAX_NEW = 64
TRAIN_MODEL = {
    "vocab": 32768, "d_model": 1024, "n_heads": 8, "n_layers": 8,
    "seq_len": 2048, "batch": 8,
}
LEARNING_RATE = "3e-4"  # the trainer's default, said out loud
CHECKPOINT_EVERY = 10   # kill lands after this step's save
RESUME_STEPS = 2        # steps the resumed trainer must add
TP_TRAIN_STEPS = 3      # --chips 4: steps of the dp x tp trainer

SERVE_PORT, GATEWAY_PORT = 18431, 18430
#: the supervisor ALWAYS waits its stopTimeout out before it kills and
#: exits (reference parity): long enough for the replica's drain and
#: for the trainer's preemption checkpoint, no longer
SERVE_STOP_TIMEOUT, TRAIN_STOP_TIMEOUT = "12s", "25s"
WARM_TIMEOUT_S = 900.0
REQUEST_TIMEOUT_S = 600.0
TRAIN_TIMEOUT_S = 600.0
CHILD_TIMEOUT_S = 900.0


class PhaseFailed(Exception):
    """A phase's check did not hold; the message says which."""


def emit(phase: str, ok: bool, **fields: Any) -> None:
    print(json.dumps({"phase": phase, "ok": ok, **fields}), flush=True)


# ---------------------------------------------------------------------
# processes
# ---------------------------------------------------------------------


def _child_env() -> Dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    env["PYTHONUNBUFFERED"] = "1"
    return env


def _spawn(argv: List[str], log_name: str) -> subprocess.Popen:
    """Start a child in its own session with its whole stdout+stderr
    in a file under OUT."""
    log = open(os.path.join(OUT, log_name), "ab")
    try:
        return subprocess.Popen(
            argv, cwd=ROOT, env=_child_env(), stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
    finally:
        log.close()


def _self_and_ancestors() -> List[int]:
    """This process and its parents (a shell that launched us may
    name the output directory on ITS command line)."""
    chain, pid = [], os.getpid()
    while pid > 0:
        chain.append(pid)
        try:
            with open(f"/proc/{pid}/stat") as fh:
                pid = int(fh.read().rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            break
    return chain


def _descendants_by_tag() -> List[Tuple[int, str]]:
    """(pid, cmdline) of every live process whose command line names
    this run's directories: the supervisors, their jobs (which run in
    process groups of their own) and our python children."""
    found = []
    skip = _self_and_ancestors()
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) in skip:
            continue
        try:
            with open(f"/proc/{entry}/cmdline", "rb") as fh:
                cmd = fh.read().replace(b"\0", b" ").decode(errors="replace")
        except OSError:
            continue
        if OUT in cmd or WORK in cmd:
            found.append((int(entry), cmd))
    return found


def _kill_all() -> List[str]:
    """SIGKILL whatever this run started and is still alive; returns
    the command lines it had to kill (empty on a clean run)."""
    killed = []
    for pid, cmd in _descendants_by_tag():
        try:
            os.kill(pid, signal.SIGKILL)
            killed.append(cmd)
        except ProcessLookupError:
            pass
    deadline = time.monotonic() + 10
    while _descendants_by_tag() and time.monotonic() < deadline:
        time.sleep(0.1)
    return killed


def _wait_exit(proc: subprocess.Popen, timeout_s: float, what: str) -> int:
    try:
        return proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        raise PhaseFailed(f"{what} still running after {timeout_s}s") from None


def _run_child(fn: str, spec: Dict[str, Any], timeout_s: float) -> Dict[str, Any]:
    """Run one of this file's `_child_*` functions in a fresh
    interpreter (the only kind of process here that imports jax) and
    return the JSON object it prints last. Its whole output is kept."""
    spec_path = os.path.join(OUT, f"{fn}.spec.json")
    with open(spec_path, "w") as fh:
        json.dump(spec, fh)
    log_name = f"{fn}.log"
    proc = _spawn(
        [sys.executable, "-c",
         f"import chip_smoke; chip_smoke._child_{fn}({spec_path!r})"],
        log_name,
    )
    rc = _wait_exit(proc, timeout_s, f"child {fn}")
    with open(os.path.join(OUT, log_name), errors="replace") as fh:
        lines = fh.read().splitlines()
    if rc != 0:
        raise PhaseFailed(f"child {fn} exit {rc}; see {log_name}")
    for line in reversed(lines):
        if line.startswith("CHILD_RESULT "):
            return json.loads(line[len("CHILD_RESULT "):])
    raise PhaseFailed(f"child {fn} printed no result; see {log_name}")


# ---------------------------------------------------------------------
# HTTP (stdlib; the gateway and the replica speak plain HTTP/1.1)
# ---------------------------------------------------------------------


def http_call(
    port: int, method: str, path: str, body: Optional[dict] = None,
    timeout_s: float = 30.0,
) -> Tuple[int, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout_s)
    try:
        payload = json.dumps(body).encode() if body is not None else None
        conn.request(
            method, path, body=payload,
            headers={"Content-Type": "application/json"} if payload else {},
        )
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def http_json(port: int, method: str, path: str, body: Optional[dict] = None,
              timeout_s: float = 30.0) -> dict:
    status, raw = http_call(port, method, path, body, timeout_s)
    if status != 200:
        raise PhaseFailed(f"{method} :{port}{path} -> {status} {raw[:300]!r}")
    return json.loads(raw)


def generate(port: int, tokens: List[int], **extra: Any) -> List[int]:
    """One buffered POST /v1/generate; returns the generated row."""
    body = {"tokens": [tokens], "max_new_tokens": MAX_NEW, **extra}
    out = http_json(port, "POST", "/v1/generate", body, REQUEST_TIMEOUT_S)
    rows = out.get("tokens")
    if not isinstance(rows, list) or len(rows) != 1:
        raise PhaseFailed(f"generate answered {str(out)[:300]}")
    return [int(t) for t in rows[0]]


def generate_stream(port: int, tokens: List[int]) -> Tuple[List[int], int]:
    """One `"stream": true` request; returns (concatenated deltas,
    number of delta events). Fails unless the stream ends in `done`."""
    conn = http.client.HTTPConnection(
        "127.0.0.1", port, timeout=REQUEST_TIMEOUT_S
    )
    try:
        conn.request(
            "POST", "/v1/generate",
            body=json.dumps({
                "tokens": [tokens], "max_new_tokens": MAX_NEW,
                "stream": True,
            }).encode(),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        if resp.status != 200:
            raise PhaseFailed(f"stream -> {resp.status} {resp.read()[:300]!r}")
        if "text/event-stream" not in resp.getheader("content-type", ""):
            raise PhaseFailed("stream answer is not text/event-stream")
        row: List[int] = []
        events = 0
        buffer = b""
        while True:
            chunk = resp.read1(65536)
            if not chunk:
                raise PhaseFailed("stream ended without its done event")
            buffer += chunk
            while b"\n\n" in buffer:
                raw, buffer = buffer.split(b"\n\n", 1)
                if not raw.startswith(b"data: "):
                    continue
                event = json.loads(raw[len(b"data: "):])
                if event.get("done"):
                    if event.get("count") != len(row):
                        raise PhaseFailed(
                            f"done.count {event.get('count')} != "
                            f"{len(row)} streamed tokens"
                        )
                    return row, events
                events += 1
                row.extend(int(t) for t in event.get("tokens") or [])
    finally:
        conn.close()


def _wait_until(what: str, timeout_s: float, probe, alive=None) -> Any:
    """Poll `probe()` (returns a truthy value when done, may raise
    OSError while the port is closed) until the deadline."""
    deadline = time.monotonic() + timeout_s
    while True:
        if alive is not None and alive.poll() is not None:
            raise PhaseFailed(
                f"supervisor exited ({alive.returncode}) while waiting "
                f"for {what}"
            )
        try:
            value = probe()
            if value:
                return value
        except (OSError, http.client.HTTPException):
            pass
        if time.monotonic() > deadline:
            raise PhaseFailed(f"timed out after {timeout_s}s waiting for {what}")
        time.sleep(0.25)


# ---------------------------------------------------------------------
# supervisor configs (JSON is JSON5)
# ---------------------------------------------------------------------


def _health_exec(port: int) -> List[str]:
    return [
        sys.executable, "-c",
        "import sys, urllib.request; "
        f"urllib.request.urlopen('http://127.0.0.1:{port}/health', timeout=5)",
    ]


def _write_config(name: str, config: dict) -> str:
    path = os.path.join(OUT, name)
    with open(path, "w") as fh:
        json.dump(config, fh, indent=1)
    return path


def _serve_config(tag: str, tp: int) -> str:
    """The shape of examples/serving-pod.json5: one supervised `serve`
    job (a FleetMember of service `inference`) with the gateway as a
    second job in front of it."""
    m = SERVE_MODEL
    catalog = f"file:{os.path.join(OUT, 'catalog-' + tag)}"
    serve = [
        sys.executable, "-m", "containerpilot_tpu.workload.serve",
        "--host", "127.0.0.1", "--port", str(SERVE_PORT),
        "--vocab", str(m["vocab"]), "--d-model", str(m["d_model"]),
        "--n-heads", str(m["n_heads"]), "--n-layers", str(m["n_layers"]),
        "--max-len", str(m["max_len"]),
        *SERVE_ENGINE,
        "--fleet-catalog", catalog, "--fleet-service", "inference",
        "--fleet-id", "replica-1",
    ]
    if tp > 1:
        serve += ["--tp", str(tp)]
    gateway = [
        sys.executable, "-m", "containerpilot_tpu.fleet",
        "--host", "127.0.0.1", "--port", str(GATEWAY_PORT),
        "--catalog", catalog, "--service", "inference",
        "--poll-interval", "0.5",
    ]
    return _write_config(f"serve-{tag}.json", {
        "consul": catalog,
        "stopTimeout": SERVE_STOP_TIMEOUT,
        "logging": {"level": "DEBUG", "format": "default", "output": "stdout"},
        "control": {"socket": os.path.join(OUT, f"serve-{tag}.sock")},
        "jobs": [
            {
                "name": "replica", "exec": serve, "restarts": "never",
                "port": SERVE_PORT, "interfaces": ["static:127.0.0.1"],
                "health": {"exec": _health_exec(SERVE_PORT),
                           "interval": 2, "ttl": 10},
            },
            {
                "name": "gateway", "exec": gateway, "restarts": "never",
                "port": GATEWAY_PORT, "interfaces": ["static:127.0.0.1"],
                "health": {"exec": _health_exec(GATEWAY_PORT),
                           "interval": 2, "ttl": 10},
            },
        ],
    })


def _train_argv(tag: str, steps: int, extra: List[str]) -> List[str]:
    m = TRAIN_MODEL
    return [
        sys.executable, "-m", "containerpilot_tpu.workload.train",
        "--steps", str(steps), "--batch", str(m["batch"]),
        "--seq-len", str(m["seq_len"]), "--vocab", str(m["vocab"]),
        "--d-model", str(m["d_model"]), "--n-heads", str(m["n_heads"]),
        "--n-layers", str(m["n_layers"]),
        "--learning-rate", LEARNING_RATE,
        "--progress-file", os.path.join(OUT, f"progress-{tag}.json"),
        *extra,
    ]


def _train_config(tag: str, argv: List[str], restarts: Any) -> str:
    """The shape of examples/training-pod.json5: the trainer job,
    health-checked on its progress file."""
    progress = os.path.join(OUT, f"progress-{tag}.json")
    return _write_config(f"train-{tag}.json", {
        "consul": f"file:{os.path.join(OUT, 'catalog-' + tag)}",
        "stopTimeout": TRAIN_STOP_TIMEOUT,
        "logging": {"level": "DEBUG", "format": "default", "output": "stdout"},
        "control": {"socket": os.path.join(OUT, f"train-{tag}.sock")},
        "jobs": [{
            "name": "trainer", "exec": argv, "restarts": restarts,
            "port": 4000, "interfaces": ["static:127.0.0.1"],
            "health": {
                "exec": ["/bin/sh", "-c",
                         f"find {progress} -newermt '-120 seconds' | grep -q ."],
                "interval": 5, "ttl": 30,
            },
        }],
    })


def _catalog_records(tag: str) -> List[str]:
    found = []
    services = os.path.join(OUT, "catalog-" + tag, "services")
    for base, _dirs, files in os.walk(services):
        found += [os.path.join(base, f) for f in files if f.endswith(".json")]
    return found


def _read_log(name: str) -> str:
    with open(os.path.join(OUT, name), errors="replace") as fh:
        return fh.read()


# ---------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------


def _prompts() -> Dict[str, List[int]]:
    """Every prompt of the run, from SEED."""
    rng = random.Random(SEED)
    vocab = SERVE_MODEL["vocab"]

    def row(n: int) -> List[int]:
        return [rng.randrange(1, vocab) for _ in range(n)]

    return {
        "greedy0": row(PROMPT_LEN), "greedy1": row(PROMPT_LEN),
        "greedy2": row(PROMPT_LEN), "long": row(LONG_PROMPT_LEN),
        "short": row(SHORT_PROMPT_LEN), "session": row(PROMPT_LEN),
        "fresh": row(16),
    }


def phase_probe() -> dict:
    """What jax runs on, from a child that exits before anything else
    starts. main() ends the whole run here when it is not the TPU."""
    device = _run_child("probe", {}, 300)
    emit("probe", device["platform"] == PLATFORM, **device)
    return device


def phase_server(tag: str, tp: int, full: bool) -> Dict[str, Any]:
    """Supervised server + gateway; requests through the gateway.
    `full` adds the one-chip checks (stream, sampling, session reuse,
    422 relay); the four-chip comparison needs only the greedy rows.
    Returns the rows the reference child compares."""
    prompts = _prompts()
    t0 = time.monotonic()
    sup = _spawn(
        [sys.executable, "-m", "containerpilot_tpu",
         "-config", _serve_config(tag, tp)],
        f"supervisor-serve-{tag}.log",
    )
    _wait_until(
        "the replica's warm /health", WARM_TIMEOUT_S,
        lambda: http_call(SERVE_PORT, "GET", "/health", timeout_s=5)[0] == 200,
        alive=sup,
    )
    warm_s = time.monotonic() - t0
    _wait_until(
        "the gateway to list the replica", 60,
        lambda: any(
            r.get("id") == "replica-1"
            for r in http_json(GATEWAY_PORT, "GET", "/fleet").get("replicas", [])
        ),
        alive=sup,
    )
    ledger = http_json(SERVE_PORT, "GET", "/v1/goodput")["stages_s"]
    model = http_json(GATEWAY_PORT, "GET", "/v1/model")
    device = model["device"]
    if device["platform"] != PLATFORM:
        raise PhaseFailed(f"server runs on {device['platform']!r}")
    for key in ("d_model", "n_heads", "n_layers", "max_len"):
        if model[key] != SERVE_MODEL[key]:
            raise PhaseFailed(f"/v1/model {key}={model[key]}")
    want_mesh = {"data": 1, "model": tp} if tp > 1 else None
    if model["mesh"] != want_mesh:
        raise PhaseFailed(f"/v1/model mesh {model['mesh']} != {want_mesh}")
    if device["param_devices"] != list(range(tp)):
        raise PhaseFailed(
            f"params sit on devices {device['param_devices']}, "
            f"want {list(range(tp))}"
        )

    rows: Dict[str, List[int]] = {}
    request_s: Dict[str, float] = {}
    for name in ("greedy0", "greedy1", "greedy2", "long"):
        t = time.monotonic()
        rows[name] = generate(GATEWAY_PORT, prompts[name])
        request_s[name] = round(time.monotonic() - t, 2)
        if len(rows[name]) != MAX_NEW:
            raise PhaseFailed(f"{name}: {len(rows[name])} tokens, want {MAX_NEW}")
    checks: Dict[str, Any] = {}
    if full:
        checks = _serving_checks(prompts)
    fleet = http_json(GATEWAY_PORT, "GET", "/fleet")
    replica = next(r for r in fleet["replicas"] if r["id"] == "replica-1")
    mux, pool = replica["mux"], replica["pool"]
    if not mux["connected"] or mux["unsupported"] or mux["streams_opened"] < 4:
        raise PhaseFailed(f"/fleet mux {mux}")
    if any(pool[k] for k in ("idle", "hits", "misses")):
        raise PhaseFailed(f"classic pool carried traffic: {pool}")
    after = http_json(GATEWAY_PORT, "GET", "/v1/model")["device"]

    # SIGTERM the supervisor: drain, deregister, exit 0, catalog empty
    t_stop = time.monotonic()
    sup.send_signal(signal.SIGTERM)
    rc = _wait_exit(sup, 120, "the serving supervisor after SIGTERM")
    if rc != 0:
        raise PhaseFailed(f"serving supervisor exit {rc}")
    left = _catalog_records(tag)
    if left:
        raise PhaseFailed(f"catalog not empty after SIGTERM: {left}")
    strays = _descendants_by_tag()
    if strays:
        raise PhaseFailed(f"processes outlived the supervisor: {strays}")
    emit(
        f"server-{tag}", True, tp=tp, model=SERVE_MODEL,
        engine=" ".join(SERVE_ENGINE), device=device,
        mesh=model["mesh"], warm_health_s=round(warm_s, 1),
        boot_s=ledger["boot"], compile_warmup_s=ledger["compile_warmup"],
        request_s=request_s, mux=mux, pool=pool,
        bytes_in_use=after["bytes_in_use"], **checks,
        sigterm_exit_s=round(time.monotonic() - t_stop, 1),
        catalog_empty=True,
    )
    return {"prompts": prompts, "rows": rows}


def _serving_checks(prompts: Dict[str, List[int]]) -> Dict[str, Any]:
    """Stream = buffered, sampling, session prefix reuse, 422 relay.
    The stream/sampling pairs use a prompt below the prefix-reuse
    floor, so both requests of a pair take the same cold path and
    must agree exactly."""
    short = prompts["short"]
    buffered = generate(GATEWAY_PORT, short)
    streamed, events = generate_stream(GATEWAY_PORT, short)
    if streamed != buffered:
        raise PhaseFailed(
            f"streamed deltas != buffered row: {streamed} vs {buffered}"
        )
    sampling = {"temperature": 0.8, "top_k": 40, "seed": 7}
    sampled = generate(GATEWAY_PORT, short, **sampling)
    again = generate(GATEWAY_PORT, short, **sampling)
    vocab = SERVE_MODEL["vocab"]
    if len(sampled) != MAX_NEW or not all(0 <= t < vocab for t in sampled):
        raise PhaseFailed(f"sampled row malformed: {sampled}")
    if sampled != again:
        raise PhaseFailed("the same seed sampled two different rows")
    if sampled == buffered:
        raise PhaseFailed("the sampled row equals the greedy row")

    # two turns of one session: turn 2 extends turn 1's row
    turn1 = generate(GATEWAY_PORT, prompts["session"], session_id="smoke-1")
    turn2_prompt = prompts["session"] + turn1 + prompts["fresh"]
    turn2 = generate(GATEWAY_PORT, turn2_prompt, session_id="smoke-1")
    if len(turn2) != MAX_NEW:
        raise PhaseFailed(f"turn 2: {len(turn2)} tokens")
    prefix = http_json(GATEWAY_PORT, "GET", "/v1/model")["prefix_cache"]
    if not prefix or prefix["tokens_reused"] <= 0:
        raise PhaseFailed(f"second turn reused nothing: {prefix}")

    # a 422 must come back through the gateway exactly as the replica
    # wrote it
    bad = {"tokens": [prompts["short"]],
           "max_new_tokens": SERVE_MODEL["max_len"] * 2}
    direct = http_call(SERVE_PORT, "POST", "/v1/generate", bad)
    relayed = http_call(GATEWAY_PORT, "POST", "/v1/generate", bad)
    if direct[0] != 422 or relayed != direct:
        raise PhaseFailed(f"422 relay: replica {direct} gateway {relayed}")
    return {
        "stream_equals_buffered": True, "stream_events": events,
        "sampled_reproducible": True,
        "tokens_reused": prefix["tokens_reused"],
        "prefix_hits": prefix["hits"], "relayed_422": True,
    }


def phase_reference(tag: str, cases: List[dict]) -> None:
    """A child decodes on the chip with the same weights; rows are
    compared token by token (see _child_reference)."""
    result = _run_child(
        "reference",
        {"model": SERVE_MODEL, "max_new": MAX_NEW, "cases": cases,
         "platform": PLATFORM},
        CHILD_TIMEOUT_S,
    )
    for line in result["ties"]:
        # said BEFORE the verdict: which disagreement was a near-tie
        emit(f"reference-{tag}-tie", True, **line)
    ok = not result["failures"]
    emit(
        f"reference-{tag}", ok, compared=result["compared"],
        agree_exactly=result["exact"], near_ties=len(result["ties"]),
        failures=result["failures"], compile_s=result["compile_s"],
        device=result["device"],
    )
    if not ok:
        raise PhaseFailed(f"rows disagree: {result['failures']}")


def _trainer_pids(tag: str) -> List[int]:
    progress = f"progress-{tag}.json"
    return [
        pid for pid, cmd in _descendants_by_tag()
        if "containerpilot_tpu.workload.train" in cmd and progress in cmd
        and "-config" not in cmd
    ]


def _read_progress(tag: str) -> Optional[dict]:
    try:
        with open(os.path.join(OUT, f"progress-{tag}.json")) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None


def _follow_progress(
    tag: str, sup: subprocess.Popen, until_step: int,
    losses: Dict[int, float], newer_than: float = 0.0,
) -> None:
    """Poll the progress file, recording loss per step, until a step
    >= until_step written after `newer_than` shows up."""
    deadline = time.monotonic() + TRAIN_TIMEOUT_S
    while True:
        if sup.poll() is not None:
            raise PhaseFailed(f"training supervisor exited ({sup.returncode})")
        p = _read_progress(tag)
        if p is not None and p["time"] > newer_than:
            losses[int(p["step"])] = float(p["loss"])
            if p["step"] >= until_step:
                return
        if time.monotonic() > deadline:
            raise PhaseFailed(
                f"no progress to step {until_step} in {TRAIN_TIMEOUT_S}s "
                f"(last: {p})"
            )
        time.sleep(0.02)


def phase_trainer() -> None:
    """Supervised trainer: steps, a checkpoint, SIGKILL; the
    supervisor's restart resumes from the checkpoint; two more steps;
    SIGTERM -> preemption checkpoint, job exit 0, supervisor exit 0."""
    tag = "train"
    ckpt = os.path.join(WORK, "ckpt")
    argv = _train_argv(tag, 1_000_000, [
        "--checkpoint-dir", ckpt,
        "--checkpoint-every", str(CHECKPOINT_EVERY),
    ])
    log_name = f"supervisor-{tag}.log"
    t0 = time.monotonic()
    sup = _spawn(
        [sys.executable, "-m", "containerpilot_tpu",
         "-config", _train_config(tag, argv, 1)],
        log_name,
    )
    first: Dict[int, float] = {}
    _follow_progress(tag, sup, 1, first)
    first_step_s = time.monotonic() - t0
    _follow_progress(tag, sup, CHECKPOINT_EVERY + 1, first)
    saved = sorted(os.listdir(ckpt))
    if f"step_{CHECKPOINT_EVERY}" not in saved:
        raise PhaseFailed(f"no checkpoint at step {CHECKPOINT_EVERY}: {saved}")
    pids = _trainer_pids(tag)
    if len(pids) != 1:
        raise PhaseFailed(f"expected one trainer process, found {pids}")
    killed_at = time.time()
    t_kill = time.monotonic()
    os.kill(pids[0], signal.SIGKILL)

    # the supervisor restarts the job; the new trainer resumes
    resumed: Dict[int, float] = {}
    _follow_progress(tag, sup, 0, resumed, newer_than=killed_at)
    resume_first_step_s = time.monotonic() - t_kill
    resumed_from = min(resumed) - 1
    _follow_progress(
        tag, sup, resumed_from + RESUME_STEPS, resumed, newer_than=killed_at
    )
    new_pids = _trainer_pids(tag)
    if new_pids == pids or len(new_pids) != 1:
        raise PhaseFailed(f"no restarted trainer: before {pids} now {new_pids}")
    sup.send_signal(signal.SIGTERM)
    rc = _wait_exit(sup, 180, "the training supervisor after SIGTERM")
    log = _read_log(log_name)
    checks = {
        "supervisor_exit_0": rc == 0,
        "killed_job_seen": "trainer exited with error: code -9" in log,
        "resumed_from_checkpoint":
            f"resumed from checkpoint at step {resumed_from}" in log,
        "resume_point_is_a_checkpoint":
            resumed_from >= CHECKPOINT_EVERY
            and resumed_from % CHECKPOINT_EVERY == 0,
        "preemption_checkpoint": "preempted: checkpoint saved at step" in log,
        "job_exit_0": "trainer exited without error" in log,
        "flash_in_log":
            f"attention train seq={TRAIN_MODEL['seq_len']} window=0: "
            "pallas flash" in log,
        "platform_in_log": f"on {PLATFORM}" in log,
        "losses_finite": all(
            v == v and abs(v) != float("inf")
            for v in list(first.values()) + list(resumed.values())
        ),
        # fixed seed, fresh random batch per step: the loss of the
        # random-init model falls from step 1 as the unigram fit lands
        "loss_falls": first[max(first)] < first[1]
        and resumed[max(resumed)] < first[1],
        "progress_advanced": max(resumed) > max(first) - 1,
        "no_stray_process": not _descendants_by_tag(),
    }
    ok = all(checks.values())
    emit(
        "trainer", ok, model=TRAIN_MODEL,
        first_step_s=round(first_step_s, 1),
        resume_first_step_s=round(resume_first_step_s, 1),
        losses_first={k: round(v, 4) for k, v in sorted(first.items())},
        losses_resumed={k: round(v, 4) for k, v in sorted(resumed.items())},
        resumed_from=resumed_from, **checks,
    )
    shutil.rmtree(ckpt, ignore_errors=True)
    if not ok:
        raise PhaseFailed(
            f"trainer checks failed: "
            f"{[k for k, v in checks.items() if not v]}; see {log_name}"
        )


def phase_kernels() -> dict:
    """The pallas kernels are IN the compiled programs of the chip
    path (nothing interpreted, nothing quietly on XLA attention); also
    the last child, so its jax.devices() is the run's device line."""
    result = _run_child(
        "kernels",
        {"serve": SERVE_MODEL, "train": TRAIN_MODEL,
         "prefill_len": LONG_PROMPT_LEN},
        CHILD_TIMEOUT_S,
    )
    ok = bool(
        result["prefill_has_kernel"] and result["train_step_has_kernel"]
        and result["device"]["platform"] == PLATFORM
    )
    emit("kernels", ok, **result)
    if not ok:
        raise PhaseFailed("a compiled chip program lacks its pallas kernel")
    return result["device"]


def phase_tp_training() -> None:
    """--chips 4 (b): `train --tensor-parallel 2` (data 2 x model 2)
    under the supervisor against the one-device loss at step 1."""
    tag = "tp-train"
    argv = _train_argv(tag, TP_TRAIN_STEPS, ["--tensor-parallel", "2"])
    log_name = f"supervisor-{tag}.log"
    sup = _spawn(
        [sys.executable, "-m", "containerpilot_tpu",
         "-config", _train_config(tag, argv, "never")],
        log_name,
    )
    rc = _wait_exit(sup, TRAIN_TIMEOUT_S, "the dp x tp training supervisor")
    log = _read_log(log_name)
    loss1 = in_use = None
    for line in log.splitlines():
        if "step 1: loss=" in line:
            loss1 = float(line.split("step 1: loss=")[1].split()[0])
        if "device bytes_in_use: " in line:
            in_use = json.loads(
                line.split("device bytes_in_use: ")[1].replace("None", "null")
            )
    if rc != 0 or loss1 is None or in_use is None:
        raise PhaseFailed(
            f"dp x tp trainer: exit {rc}, loss {loss1}, "
            f"bytes_in_use {in_use}; see {log_name}"
        )
    ref = _run_child(
        "train_reference", {"train": TRAIN_MODEL}, CHILD_TIMEOUT_S
    )
    spread = (
        len(in_use) == 4 and all(in_use)
        and max(in_use) < 2 * min(in_use)
    )
    checks = {
        "mesh_in_log": "mesh: {'data': 2, 'model': 2} on " + PLATFORM in log,
        "job_exit_0": "trainer exited without error" in log,
        "loss_matches_one_device": abs(loss1 - ref["loss"]) <= 0.02,
        "state_spread_over_devices": spread,
    }
    ok = all(checks.values())
    emit(
        "tp-training", ok, model=TRAIN_MODEL, mesh={"data": 2, "model": 2},
        loss_step1=loss1, one_device_loss=ref["loss"],
        bytes_in_use=in_use, one_device_bytes_in_use=ref["bytes_in_use"],
        **checks,
    )
    if not ok:
        raise PhaseFailed(
            f"dp x tp training checks failed: "
            f"{[k for k, v in checks.items() if not v]}"
        )


# ---------------------------------------------------------------------
# children: the ONLY code here that imports jax. Each runs in its own
# interpreter (see _run_child) and exits before the next phase starts.
# ---------------------------------------------------------------------


def _child_result(result: dict) -> None:
    print("CHILD_RESULT " + json.dumps(result), flush=True)


def _device_facts() -> dict:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def _child_probe(_spec_path: str) -> None:
    _child_result(_device_facts())


def _model_cfg(model: dict):
    """The TransformerConfig the serve/train flags build from one of
    the size dicts above (max_len for serving, seq_len for training)."""
    from containerpilot_tpu.models.transformer import TransformerConfig
    from containerpilot_tpu.workload.modelcfg import derive_d_ff

    return TransformerConfig(
        vocab_size=model["vocab"], d_model=model["d_model"],
        n_heads=model["n_heads"], n_layers=model["n_layers"],
        d_ff=derive_d_ff(model["d_model"]),
        max_seq_len=model.get("max_len") or model["seq_len"],
    )


def _child_reference(spec_path: str) -> None:
    """Each case is a prompt with rows to compare: `a` (a server's
    row) against `b` (another server's row) or, without `b`, against
    models.decode.generate run here with the same PRNGKey(0) weights.
    Rows must agree token by token. The ONE admitted exception: at the
    first disagreement BOTH tokens are, by this reference's own
    logits, inside bf16 rounding of its best token: each at most
    4 x 2^-8 (bf16's unit roundoff) x |winning logit| below it, about
    0.06 at the logit sizes random weights give. Activations are
    rounded to bf16 after every one of the model's layers, and two
    correct programs that round in a different order (slot engine vs
    generate's scan, four shards vs one) differ by that much; three
    tokens can sit inside it. The rows then fork for good, so later
    positions are not compared. Every near-tie is reported."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from containerpilot_tpu.models.decode import generate
    from containerpilot_tpu.models.transformer import forward, init_params
    from containerpilot_tpu.workload.modelcfg import enable_compile_cache

    enable_compile_cache()
    with open(spec_path) as fh:
        spec = json.load(fh)
    device = _device_facts()
    cfg = _model_cfg(spec["model"])
    max_len, max_new = spec["model"]["max_len"], spec["max_new"]
    params = init_params(jax.random.PRNGKey(0), cfg)
    score = jax.jit(lambda p, t: forward(p, t, cfg)[0, -1])
    compile_s = 0.0
    seen_shapes = set()
    exact, ties, failures = 0, [], []
    for case in spec["cases"]:
        prompt, a = case["prompt"], case["a"]
        if "b" in case:
            b, versus = case["b"], case["b_name"]
        else:
            t0 = time.monotonic()
            out = generate(
                params, jnp.asarray([prompt], jnp.int32), cfg,
                max_new_tokens=max_new, max_len=max_len,
            )
            b = [int(t) for t in np.asarray(out)[0]]
            versus = "generate"
            if len(prompt) not in seen_shapes:
                # the first call per prompt length compiles
                seen_shapes.add(len(prompt))
                compile_s += time.monotonic() - t0
        if len(a) != len(b):
            failures.append({"case": case["name"], "why": "length",
                             "a": len(a), "b": len(b)})
            continue
        diff = next((i for i in range(len(a)) if a[i] != b[i]), None)
        if diff is None:
            exact += 1
            continue
        # this reference's own next-token logits after the common
        # prefix, teacher-forced through the plain forward
        context = jnp.asarray([prompt + a[:diff]], jnp.int32)
        logits = np.asarray(score(params, context), np.float32)
        top2 = np.argsort(logits)[-2:][::-1]
        best = float(logits[top2[0]])
        tol = 4 * 2.0 ** -8 * abs(best)
        gaps = [best - float(logits[a[diff]]), best - float(logits[b[diff]])]
        line = {
            "case": case["name"], "versus": versus, "position": diff,
            "a": a[diff], "b": b[diff],
            "reference_top2": [int(t) for t in top2],
            "top2_margin": round(best - float(logits[top2[1]]), 5),
            "gap_a": round(gaps[0], 5), "gap_b": round(gaps[1], 5),
            "tolerance": round(tol, 5),
        }
        (ties if max(gaps) <= tol else failures).append(line)
    _child_result({
        "device": device, "compared": len(spec["cases"]), "exact": exact,
        "ties": ties, "failures": failures,
        "compile_s": round(compile_s, 1),
    })


def _child_kernels(spec_path: str) -> None:
    """Compile the serving prefill at >= 1024 tokens and one training
    step for the device jax runs on, from shapes alone, and look for
    the Mosaic kernel (`tpu_custom_call`) in the compiled text."""
    import jax
    import jax.numpy as jnp

    from containerpilot_tpu.models.decode import prefill
    from containerpilot_tpu.models.transformer import init_params
    from containerpilot_tpu.parallel import (
        MeshPlan,
        abstract_train_state,
        make_mesh,
        make_train_step,
    )
    from containerpilot_tpu.workload.modelcfg import enable_compile_cache

    enable_compile_cache()
    with open(spec_path) as fh:
        spec = json.load(fh)
    marker = "tpu_custom_call"
    cfg = _model_cfg(spec["serve"])
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((1, spec["prefill_len"]), jnp.int32)
    t0 = time.monotonic()
    prefill_text = jax.jit(
        lambda p, t: prefill(p, t, cfg, spec["serve"]["max_len"])
    ).lower(params, tokens).compile().as_text()
    prefill_s = time.monotonic() - t0

    tcfg = _model_cfg(spec["train"])
    mesh = make_mesh(jax.devices()[:1], plan=MeshPlan(1, 1))
    state = abstract_train_state(jax.random.PRNGKey(0), tcfg, mesh)
    batch = jax.ShapeDtypeStruct(
        (spec["train"]["batch"], spec["train"]["seq_len"] + 1), jnp.int32
    )
    t0 = time.monotonic()
    # make_train_step returns a closure over its jitted step; an outer
    # jit traces straight through it
    step_text = jax.jit(make_train_step(tcfg, mesh)).lower(
        state, batch
    ).compile().as_text()
    step_s = time.monotonic() - t0
    _child_result({
        "device": _device_facts(),
        "prefill_len": spec["prefill_len"],
        "prefill_has_kernel": marker in prefill_text,
        "prefill_kernel_calls": prefill_text.count(marker),
        "prefill_compile_s": round(prefill_s, 1),
        "train_step_has_kernel": marker in step_text,
        "train_step_kernel_calls": step_text.count(marker),
        "train_step_compile_s": round(step_s, 1),
    })


def _child_train_reference(spec_path: str) -> None:
    """The trainer's step-1 loss on ONE device: same init key, same
    step-0 batch key as workload/train.py."""
    import jax
    import jax.numpy as jnp

    from containerpilot_tpu.parallel import (
        MeshPlan,
        init_train_state,
        make_mesh,
        make_train_step,
    )
    from containerpilot_tpu.workload.modelcfg import enable_compile_cache

    enable_compile_cache()
    with open(spec_path) as fh:
        model = json.load(fh)["train"]
    cfg = _model_cfg(model)
    mesh = make_mesh(jax.devices()[:1], plan=MeshPlan(1, 1))
    state = init_train_state(jax.random.PRNGKey(0), cfg, mesh)
    tokens = jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(1), 0),
        (model["batch"], model["seq_len"] + 1), 0, cfg.vocab_size, jnp.int32,
    )
    _state, loss = make_train_step(cfg, mesh)(state, tokens)
    _child_result({
        "device": _device_facts(), "loss": float(loss),
        "bytes_in_use": [
            (d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.local_devices()
        ],
    })


# ---------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------


def _cases(served: Optional[Dict[str, Any]]) -> List[dict]:
    if served is None:
        raise PhaseFailed("not run: a server phase gave no rows")
    return [
        {"name": name, "prompt": served["prompts"][name], "a": row}
        for name, row in served["rows"].items()
    ]


def _phase(name: str, fn, *args: Any) -> Tuple[bool, Any]:
    """Run one phase. A failed phase is reported and fails the run;
    whatever it left alive is killed, so the next phase (they all
    still run) starts from a free chip."""
    try:
        return True, fn(*args)
    except PhaseFailed as exc:
        emit(name, False, error=str(exc), killed=_kill_all())
        return False, None


def run_one_chip() -> Tuple[bool, Optional[dict]]:
    """server -> reference -> trainer (+ its resumed self) -> kernels;
    returns (all ok, the last child's device facts)."""
    ok_server, served = _phase(
        "server-tp1", phase_server, "tp1", 1, True
    )
    ok_reference, _ = _phase(
        "reference-tp1", lambda: phase_reference("tp1", _cases(served))
    )
    ok_trainer, _ = _phase("trainer", phase_trainer)
    ok_kernels, device = _phase("kernels", phase_kernels)
    return ok_server and ok_reference and ok_trainer and ok_kernels, device


def run_four_chips() -> bool:
    """Only what exists across chips, and what it is compared with."""
    ok_tp4, tp4 = _phase("server-tp4", phase_server, "tp4", 4, False)
    ok_tp1, tp1 = _phase("server-tp1", phase_server, "tp1", 1, False)

    def compare() -> None:
        others = {case["name"]: case["a"] for case in _cases(tp1)}
        phase_reference("tp4-vs-tp1", [
            {**case, "b": others[case["name"]], "b_name": "tp1"}
            for case in _cases(tp4)
        ])

    ok_rows, _ = _phase("reference-tp4-vs-tp1", compare)
    ok_train, _ = _phase("tp-training", phase_tp_training)
    return ok_tp4 and ok_tp1 and ok_rows and ok_train


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 = run ONLY the tensor-parallel serving and dp x tp "
        "training comparisons (needs a four-chip host)",
    )
    args = parser.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "containerpilot_tpu")):
        print("chip_smoke.py runs from the root of the repo", file=sys.stderr)
        return 2
    shutil.rmtree(OUT, ignore_errors=True)
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(OUT)
    os.makedirs(WORK)
    device = {"platform": None, "kind": None, "count": 0}
    ok = False
    try:
        device = phase_probe()
        if device["platform"] != PLATFORM:
            raise PhaseFailed(
                f"jax found platform {device['platform']!r}, not "
                f"{PLATFORM!r}: nothing else was started"
            )
        if device["count"] != args.chips:
            raise PhaseFailed(
                f"--chips {args.chips} on a host with {device['count']} devices"
            )
        if args.chips == 4:
            ok = run_four_chips()
        else:
            ok, last = run_one_chip()
            ok = ok and last == device
    except PhaseFailed as exc:
        emit("run", False, error=str(exc))
    finally:
        killed = _kill_all()
        if killed:
            ok = False
            emit("cleanup", False, killed=killed)
        shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
