"""One run of a serving cell: supervisor -> replica job(s) (the
launcher, then the program's ``serve`` main: FleetMember, slot engine,
prefix cache, spill tier) + the gateway job, against a ``file:``
catalog; every request goes through the GATEWAY. Orchestration copied
from chip_smoke.py (PR 21).

Set-up (boot, weights, compile or cache load, warm /health, one
request per listed shape) ends when the window opens; then the mix
runs for ``--seconds``; then counters are read, the tree is stopped,
and, with the chip free, the reference checks a sample of what was
served.
"""
from __future__ import annotations

import asyncio
import json
import math
import os
import random
import sys
import time
from typing import Any, Dict, List

from . import client, loadgen, procs
from .procs import RunFailed
from .stats import percentile, summary

WARM_TIMEOUT_S = 1100.0
TRACE_SECONDS = 3.0


def _supervisor_config(ctx: Dict[str, Any]) -> str:
    """The shape of examples/serving-pod.json5: supervised replica
    job(s), each a FleetMember of service ``inference``, and the
    gateway as another job in front."""
    launch = ctx["config"]["launch"]
    out, root = ctx["out"], ctx["root"]
    catalog = f"file:{os.path.join(out, 'catalog')}"
    jobs = []
    for i, port in enumerate(ctx["replica_ports"]):
        argv = [
            sys.executable, os.path.join(root, launch["launcher"]),
            ctx["config_path"], ctx["control_dirs"][i], "--",
            "--host", "127.0.0.1", "--port", str(port),
            *launch["replica_args"], *ctx["control_args"],
            "--fleet-catalog", catalog, "--fleet-service", "inference",
            "--fleet-id", f"replica-{i + 1}",
        ]
        jobs.append({
            "name": f"replica-{i + 1}", "exec": argv, "restarts": "never",
            "port": port, "interfaces": ["static:127.0.0.1"],
            "health": {"exec": procs.health_exec(port), "interval": 2, "ttl": 10},
        })
    gateway = [
        sys.executable, "-m", "containerpilot_tpu.fleet",
        "--host", "127.0.0.1", "--port", str(ctx["gateway_port"]),
        "--catalog", catalog, "--service", "inference",
        *launch.get("gateway_args", []),
    ]
    jobs.append({
        "name": "gateway", "exec": gateway, "restarts": "never",
        "port": ctx["gateway_port"], "interfaces": ["static:127.0.0.1"],
        "health": {"exec": procs.health_exec(ctx["gateway_port"]),
                   "interval": 2, "ttl": 10},
    })
    path = os.path.join(out, "supervisor.json")
    with open(path, "w") as fh:
        json.dump({
            "consul": catalog,
            "stopTimeout": launch.get("stop_timeout", "5s"),
            "logging": {"level": "INFO", "format": "default", "output": "stdout"},
            # relative to the supervisor's cwd (the checkout's root): a unix
            # socket's path may hold 107 bytes, a checkout's need not fit
            "control": {"socket": os.path.relpath(
                os.path.join(out, "supervisor.sock"), ctx["root"])},
            "jobs": jobs,
        }, fh, indent=1)
    return path


def _snapshot(ctx: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "at": time.monotonic(),
        "goodput": [procs.http_json(p, "GET", "/v1/goodput")
                    for p in ctx["replica_ports"]],
        "model": [procs.http_json(p, "GET", "/v1/model")
                  for p in ctx["replica_ports"]],
        "fleet": procs.http_json(ctx["gateway_port"], "GET", "/fleet"),
    }


def _check_device(ctx: Dict[str, Any], sup) -> Dict[str, Any]:
    """The launcher's first words: what jax runs on. Ends the run
    before anything is built when it is not what the cell asks for."""
    facts = procs.wait_until(
        "the launcher's device facts", 300,
        lambda: procs.read_json(os.path.join(ctx["control_dirs"][0], "device.json")),
        alive=sup,
    )
    if facts["platform"] != ctx["platform"]:
        raise RunFailed(f"jax found platform {facts['platform']!r}, the cell "
                        f"needs {ctx['platform']!r}: nothing was measured")
    if facts["count"] < ctx["chips"]:
        raise RunFailed(f"jax found {facts['count']} devices, the cell asks "
                        f"for {ctx['chips']}")
    return facts


def _failed(record: Dict[str, Any]) -> bool:
    return bool(
        record["error"] or record["status"] != 200 or not record["done"]
        or len(record["tokens"]) != record["max_new"]
    )


def end_to_end(records: List[Dict[str, Any]], window_s: float) -> Dict[str, float]:
    """The serving end-to-end metrics over ALL the window's requests.
    A failed request has no first token: it counts as missing (inf)."""
    judged = [r for r in records if not r["cut"]]
    ttft, tpot, late = [], [], []
    for r in judged:
        if _failed(r) or r["first_s"] is None:
            ttft.append(math.inf)
            continue
        ttft.append((r["first_s"] - r["due_s"]) * 1e3)
        late.append((r["sent_s"] - r["due_s"]) * 1e3)
        if len(r["tokens"]) > 1:
            tpot.append((r["last_s"] - r["first_s"]) * 1e3 / (len(r["tokens"]) - 1))
    tokens = sum(
        n for r in records if not r["error"] for t, n in r["arrivals"]
        if 0.0 <= t <= window_s
    )
    # for the earlier output line: tokens by second of the window, and the
    # longest stretch in which no stream got a token (a stall shows here)
    by_second = [0] * int(math.ceil(window_s))
    instants = sorted(
        t for r in records for t, _n in r["arrivals"] if 0.0 <= t <= window_s)
    for r in records:
        for t, n in r["arrivals"]:
            if 0.0 <= t < len(by_second):
                by_second[int(t)] += n
    edges = [0.0] + instants + [window_s]
    silence = max(b - a for a, b in zip(edges, edges[1:]))
    return {
        "ttft_p95_ms": percentile(ttft, 95),
        "tpot_p95_ms": percentile(tpot, 95),
        "serve_tokens_per_s": tokens / window_s,
        "_ttft": summary(ttft), "_tpot": summary(tpot), "_late": summary(late),
        "_tokens_in_window": tokens, "_tokens_by_second": by_second,
        "_longest_silence_s": silence,
    }


def _sample(records: List[Dict[str, Any]], seed: int, count: int) -> List[Dict[str, Any]]:
    """Finished requests for the reference: the longest, and a draw
    from the seed."""
    done = [r for r in records if not r["cut"] and not _failed(r)]
    if not done:
        return []
    longest = max(done, key=lambda r: r["prompt_len"] + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    random.Random(f"sample:{seed}").shuffle(rest)
    return [longest] + rest[: max(count - 1, 0)]


def window(ctx: Dict[str, Any], seed: int, traced: bool) -> Dict[str, Any]:
    """One measured window of the cell's mix from ``seed`` against the
    server that is up: every request's record, and the counters at
    the window's two edges: before the load starts and when it
    returns (a closed loop: the close; an open loop: the end of its
    drain), BEFORE the profiler's export is waited for, so the export
    is in no counter."""
    traffic = ctx["traffic"]
    vocab = int(ctx["config"]["vocab_size"])
    window_s = float(ctx["seconds"])
    before = _snapshot(ctx)
    marks: Dict[str, Any] = {}
    tracer = None
    if traced:
        length = float(traffic.get("trace_seconds", TRACE_SECONDS))
        tracer = procs.trace_window(
            ctx["control_dirs"][0], ctx["trace_dir"], marks,
            max((window_s - length) / 2, 0.0), length)
    kind = traffic["kind"]
    if kind == "closed":
        records, _zero = asyncio.run(client.run_closed(
            ctx["gateway_port"], loadgen.closed_requests(traffic, vocab, seed),
            int(traffic["clients"]), window_s))
    elif kind == "open":
        schedule = loadgen.open_schedule(traffic, vocab, seed, window_s)
        records, _zero = asyncio.run(client.run_open(
            ctx["gateway_port"], schedule, window_s,
            float(traffic.get("drain_s", 10.0))))
    else:
        raise RunFailed(f"traffic kind {kind!r} is not a serving kind")
    after = _snapshot(ctx)
    procs.join_trace(tracer, marks)
    return {"seed": seed, "records": records, "before": before,
            "after": after, "marks": marks}


def judge(ctx: Dict[str, Any], records: List[Dict[str, Any]],
          seed: int) -> Dict[str, Any]:
    """``correct`` for one window: the reference over a seeded sample
    of the requests it finished, and no failed request."""
    check = ctx["config"]["check"]
    failed = [r for r in records if not r["cut"] and _failed(r)]
    cases = [{"index": r["index"], "prompt": r["prompt"], "tokens": r["tokens"]}
             for r in _sample(records, seed, int(ctx["traffic"]["check_sample"]))]
    verdict: Dict[str, Any] = {"compared": [], "correct": False}
    if cases:
        result = ctx["reference"]({
            "check": "check_served", "cases": cases,
            "max_len": int(check["max_len"]), "window": int(check.get("window", 0)),
            "controls": list(ctx.get("reference_controls", ())),
        })
        for number in ("max_logit_gap", "mean_logit_gap"):
            value, limit = result[number], float(check[number])
            verdict["compared"].append({
                "number": number, "value": value, "limit": limit,
                "positions": result["positions"], "requests": len(cases),
                "holds": value <= limit,
            })
        if result.get("controls"):
            # a builder's call: what the reference itself reads in a lower
            # precision on the same prompts and tokens; never judged
            verdict["controls"] = result["controls"]
        verdict["reference_seconds"] = result["seconds"]
        verdict["cases"] = result["cases"]
    verdict["compared"].append({
        "number": "failed_requests", "value": len(failed), "limit": 0,
        "holds": not failed,
    })
    verdict["correct"] = bool(cases) and all(c["holds"] for c in verdict["compared"])
    return verdict


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    config, traffic = ctx["config"], ctx["traffic"]
    launch = config["launch"]
    out = ctx["out"]
    replicas = int(launch.get("replicas", 1))
    ctx["replica_ports"] = [procs.free_port() for _ in range(replicas)]
    ctx["gateway_port"] = procs.free_port()
    ctx["control_dirs"] = [os.path.join(out, f"control-{i + 1}")
                           for i in range(replicas)]
    for directory in ctx["control_dirs"]:
        os.makedirs(directory)
    ctx["control_args"] = procs.control_args(launch, ctx["control"])
    vocab = int(config["vocab_size"])
    window_s = float(ctx["seconds"])

    sup = procs.spawn(
        [sys.executable, "-m", "containerpilot_tpu", "-config",
         _supervisor_config(ctx)],
        os.path.join(out, "supervisor.log"), ctx["root"],
    )
    ctx["supervisor"] = sup
    facts = _check_device(ctx, sup)
    died = procs.job_died(
        os.path.join(out, "supervisor.log"),
        [f"replica-{i + 1}" for i in range(replicas)] + ["gateway"])
    for port in ctx["replica_ports"]:
        procs.wait_until(
            "a replica's warm /health", WARM_TIMEOUT_S,
            lambda port=port: died() or procs.http_call(
                port, "GET", "/health", timeout_s=5)[0] == 200,
            alive=sup, every_s=0.25,
        )
    health_s = time.monotonic() - ctx["t0"]
    procs.wait_until(
        "the gateway to list every replica", 120,
        lambda: len(procs.http_json(ctx["gateway_port"], "GET", "/fleet")
                    .get("replicas", [])) >= replicas,
        alive=sup,
    )
    warm = asyncio.run(client.run_sequence(
        ctx["gateway_port"], loadgen.warm_requests(traffic, vocab)))
    bad = [r for r in warm if _failed(r)]
    if bad:
        raise RunFailed(f"warm-up request failed: {bad[0]['error'] or bad[0]['status']}")

    # ---- the window ---------------------------------------------------
    for directory in ctx["control_dirs"]:
        procs.command(directory, "window-open")
    setup_s = time.monotonic() - ctx["t0"]
    print(json.dumps({"phase": "window-open", "setup_s": setup_s,
                      "health_s": health_s, "warm_requests": len(warm)}),
          flush=True)
    stalls = procs.StallClock()
    first = window(ctx, ctx["seed"], ctx["trace"])
    stall_s = stalls.stop()
    # a builder's call may read further seeds from the same server
    more = []
    for seed in ctx.get("more_seeds", ()):
        time.sleep(2.0)  # the streams cut at the close free their slots
        more.append(window(ctx, seed, False))
    records, before, after, marks = (
        first["records"], first["before"], first["after"], first["marks"])
    kind = traffic["kind"]
    launcher = [procs.command(d, "stats") for d in ctx["control_dirs"]]

    # ---- teardown: SIGTERM the supervisor, the chip must come free -----
    rc = procs.stop_supervisor(sup, 120, "the serving supervisor")
    strays = procs.tagged([out])
    if rc != 0 or strays:
        raise RunFailed(f"teardown: supervisor exit {rc}, left alive {strays}")

    e2e = end_to_end(records, window_s)
    e2e["setup_s"] = setup_s
    judged = [r for r in records if not r["cut"]]
    failed = [r for r in judged if _failed(r)]
    print(json.dumps({
        "phase": "window", "kind": kind, "requests": len(records),
        "judged": len(judged), "cut_at_close": len(records) - len(judged),
        "failed": len(failed), "first_failure": (failed[0]["error"] or
                                                 failed[0]["status"]) if failed else None,
        "ttft_ms": e2e["_ttft"], "tpot_ms": e2e["_tpot"],
        "generator_late_ms": e2e["_late"],
        "tokens_in_window": e2e["_tokens_in_window"],
        "tokens_by_second": e2e["_tokens_by_second"],
        "longest_silence_s": e2e["_longest_silence_s"],
        "harness_clock_stall_s": stall_s,
        "engine_stage_seconds": {
            stage: round(sum(a["stages_s"].get(stage, 0.0) - b["stages_s"].get(stage, 0.0)
                             for a, b in zip(after["goodput"], before["goodput"])), 3)
            for stage in sorted(after["goodput"][0]["stages_s"])},
        "prefix_cache": [m.get("prefix_cache") for m in after["model"]],
        "kv_spill": [m.get("kv_spill") for m in after["model"]],
    }), flush=True)

    verdict = judge(ctx, records, ctx["seed"])
    if more:
        verdict["more_seeds"] = [
            {"seed": w["seed"], **judge(ctx, w["records"], w["seed"]),
             "e2e": {k: v for k, v in end_to_end(w["records"], window_s).items()
                     if not k.startswith("_")}}
            for w in more]
    for r in records:
        r.pop("prompt", None)  # the artefact keeps sizes, not ids
    return {
        "e2e": e2e, "attempted": len(judged), "failed": len(failed),
        "verdict": verdict, "facts": facts,
        "artefacts": {
            "records": records, "warm_records": warm, "before": before,
            "after": after, "trace_marks": marks, "launcher": launcher,
            "window_s": window_s,
        },
    }
