#!/usr/bin/env python3
"""The benchmark's one command: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about the cell comes from BENCHMARK.json and the files it
names (see benchmark/README.md). The last line of stdout is the
result object of the contract, validated by harness/contract.py BEFORE
it is printed; on a violation, or when jax finds no TPU or too few
chips, the diagnosis goes on an earlier line, no result is printed and
the exit code is not 0.

This process never imports jax: the chip belongs to the supervised
child while it lives, and to the reference child after it has exited.
"""
from __future__ import annotations

import time

T0 = time.monotonic()  # set-up is counted from the command's start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional, Sequence  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark.harness import contract, procs, serving, training  # noqa: E402
from benchmark.harness.procs import RunFailed  # noqa: E402
from benchmark.harness.spec import Cell, SpecError, load_module  # noqa: E402

CHIP = "tpu"
ROLES = {"serve": serving.run, "train": training.run}
REFERENCE_TIMEOUT_S = 600.0
REDUCE_TIMEOUT_S = 300.0


def say(**fields: Any) -> None:
    print(json.dumps(fields), flush=True)


def _platform_env(platform: str) -> Dict[str, str]:
    # a rehearsal pins its children to the CPU; a real run leaves jax
    # to find the chip and fail if it cannot
    return {"JAX_PLATFORMS": "cpu"} if platform != CHIP else {}


def _child(argv: List[str], log: str, env: Dict[str, str], timeout_s: float,
           what: str) -> None:
    proc = procs.spawn(argv, log, ROOT, env)
    try:
        rc = proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        raise RunFailed(f"{what} still running after {timeout_s}s") from None
    if rc != 0:
        with open(log, errors="replace") as fh:
            tail = fh.read()[-1500:]
        raise RunFailed(f"{what} exit {rc}: {tail}")


def _say_compared(compared: Dict[str, Dict[str, Any]]) -> None:
    """The last lines of standard error: each number ``correct``
    compared, beside its limit."""
    for number, c in compared.items():
        print(f"compared {number} {json.dumps(c['value'])} limit "
              f"{json.dumps(c['limit'])}", file=sys.stderr, flush=True)


def make_reference(cell: Cell, ctx: Dict[str, Any]):
    """The configuration's plain reference, run in a child once the
    chip is free. Returns what the reference's check returned."""
    module = os.path.join(os.path.dirname(cell.config_path),
                          cell.config["reference"])

    def reference(spec: Dict[str, Any]) -> Dict[str, Any]:
        spec = {
            **spec, "reference": module, "config_path": cell.config_path,
            "platform": ctx["platform"],
            "compile_cache": os.path.join(ROOT, ".compile_cache"),
        }
        spec_path = os.path.join(ctx["work"], "reference.spec.json")
        out_path = os.path.join(ctx["out"], "reference.json")
        with open(spec_path, "w") as fh:
            json.dump(spec, fh)
        _child(
            [sys.executable, os.path.join(HERE, "harness", "refchild.py"),
             spec_path, out_path],
            os.path.join(ctx["out"], "reference.log"),
            _platform_env(ctx["platform"]), REFERENCE_TIMEOUT_S,
            "the reference child",
        )
        return procs.read_json(out_path)

    return reference


def reduce_trace(ctx: Dict[str, Any], marks: Dict[str, Any]) -> Dict[str, Any]:
    stamps = os.path.join(ctx["work"], "trace.stamps.json")
    with open(stamps, "w") as fh:
        json.dump({"start": marks["start"]["at"], "stop": marks["stop"]["at"]}, fh)
    out_path = os.path.join(ctx["out"], "trace.json")
    argv = [sys.executable, os.path.join(HERE, "harness", "trace_reduce.py"),
            ctx["trace_dir"], out_path, stamps]
    if ctx["platform"] != CHIP:
        argv.append("rehearsal")  # no device plane: see trace_reduce
    try:
        _child(argv, os.path.join(ctx["out"], "trace_reduce.log"),
               {"JAX_PLATFORMS": "cpu"}, REDUCE_TIMEOUT_S, "the trace reduction")
    except RunFailed:
        if not os.path.exists(out_path):
            raise
    summary = procs.read_json(out_path)
    if "error" in summary:
        raise RunFailed(f"trace reduction: {summary['error']} "
                        f"({summary.get('planes') or summary.get('lines')})")
    return summary


def device_object(ctx: Dict[str, Any], launcher: List[Dict[str, Any]],
                  trace: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    peaks = [
        int(stats["peak_bytes_in_use"]) for answer in launcher
        for stats in answer["memory_stats"] if stats
    ]
    first = launcher[0]
    device = {
        "platform": first["platform"], "kind": first["kind"],
        "count": int(first["count"]),
        # the fullest chip's peak, read inside the process that held it
        "memory_peak_bytes": max(peaks) if peaks else 0,
    }
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    return device


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    return run_cell(parser.parse_args(argv))


def run_cell(args: argparse.Namespace, platform: str = CHIP, control: str = "",
             rate_rps: float = 0.0, more_seeds: Sequence[int] = (),
             reference_controls: Sequence[str] = ()) -> int:
    """One run of one cell. The keywords are the builder's
    (benchmark/tests/builder.py), never the driver's: a CPU rehearsal
    of the whole flow (prints no result, exits 3), the program's own
    lower-precision path as the control, the knee sweep's rate,
    further seeds read from the same server, and the reference's own
    lower-precision readings."""
    if not os.path.isdir(os.path.join(ROOT, "containerpilot_tpu")):
        print("benchmark/run.py needs the program: no containerpilot_tpu/ "
              f"beside {HERE}", file=sys.stderr)
        return 2
    try:
        cell = Cell(ROOT, args.workload)
        role = cell.config["launch"]["role"]
        if role not in ROLES:
            raise SpecError(f"configuration role {role!r} has no driver")
    except (SpecError, KeyError) as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 2
    if rate_rps > 0:
        cell.traffic["arrivals"]["rate_rps"] = rate_rps
    traced = bool(args.trace)
    out = os.path.join(ROOT, "chiprun_out", "benchmark", cell.name)
    work = os.path.join(ROOT, ".benchmark_work", cell.name)
    for directory in (out, work):
        shutil.rmtree(directory, ignore_errors=True)
        os.makedirs(directory)
    ctx: Dict[str, Any] = {
        "root": ROOT, "out": out, "work": work, "t0": T0,
        "config": cell.config, "config_path": cell.config_path,
        "traffic": cell.traffic, "seed": args.seed, "seconds": args.seconds,
        "trace": traced, "trace_dir": os.path.join(work, "trace"),
        "platform": platform, "chips": cell.chips, "control": control,
        "more_seeds": list(more_seeds),
        "reference_controls": list(reference_controls),
    }
    ctx["reference"] = make_reference(cell, ctx)
    say(phase="start", workload=cell.name, seed=args.seed, seconds=args.seconds,
        trace=args.trace, control=control or None,
        rate_rps=rate_rps or None,
        compile_cache=os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or os.path.join(ROOT, ".compile_cache"))
    failure = ""
    got: Dict[str, Any] = {}
    trace: Optional[Dict[str, Any]] = None
    try:
        if platform != CHIP:
            os.environ["JAX_PLATFORMS"] = "cpu"  # inherited by the supervisor
        got = ROLES[role](ctx)
        if traced:
            trace = reduce_trace(ctx, got["artefacts"]["trace_marks"])
    except (RunFailed, SpecError, OSError, KeyError, ValueError) as exc:
        failure = f"{type(exc).__name__}: {exc}"
    finally:
        procs.reap(ctx.get("supervisor"))
        killed = procs.kill_tagged([out, work])
        if killed:
            failure = failure or f"processes were left alive: {killed}"
    if failure:
        say(phase="failed", error=failure)
        return 1

    # ---- metrics of this mode -------------------------------------------
    run = {
        "cell": cell.name, "config": cell.config, "traffic": cell.traffic,
        "e2e": got["e2e"], "trace": trace, "device_kind": got["facts"]["kind"],
        **got["artefacts"],
    }
    metrics: Dict[str, Dict[str, Any]] = {}
    no_peak: List[str] = []
    if traced:
        wanted = cell.per_layer()
        for entry in wanted:
            try:
                value = cell.reader(entry["name"])(run)
            except ValueError as exc:
                # e.g. no published peak for this device: an error on the
                # chip, the expected answer in a CPU rehearsal
                if platform == CHIP:
                    say(phase="failed", error=f"{entry['name']}: {exc}")
                    return 1
                no_peak.append(entry["name"])
                continue
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    else:
        wanted = cell.end_to_end()
        for entry in wanted:
            value = got["e2e"].get(entry["name"])
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    for mode, numbers in got["verdict"].get("controls", {}).items():
        say(phase="control", reference_in=mode, **numbers)
    for other in got["verdict"].get("more_seeds", ()):
        say(phase="more-seed", seed=other["seed"], correct=other["correct"],
            compared=other["compared"], controls=other.get("controls"),
            end_to_end=other["e2e"])
    say(phase="verdict", correct=got["verdict"]["correct"],
        reference_seconds=got["verdict"].get("reference_seconds"),
        end_to_end={k: v for k, v in got["e2e"].items() if not k.startswith("_")})
    result: Dict[str, Any] = {
        "correct": bool(got["verdict"]["correct"]),
        "attempted": int(got["attempted"]), "failed": int(got["failed"]),
        "metrics": metrics,
        "device": device_object(ctx, got["artefacts"]["launcher"], trace),
    }
    if trace is not None:
        result["breakdown"] = {
            "device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"],
        }
        # which statistic of the trace the scope readers take an
        # operation's path from
        result["path_stat"] = load_module(os.path.join(
            HERE, "layer_metrics", "trace_scopes.py")).path_stat(run)
    # every number compared, beside its limit: the result's last key
    compared = {
        c["number"]: {"value": c["value"], "limit": c.get("limit", c.get("at_least"))}
        for c in got["verdict"]["compared"]}
    result["compared"] = compared
    rehearsal = platform != CHIP
    if rehearsal and not result["device"]["memory_peak_bytes"]:
        result["device"]["memory_peak_bytes"] = 1  # the CPU keeps no stats
    with open(os.path.join(out, "result.json"), "w") as fh:
        json.dump({"result": result, "verdict": got["verdict"],
                   "e2e": got["e2e"], "trace": trace,
                   "launcher": got["artefacts"]["launcher"]}, fh)
    # a CPU trace has no device plane: what only it can give may be absent
    optional = [m["name"] for m in wanted
                if rehearsal and m["source"] == "device_trace"] + no_peak
    bad = contract.violations(
        result, wanted, traced, platform, cell.chips, optional)
    if bad:
        say(phase="contract", ok=False, violations=bad)
        return 1
    if rehearsal:
        # a CPU run reports no result: nothing here is a device number
        print("REHEARSAL " + json.dumps(result), flush=True)
        _say_compared(compared)
        return 3
    print(json.dumps(result), flush=True)
    _say_compared(compared)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
