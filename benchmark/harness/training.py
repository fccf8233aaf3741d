"""One run of a training cell: supervisor -> one ``train`` job (the
launcher, then the program's ``train`` main) fed from token shards
the harness wrote from ``--seed``. The harness follows the job's
progress file (step, loss, and the wall time stamped after
``float(loss)`` has synchronised), opens the window once the first
steps have compiled and settled, and SIGTERMs the supervisor at the
end (no checkpoint directory, so nothing is saved in the window).
Orchestration copied from chip_smoke.py (PR 21).
"""
from __future__ import annotations

import json
import math
import os
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import loadgen, procs
from .procs import RunFailed
from .stats import leaf_sum_gap, worst_leaf_gap

FIRST_STEP_TIMEOUT_S = 1100.0
STEP_TIMEOUT_S = 120.0
TRACE_SECONDS = 3.0


def _write_shards(ctx: Dict[str, Any]) -> List[str]:
    """The trainer's ``--data-dir`` layout: ``shard_*.npy``, flat int32
    ids. One shard; whole windows only."""
    directory = os.path.join(ctx["work"], "data")
    os.makedirs(directory)
    tokens = loadgen.train_tokens(
        ctx["traffic"], int(ctx["config"]["vocab_size"]), ctx["seed"])
    path = os.path.join(directory, "shard_00000.npy")
    np.save(path, tokens)
    return [path]


def _supervisor_config(ctx: Dict[str, Any], progress: str) -> str:
    """The shape of examples/training-pod.json5: the trainer job,
    health-checked on its progress file."""
    launch, traffic = ctx["config"]["launch"], ctx["traffic"]
    out = ctx["out"]
    argv = [
        sys.executable, os.path.join(ctx["root"], launch["launcher"]),
        ctx["config_path"], ctx["control_dir"], "--",
        *launch["trainer_args"], *ctx["control_args"],
        "--seq-len", str(traffic["seq_len"]), "--batch", str(traffic["batch"]),
        "--steps", "100000000", "--data-dir", os.path.join(ctx["work"], "data"),
        "--progress-file", progress,
    ]
    path = os.path.join(out, "supervisor.json")
    with open(path, "w") as fh:
        json.dump({
            "consul": f"file:{os.path.join(out, 'catalog')}",
            "stopTimeout": launch.get("stop_timeout", "10s"),
            "logging": {"level": "INFO", "format": "default", "output": "stdout"},
            # relative to the supervisor's cwd (the checkout's root): a unix
            # socket's path may hold 107 bytes, a checkout's need not fit
            "control": {"socket": os.path.relpath(
                os.path.join(out, "supervisor.sock"), ctx["root"])},
            "jobs": [{
                "name": "trainer", "exec": argv, "restarts": "never",
                "port": procs.free_port(), "interfaces": ["static:127.0.0.1"],
                "health": {
                    "exec": ["/bin/sh", "-c",
                             f"find {progress} -newermt '-120 seconds' | grep -q ."],
                    "interval": 5, "ttl": 30,
                },
            }],
        }, fh, indent=1)
    return path


class Follower:
    """Reads the progress file as fast as steps can land and keeps
    every step it saw: {step, loss, time}."""

    def __init__(self, path: str, sup, log: str) -> None:
        self.path, self.sup = path, sup
        self.steps: Dict[int, Dict[str, float]] = {}
        self.died = procs.job_died(log, ["trainer"])
        self._looked = 0.0

    def poll(self) -> Optional[int]:
        try:
            with open(self.path) as fh:
                p = json.load(fh)
        except (OSError, ValueError):
            return None
        step = int(p["step"])
        if step not in self.steps:
            self.steps[step] = {"step": step, "loss": float(p["loss"]),
                                "time": float(p["time"])}
        return step

    def until(self, what: str, timeout_s: float, done) -> None:
        deadline = time.monotonic() + timeout_s
        while True:
            if self.sup.poll() is not None:
                raise RunFailed(f"training supervisor exited "
                                f"({self.sup.returncode}) before {what}")
            self.poll()
            if time.monotonic() - self._looked > 1.0:
                self._looked = time.monotonic()
                try:
                    self.died()
                except OSError:
                    pass  # no log yet
            if done():
                return
            if time.monotonic() > deadline:
                raise RunFailed(f"timed out after {timeout_s}s waiting for {what}")
            time.sleep(0.005)


def end_to_end(steps: List[Dict[str, float]], open_at: float, window_s: float,
               tokens_per_step: int) -> Dict[str, Any]:
    """Tokens of the steps whose stamped time falls inside the window,
    over the span between the first and the last of them (the first
    one's own duration is not inside that span, so it is not counted)."""
    inside = [s for s in steps if open_at <= s["time"] <= open_at + window_s]
    inside.sort(key=lambda s: s["step"])
    if len(inside) < 2:
        return {"train_tokens_per_s": None, "_steps": len(inside)}
    span = inside[-1]["time"] - inside[0]["time"]
    gaps = [b["time"] - a["time"] for a, b in zip(inside, inside[1:])]
    missing = inside[-1]["step"] - inside[0]["step"] + 1 - len(inside)
    return {
        "train_tokens_per_s":
            (inside[-1]["step"] - inside[0]["step"]) * tokens_per_step / span,
        "_steps": len(inside), "_steps_missed_by_poll": missing,
        "_step_s_median": sorted(gaps)[len(gaps) // 2],
        "_step_s_max": max(gaps),
        "_loss_first": inside[0]["loss"], "_loss_last": inside[-1]["loss"],
        "_last_step_inside": inside[-1]["step"],
    }


def judge(ctx: Dict[str, Any], seen: Dict[int, Dict[str, float]],
          steps: List[Dict[str, float]], e2e: Dict[str, Any],
          shards: List[str]) -> Dict[str, Any]:
    """``correct`` for a training run. The reference follows the
    trainer through its first ``follow_steps`` steps from the seeded
    weights; compared are each of those steps' loss, the first moment
    AdamW held after step 1 (the first gradient as it got it) and the
    parameters' change after the last, the two by the worst leaf; and
    for the last step INSIDE the window, how far the program's loss
    has fallen below the reference's loss at the seeded weights on the
    same rows (a step that stops updating, or a feed that stops, shows
    there)."""
    config, traffic = ctx["config"], ctx["traffic"]
    check = config["check"]
    batch, seq = int(traffic["batch"]), int(traffic["seq_len"])
    n_windows = int(traffic["windows"])
    follow = int(check["follow_steps"])
    inside = e2e.get("_last_step_inside")
    observed = procs.read_json(os.path.join(ctx["control_dir"], "train_observed.json"))
    result = ctx["reference"]({
        "check": "check_trained", "shards": shards, "seq_len": seq,
        "window": int(check.get("window", 0)),
        "steps": [loadgen.batch_rows(s, batch, n_windows) for s in range(follow)],
        "seeded_batches": [loadgen.batch_rows(inside - 1, batch, n_windows)]
        if inside else [],
    })
    compared = []
    have = bool(observed) and all(s in seen for s in range(1, follow + 1))
    if have:
        for k in range(1, follow + 1):
            limit = float(check["step_loss_gap"][k - 1])
            gap = abs(seen[k]["loss"] - result["losses"][k - 1])
            compared.append({
                "number": f"step{k}_loss_gap", "value": gap, "limit": limit,
                "program": seen[k]["loss"], "reference": result["losses"][k - 1],
                "holds": gap <= limit,
            })
        for number, key in (("first_gradient_norm_gap", "first_moment_norms"),
                            ("update_norm_gap", "change_norms")):
            gap, leaf = worst_leaf_gap(observed[key], result[key])
            compared.append({
                "number": number, "value": gap, "limit": float(check[number]),
                "worst_leaf": leaf, "holds": gap <= float(check[number]),
            })
        gap = leaf_sum_gap(observed["first_moment_sums"], result["first_moment_sums"],
                           result["first_moment_norms"])
        compared.append({
            "number": "first_gradient_sum_gap", "value": gap,
            "limit": float(check["first_gradient_sum_gap"]),
            "holds": gap <= float(check["first_gradient_sum_gap"]),
        })
        rows = [batch, seq + 1]
        compared.append({
            "number": "rows_in_a_step", "value": observed["tokens_shape"],
            "limit": rows, "holds": observed["tokens_shape"] == rows,
        })
    if inside and result["seeded_losses"]:
        fall = result["seeded_losses"][0] - seen[inside]["loss"]
        compared.append({
            "number": "window_loss_fall", "value": fall,
            "at_least": float(check["window_loss_fall_at_least"]),
            "step": inside, "program": seen[inside]["loss"],
            "reference_at_seeded_weights": result["seeded_losses"][0],
            "holds": fall >= float(check["window_loss_fall_at_least"]),
        })
    finite = bool(steps) and all(math.isfinite(s["loss"]) for s in steps)
    compared.append({"number": "losses_finite", "value": int(finite),
                     "limit": 1, "holds": finite})
    return {
        "compared": compared, "reference_seconds": result["seconds"],
        "reference": {k: result[k] for k in ("losses", "grad_norms", "clip")},
        "correct": have and bool(inside) and all(c["holds"] for c in compared),
    }


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    config, traffic = ctx["config"], ctx["traffic"]
    launch = config["launch"]
    out = ctx["out"]
    if traffic["kind"] != "train":
        raise RunFailed(f"traffic kind {traffic['kind']!r} is not a training kind")
    ctx["control_dir"] = os.path.join(out, "control-1")
    os.makedirs(ctx["control_dir"])
    ctx["control_args"] = procs.control_args(launch, ctx["control"])
    window_s = float(ctx["seconds"])
    batch, seq = int(traffic["batch"]), int(traffic["seq_len"])
    warm_steps = int(traffic["warm_steps"])
    shards = _write_shards(ctx)
    progress = os.path.join(out, "progress.json")
    sup = procs.spawn(
        [sys.executable, "-m", "containerpilot_tpu", "-config",
         _supervisor_config(ctx, progress)],
        os.path.join(out, "supervisor.log"), ctx["root"],
    )
    ctx["supervisor"] = sup
    facts = procs.wait_until(
        "the launcher's device facts", 300,
        lambda: procs.read_json(os.path.join(ctx["control_dir"], "device.json")),
        alive=sup,
    )
    if facts["platform"] != ctx["platform"] or facts["count"] < ctx["chips"]:
        raise RunFailed(
            f"jax found {facts['count']} x {facts['platform']!r}, the cell "
            f"needs {ctx['chips']} x {ctx['platform']!r}: nothing was measured")
    follower = Follower(progress, sup, os.path.join(out, "supervisor.log"))
    follower.until("the first step", FIRST_STEP_TIMEOUT_S,
                   lambda: bool(follower.steps))
    first_step_s = time.monotonic() - ctx["t0"]
    follower.until(f"step {warm_steps}", STEP_TIMEOUT_S * warm_steps,
                   lambda: max(follower.steps) >= warm_steps)

    # ---- the window: the same process, the same compiled step ----------
    procs.command(ctx["control_dir"], "window-open")
    open_at = time.time()
    zero = time.monotonic()
    setup_s = zero - ctx["t0"]
    print(json.dumps({"phase": "window-open", "setup_s": setup_s,
                      "first_step_s": first_step_s,
                      "steps_before": max(follower.steps)}), flush=True)
    marks: Dict[str, Any] = {}
    tracer = None
    if ctx["trace"]:
        length = float(traffic.get("trace_seconds", TRACE_SECONDS))
        tracer = procs.trace_window(
            ctx["control_dir"], ctx["trace_dir"], marks,
            max((window_s - length) / 2, 0.0), length)
    follower.until("the window to close", window_s + STEP_TIMEOUT_S,
                   lambda: time.monotonic() - zero >= window_s)
    procs.join_trace(tracer, marks)
    launcher = [procs.command(ctx["control_dir"], "stats")]
    rc = procs.stop_supervisor(sup, 180, "the training supervisor")
    strays = procs.tagged([out])
    if rc != 0 or strays:
        raise RunFailed(f"teardown: supervisor exit {rc}, left alive {strays}")

    steps = [follower.steps[k] for k in sorted(follower.steps)]
    e2e = end_to_end(steps, open_at, window_s, batch * seq)
    e2e["setup_s"] = setup_s
    print(json.dumps({"phase": "window", "kind": "train",
                      **{k: v for k, v in e2e.items()}}), flush=True)

    verdict = judge(ctx, follower.steps, steps, e2e, shards)
    judged = e2e["_steps"]
    return {
        "e2e": e2e, "attempted": max(judged, 1), "failed": 0,
        "verdict": verdict, "facts": facts,
        "artefacts": {
            "steps": steps, "open_at": open_at, "trace_marks": marks,
            "launcher": launcher, "window_s": window_s,
            "tokens_per_step": batch * seq,
        },
    }
