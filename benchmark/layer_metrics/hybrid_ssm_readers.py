"""Shared by the readers of the hybrid state-space family
(``decode_step_device_ms.hybrid-ssm``, ``decode_step_roofline.hybrid-ssm``,
``decode_ssm_share``, ``ssm_state_roofline``,
``expert_matmul_roofline.hybrid-ssm``).

From the trace (``scoped``): what mla_moe_readers.py's ``scoped``
gives, with the ``ssm.*`` scopes among the innermost names: inside the
slot engine's decode programs (``jit_run``), self seconds by the
INNERMOST dotted scope of an operation's path (``ssm.update``,
``mlp.experts``, ...), the token-steps of the traced window (the
executions of ``sample``) and the programs' device seconds.

From the counters: ``/v1/model`` ``state.ssm_row_steps`` counts every
row of the pool x mamba layers x steps (a retired row steps on, on
pads), so over the window's two snapshots it gives the pool's steps;
the LIVE rows of a step are the tokens the engine handed out (less one
per admission, the prefill's) over those steps, NOT clamped to the
slots: a count that came out over them would show as a roofline over
100 %, not hide. ``experts`` is read as for the other expert families
(mla_moe_readers.py).

Two windows: the counters' deltas span the whole measured window (30 s,
which opens on an EMPTY pool: the slots fill one admission at a time),
the times come from the 3 s trace in its middle, where the pool is
full. The harness takes no snapshot at the trace's marks, so the live
rows are the whole window's mean per step, a little under the traced
seconds' own, and the two rooflines that rest on them
(``ssm_state_roofline``, ``decode_step_roofline.hybrid-ssm``) read a
little LOW (PERF.md section 3 has the sizes).

A program without these scopes or counters (any before PR 37, any
other family) gives None, and so do the readers."""
from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, Optional

from benchmark.harness.spec import load_module

HERE = os.path.dirname(__file__)
readers = load_module(os.path.join(HERE, "mla_moe_readers.py"))
scopes = readers.scopes
programs = readers.programs

_CHILD = re.compile(r"(?:ssm|attn|mlp)\.[A-Za-z_]\w*")


def scoped(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """{"children": {scope: seconds}, "steps": n, "decode_s": s} of
    the decode programs, averaged over the device planes; None where
    there is no trace, no step loop or no ``ssm.*`` scope."""
    if "_hybrid_ssm_scoped" in run:
        return run["_hybrid_ssm_scoped"]
    found = None
    doc = scopes.xplane_of(run)
    steps = programs.token_steps(run)
    if doc is not None and steps:
        lo, hi = scopes.window_of(run)
        children: Dict[str, float] = {}
        for plane in doc["planes"]:
            program_of = programs.program_finder(plane["modules"])
            inside = [op for op in plane["ops"] if program_of(op[1])]
            for _name, path, self_ns in readers._self_ns(inside, lo, hi):
                child = _CHILD.findall(path or "")
                if child:
                    children[child[-1]] = children.get(child[-1], 0.0) + self_ns / 1e9
        if any(name.startswith("ssm.") for name in children):
            planes = len(doc["planes"])
            found = {
                "children": {k: v / planes for k, v in children.items()},
                "steps": steps,
                "decode_s": programs.decode_seconds(run["trace"]),
            }
            out = scopes.artefact_dir(run)
            if "cell" in run and os.path.isdir(out):
                # beside the run's other artefacts, for PERF.md's breakdown
                with open(os.path.join(out, "hybrid_ssm_scopes.json"), "w") as fh:
                    json.dump(found, fh)
    run["_hybrid_ssm_scoped"] = found
    return found


def step_ms(run: Dict[str, Any]) -> Optional[float]:
    """Device ms of the decode programs per token-step."""
    found = scoped(run)
    if not found or not found["decode_s"]:
        return None
    return found["decode_s"] * 1e3 / found["steps"]


def per_step_ms(run: Dict[str, Any], *children: str) -> Optional[float]:
    """Device ms per token-step under the named innermost scopes."""
    found = scoped(run)
    if not found:
        return None
    seconds = sum(found["children"].get(c, 0.0) for c in children)
    return seconds * 1e3 / found["steps"] if seconds > 0 else None


def share(run: Dict[str, Any], prefix: str) -> Optional[float]:
    """Self time under the scopes that start with ``prefix``, as a
    share of the decode programs' device time, in percent."""
    found = scoped(run)
    if not found or not found["decode_s"]:
        return None
    part = sum(v for k, v in found["children"].items() if k.startswith(prefix))
    return 100.0 * part / found["decode_s"] if part > 0 else None


def _goodput_delta(run: Dict[str, Any], *path: str) -> float:
    total = 0.0
    for after, before in zip(run["after"]["goodput"], run["before"]["goodput"]):
        a, b = after, before
        for key in path:
            a, b = (a or {}).get(key), (b or {}).get(key)
        total += (a or 0) - (b or 0)
    return total


def pool(run: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Over the window: the pool's decode ``steps``, the ``live_rows``
    of a step (as counted: nothing holds them to the slots) and the
    ``slots``; None without the ``state`` counters."""
    if "after" not in run:
        return None
    if "_hybrid_ssm_pool" in run:
        return run["_hybrid_ssm_pool"]
    run["_hybrid_ssm_pool"] = None
    row_steps = mamba = 0
    for after, before in zip(run["after"]["model"], run["before"]["model"]):
        a, b = after.get("state"), before.get("state")
        if not a or not b:
            return None
        row_steps += a["ssm_row_steps"] - b["ssm_row_steps"]
        mamba = a["layer_kinds"]["mamba"]
    slots = readers.slots(run)
    if not row_steps or not mamba or not slots:
        return None
    steps = row_steps / (slots * mamba)
    tokens = _goodput_delta(run, "tokens_out") - _goodput_delta(
        run, "engine", "admissions")
    found = {"steps": steps, "slots": slots,
             "live_rows": max(tokens, 0.0) / steps}
    out = scopes.artefact_dir(run) if "cell" in run else ""
    if os.path.isdir(out):
        # beside the run's other artefacts, for PERF.md's breakdown
        with open(os.path.join(out, "state_counters.json"), "w") as fh:
            json.dump({**found, "state": run["after"]["model"][0]["state"]}, fh)
    run["_hybrid_ssm_pool"] = found
    return found


def touched_per_step(run: Dict[str, Any]) -> Optional[float]:
    """Experts (over all layers) that got at least one token, per
    decode step of the pool: ``experts.rows`` counts slots x layers a
    step (every layer has experts)."""
    counted = readers.experts(run)
    rows = readers.slots(run) * int(run["config"]["num_hidden_layers"])
    if not counted or not rows:
        return None
    steps = counted["rows"] / rows
    return counted["expert_steps_touched"] / steps if steps else None


def live_positions(run: Dict[str, Any]) -> Optional[float]:
    """The live rows' contexts added up (prompt plus half the output of
    the window's finished requests, times the live rows)."""
    context, counted = readers.live_context(run), pool(run)
    return counted["live_rows"] * context if context and counted else None
