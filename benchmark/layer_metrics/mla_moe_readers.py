"""Shared by the readers of the latent-attention, routed-expert
family (``decode_expert_share``, ``expert_matmul_roofline``,
``latent_attention_roofline``, ``decode_step_roofline.mla-moe``,
``decode_step_device_ms.mla-moe``, ``expert_load_max_over_mean``).

From the trace (``scoped``): inside the slot engine's decode programs
(``jit_run``, decode_programs.py), self seconds by the INNERMOST
dotted scope of an operation's path (``attn.scores``, ``mlp.experts``,
...; trace_scopes.py finds the paths), and the token-steps in the
traced window, counted as for every family by the executions of the
``sample`` scope (decode_programs.py ``token_steps``).

From the counters (``experts``): what ``/v1/model`` ``experts`` moved
by between the window's two snapshots, summed over replicas.

A program without these scopes or counters (any before PR 27, any
other family) gives None, and so do the readers."""
from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, List, Optional

from benchmark.harness.spec import load_module

HERE = os.path.dirname(__file__)
scopes = load_module(os.path.join(HERE, "trace_scopes.py"))
programs = load_module(os.path.join(HERE, "decode_programs.py"))

_CHILD = re.compile(r"(?:attn|mlp)\.[A-Za-z_]\w*")


def _self_ns(ops: List[List[Any]], lo: int, hi: int):
    """(name, path, self ns) per operation clipped to [lo, hi]: an
    operation's time less that of the operations it holds."""
    clipped = []
    for name, start, dur, path in ops:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            clipped.append((s, e - s, name, path))
    out, stack = [], []  # stack: [end, self, name, path]
    for start, dur, name, path in sorted(clipped, key=lambda e: (e[0], -e[1])):
        while stack and start >= stack[-1][0]:
            out.append(tuple(stack.pop()[1:]))
        if stack:
            stack[-1][1] -= min(dur, stack[-1][0] - start)
        stack.append([start + dur, dur, name, path])
    out.extend(tuple(item[1:]) for item in stack)
    return [(name, path, self_ns) for self_ns, name, path in out]


def scoped(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """{"children": {scope: seconds}, "steps": n, "decode_s": s} of
    the decode programs, averaged over the device planes; None where
    there is no trace or no step loop was found."""
    if "_mla_moe_scoped" in run:
        return run["_mla_moe_scoped"]
    found = None
    doc = scopes.xplane_of(run)
    steps = programs.token_steps(run)
    if doc is not None and steps:
        lo, hi = scopes.window_of(run)
        children: Dict[str, float] = {}
        for plane in doc["planes"]:
            program_of = programs.program_finder(plane["modules"])
            inside = [op for op in plane["ops"] if program_of(op[1])]
            for _name, path, self_ns in _self_ns(inside, lo, hi):
                child = _CHILD.findall(path or "")
                if child:
                    children[child[-1]] = children.get(child[-1], 0.0) + self_ns / 1e9
        planes = len(doc["planes"])
        found = {
            "children": {k: v / planes for k, v in children.items()},
            "steps": steps,
            "decode_s": programs.decode_seconds(run["trace"]),
        }
    run["_mla_moe_scoped"] = found
    return found


def per_step_ms(run: Dict[str, Any], *children: str) -> Optional[float]:
    """Device ms per token-step under the named innermost scopes."""
    found = scoped(run)
    if not found:
        return None
    seconds = sum(found["children"].get(c, 0.0) for c in children)
    return seconds * 1e3 / found["steps"] if seconds > 0 else None


def step_ms(run: Dict[str, Any]) -> Optional[float]:
    """Device ms of the decode programs per token-step."""
    found = scoped(run)
    if not found or not found["decode_s"]:
        return None
    return found["decode_s"] * 1e3 / found["steps"]


def experts(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Deltas of the ``experts`` counters over the window (``load``
    elementwise), with ``held`` and ``published`` as they stand."""
    if "after" not in run:
        return None
    if "_mla_moe_experts" in run:
        return run["_mla_moe_experts"]
    run["_mla_moe_experts"] = None
    total: Dict[str, Any] = {}
    for after, before in zip(run["after"]["model"], run["before"]["model"]):
        a, b = after.get("experts"), before.get("experts")
        if not a or not b:
            return None
        for key in ("rows", "assignments_here", "expert_steps_touched",
                    "expert_steps"):
            total[key] = total.get(key, 0) + a[key] - b[key]
        load = [x - y for x, y in zip(a["load"], b["load"])]
        total["load"] = [x + y for x, y in zip(total.get("load", [0] * len(load)), load)]
        total["held"] = a["held"][1] - a["held"][0]
        total["published"] = a["published"]
    out = scopes.artefact_dir(run)
    if "cell" in run and os.path.isdir(out):
        # beside the run's other artefacts, for PERF.md's breakdown
        with open(os.path.join(out, "experts_counters.json"), "w") as fh:
            json.dump(total, fh)
    run["_mla_moe_experts"] = total if total.get("rows") else None
    return run["_mla_moe_experts"]


def slots(run: Dict[str, Any]) -> int:
    engines = [m["slot_engine"] for m in run["after"]["model"] if m.get("slot_engine")]
    return sum(e["slots"] for e in engines)


def live_context(run: Dict[str, Any]) -> Optional[float]:
    """Mean live context of a slot, from the window's finished
    requests: prompt plus half the output (as decode_step_roofline.py
    takes it for the per-head cache)."""
    done = [r for r in run.get("records", ()) if r["done"] and not r["cut"]]
    if not done:
        return None
    return sum(r["prompt_len"] + len(r["tokens"]) / 2 for r in done) / len(done)


def touched_per_step(run: Dict[str, Any]) -> Optional[float]:
    """Experts (over all sparse layers) that got at least one token,
    per decode step of the pool: ``rows`` counts slots x sparse layers
    a step."""
    counted = experts(run)
    if not counted:
        return None
    rows = slots(run)
    sparse = run["config"]["num_hidden_layers"] - run["config"]["first_k_dense_replace"]
    steps = counted["rows"] / (rows * sparse) if rows else 0
    return counted["expert_steps_touched"] / steps if steps else None
