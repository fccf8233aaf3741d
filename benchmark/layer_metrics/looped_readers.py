"""Shared by the readers of the looped family
(``decode_step_device_ms.looped``, ``decode_step_roofline.looped``,
``loop_passes_per_token``).

From the trace: the decode programs' device seconds over the
token-steps of the traced window, counted as for every family by the
executions of the ``sample`` scope (decode_programs.py ``token_steps``:
one a step of the pool, however many passes the step's layers run).

From the counters (``loop``): what ``/v1/model`` ``loop`` moved by
between the window's two snapshots, summed over replicas:
``loop_row_steps`` counts every row of the pool x steps (a retired row
steps on, on pads), so it gives the pool's steps; ``loop_row_passes``
the passes run over those rows. The LIVE rows of a step are the tokens
the engine handed out (less one per admission, the prefill's) over
those steps, NOT clamped to the slots: a count that came out over them
would show as a roofline over 100 %, not hide.

Two windows, as for the hybrid state-space readers: the counters span
the whole measured window (which opens on an EMPTY pool), the times
come from the 3 s trace in its middle, where the pool is full, so
``decode_step_roofline.looped`` reads a little LOW.

A program without the ``loop`` counters (any before PR 42, any other
family) gives None, and so do the readers."""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional

from benchmark.harness.spec import load_module

HERE = os.path.dirname(__file__)
readers = load_module(os.path.join(HERE, "mla_moe_readers.py"))
scopes = readers.scopes
programs = readers.programs


def loop(run: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Over the window: the pool's decode ``steps``, its ``row_steps``
    and ``row_passes``, the ``live_rows`` of a step (as counted) and
    the ``slots``; None without the ``loop`` counters."""
    if "after" not in run:
        return None
    if "_looped_loop" in run:
        return run["_looped_loop"]
    run["_looped_loop"] = None
    row_steps = row_passes = 0
    for after, before in zip(run["after"]["model"], run["before"]["model"]):
        a, b = after.get("loop"), before.get("loop")
        if not a or not b:
            return None
        row_steps += a["loop_row_steps"] - b["loop_row_steps"]
        row_passes += a["loop_row_passes"] - b["loop_row_passes"]
    slots = readers.slots(run)
    if not row_steps or not slots:
        return None
    steps = row_steps / slots
    tokens = sum(
        (a.get("tokens_out") or 0) - (b.get("tokens_out") or 0)
        - ((a.get("engine") or {}).get("admissions") or 0)
        + ((b.get("engine") or {}).get("admissions") or 0)
        for a, b in zip(run["after"]["goodput"], run["before"]["goodput"]))
    found = {"steps": steps, "slots": slots, "row_steps": row_steps,
             "row_passes": row_passes,
             "live_rows": max(tokens, 0.0) / steps}
    out = scopes.artefact_dir(run) if "cell" in run else ""
    if os.path.isdir(out):
        # beside the run's other artefacts, for PERF.md's breakdown
        with open(os.path.join(out, "loop_counters.json"), "w") as fh:
            json.dump({**found, "loop": run["after"]["model"][0]["loop"]}, fh)
    run["_looped_loop"] = found
    return found


def step_ms(run: Dict[str, Any]) -> Optional[float]:
    """Device ms of the decode programs per token-step; None for a
    program without the ``loop`` counters or a run without a trace."""
    if not loop(run) or not run.get("trace"):
        return None
    steps = programs.token_steps(run)
    seconds = programs.decode_seconds(run["trace"])
    return seconds * 1e3 / steps if steps and seconds else None


def live_positions(run: Dict[str, Any]) -> Optional[float]:
    """The live rows' contexts added up (prompt plus half the output of
    the window's finished requests, times the live rows)."""
    context, counted = readers.live_context(run), loop(run)
    return counted["live_rows"] * context if context and counted else None


def passes_per_token(run: Dict[str, Any]) -> Optional[float]:
    counted = loop(run)
    return counted["row_passes"] / counted["row_steps"] if counted else None
