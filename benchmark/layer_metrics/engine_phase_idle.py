"""Shared by the three device_idle_* readers: the device's idle time
inside the traced window, split by what the slot engine's worker
thread was doing in it.

The engine writes its phases into the profiler's trace as
``TraceAnnotation``s on the host line named ``slot-engine``
(containerpilot_tpu/workload/serve_slots.py, telemetry/goodput.py
``EnginePhases``), on the clock of the device's events. The events
file the reduction keeps (``chiprun_out/benchmark/<cell>/
trace.json.events.json.gz``) holds them with their line's name. Idle is
computed the way harness/trace_reduce.py computes busy: the window
less the union of the operation line's intervals, per device plane,
averaged. Each idle instant then falls in exactly one class, by the
engine event that covers it:

    admission   any ``engine.admit*`` event (reuse, prefill, store
                and spill, first token)
    fetch       ``engine.dispatch``, ``engine.fetch``, ``engine.deliver``
    wait_work   ``engine.wait_work``: no request queued, no slot live
    unnamed     none of them: what the spans still cannot explain

so the four shares add up to the device's idle share of the window.
The engine's cycle phases tile its thread's time, so ``unnamed`` is
idle outside any cycle: events under 20 us (the export drops them),
the thread before its first cycle, a trace whose engine line is
missing. A program without the annotations (before PR 24) has no
``slot-engine`` line: all its idle reads ``unnamed``.
"""
from __future__ import annotations

import gzip
import json
import os
from typing import Any, Dict, List, Optional, Tuple

from benchmark.harness import trace_reduce
from benchmark.harness.spec import load_module

scopes = load_module(os.path.join(os.path.dirname(__file__), "trace_scopes.py"))

ENGINE_LINE = "slot-engine"
FETCH = ("engine.dispatch", "engine.fetch", "engine.deliver")
Interval = Tuple[int, int]


def phase_class(name: str) -> Optional[str]:
    if name.startswith("engine.admit"):
        return "admission"
    if name in FETCH:
        return "fetch"
    if name == "engine.wait_work":
        return "wait_work"
    return None


def _overlap(gaps: List[Interval], spans: List[Interval]) -> int:
    """Total length of the intersection of two sorted, merged lists."""
    total, j = 0, 0
    for lo, hi in gaps:
        while j < len(spans) and spans[j][1] <= lo:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < hi:
            total += min(hi, spans[k][1]) - max(lo, spans[k][0])
            k += 1
    return total


def split_idle(doc: Dict[str, Any], lo: int, hi: int,
               device_prefix: str = trace_reduce.DEVICE_PREFIX) -> Optional[Dict[str, float]]:
    """Seconds of device idle inside [lo, hi] by class, plus
    ``window`` and ``idle``; None where the document has no device
    operations."""
    per_device = []
    for plane in doc["planes"]:
        if not plane["name"].startswith(device_prefix):
            continue
        ops = [e for line in plane["lines"] if line["kind"] == "ops"
               for e in line["events"]]
        if ops:
            per_device.append(ops)
    if not per_device or hi <= lo:
        return None
    spans: Dict[str, List[Interval]] = {"admission": [], "fetch": [], "wait_work": []}
    for plane in doc["planes"]:
        for line in plane["lines"]:
            if line["kind"] != "host" or not line["name"].startswith(ENGINE_LINE):
                continue
            for name, start, dur in line["events"]:
                kind = phase_class(name)
                if kind:
                    spans[kind].append((max(start, lo), min(start + dur, hi)))
    merged = {k: trace_reduce._merge([s for s in v if s[1] > s[0]])
              for k, v in spans.items()}
    out = {"window": (hi - lo) / 1e9, "idle": 0.0, "admission": 0.0,
           "fetch": 0.0, "wait_work": 0.0}
    for ops in per_device:
        busy = trace_reduce._merge(
            [(s, s + d) for _n, s, d in trace_reduce._clip(ops, lo, hi)])
        edges = [(lo, lo)] + busy + [(hi, hi)]
        gaps = [(a[1], b[0]) for a, b in zip(edges, edges[1:]) if b[0] > a[1]]
        out["idle"] += sum(b - a for a, b in gaps) / 1e9 / len(per_device)
        for kind, intervals in merged.items():
            out[kind] += _overlap(gaps, intervals) / 1e9 / len(per_device)
    out["unnamed"] = max(
        out["idle"] - out["admission"] - out["fetch"] - out["wait_work"], 0.0)
    return out


def load(run: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """``split_idle`` of this run's events file and window; None where
    the run has no trace. Kept on the run (three readers share it) and
    written to ``engine_idle.json`` beside the run's artefacts, for
    PERF.md's breakdown."""
    if "_engine_phase_idle" in run:
        return run["_engine_phase_idle"]
    if not run.get("trace"):
        return None
    out = scopes.artefact_dir(run)
    path = os.path.join(out, "trace.json.events.json.gz")
    if not os.path.isfile(path):
        return None
    with gzip.open(path, "rt") as fh:
        doc = json.load(fh)
    found = split_idle(doc, *scopes.window_of(run))
    if found is not None:
        with open(os.path.join(out, "engine_idle.json"), "w") as fh:
            json.dump(found, fh)
    run["_engine_phase_idle"] = found
    return found


def share(run: Dict[str, Any], kind: str) -> Optional[float]:
    """Percent of the traced window the device idled in ``kind``."""
    found = load(run)
    if found is None:
        return None
    return 100.0 * found[kind] / found["window"]
