"""Shared by the admission readers of PR 39: what the children of
``engine.admit.first_token`` and the arguments of the engine's spans
say about an admission.

Since PR 39 the step program opens, inside ``engine.admit.first_token``
(models/stepprog.py ``admit``): ``.sample`` (the row key, the first
sample's puts and dispatch), ``.sync`` (the fetch of the first token,
which waits out the prefill), ``.insert`` (the row's write) and
``.state`` (the slot state's puts and write). In ``sync`` the engine's
thread is BLOCKED on the device; in the other three, the HOST children,
it is issuing work while the device may stand idle. ``engine.admit``
carries ``prompt``, ``slot`` and ``trace``; ``engine.dispatch`` carries
``fused`` and ``live``, the pool's live rows at that dispatch.

Three places are read, none of them new:

- the ``engine`` block of ``/v1/goodput`` between the window's two
  snapshots (engine_counters.py ``delta``): seconds and counts by child;
- the events file the reduction keeps (``trace.json.events.json.gz``:
  name, start, duration by line), for the device's idle time under a
  child (the arithmetic of engine_phase_idle.py ``split_idle``, handed
  a document whose ``slot-engine`` line holds the chosen events only)
  and for the device programs an admission issues;
- the profiler's ``.xplane.pb`` itself for a span's ARGUMENTS, which
  the events file does not keep: they are the event's own statistics
  (``XStat``: a number the profiler parsed out of ``name#key=value#``).

A program without the children or the arguments (before PR 39) has
nothing under those names: the counters' deltas read 0, no idle lies
under a child, no ``engine.dispatch`` carries ``live``. The readers
then read 0, not None, because the harness's contract refuses a last
line that leaves a listed metric out (as engine_counters.py does for a
program before PR 24).

Every reader leaves what it found in ``admission_children.json`` beside
``engine_idle.json``, under a key of its own, for PERF.md's breakdown.
"""
from __future__ import annotations

import bisect
import gzip
import json
import os
from typing import Any, Dict, List, Optional, Tuple

from benchmark.harness import trace_reduce
from benchmark.harness.spec import load_module

_HERE = os.path.dirname(__file__)
counters = load_module(os.path.join(_HERE, "engine_counters.py"))
idle = load_module(os.path.join(_HERE, "engine_phase_idle.py"))
decode = load_module(os.path.join(_HERE, "decode_programs.py"))
scopes = idle.scopes

ADMIT = "engine.admit"
FIRST_TOKEN = "engine.admit.first_token"
DISPATCH = "engine.dispatch"
SYNC = FIRST_TOKEN + ".sync"
#: the children in which the thread issues work (puts, dispatches)
HOST_CHILDREN = tuple(f"{FIRST_TOKEN}.{c}" for c in ("sample", "insert", "state"))
CHILDREN = (HOST_CHILDREN[0], SYNC) + HOST_CHILDREN[1:]
ARTEFACT = "admission_children.json"


def keep(run: Dict[str, Any], key: str, found: Any) -> None:
    """Put ``found`` under ``key`` of the run's ``admission_children.json``
    (each reader adds its own table to the one file)."""
    if "cell" not in run:
        return
    out = scopes.artefact_dir(run)
    if not os.path.isdir(out):
        return
    path = os.path.join(out, ARTEFACT)
    table: Dict[str, Any] = {}
    if run.get("_admission_kept") and os.path.isfile(path):
        with open(path) as fh:
            table = json.load(fh)
    run["_admission_kept"] = True
    table[key] = found
    with open(path, "w") as fh:
        json.dump(table, fh)


# ---- counters ---------------------------------------------------------------


def child_ms_per_admission(run: Dict[str, Any], children) -> Optional[float]:
    """The named children's seconds over the admissions of the window,
    in ms (0.0 where nothing was admitted, and for a program that
    opens no such child); None where the run kept no snapshots."""
    admissions = counters.delta(run, "admissions")
    if admissions is None:
        return None
    table = {"admissions": admissions, "children": {
        child: {"seconds": counters.delta(run, "phase_s", child),
                "count": counters.delta(run, "phase_n", child)}
        for child in (FIRST_TOKEN,) + CHILDREN}}
    keep(run, "counters", table)
    seconds = sum(table["children"][child]["seconds"] for child in children)
    return seconds * 1e3 / admissions if admissions > 0 else 0.0


# ---- the events file ----------------------------------------------------------


def events_doc(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The run's events file (read once a run), or None without a trace."""
    if "_admission_doc" not in run:
        doc = None
        if run.get("trace"):
            path = os.path.join(scopes.artefact_dir(run),
                                "trace.json.events.json.gz")
            if os.path.isfile(path):
                with gzip.open(path, "rt") as fh:
                    doc = json.load(fh)
        run["_admission_doc"] = doc
    return run["_admission_doc"]


def device_prefix(doc: Dict[str, Any]) -> str:
    """The chip's planes, or in a CPU rehearsal (no such plane) the
    stand-in harness/trace_reduce.py exported as the device."""
    if any(p["name"].startswith(trace_reduce.DEVICE_PREFIX) for p in doc["planes"]):
        return trace_reduce.DEVICE_PREFIX
    return trace_reduce.REHEARSAL["device_prefix"]


def engine_events(doc: Dict[str, Any]) -> List[List[Any]]:
    """``[name, start, dur]`` of the ``slot-engine`` line(s), by start."""
    found = [e for plane in doc["planes"] for line in plane["lines"]
             if line["kind"] == "host" and line["name"].startswith(idle.ENGINE_LINE)
             for e in line["events"]]
    return sorted(found, key=lambda e: (e[1], -e[2]))


def idle_under(doc: Dict[str, Any], lo: int, hi: int,
               groups: Dict[str, Tuple[str, ...]]) -> Optional[Dict[str, float]]:
    """Seconds of device idle inside [lo, hi] covered by the engine
    events of each group of names (``engine.admit*`` names, which
    ``split_idle`` reads as its ``admission`` class), plus ``window``
    and ``idle``.
    The device's operations are merged to its busy intervals once;
    ``split_idle`` only ever takes their union."""
    prefix = device_prefix(doc)
    planes = []
    for plane in doc["planes"]:
        ops = [e for line in plane["lines"] if line["kind"] == "ops"
               for e in line["events"]]
        if plane["name"].startswith(prefix) and ops:
            busy = trace_reduce._merge(
                [(s, s + d) for _n, s, d in trace_reduce._clip(ops, lo, hi)])
            planes.append({"name": plane["name"], "lines": [{
                "name": "busy", "kind": "ops",
                "events": [["busy", s, e - s] for s, e in busy]}]})
    events = engine_events(doc)
    out: Optional[Dict[str, float]] = None
    for group, names in groups.items():
        chosen = [e for e in events if e[0] in names]
        found = idle.split_idle({"planes": planes + [{"name": "engine", "lines": [{
            "name": idle.ENGINE_LINE, "kind": "host", "events": chosen}]}]},
            lo, hi, prefix)
        if found is None:
            return None
        out = out or {"window": found["window"], "idle": found["idle"]}
        out[group] = found["admission"]
    return out


def host_idle(run: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """``idle_under`` of the run's events and traced window: under the
    host children together (``host``), under each child, and under the
    whole ``engine.admit`` events; None where the run has no trace."""
    doc = events_doc(run)
    if doc is None:
        return None
    groups = {"host": HOST_CHILDREN, ADMIT: (ADMIT,)}
    groups.update({child: (child,) for child in CHILDREN})
    return idle_under(doc, *scopes.window_of(run), groups)


def programs_by_admission(doc: Dict[str, Any], lo: int, hi: int) -> Optional[Dict[str, Any]]:
    """The device programs other than the decode programs, owed to the
    admissions they ran beside, per admission.

    The host's line and the device's are on two clocks that the
    profiler aligns to a millisecond or so (in PR 39's batch-decode
    trace every decode program STARTS 1.0-1.2 ms before the host span
    that issues it opens), and dispatch is asynchronous: an admission's
    last write starts on the device after its span has closed. So
    nothing here compares a device time with a host time closer than
    half an admission. The device runs its programs in order, and the
    engine admits only when no decode program is in flight and
    dispatches the next one after the cycle's admissions: the decode
    programs' starts cut the device's line into stretches, an admission
    belongs to the stretch its MIDPOINT falls in, and it owns, with the
    admissions beside it, every other program that starts in that
    stretch: its prefill, first sample, insert and state write with a
    ``convert_element_type`` for every scalar put, and the ``retire``
    (a put and a write) of each row harvested since the last decode
    program, which the host issues while it delivers tokens. The two
    open stretches at the trace's ends, and a stretch that holds an
    admission cut by [lo, hi], are left out with their programs. None
    where the trace has no module line of a chip (a CPU rehearsal's
    stand-in lists the client's threads, not programs)."""
    prefix = trace_reduce.DEVICE_PREFIX
    device = [plane for plane in doc["planes"] if plane["name"].startswith(prefix)
              and any(line["kind"] == "modules" for line in plane["lines"])]
    if not device:
        return None
    family = [e for e in engine_events(doc) if e[0].startswith(ADMIT)]
    family_starts = [e[1] for e in family]
    admissions = owed = stretches = outside = 0
    by_program: Dict[str, int] = {}
    by_child: Dict[str, int] = {}
    for plane in device:
        modules = sorted((e for line in plane["lines"] if line["kind"] == "modules"
                          for e in line["events"]), key=lambda m: m[1])
        cuts = [m[1] for m in modules if m[0].startswith(decode.DECODE_MODULE)]
        held: Dict[int, List[List[Any]]] = {}
        for event in family:
            if event[0] == ADMIT:
                held.setdefault(bisect.bisect_right(cuts, event[1] + event[2] // 2),
                                []).append(event)
        counted = {i for i, events in held.items() if 0 < i < len(cuts)
                   and all(lo <= s and s + d <= hi for _n, s, d in events)}
        stretches += len(counted)
        admissions += sum(len(held[i]) for i in counted)
        for name, start, _dur in modules:
            if name.startswith(decode.DECODE_MODULE):
                continue
            stretch = bisect.bisect_right(cuts, start)
            if stretch not in counted:
                outside += lo <= start < hi
                continue
            owed += 1
            program = name.split("(", 1)[0]
            by_program[program] = by_program.get(program, 0) + 1
            first, last = held[stretch][0], held[stretch][-1]
            if start < first[1]:
                child = "before the span"
            elif start >= last[1] + last[2]:
                child = "after the span"
            else:
                child = family[bisect.bisect_right(family_starts, start) - 1][0]
            by_child[child] = by_child.get(child, 0) + 1
    planes = len(device)
    return {
        "admissions": admissions / planes, "stretches": stretches / planes,
        "programs": owed / planes,
        "per_admission": owed / admissions if admissions else 0.0,
        "by_program": {k: v / planes for k, v in sorted(by_program.items())},
        # by the ``engine.admit*`` event that had started last when the
        # program STARTED ON THE DEVICE, give or take the two clocks'
        # millisecond: a program queued behind a running prefill starts
        # under a later child than issued it; ``before the span`` holds
        # the retires
        "by_child": {k: v / planes for k, v in sorted(by_child.items())},
        "outside_counted_stretches": outside / planes,
    }


# ---- a span's arguments, from the .xplane.pb ----------------------------------


def _widened_xplane():
    """trace_scopes.py's table of xplane.proto, in a copy of the module
    this file keeps to itself, with the two integer values of an
    ``XStat`` added before the classes are built (the scope readers
    take strings only): ``uint64_value`` = 3, ``int64_value`` = 4."""
    module = load_module(os.path.join(_HERE, "trace_scopes.py"))
    module._MESSAGES["XStat"] = module._MESSAGES["XStat"] + [
        ("uint64_value", 3, "uint64", "", ""), ("int64_value", 4, "int64", "", "")]
    return module


def span_arguments(path: str, name: str, line_name: str = idle.ENGINE_LINE,
                   ) -> List[Tuple[int, int, Dict[str, Any]]]:
    """``(start_ns, dur_ns, {argument: value})`` of every event called
    ``name`` on the host lines whose name starts with ``line_name``. A
    value is the number the profiler parsed, else the string."""
    space = _widened_xplane()._xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    found = []
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        wanted = {e.key for e in plane.event_metadata if e.value.name == name}
        for line in plane.lines:
            if not line.name.startswith(line_name):
                continue
            for event in line.events:
                if event.metadata_id not in wanted:
                    continue
                args = {}
                for stat in event.stats:
                    text = stat.str_value or stat_names.get(stat.ref_value, "")
                    args[stat_names.get(stat.metadata_id, "")] = (
                        text or stat.int64_value or stat.uint64_value)
                found.append((line.timestamp_ns + event.offset_ps // 1000,
                              event.duration_ps // 1000, args))
    return sorted(found, key=lambda e: e[0])


def live_of(path: str, lo: int, hi: int) -> Dict[str, Any]:
    """``live`` of the ``engine.dispatch`` events of the ``.xplane.pb``
    at ``path`` that started inside [lo, hi): ``{dispatches, with_live,
    fused, mean, min, max}``; ``mean`` 0.0 where no dispatch carries
    the argument (a program before PR 39)."""
    inside = [args for start, _dur, args in span_arguments(path, DISPATCH)
              if lo <= start < hi]
    rows = [int(args["live"]) for args in inside if "live" in args]
    return {
        "dispatches": len(inside), "with_live": len(rows),
        "fused": sum(1 for args in inside if args.get("fused")),
        "mean": sum(rows) / len(rows) if rows else 0.0,
        "min": min(rows, default=0), "max": max(rows, default=0),
    }


def live_rows(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``live_of`` the run's trace and traced window; None where the
    run has no trace."""
    path = run.get("trace") and scopes.newest_xplane(os.path.join(
        scopes.root_of_checkout(), ".benchmark_work", run["cell"], "trace"))
    return live_of(path, *scopes.window_of(run)) if path else None
