"""From a profiler trace to numbers.

Two steps, so that the second can be tested on a recorded trace with
nothing but Python:

``export(trace_dir)`` reads the ``.xplane.pb`` the launcher's
``jax.profiler`` session wrote (``jax.profiler.ProfileData``, so it
runs in a child with ``JAX_PLATFORMS=cpu`` after the supervised tree
has exited) and returns the events of every plane as plain lists.

``reduce(doc, stamps)`` takes that document and gives: the traced
window, the seconds in which an operation ran on the device (the union
of the operation line's intervals, averaged over the device planes),
each operation's SELF time (its interval less the operations nested
in it, so a ``while`` does not swallow its body), the programs
(``XLA Modules``) with their counts, and the longest idle gaps, each
named by the innermost host-plane event that covers it.

One device plane per chip (``/device:TPU:<n>``). The operation line is
``XLA Ops``; the module line ``XLA Modules``. The ``Steps`` line and
the module line are never used for busy time: their union is the whole
window.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: a CPU rehearsal has no device plane: the XLA CPU client's threads
#: stand in, so that the whole flow can be driven without a chip
REHEARSAL = {"device_prefix": "/host:CPU", "ops_line": "tf_XLAEigen",
             "modules_line": "tf_XLAPjRtCpuClient"}
#: host events shorter than this cannot name an idle gap worth a line
MIN_HOST_EVENT_NS = 20_000
TOP = 10


def export(trace_dir: str, device_prefix: str = DEVICE_PREFIX,
           ops_line: str = OPS_LINE, modules_line: str = MODULES_LINE) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    inventory = []
    for plane in data.planes:
        device = plane.name.startswith(device_prefix)
        lines = []
        seen = []
        for line in plane.lines:
            events = [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events
            ]
            seen.append([line.name, len(events)])
            kind = "host"
            if device and line.name.startswith(ops_line):
                kind = "ops"
            elif device and line.name.startswith(modules_line):
                kind = "modules"
            elif device and device_prefix == DEVICE_PREFIX:
                continue  # Steps and the like: never used
            else:
                events = [e for e in events if e[2] >= MIN_HOST_EVENT_NS]
            if events:
                lines.append({"name": line.name, "kind": kind, "events": events})
        inventory.append({"plane": plane.name, "lines": seen})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"file": os.path.basename(paths[-1]), "planes": planes,
            "inventory": inventory}


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def short_name(name: str) -> str:
    """The TPU's operation events carry the whole HLO instruction
    (``%broadcast.1183 = f32[16,4096,8,4,128]{...} broadcast(...)``):
    keep the instruction's name and its result type."""
    head, _, rest = name.partition(" = ")
    head = head.lstrip("%")
    if not rest:
        return head
    return f"{head} {rest.split('{', 1)[0].split(' ', 1)[0]}"


def self_times(events: List[List[Any]]) -> Dict[str, List[float]]:
    """short name -> [self seconds, count, inclusive seconds]. An event
    nested inside another (same line) takes its time out of its
    parent's self time."""
    out: Dict[str, List[float]] = {}
    stack: List[List[Any]] = []  # [name, end, self_ns, dur_ns]

    def close(item: List[Any]) -> None:
        slot = out.setdefault(short_name(item[0]), [0.0, 0, 0.0])
        slot[0] += item[2] / 1e9
        slot[1] += 1
        slot[2] += item[3] / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur, dur])
    while stack:
        close(stack.pop())
    return out


def _clip(events: List[List[Any]], lo: int, hi: int) -> List[List[Any]]:
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append([name, s, e - s])
    return out


def _gap_owner(gap: Tuple[int, int], host_events: List[List[Any]]) -> str:
    """The innermost host event that covers at least half the gap."""
    best: Optional[Tuple[int, str]] = None
    need = (gap[1] - gap[0]) / 2
    for name, start, dur in host_events:
        overlap = min(start + dur, gap[1]) - max(start, gap[0])
        if overlap >= need and (best is None or dur < best[0]):
            best = (dur, name)
    return best[1] if best else "unknown"


def reduce(doc: Dict[str, Any], stamps: Optional[Dict[str, float]] = None,
           device_prefix: str = DEVICE_PREFIX) -> Dict[str, Any]:
    devices = [p for p in doc["planes"] if p["name"].startswith(device_prefix)]
    if not devices:
        return {"error": f"no plane named {device_prefix}*",
                "planes": [p["name"] for p in doc["planes"]]}
    per_device = []
    for plane in devices:
        lines: Dict[str, List[List[Any]]] = {OPS_LINE: [], MODULES_LINE: []}
        for line in plane["lines"]:
            if line["kind"] == "ops":
                lines[OPS_LINE] += line["events"]
            elif line["kind"] == "modules":
                lines[MODULES_LINE] += line["events"]
        if not lines[OPS_LINE]:
            return {"error": f"plane {plane['name']} has no operation line",
                    "lines": [line["name"] for line in plane["lines"]]}
        per_device.append((plane["name"], lines))
    first = min(e[1] for _n, l in per_device for e in l[OPS_LINE])
    last = max(e[1] + e[2] for _n, l in per_device for e in l[OPS_LINE])
    lo, hi, clock = first, last, "device events' extent"
    if stamps:
        s_ns, e_ns = int(stamps["start"] * 1e9), int(stamps["stop"] * 1e9)
        # the trace's clock is the host's epoch clock when the events
        # fall inside the stamped session (give or take a second)
        if s_ns - 1e9 <= first and last <= e_ns + 1e9 and e_ns > s_ns:
            lo, hi, clock = s_ns, e_ns, "launcher stamps"
    window_s = (hi - lo) / 1e9
    busy = []
    ops: Dict[str, List[float]] = {}
    modules: Dict[str, List[float]] = {}
    gaps: List[Tuple[int, int]] = []
    for _name, lines in per_device:
        clipped = _clip(lines[OPS_LINE], lo, hi)
        merged = _merge([(s, s + d) for _n, s, d in clipped])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, (secs, count, inclusive) in self_times(clipped).items():
            slot = ops.setdefault(name, [0.0, 0, 0.0])
            slot[0] += secs / len(per_device)
            slot[1] += count
            slot[2] += inclusive / len(per_device)
        for name, start, dur in lines.get(MODULES_LINE, []):
            inside = min(start + dur, hi) - max(start, lo)
            if inside <= 0:
                continue
            slot = modules.setdefault(name, [0.0, 0, 0, 0.0])
            slot[0] += inside / 1e9 / len(per_device)
            slot[1] += 1
            if inside == dur:  # ran whole inside the window
                slot[2] += 1
                slot[3] += dur / 1e9
        edges = [(lo, lo)] + merged + [(hi, hi)]
        gaps += [(a[1], b[0]) for a, b in zip(edges, edges[1:]) if b[0] > a[1]]
    host_events = [e for p in doc["planes"] for line in p["lines"]
                   if line["kind"] == "host" for e in line["events"]]
    gaps.sort(key=lambda g: g[0] - g[1])
    named: Dict[str, float] = {}
    for gap in gaps[:200]:
        owner = _gap_owner(gap, host_events)
        named[owner] = named.get(owner, 0.0) + (gap[1] - gap[0]) / 1e9
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1][0])
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_device": busy,
        "clock": clock,
        "first_event_ns": first, "last_event_ns": last,
        "device_planes": [n for n, _l in per_device],
        "device_ops": [[n, v[0]] for n, v in top_ops[:TOP]],
        "op_self_s": {n: v[0] for n, v in top_ops[:40]},
        # loops hold their bodies: [count, inclusive seconds] of each
        "loops": {n: [v[1], v[2]] for n, v in ops.items()
                  if n.startswith("while")},
        "modules": {n: {"seconds": v[0], "count": v[1], "whole": v[2],
                        "whole_seconds": v[3]} for n, v in modules.items()},
        "idle_gaps": [[n, s] for n, s in sorted(
            named.items(), key=lambda kv: -kv[1])[:TOP]],
        "longest_gap_s": (gaps[0][1] - gaps[0][0]) / 1e9 if gaps else 0.0,
    }


def main(argv: List[str]) -> int:
    """``trace_reduce.py <trace_dir> <out.json> [stamps.json] [rehearsal]``:
    export, keep the events (gzipped) beside the summary, reduce."""
    trace_dir, out = argv[0], argv[1]
    stamps = None
    if len(argv) > 2 and argv[2]:
        with open(argv[2]) as fh:
            stamps = json.load(fh)
    rehearsal = len(argv) > 3 and argv[3] == "rehearsal"
    names = REHEARSAL if rehearsal else {}
    prefix = names.get("device_prefix", DEVICE_PREFIX)
    doc = export(trace_dir, **names)
    with gzip.open(out + ".events.json.gz", "wt") as fh:
        json.dump(doc, fh)
    summary = reduce(doc, stamps, prefix)
    summary["inventory"] = doc["inventory"]
    with open(out, "w") as fh:
        json.dump(summary, fh)
    return 0 if "error" not in summary else 1


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
