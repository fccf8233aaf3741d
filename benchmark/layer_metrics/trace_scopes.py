"""Shared by the scope readers (decode_attention_share,
train_attention_share): device time by the program's own
``jax.named_scope`` names, from the profiler's ``.xplane.pb``.

The events file the reduction keeps (harness/trace_reduce.py) holds an
operation's name, start and duration only. Which layer of the model an
operation belongs to is in the operation's ``op_name`` path
(``jit(run)/.../layers/while/body/closed_call/attn/attn.scores/...``),
and the profiler stores that as a statistic of the operation's EVENT
METADATA, which ``jax.profiler.ProfileData`` does not hand out (its
``event.stats`` are the event's own; seen with a synthetic trace). So
this file reads the ``.xplane.pb`` itself, with ``google.protobuf`` and
the seven messages of xplane.proto described below; no jax, so it runs
inside the harness's process.

Which statistic carries the path: ``tf_op`` where the operation line's
events or their metadata carry it with a ``jit(`` in it (the v5e,
PERF.md section 3); else, among their string statistics, the one whose
values most often hold ``jit(``. A ``/`` says nothing: the file names
in ``source`` hold it too, and in PR 28's batch-decode trace they
outvoted ``tf_op`` 32,270 to 31,535, so every operation read
``unnamed``.

A scope is matched as a whole name anywhere in the path, also inside
what transforms wrap around it (``transpose(jvp(layers))``). An
operation belongs to the OUTERMOST of the layer map's scopes in its
path; ``layers``, ``steps`` and ``loss.chunks`` (what the scans do
around their bodies) count only where none of those is there, the
innermost of them first. An operation's time
is its SELF time: a ``while`` does not swallow its body. A fusion is
one event and carries one path, its root instruction's: what XLA fused
across a scope's edge is counted on one side of it.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
from typing import Any, Dict, List, Optional, Tuple

SCOPES = ("embed", "norm", "attn", "mlp", "head", "sample", "loss", "optimizer")
FALLBACK = {"layers": "layers", "loss.chunks": "loss", "steps": "steps"}
UNNAMED = "unnamed"
_NAME = re.compile(r"[A-Za-z_][\w.]*")

DEVICE_PREFIX = "/device:TPU:"
PATH_STAT = "tf_op"
PATH_MARK = "jit("
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def scope_of(path: str) -> str:
    names = _NAME.findall(path or "")
    for name in names:
        if name in SCOPES:
            return name
    for name in reversed(names):
        if name in FALLBACK:
            return FALLBACK[name]
    return UNNAMED


def under(path: str, scope: str) -> bool:
    """Whether ``scope`` is anywhere in the path (nested or not)."""
    return scope in _NAME.findall(path or "")


# ---- xplane.proto, as far as it is read here ---------------------------

_MESSAGES = {
    # message: [(field, number, type, label, type_name)]
    "XSpace": [("planes", 1, "message", "repeated", "XPlane")],
    "XPlane": [("id", 1, "int64", "", ""), ("name", 2, "string", "", ""),
               ("lines", 3, "message", "repeated", "XLine"),
               ("event_metadata", 4, "message", "repeated", "EventMetadataEntry"),
               ("stat_metadata", 5, "message", "repeated", "StatMetadataEntry")],
    "EventMetadataEntry": [("key", 1, "int64", "", ""),
                           ("value", 2, "message", "", "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64", "", ""),
                          ("value", 2, "message", "", "XStatMetadata")],
    "XLine": [("id", 1, "int64", "", ""), ("name", 2, "string", "", ""),
              ("timestamp_ns", 3, "int64", "", ""),
              ("events", 4, "message", "repeated", "XEvent")],
    "XEvent": [("metadata_id", 1, "int64", "", ""), ("offset_ps", 2, "int64", "", ""),
               ("duration_ps", 3, "int64", "", ""),
               ("stats", 4, "message", "repeated", "XStat")],
    "XStat": [("metadata_id", 1, "int64", "", ""), ("str_value", 5, "string", "", ""),
              ("ref_value", 7, "uint64", "", "")],
    "XEventMetadata": [("id", 1, "int64", "", ""), ("name", 2, "string", "", ""),
                       ("stats", 5, "message", "repeated", "XStat")],
    "XStatMetadata": [("id", 1, "int64", "", ""), ("name", 2, "string", "", "")],
}
_classes: Dict[str, Any] = {}


def _xspace_class():
    """The message classes, built once from the table above (a map
    field is a repeated key/value entry on the wire; fields not listed
    are skipped as unknown)."""
    if _classes:
        return _classes["XSpace"]
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    types = descriptor_pb2.FieldDescriptorProto
    kinds = {"int64": types.TYPE_INT64, "uint64": types.TYPE_UINT64,
             "string": types.TYPE_STRING, "message": types.TYPE_MESSAGE}
    file = descriptor_pb2.FileDescriptorProto(
        name="benchmark_xplane.proto", package="benchmark_xplane", syntax="proto3")
    for message, fields in _MESSAGES.items():
        entry = file.message_type.add(name=message)
        for name, number, kind, label, type_name in fields:
            field = entry.field.add(
                name=name, number=number, type=kinds[kind],
                label=types.LABEL_REPEATED if label else types.LABEL_OPTIONAL)
            if type_name:
                field.type_name = f".benchmark_xplane.{type_name}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    for message in _MESSAGES:
        _classes[message] = message_factory.GetMessageClass(
            pool.FindMessageTypeByName(f"benchmark_xplane.{message}"))
    return _classes["XSpace"]


def newest_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def read_xplane(path: str, device_prefix: str = DEVICE_PREFIX) -> Dict[str, Any]:
    """Per device plane: the operation events as
    ``[name, start_ns, dur_ns, path]`` and the module events as
    ``[name, start_ns, dur_ns]``; and the statistic the paths were
    taken from (None where no statistic holds a ``jit(``)."""
    space = _xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    planes = []
    votes: Dict[str, int] = {}
    for plane in space.planes:
        if not plane.name.startswith(device_prefix):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        metadata = {e.key: e.value for e in plane.event_metadata}

        def strings(stats) -> Dict[str, str]:
            out = {}
            for stat in stats:
                value = stat.str_value or stat_names.get(stat.ref_value, "")
                if value:
                    out[stat_names.get(stat.metadata_id, "")] = value
            return out

        of_metadata = {key: strings(m.stats) for key, m in metadata.items()}
        ops: List[List[Any]] = []
        modules: List[List[Any]] = []
        for line in plane.lines:
            is_ops = line.name.startswith(OPS_LINE)
            if not is_ops and not line.name.startswith(MODULES_LINE):
                continue
            for event in line.events:
                meta = metadata.get(event.metadata_id)
                row = [meta.name if meta is not None else "",
                       line.timestamp_ns + event.offset_ps // 1000,
                       event.duration_ps // 1000]
                if not is_ops:
                    modules.append(row)
                    continue
                found = dict(of_metadata.get(event.metadata_id, {}))
                found.update(strings(event.stats))
                for stat, value in found.items():
                    if PATH_MARK in value:
                        votes[stat] = votes.get(stat, 0) + 1
                row.append(found)
                ops.append(row)
        planes.append({"name": plane.name, "ops": ops, "modules": modules})
    stat = PATH_STAT if PATH_STAT in votes else (
        max(votes, key=votes.get) if votes else None)
    for plane in planes:
        for row in plane["ops"]:
            row[3] = row[3].get(stat, "") if stat else ""
    return {"planes": planes, "path_stat": stat, "path_stat_votes": votes}


# ---- from events to seconds by module and scope --------------------------


def self_seconds(ops: List[List[Any]], modules: List[List[Any]],
                 lo: int, hi: int) -> Dict[str, Dict[str, Dict[str, float]]]:
    """module name -> {"scope": {scope: self seconds}, "attn": {child:
    seconds of operations under attn, by its innermost attn.* name}},
    over the operations clipped to [lo, hi]. An operation belongs to
    the module event that holds its start."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]

    def module_of(start: int) -> str:
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < modules[i][1] + modules[i][2]:
            return modules[i][0]
        return ""

    out: Dict[str, Dict[str, Dict[str, float]]] = {}
    stack: List[List[Any]] = []  # [end, self_ns, module, path]

    def close(item: List[Any]) -> None:
        _end, self_ns, module, path = item
        slot = out.setdefault(module, {"scope": {}, "attn": {}})
        scope = scope_of(path)
        slot["scope"][scope] = slot["scope"].get(scope, 0.0) + self_ns / 1e9
        if under(path, "attn"):
            child = [n for n in _NAME.findall(path) if n.startswith("attn.")]
            name = child[-1] if child else "attn"
            slot["attn"][name] = slot["attn"].get(name, 0.0) + self_ns / 1e9

    clipped = []
    for _name, start, dur, path in ops:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            clipped.append((s, e - s, module_of(start), path))
    for start, dur, module, path in sorted(clipped, key=lambda e: (e[0], -e[1])):
        while stack and start >= stack[-1][0]:
            close(stack.pop())
        if stack:
            stack[-1][1] -= min(dur, stack[-1][0] - start)
        stack.append([start + dur, dur, module, path])
    while stack:
        close(stack.pop())
    return out


def window_of(run: Dict[str, Any]) -> Tuple[int, int]:
    """The traced window harness/trace_reduce.py settled on, in ns."""
    trace = run["trace"]
    if trace.get("clock") == "launcher stamps":
        marks = run["trace_marks"]
        return int(marks["start"]["at"] * 1e9), int(marks["stop"]["at"] * 1e9)
    return int(trace["first_event_ns"]), int(trace["last_event_ns"])


def root_of_checkout() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def artefact_dir(run: Dict[str, Any]) -> str:
    """Where the harness keeps this run's artefacts; the readers of
    PR 24 leave what they found there too, for PERF.md's breakdown."""
    return os.path.join(root_of_checkout(), "chiprun_out", "benchmark", run["cell"])


def xplane_of(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``read_xplane`` of the run's trace, read once a run and kept in
    it (every reader's file loads its own copy of this module; the run
    is the one thing they share); None where the run has no trace or no
    device plane."""
    if "_xplane" not in run:
        path = run.get("trace") and newest_xplane(os.path.join(
            root_of_checkout(), ".benchmark_work", run["cell"], "trace"))
        doc = read_xplane(path) if path else None
        run["_xplane"] = doc if doc and doc["planes"] else None
    return run["_xplane"]


def path_stat(run: Dict[str, Any]) -> Optional[str]:
    """The trace statistic the run's paths were taken from, for the
    traced run's result line; None where there is no device plane or no
    statistic holds a ``jit(``."""
    doc = xplane_of(run)
    return doc["path_stat"] if doc else None


def load(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """{"modules": {module: {"scope": ..., "attn": ...}}, "path_stat"},
    averaged over the device planes; None where the run has no trace.
    What was found is also written beside the run's other artefacts
    (``chiprun_out/benchmark/<cell>/scopes.json``) for PERF.md's
    breakdown."""
    doc = xplane_of(run)
    if doc is None:
        return None
    lo, hi = window_of(run)
    merged: Dict[str, Dict[str, Dict[str, float]]] = {}
    for plane in doc["planes"]:
        for module, parts in self_seconds(plane["ops"], plane["modules"], lo, hi).items():
            slot = merged.setdefault(module, {"scope": {}, "attn": {}})
            for kind, seconds in parts.items():
                for name, value in seconds.items():
                    slot[kind][name] = slot[kind].get(name, 0.0) + value / len(doc["planes"])
    found = {"modules": merged, "path_stat": doc["path_stat"],
             "path_stat_votes": doc["path_stat_votes"]}
    out = artefact_dir(run)
    if os.path.isdir(out):
        with open(os.path.join(out, "scopes.json"), "w") as fh:
            json.dump(found, fh)
    return found


def attention_share(found: Dict[str, Any], modules: List[str],
                    device_seconds: float) -> float:
    """Self seconds under ``attn`` in the named modules, as a share of
    ``device_seconds`` (those modules' device time), in percent."""
    attn = sum(sum(found["modules"][m]["attn"].values())
               for m in modules if m in found["modules"])
    return 100.0 * attn / device_seconds if device_seconds > 0 else 0.0
