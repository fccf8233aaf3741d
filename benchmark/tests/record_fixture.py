#!/usr/bin/env python3
"""The builder's tool that cut recorded/engine_scopes_trace.json.gz
from a traced chip run, on the machine that made it (the ``.xplane.pb``
stays under ``.benchmark_work/``, which the chip tool does not bring
back):

    python3 benchmark/tests/record_fixture.py <cell> <seconds> <out.json.gz>

Keeps, of the first ``seconds`` of device activity: the device's
operation events ``[short name, start, dur, op_name path]`` and module
events, the ``slot-engine`` line's ``engine.*`` / ``kvtier.*`` events,
the reduction's summary of that cut, and the ``engine`` blocks of the
run's two ``/v1/goodput`` snapshots (engine_counters.json)."""
import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import trace_reduce  # noqa: E402
from benchmark.harness.spec import load_module  # noqa: E402

scopes = load_module(os.path.join(ROOT, "benchmark", "layer_metrics", "trace_scopes.py"))


def main(cell: str, seconds: float, out: str) -> int:
    run_dir = os.path.join(ROOT, "chiprun_out", "benchmark", cell)
    with gzip.open(os.path.join(run_dir, "trace.json.events.json.gz"), "rt") as fh:
        doc = json.load(fh)
    xplane = scopes.read_xplane(
        scopes.newest_xplane(os.path.join(ROOT, ".benchmark_work", cell, "trace")))
    # the events file holds the same operation events in the same
    # order (its stamps went through a float: exact to 256 ns only)
    paths = {}
    for plane in doc["planes"]:
        theirs = [op for p in xplane["planes"] if p["name"] == plane["name"]
                  for op in p["ops"]]
        ours = [e for line in plane["lines"] if line["kind"] == "ops"
                for e in line["events"]]
        if len(theirs) != len(ours):
            raise SystemExit(f"{plane['name']}: {len(theirs)} operations in the "
                             f".xplane.pb, {len(ours)} in the events file")
        for mine, op in zip(ours, theirs):
            paths[(plane["name"], mine[1], mine[2], mine[0])] = op[3]
    first = min(e[1] for p in doc["planes"] for line in p["lines"]
                if line["kind"] == "ops" for e in line["events"])
    lo, hi = first, first + int(seconds * 1e9)
    planes = []
    for plane in doc["planes"]:
        lines = []
        for line in plane["lines"]:
            events = [e for e in line["events"] if lo <= e[1] and e[1] + e[2] <= hi]
            if line["kind"] == "modules":  # a program cut by the edge stays, clipped
                events = [[n, max(s, lo), min(s + d, hi) - max(s, lo)]
                          for n, s, d in line["events"] if s < hi and s + d > lo]
            if line["kind"] == "ops":
                events = [[trace_reduce.short_name(n), s, d,
                           paths.get((plane["name"], s, d, n), "")]
                          for n, s, d in events]
            elif line["kind"] == "host":
                if not line["name"].startswith("slot-engine"):
                    continue
                events = [e for e in events if e[0].startswith(("engine.", "kvtier."))]
            if events:
                lines.append({"name": line["name"], "kind": line["kind"], "events": events})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    cut = {"planes": [{"name": p["name"], "lines": [
        {**line, "events": [e[:3] for e in line["events"]]} for line in p["lines"]]}
        for p in planes]}
    summary = trace_reduce.reduce(cut)
    with open(os.path.join(run_dir, "engine_counters.json")) as fh:
        counters = json.load(fh)
    fixture = {
        "cell": cell, "path_stat": xplane["path_stat"],
        "lo": summary["first_event_ns"], "hi": summary["last_event_ns"],
        "events": {"planes": planes},
        "trace": {k: summary[k] for k in ("window_s", "busy_s", "modules", "loops",
                                          "first_event_ns", "last_event_ns", "clock")},
        "before": {"goodput": [{"engine": g.get("engine")} for g in counters["before"]]},
        "after": {"goodput": [{"engine": g.get("engine")} for g in counters["after"]]},
    }
    with gzip.open(out, "wt") as fh:
        json.dump(fixture, fh)
    matched = sum(1 for p in planes for line in p["lines"] if line["kind"] == "ops"
                  for e in line["events"] if e[3])
    print(json.dumps({"fixture": out, "bytes": os.path.getsize(out),
                      "ops_with_path": matched, "path_stat": xplane["path_stat"],
                      "votes": xplane["path_stat_votes"]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1], float(sys.argv[2]), sys.argv[3]))
