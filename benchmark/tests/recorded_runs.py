"""The traces recorded on the chip (``recorded/*.json.gz``, cut by
record_fixture.py) as the ``run`` a per-layer reader is handed: the
reduction's summary under ``trace``, the operation events with their
paths where trace_scopes.xplane_of keeps them, the two ``/v1/goodput``
snapshots."""
import gzip
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def fixture(name):
    with gzip.open(os.path.join(HERE, "recorded", name), "rt") as fh:
        return json.load(fh)


def lines_of(found, kind, prefix=""):
    return [e for plane in found["events"]["planes"] if plane["name"].startswith(prefix)
            for line in plane["lines"] if line["kind"] == kind for e in line["events"]]


def as_run(found):
    planes = [
        {"name": plane["name"],
         "ops": [e for line in plane["lines"] if line["kind"] == "ops"
                 for e in line["events"]],
         "modules": [e for line in plane["lines"] if line["kind"] == "modules"
                     for e in line["events"]]}
        for plane in found["events"]["planes"]
        if plane["name"].startswith("/device:TPU:")]
    return {
        "cell": found["cell"], "trace": found["trace"],
        "before": found["before"], "after": found["after"],
        "_xplane": {"planes": planes, "path_stat": found["path_stat"],
                    "path_stat_votes": {}},
    }
