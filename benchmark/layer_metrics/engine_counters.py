"""Shared by the engine counter readers: what the ``engine`` block of
``GET /v1/goodput`` (telemetry/goodput.py ``EnginePhases``) moved by
between the window's two snapshots, summed over the replicas.

A program without the block (before PR 24) counts nothing: its deltas
read 0, and so do the readers, because the harness's contract refuses a
last line that leaves a listed metric out."""


import json
import os

from benchmark.harness.spec import load_module

scopes = load_module(os.path.join(os.path.dirname(__file__), "trace_scopes.py"))


def _keep(run):
    """Every replica's two ``/v1/goodput`` bodies, beside the run's
    other artefacts (``engine_counters.json``, written once a run):
    PERF.md's breakdown reads phases, bytes and stages no listed
    metric reports."""
    if run.get("_engine_counters_kept") or "cell" not in run:
        return
    run["_engine_counters_kept"] = True
    out = scopes.artefact_dir(run)
    if os.path.isdir(out):
        with open(os.path.join(out, "engine_counters.json"), "w") as fh:
            json.dump({side: run[side]["goodput"] for side in ("before", "after")}, fh)


def delta(run, *path):
    """The sum over replicas of after - before at ``path`` inside the
    ``engine`` block, e.g. ``delta(run, "phase_s", "engine.admit")``;
    None where the run kept no snapshots (a training cell)."""
    if "after" not in run:
        return None
    _keep(run)
    total = 0.0
    for after, before in zip(run["after"]["goodput"], run["before"]["goodput"]):
        a, b = after.get("engine", {}), before.get("engine", {})
        for key in path:
            a = a.get(key, {}) if isinstance(a, dict) else {}
            b = b.get(key, {}) if isinstance(b, dict) else {}
        total += (a or 0.0) - (b or 0.0)
    return total


def per_admission_ms(run, *path):
    """Seconds at ``path`` per admission of the window, in ms; 0.0
    where nothing was admitted."""
    seconds, admissions = delta(run, *path), delta(run, "admissions")
    if seconds is None or admissions is None:
        return None
    return seconds * 1e3 / admissions if admissions > 0 else 0.0
