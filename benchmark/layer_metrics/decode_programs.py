"""Shared by the decode-step readers of every family
(``decode_step_device_ms``, ``decode_step_roofline``, their ``.mla-moe``
twins through mla_moe_readers.py): which programs of the device trace
are the slot engine's decode programs, and how many token-steps they
ran inside the traced window.

The chunk and the fused-window programs are both jitted from a
function named ``run`` (models/slots.py), so the trace's module line
names them ``jit_run(<fingerprint>)``. A token-step is one pass of
every layer over the whole slot pool, and it ends in the sampler: the
``sample`` scope of ``_round_step_body`` runs exactly once a step in
both programs, for every family, whatever implements the layer loop (a
scan until PR 27, unrolled layers since PR 28; on the v5e a ``while``
carries no path at all, so loops cannot be counted by name). The
program's tests pin both names (``tests/test_engine_phases.py``).

So the steps are counted from the trace itself, on the profiler's
clock: per decode program, the executions of an operation under
``sample`` that runs once a step. Most of the scope's operations do;
some sit in a loop of the sampler (a sort) and run several times a
step, one under a conditional may run less. The count is therefore the
one MOST of the scope's operations agree on, give or take the two
steps the traced window's edges can cut, and of those the largest: a
step cut by an edge is counted where any of its sampler's operations
started inside. Summed over the decode programs."""
import bisect
import os

from benchmark.harness.spec import load_module

scopes = load_module(os.path.join(os.path.dirname(__file__), "trace_scopes.py"))

DECODE_MODULE = "jit_run"
STEP_SCOPE = "sample"
#: the traced window has two edges; each can cut one step
EDGE_STEPS = 2


def decode_seconds(trace):
    return sum(m["seconds"] for name, m in trace["modules"].items()
               if name.startswith(DECODE_MODULE))


def program_finder(modules):
    """start ns -> the decode program event that holds it (its name,
    ``""`` for any other program or none), from a plane's module
    events ``[name, start, dur]``."""
    spans = sorted((m[1], m[1] + m[2], m[0]) for m in modules
                   if m[0].startswith(DECODE_MODULE))
    begins = [span[0] for span in spans]

    def program_of(start):
        i = bisect.bisect_right(begins, start) - 1
        return spans[i][2] if i >= 0 and start < spans[i][1] else ""

    return program_of


def agreed_count(counts):
    """Of the executions of each operation of a scope: the count most
    of them agree on within ``EDGE_STEPS``, and the largest of those."""
    best, votes = 0, 0
    for top in sorted(set(counts)):
        near = [c for c in counts if top - EDGE_STEPS <= c <= top]
        if len(near) > votes:
            best, votes = max(near), len(near)
    return best


def plane_steps(ops, modules, lo, hi):
    """Token-steps of the decode programs in one device plane's
    operation events ``[name, start, dur, path]`` that started in
    [lo, hi)."""
    program_of = program_finder(modules)
    sampled = {}
    for name, start, _dur, path in ops:
        if lo <= start < hi and scopes.under(path, STEP_SCOPE):
            program = program_of(start)
            if program:
                counts = sampled.setdefault(program, {})
                counts[name] = counts.get(name, 0) + 1
    return sum(agreed_count(list(counts.values())) for counts in sampled.values())


def token_steps(run):
    """Token-steps in the run's traced window, averaged over the
    device planes; 0 where the run has no trace or its decode programs
    no ``sample`` scope (a program before PR 24)."""
    if "_token_steps" not in run:
        doc = scopes.xplane_of(run)
        steps = 0.0
        if doc is not None:
            lo, hi = scopes.window_of(run)
            steps = sum(plane_steps(p["ops"], p["modules"], lo, hi)
                        for p in doc["planes"]) / len(doc["planes"])
        run["_token_steps"] = steps
    return run["_token_steps"]
