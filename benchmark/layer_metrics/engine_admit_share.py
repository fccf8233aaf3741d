"""Layer: slot engine. Share of the window the engine's worker thread
spent admitting: the ``engine.admit`` phase's seconds (prefix lookup,
the prefill dispatch, the prefix store and the spill it triggers, the
first sample and the row insert) between the window's two snapshots,
over the seconds between them; mean over replicas. While an admission runs
no decode chunk is dispatched, so under a backlog this is the share of
the window in which the device waits for the host. The second snapshot
is taken at the window's close, before the profiler's export
(harness/serving.py ``window``). Source: program counter
(``/v1/goodput`` ``engine``, see engine_counters.py)."""
import os

from benchmark.harness.spec import load_module

counters = load_module(os.path.join(os.path.dirname(__file__), "engine_counters.py"))


def read(run):
    seconds = counters.delta(run, "phase_s", "engine.admit")
    if seconds is None:
        return None
    between = run["after"]["at"] - run["before"]["at"]
    return 100.0 * seconds / (between * len(run["after"]["goodput"]))
