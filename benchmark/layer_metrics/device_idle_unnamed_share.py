"""Layer: slot engine. Share of the traced window in which the device
idled under no ``engine.*`` event of the trace's ``slot-engine`` line:
what the engine's phases still cannot explain (all of the idle, for a
program that writes none). See engine_phase_idle.py. Source: device
trace."""
import os

from benchmark.harness.spec import load_module

idle = load_module(os.path.join(os.path.dirname(__file__), "engine_phase_idle.py"))


def read(run):
    return idle.share(run, "unnamed")
