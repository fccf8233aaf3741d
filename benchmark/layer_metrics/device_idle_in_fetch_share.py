"""Layer: slot engine. Share of the traced window in which the device
idled while the engine's worker thread was dispatching a decode
program, fetching its tokens or delivering them (``engine.dispatch``,
``engine.fetch``, ``engine.deliver`` on the trace's ``slot-engine``
line): see engine_phase_idle.py. Source: device trace."""
import os

from benchmark.harness.spec import load_module

idle = load_module(os.path.join(os.path.dirname(__file__), "engine_phase_idle.py"))


def read(run):
    return idle.share(run, "fetch")
