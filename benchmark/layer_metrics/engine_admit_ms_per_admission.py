"""Layer: slot engine. How long one admission holds the engine's
worker thread: the ``engine.admit`` phase's seconds (prefix lookup and
rewind+extend or the prefill dispatch, the prefix store and the spill
it triggers, the first sample and the row insert) over the admissions
of the window. While it runs no decode chunk is dispatched. Source:
program counter (``/v1/goodput`` ``engine``, see engine_counters.py)."""
import os

from benchmark.harness.spec import load_module

counters = load_module(os.path.join(os.path.dirname(__file__), "engine_counters.py"))


def read(run):
    return counters.per_admission_ms(run, "phase_s", "engine.admit")
