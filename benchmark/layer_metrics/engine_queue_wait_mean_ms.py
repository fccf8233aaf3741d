"""Layer: slot engine. Mean time a request waited in the engine's
queue: the sum over the window's admissions of (admitted - enqueued)
over their count. Source: program counter (``/v1/goodput`` ``engine``,
see engine_counters.py)."""
import os

from benchmark.harness.spec import load_module

counters = load_module(os.path.join(os.path.dirname(__file__), "engine_counters.py"))


def read(run):
    return counters.per_admission_ms(run, "queue_wait_s")
