"""Layer: kvtier. What the prefix store costs an admission: the
``engine.admit.store`` phase's seconds (``PrefixCache.store`` of the
admitted row, with the ``kvtier.spill`` device-to-host copies of the
rows it evicts inside it) over the admissions of the window. Part of
engine_admit_ms_per_admission. Source: program span (``/v1/goodput``
``engine``, see engine_counters.py)."""
import os

from benchmark.harness.spec import load_module

counters = load_module(os.path.join(os.path.dirname(__file__), "engine_counters.py"))


def read(run):
    return counters.per_admission_ms(run, "phase_s", "engine.admit.store")
