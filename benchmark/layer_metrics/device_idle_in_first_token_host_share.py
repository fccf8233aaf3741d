"""Layer: slot engine. Share of the traced window in which the device
idled while the engine's worker thread was issuing an admission's
first-token work: under an ``engine.admit.first_token.sample``,
``.insert`` or ``.state`` event of the trace's ``slot-engine`` line
(models/stepprog.py ``admit``), the host children of
admission_spans.py. It is a part of the idle that
``device_idle_in_admission_share`` reads under ``engine.admit*``, by
the same arithmetic (engine_phase_idle.py ``split_idle``); the table
by child, ``.sync`` included, goes to ``admission_children.json``. 0
for a program that opens no such span. Source: the program's spans on
the device trace's clock."""
import os

from benchmark.harness.spec import load_module

spans = load_module(os.path.join(os.path.dirname(__file__), "admission_spans.py"))


def read(run):
    found = spans.host_idle(run)
    if found is None:
        return None
    spans.keep(run, "idle_s", found)
    return 100.0 * found["host"] / found["window"]
