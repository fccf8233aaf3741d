"""Layer: slot engine. How long an admission's thread spends ISSUING the
first token's work: the seconds of ``engine.admit.first_token.sample``
(the row key, the first sample's puts and dispatch), ``.insert`` (the
row's write) and ``.state`` (the slot state's puts and write) over the
admissions of the window (models/stepprog.py ``admit``). These are the
host's own milliseconds: where the prefill is short the device stands
idle through them (the slot state as one put, ROADMAP SA 4, is what
shrinks them). With ``admit_sync_ms_per_admission`` they tile
``engine.admit.first_token``. Source: program counter (``/v1/goodput``
``engine``, see admission_spans.py and engine_counters.py)."""
import os

from benchmark.harness.spec import load_module

spans = load_module(os.path.join(os.path.dirname(__file__), "admission_spans.py"))


def read(run):
    return spans.child_ms_per_admission(run, spans.HOST_CHILDREN)
