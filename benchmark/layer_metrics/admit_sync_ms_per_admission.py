"""Layer: slot engine. How long an admission's thread is BLOCKED on the
device: the seconds of ``engine.admit.first_token.sync`` (the fetch of
the first token and nothing else, models/stepprog.py ``admit``) over
the admissions of the window. The fetch waits out the prefill, so in a
cell whose prefill keeps the device busy this is nearly all of an
admission and the device works through it, while every live row
stalls (admit beside decode, ROADMAP SA 3, is what shrinks it). A
program that fetches no first token (block diffusion) opens no such
span. Source: program counter (``/v1/goodput`` ``engine``, see
admission_spans.py and engine_counters.py)."""
import os

from benchmark.harness.spec import load_module

spans = load_module(os.path.join(os.path.dirname(__file__), "admission_spans.py"))


def read(run):
    return spans.child_ms_per_admission(run, (spans.SYNC,))
