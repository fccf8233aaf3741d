"""Layer: model + kernels. Device time of the slot engine's decode
programs per token-step, for the looped family (looped_readers.py: the
steps are the executions of ``sample``, one a step of the pool however
many passes its layers run; the family is told by its ``loop``
counters). Source: device trace."""
import os

from benchmark.harness.spec import load_module

readers = load_module(os.path.join(os.path.dirname(__file__), "looped_readers.py"))


def read(run):
    return readers.step_ms(run)
