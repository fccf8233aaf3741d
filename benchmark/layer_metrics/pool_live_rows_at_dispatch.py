"""Layer: slot engine. Rows of the pool that were live when the engine
dispatched a decode program: the mean of the ``live`` argument over
the ``engine.dispatch`` events that started inside the traced window,
read from the ``.xplane.pb`` (an event's own statistics; the events
file keeps no arguments: admission_spans.py ``span_arguments``). The
count is the engine's own at the dispatch, INSIDE the traced window,
where the device's times are; inside a fused window it is an upper
bound, since rows finish before its end. 0 for a program whose
dispatches carry no ``live``. Source: the program's spans."""
import os

from benchmark.harness.spec import load_module

spans = load_module(os.path.join(os.path.dirname(__file__), "admission_spans.py"))


def read(run):
    found = spans.live_rows(run)
    if found is None:
        return None
    spans.keep(run, "live_rows", found)
    return found["mean"]
