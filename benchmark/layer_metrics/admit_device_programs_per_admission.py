"""Layer: slot engine. Device programs the host issues for an
admission: the events of the trace's ``XLA Modules`` line other than
the decode programs (``jit_run``) that start between the two decode
programs around an admission, over the ``engine.admit`` events there
(admission_spans.py ``programs_by_admission``: by the device's own
order, because the host's and the device's clocks differ by a
millisecond and an admission's last write starts after its span has
closed). The prefill, the first sample, the row's insert, the state's
write and one ``convert_element_type`` for every scalar the host puts
on the device one by one, and the ``retire`` (a put and a write) of
each row harvested before it. Stretches at the trace's ends or with
an admission the window cuts are left out. The counts by program and
by child go to ``admission_children.json``. Source: device trace."""
import os

from benchmark.harness.spec import load_module

spans = load_module(os.path.join(os.path.dirname(__file__), "admission_spans.py"))


def read(run):
    doc = spans.events_doc(run)
    if doc is None:
        return None
    found = spans.programs_by_admission(doc, *spans.scopes.window_of(run))
    if found is None:
        return None
    spans.keep(run, "programs", found)
    return found["per_admission"]
