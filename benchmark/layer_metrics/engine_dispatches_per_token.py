"""Layer: slot engine (workload/serve_slots.py, models/stepprog.py).
Device dispatches per token out, over the window: the deltas of
``dispatches`` and ``tokens_out`` in ``/v1/goodput``, summed over
replicas. Source: program counter."""


def _delta(run, key):
    return sum(a[key] - b[key] for a, b in
               zip(run["after"]["goodput"], run["before"]["goodput"]))


def read(run):
    if "after" not in run:
        return None
    tokens = _delta(run, "tokens_out")
    return _delta(run, "dispatches") / tokens if tokens else None
