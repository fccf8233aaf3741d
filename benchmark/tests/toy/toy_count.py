"""A toy per-layer metric for the add-by-files test: how many records
or steps the run holds."""


def read(run):
    return float(len(run.get("records") or run.get("steps") or []))
