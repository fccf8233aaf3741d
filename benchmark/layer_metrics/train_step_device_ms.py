"""Layer: trainer. Device time per training step: the seconds the
train-step program's events cover in the device trace, over the steps
that ran WHOLE inside the traced window. The step is the most
time-consuming program of a training trace. Source: device trace."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace["modules"]:
        return None
    step = max(trace["modules"].values(), key=lambda m: m["seconds"])
    if not step["whole"]:
        return None
    # steps cut by the window's edges are left out of both sums
    return step["whole_seconds"] * 1e3 / step["whole"]
