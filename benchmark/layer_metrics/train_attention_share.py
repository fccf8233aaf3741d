"""Layer: trainer. Share of the train step's device time spent in
operations under the ``attn`` scope, forward, backward and
rematerialised alike (projections, rope, the flash kernels, the output
projection): self time by ``jax.named_scope`` path (trace_scopes.py)
inside the step program, over that program's device time in the traced
window. The step is the most time-consuming program of a training
trace, as train_step_device_ms.py takes it. A program without scopes
(before PR 24) reads 0. Source: device trace."""
import os

from benchmark.harness.spec import load_module

scopes = load_module(os.path.join(os.path.dirname(__file__), "trace_scopes.py"))


def read(run):
    found = scopes.load(run)
    if found is None or not run["trace"]["modules"]:
        return None
    name, step = max(run["trace"]["modules"].items(), key=lambda kv: kv[1]["seconds"])
    return scopes.attention_share(found, [name], step["seconds"])
