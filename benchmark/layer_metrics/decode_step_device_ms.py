"""Layer: model + kernels (models/, ops/). Device time of the slot
engine's decode programs (``jit_run``) per token-step, a step being one
execution of their ``sample`` scope (decode_programs.py). Source:
device trace."""
import os

from benchmark.harness.spec import load_module

programs = load_module(os.path.join(os.path.dirname(__file__), "decode_programs.py"))


def read(run):
    trace = run.get("trace")
    if not trace:
        return None
    steps = programs.token_steps(run)
    seconds = programs.decode_seconds(trace)
    return seconds * 1e3 / steps if steps and seconds else None
