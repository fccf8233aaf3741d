"""Layer: slot engine. Share of the window's decode dispatches that
ran the fused K-round window program instead of one chunk: the engine
fuses only when no admission, queued request or cancel is pending.
Source: program counter (``/v1/goodput`` ``engine``, see
engine_counters.py)."""
import os

from benchmark.harness.spec import load_module

counters = load_module(os.path.join(os.path.dirname(__file__), "engine_counters.py"))


def read(run):
    fused = counters.delta(run, "dispatches_fused")
    single = counters.delta(run, "dispatches_single")
    if fused is None or single is None:
        return None
    return 100.0 * fused / (fused + single) if fused + single > 0 else 0.0
