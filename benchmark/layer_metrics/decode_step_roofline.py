"""Layer: model + kernels. The least time one decode step could take
over the time it took. The bound is MEMORY: a step must read every
matmul weight once at the compute dtype (bf16) plus the live keys and
values of the slots (benchmark/harness/counts.py), at the chip's peak
bytes/s (benchmark/harness/peaks.py). Live context is taken from the
window's finished requests: prompt plus half the output, times the
slots in use. Source: device trace."""
import os

from benchmark.harness import counts, peaks
from benchmark.harness.spec import load_module

_step = load_module(os.path.join(os.path.dirname(__file__), "decode_step_device_ms.py"))


def read(run):
    step_ms = _step.read(run)
    if not step_ms:
        return None
    done = [r for r in run["records"] if r["done"] and not r["cut"]]
    if not done:
        return None
    context = sum(r["prompt_len"] + len(r["tokens"]) / 2 for r in done) / len(done)
    slots = [m["slot_engine"] for m in run["after"]["model"] if m.get("slot_engine")]
    rows = sum(s["slots"] for s in slots)
    step_bytes = counts.decode_step_bytes(run["config"], context * rows)
    least_ms = step_bytes / peaks.peak(run["device_kind"], "hbm_bytes_per_s") * 1e3
    return 100.0 * least_ms / step_ms
