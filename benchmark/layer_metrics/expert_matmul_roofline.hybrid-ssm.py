"""Layer: model + kernels. ``expert_matmul_roofline`` for the hybrid
state-space family, whose configuration names an expert's width
``intermediate_size`` and has no dense layer: the least time the
experts touched in a decode step could take over the time their
matmuls took. The bound is MEMORY: an expert that got at least one
token reads its three matrices once (counts_hybrid_ssm.expert_bytes,
bf16) at the chip's peak bytes/s; how many were touched per step is the
program's ``experts`` counter over the window. The time is device time
under ``mlp.experts`` per token-step of the traced window. Source:
device trace."""
import os

from benchmark.harness import counts_hybrid_ssm, peaks
from benchmark.harness.spec import load_module

readers = load_module(os.path.join(os.path.dirname(__file__), "hybrid_ssm_readers.py"))


def read(run):
    took_ms = readers.per_step_ms(run, "mlp.experts")
    touched = readers.touched_per_step(run)
    if not took_ms or not touched:
        return None
    least_ms = counts_hybrid_ssm.expert_bytes(run["config"], touched) / peaks.peak(
        run["device_kind"], "hbm_bytes_per_s") * 1e3
    return 100.0 * least_ms / took_ms
