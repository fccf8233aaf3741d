"""Layer: model + kernels. The least time a decode step's recurrence
could take over the time it took. The bound is MEMORY: the LIVE rows'
state S of every mamba layer, float32, read once and written once
(counts_hybrid_ssm.ssm_update_bytes) at the chip's peak bytes/s; the
live rows are the engine's tokens over the pool's steps
(hybrid_ssm_readers.pool). The time is device time under
``ssm.update`` per token-step of the traced window. Source: device
trace."""
import os

from benchmark.harness import counts_hybrid_ssm, peaks
from benchmark.harness.spec import load_module

readers = load_module(os.path.join(os.path.dirname(__file__), "hybrid_ssm_readers.py"))


def read(run):
    took_ms = readers.per_step_ms(run, "ssm.update")
    counted = readers.pool(run)
    if not took_ms or not counted:
        return None
    least_ms = counts_hybrid_ssm.ssm_update_bytes(
        run["config"], counted["live_rows"]) / peaks.peak(
        run["device_kind"], "hbm_bytes_per_s") * 1e3
    return 100.0 * least_ms / took_ms
