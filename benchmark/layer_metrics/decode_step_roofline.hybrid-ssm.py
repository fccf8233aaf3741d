"""Layer: model + kernels. The least time one decode step of the hybrid
state-space family could take over the time it took. The bound is
MEMORY (counts_hybrid_ssm.decode_step_bytes): every layer's mixer,
shared expert and router, the experts that got a token (the program's
``experts`` counter), the head, the LIVE rows' recurrent state read
once and written once (the ``state`` counter and the engine's tokens),
and the keys and values of the live positions (prompt plus half the
output of the window's finished requests, times the live rows), at the
chip's peak bytes/s. The time is the decode programs' device time per
token-step of the traced window (hybrid_ssm_readers.py). Source: device
trace."""
import os

from benchmark.harness import counts_hybrid_ssm, peaks
from benchmark.harness.spec import load_module

readers = load_module(os.path.join(os.path.dirname(__file__), "hybrid_ssm_readers.py"))


def read(run):
    step_ms = readers.step_ms(run)
    touched = readers.touched_per_step(run)
    counted = readers.pool(run)
    positions = readers.live_positions(run)
    if not step_ms or not touched or not counted or not positions:
        return None
    step_bytes = counts_hybrid_ssm.decode_step_bytes(
        run["config"], counted["live_rows"], positions, touched)
    least_ms = step_bytes / peaks.peak(run["device_kind"], "hbm_bytes_per_s") * 1e3
    return 100.0 * least_ms / step_ms
