"""Layer: model + kernels. The least time one decode step of the looped
family could take over the time it took. The bound is MEMORY
(counts_looped.decode_step_bytes): the stack's matmul weights once a
PASS, the head once, and every plane's keys and values of the LIVE
positions (prompt plus half the output of the window's finished
requests, times the live rows the ``loop`` counters and the engine's
tokens give), at the chip's peak bytes/s. Counted from the
configuration and the counters, never from what the program chose to
read: a pool read to every row's end lies further from the floor, a
read cut to the live positions nearer. The time is the decode programs'
device time per token-step of the traced window (looped_readers.py).
Source: device trace."""
import os

from benchmark.harness import counts_looped, peaks
from benchmark.harness.spec import load_module

readers = load_module(os.path.join(os.path.dirname(__file__), "looped_readers.py"))


def read(run):
    step_ms = readers.step_ms(run)
    positions = readers.live_positions(run)
    if not step_ms or not positions:
        return None
    step_bytes = counts_looped.decode_step_bytes(run["config"], positions)
    least_ms = step_bytes / peaks.peak(run["device_kind"], "hbm_bytes_per_s") * 1e3
    return 100.0 * least_ms / step_ms
