"""Layer: model + kernels. The least time a decode step's Mamba-1
recurrence could take over the time it took. The bound is MEMORY: the
LIVE rows' state S of every Mamba layer, float32, read once and written
once (counts_decoder_hybrid.ssm_update_bytes) at the chip's peak
bytes/s; the live rows are the ``live`` argument of the traced window's
dispatches. The time is device time under ``ssm.update`` per token-step
of the traced window (decoder_hybrid_readers.py). Source: device
trace."""
import os

from benchmark.harness import counts_decoder_hybrid as counts
from benchmark.harness.spec import load_module

readers = load_module(os.path.join(os.path.dirname(__file__), "decoder_hybrid_readers.py"))


def read(run):
    live = readers.live(run)
    if not live:
        return None
    least = counts.ssm_update_bytes(run["config"], live["rows"])
    return readers.roofline(run, least, readers.per_step_ms(run, "ssm.update"))
