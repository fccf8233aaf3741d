"""Layer: model + kernels. The least time one decode step of the
decoder-hybrid-decoder family could take over the time it took. The
bound is MEMORY (counts_decoder_hybrid.decode_step_bytes): every
matrix once, the head once, the LIVE rows' recurrent state read once
and written once, the rings' live positions, and the full layer's live
positions once for every layer that reads them, at the chip's peak
bytes/s. Counted from the configuration, the ``live`` argument of the
traced window's dispatches and the finished requests' lengths, never
from what the program chose to read: a plane read to every row's end
lies further from the floor, a read cut to the live positions nearer.
The time is the decode programs' device time per token-step of the
traced window (decoder_hybrid_readers.py). Source: device trace."""
import os

from benchmark.harness.spec import load_module

readers = load_module(os.path.join(os.path.dirname(__file__), "decoder_hybrid_readers.py"))


def read(run):
    step_bytes = readers.step_bytes(run)
    if not step_bytes:
        return None
    return readers.roofline(run, step_bytes, readers.step_ms(run))
