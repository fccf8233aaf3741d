"""Layer: model + kernels. The least time a decode step's reads of the
window layers' rings could take over the time they took. The bound is
MEMORY: every window layer's keys and values of the live rows' live
positions, a row's context or at most the window
(counts_decoder_hybrid.ring_bytes), at the chip's peak bytes/s. The
time is device time under ``attn.window`` (a layer's write and read of
its ring) per token-step of the traced window
(decoder_hybrid_readers.py). Source: device trace."""
import os

from benchmark.harness import counts_decoder_hybrid as counts
from benchmark.harness.spec import load_module

readers = load_module(os.path.join(os.path.dirname(__file__), "decoder_hybrid_readers.py"))


def read(run):
    live = readers.live(run)
    if not live:
        return None
    least = counts.ring_bytes(run["config"], live["rows"], live["context"])
    return readers.roofline(run, least, readers.per_step_ms(run, "attn.window"))
