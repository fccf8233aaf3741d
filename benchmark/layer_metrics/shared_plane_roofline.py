"""Layer: model + kernels. The least time a decode step's reads of the
ONE shared plane could take over the time they took. The bound is
MEMORY: the full layer's keys and values of the LIVE positions, once
for every layer that reads them (the full layer and each cross layer:
counts_decoder_hybrid.shared_plane_bytes), at the chip's peak bytes/s.
The time is device time under ``attn.full`` and ``attn.cross`` (a
layer's write and read of the plane) per token-step of the traced
window (decoder_hybrid_readers.py). Source: device trace."""
import os

from benchmark.harness import counts_decoder_hybrid as counts
from benchmark.harness.spec import load_module

readers = load_module(os.path.join(os.path.dirname(__file__), "decoder_hybrid_readers.py"))


def read(run):
    live = readers.live(run)
    if not live:
        return None
    least = counts.shared_plane_bytes(run["config"], live["rows"] * live["context"])
    return readers.roofline(
        run, least, readers.per_step_ms(run, "attn.full", "attn.cross"))
