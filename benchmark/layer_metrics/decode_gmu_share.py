"""Layer: model + kernels. Share of the decode programs' device time
spent in the gated memory units: self time under the scope ``gmu`` (the
gate's projection, the product with the memory, the output projection)
inside ``jit_run`` (decoder_hybrid_readers.py). Source: device trace."""
import os

from benchmark.harness.spec import load_module

readers = load_module(os.path.join(os.path.dirname(__file__), "decoder_hybrid_readers.py"))


def read(run):
    return readers.share(run, "gmu")
