"""Layer: model + kernels. Device time of the slot engine's decode
programs (``jit_run``) per POOL FORWARD of the block-diffusion family:
every slot's block of ``block_length`` positions through every layer,
the head and the reveal. Forwards are counted by the executions of the
``sample`` scope, once a forward (block_diffusion_readers.py). Source:
device trace."""
import os

from benchmark.harness.spec import load_module

readers = load_module(os.path.join(
    os.path.dirname(__file__), "block_diffusion_readers.py"))


def read(run):
    return readers.forward_ms(run)
