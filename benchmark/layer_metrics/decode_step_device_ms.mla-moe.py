"""Layer: model + kernels. Device time of the slot engine's decode
programs per token-step, for the latent-attention, routed-expert
family: its layers are unrolled, so the steps are counted by the
executions of a sparse layer's router (mla_moe_readers.py) where
decode_step_device_ms.py looks for a layer loop. Source: device
trace."""
import os

from benchmark.harness.spec import load_module

readers = load_module(os.path.join(os.path.dirname(__file__), "mla_moe_readers.py"))


def read(run):
    return readers.step_ms(run)
