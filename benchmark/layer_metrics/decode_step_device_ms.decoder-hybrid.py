"""Layer: model + kernels. Device time of the slot engine's decode
programs per token-step, for the decoder-hybrid-decoder family
(decoder_hybrid_readers.py: the steps are the executions of ``sample``,
the family is told by its ``hybrid_decoder`` counters). Source: device
trace."""
import os

from benchmark.harness.spec import load_module

readers = load_module(os.path.join(os.path.dirname(__file__), "decoder_hybrid_readers.py"))


def read(run):
    return readers.step_ms(run)
