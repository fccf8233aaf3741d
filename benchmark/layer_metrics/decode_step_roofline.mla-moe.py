"""Layer: model + kernels. The least time one decode step of the
latent-attention, routed-expert family could take over the time it
took. The bound is MEMORY (counts_mla_moe.decode_step_bytes): the dense
layer, each sparse layer's attention, shared expert and router, the
experts that got a token (the program's ``experts`` counter), the head,
and the latents of the live positions (prompt plus half the output of
the window's finished requests, times the slots), at the chip's peak
bytes/s. The time is the decode programs' device time per token-step
of the traced window (mla_moe_readers.py). Source: device trace."""
import os

from benchmark.harness import counts_mla_moe, peaks
from benchmark.harness.spec import load_module

readers = load_module(os.path.join(os.path.dirname(__file__), "mla_moe_readers.py"))


def read(run):
    step_ms = readers.step_ms(run)
    touched = readers.touched_per_step(run)
    context = readers.live_context(run)
    if not step_ms or not touched or not context:
        return None
    step_bytes = counts_mla_moe.decode_step_bytes(
        run["config"], readers.slots(run) * context, touched)
    least_ms = step_bytes / peaks.peak(run["device_kind"], "hbm_bytes_per_s") * 1e3
    return 100.0 * least_ms / step_ms
