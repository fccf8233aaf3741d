"""Layer: model + kernels. The least time a decode step's attention
over the latents could take over the time it took. The bound is the
greater of MEMORY (the LIVE positions' latent + rope key over all
layers, once, at the chip's peak bytes/s) and COMPUTE (absorbed-form
FLOPs over the live context, counts_mla_moe, at the bf16 peak). Live
context is prompt plus half the output of the window's finished
requests, times the slots (mla_moe_readers.live_context). The time is
device time under ``attn.absorb`` + ``attn.scores`` per token-step of
the traced window. Source: device trace."""
import os

from benchmark.harness import counts_mla_moe, peaks
from benchmark.harness.spec import load_module

readers = load_module(os.path.join(os.path.dirname(__file__), "mla_moe_readers.py"))


def read(run):
    took_ms = readers.per_step_ms(run, "attn.absorb", "attn.scores")
    context = readers.live_context(run)
    if not took_ms or not context or "after" not in run:
        return None
    rows = readers.slots(run)
    kind = run["device_kind"]
    memory_s = rows * context * counts_mla_moe.latent_bytes_per_position(
        run["config"]) / peaks.peak(kind, "hbm_bytes_per_s")
    compute_s = counts_mla_moe.absorbed_attention_flops(
        run["config"], rows, context) / peaks.peak(kind, "bf16_flops")
    return 100.0 * max(memory_s, compute_s) * 1e3 / took_ms
