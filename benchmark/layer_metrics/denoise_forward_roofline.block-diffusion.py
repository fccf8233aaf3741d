"""Layer: model + kernels. The least time one pool forward of the
block-diffusion family could take over the time it took. The least
time is the LARGER of two (counts_block_diffusion.py): the bytes it
must read (every layer's attention and router, the experts that got a
token by the program's ``experts`` counter, the head, the keys and
values of the live positions) at the chip's peak bytes/s, and its
operations (slots x block_length positions through attention, router,
8 experts and the head, and their attention over the live context) at
the chip's peak bf16 FLOP/s. The time is the decode programs' device
time per pool forward of the traced window
(block_diffusion_readers.py). Source: device trace."""
import os

from benchmark.harness import counts_block_diffusion as counts
from benchmark.harness import peaks
from benchmark.harness.spec import load_module

readers = load_module(os.path.join(
    os.path.dirname(__file__), "block_diffusion_readers.py"))


def read(run):
    took_ms = readers.forward_ms(run)
    touched = readers.touched_per_forward(run)
    live = readers.live_positions(run)
    if not took_ms or not touched or not live:
        return None
    config, kind = run["config"], run["device_kind"]
    by_bytes = counts.forward_bytes(config, live, touched) / peaks.peak(
        kind, "hbm_bytes_per_s")
    by_flops = counts.forward_flops(
        config, readers.positions_per_forward(run), live) / peaks.peak(
        kind, "bf16_flops")
    return 100.0 * max(by_bytes, by_flops) * 1e3 / took_ms
