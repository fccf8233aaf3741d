"""Layer: model + kernels. The least time the block attention of one
pool forward could take over the time it took. The bound is MEMORY: the
keys and values of the live positions of every layer read once
(counts_block_diffusion.live_kv_bytes, bf16) at the chip's peak
bytes/s; a block's 4 queries a row share that read. The time is device
time under the ``attn.block`` scope (scores, softmax and weighted sum
over the slot's row) per pool forward of the traced window
(block_diffusion_readers.py). Source: device trace."""
import os

from benchmark.harness import counts_block_diffusion as counts
from benchmark.harness import peaks
from benchmark.harness.spec import load_module

readers = load_module(os.path.join(
    os.path.dirname(__file__), "block_diffusion_readers.py"))


def read(run):
    took_ms = readers.scope_ms(run, "attn.block")
    live = readers.live_positions(run)
    if not took_ms or not live:
        return None
    least_ms = counts.live_kv_bytes(run["config"], live) / peaks.peak(
        run["device_kind"], "hbm_bytes_per_s") * 1e3
    return 100.0 * least_ms / took_ms
