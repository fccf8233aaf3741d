"""Layer: slot engine. Tokens revealed per forward of a live row, over
the window: the deltas of ``/v1/model`` ``diffusion``
``tokens_revealed`` and ``row_forwards``
(block_diffusion_readers.py). With 2 denoising steps and a commit
forward of its own a block of 4 takes 3 forwards: 1.33; a commit that
rode with the next block's first step would read 2.0. Source: program
counter."""
import os

from benchmark.harness.spec import load_module

readers = load_module(os.path.join(
    os.path.dirname(__file__), "block_diffusion_readers.py"))


def read(run):
    counted = readers.diffusion(run)
    if not counted:
        return None
    return counted["tokens_revealed"] / counted["row_forwards"]
