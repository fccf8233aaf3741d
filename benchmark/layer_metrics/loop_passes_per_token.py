"""Layer: model + kernels. Passes of the layer stack run per row and
step, over the window: ``/v1/model`` ``loop.loop_row_passes`` over
``loop.loop_row_steps`` (looped_readers.py). ``total_ut_steps`` (4.0)
while every row takes every pass; the number a later exit per row
moves. Source: program counter."""
import os

from benchmark.harness.spec import load_module

readers = load_module(os.path.join(os.path.dirname(__file__), "looped_readers.py"))


def read(run):
    return readers.passes_per_token(run)
