"""Layer: model + kernels. The busiest held expert's assignments over
the mean held expert's, over the window's decode rounds: the deltas of
``/v1/model`` ``experts.load`` (mla_moe_readers.py). 1.0 is even
routing; every row of the pool is counted, a finished slot's too (it
decodes on until it is reused, and its tokens cost what a live one's
do). Source: program counter."""
import os

from benchmark.harness.spec import load_module

readers = load_module(os.path.join(os.path.dirname(__file__), "mla_moe_readers.py"))


def read(run):
    counted = readers.experts(run)
    if not counted or not sum(counted["load"]):
        return None
    load = counted["load"]
    return max(load) / (sum(load) / len(load))
