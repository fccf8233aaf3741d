"""Layer: model + kernels (models/, ops/). Share of the decode
programs' device time spent in the state-space mixers: self time under
the scopes ``ssm.in_proj``, ``ssm.conv``, ``ssm.update`` (decay, outer
product, the read through C), ``ssm.norm`` and ``ssm.out_proj``, inside
``jit_run`` (hybrid_ssm_readers.py). Source: device trace."""
import os

from benchmark.harness.spec import load_module

readers = load_module(os.path.join(os.path.dirname(__file__), "hybrid_ssm_readers.py"))


def read(run):
    return readers.share(run, "ssm.")
