"""Layer: model + kernels. Share of the decode programs' device time
spent in operations under the ``attn`` scope (projections, rope, the
cache write, scores over the cache, the output projection): self time
by ``jax.named_scope`` path (trace_scopes.py) inside the ``jit_run``
programs (decode_programs.py), over those programs' device time. A
program without scopes (before PR 24) reads 0. Source: device trace."""
import os

from benchmark.harness.spec import load_module

HERE = os.path.dirname(__file__)
scopes = load_module(os.path.join(HERE, "trace_scopes.py"))
programs = load_module(os.path.join(HERE, "decode_programs.py"))


def read(run):
    found = scopes.load(run)
    if found is None:
        return None
    decode = [m for m in found["modules"] if m.startswith(programs.DECODE_MODULE)]
    return scopes.attention_share(found, decode, programs.decode_seconds(run["trace"]))
