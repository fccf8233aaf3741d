"""Layer: model + kernels. Share of the decode programs' device time
spent under the ``sample`` scope of a pool forward: the argmax and its
probability over the whole vocabulary at every position of every block
(``sample.confidence``) and the reveal (``sample.reveal``). Self time
by ``jax.named_scope`` path (trace_scopes.py) inside the ``jit_run``
programs (decode_programs.py), over those programs' device time.
Source: device trace."""
import os

from benchmark.harness.spec import load_module

HERE = os.path.dirname(__file__)
scopes = load_module(os.path.join(HERE, "trace_scopes.py"))
programs = load_module(os.path.join(HERE, "decode_programs.py"))


def read(run):
    found = scopes.load(run)
    decode_s = programs.decode_seconds(run["trace"]) if run.get("trace") else 0.0
    if found is None or not decode_s:
        return None
    sample = sum(parts["scope"].get("sample", 0.0)
                 for module, parts in found["modules"].items()
                 if module.startswith(programs.DECODE_MODULE))
    return 100.0 * sample / decode_s if sample > 0 else None
