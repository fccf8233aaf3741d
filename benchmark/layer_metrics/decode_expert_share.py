"""Layer: model + kernels (models/, ops/). Share of the decode
programs' device time spent in the routed experts: self time under the
scopes ``mlp.dispatch`` (sort, block plan, gather), ``mlp.experts`` (the
grouped SwiGLU over the experts touched) and ``mlp.combine`` (the gated
add into the tokens' rows), inside ``jit_run`` (mla_moe_readers.py).
Source: device trace."""
import os

from benchmark.harness.spec import load_module

readers = load_module(os.path.join(os.path.dirname(__file__), "mla_moe_readers.py"))


def read(run):
    found = readers.scoped(run)
    if not found or not found["decode_s"]:
        return None
    part = sum(found["children"].get(c, 0.0)
               for c in ("mlp.dispatch", "mlp.experts", "mlp.combine"))
    return 100.0 * part / found["decode_s"] if part > 0 else None
