"""Layer: slot engine. Time per output token at the CLIENT, per
request (last token - first token) / (tokens - 1), 95th percentile
across the finished requests due in the window: ``tpot_p95_ms`` as the
closed loops report it end to end, for a cell where it is too unsteady
to carry a bound (an open loop's 75 requests: the tail is a short
request whose one chunk of tokens waited behind an admission; PERF.md
section 2). Source: the harness's own clock (harness/serving.py
``end_to_end``)."""
import math


def read(run):
    value = run["e2e"].get("tpot_p95_ms")
    return value if value is not None and math.isfinite(value) else None
