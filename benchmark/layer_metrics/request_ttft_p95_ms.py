"""Layer: gateway + admission (fleet/). Time to first token at the
CLIENT, due time on the schedule -> first streamed token, 95th
percentile over ALL requests due in the window (a failed request
counts as missing): the number ``ttft_p95_ms`` was end to end until
PR 30. With 75 requests in a window it is the fourth largest of them
and repeats to 4-11 %, more than half of the widest bound there is, so
it stands here, where it is recorded and held to no bound (PERF.md
section 2). Source: the harness's own clock (harness/serving.py
``end_to_end``)."""
import math


def read(run):
    value = run["e2e"].get("ttft_p95_ms")
    return value if value is not None and math.isfinite(value) else None
