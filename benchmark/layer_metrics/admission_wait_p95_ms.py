"""Layer: gateway + admission (fleet/). Time a request waited in the
gateway's admission queue: the ``admission_queue_wait`` stage of the
gateway's span digest (response header), 0 where the gateway recorded
none; 95th percentile over answered requests. Source: program span."""
from benchmark.harness.stats import percentile


def read(run):
    waits = [r["stages"].get("admission_queue_wait", 0.0) * 1e3
             for r in run.get("records", []) if r["status"] == 200 and not r["cut"]]
    return percentile(waits, 95)
