"""Layer: gateway + admission (fleet/). What the gateway adds to a
request before its first token: the client's time from send to first
token, less the replica's own account of the same request up to its
first token (slot_queue_wait + kv + prefill, from the span digest in
the stream's last frame). Median over finished requests. Source: host
clocks of two processes on one machine; differences only."""
from benchmark.harness.stats import percentile


def read(run):
    added = []
    for r in run.get("records", []):
        stages = r.get("replica_stages") or {}
        if r["cut"] or r["first_s"] is None or "prefill" not in stages:
            continue
        inside = sum(stages.get(k, 0.0) for k in ("slot_queue_wait", "kv", "prefill"))
        added.append(((r["first_s"] - r["sent_s"]) - inside) * 1e3)
    return percentile(added, 50)
