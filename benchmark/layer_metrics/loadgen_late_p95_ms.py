"""Layer: load generator (benchmark/). How late the generator sent each
request against its schedule: actual send minus due time, 95th
percentile over the window's requests. A starved generator must not be
read as a fast server. Source: the harness's own clock."""
from benchmark.harness.stats import percentile


def read(run):
    late = [(r["sent_s"] - r["due_s"]) * 1e3 for r in run.get("records", [])
            if r["sent_s"] is not None and not r["cut"]]
    return percentile(late, 95)
