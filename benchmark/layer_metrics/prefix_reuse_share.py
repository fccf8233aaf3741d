"""Layer: kvtier (workload/serve_prefix.py, kvtier/). Share of the
prompt tokens sent in the window that the replica served from a cached
prefix: the delta of ``/v1/model`` ``prefix_cache.tokens_reused`` over
the prompt tokens of the window's answered requests. Source: program
counter."""


def read(run):
    if "after" not in run:
        return None
    reused = 0
    for a, b in zip(run["after"]["model"], run["before"]["model"]):
        if not a.get("prefix_cache"):
            return None
        reused += a["prefix_cache"]["tokens_reused"] - b["prefix_cache"]["tokens_reused"]
    sent = sum(r["prompt_len"] for r in run["records"] if r["status"] == 200)
    return 100.0 * reused / sent if sent else None
