"""Layer: slot engine. Share of the window the engine's own ledger
spent in ``idle`` (``/v1/goodput`` stage seconds, a host-clock state
machine): the engine's account, NOT the device's; the device's idle
share is device.busy_s over device.window_s. Mean over replicas.
Source: program span."""


def read(run):
    if "after" not in run:
        return None
    shares = []
    for a, b in zip(run["after"]["goodput"], run["before"]["goodput"]):
        idle = a["stages_s"].get("idle", 0.0) - b["stages_s"].get("idle", 0.0)
        shares.append(100.0 * idle / run["window_s"])
    return sum(shares) / len(shares) if shares else None
