"""The contract of the benchmark's last line, as code.

``run.py`` builds the result object, validates it HERE for the trace
mode it ran in, and prints it only when it holds. On a violation the
diagnosis goes on an earlier line and the exit code is not 0: a
malformed last line can no longer reach the driver (PR 22 was refused
for exactly that).
"""
from __future__ import annotations

import json
import math
from typing import Any, Dict, List, Sequence

TOP_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACED_DEVICE_KEYS = ("busy_s", "window_s")
BREAKDOWN_KEYS = ("device_ops", "idle_gaps")
MAX_BREAKDOWN = 10
#: a roofline or mfu share above this is a counting fault, not a result
SHARE_CEILING = 105.0


def _is_number(value: Any) -> bool:
    return (
        isinstance(value, (int, float)) and not isinstance(value, bool)
        and math.isfinite(value)
    )


def violations(
    result: Any,
    metrics: Sequence[Dict[str, Any]],
    traced: bool,
    platform: str,
    chips: int,
    optional: Sequence[str] = (),
) -> List[str]:
    """Every way ``result`` breaks the contract; empty when it holds.

    ``metrics`` are the BENCHMARK.json entries (name, unit) this cell
    reports in this mode. ``optional`` names per-layer metrics whose
    reader may find nothing to read in this cell; none is optional
    unless listed."""
    if not isinstance(result, dict):
        return ["the result is not a JSON object"]
    bad: List[str] = []
    for key in TOP_KEYS:
        if key not in result:
            bad.append(f"key {key!r} is missing")
    if bad:
        return bad
    if not isinstance(result["correct"], bool):
        bad.append("'correct' is not true or false")
    for key in ("attempted", "failed"):
        value = result[key]
        if not isinstance(value, int) or isinstance(value, bool) or value < 0:
            bad.append(f"{key!r} is not a count")
    if not bad and result["failed"] > result["attempted"]:
        bad.append("'failed' exceeds 'attempted'")
    if not bad and result["attempted"] < 1:
        bad.append("nothing was attempted")

    got = result["metrics"]
    if not isinstance(got, dict):
        return bad + ["'metrics' is not an object"]
    wanted = {m["name"]: m for m in metrics}
    for name, entry in wanted.items():
        if name not in got:
            if name not in optional:
                bad.append(f"metric {name!r} is missing")
            continue
        value = got[name]
        if not isinstance(value, dict) or set(value) != {"value", "unit"}:
            bad.append(f"metric {name!r} is not {{value, unit}}")
            continue
        if not _is_number(value["value"]):
            bad.append(f"metric {name!r} has no finite number")
        if value["unit"] != entry["unit"]:
            bad.append(
                f"metric {name!r} has unit {value['unit']!r}, "
                f"BENCHMARK.json says {entry['unit']!r}"
            )
        if _is_number(value["value"]) and value["unit"] == "%" and (
            "_roofline" in name or "mfu" in name
        ) and value["value"] > SHARE_CEILING:
            bad.append(
                f"share {name!r} reads {value['value']} % of its peak: "
                "operations or bytes are over-counted, or time is left out"
            )
    for name in got:
        if name not in wanted:
            bad.append(f"metric {name!r} is not one this cell reports "
                       f"with --trace {int(traced)}")

    device = result["device"]
    if not isinstance(device, dict):
        return bad + ["'device' is not an object"]
    for key in DEVICE_KEYS:
        if key not in device:
            bad.append(f"device.{key} is missing")
    if device.get("platform") != platform:
        bad.append(
            f"device.platform is {device.get('platform')!r}, "
            f"the cell runs on {platform!r}"
        )
    if device.get("count") != chips:
        bad.append(
            f"device.count is {device.get('count')!r}, "
            f"the cell asks for {chips}"
        )
    if not isinstance(device.get("kind"), str) or not device.get("kind"):
        bad.append("device.kind is not a name")
    peak = device.get("memory_peak_bytes")
    if not isinstance(peak, int) or isinstance(peak, bool) or peak <= 0:
        bad.append("device.memory_peak_bytes is not a positive count")
    if traced:
        for key in TRACED_DEVICE_KEYS:
            if not _is_number(device.get(key)):
                bad.append(f"device.{key} is missing in a traced run")
        busy, window = device.get("busy_s"), device.get("window_s")
        if _is_number(busy) and _is_number(window):
            if not busy > 0:
                bad.append("device.busy_s is not above 0")
            if busy > window:
                bad.append("device.busy_s exceeds device.window_s")
    else:
        for key in TRACED_DEVICE_KEYS:
            if key in device:
                bad.append(f"device.{key} is present in an untraced run")

    if "breakdown" in result:
        if not traced:
            bad.append("'breakdown' is present in an untraced run")
        breakdown = result["breakdown"]
        if not isinstance(breakdown, dict) or set(breakdown) != set(
            BREAKDOWN_KEYS
        ):
            bad.append("'breakdown' is not {device_ops, idle_gaps}")
        else:
            for key in BREAKDOWN_KEYS:
                rows = breakdown[key]
                if not isinstance(rows, list) or len(rows) > MAX_BREAKDOWN:
                    bad.append(f"breakdown.{key} is not a list of at "
                               f"most {MAX_BREAKDOWN}")
                    continue
                for row in rows:
                    if (
                        not isinstance(row, list) or len(row) != 2
                        or not isinstance(row[0], str)
                        or not _is_number(row[1])
                    ):
                        bad.append(f"breakdown.{key} holds {row!r}, "
                                   "not [name, seconds]")
                        break
    try:
        line = json.dumps(result)
    except (TypeError, ValueError) as exc:
        bad.append(f"the result does not serialise: {exc}")
    else:
        if "\n" in line:
            bad.append("the result does not fit one line")
    return bad
