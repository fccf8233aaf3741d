"""The last line's contract: a good line passes, each malformed one is
named."""
import copy

import pytest

from benchmark.harness import contract

E2E = [{"name": "tpot_p95_ms", "unit": "ms"}, {"name": "setup_s", "unit": "s"}]
LAYER = [{"name": "decode_step_roofline", "unit": "%"},
         {"name": "compiles_in_window.serve", "unit": "count"}]


def untraced():
    return {
        "correct": True, "attempted": 120, "failed": 0,
        "metrics": {"tpot_p95_ms": {"value": 6.25, "unit": "ms"},
                    "setup_s": {"value": 41.5, "unit": "s"}},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 12_000_000_000},
    }


def traced():
    line = untraced()
    line["metrics"] = {"decode_step_roofline": {"value": 48.0, "unit": "%"},
                       "compiles_in_window.serve": {"value": 0.0, "unit": "count"}}
    line["device"].update(busy_s=2.4, window_s=3.0)
    line["breakdown"] = {"device_ops": [["fusion.1", 1.2]],
                         "idle_gaps": [["unknown", 0.3]]}
    return line


def check(line, is_traced):
    return contract.violations(line, LAYER if is_traced else E2E, is_traced, "tpu", 1)


def test_good_lines_pass():
    assert check(untraced(), False) == []
    assert check(traced(), True) == []


def edit(line, path, value=KeyError):
    line = copy.deepcopy(line)
    node = line
    for key in path[:-1]:
        node = node[key]
    if value is KeyError:
        del node[path[-1]]
    else:
        node[path[-1]] = value
    return line


@pytest.mark.parametrize("is_traced,path,value,says", [
    (False, ["correct"], KeyError, "'correct' is missing"),
    (False, ["device"], KeyError, "'device' is missing"),
    (False, ["metrics"], KeyError, "'metrics' is missing"),
    (False, ["attempted"], KeyError, "'attempted' is missing"),
    (False, ["failed"], KeyError, "'failed' is missing"),
    (False, ["correct"], "yes", "not true or false"),
    (False, ["failed"], 121, "exceeds"),
    (False, ["metrics", "tpot_p95_ms"], KeyError, "'tpot_p95_ms' is missing"),
    (False, ["metrics", "tpot_p95_ms", "unit"], KeyError, "not {value, unit}"),
    (False, ["metrics", "tpot_p95_ms", "unit"], "s", "has unit"),
    (False, ["metrics", "tpot_p95_ms", "value"], float("nan"), "no finite number"),
    (False, ["metrics", "tpot_p95_ms", "value"], None, "no finite number"),
    (False, ["metrics", "extra"], {"value": 1, "unit": "ms"}, "not one this cell reports"),
    (False, ["device", "memory_peak_bytes"], KeyError, "memory_peak_bytes is missing"),
    (False, ["device", "memory_peak_bytes"], 0, "not a positive count"),
    (False, ["device", "platform"], "cpu", "device.platform"),
    (False, ["device", "count"], 4, "device.count"),
    (False, ["device", "window_s"], 3.0, "present in an untraced run"),
    (False, ["breakdown"], {"device_ops": [], "idle_gaps": []}, "untraced run"),
    (True, ["device", "window_s"], KeyError, "window_s is missing"),
    (True, ["device", "busy_s"], KeyError, "busy_s is missing"),
    (True, ["device", "busy_s"], 0.0, "not above 0"),
    (True, ["device", "busy_s"], 3.5, "exceeds device.window_s"),
    (True, ["metrics", "decode_step_roofline", "value"], 106.0, "over-counted"),
    (True, ["breakdown", "idle_gaps"], [["a", 1.0]] * 11, "at most 10"),
    (True, ["breakdown", "device_ops"], [["a", "b"]], "not [name, seconds]"),
    (True, ["breakdown", "extra"], [], "not {device_ops, idle_gaps}"),
])
def test_each_malformed_line_is_named(is_traced, path, value, says):
    line = edit(traced() if is_traced else untraced(), path, value)
    bad = check(line, is_traced)
    assert bad and any(says in b for b in bad), bad


def test_not_an_object():
    assert contract.violations([], E2E, False, "tpu", 1)
