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


def test_keys_the_driver_ignores_may_follow():
    """run.py ends every line in ``compared`` (each number beside its
    limit) and a traced one also names its ``path_stat``."""
    line = traced()
    line["path_stat"] = "tf_op"
    line["compared"] = {"mean_logit_gap": {"value": 0.0006, "limit": 0.0009}}
    assert check(line, True) == []


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


@pytest.mark.parametrize("name", ["decode_step_roofline.mla-moe", "decode_step_roofline.open",
                                  "train_mfu"])
def test_a_share_over_its_peak_is_named_whatever_follows_the_name(name):
    """A roofline share with a suffix after ``_roofline`` (a family's or
    an open loop's twin) is held to the ceiling like the plain one."""
    line = traced()
    line["metrics"] = {name: {"value": 106.0, "unit": "%"}}
    bad = contract.violations(line, [{"name": name, "unit": "%"}], True, "tpu", 1)
    assert any("over-counted" in b for b in bad), bad


def _committed_cells():
    import json
    import os
    from benchmark.harness.spec import Cell
    root = os.path.join(os.path.dirname(__file__), "..", "..")
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        names = [w["name"] for w in json.load(fh)["workloads"]]
    return [Cell(root, name) for name in names]


@pytest.mark.parametrize("cell", _committed_cells(), ids=lambda c: c.name)
def test_committed_cell_reports_what_its_layer_metrics_move(cell):
    """Every cell as committed: an end-to-end metric besides
    ``setup_s``, at least one per-layer metric, a reader file for each,
    and each per-layer metric names an end-to-end metric this cell
    reports (so a tail that left a cell's end-to-end list took its
    ``moves`` with it)."""
    reported = {m["name"] for m in cell.end_to_end()}
    assert "setup_s" in reported and len(reported) >= 2
    layer = cell.per_layer()
    assert layer
    for metric in layer:
        assert metric["moves"] in reported, (metric["name"], metric["moves"])
        assert callable(cell.reader(metric["name"]))


@pytest.mark.parametrize("name,key", [("request_ttft_p95_ms", "ttft_p95_ms"),
                                      ("request_tpot_p95_ms", "tpot_p95_ms")])
def test_a_tail_without_a_bound_is_read_from_the_same_number(name, key):
    """The per-layer tails are the harness's own end-to-end numbers; a
    window in which a request failed (inf) or nothing was judged gives
    nothing, never 0."""
    import math
    cell = _committed_cells()[0]
    read = cell.reader(name)
    assert read({"e2e": {key: 431.25}}) == 431.25
    assert read({"e2e": {key: math.inf}}) is None
    assert read({"e2e": {key: None}}) is None
    assert read({"e2e": {}}) is None
