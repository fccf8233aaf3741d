"""The reduction from a trace's events to numbers, on synthetic events
and on a trace recorded on the chip (PR 23's first traced run of
mistral-7b-serve.batch-decode, the first 0.85 s: two dispatches of the
slot engine's chunk program; host events thinned to those over 0.2 ms).
"""
import gzip
import json
import os

import pytest

from benchmark.harness import trace_reduce
from benchmark.harness.spec import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
LAYER_METRICS = os.path.join(os.path.dirname(HERE), "layer_metrics")


def reader(name):
    return load_module(os.path.join(LAYER_METRICS, name + ".py"))


def doc(ops, modules=(), host=()):
    ms = 1_000_000
    scale = lambda events: [[n, s * ms, d * ms] for n, s, d in events]  # noqa: E731
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "kind": "ops", "events": scale(ops)},
            {"name": "XLA Modules", "kind": "modules", "events": scale(modules)}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "kind": "host", "events": scale(host)}]},
    ]}


def test_busy_is_the_union_and_self_time_leaves_out_children():
    summary = trace_reduce.reduce(doc(
        ops=[("%while.1 = (s32[]) while(...)", 0, 100),
             ("%fusion.1 = f32[8]{0} fusion(...)", 10, 30),
             ("%fusion.1 = f32[8]{0} fusion(...)", 50, 30),
             ("%copy.2 = f32[8]{0} copy(...)", 150, 50)],
        modules=[("jit_run(1)", 0, 100), ("jit_other(2)", 150, 50)],
        host=[("$engine.py:1 harvest", 95, 60), ("$thread run", 0, 300)],
    ))
    assert summary["window_s"] == pytest.approx(0.200)
    assert summary["busy_s"] == pytest.approx(0.150)  # 0-100 and 150-200
    ops = dict(summary["device_ops"])
    assert ops["fusion.1 f32[8]"] == pytest.approx(0.060)
    assert ops["while.1 (s32[])"] == pytest.approx(0.040)  # 100 - 2 x 30
    assert summary["loops"]["while.1 (s32[])"] == [1, pytest.approx(0.100)]
    assert summary["modules"]["jit_run(1)"]["whole"] == 1
    # the 50 ms gap is named by the innermost host event covering it
    assert summary["idle_gaps"] == [["$engine.py:1 harvest", pytest.approx(0.050)]]


def test_no_device_plane_is_an_error_not_a_zero():
    summary = trace_reduce.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})
    assert "error" in summary


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(HERE, "recorded", "serve_trace.events.json.gz")
    with gzip.open(path, "rt") as fh:
        return trace_reduce.reduce(json.load(fh))


def test_recorded_trace_busy_and_window(recorded):
    assert recorded["device_planes"] == ["/device:TPU:0"]
    assert recorded["window_s"] == pytest.approx(0.7909, abs=1e-3)
    assert recorded["busy_s"] == pytest.approx(0.7203, abs=1e-3)
    assert 0 < recorded["busy_s"] <= recorded["window_s"]
    assert len(recorded["device_ops"]) == 10 and len(recorded["idle_gaps"]) <= 10
    # what took the time: the GQA broadcast of the float32 cache
    assert recorded["device_ops"][0][0].startswith("broadcast.")
    assert recorded["idle_gaps"][0][0] == "$<unknown> poll"


def test_recorded_trace_decode_step(recorded):
    programs = reader("decode_programs")
    assert programs.decode_seconds(recorded) == pytest.approx(0.7071, abs=1e-3)
    # This fixture cannot exercise the step counter: PR 23's program
    # named no scope, and the cut kept [name, start, duration] of each
    # event with no path, so there is no ``sample`` to count and the step
    # readers read nothing. The counter is tested on the two traces that
    # carry paths (test_token_steps.py: PR 24's scan, PR 30's unrolled
    # layers). What is left to test here is the division, so the count
    # is fed BY HAND: 14 executions of the loop that held the four
    # layers (the ``while`` rule's reading, PR 23), 50.5 ms each.
    run = {"trace": recorded, "cell": "no-such-cell"}
    assert programs.token_steps(run) == 0
    assert reader("decode_step_device_ms").read(run) is None
    run = {"trace": recorded, "_token_steps": 14}  # where token_steps keeps its count
    assert reader("decode_step_device_ms").read(run) == pytest.approx(50.5, abs=0.1)


def test_recorded_trace_roofline_is_a_share_under_the_peak(recorded):
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "mistral-7b-serve.json")) as fh:
        config = json.load(fh)
    run = {
        "trace": recorded, "_token_steps": 14,  # fed by hand, see above
        "config": config, "device_kind": "TPU v5 lite",
        "records": [{"done": True, "cut": False, "prompt_len": 128,
                     "tokens": [0] * 192}],
        "after": {"model": [{"slot_engine": {"slots": 16}}]},
    }
    share = reader("decode_step_roofline").read(run)
    # 2.07 GB a step at 819 GB/s is 2.5 ms of the 50.5 ms it took
    assert share == pytest.approx(5.0, abs=0.1)
    assert reader("decode_step_roofline").read({"trace": None}) is None
