"""One token-step counter for every family (layer_metrics/
decode_programs.py): a step is one execution of the decode programs'
``sample`` scope. On the two traces recorded on the chip it reads the
same work whatever implements the layer loop; and the statistic an
operation's path is taken from is chosen by ``jit(``, not by ``/``."""
import os

import pytest

import recorded_runs
from benchmark.harness.spec import load_module
from test_engine_scope_readers import MS, module

STEPS_A_CHUNK = 8  # the cells' --slot-chunk


def while_rule_steps(trace):
    """What decode_programs.token_steps was until PR 30: the most
    frequent ``while`` that still holds half the decode programs' device
    time. True while the layers were a scan (PR 23-27), eight times too
    few once they were unrolled (PR 28)."""
    programs = module("decode_programs")
    floor = programs.decode_seconds(trace) / 2
    counts = [count for count, inclusive in trace["loops"].values()
              if inclusive >= floor]
    return max(counts) if counts else 0


def test_agreed_count_is_the_one_most_operations_share():
    agreed = module("decode_programs").agreed_count
    # 24 operations once a step, a sort four times a step, one under a
    # conditional; the window's edges cut two of the steps
    assert agreed([22] * 20 + [21] * 3 + [20] + [88, 87] + [3]) == 22
    assert agreed([224] * 5 + [672] * 2) == 224
    assert agreed([]) == 0


def test_scan_form_reads_what_the_while_rule_read():
    """PR 24's trace of chat-sessions: the layers are a scan, the
    ``while`` rule finds the step loop (22 executions), and so does the
    sampler's count. It is the one fixture of the scan form that carries
    paths: PR 23's (``serve_trace.events.json.gz``) kept no path and its
    program named no scope, so the counter reads 0 there and
    test_trace_reduce.py feeds its 14 steps by hand."""
    run = recorded_runs.as_run(recorded_runs.fixture("engine_scopes_trace.json.gz"))
    programs = module("decode_programs")
    assert while_rule_steps(run["trace"]) == 22
    assert programs.token_steps(run) == 22
    step_ms = module("decode_step_device_ms").read(run)
    assert step_ms == pytest.approx(
        programs.decode_seconds(run["trace"]) * 1e3 / 22)


def test_unrolled_form_reads_dispatches_times_steps():
    """This tree's trace of batch-decode (PR 30's chip run, seed
    ..401, the first 0.8 s of device activity): the layers are unrolled,
    every dispatch is the chunk program (nothing fuses under a
    backlog). Eight dispatches ran whole in the cut, the ninth was cut
    15 ms in, before its first sampler (its entry converts the weights
    for 16 ms): 8 x 8 steps. The ``while`` rule finds the loop of eight
    STEPS, once a dispatch: what made ``decode_step_device_ms`` read
    53 ms for 6.7 (ledger, PR 28 and 29)."""
    found = recorded_runs.fixture("unrolled_flagship_trace.json.gz")
    run = recorded_runs.as_run(found)
    programs = module("decode_programs")
    dispatches = [e for e in recorded_runs.lines_of(found, "host")
                  if e[0] == "engine.dispatch"]
    whole = run["trace"]["modules"]["jit_run(9819148469392358689)"]["whole"]
    assert (len(dispatches), whole) == (9, 8)
    assert programs.token_steps(run) == whole * STEPS_A_CHUNK == 64
    assert while_rule_steps(run["trace"]) == 8
    assert module("decode_step_device_ms").read(run) == pytest.approx(
        programs.decode_seconds(run["trace"]) * 1e3 / 64)
    assert 6.5 < module("decode_step_device_ms").read(run) < 7.0


def test_unrolled_form_names_its_attention():
    """The same cut through the scope reader: with the paths taken from
    ``tf_op`` the attention's share of the decode program is what PR 28
    found by hand (28 %), not the 0.0 the vote on ``/`` gave."""
    found = recorded_runs.fixture("unrolled_flagship_trace.json.gz")
    assert found["path_stat"] == "tf_op"
    scopes, programs = module("trace_scopes"), module("decode_programs")
    (plane,) = recorded_runs.as_run(found)["_xplane"]["planes"]
    parts = scopes.self_seconds(plane["ops"], plane["modules"], found["lo"], found["hi"])
    decode = [m for m in parts if m.startswith(programs.DECODE_MODULE)]
    share = scopes.attention_share({"modules": parts}, decode,
                                   programs.decode_seconds(found["trace"]))
    assert 24.0 < share < 32.0
    by_scope = parts[decode[0]]["scope"]
    assert by_scope["mlp"] > by_scope["attn"] > by_scope["sample"] > 0.0


def test_a_trace_without_paths_reads_nothing():
    run = recorded_runs.as_run(recorded_runs.fixture("engine_scopes_trace.json.gz"))
    for plane in run["_xplane"]["planes"]:
        plane["ops"] = [op[:3] + [""] for op in plane["ops"]]
    assert module("decode_programs").token_steps(run) == 0
    assert module("decode_step_device_ms").read(run) is None
    assert module("decode_step_device_ms").read({"trace": None}) is None


def synthetic_votes(path, with_both, source_only):
    """A device plane in which every operation carries a ``source``
    (a file name: it holds ``/``) and all but ``source_only`` of them a
    ``tf_op``: the vote of PR 28's batch-decode trace."""
    scopes = module("trace_scopes")
    space = scopes._xspace_class()()
    plane = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "source"), (3, "hlo_category")):
        entry = plane.stat_metadata.add(key=key)
        entry.value.id, entry.value.name = key, name
    named = plane.event_metadata.add(key=1)
    named.value.id, named.value.name = 1, "%fusion.1 = fusion(...)"
    named.value.stats.add(metadata_id=1,
                          str_value="jit(run)/steps/while/body/attn/attn.scores/exp")
    named.value.stats.add(metadata_id=2, str_value="models/decode.py:472")
    named.value.stats.add(metadata_id=3, str_value="fusion")
    bare = plane.event_metadata.add(key=2)
    bare.value.id, bare.value.name = 2, "%while.7 = while(...)"
    bare.value.stats.add(metadata_id=2, str_value="models/slots.py:364")
    module_meta = plane.event_metadata.add(key=3)
    module_meta.value.id, module_meta.value.name = 3, "jit_run(1)"
    line = plane.lines.add(name="XLA Ops", timestamp_ns=0)
    for i in range(with_both + source_only):
        line.events.add(metadata_id=1 if i < with_both else 2,
                        offset_ps=i * 1000 * 1000, duration_ps=1000 * 1000)
    line = plane.lines.add(name="XLA Modules", timestamp_ns=0)
    line.events.add(metadata_id=3, offset_ps=0,
                    duration_ps=(with_both + source_only) * 1000 * 1000)
    with open(path, "wb") as fh:
        fh.write(space.SerializeToString())


def test_paths_come_from_tf_op_however_many_file_names_hold_a_slash(tmp_path):
    scopes = module("trace_scopes")
    path = str(tmp_path / "votes.xplane.pb")
    synthetic_votes(path, with_both=31_535, source_only=735)
    doc = scopes.read_xplane(path)
    # by "/" the vote would go 32,270 : 31,535 to ``source``
    assert doc["path_stat"] == "tf_op"
    assert doc["path_stat_votes"] == {"tf_op": 31_535}
    (plane,) = doc["planes"]
    assert plane["ops"][0][3].endswith("attn/attn.scores/exp")
    assert plane["ops"][-1][3] == ""
    found = scopes.self_seconds(plane["ops"], plane["modules"], 0, 10**12)
    assert found["jit_run(1)"]["scope"]["attn"] == pytest.approx(31_535e-6)


def test_without_tf_op_the_statistic_that_most_often_holds_jit(tmp_path):
    """Another profiler version may name the statistic otherwise."""
    scopes = module("trace_scopes")
    space = scopes._xspace_class()()
    plane = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "hlo_op_path"), (2, "source")):
        entry = plane.stat_metadata.add(key=key)
        entry.value.id, entry.value.name = key, name
    meta = plane.event_metadata.add(key=1)
    meta.value.id, meta.value.name = 1, "%fusion.1 = fusion(...)"
    meta.value.stats.add(metadata_id=1, str_value="jit(run)/sample/select_n")
    meta.value.stats.add(metadata_id=2, str_value="a/b/c.py:1")
    line = plane.lines.add(name="XLA Ops", timestamp_ns=0)
    line.events.add(metadata_id=1, offset_ps=0, duration_ps=1000)
    path = str(tmp_path / "other.xplane.pb")
    with open(path, "wb") as fh:
        fh.write(space.SerializeToString())
    doc = scopes.read_xplane(path)
    assert doc["path_stat"] == "hlo_op_path"
    assert doc["planes"][0]["ops"][0][3] == "jit(run)/sample/select_n"
