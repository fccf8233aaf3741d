"""The readers PR 39 added (admission_spans.py and the five metrics
that load it): an admission read by the children of
``engine.admit.first_token``, the device programs it issues, and the
pool's live rows from the ``live`` argument of ``engine.dispatch``. On
synthetic inputs, and in a CPU rehearsal of a toy cell that lists
them."""
import json
import os

import pytest

import toyroot
from benchmark.harness.spec import load_module
from test_engine_scope_readers import MS, counter_run, events_doc, goodput
from test_rehearsal import rehearsal, run_cell

HERE = os.path.dirname(os.path.abspath(__file__))
LAYER_METRICS = os.path.join(os.path.dirname(HERE), "layer_metrics")
FIRST = "engine.admit.first_token"
NEW = ("admit_sync_ms_per_admission", "admit_first_token_host_ms_per_admission",
       "device_idle_in_first_token_host_share", "admit_device_programs_per_admission",
       "pool_live_rows_at_dispatch")


def module(name):
    return load_module(os.path.join(LAYER_METRICS, name + ".py"))


# ---- counters: an admission as wait against issue ----------------------------


def with_children(body, first, sample, sync, insert, state, n):
    phases = body["engine"]
    phases["phase_s"].update({
        FIRST: first, FIRST + ".sample": sample, FIRST + ".sync": sync,
        FIRST + ".insert": insert, FIRST + ".state": state})
    phases["phase_n"].update({name: n for name in phases["phase_s"]})
    return body


def test_sync_and_host_tile_the_first_token_per_admission():
    """30 admissions between the snapshots, 0.6 s of them blocked on
    the device and 0.15 + 0.03 + 0.12 = 0.3 s issuing: 20 ms and 10 ms
    an admission, together the first token's 30."""
    run = counter_run(
        with_children(goodput(1.0, 0.25, 10, 2.0, 5, 20), 0.8, 0.1, 0.5, 0.1, 0.1, 10),
        with_children(goodput(4.0, 1.75, 40, 14.0, 5, 80), 1.7, 0.25, 1.1, 0.13, 0.22, 40))
    sync = module("admit_sync_ms_per_admission").read(run)
    host = module("admit_first_token_host_ms_per_admission").read(run)
    assert sync == pytest.approx(20.0) and host == pytest.approx(10.0)
    whole = module("engine_counters").per_admission_ms(run, "phase_s", FIRST)
    assert sync + host == pytest.approx(whole)
    for twin in ("admit_sync_ms_per_admission", "admit_first_token_host_ms_per_admission"):
        assert module(twin + ".open").read(run) == module(twin).read(run)


@pytest.mark.parametrize("name", [
    "admit_sync_ms_per_admission", "admit_first_token_host_ms_per_admission",
    "admit_sync_ms_per_admission.open", "admit_first_token_host_ms_per_admission.open",
])
def test_a_program_without_the_children_reads_zero_not_none(name):
    """The parent of PR 39 keeps the ``engine`` block and no child of
    ``first_token``; block diffusion opens no ``sync``: a number either
    way, because the contract refuses a line that leaves a listed
    metric out. A training run has no snapshots: nothing to read."""
    run = counter_run(goodput(1.0, 0.25, 10, 2.0, 5, 20),
                      goodput(4.0, 1.75, 40, 14.0, 5, 80))
    assert module(name).read(run) == 0.0
    bare = {"stages_s": {"idle": 1.0}}
    assert module(name).read(counter_run(bare, bare)) == 0.0
    assert module(name).read({"records": []}) is None


# ---- device idle by child -------------------------------------------------------


def admission_doc():
    """One admission, 100-200 ms: the prefill's dispatch 100-110, then
    ``first_token`` 110-200 tiled by sample 110-130, sync 130-160
    (the device runs the prefill 120-158), insert 160-170, state
    170-200; decode programs before and after."""
    return events_doc(
        ops=[("%fusion.1", 0, 100), ("%prefill", 120, 38), ("%first", 158, 1),
             ("%insert", 165, 2), ("%admit", 199, 1), ("%fusion.2", 210, 90)],
        engine=[("engine.admit", 100, 100), ("engine.admit.prefill", 100, 10),
                (FIRST, 110, 90), (FIRST + ".sample", 110, 20),
                (FIRST + ".sync", 130, 30), (FIRST + ".insert", 160, 10),
                (FIRST + ".state", 170, 30), ("engine.dispatch", 200, 5),
                ("engine.fetch", 205, 95)],
    )


def test_idle_is_split_by_the_child_that_covers_it():
    spans = module("admission_spans")
    groups = {"host": spans.HOST_CHILDREN}
    groups.update({child: (child,) for child in spans.CHILDREN})
    found = spans.idle_under(admission_doc(), 0, 300 * MS, groups)
    assert found["window"] == pytest.approx(0.300)
    # gaps: 100-120, 159-165, 167-199, 200-210
    assert found["idle"] == pytest.approx(0.068)
    assert found[FIRST + ".sample"] == pytest.approx(0.010)   # 110-120
    assert found[FIRST + ".sync"] == pytest.approx(0.001)     # 159-160
    assert found[FIRST + ".insert"] == pytest.approx(0.008)   # 160-165, 167-170
    assert found[FIRST + ".state"] == pytest.approx(0.029)    # 170-199
    assert found["host"] == pytest.approx(0.047)
    whole = module("engine_phase_idle").split_idle(admission_doc(), 0, 300 * MS)
    assert found["idle"] == pytest.approx(whole["idle"])
    assert found["host"] <= whole["admission"] == pytest.approx(0.058)
    # a window that cuts the admission takes the part inside it
    cut = spans.idle_under(admission_doc(), 0, 180 * MS, groups)
    assert cut[FIRST + ".state"] == pytest.approx(0.010)
    # a program that opens no child: nothing lies under one
    doc = admission_doc()
    doc["planes"][1]["lines"][0]["events"] = [
        e for e in doc["planes"][1]["lines"][0]["events"] if not e[0].startswith(FIRST + ".")]
    bare = spans.idle_under(doc, 0, 300 * MS, groups)
    assert bare["host"] == 0.0 and bare["idle"] == pytest.approx(0.068)


# ---- device programs by admission -----------------------------------------------


def programs_doc():
    """Three admissions and the decode programs around them, in ms, with
    the device's clock running 2 ms EARLY against the host's (a decode
    program starts before the host span that issues it opens). The
    first admission began before the window opens at 50 and before the
    trace's first decode program; the second runs 300-340 on the host,
    its first program starts at 299 by the device's clock and its last
    write at 341, after its span has closed; the third runs 600-630; a
    fourth began at 700 and was cut by the trace's end, so that only
    its prefill child is there. The ``retire`` of a harvested row (a
    put and a write) follows each decode program."""
    scale = lambda events: [[n, s * MS, d * MS] for n, s, d in events]  # noqa: E731
    modules = [
        ("jit_convert_element_type(1)", 45, 1), ("jit__lambda(2)", 55, 20),  # 1st
        ("jit_first(3)", 76, 1), ("jit_run(9)", 100, 150),
        ("jit_convert_element_type(1)", 260, 1), ("jit__lambda(5)", 262, 1),  # retire
        ("jit_convert_element_type(1)", 299, 1), ("jit__lambda(2)", 303, 20),
        ("jit_first(3)", 324, 1), ("jit_insert_row(4)", 330, 1),
        ("jit_admit(6)", 341, 1), ("jit_run(9)", 343, 150),
        ("jit_convert_element_type(1)", 520, 1), ("jit__lambda(5)", 522, 1),  # retire
        ("jit_convert_element_type(1)", 601, 1), ("jit__lambda(2)", 603, 20),
        ("jit_first(3)", 624, 1), ("jit_run(9)", 633, 60),
        ("jit_convert_element_type(1)", 701, 1), ("jit__lambda(2)", 703, 20),  # 4th
    ]
    engine = [
        ("engine.admit", 40, 40), ("engine.admit", 300, 40),
        ("engine.admit.prefill", 300, 5), (FIRST, 305, 35),
        (FIRST + ".sample", 305, 15), (FIRST + ".sync", 320, 5),
        (FIRST + ".insert", 325, 10), (FIRST + ".state", 335, 5),
        ("engine.dispatch", 345, 2),
        ("engine.admit", 600, 30), ("engine.admit.prefill", 600, 5), (FIRST, 605, 25),
        ("engine.dispatch", 635, 2), ("engine.admit.prefill", 700, 5),
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "kind": "modules", "events": scale(modules)},
            {"name": "XLA Ops", "kind": "ops", "events": scale(modules)}]},
        {"name": "/host:CPU", "lines": [
            {"name": "slot-engine", "kind": "host", "events": scale(engine)}]},
    ]}


def test_programs_are_owed_to_the_admissions_between_two_decode_programs():
    spans = module("admission_spans")
    found = spans.programs_by_admission(programs_doc(), 50 * MS, 750 * MS)
    # the first admission lies before the trace's first decode program
    # and the fourth after its last: open stretches, left out with
    # their programs; the second and third own what runs between the
    # decode programs around them, the retires before them included
    assert found["admissions"] == 2 and found["stretches"] == 2
    assert found["programs"] == 7 + 5
    assert found["per_admission"] == pytest.approx(6.0)
    assert found["by_program"] == {
        "jit__lambda": 4, "jit_admit": 1, "jit_convert_element_type": 4,
        "jit_first": 2, "jit_insert_row": 1}
    # the two clocks' skew puts the second admission's first put before
    # its span, with the retire; the write after its span is its own too
    assert found["by_child"] == {
        "before the span": 5, "after the span": 1, "engine.admit.prefill": 3,
        FIRST: 1, FIRST + ".sync": 1, FIRST + ".insert": 1}
    assert found["outside_counted_stretches"] == 4  # 55, 76, 701, 703
    # a window that cuts the second admission leaves its stretch out
    cut = spans.programs_by_admission(programs_doc(), 310 * MS, 750 * MS)
    assert (cut["admissions"], cut["programs"]) == (1, 5)
    # no module line, nothing to count
    doc = programs_doc()
    doc["planes"][0]["lines"] = doc["planes"][0]["lines"][1:]
    assert spans.programs_by_admission(doc, 0, 750 * MS) is None


# ---- a span's arguments ---------------------------------------------------------


def xplane_with_dispatches(path, dispatches, arguments=("fused", "live")):
    """A host plane as the profiler writes it: the arguments of
    ``engine.dispatch#fused=0,live=16#`` are integer statistics of the
    EVENT, a trace id a reference to a statistic's name."""
    spans = module("admission_spans")
    space = spans._widened_xplane()._xspace_class()()
    space.planes.add(name="/device:TPU:0")
    plane = space.planes.add(name="/host:CPU")
    for key, name in ((1, "fused"), (2, "live"), (3, "trace"), (9, "feedc0de")):
        entry = plane.stat_metadata.add(key=key)
        entry.value.id, entry.value.name = key, name
    for key, name in ((1, "engine.dispatch"), (2, "engine.admit"), (3, "engine.fetch")):
        entry = plane.event_metadata.add(key=key)
        entry.value.id, entry.value.name = key, name
    other = plane.lines.add(name="python3", timestamp_ns=1000)
    other.events.add(metadata_id=1, offset_ps=0, duration_ps=MS * 1000).stats.add(
        metadata_id=2, int64_value=99)  # not the engine's line: ignored
    line = plane.lines.add(name="slot-engine/77", timestamp_ns=1000)
    for start_ms, fused, live in dispatches:
        event = line.events.add(metadata_id=1, offset_ps=start_ms * MS * 1000,
                                duration_ps=2 * MS * 1000)
        if "fused" in arguments:
            event.stats.add(metadata_id=1, int64_value=fused)
        if "live" in arguments:
            event.stats.add(metadata_id=2, uint64_value=live)
    line.events.add(metadata_id=3, offset_ps=0, duration_ps=MS * 1000)
    admit = line.events.add(metadata_id=2, offset_ps=5 * MS * 1000, duration_ps=MS * 1000)
    admit.stats.add(metadata_id=3, ref_value=9)
    with open(path, "wb") as fh:
        fh.write(space.SerializeToString())


def test_live_rows_are_the_mean_of_the_dispatches_argument(tmp_path):
    spans = module("admission_spans")
    path = str(tmp_path / "t.xplane.pb")
    xplane_with_dispatches(path, [(10, 0, 16), (50, 1, 15), (90, 1, 14), (400, 0, 3)])
    found = spans.live_of(path, 0, 300 * MS)  # the last one lies outside
    assert found == {"dispatches": 3, "with_live": 3, "fused": 2,
                     "mean": pytest.approx(15.0), "min": 14, "max": 16}
    (admit,) = spans.span_arguments(path, "engine.admit")
    assert admit == (1000 + 5 * MS, MS, {"trace": "feedc0de"})
    # a program whose dispatches carry ``fused`` alone (before PR 39)
    xplane_with_dispatches(path, [(10, 0, 16), (50, 1, 15)], arguments=("fused",))
    found = spans.live_of(path, 0, 300 * MS)
    assert found["dispatches"] == 2 and found["with_live"] == 0
    assert found["mean"] == 0.0


# ---- the entries, and a CPU rehearsal that lists the readers in a toy cell ----------


def test_the_entries_name_their_cells_and_what_they_move():
    with open(os.path.join(toyroot.REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entries = {m["name"]: m for m in bench["per_layer"]}
    closed = ["mistral-7b-serve.batch-decode", "ax-k1-serve.ep-decode",
              "sdar-30b-a3b-serve.block-decode", "granite-4-h-small-serve.ssm-decode"]
    for name in NEW:
        assert entries[name]["moves"] == "tpot_p95_ms"
        assert entries[name]["workloads"] == [
            c for c in closed if "sdar" not in c or name != "admit_sync_ms_per_admission"]
    for name in NEW[:2]:
        twin = entries[name + ".open"]
        assert twin["moves"] == "serve_tokens_per_s"
        assert twin["workloads"] == ["mistral-7b-serve.chat-sessions"]
        assert (twin["unit"], twin["source"]) == (entries[name]["unit"], entries[name]["source"])
    assert [m["name"] for m in bench["per_layer"]][-7:] == list(NEW) + [
        NEW[0] + ".open", NEW[1] + ".open"]


def test_rehearsal_reports_an_admission_by_child(tmp_path):
    """The toy serving cell with the new readers listed for it: the
    supervised server's ``/v1/goodput`` carries the four children, its
    trace the ``slot-engine`` line with their events and the
    dispatches' ``live``. (The one ``device_trace`` reader has no
    module line of a device to read on the CPU.)"""
    root = toyroot.build(str(tmp_path / "checkout"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    for metric in bench["per_layer"]:
        if metric["name"] in NEW:
            metric["workloads"].append("toy-serve.toy-closed")
    with open(path, "w") as fh:
        json.dump(bench, fh)
    result = rehearsal(run_cell(root, "toy-serve.toy-closed", 3_900_000_011, 1))
    got = {name: result["metrics"][name]["value"] for name in NEW if name in result["metrics"]}
    assert "admit_device_programs_per_admission" not in got  # no chip's module line
    assert got["admit_sync_ms_per_admission"] > 0.0
    assert got["admit_first_token_host_ms_per_admission"] > 0.0
    assert got["device_idle_in_first_token_host_share"] >= 0.0
    with open(os.path.join(root, "benchmark", "configs", "toy-serve.json")) as fh:
        args = json.load(fh)["launch"]["replica_args"]
    slots = int(args[args.index("--slots") + 1])
    assert 1.0 <= got["pool_live_rows_at_dispatch"] <= slots
    with open(os.path.join(root, "chiprun_out", "benchmark", "toy-serve.toy-closed",
                           "admission_children.json")) as fh:
        kept = json.load(fh)
    counted = kept["counters"]
    assert counted["admissions"] > 0
    children = counted["children"]
    parts = sum(children[FIRST + "." + c]["seconds"] for c in ("sample", "sync", "insert", "state"))
    # at toy size on a busy CPU an admission's five span boundaries (some
    # tens of us of Python) are 2-4 % of a 6 ms first token; the chip's
    # first tokens are 17-75 ms and hold 2 % (PERF.md section 5)
    assert 0.9 * children[FIRST]["seconds"] <= parts <= children[FIRST]["seconds"]
    assert (got["admit_sync_ms_per_admission"] + got["admit_first_token_host_ms_per_admission"]
            == pytest.approx(parts * 1e3 / counted["admissions"]))
    assert kept["live_rows"]["with_live"] == kept["live_rows"]["dispatches"] > 0
    assert kept["idle_s"]["host"] <= kept["idle_s"]["idle"]
