"""The readers PR 24 added (engine counters, device idle by engine
phase, device time by named scope): on synthetic inputs, on a trace
recorded on the chip (recorded/engine_scopes_trace.json.gz, see
``recorded_run``), and in a CPU rehearsal of a toy cell that lists
them."""
import json
import os

import pytest

import recorded_runs
import toyroot
from benchmark.harness.spec import load_module
from test_rehearsal import rehearsal, run_cell

HERE = os.path.dirname(os.path.abspath(__file__))
LAYER_METRICS = os.path.join(os.path.dirname(HERE), "layer_metrics")
MS = 1_000_000


def module(name):
    return load_module(os.path.join(LAYER_METRICS, name + ".py"))


# ---- engine counters -------------------------------------------------------


def goodput(admit_s, store_s, admissions, wait_s, fused, single):
    return {"stages_s": {}, "engine": {
        "phase_s": {"engine.admit": admit_s, "engine.admit.store": store_s},
        "phase_n": {}, "admissions": admissions, "queue_wait_s": wait_s,
        "dispatches_fused": fused, "dispatches_single": single,
    }}


def counter_run(before, after):
    return {"before": {"goodput": [before]}, "after": {"goodput": [after]}}


def test_counter_readers_take_deltas_over_the_window():
    run = counter_run(goodput(1.0, 0.25, 10, 2.0, 5, 20),
                      goodput(4.0, 1.75, 40, 14.0, 5, 80))
    assert module("engine_admit_ms_per_admission").read(run) == pytest.approx(100.0)
    assert module("kvtier_store_spill_ms_per_admission").read(run) == pytest.approx(50.0)
    assert module("engine_queue_wait_mean_ms").read(run) == pytest.approx(400.0)
    assert module("engine_fused_dispatch_share").read(run) == pytest.approx(0.0)
    run = counter_run(goodput(0, 0, 0, 0, 2, 4), goodput(0, 0, 0, 0, 5, 5))
    assert module("engine_fused_dispatch_share").read(run) == pytest.approx(75.0)


def test_admit_share_is_the_phases_seconds_over_the_seconds_between_snapshots():
    """13.2 s of admissions between two snapshots 30 s apart, on one
    replica and as the mean of two: the engine's worker thread admits
    44 % of the window (PERF.md section 5, batch-decode)."""
    run = counter_run(goodput(1.0, 0.25, 10, 2.0, 5, 20),
                      goodput(14.2, 9.0, 186, 14.0, 5, 80))
    run["before"]["at"], run["after"]["at"] = 100.0, 130.0
    assert module("engine_admit_share").read(run) == pytest.approx(44.0)
    for side in ("before", "after"):
        run[side]["goodput"] = run[side]["goodput"] * 2
    assert module("engine_admit_share").read(run) == pytest.approx(44.0)
    quiet = counter_run(goodput(1.0, 0.5, 7, 3.0, 2, 9), goodput(1.0, 0.5, 7, 3.0, 2, 9))
    quiet["before"]["at"], quiet["after"]["at"] = 0.0, 30.0
    assert module("engine_admit_share").read(quiet) == 0.0
    assert module("engine_admit_share").read({"records": []}) is None


@pytest.mark.parametrize("name", [
    "engine_admit_ms_per_admission", "kvtier_store_spill_ms_per_admission",
    "engine_queue_wait_mean_ms", "engine_fused_dispatch_share",
])
def test_counter_readers_read_zero_not_none(name):
    """Counters that exist and did not move, and a program that keeps
    none (the parent of PR 24): a number either way, because the
    contract refuses a line that leaves a listed metric out. A
    training run has no snapshots: nothing to read."""
    quiet = goodput(1.0, 0.5, 7, 3.0, 2, 9)
    assert module(name).read(counter_run(quiet, quiet)) == 0.0
    bare = {"stages_s": {"idle": 1.0}}
    assert module(name).read(counter_run(bare, bare)) == 0.0
    assert module(name).read({"records": []}) is None


# ---- device idle by engine phase --------------------------------------------


def events_doc(ops, engine, other=()):
    scale = lambda events: [[n, s * MS, d * MS] for n, s, d in events]  # noqa: E731
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "kind": "ops", "events": scale(ops)}]},
        {"name": "/host:CPU", "lines": [
            {"name": "slot-engine", "kind": "host", "events": scale(engine)},
            {"name": "python3", "kind": "host", "events": scale(other)}]},
    ]}


def test_idle_is_split_by_the_engine_phase_that_covers_it():
    idle = module("engine_phase_idle")
    doc = events_doc(
        ops=[("%fusion.1", 0, 100), ("%fusion.2", 150, 50), ("%fusion.3", 260, 40),
             ("%fusion.4", 350, 50)],
        engine=[("engine.admit", 90, 50), ("engine.admit.store", 100, 30),
                ("engine.dispatch", 140, 5), ("engine.fetch", 145, 75),
                ("engine.deliver", 220, 20), ("engine.wait_work", 300, 40)],
        other=[("engine.admit", 0, 400)],  # not the engine's line: ignored
    )
    found = idle.split_idle(doc, 0, 400 * MS)
    assert found["window"] == pytest.approx(0.400)
    # gaps: 100-150, 200-260, 300-350
    assert found["idle"] == pytest.approx(0.160)
    assert found["admission"] == pytest.approx(0.040)   # 100-140
    assert found["fetch"] == pytest.approx(0.050)       # 140-150, 200-240
    assert found["wait_work"] == pytest.approx(0.040)   # 300-340
    assert found["unnamed"] == pytest.approx(0.030)     # 240-260, 340-350
    assert sum(found[k] for k in ("admission", "fetch", "wait_work", "unnamed")) \
        == pytest.approx(found["idle"])


def test_a_program_without_the_annotations_reads_all_idle_unnamed():
    idle = module("engine_phase_idle")
    doc = events_doc(ops=[("%fusion.1", 0, 100), ("%fusion.2", 150, 50)], engine=[])
    found = idle.split_idle(doc, 0, 200 * MS)
    assert found["unnamed"] == pytest.approx(found["idle"]) == pytest.approx(0.050)
    assert found["admission"] == found["fetch"] == 0.0
    assert idle.split_idle({"planes": doc["planes"][1:]}, 0, 200 * MS) is None


# ---- device time by scope -----------------------------------------------------


def test_scope_of_takes_the_outermost_layer_scope():
    scopes = module("trace_scopes")
    path = "jit(run)/while/body/closed_call/vmap()/layers/while/body/{}"
    assert scopes.scope_of(path.format("attn/attn.qkv/norm/mul")) == "attn"
    assert scopes.scope_of(path.format("mlp/bsd,df->bsf/dot_general")) == "mlp"
    assert scopes.scope_of("jit(run)/while/body/closed_call/sample/sort") == "sample"
    assert scopes.scope_of(path.format("dynamic_slice")) == "layers"
    assert scopes.scope_of("jit(run)/steps/while") == "steps"
    assert scopes.scope_of(
        "jit(run)/steps/while/body/closed_call/vmap(layers)/while") == "layers"
    assert scopes.scope_of(
        "jit(step_fn)/transpose(jvp(layers))/while/body/checkpoint/attn/attn.out/add"
    ) == "attn"
    assert scopes.scope_of("jit(step_fn)/transpose(jvp(layers))/while/body/sub") == "layers"
    assert scopes.scope_of("jit(step_fn)/jvp(loss.chunks)/while/body/head/dot_general") == "head"
    assert scopes.scope_of("jit(step_fn)/jvp(loss.chunks)/while/body/dynamic_slice") == "loss"
    assert scopes.scope_of("jit(step_fn)/optimizer/sub") == "optimizer"
    # whole names only: no scope hides inside another word
    assert scopes.scope_of("jit(run)/normalize/attnx/headroom") == "unnamed"
    assert scopes.scope_of("") == "unnamed"
    assert scopes.under("jit(run)/layers/while/body/attn/attn.scores/exp", "attn")
    assert not scopes.under("jit(run)/layers/while/body/mlp/mul", "attn")


def synthetic_xplane(path):
    """A device plane as the TPU's profiler writes it: the op_name path
    is a string statistic of the event METADATA (here ``tf_op``, once
    inline and once by reference); times in ps from the line's stamp."""
    scopes = module("trace_scopes")
    space = scopes._xspace_class()()
    plane = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "hlo_category"), (3, "flops"),
                      (9, "jit(run)/layers/while/body/mlp/dot_general")):
        entry = plane.stat_metadata.add(key=key)
        entry.value.id, entry.value.name = key, name
    ops = [  # id, name, path (None: by reference to stat name 9)
        (1, "%while.1 = while(...)", "jit(run)/layers/while"),
        (2, "%fusion.1 = fusion(...)", "jit(run)/layers/while/body/attn/attn.scores/exp"),
        (3, "%fusion.2 = fusion(...)", None),
        (4, "%copy.3 = copy(...)", "jit(other)/copy"),
    ]
    for key, name, op_path in ops:
        entry = plane.event_metadata.add(key=key)
        entry.value.id, entry.value.name = key, name
        stat = entry.value.stats.add(metadata_id=1)
        if op_path is None:
            stat.ref_value = 9
        else:
            stat.str_value = op_path
        entry.value.stats.add(metadata_id=2, str_value="fusion")
    for key, name in ((10, "jit_run(123)"), (11, "jit_other(456)")):
        entry = plane.event_metadata.add(key=key)
        entry.value.id, entry.value.name = key, name
    line = plane.lines.add(name="XLA Ops", timestamp_ns=1000)
    for meta, start_ms, dur_ms in ((1, 0, 100), (2, 10, 30), (3, 50, 40), (4, 150, 50)):
        line.events.add(metadata_id=meta, offset_ps=start_ms * MS * 1000,
                        duration_ps=dur_ms * MS * 1000)
    line = plane.lines.add(name="XLA Modules", timestamp_ns=1000)
    for meta, start_ms, dur_ms in ((10, 0, 100), (11, 150, 50)):
        line.events.add(metadata_id=meta, offset_ps=start_ms * MS * 1000,
                        duration_ps=dur_ms * MS * 1000)
    space.planes.add(name="/host:CPU")
    with open(path, "wb") as fh:
        fh.write(space.SerializeToString())


def test_xplane_paths_come_from_the_metadata_statistic(tmp_path):
    scopes = module("trace_scopes")
    path = str(tmp_path / "t.xplane.pb")
    synthetic_xplane(path)
    doc = scopes.read_xplane(path)
    assert doc["path_stat"] == "tf_op"
    (plane,) = doc["planes"]
    assert [op[3] for op in plane["ops"]][1:3] == [
        "jit(run)/layers/while/body/attn/attn.scores/exp",
        "jit(run)/layers/while/body/mlp/dot_general"]
    assert plane["ops"][1][1:3] == [1000 + 10 * MS, 30 * MS]
    found = scopes.self_seconds(plane["ops"], plane["modules"], 0, 10**12)
    run = found["jit_run(123)"]
    assert run["scope"] == {"attn": pytest.approx(0.030), "mlp": pytest.approx(0.040),
                            "layers": pytest.approx(0.030)}  # the while's own 30 ms
    assert run["attn"] == {"attn.scores": pytest.approx(0.030)}
    assert found["jit_other(456)"]["scope"] == {"unnamed": pytest.approx(0.050)}
    merged = {"modules": found}
    assert scopes.attention_share(merged, ["jit_run(123)"], 0.100) == pytest.approx(30.0)
    assert scopes.attention_share(merged, ["jit_other(456)"], 0.050) == 0.0


# ---- recorded on the chip ------------------------------------------------------


@pytest.fixture(scope="module")
def recorded_run(tmp_path_factory):
    """PR 24's traced chip run of mistral-7b-serve.chat-sessions, cut
    to its first 1.2 s: the events document the reduction kept (device
    operations and the ``slot-engine`` line only), each operation with
    its ``tf_op`` path as a fourth element, the reduction's summary of
    the same cut, and the two ``/v1/goodput`` snapshots of that run."""
    return recorded_runs.fixture("engine_scopes_trace.json.gz")


def test_recorded_idle_split_adds_up_and_is_mostly_named(recorded_run):
    idle = module("engine_phase_idle")
    doc = {"planes": [
        {"name": p["name"], "lines": [
            {**line, "events": [e[:3] for e in line["events"]]} for line in p["lines"]]}
        for p in recorded_run["events"]["planes"]]}
    found = idle.split_idle(doc, recorded_run["lo"], recorded_run["hi"])
    parts = sum(found[k] for k in ("admission", "fetch", "wait_work", "unnamed"))
    assert parts == pytest.approx(found["idle"], rel=1e-9)
    assert 0.0 < found["idle"] < found["window"]
    assert found["admission"] > 0.0 and found["fetch"] > 0.0
    assert found["unnamed"] < 0.03 * found["window"]


def test_recorded_scopes_name_most_of_the_decode_programs(recorded_run):
    scopes = module("trace_scopes")
    programs = module("decode_programs")
    (plane,) = [p for p in recorded_run["events"]["planes"]
                if p["name"].startswith("/device:TPU:")]
    ops = [e for line in plane["lines"] if line["kind"] == "ops" for e in line["events"]]
    modules = [e for line in plane["lines"] if line["kind"] == "modules"
               for e in line["events"]]
    found = scopes.self_seconds(ops, modules, recorded_run["lo"], recorded_run["hi"])
    decode = [m for m in found if m.startswith(programs.DECODE_MODULE)]
    assert decode
    by_scope = {}
    for name in decode:
        for scope, seconds in found[name]["scope"].items():
            by_scope[scope] = by_scope.get(scope, 0.0) + seconds
    total = sum(by_scope.values())
    assert by_scope.get("unnamed", 0.0) < 0.10 * total
    assert by_scope["attn"] > by_scope["mlp"] > 0.0
    share = scopes.attention_share(
        {"modules": found}, decode, programs.decode_seconds(recorded_run["trace"]))
    assert 50.0 < share <= 100.0


def test_recorded_counters_read_as_numbers(recorded_run):
    run = {"before": recorded_run["before"], "after": recorded_run["after"]}
    assert module("engine_admit_ms_per_admission").read(run) > 0.0
    assert module("kvtier_store_spill_ms_per_admission").read(run) >= 0.0
    assert module("engine_queue_wait_mean_ms").read(run) > 0.0
    assert 0.0 <= module("engine_fused_dispatch_share").read(run) <= 100.0


# ---- a CPU rehearsal that lists the readers in a toy cell ------------------------


def test_rehearsal_reports_the_engine_counters(tmp_path):
    """The toy serving cell with the counter readers listed for it, the
    way BENCHMARK.json lists them for the real cells: the supervised
    server's ``/v1/goodput`` carries the ``engine`` block and the
    readers turn it into numbers. (The device_trace readers have no
    device plane to read on the CPU.)"""
    root = toyroot.build(str(tmp_path / "checkout"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    counters = ("engine_admit_ms_per_admission", "engine_queue_wait_mean_ms",
                "engine_fused_dispatch_share", "kvtier_store_spill_ms_per_admission")
    for metric in bench["per_layer"]:
        if metric["name"] in counters:
            metric["workloads"].append("toy-serve.toy-closed")
    with open(path, "w") as fh:
        json.dump(bench, fh)
    result = rehearsal(run_cell(root, "toy-serve.toy-closed", 2_400_000_011, 1))
    for name in counters:
        assert result["metrics"][name]["value"] >= 0.0, name
    assert result["metrics"]["engine_admit_ms_per_admission"]["value"] > 0.0
