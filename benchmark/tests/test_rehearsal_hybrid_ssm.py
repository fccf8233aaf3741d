"""End-to-end rehearsal, at toy size on the CPU, of the hybrid
state-space family (``--model-config``, models/hybrid_ssm.py): the toy
checkout of toyroot.py plus a toy of the granitemoehybrid keys, a cell
and the family's per-layer metrics, all ADDED AS FILES. Supervisor, the
launcher, the program's own main() without a prefix cache, gateway,
closed-loop load, trace, teardown, ``granite_reference.py``,
contract."""
import json
import os
import shutil

import pytest

import toyroot
from test_rehearsal import rehearsal, run_cell

CELL = "toy-granite.toy-closed"
COUNTER_METRICS = ("expert_load_max_over_mean", "engine_dispatches_per_token",
                   "engine_fused_dispatch_share", "compiles_in_window.serve")
TRACE_METRICS = ("decode_expert_share", "decode_attention_share",
                 "decode_step_device_ms.hybrid-ssm",
                 "decode_step_roofline.hybrid-ssm", "decode_ssm_share",
                 "ssm_state_roofline", "expert_matmul_roofline.hybrid-ssm")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = toyroot.build(str(tmp_path_factory.mktemp("toy-ssm") / "checkout"))
    shutil.copy(os.path.join(toyroot.TOY, "toy-granite.json"),
                os.path.join(root, "benchmark", "configs"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["configs"].append({
        "name": "toy-granite", "source": "benchmark/tests/toy",
        "file": "benchmark/configs/toy-granite.json", "reduced": [],
        "why": "toy sizes for a CPU rehearsal"})
    bench["workloads"].append({
        "name": CELL, "config": "toy-granite", "traffic": "toy-closed",
        "chips": 1, "why": "toy cell for a CPU rehearsal"})
    for metric in bench["end_to_end"]:
        if metric["name"] == "tpot_p95_ms":
            metric["workloads"].append(CELL)
    for metric in bench["per_layer"]:
        if metric["name"] in COUNTER_METRICS + TRACE_METRICS + ("toy_count",):
            metric["workloads"].append(CELL)
    with open(path, "w") as fh:
        json.dump(bench, fh, indent=1)
    return root


def test_hybrid_state_space_model_serves_and_is_judged(root):
    result = rehearsal(run_cell(root, CELL, 3_000_000_019, 1))
    assert result["correct"] is True and result["failed"] == 0
    metrics = result["metrics"]
    assert metrics["expert_load_max_over_mean"]["value"] >= 1.0
    assert metrics["compiles_in_window.serve"]["value"] == 0
    assert 0 < metrics["engine_dispatches_per_token"]["value"] < 1
    out = os.path.join(root, "chiprun_out", "benchmark", CELL)
    with open(os.path.join(out, "experts_counters.json")) as fh:
        experts = json.load(fh)
    assert experts["published"] == 8 and experts["held"] == 4
    assert 0 < experts["assignments_here"] == sum(experts["load"])
    with open(os.path.join(out, "reference.json")) as fh:
        reference = json.load(fh)
    assert reference["positions"] > 0
    assert 0.0 <= reference["near_tie_share"] < 0.2


def test_the_readers_count_steps_and_live_rows_from_the_counters(root):
    """What the family's readers made of the rehearsal's own snapshots
    (``state_counters.json``): the pool's steps from
    ``state.ssm_row_steps``, the live rows from the engine's tokens,
    and ``/v1/model`` ``state`` equal to what
    harness/counts_hybrid_ssm.py reckons from the toy's keys."""
    from benchmark.harness import counts_hybrid_ssm as counts

    out = os.path.join(root, "chiprun_out", "benchmark", CELL)
    with open(os.path.join(out, "state_counters.json")) as fh:
        kept = json.load(fh)
    with open(os.path.join(toyroot.TOY, "toy-granite.json")) as fh:
        config = json.load(fh)
    state = kept["state"]
    assert state["layer_kinds"] == {"mamba": 3, "attention": 1}
    assert state["state_bytes_per_slot"] == counts.state_bytes_per_slot(config)
    assert state["kv_bytes_per_position"] == counts.kv_bytes_per_position(config)
    assert kept["slots"] == 4 and kept["steps"] > 0
    assert 0 < kept["live_rows"] <= 4
    assert state["ssm_row_steps"] >= kept["steps"] * 4 * 3


def test_a_count_over_the_slots_is_shown_not_hidden():
    """Nothing holds the live rows to the slots: a token or step count
    gone wrong reads as more rows than the pool has, and so as a
    roofline over 100 %."""
    from benchmark.harness.spec import load_module

    readers = load_module(os.path.join(
        toyroot.REPO, "benchmark", "layer_metrics", "hybrid_ssm_readers.py"))

    def snapshot(row_steps, tokens, admissions):
        return {
            "model": [{"state": {"ssm_row_steps": row_steps,
                                 "layer_kinds": {"mamba": 3, "attention": 1}},
                       "slot_engine": {"slots": 4}}],
            "goodput": [{"tokens_out": tokens,
                         "engine": {"admissions": admissions}}],
        }

    # 10 steps of a pool of 4 rows x 3 mamba layers
    sound = readers.pool({"before": snapshot(0, 0, 0),
                          "after": snapshot(120, 38, 3)})
    assert sound == {"steps": 10.0, "slots": 4, "live_rows": 3.5}
    wrong = readers.pool({"before": snapshot(0, 0, 0),
                          "after": snapshot(120, 63, 3)})
    assert wrong["live_rows"] == 6.0 > wrong["slots"]


REAL = os.path.join(toyroot.REPO, "benchmark", "configs",
                    "granite-4-h-small-serve.json")


@pytest.mark.parametrize("what, millions", [
    ("mamba_params", 102.28), ("attention_params", 41.94),
    ("expert_params", 9.437), ("held_params", 4757.0),
    ("head_params", 205.5),
])
def test_counts_reckon_the_real_configuration(what, millions):
    """ISSUE 37's reckoned sizes, from the configuration's own keys."""
    from benchmark.harness import counts_hybrid_ssm as counts

    with open(REAL) as fh:
        config = json.load(fh)
    assert getattr(counts, what)(config) / 1e6 == pytest.approx(millions, rel=2e-4)


def test_a_steps_bytes_are_the_issues_arithmetic():
    """64 live rows at 1,300 live positions each, every held expert
    touched: 9.51 GB of weights less the embedding's second reading,
    4.89 GB of state read and written, 0.34 GB of keys and values."""
    from benchmark.harness import counts_hybrid_ssm as counts

    with open(REAL) as fh:
        config = json.load(fh)
    assert counts.state_bytes_per_slot(config) == 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert counts.kv_bytes_per_position(config) == 4096
    assert counts.ssm_update_bytes(config, 64) == pytest.approx(4.83e9, rel=1e-3)
    step = counts.decode_step_bytes(config, 64, 64 * 1300, 36 * 10)
    assert step == pytest.approx(
        2 * counts.held_params(config) + 2 * 64 * counts.state_bytes_per_slot(config)
        + 64 * 1300 * 4096)
    assert step / 819e9 * 1e3 == pytest.approx(18.0, abs=0.3)
