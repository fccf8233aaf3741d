"""End-to-end rehearsal, at toy size on the CPU, of a configuration
that is read from a file (``--model-config``, models/mla_moe.py): the
toy checkout of toyroot.py plus a toy of the axk1 keys, a cell and the
family's per-layer metrics, all ADDED AS FILES. Supervisor, the new
launcher, the program's own main(), gateway, closed-loop load, trace,
teardown, ``axk1_reference.py``, contract."""
import json
import os
import shutil

import pytest

import toyroot
from test_rehearsal import rehearsal, run_cell

CELL = "toy-axk1.toy-closed"
COUNTER_METRICS = ("expert_load_max_over_mean",)
TRACE_METRICS = ("decode_expert_share", "expert_matmul_roofline",
                 "latent_attention_roofline", "decode_step_roofline.mla-moe",
                 "decode_step_device_ms.mla-moe")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = toyroot.build(str(tmp_path_factory.mktemp("toy-mla") / "checkout"))
    shutil.copy(os.path.join(toyroot.TOY, "toy-axk1.json"),
                os.path.join(root, "benchmark", "configs"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["configs"].append({
        "name": "toy-axk1", "source": "benchmark/tests/toy",
        "file": "benchmark/configs/toy-axk1.json", "reduced": [],
        "why": "toy sizes for a CPU rehearsal"})
    bench["workloads"].append({
        "name": CELL, "config": "toy-axk1", "traffic": "toy-closed",
        "chips": 1, "why": "toy cell for a CPU rehearsal"})
    for metric in bench["end_to_end"]:
        if metric["name"] in ("tpot_p95_ms", "serve_tokens_per_s"):
            metric["workloads"].append(CELL)
    for metric in bench["per_layer"]:
        if metric["name"] in COUNTER_METRICS + TRACE_METRICS + (
                "toy_count", "engine_dispatches_per_token",
                "engine_fused_dispatch_share", "compiles_in_window.serve"):
            metric["workloads"].append(CELL)
    with open(path, "w") as fh:
        json.dump(bench, fh, indent=1)
    return root


def test_model_from_a_file_serves_and_is_judged(root):
    result = rehearsal(run_cell(root, CELL, 3_000_000_019, 1))
    assert result["correct"] is True and result["failed"] == 0
    metrics = result["metrics"]
    assert metrics["expert_load_max_over_mean"]["value"] >= 1.0
    assert metrics["compiles_in_window.serve"]["value"] == 0
    out = os.path.join(root, "chiprun_out", "benchmark", CELL)
    with open(os.path.join(out, "experts_counters.json")) as fh:
        experts = json.load(fh)
    assert experts["published"] == 16 and experts["held"] == 4
    assert experts["rows"] > 0
    assert 0 < experts["assignments_here"] == sum(experts["load"])
    assert 0 < experts["expert_steps_touched"] <= experts["expert_steps"]
    with open(os.path.join(out, "reference.json")) as fh:
        reference = json.load(fh)
    assert 0.0 <= reference["near_tie_share"] < 0.2
    assert reference["positions"] > 0
    clear = reference["clear"]
    assert [part["margin_over"] for part in clear] == [1e-3, 3e-3, 1e-2, 3e-2]
    assert reference["positions"] >= clear[0]["positions"] >= clear[-1]["positions"]
    assert clear[0]["positions"] > 0
    assert all(part["max_logit_gap"] <= reference["max_logit_gap"] for part in clear)
