"""End-to-end rehearsal, at toy size on the CPU, of the block-diffusion
family read from a file (``--model-config``,
models/block_diffusion.py): the toy checkout of toyroot.py plus a toy
of the sdar_moe keys, a cell and the family's per-layer metrics, all
ADDED AS FILES. Supervisor, launcher, the program's own main(),
gateway, closed-loop load (outputs that are no multiples of the block),
trace, teardown, ``sdar_reference.py``'s order-free judgement,
contract."""
import json
import os
import shutil

import pytest

import toyroot
from test_rehearsal import rehearsal, run_cell

CELL = "toy-sdar.toy-closed"
COUNTER_METRICS = ("tokens_per_row_forward", "expert_load_max_over_mean")
TRACE_METRICS = ("denoise_forward_device_ms.block-diffusion",
                 "denoise_forward_roofline.block-diffusion",
                 "block_attention_roofline", "denoise_sample_share",
                 "decode_expert_share", "decode_attention_share")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = toyroot.build(str(tmp_path_factory.mktemp("toy-sdar") / "checkout"))
    shutil.copy(os.path.join(toyroot.TOY, "toy-sdar.json"),
                os.path.join(root, "benchmark", "configs"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["configs"].append({
        "name": "toy-sdar", "source": "benchmark/tests/toy",
        "file": "benchmark/configs/toy-sdar.json", "reduced": [],
        "why": "toy sizes for a CPU rehearsal"})
    bench["workloads"].append({
        "name": CELL, "config": "toy-sdar", "traffic": "toy-closed",
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


def test_block_diffusion_serves_and_is_judged(root):
    result = rehearsal(run_cell(root, CELL, 3_000_000_019, 1))
    assert result["correct"] is True and result["failed"] == 0
    metrics = result["metrics"]
    # 4 tokens a block in 2 denoising steps and a commit: 4 / 3, less
    # where a prompt ends inside its first block
    assert 1.0 < metrics["tokens_per_row_forward"]["value"] <= 4 / 3 + 1e-9
    assert metrics["expert_load_max_over_mean"]["value"] >= 1.0
    assert metrics["compiles_in_window.serve"]["value"] == 0
    out = os.path.join(root, "chiprun_out", "benchmark", CELL)
    with open(os.path.join(out, "diffusion_counters.json")) as fh:
        counted = json.load(fh)
    assert counted["block_length"] == 4 and counted["denoising_steps"] == 2
    assert counted["blocks_committed"] == counted["commit_forwards"] > 0
    assert counted["row_forwards"] >= 3 * counted["blocks_committed"]
    with open(os.path.join(out, "experts_counters.json")) as fh:
        experts = json.load(fh)
    assert experts["published"] == 8 and experts["held"] == 8
    assert 0 < experts["assignments_here"] == sum(experts["load"])
    with open(os.path.join(out, "reference.json")) as fh:
        reference = json.load(fh)
    assert reference["positions"] > 0
    assert all(case["blocks_judged"] > 0 for case in reference["cases"])
