"""End-to-end rehearsal, at toy size on the CPU, of the looped family
(``--model-config``, models/looped.py): the toy checkout of toyroot.py
plus a toy of the ouro keys, a cell and the family's per-layer metrics,
all ADDED AS FILES. Supervisor, the launcher, the program's own main(),
gateway, closed-loop load, trace, teardown, ``ouro_reference.py``,
contract."""
import json
import os
import shutil

import pytest

import toyroot
from test_rehearsal import rehearsal, run_cell

CELL = "toy-ouro.toy-closed"
COUNTER_METRICS = ("engine_dispatches_per_token", "engine_fused_dispatch_share",
                   "compiles_in_window.serve", "loop_passes_per_token")
TRACE_METRICS = ("decode_attention_share", "decode_step_device_ms.looped",
                 "decode_step_roofline.looped")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = toyroot.build(str(tmp_path_factory.mktemp("toy-loop") / "checkout"))
    shutil.copy(os.path.join(toyroot.TOY, "toy-ouro.json"),
                os.path.join(root, "benchmark", "configs"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["configs"].append({
        "name": "toy-ouro", "source": "benchmark/tests/toy",
        "file": "benchmark/configs/toy-ouro.json", "reduced": [],
        "why": "toy sizes for a CPU rehearsal"})
    bench["workloads"].append({
        "name": CELL, "config": "toy-ouro", "traffic": "toy-closed",
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


def test_looped_model_serves_and_is_judged(root):
    result = rehearsal(run_cell(root, CELL, 3_000_000_019, 1))
    assert result["correct"] is True and result["failed"] == 0
    metrics = result["metrics"]
    assert metrics["compiles_in_window.serve"]["value"] == 0
    assert 0 < metrics["engine_dispatches_per_token"]["value"] < 1
    assert metrics["loop_passes_per_token"]["value"] == 4.0
    out = os.path.join(root, "chiprun_out", "benchmark", CELL)
    with open(os.path.join(out, "reference.json")) as fh:
        reference = json.load(fh)
    assert reference["positions"] > 0


def test_the_readers_count_steps_and_live_rows_from_the_counters(root):
    """What the family's readers made of the rehearsal's own snapshots
    (``loop_counters.json``): the pool's steps from
    ``loop.loop_row_steps``, the live rows from the engine's tokens,
    and ``/v1/model`` ``loop`` equal to what harness/counts_looped.py
    reckons from the toy's keys."""
    from benchmark.harness import counts_looped as counts

    out = os.path.join(root, "chiprun_out", "benchmark", CELL)
    with open(os.path.join(out, "loop_counters.json")) as fh:
        kept = json.load(fh)
    with open(os.path.join(toyroot.TOY, "toy-ouro.json")) as fh:
        config = json.load(fh)
    loop = kept["loop"]
    assert (loop["passes"], loop["layers"], loop["cache_planes"]) == (4, 3, 12)
    assert loop["cache_planes"] == counts.cache_planes(config)
    assert loop["cache_bytes_per_position"] == counts.cache_bytes_per_position(config)
    assert kept["slots"] == 4 and kept["steps"] > 0
    assert 0 < kept["live_rows"] <= 4
    assert kept["row_passes"] == 4 * kept["row_steps"] == 16 * kept["steps"]


def test_a_count_over_the_slots_is_shown_not_hidden():
    """Nothing holds the live rows to the slots: a token or step count
    gone wrong reads as more rows than the pool has, and so as a
    roofline over 100 %."""
    from benchmark.harness.spec import load_module

    readers = load_module(os.path.join(
        toyroot.REPO, "benchmark", "layer_metrics", "looped_readers.py"))

    def snapshot(row_steps, tokens, admissions):
        return {
            "model": [{"loop": {"loop_row_steps": row_steps,
                                "loop_row_passes": 4 * row_steps},
                       "slot_engine": {"slots": 4}}],
            "goodput": [{"tokens_out": tokens,
                         "engine": {"admissions": admissions}}],
        }

    # 10 steps of a pool of 4 rows
    sound = readers.loop({"before": snapshot(0, 0, 0),
                          "after": snapshot(40, 38, 3)})
    assert sound == {"steps": 10.0, "slots": 4, "row_steps": 40,
                     "row_passes": 160, "live_rows": 3.5}
    wrong = readers.loop({"before": snapshot(0, 0, 0),
                          "after": snapshot(40, 63, 3)})
    assert wrong["live_rows"] == 6.0 > wrong["slots"]
    assert readers.loop({"before": {"model": [{}], "goodput": [{}]},
                         "after": {"model": [{}], "goodput": [{}]}}) is None


REAL = os.path.join(toyroot.REPO, "benchmark", "configs", "ouro-2.6b-serve.json")


@pytest.mark.parametrize("what, number", [
    ("attention_params", 16_777_216), ("mlp_params", 34_603_008),
    ("layer_params", 51_388_416), ("head_params", 100_663_296),
    ("total_params", 2_667_974_657), ("cache_planes", 192),
    ("cache_bytes_per_position", 1_572_864),
])
def test_counts_reckon_the_real_configuration(what, number):
    """ISSUE 42's reckoned sizes, from the configuration's own keys."""
    from benchmark.harness import counts_looped as counts

    with open(REAL) as fh:
        config = json.load(fh)
    assert getattr(counts, what)(config) == number


def test_a_steps_bytes_are_the_issues_arithmetic():
    """16 rows read to their 320th position: the looped weights four
    times (19.73 GB), the head once (0.20 GB), the pool once (8.05 GB):
    27.98 GB, 34 ms at the chip's 819 GB/s; the live half of it less."""
    from benchmark.harness import counts_looped as counts

    with open(REAL) as fh:
        config = json.load(fh)
    assert 4 * counts.looped_weight_bytes(config) == pytest.approx(19.73e9, rel=1e-3)
    whole = counts.decode_step_bytes(config, 16 * 320)
    assert whole == pytest.approx(27.98e9, rel=1e-3)
    assert whole / 819e9 * 1e3 == pytest.approx(34.2, abs=0.1)
    live = counts.decode_step_bytes(config, 16 * 150)
    assert live == pytest.approx(19.73e9 + 0.2013e9 + 16 * 150 * 1_572_864, rel=1e-4)
    assert live < whole
