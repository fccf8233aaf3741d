"""End-to-end rehearsal, at toy size on the CPU, of the
decoder-hybrid-decoder family (``--model-config``,
models/decoder_hybrid.py): the toy checkout of toyroot.py plus a toy of
the phi4flash keys, a cell and the family's per-layer metrics, all
ADDED AS FILES. Supervisor, the launcher, the program's own main(),
gateway, closed-loop load, trace, teardown, ``phi4flash_reference.py``,
contract. And the counts module against ISSUE 45's arithmetic."""
import json
import os
import shutil

import pytest

import toyroot
from test_rehearsal import rehearsal, run_cell

CELL = "toy-phi4flash.toy-closed"
COUNTER_METRICS = ("engine_dispatches_per_token", "engine_fused_dispatch_share",
                   "compiles_in_window.serve")
TRACE_METRICS = ("decode_attention_share", "decode_ssm_share",
                 "decode_step_device_ms.decoder-hybrid",
                 "decode_step_roofline.decoder-hybrid", "shared_plane_roofline",
                 "window_ring_roofline", "ssm_state_roofline.mamba1",
                 "decode_gmu_share", "pool_live_rows_at_dispatch")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = toyroot.build(str(tmp_path_factory.mktemp("toy-yoco") / "checkout"))
    shutil.copy(os.path.join(toyroot.TOY, "toy-phi4flash.json"),
                os.path.join(root, "benchmark", "configs"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["configs"].append({
        "name": "toy-phi4flash", "source": "benchmark/tests/toy",
        "file": "benchmark/configs/toy-phi4flash.json", "reduced": [],
        "why": "toy sizes for a CPU rehearsal"})
    bench["workloads"].append({
        "name": CELL, "config": "toy-phi4flash", "traffic": "toy-closed",
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


def test_decoder_hybrid_model_serves_and_is_judged(root):
    result = rehearsal(run_cell(root, CELL, 3_000_000_023, 1))
    assert result["correct"] is True and result["failed"] == 0
    metrics = result["metrics"]
    assert metrics["compiles_in_window.serve"]["value"] == 0
    assert 0 < metrics["engine_dispatches_per_token"]["value"] < 1
    assert 0 < metrics["pool_live_rows_at_dispatch"]["value"] <= 4
    out = os.path.join(root, "chiprun_out", "benchmark", CELL)
    with open(os.path.join(out, "reference.json")) as fh:
        reference = json.load(fh)
    assert reference["positions"] > 0


def test_the_counters_are_the_families_arithmetic(root):
    """``/v1/model`` ``hybrid_decoder`` at the window's close, as the
    harness kept it: every step of the pool (4 rows) steps 4 Mamba
    layers and 3 rings a row and reads the plane 3 times a row (the
    full layer and two cross layers); every prompt (32 tokens) passes
    the window of 8, so every row of a step has wrapped once admitted;
    a prefill runs the self-decoder over the prompt and the
    cross-decoder for one position."""
    from benchmark.harness import counts_decoder_hybrid as counts

    out = os.path.join(root, "chiprun_out", "benchmark", CELL)
    with open(os.path.join(out, "result.json")) as fh:
        kept = json.load(fh)
    assert kept["result"]["correct"] is True
    with open(os.path.join(toyroot.TOY, "toy-phi4flash.json")) as fh:
        config = json.load(fh)
    # the readers' own copy of the block (decoder_hybrid_scopes.json is
    # written only where the CPU trace has a device plane: it has none)
    from benchmark.harness import procs

    counted = procs.read_json(os.path.join(out, "hybrid_decoder.json"))
    kinds = counted["layer_kinds"]
    assert kinds == {"mamba": 4, "window": 3, "full": 1, "gmu": 2, "cross": 2}
    assert kinds == {kind: counts.count(config, kind) for kind in counts.KINDS}
    assert counted["plane_readers"] == counts.plane_readers(config) == 3
    assert counted["window"] == 8
    row = counts.row_cache_bytes(config, 256)
    assert counted["state_bytes_per_slot"] == row["state"]
    assert counted["ring_bytes_per_slot"] == row["rings"]
    assert counted["plane_bytes_per_position"] * 256 == row["plane"]
    steps = counted["ssm_row_steps"] // (4 * 4)
    assert steps > 0 and counted["ssm_row_steps"] == steps * 16
    assert counted["ring_row_steps"] == steps * 4 * 3
    assert counted["shared_plane_reads"] == steps * 4 * 3
    assert 0 < counted["ring_rows_wrapped"] <= steps * 4
    # between the window's two snapshots (before them a boot's warm-up
    # admits a prompt of its own length)
    admissions = counted["moved_in_window"]["prefill_positions_cross"]
    assert admissions > 0
    assert counted["moved_in_window"]["prefill_positions_self"] == 32 * admissions


def test_readers_give_nothing_for_another_family():
    """A program without the ``hybrid_decoder`` block (the parent, any
    other family): every reader returns None and raises nothing."""
    from benchmark.harness.spec import load_module

    metrics = os.path.join(toyroot.REPO, "benchmark", "layer_metrics")
    run = {"after": {"model": [{"state": {"ssm_row_steps": 9}}]},
           "trace": {"modules": {}}, "cell": "no-such-cell", "records": [],
           "config": {}, "device_kind": "TPU v5 lite"}
    for name in TRACE_METRICS[2:-1]:
        assert load_module(os.path.join(metrics, name + ".py")).read(dict(run)) is None


REAL = os.path.join(toyroot.REPO, "benchmark", "configs",
                    "phi-4-mini-flash-serve.json")


@pytest.fixture(scope="module")
def real():
    with open(REAL) as fh:
        return json.load(fh)


def test_the_real_file_holds_every_key_of_the_catalog_row(real):
    row = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
           "intermediate_size": 10240, "layer_norm_eps": 1e-05,
           "max_position_embeddings": 262144, "mb_per_layer": 2,
           "model_type": "phi4flash", "num_attention_heads": 40,
           "num_hidden_layers": 32, "num_key_value_heads": 20,
           "resid_pdrop": 0, "sliding_window": 512,
           "tie_word_embeddings": True, "mlp_bias": False,
           "lm_head_bias": False, "vocab_size": 200064}
    assert {key: real[key] for key in row} == row
    assert real["reduced"] == {}


@pytest.mark.parametrize("what, arguments, number", [
    ("mixer_matrices", ("mamba",), 41_241_600),
    ("mixer_matrices", ("window",), 19_660_800),
    ("mixer_matrices", ("full",), 19_660_800),
    ("mixer_matrices", ("gmu",), 26_214_400),
    ("mixer_matrices", ("cross",), 13_107_200),
    ("mlp_matrices", (), 78_643_200),
    ("embedding_params", (), 512_163_840),
    ("matrix_params", (), 3_852_119_040),
    ("vector_params", (), 443_904),
    ("total_params", (), 3_852_562_944),
    ("plane_readers", (), 8),
    ("position_bytes", (), 5_120),
])
def test_counts_reckon_the_real_configuration(real, what, arguments, number):
    """ISSUE 45's reckoned sizes, from the configuration's own keys."""
    from benchmark.harness import counts_decoder_hybrid as counts

    assert getattr(counts, what)(real, *arguments) == number


def test_the_layer_kinds_are_the_published_rule(real):
    from benchmark.harness import counts_decoder_hybrid as counts

    kinds = counts.layer_kinds(32)
    assert [i for i, k in enumerate(kinds) if k == "mamba"] == list(range(0, 17, 2))
    assert [i for i, k in enumerate(kinds) if k == "window"] == list(range(1, 16, 2))
    assert kinds.index("full") == 17 and kinds.count("full") == 1
    assert [i for i, k in enumerate(kinds) if k == "gmu"] == list(range(18, 32, 2))
    assert [i for i, k in enumerate(kinds) if k == "cross"] == list(range(19, 32, 2))
    assert counts.layer_kinds(12) == (
        "mamba", "window", "mamba", "window", "mamba", "window", "mamba",
        "full", "gmu", "cross", "gmu", "cross")


def test_a_rows_cache_and_a_steps_bytes_are_the_issues_arithmetic(real):
    """39.9 MB a row at 3,072 positions; at 64 live rows of 1,280 live
    positions a step must move 12.8 GB (the plane's live positions
    eight times 3.4 GB, the rings 1.34 GB, the state 0.38 GB), 15.6 ms
    at the chip's 819 GB/s; read whole, the plane alone is 8.05 GB."""
    from benchmark.harness import counts_decoder_hybrid as counts

    row = counts.row_cache_bytes(real, 3072)
    assert row == {"state": 3_225_600, "rings": 20_971_520, "plane": 15_728_640}
    assert sum(row.values()) == 39_925_760
    assert counts.weight_bytes_per_step(real) == 2 * 3_852_119_040
    assert counts.shared_plane_bytes(real, 64 * 1280) == pytest.approx(3.355e9, rel=1e-3)
    assert counts.shared_plane_bytes(real, 64 * 3072) == pytest.approx(8.05e9, rel=1e-3)
    assert counts.ring_bytes(real, 64, 1280) == pytest.approx(1.342e9, rel=1e-3)
    assert counts.ring_bytes(real, 64, 100) < counts.ring_bytes(real, 64, 512)
    assert counts.ssm_update_bytes(real, 64) == pytest.approx(0.3775e9, rel=1e-3)
    step = counts.decode_step_bytes(real, 64, 1280)
    assert step == pytest.approx(12.78e9, rel=1e-3)
    assert step / 819e9 * 1e3 == pytest.approx(15.6, abs=0.1)
