"""The counts and the readers of the latent-attention, routed-expert
family (harness/counts_mla_moe.py, layer_metrics/mla_moe_readers.py and
the six metrics beside it) on a made-up trace and made-up counters:
what a number is computed from is part of the yardstick."""
import json
import os

import pytest

from benchmark.harness import counts_mla_moe as counts
from benchmark.harness.spec import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
METRICS = os.path.join(BENCH, "layer_metrics")

with open(os.path.join(BENCH, "configs", "ax-k1-serve.json")) as fh:
    CONFIG = json.load(fh)


def reader(name):
    return load_module(os.path.join(METRICS, f"{name}.py"))


@pytest.mark.parametrize("what,millions", [
    (counts.attention_params, 101.12), (counts.expert_params, 44.04),
    (counts.dense_layer_params, 497.48),
    (counts.sparse_layer_fixed_params, 146.54),
    (counts.held_params, 4166.19), (counts.head_params, 146.80),
])
def test_parameter_counts_of_the_published_widths(what, millions):
    assert abs(what(CONFIG) / 1e6 - millions) < 0.01


def test_bytes_of_a_step_and_of_a_position():
    assert counts.latent_bytes_per_position(CONFIG) == 6 * (512 + 64) * 2
    assert counts.router_width(CONFIG) == 192
    # nothing routed: the fixed weights and the pool
    fixed = (counts.dense_layer_params(CONFIG)
             + 5 * counts.sparse_layer_fixed_params(CONFIG)
             + counts.head_params(CONFIG)) * 2
    assert counts.decode_step_bytes(CONFIG, 0, 0) == fixed
    # the floor is over LIVE positions: a pool half full costs half
    live = counts.decode_step_bytes(CONFIG, 64 * 1280, 56)
    assert live == fixed + 56 * counts.expert_params(CONFIG) * 2 + 64 * 1280 * 6912
    assert 8.1e9 < live < 8.4e9
    # what the engine reads today, every row to its end (PERF.md)
    assert 8.9e9 < counts.decode_step_bytes(CONFIG, 64 * 3072, 56) < 9.2e9
    flops = counts.absorbed_attention_flops(CONFIG, 64, 3072)
    assert 1.6e11 < flops < 1.8e11


# -- a made-up run ---------------------------------------------------------

STEPS = 4
STEP_NS = 1000


def _ops():
    """Four token-steps of two sparse layers inside one ``jit_run``:
    per layer a router op, an expert loop (a ``while`` WITHOUT a path,
    as the v5e writes it) holding a dispatch, an experts and a combine
    op, and two attention ops; then the sampler: two operations once a
    step and a sort three times a step (a loop inside it); plus a
    prefill program with the same scopes that must not be counted."""
    ops = []
    for step in range(STEPS):
        t = 100 + step * STEP_NS
        for layer in range(2):
            base = "jit(run)/steps/while/body/layers"
            tag = f".{layer}"
            ops += [
                ["fusion.a" + tag, t, 30, f"{base}/attn/attn.absorb/dot"],
                ["fusion.s" + tag, t + 30, 70, f"{base}/attn/attn.scores/dot"],
                ["fusion.r" + tag, t + 100, 10, f"{base}/mlp/mlp.router/dot"],
                ["while.9" + tag, t + 110, 300, ""],
                ["fusion.d" + tag, t + 110, 50, f"{base}/mlp/while/body/mlp.dispatch/gather"],
                ["fusion.e" + tag, t + 160, 200, f"{base}/mlp/while/body/mlp.experts/dot"],
                ["fusion.c" + tag, t + 360, 50, f"{base}/mlp/while/body/mlp.combine/scatter"],
            ]
            t += 450
        sample = "jit(run)/steps/while/body/sample"
        ops += [["fusion.k", t, 5, f"{sample}/jit(_threefry_fold_in)/slice"],
                ["fusion.g", t + 5, 5, f"{sample}/select_n"]]
        ops += [["sort.1", t + 10 + 5 * i, 5, f"{sample}/while/body/sort"]
                for i in range(3)]
    ops.append(["fusion.p", 9000, 500, "jit(fn)/layers/mlp/mlp.experts/dot"])
    ops.append(["fusion.q", 9500, 10, "jit(fn)/sample/select_n"])
    return ops


@pytest.fixture
def run():
    readers = reader("mla_moe_readers")
    doc = {"planes": [{"name": "/device:TPU:0", "ops": _ops(), "modules": [
        ["jit_run(1)", 0, 100 + STEPS * STEP_NS], ["jit_fn(2)", 8990, 600]]}],
        "path_stat": "tf_op", "path_stat_votes": {}}

    def model(rows, load):
        return {"max_len": 3072, "slot_engine": {"slots": 64}, "experts": {
            "published": 192, "held": [0, 12], "per_token": 8,
            "rows": rows, "assignments_here": sum(load),
            "expert_steps_touched": rows // 64 // 5 * 50,
            "expert_steps": rows // 64 // 5 * 60, "load": load}}

    made = {
        "cell": "made-up.cell", "config": CONFIG, "device_kind": "TPU v5 lite",
        "_xplane": doc,  # what trace_scopes.xplane_of keeps in a run
        "trace": {"clock": "device events' extent", "first_event_ns": 0,
                  "last_event_ns": 10_000,
                  "modules": {"jit_run(1)": {"seconds": 16e-3 * STEPS},
                              "jit_fn(2)": {"seconds": 1.0}}},
        # live context (512 + 256 + 1536 + 128) / 2 = 1216; a stream
        # the window cut does not count
        "records": [
            {"done": True, "cut": False, "prompt_len": 512, "tokens": [1] * 512},
            {"done": True, "cut": False, "prompt_len": 1536, "tokens": [1] * 256},
            {"done": False, "cut": True, "prompt_len": 1536, "tokens": [1] * 900},
        ],
        "before": {"model": [model(0, [0] * 12)]},
        "after": {"model": [model(64 * 5 * 100, [300] * 11 + [360])]},
    }
    return made, readers


def test_steps_are_sampler_executions_and_time_is_by_innermost_scope(run):
    made, readers = run
    found = readers.scoped(made)
    assert found["steps"] == STEPS
    assert found["decode_s"] == pytest.approx(64e-3)
    ns = {k: round(v * 1e9) for k, v in found["children"].items()}
    # the prefill program's experts are not the decode programs'
    assert ns == {"attn.absorb": 240, "attn.scores": 560, "mlp.router": 80,
                  "mlp.dispatch": 400, "mlp.experts": 1600, "mlp.combine": 400}


def test_the_metrics_read_what_their_notes_say(run):
    made, readers = run
    # each metric's file loads its own copy of the shared module; what
    # the first of them found is kept in the run, as in a real run
    readers.scoped(made)
    share = reader("decode_expert_share").read(made)
    assert share == pytest.approx(100 * 2400e-9 / 64e-3)
    # 50 of 60 (expert, layer) pairs touched a step
    assert readers.touched_per_step(made) == pytest.approx(50.0)
    least_ms = 50 * counts.expert_params(CONFIG) * 2 / 819e9 * 1e3
    assert reader("expert_matmul_roofline").read(made) == pytest.approx(
        100 * least_ms / (1600e-9 * 1e3 / STEPS))
    assert readers.live_context(made) == pytest.approx(1216.0)
    live_ms = 64 * 1216 * 6912 / 819e9 * 1e3
    assert reader("latent_attention_roofline").read(made) == pytest.approx(
        100 * live_ms / (800e-9 * 1e3 / STEPS))
    assert reader("decode_step_device_ms.mla-moe").read(made) == pytest.approx(16.0)
    step_ms = counts.decode_step_bytes(CONFIG, 64 * 1216, 50) / 819e9 * 1e3
    assert reader("decode_step_roofline.mla-moe").read(made) == pytest.approx(
        100 * step_ms / 16.0)
    assert reader("expert_load_max_over_mean").read(made) == pytest.approx(
        360 / (3660 / 12))


@pytest.mark.parametrize("name", [
    "decode_expert_share", "expert_matmul_roofline", "latent_attention_roofline",
    "decode_step_roofline.mla-moe", "decode_step_device_ms.mla-moe",
    "expert_load_max_over_mean"])
def test_a_program_without_the_scopes_or_counters_reads_nothing(name, run):
    made, _readers = run
    bare = {k: v for k, v in made.items() if k not in ("trace", "_xplane")}
    for side in ("before", "after"):
        bare[side] = {"model": [{"max_len": 4096, "slot_engine": {"slots": 16}}]}
    assert reader(name).read(bare) is None
    assert reader(name).read({"cell": "x", "config": CONFIG}) is None


# -- the limits of ``correct``, through the harness's own comparison -------

# (mean_logit_gap, max_logit_gap) as read on the chip (PERF.md section 2;
# my chip runs, PR 27): the ends of the sound program's range, and the
# reference's own int8-weight reading, the lower precision that the
# limits have to refuse
@pytest.mark.parametrize("what,mean_gap,max_gap,correct,fails", [
    ("sound, smallest", 0.01485, 1.18, True, []),
    ("sound, largest", 0.01699, 1.88, True, []),
    ("int8 weights, smallest", 0.04461, 1.72, False, ["mean_logit_gap"]),
    ("int8 weights, largest", 0.04740, 1.73, False, ["mean_logit_gap"]),
])
def test_the_committed_limits_refuse_the_lower_precision(
        what, mean_gap, max_gap, correct, fails):
    from benchmark.harness import serving

    record = {"index": 0, "cut": False, "error": None, "status": 200,
              "done": True, "prompt": [1, 2, 3], "prompt_len": 3,
              "tokens": [4, 5], "max_new": 2}
    asked = []

    def reference(spec):
        asked.append(spec)
        return {"max_logit_gap": max_gap, "mean_logit_gap": mean_gap,
                "positions": 2, "seconds": 0.0, "cases": []}

    verdict = serving.judge(
        {"config": CONFIG, "traffic": {"check_sample": 48}, "reference": reference},
        [record], 7)
    assert asked[0]["max_len"] == 3072 and len(asked[0]["cases"]) == 1
    assert verdict["correct"] is correct, what
    assert [c["number"] for c in verdict["compared"] if not c["holds"]] == fails
