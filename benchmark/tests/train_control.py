#!/usr/bin/env python3
"""The training cells' control, read in one process: the reference put
in the program's place and computed in the nearest precision below the
one the configuration states (int8 weights, single-pass bfloat16
products; mistral_reference.make_grad_fn), followed through the same
first steps on the same rows as the float32 reference, and held to the
same numbers as a run of the program. It has to come out NOT correct.

    python3 benchmark/tests/train_control.py <workload> <seed> [<seed> ...]

On the chip this is the cell's own size (three seeds or more when a
limit is set); test_train_control.py runs it at toy size on the CPU.
Prints one JSON line per seed and exits 0 iff every seed failed a limit.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark.harness import loadgen  # noqa: E402
from benchmark.harness.spec import Cell, load_module  # noqa: E402
from benchmark.harness.stats import leaf_sum_gap, worst_leaf_gap  # noqa: E402


def read(root: str, workload: str, seeds, mode: str = "int8"):
    cell = Cell(root, workload)
    config, traffic = cell.config, cell.traffic
    check = config["check"]
    reference = load_module(os.path.join(
        os.path.dirname(cell.config_path), config["reference"]))
    batch, n_windows = int(traffic["batch"]), int(traffic["windows"])
    for seed in seeds:
        with tempfile.TemporaryDirectory(dir=os.environ.get("TMPDIR")) as tmp:
            shard = os.path.join(tmp, "shard_00000.npy")
            np.save(shard, loadgen.train_tokens(
                traffic, int(config["vocab_size"]), seed))
            spec = {
                "shards": [shard], "seq_len": int(traffic["seq_len"]),
                "window": int(check.get("window", 0)),
                "steps": [loadgen.batch_rows(s, batch, n_windows)
                          for s in range(int(check["follow_steps"]))],
            }
            sound = reference.train_steps(config, spec)
            lower = reference.train_steps(config, spec, mode)
        numbers = {f"step{k + 1}_loss_gap": abs(a - b) for k, (a, b) in
                   enumerate(zip(lower["losses"], sound["losses"]))}
        for number, key in (("first_gradient_norm_gap", "first_moment_norms"),
                            ("update_norm_gap", "change_norms")):
            numbers[number], numbers[number + "_leaf"] = worst_leaf_gap(
                lower[key], sound[key])
        numbers["first_gradient_sum_gap"] = leaf_sum_gap(
            lower["first_moment_sums"], sound["first_moment_sums"],
            sound["first_moment_norms"])
        limits = {n: float(check["step_loss_gap"][int(n[4]) - 1]
                           if n.endswith("loss_gap") else check[n])
                  for n in numbers if not n.endswith("_leaf")}
        failed = [n for n, limit in limits.items() if not numbers[n] <= limit]
        yield {"seed": seed, "control": mode, "numbers": numbers, "limits": limits,
               "fails": failed, "correct": not failed,
               "reference_grad_norms": sound["grad_norms"], "reference_clip": sound["clip"]}


def main(argv) -> int:
    verdicts = []
    for line in read(ROOT, argv[0], [int(s) for s in argv[1:]]):
        print(json.dumps(line), flush=True)
        verdicts.append(line["correct"])
    return 0 if verdicts and not any(verdicts) else 1


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
