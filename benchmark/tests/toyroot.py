"""Builds a temporary copy of the benchmark with a toy configuration,
toy mixes and a toy per-layer metric ADDED AS FILES (plus their entries
in BENCHMARK.json), the way a later PR adds a cell: no file that exists
is edited. Used by the CPU rehearsals in this directory."""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, "toy")

CELLS = [
    ("toy-serve", "toy-closed"), ("toy-serve", "toy-open"),
    ("toy-train", "toy-steady"),
]


def build(root: str, serve_launcher: str = "", train_fault: str = "") -> str:
    """``root`` becomes a checkout: BENCHMARK.json, benchmark/, and the
    program by symlink. For the tests that break the timed path:
    ``serve_launcher`` (a path relative to the root) replaces the toy
    serving configuration's launcher; ``train_fault`` puts
    broken_trainer.py, with that fault, in the toy trainer's place."""
    os.makedirs(root, exist_ok=True)
    shutil.copytree(
        os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    os.symlink(os.path.join(REPO, "containerpilot_tpu"),
               os.path.join(root, "containerpilot_tpu"))
    bench_dir = os.path.join(root, "benchmark")
    for name in ("toy-serve.json", "toy-train.json"):
        shutil.copy(os.path.join(TOY, name), os.path.join(bench_dir, "configs"))
    for name in ("toy-closed.json", "toy-open.json", "toy-steady.json"):
        shutil.copy(os.path.join(TOY, name), os.path.join(bench_dir, "traffic"))
    shutil.copy(os.path.join(TOY, "toy_count.py"),
                os.path.join(bench_dir, "layer_metrics"))
    if serve_launcher:
        path = os.path.join(bench_dir, "configs", "toy-serve.json")
        with open(path) as fh:
            config = json.load(fh)
        config["launch"]["launcher"] = serve_launcher
        with open(path, "w") as fh:
            json.dump(config, fh)
    if train_fault:
        path = os.path.join(bench_dir, "configs", "toy-train.json")
        with open(path) as fh:
            config = json.load(fh)
        config["launch"]["launcher"] = "benchmark/tests/broken_trainer.py"
        config["launch"]["test_fault"] = train_fault
        with open(path, "w") as fh:
            json.dump(config, fh)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    names = [f"{c}.{t}" for c, t in CELLS]
    for config in ("toy-serve", "toy-train"):
        bench["configs"].append({
            "name": config, "source": "benchmark/tests/toy",
            "file": f"benchmark/configs/{config}.json", "reduced": [],
            "why": "toy sizes for a CPU rehearsal",
        })
    for (config, traffic), name in zip(CELLS, names):
        bench["workloads"].append({
            "name": name, "config": config, "traffic": traffic, "chips": 1,
            "why": "toy cell for a CPU rehearsal",
        })
    # the toy cells report the metrics their kind reports
    serve_cells, train_cells = names[:2], names[2:]
    for metric in bench["end_to_end"]:
        if "workloads" not in metric:
            continue
        if metric["name"] == "train_tokens_per_s":
            metric["workloads"] += train_cells
        elif metric["name"] == "serve_tokens_per_s":
            metric["workloads"] += serve_cells[:1]
        else:
            metric["workloads"] += serve_cells
    bench["per_layer"].append({
        "name": "toy_count", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "toy", "moves": "setup_s",
        "workloads": names,
    })
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh, indent=1)
    return root
