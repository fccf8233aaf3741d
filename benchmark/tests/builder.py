#!/usr/bin/env python3
"""The builder's way into the benchmark's one command, for what the
driver never asks: benchmark/run.py takes the contract's four options
and nothing else.

    python3 benchmark/tests/builder.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
        [--platform cpu]           a CPU rehearsal of the whole flow: prints no
                                   result (the object it would have printed
                                   follows the word REHEARSAL), exits 3
        [--control <name>]         the program's own lower-precision path
                                   (``launch.controls`` of the configuration)
        [--rate-rps <r>]           another rate for an open-loop mix: the knee sweep
        [--more-seeds a,b,...]     further windows, one per seed, from the same
                                   server; each judged on a ``more-seed`` line
        [--reference-controls m,.] the reference's own reading in a lower
                                   precision (bf16, int8) on the same sample
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import run  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--platform", default=run.CHIP)
    parser.add_argument("--control", default="")
    parser.add_argument("--rate-rps", type=float, default=0.0)
    parser.add_argument("--more-seeds", default="")
    parser.add_argument("--reference-controls", default="")
    args = parser.parse_args()
    return run.run_cell(
        args, platform=args.platform, control=args.control, rate_rps=args.rate_rps,
        more_seeds=[int(s) for s in args.more_seeds.split(",") if s],
        reference_controls=[m for m in args.reference_controls.split(",") if m])


if __name__ == "__main__":
    raise SystemExit(main())
