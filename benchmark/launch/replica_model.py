"""The serving replica of a configuration that is read from a file, as
the supervisor's job execs it for the benchmark: ``python
benchmark/launch/replica_model.py <config.json> <control-dir> --
<serve flags>``. See common.py for what is installed (the control
thread, the compile counter, the device facts); then the program's own
``serve`` main() runs with the flags, ``--model-config <file>`` among
them: the model is built from the file's published keys, nothing of
the program is overridden.

A program that does not know ``--model-config`` (any before PR 27) ends
here at once with argparse's exit code 2, which the supervisor logs as
the job's exit with an error: the harness sees that line and ends the
run instead of waiting for a warm /health."""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import common  # noqa: E402


def main() -> int:
    _config, args = common.prepare(sys.argv[1:])
    from containerpilot_tpu.workload import serve_cli

    sys.argv = ["containerpilot_tpu.workload.serve", *args]
    return serve_cli.main()


if __name__ == "__main__":
    raise SystemExit(main())
