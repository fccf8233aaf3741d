"""The serving replica as the supervisor's job execs it for the
benchmark: ``python benchmark/launch/replica.py <config.json>
<control-dir> -- <serve flags>``. See common.py for what is installed;
then the program's own ``serve`` main() runs with the flags."""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import common  # noqa: E402


def main() -> int:
    config, args = common.prepare(sys.argv[1:])
    from containerpilot_tpu.workload import modelcfg, serve_cli

    common.override_d_ff(config, [modelcfg, serve_cli])
    sys.argv = ["containerpilot_tpu.workload.serve", *args]
    return serve_cli.main()


if __name__ == "__main__":
    raise SystemExit(main())
