"""The trainer as the supervisor's job execs it for the benchmark:
``python benchmark/launch/trainer.py <config.json> <control-dir> --
<train flags>``. See common.py for what is installed; then the
program's own ``train`` main() runs with the flags."""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import common  # noqa: E402


def main() -> int:
    config, args = common.prepare(sys.argv[1:])
    from containerpilot_tpu import parallel
    from containerpilot_tpu.workload import modelcfg, train

    # train.main() imports both names when it runs
    common.override_d_ff(config, [modelcfg])
    common.observe_train_steps(
        parallel, sys.argv[2], int(config["check"]["follow_steps"]))
    sys.argv = ["containerpilot_tpu.workload.train", *args]
    return train.main()


if __name__ == "__main__":
    raise SystemExit(main())
