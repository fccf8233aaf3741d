"""The training launcher with the timed path broken underneath, for
test_rehearsal.py. The fault named by ``launch.test_fault`` in the
(toy) configuration is planted in the step ``make_train_step``
returns, UNDER the launcher's observer:

- ``no-update``: the step returns its state unchanged (the loss is
  still computed);
- ``row-left-out``: the step trains on the first row alone (every row
  of the batch is replaced by it).

Either run must come out ``correct: false``."""
from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "launch"))

import common  # noqa: E402


def main() -> int:
    config, args = common.prepare(sys.argv[1:])
    import jax
    import jax.numpy as jnp

    from containerpilot_tpu import parallel
    from containerpilot_tpu.workload import modelcfg, train

    common.override_d_ff(config, [modelcfg])
    fault = config["launch"]["test_fault"]
    sound = parallel.make_train_step

    def make_broken(*a, **kw):
        step = sound(*a, **kw)

        def broken(state, tokens):
            if fault == "row-left-out":
                return step(state, jnp.broadcast_to(tokens[:1], tokens.shape))
            kept = jax.tree.map(jnp.copy, state)  # the step donates its state
            _moved, loss = step(state, tokens)
            return kept, loss

        return broken

    parallel.make_train_step = make_broken
    common.observe_train_steps(
        parallel, sys.argv[2], int(config["check"]["follow_steps"]))
    sys.argv = ["containerpilot_tpu.workload.train", *args]
    return train.main()


if __name__ == "__main__":
    raise SystemExit(main())
