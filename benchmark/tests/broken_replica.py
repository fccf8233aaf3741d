"""The serving launcher with the timed path broken underneath, for
test_rehearsal.py: every token the slot engine hands to a stream after
the first is altered where the host receives it (+1 mod vocab). The
run must come out ``correct: false``."""
from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "launch"))

import common  # noqa: E402


def main() -> int:
    config, args = common.prepare(sys.argv[1:])
    from containerpilot_tpu.workload import modelcfg, serve_cli, serve_slots

    common.override_d_ff(config, [modelcfg, serve_cli])
    vocab = int(config["vocab_size"])
    sound = serve_slots.append_chunk

    def altered(emitted, toks, max_new, eos_id):
        return sound(emitted, [(int(t) + 1) % vocab for t in toks], max_new, eos_id)

    serve_slots.append_chunk = altered
    sys.argv = ["containerpilot_tpu.workload.serve", *args]
    return serve_cli.main()


if __name__ == "__main__":
    raise SystemExit(main())
