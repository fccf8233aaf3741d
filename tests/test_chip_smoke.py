"""chip_smoke.py off the chip: it must say so, and its plumbing must hold.

Two runs, both under JAX_PLATFORMS=cpu:

- as the driver runs it (no arguments, real sizes): the probe child
  finds no TPU, the run ends there, prints `"ok": false`, exits
  non-zero — before anything is started at a 1.2B width on a CPU;
- a rehearsal at toy size with the required platform steered to the
  CPU *in the test* (module constants, no option of the program):
  every phase runs, in order, through the real supervisor, serve,
  gateway and train entry points; the output lines have their shape;
  and `"ok"` is STILL false, because on the CPU the pallas kernels are
  interpreted and the kernel checks do not hold.
"""
import json
import os
import socket
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one CPU device, like one chip
    return env


def _lines(stdout: str):
    return [json.loads(line) for line in stdout.splitlines() if line]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_no_accelerator_is_not_ok():
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO, env=_env(),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    lines = _lines(proc.stdout)
    assert [l.get("phase") for l in lines[:-1]] == ["probe", "run"]
    assert lines[0]["ok"] is False and lines[0]["platform"] == "cpu"
    assert lines[-1] == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }


REHEARSAL = """
import sys
import chip_smoke as cs

cs.PLATFORM = "cpu"
cs.OUT, cs.WORK = sys.argv[1], sys.argv[2]
cs.SERVE_PORT, cs.GATEWAY_PORT = int(sys.argv[3]), int(sys.argv[4])
cs.SERVE_MODEL = {"vocab": 512, "d_model": 64, "n_heads": 4,
                  "n_layers": 2, "max_len": 256}
cs.SERVE_ENGINE = ["--slots", "2", "--prefix-cache", "2",
                   "--kv-spill-mb", "4"]
cs.PROMPT_LEN, cs.LONG_PROMPT_LEN, cs.MAX_NEW = 24, 128, 16
cs.TRAIN_MODEL = {"vocab": 512, "d_model": 64, "n_heads": 4,
                  "n_layers": 2, "seq_len": 128, "batch": 4}
cs.LEARNING_RATE = "3e-2"
cs.CHECKPOINT_EVERY = 4
cs.SERVE_STOP_TIMEOUT, cs.TRAIN_STOP_TIMEOUT = "3s", "6s"
rc = cs.main([])
print("PARENT_IMPORTED_JAX", "jax" in sys.modules, file=sys.stderr)
sys.exit(rc)
"""


def test_rehearsal_runs_every_phase_in_order(tmp_path):
    out, work = tmp_path / "out", tmp_path / "work"
    proc = subprocess.run(
        [sys.executable, "-c", REHEARSAL, str(out), str(work),
         str(_free_port()), str(_free_port())],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=600,
    )
    lines = _lines(proc.stdout)
    by_phase = {}
    for line in lines[:-1]:
        # every phase line: a JSON object naming its phase and verdict
        assert isinstance(line["phase"], str), line
        assert isinstance(line["ok"], bool), line
        by_phase.setdefault(line["phase"], line)  # first = the data line
    assert list(by_phase) == [
        "probe", "server-tp1", "reference-tp1", "trainer", "kernels",
    ], proc.stdout + proc.stderr[-3000:]

    server = by_phase["server-tp1"]
    assert server["ok"], server
    assert server["device"]["platform"] == "cpu"
    assert server["device"]["param_devices"] == [0]
    assert server["mesh"] is None
    assert server["mux"]["connected"] and server["mux"]["streams_opened"] >= 4
    assert server["pool"] == {"idle": 0, "hits": 0, "misses": 0, "evicted": 0}
    assert server["stream_equals_buffered"] and server["relayed_422"]
    assert server["sampled_reproducible"] and server["tokens_reused"] > 0
    assert server["catalog_empty"]
    assert set(server["request_s"]) == {
        "greedy0", "greedy1", "greedy2", "long",
    }

    reference = by_phase["reference-tp1"]
    assert reference["ok"] and reference["compared"] == 4
    assert reference["failures"] == []

    trainer = by_phase["trainer"]
    for check in (
        "supervisor_exit_0", "killed_job_seen", "resumed_from_checkpoint",
        "resume_point_is_a_checkpoint", "preemption_checkpoint",
        "job_exit_0", "losses_finite", "progress_advanced",
        "no_stray_process", "platform_in_log",
    ):
        assert trainer[check] is True, (check, trainer)
    assert trainer["resumed_from"] == 4
    assert min(map(int, trainer["losses_resumed"])) == 5
    # seq 128 is below the flash crossover: the log says XLA, so the
    # chip-path check fails here, as it must off the chip
    assert trainer["flash_in_log"] is False and trainer["ok"] is False

    kernels = by_phase["kernels"]
    assert kernels["ok"] is False
    assert kernels["prefill_has_kernel"] is False  # interpreted on cpu
    assert kernels["train_step_has_kernel"] is False

    # the last line is the contract's, and carries nothing more
    assert lines[-1] == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert proc.returncode == 1
    assert "PARENT_IMPORTED_JAX False" in proc.stderr
    # whole logs of every child are kept; nothing is left running
    for name in (
        "supervisor-serve-tp1.log", "supervisor-train.log",
        "reference.log", "kernels.log", "probe.log",
    ):
        assert (out / name).stat().st_size > 0
    assert not work.exists()
