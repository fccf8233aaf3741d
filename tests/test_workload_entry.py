"""The workload's entry points end to end: the driver's
`__graft_entry__` (single-chip forward + eleven sharded layouts on the
virtual 8-device CPU mesh) and the train/serve CLIs as real
subprocesses under the supervisor (continuous deployment, graceful
preemption). A file of its own: these are the longest workload tests,
and the driver runs tier-1 one file per worker."""
import jax


def test_graft_entry_points():
    import __graft_entry__ as graft

    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[-1] == 256
    graft.dryrun_multichip(8)


def test_continuous_deployment_reload_serves_new_checkpoint(tmp_path):
    """The documented continuous-deployment loop
    (examples/serving-pod.json5): ONE supervisor runs a trainer
    writing checkpoints to a shared dir alongside an inference server
    that started before any checkpoint existed; when training lands,
    a control-socket reload reincarnates the server, which restores
    the new weights — scores for a fixed input change, and the
    supervisor log names the served step."""
    import json
    import os
    import signal
    import subprocess
    import sys
    import time as time_mod
    import urllib.request

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def wrapper(name, module):
        path = tmp_path / name
        path.write_text(
            "import sys\n"
            "import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            f"sys.path.insert(0, {repo!r})\n"
            f"from containerpilot_tpu.workload.{module} import main\n"
            "sys.exit(main())\n"
        )
        return str(path)

    import socket as socket_mod

    with socket_mod.socket() as s:
        s.bind(("127.0.0.1", 0))
        http_port = s.getsockname()[1]
    ck = tmp_path / "ck"
    ctl = tmp_path / "cp.socket"
    model_flags = ["--d-model", "32", "--n-layers", "1",
                   "--n-heads", "2", "--vocab", "64"]
    config = {
        "stopTimeout": "5s",
        "control": {"socket": str(ctl)},
        "logging": {"level": "INFO", "format": "default",
                    "output": "stdout"},
        "jobs": [
            {
                "name": "trainer",
                # gated on a file the TEST creates after scoring the
                # pre-training weights — deterministic ordering on a
                # box where job startup times race
                "exec": ["/bin/sh", "-c",
                         "while [ ! -f "
                         + __import__("shlex").quote(
                             str(tmp_path / "train-gate")
                         )
                         + " ]; do sleep 0.2; done; exec "
                         + __import__("shlex").join(
                             [sys.executable, "-u",
                              wrapper("train_cpu.py", "train"),
                              "--steps", "4", "--batch", "2",
                              "--seq-len", "16",
                              "--checkpoint-dir", str(ck),
                              "--checkpoint-every", "1"]
                             + model_flags
                         )],
                "restarts": "never",
            },
            {
                "name": "server",
                "exec": [sys.executable, "-u",
                         wrapper("serve_cpu.py", "serve"),
                         "--host", "127.0.0.1",
                         "--port", str(http_port),
                         "--max-len", "32",
                         "--checkpoint-dir", str(ck)] + model_flags,
                "restarts": "never",
            },
        ],
    }
    cfg_path = tmp_path / "cd.json5"
    cfg_path.write_text(json.dumps(config))
    env = dict(os.environ, PYTHONPATH=repo)
    env.pop("XLA_FLAGS", None)
    log_fh = open(tmp_path / "sup.log", "w")
    sup = subprocess.Popen(
        [sys.executable, "-m", "containerpilot_tpu",
         "-config", str(cfg_path)],
        cwd=repo, env=env, stdout=log_fh, stderr=subprocess.STDOUT,
    )

    def score():
        req = urllib.request.Request(
            f"http://127.0.0.1:{http_port}/v1/score",
            data=json.dumps({"tokens": [[1, 2, 3, 4]]}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            return json.loads(resp.read().decode())

    def wait_health(deadline_s):
        deadline = time_mod.monotonic() + deadline_s
        while True:
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{http_port}/health", timeout=2
                )
                return
            except Exception:
                assert sup.poll() is None, (
                    tmp_path / "sup.log"
                ).read_text()[-3000:]
                assert time_mod.monotonic() < deadline, (
                    tmp_path / "sup.log"
                ).read_text()[-3000:]
                time_mod.sleep(0.5)

    try:
        wait_health(300)
        before = score()  # fresh-init weights (training is gated off)
        (tmp_path / "train-gate").write_text("go")
        from containerpilot_tpu.parallel import latest_step

        deadline = time_mod.monotonic() + 300
        while (latest_step(str(ck)) or 0) < 4:
            assert time_mod.monotonic() < deadline, (
                tmp_path / "sup.log"
            ).read_text()[-3000:]
            time_mod.sleep(0.5)

        # the documented CD step: reload; the new generation's server
        # restores the freshly trained checkpoint
        from containerpilot_tpu.client import ControlClient

        ControlClient(str(ctl)).reload()
        # the OLD server keeps draining (and answering) for up to
        # stopTimeout — don't race it: wait for the NEW generation's
        # own markers (it restored the checkpoint, then bound the
        # port — which it can only do once the old one released it)
        deadline = time_mod.monotonic() + 300
        while True:
            log_text = (tmp_path / "sup.log").read_text()
            if (
                "serving checkpoint step 4" in log_text
                and log_text.count("accepting traffic") >= 2
            ):
                break
            assert sup.poll() is None, log_text[-3000:]
            assert time_mod.monotonic() < deadline, log_text[-3000:]
            time_mod.sleep(0.5)
        wait_health(300)
        after = score()
        assert after["logprobs"] != before["logprobs"], (
            "reload did not swap weights"
        )
    finally:
        if sup.poll() is None:
            sup.send_signal(signal.SIGTERM)
            try:
                sup.wait(timeout=60)
            except subprocess.TimeoutExpired:
                sup.kill()
        log_fh.close()


def test_trainer_graceful_preemption(tmp_path):
    """SIGTERM mid-run: the trainer finishes the in-flight step,
    checkpoints, exits 0; a restart resumes from that exact step —
    the TPU-maintenance / supervisor-stop path."""
    import json
    import os
    import signal
    import subprocess
    import sys
    import time as time_mod

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    wrapper = tmp_path / "train_cpu.py"
    wrapper.write_text(
        "import sys\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        f"sys.path.insert(0, {repo!r})\n"
        "from containerpilot_tpu.workload.train import main\n"
        "sys.exit(main())\n"
    )
    ckpt = tmp_path / "ckpt"
    progress = tmp_path / "progress.json"
    argv = [
        sys.executable, "-u", str(wrapper),
        "--steps", "500000", "--batch", "2", "--seq-len", "16",
        "--d-model", "32", "--n-layers", "1", "--n-heads", "2",
        "--vocab", "64",
        "--checkpoint-dir", str(ckpt), "--checkpoint-every", "100000",
        "--progress-file", str(progress),
    ]
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        argv, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
    )
    try:
        deadline = time_mod.monotonic() + 240
        while True:
            if progress.exists():
                try:
                    if json.loads(progress.read_text())["step"] >= 5:
                        break
                except (ValueError, KeyError):
                    pass
            assert time_mod.monotonic() < deadline, "trainer never progressed"
            assert proc.poll() is None, proc.stdout.read()[-2000:]
            time_mod.sleep(0.05)
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, out[-2000:]
    assert "preempted: checkpoint saved at step" in out, out[-2000:]

    from containerpilot_tpu.parallel import latest_step

    saved = latest_step(str(ckpt))
    assert saved is not None and saved >= 5
    # the preemption message names the saved step — the save cannot be
    # explained by the (100000-step) periodic cadence alone
    assert f"checkpoint saved at step {saved}" in out, out[-2000:]

    # restart resumes from exactly the preemption step and completes
    finish = subprocess.run(
        argv[:argv.index("500000")] + [str(saved + 3)]
        + argv[argv.index("500000") + 1:],
        env=env, capture_output=True, text=True, timeout=240,
    )
    assert finish.returncode == 0, finish.stdout[-2000:]
    assert f"resumed from checkpoint at step {saved}" in finish.stdout, (
        finish.stdout[-2000:]
    )
