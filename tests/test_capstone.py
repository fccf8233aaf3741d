"""The SURVEY §7 capstone: supervised multi-process training e2e.

N supervisor instances (the real CLI, real configs) each run a
training worker job. The workers rendezvous through a live catalog
server (``-catalog-server``, the supervisor's own daemon), complete a
pod run over an N-process CPU mesh — pmap data-parallel at N=2, the
production 2x2 dp x tp mesh path (parallel.train + sharded
checkpointing) at N=4 — and checkpoint every step. A fault is injected: one worker crashes mid-run; its peer's
step watchdog turns the resulting collective hang into an exit; BOTH
supervisors apply their restart budgets; the reincarnated pod
re-rendezvouses and resumes from the latest checkpoint.

Asserted: final loss parity with a single-process run of the same
global batch schedule, both workers resumed (not restarted from
scratch), and the crash was catalog-visible (the dead worker's service
left the catalog and returned). Mirrors the reference's
multi-container integration tier (scripts/test.sh:50-140).
"""
import json
import os
import socket
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "capstone_worker.py")

STEPS = 6
CRASH_STEP = 2
GLOBAL_BATCH = 8


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _sub_env() -> dict:
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # exactly 1 CPU device per process
    # pod boots across this suite recompile the same tiny-model
    # program sets; conftest exported the ONE compile cache dir
    # (JAX_COMPILATION_CACHE_DIR), which the workload CLIs'
    # enable_compile_cache honours, so every boot after the first is
    # a cache re-warm — exactly the crash->restart path the cache
    # exists for, and minutes off the suite on one core
    return env


def _wait_http(url: str, deadline_s: float = 30) -> None:
    import urllib.request

    deadline = time.monotonic() + deadline_s
    while True:
        try:
            urllib.request.urlopen(url, timeout=1)
            return
        except Exception:
            if time.monotonic() > deadline:
                raise TimeoutError(f"never reachable: {url}")
            time.sleep(0.2)


def _supervisor_config(
    tmp_path, idx: int, catalog_port: int, coord_port: int,
    job_port: int, crash_idx: int = 1, n_procs: int = 2, tp: int = 0,
) -> str:
    # ONE shared checkpoint dir for the pod (orbax is a global
    # checkpointer: primary-process writes + cross-process barriers;
    # per-process dirs would leave worker 1's empty and deadlock the
    # post-restart restore — parallel/checkpoint.py module docstring)
    ckpt = tmp_path / "ckpt"
    out = tmp_path / f"out{idx}.json"
    heartbeat = tmp_path / f"heartbeat{idx}"
    exec_argv = [
        sys.executable, WORKER,
        "--process-id", str(idx),
        "--num-processes", str(n_procs),
        "--catalog", f"127.0.0.1:{catalog_port}",
        "--coordinator-port", str(coord_port),
        "--steps", str(STEPS),
        "--global-batch", str(GLOBAL_BATCH),
        "--checkpoint-dir", str(ckpt),
        "--out", str(out),
        # the single-core box serializes n_procs compiles: scale the
        # deadlines with the pod size
        "--step-timeout", str(30 * max(1, n_procs // 2)),
        "--startup-timeout", str(120 * max(1, n_procs // 2)),
        "--heartbeat-file", str(heartbeat),
    ]
    if tp:
        exec_argv += ["--tp", str(tp)]
    if idx == crash_idx:
        exec_argv += [
            "--crash-step", str(CRASH_STEP),
            "--crash-sentinel", str(tmp_path / "crash-sentinel"),
        ]
    config = {
        "consul": f"127.0.0.1:{catalog_port}",
        "stopTimeout": "5s",
        "logging": {
            "level": "INFO", "format": "default", "output": "stdout"
        },
        "jobs": [
            {
                "name": f"trainer{idx}",
                "exec": exec_argv,
                # budget absorbs: the injected crash / watchdog exit,
                # rendezvous-race failures (more peers, more races),
                # the successful rerun, and already-complete no-ops
                "restarts": 4 + max(0, n_procs - 2),
                "port": job_port,
                "interfaces": ["static:127.0.0.1"],
                # progress-based health: passes only while the worker
                # keeps its per-step heartbeat file fresh, so a crash
                # (or a wedge) lapses the TTL and the service goes
                # catalog-critical until the reincarnation resumes
                # stepping — the reference's TTL-criticality
                # semantics, driven by real training progress
                "health": {
                    "exec": [
                        "/bin/sh", "-c",
                        f'test -f "{heartbeat}" && '
                        f'test "$(( $(date +%s) - '
                        f'$(stat -c %Y "{heartbeat}") ))" -lt 12',
                    ],
                    "interval": 1, "ttl": 5,
                },
            }
        ],
    }
    path = tmp_path / f"host{idx}.json5"
    path.write_text(json.dumps(config))
    return str(path)


# the 2-proc worker-crash case is subsumed by dp2xtp2-worker-crash
# (same crash target, superset topology) — dropped to hold the
# one-core suite budget; coordinator-crash stays 2-proc because the
# crash TARGET differs
@pytest.mark.parametrize(
    "n_procs,tp,crash_idx", [(2, 0, 0), (4, 2, 1)],
    ids=["coordinator-crash", "dp2xtp2-worker-crash"],
)
def test_supervised_multiprocess_training_with_crash_and_resume(
    tmp_path, n_procs, tp, crash_idx
):
    """crash_idx=0 kills the process HOSTING the jax coordinator —
    the harder failure: the whole rendezvous must rebuild (the
    reincarnated process 0 clears the stale coordinator registration
    and re-registers; the survivor's watchdog turns its hang into a
    restart that discovers the fresh coordinator).

    The dp2xtp2 variant runs FOUR supervised processes on a 2x2
    dp x tp mesh through the production path (parallel.train +
    sharded checkpointing), so the crash/restart/resume story covers
    cross-process tensor parallelism, not just pmap dp."""
    from containerpilot_tpu.discovery.consul import ConsulBackend

    catalog_port, coord_port = _free_port(), _free_port()
    job_ports = tuple(_free_port() for _ in range(n_procs))
    env = _sub_env()
    # the restart half of the story is exactly what the shared XLA
    # compile cache exists for: the reincarnated worker re-warms from
    # cached executables instead of recompiling the train step
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla-cache")

    catalog = subprocess.Popen(
        [sys.executable, "-m", "containerpilot_tpu",
         "-catalog-server", f"127.0.0.1:{catalog_port}"],
        cwd=REPO, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    supervisors = []
    logs = []
    timeline = []  # (monotonic_t, crashing trainer present in catalog)
    stop_poll = threading.Event()
    try:
        _wait_http(
            f"http://127.0.0.1:{catalog_port}/v1/health/service/none"
        )
        for idx in range(n_procs):
            cfg_path = _supervisor_config(
                tmp_path, idx, catalog_port, coord_port,
                job_ports[idx], crash_idx, n_procs=n_procs, tp=tp,
            )
            log_fh = open(tmp_path / f"sup{idx}.log", "w")
            logs.append(log_fh)
            supervisors.append(
                subprocess.Popen(
                    [sys.executable, "-m", "containerpilot_tpu",
                     "-config", cfg_path],
                    cwd=REPO, env=env,
                    stdout=log_fh, stderr=subprocess.STDOUT,
                )
            )

        backend = ConsulBackend(address=f"127.0.0.1:{catalog_port}")

        def poll_catalog() -> None:
            while not stop_poll.is_set():
                try:
                    present = bool(
                        backend.instances(f"trainer{crash_idx}")
                    )
                    timeline.append((time.monotonic(), present))
                except Exception:
                    pass
                stop_poll.wait(0.25)

        poller = threading.Thread(target=poll_catalog, daemon=True)
        poller.start()

        deadline = time.monotonic() + 480 * max(1, n_procs // 2)
        for proc in supervisors:
            remaining = max(5.0, deadline - time.monotonic())
            try:
                proc.wait(timeout=remaining)
            except subprocess.TimeoutExpired:
                pytest.fail(
                    "supervisor did not exit; logs:\n"
                    + "\n".join(
                        (tmp_path / f"sup{i}.log").read_text()[-3000:]
                        for i in range(n_procs)
                    )
                )
        stop_poll.set()
        poller.join(timeout=5)

        for i, proc in enumerate(supervisors):
            assert proc.returncode == 0, (
                f"supervisor {i} rc={proc.returncode}:\n"
                + (tmp_path / f"sup{i}.log").read_text()[-3000:]
            )

        # the fault actually fired
        assert (tmp_path / "crash-sentinel").exists()

        outs = []
        for idx in range(n_procs):
            out_path = tmp_path / f"out{idx}.json"
            assert out_path.exists(), (
                f"worker {idx} never finished:\n"
                + (tmp_path / f"sup{idx}.log").read_text()[-3000:]
            )
            outs.append(json.loads(out_path.read_text()))

        # every worker completed the SAME run and resumed mid-stream
        # (a from-scratch restart would report resumed_from == 0)
        for out in outs:
            assert out["resumed_from"] > 0, out
            assert out["final_loss"] == pytest.approx(
                outs[0]["final_loss"], abs=1e-5
            )

        # loss parity with a single-process run over the identical
        # global batch schedule
        base_out = tmp_path / "baseline.json"
        baseline = subprocess.run(
            [sys.executable, WORKER,
             "--process-id", "0", "--num-processes", "1",
             "--steps", str(STEPS),
             "--global-batch", str(GLOBAL_BATCH),
             "--checkpoint-dir", str(tmp_path / "ckpt-base"),
             "--out", str(base_out)]
            + (["--tp", "1"] if tp else []),  # same code path as pod
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=240,
        )
        assert baseline.returncode == 0, baseline.stderr[-2000:]
        base = json.loads(base_out.read_text())
        assert outs[0]["final_loss"] == pytest.approx(
            base["final_loss"], abs=1e-4
        )
        assert outs[0]["params_digest"] == pytest.approx(
            base["params_digest"], rel=1e-5
        )

        # the crash was catalog-visible: the crashing trainer was in
        # the passing set, fell out (stale heartbeat -> failing health
        # exec -> TTL lapse -> critical), and returned once the
        # reincarnated pod resumed stepping
        saw_present = saw_gap_after_present = saw_return = False
        for _, present in timeline:
            if present and not saw_present:
                saw_present = True
            elif saw_present and not present:
                saw_gap_after_present = True
            elif saw_gap_after_present and present:
                saw_return = True
        assert saw_present and saw_gap_after_present and saw_return, (
            f"catalog timeline never showed a restart gap: "
            f"{[(round(t, 1), p) for t, p in timeline]}"
        )
    finally:
        stop_poll.set()
        for proc in supervisors:
            if proc.poll() is None:
                proc.terminate()
        for proc in supervisors:
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()
        catalog.terminate()
        catalog.wait(timeout=10)
        for fh in logs:
            fh.close()
