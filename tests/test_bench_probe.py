"""bench.py's launcher: device benches need the chip, and say so.

The launcher runs every workload bench in its own child process, one
at a time, and never imports jax itself (a parent that touched the
chip would hold it against its children). No chip means a non-zero
exit and no metric line; a failed child fails the run with its whole
stderr; nothing is retried and nothing re-runs on the CPU.
"""
import asyncio
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import bench  # noqa: E402


def test_no_chip_exits_nonzero_and_prints_no_metric_line():
    """`python bench.py` on a machine without a TPU (this one:
    JAX_PLATFORMS=cpu): the first device child finds the cpu platform
    and fails, the run exits non-zero, stdout carries no result."""
    proc = subprocess.run(
        [sys.executable, "bench.py"], cwd=REPO, capture_output=True,
        text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert "metric" not in proc.stdout
    assert proc.stdout.strip() == ""
    assert "needs a tpu, jax found cpu" in proc.stderr


def test_failed_child_fails_the_run_and_stops_it(monkeypatch, capsys):
    started = []

    def fake_sub(fn_name, timeout_s):
        started.append(fn_name)
        if fn_name == "gateway_overhead_bench":
            raise bench.BenchFailed(f"{fn_name}: exit 1\nboom")
        return {"ok": fn_name}

    async def fake_dispatch():
        return 1.0

    monkeypatch.setattr(bench, "_bench_subprocess", fake_sub)
    monkeypatch.setattr(bench, "dispatch_bench", fake_dispatch)
    # main() silences logging for the timed cycles, process-wide: not
    # in the test process, where every later test still needs its logs
    monkeypatch.setattr(bench.logging, "disable", lambda level: None)
    assert asyncio.run(bench.main()) == 1
    out = capsys.readouterr()
    assert out.out == ""  # no metric line, not even the host one
    assert "gateway_overhead_bench: exit 1" in out.err
    # nothing retried, nothing after the failure started
    assert started == ["host_overhead_bench", "gateway_overhead_bench"]


def test_whole_stderr_of_a_failed_child_is_kept(monkeypatch):
    """The cause of a crash is at the TOP of a jax traceback; a tail
    of the last 200 characters keeps only its footer."""
    stderr = "ROOT CAUSE line\n" + "frame\n" * 2000 + "footer\n"

    def fake_run(argv, **kwargs):
        return subprocess.CompletedProcess(argv, 1, "", stderr)

    monkeypatch.setattr(subprocess, "run", fake_run)
    try:
        bench._bench_subprocess("training_bench", 5)
    except bench.BenchFailed as exc:
        assert "training_bench: exit 1" in str(exc)
        assert stderr in str(exc)
    else:
        raise AssertionError("a failed child must raise")


def test_launcher_never_imports_jax():
    """The parent must stay off jax entirely: importing bench and
    running the launcher (with the child stubbed out) leaves jax out
    of sys.modules, so no backend can have been initialised."""
    code = (
        "import asyncio, sys, bench\n"
        "bench._bench_subprocess = lambda fn, t: {'ok': fn}\n"
        "bench.CYCLES, bench.WARMUP = 2, 1\n"
        "rc = asyncio.run(bench.main())\n"
        "assert rc == 0, rc\n"
        "assert 'jax' not in sys.modules, 'launcher imported jax'\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert '"metric": "supervisor_job_dispatch_latency_p50"' in proc.stdout


def test_each_child_starts_only_after_the_previous_exited(monkeypatch):
    """One process per chip: the launcher's children never overlap,
    run in the declared order, and each checks for the TPU before it
    runs its bench."""
    events = []
    live = []

    def fake_run(argv, **kwargs):
        code = argv[-1]
        fn = code.rsplit("bench.", 1)[1].split("(", 1)[0]
        assert not live, f"{fn} started while {live} still ran"
        assert "dev.platform == 'tpu' or sys.exit(" in code
        assert "JAX_PLATFORMS" not in str(kwargs.get("env") or {})
        live.append(fn)
        events.append(("start", fn))
        live.remove(fn)
        events.append(("exit", fn))
        return subprocess.CompletedProcess(
            argv, 0, 'BENCH_RESULT {"ran": "%s"}\n' % fn, ""
        )

    monkeypatch.setattr(subprocess, "run", fake_run)
    extras = bench.workload_benches()
    order = [fn for _name, fn, _t in bench.WORKLOAD_BENCHES]
    assert events == [
        (kind, fn) for fn in order for kind in ("start", "exit")
    ]
    assert list(extras) == [name for name, _f, _t in bench.WORKLOAD_BENCHES]
    assert extras["training"] == {"ran": "training_bench"}
