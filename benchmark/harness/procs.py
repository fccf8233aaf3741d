"""Processes and plain HTTP for the harness. Copied from chip_smoke.py
(PR 21), which the program may change later: spawn a child in its own
session with its whole output in a file, find and kill whatever a run
left alive by the run's directories on the command line, poll until.

The parent that uses this never imports jax.
"""
from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple


class RunFailed(Exception):
    """The run cannot produce a result; the message says why."""


def child_env(root: str, extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = root + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    env["PYTHONUNBUFFERED"] = "1"
    env.pop("BENCH_RUN", None)  # the driver's own; no child acts on it
    env.update(extra or {})
    return env


def spawn(
    argv: Sequence[str], log_path: str, root: str,
    extra_env: Optional[Dict[str, str]] = None,
) -> subprocess.Popen:
    """Start a child in its own session, stdout+stderr whole in a file."""
    with open(log_path, "ab") as log:
        return subprocess.Popen(
            list(argv), cwd=root, env=child_env(root, extra_env),
            stdout=log, stderr=subprocess.STDOUT, start_new_session=True,
        )


def _self_and_ancestors() -> List[int]:
    chain, pid = [], os.getpid()
    while pid > 0:
        chain.append(pid)
        try:
            with open(f"/proc/{pid}/stat") as fh:
                pid = int(fh.read().rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            break
    return chain


def tagged(tags: Sequence[str]) -> List[Tuple[int, str]]:
    """(pid, cmdline) of every live process, other than this one and
    its ancestors, whose command line names one of ``tags`` (the run's
    directories): supervisors, their jobs, our children."""
    found = []
    skip = _self_and_ancestors()
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) in skip:
            continue
        try:
            with open(f"/proc/{entry}/cmdline", "rb") as fh:
                cmd = fh.read().replace(b"\0", b" ").decode(errors="replace")
        except OSError:
            continue
        if any(tag in cmd for tag in tags):
            found.append((int(entry), cmd))
    return found


def kill_tagged(tags: Sequence[str]) -> List[str]:
    """SIGKILL whatever the run left alive and wait until it is gone;
    returns the command lines killed (empty after a clean run)."""
    killed = []
    for pid, cmd in tagged(tags):
        try:
            os.kill(pid, signal.SIGKILL)
            killed.append(cmd)
        except ProcessLookupError:
            pass
    deadline = time.monotonic() + 10
    while tagged(tags) and time.monotonic() < deadline:
        time.sleep(0.1)
    return killed


def reap(proc: Optional[subprocess.Popen]) -> None:
    """Collect a child this process started, so none stays a zombie."""
    if proc is None:
        return
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()


def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def http_call(
    port: int, method: str, path: str, body: Optional[dict] = None,
    timeout_s: float = 30.0,
) -> Tuple[int, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout_s)
    try:
        payload = json.dumps(body).encode() if body is not None else None
        conn.request(
            method, path, body=payload,
            headers={"Content-Type": "application/json"} if payload else {},
        )
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def http_json(port: int, method: str, path: str, body: Optional[dict] = None,
              timeout_s: float = 30.0) -> dict:
    status, raw = http_call(port, method, path, body, timeout_s)
    if status != 200:
        raise RunFailed(f"{method} :{port}{path} -> {status} {raw[:300]!r}")
    return json.loads(raw)


def wait_until(
    what: str, timeout_s: float, probe: Callable[[], Any],
    alive: Optional[subprocess.Popen] = None, every_s: float = 0.1,
) -> Any:
    """Poll ``probe()`` (truthy when done; may raise OSError while a
    port is closed or a file is missing) until the deadline."""
    deadline = time.monotonic() + timeout_s
    while True:
        if alive is not None and alive.poll() is not None:
            raise RunFailed(
                f"supervisor exited ({alive.returncode}) while waiting "
                f"for {what}"
            )
        try:
            value = probe()
            if value:
                return value
        except (OSError, ValueError, http.client.HTTPException):
            pass
        if time.monotonic() > deadline:
            raise RunFailed(f"timed out after {timeout_s}s waiting for {what}")
        time.sleep(every_s)


def job_died(log_path: str, names: Sequence[str]) -> Callable[[], bool]:
    """A probe for ``wait_until``: raises once the supervisor's log says
    a job of ``names`` exited with an error (restarts are off, so it
    will not come back: waiting out the timeout would only hold the
    chip)."""
    marks = [f"] {name} exited with error".encode() for name in names]
    state = {"at": 0}

    def probe() -> bool:
        with open(log_path, "rb") as fh:
            fh.seek(state["at"])
            lines = fh.read().split(b"\n")
        state["at"] += sum(len(l) + 1 for l in lines[:-1])
        for line in lines[:-1]:
            if any(mark in line for mark in marks):
                raise RunFailed("the supervisor's log: " +
                                line.decode(errors="replace").strip()[-300:])
        return False

    return probe


def read_json(path: str) -> Any:
    with open(path) as fh:
        return json.load(fh)


def health_exec(port: int) -> List[str]:
    return [
        sys.executable, "-c",
        "import sys, urllib.request; "
        f"urllib.request.urlopen('http://127.0.0.1:{port}/health', timeout=5)",
    ]


def command(control_dir: str, name: str, arg: str = "",
            timeout_s: float = 120.0) -> dict:
    """Send one command to the launcher's control thread and return
    its answer (see benchmark/launch/common.py)."""
    done = os.path.join(control_dir, f"{name}.done")
    if os.path.exists(done):
        os.remove(done)
    tmp = os.path.join(control_dir, f"{name}.tmp")
    with open(tmp, "w") as fh:
        fh.write(arg)
    os.replace(tmp, os.path.join(control_dir, name))
    answer = wait_until(
        f"the launcher to answer {name!r}", timeout_s,
        lambda: read_json(done), every_s=0.01,
    )
    if "error" in answer:
        raise RunFailed(f"launcher command {name!r}: {answer['error']}")
    return answer


def control_args(launch: Dict[str, Any], control: str) -> List[str]:
    """The flags of a configuration's named lower-precision control
    (``launch.controls``); none when no control is asked for."""
    if not control:
        return []
    flags = list(launch.get("controls", {}).get(control, []))
    if not flags:
        raise RunFailed(f"configuration has no control {control!r}")
    return flags


def trace_window(control_dir: str, trace_dir: str, marks: Dict[str, Any],
                 after_s: float, length_s: float) -> threading.Thread:
    """A thread that starts the profiler in the supervised process
    ``after_s`` from now and stops it ``length_s`` later, leaving the
    launcher's answers (or an ``error``) in ``marks``."""
    def work() -> None:
        try:
            time.sleep(after_s)
            marks["start"] = command(control_dir, "trace-start", trace_dir)
            time.sleep(length_s)
            marks["stop"] = command(control_dir, "trace-stop", timeout_s=300)
        except (RunFailed, OSError) as exc:
            marks["error"] = str(exc)

    thread = threading.Thread(target=work, name="trace-window", daemon=True)
    thread.start()
    return thread


def join_trace(thread: Optional[threading.Thread], marks: Dict[str, Any]) -> None:
    if thread is None:
        return
    thread.join(timeout=400)
    if "error" in marks or "stop" not in marks:
        raise RunFailed(f"trace window: {marks.get('error', 'no answer')}")


class StallClock:
    """A thread that sleeps 10 ms at a time and keeps the longest it
    overslept: when the whole machine stops (PR 23 saw one 11 s stall
    in 24 runs: every process of the run, the gateway's 5 s log line
    included, stood still), the number says so on the window's line.
    A diagnosis only; no metric reads it."""

    def __init__(self) -> None:
        self.worst = 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._tick, daemon=True)
        self._thread.start()

    def _tick(self) -> None:
        last = time.monotonic()
        while not self._stop.wait(0.01):
            now = time.monotonic()
            self.worst = max(self.worst, now - last - 0.01)
            last = now

    def stop(self) -> float:
        self._stop.set()
        self._thread.join()
        return self.worst


def stop_supervisor(sup: subprocess.Popen, timeout_s: float, what: str) -> int:
    sup.send_signal(signal.SIGTERM)
    try:
        return sup.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        raise RunFailed(f"{what} still running {timeout_s}s after SIGTERM") from None
