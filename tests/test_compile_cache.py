"""The persistent compile cache is placed from outside, in ONE place.

`workload/modelcfg.enable_compile_cache` is the one function serve,
serve_dist, train and evaluate call: where JAX_COMPILATION_CACHE_DIR
is set the cache lives there and no code path sets another (not a
repo variable of its own, not a peer's advertised directory, not the
server's constructor); unset, it is the checkout's fixed, git-ignored
`.compile_cache/`, wherever the process was launched from. The
warm-bucket marker and the `cc=` heartbeat note follow the directory
in force.
"""
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
TINY = [
    "--d-model", "32", "--n-layers", "1", "--n-heads", "2",
    "--vocab", "64",
]


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_placed_cache_dir_is_the_only_one(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: train start-up leaves jax's
    cache dir at that value, and a serve replica joining a fleet whose
    peer advertises ANOTHER directory (the retired adoption route)
    still caches, marks and advertises in the placed one."""
    from containerpilot_tpu.discovery import FileCatalogBackend
    from containerpilot_tpu.discovery.backend import ServiceRegistration
    from containerpilot_tpu.fleet import notes

    placed, other = tmp_path / "placed", tmp_path / "peer-cache"
    other.mkdir()
    env = _env(JAX_COMPILATION_CACHE_DIR=str(placed))

    train = subprocess.run(
        [sys.executable, "-c",
         "import sys, jax\n"
         "from containerpilot_tpu.workload import train\n"
         "sys.argv = ['train', '--steps', '1', '--batch', '2', "
         "'--seq-len', '16'] + sys.argv[1:]\n"
         "assert train.main() == 0\n"
         "print('CACHE_DIR', jax.config.jax_compilation_cache_dir)\n",
         *TINY],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=240,
    )
    assert train.returncode == 0, train.stderr[-2000:]
    assert f"CACHE_DIR {placed}" in train.stdout
    assert any(placed.iterdir()), "train compiled nothing into the placed dir"

    # a warm same-host peer advertising its own cache dir on cc=
    catalog = tmp_path / "catalog"
    backend = FileCatalogBackend(str(catalog))
    backend.service_register(
        ServiceRegistration(
            id="peer-1", name="inference", address="127.0.0.1",
            port=1, ttl=60, tags=[],
        ),
        status="passing",
    )
    backend.update_ttl(
        "service:peer-1",
        "ok cc=" + notes.encode_compile_cache("beef", str(other)),
        "pass",
    )
    port = _free_port()
    serve = subprocess.Popen(
        [sys.executable, "-m", "containerpilot_tpu.workload.serve",
         "--host", "127.0.0.1", "--port", str(port), "--max-len", "32",
         *TINY, "--fleet-catalog", f"file:{catalog}",
         "--fleet-id", "replica-1", "--fleet-ttl", "5"],
        env=env, cwd=tmp_path, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
    )
    try:
        advertised = ""
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline and not advertised:
            assert serve.poll() is None, serve.stdout.read()[-2000:]
            for inst in backend.instances("inference"):
                if inst.id == "replica-1":
                    fields = notes.split_note(inst.notes)
                    _digest, advertised = notes.parse_field(
                        "cc", fields.get("cc", "")
                    )
            time.sleep(0.2)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=5
        ) as resp:
            assert resp.status == 200
    finally:
        serve.send_signal(signal.SIGTERM)
        out, _ = serve.communicate(timeout=60)
    assert advertised == str(placed), out[-2000:]
    assert (placed / "cp_warm_buckets.json").exists()
    assert not any(other.iterdir()), "the peer's directory was adopted"
    assert "adopted fleet compile cache" not in out


def test_unset_resolves_one_fixed_dir_in_the_checkout(tmp_path):
    """JAX_COMPILATION_CACHE_DIR unset: two launches from different
    working directories resolve the same path, inside the checkout,
    and .gitignore lists it."""
    code = (
        "import json, jax\n"
        "from containerpilot_tpu.workload.modelcfg import "
        "enable_compile_cache\n"
        "print(json.dumps([enable_compile_cache(), "
        "jax.config.jax_compilation_cache_dir]))\n"
    )
    resolved = []
    for name in ("a", "b"):
        cwd = tmp_path / name
        cwd.mkdir()
        proc = subprocess.run(
            [sys.executable, "-c", code], env=_env(), cwd=cwd,
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        resolved.append(json.loads(proc.stdout.splitlines()[-1]))
    fixed = str(REPO / ".compile_cache")
    assert resolved == [[fixed, fixed], [fixed, fixed]]
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".compile_cache/" in ignored


def test_compile_cache_env_populates_and_reuses(tmp_path):
    """JAX_COMPILATION_CACHE_DIR places the compile cache from
    outside: a workload CLI run persists its compiled programs THERE
    (and nowhere else) and a second process reuses the dir without
    error — the reincarnation-warmup lever the supervisor's restart
    story leans on."""
    cache = tmp_path / "xla-cache"
    argv = [
        sys.executable, "-u", "-m", "containerpilot_tpu.workload.train",
        "--steps", "2", "--batch", "2", "--seq-len", "16", *TINY,
    ]
    env = _env(JAX_COMPILATION_CACHE_DIR=str(cache))
    env.pop("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", None)
    first = subprocess.run(
        argv, env=env, cwd=str(tmp_path), capture_output=True,
        text=True, timeout=240,
    )
    assert first.returncode == 0, first.stdout[-2000:] + first.stderr[-2000:]
    entries = list(cache.iterdir())
    assert entries, "compile cache never populated"
    # second process must HIT the persisted entries, not just write new
    env["JAX_EXPLAIN_CACHE_MISSES"] = "true"
    before = {e.name for e in entries}
    second = subprocess.run(
        argv, env=env, cwd=str(tmp_path), capture_output=True,
        text=True, timeout=240,
    )
    assert second.returncode == 0, second.stderr[-2000:]
    after = {e.name for e in cache.iterdir()}
    assert before <= after  # nothing evicted; hits don't rewrite
