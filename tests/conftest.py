"""Shared test configuration.

Supervisor tests are pure-host and need no accelerator. Workload tests
exercise multi-chip sharding on a virtual 8-device CPU mesh, so the JAX
platform must be pinned *before* jax is first imported anywhere.
"""
import asyncio
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from containerpilot_tpu.workload.modelcfg import enable_compile_cache

# persistent XLA compile cache for the in-process JAX tier: the
# workload modules re-compile the same tiny-model programs on every
# suite run, which dominates wall time on this one-core box. It is
# the ONE directory the workload CLIs resolve themselves
# (modelcfg.enable_compile_cache: JAX_COMPILATION_CACHE_DIR when set
# from outside, else the checkout's fixed .compile_cache/), exported
# so every child a test starts — CLI or bare library wrapper — lands
# in the same cache and one suite run warms them all.
COMPILE_CACHE_DIR = enable_compile_cache()
os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE_DIR
os.environ.setdefault(
    "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5"
)

import logging

import pytest

# ---------------------------------------------------------------------------
# Test tiers. The supervisor tier (all host-side packages: events, jobs,
# watches, config, control, discovery, telemetry, core, CLI) runs in
# ~2 minutes; the workload tier (models/ops/parallel on the virtual
# 8-device CPU mesh) dominates the full suite's wall time. Mirrors the
# reference's unit/integration split (its makefile runs
# scripts/unit_test.sh separately):
#     pytest -m supervisor      # fast tier (make test-fast)
#     pytest -m workload        # JAX tier
#     pytest                    # everything (make test)
# ---------------------------------------------------------------------------

_WORKLOAD_MODULES = {
    "test_workload", "test_workload_entry", "test_pipeline",
    "test_window", "test_data", "test_flops",
    "test_capstone", "test_tuning", "test_slots",
    "test_serve_dist", "test_fleet", "test_chaos", "test_kvtier",
    "test_goodput", "test_compile_cache", "test_tpu_compile",
    "test_chip_smoke",
}
_WORKLOAD_TESTS = {"test_fuzz_sample_logits_invariants"}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "supervisor: host-side supervisor tier (fast, no JAX)"
    )
    config.addinivalue_line(
        "markers", "workload: JAX models/ops/parallel tier (slow)"
    )
    config.addinivalue_line(
        "markers",
        "slow: long-running scenarios excluded from tier-1 "
        "(`pytest -m 'not slow'`); `make chaos` runs them",
    )


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = item.module.__name__.rpartition(".")[2]
        if mod in _WORKLOAD_MODULES or (
            item.originalname or item.name
        ) in _WORKLOAD_TESTS:
            item.add_marker(pytest.mark.workload)
        else:
            item.add_marker(pytest.mark.supervisor)


@pytest.fixture(autouse=True)
def restore_containerpilot_logger():
    """LogConfig.init() mutates the shared 'containerpilot' logger
    (handlers, level, propagate); snapshot/restore per test so App
    tests can't break caplog-based tests elsewhere."""
    logger = logging.getLogger("containerpilot")
    saved = (list(logger.handlers), logger.level, logger.propagate)
    yield
    logger.handlers, logger.level, logger.propagate = (
        saved[0],
        saved[1],
        saved[2],
    )


@pytest.fixture
def run():
    """Run a coroutine to completion on a fresh event loop."""

    def _run(coro, timeout=30.0):
        return asyncio.run(asyncio.wait_for(coro, timeout=timeout))

    return _run


class SpillGate:
    """Stands in the way of the spill tier's ``kv-spill`` worker at
    ``jax.device_get`` (every other thread's calls pass): shut until
    ``open()``, so "a row is in flight" is a state a test HOLDS, not a
    race it hopes to win. Every wait has a timeout."""

    WAIT = 60.0

    def __init__(self, monkeypatch) -> None:
        import threading

        self.opened = threading.Event()
        self.reached = threading.Event()  # the worker is at a copy
        self.fail = 0  # this many copies raise once let through
        real = jax.device_get

        def gated(tree):
            if threading.current_thread().name != "kv-spill":
                return real(tree)
            self.reached.set()
            assert self.opened.wait(self.WAIT), "the gate was never opened"
            if self.fail:
                self.fail -= 1
                raise RuntimeError("the copy to the host failed")
            return real(tree)

        monkeypatch.setattr(jax, "device_get", gated)

    def open(self) -> None:
        self.opened.set()


@pytest.fixture
def spill_gate(monkeypatch):
    gate = SpillGate(monkeypatch)
    yield gate
    gate.open()  # never leave a worker behind a shut gate


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Drop compiled executables when a test module finishes.

    The suite runs ~380 tests in ONE interpreter; by the tail of the
    session the process holds hundreds of live XLA executables and
    the CPU compiler starts degrading — observed as multi-minute
    compile stalls and, twice, a segfault inside
    backend_compile_and_load ~50 minutes in (the crashing test passes
    alone). Per-module cache clearing bounds that accumulation; the
    cross-module recompile cost is small because modules share almost
    no shapes."""
    yield
    jax.clear_caches()
