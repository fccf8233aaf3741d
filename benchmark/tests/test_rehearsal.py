"""End-to-end rehearsals at toy size on the CPU. Each drives the
benchmark's one command in a temporary checkout to which a toy
configuration, toy mixes and a toy per-layer metric were ADDED AS
FILES (toyroot.py): supervisor, launcher, the program's own main(),
gateway, load, trace, teardown, reference, reduction, contract.

A CPU run reports no result: the exit code is not 0 and the last line
is no result object. The builder's entry (tests/builder.py
``--platform cpu``; the driver's command has no such option) prints
the object it WOULD have printed behind the word REHEARSAL, so these
tests can look at it.
"""
import hashlib
import json
import os
import subprocess
import sys

import pytest

import toyroot


def run_cell(root, workload, seed, trace, *extra, platform="cpu", seconds="4"):
    # the driver's command takes the contract's four options; anything
    # more goes through the builder's entry
    script = "benchmark/tests/builder.py" if platform or extra else "benchmark/run.py"
    argv = [sys.executable, script, "--workload", workload,
            "--seed", str(seed), "--seconds", seconds, "--trace", str(trace),
            *extra]
    if platform:
        argv += ["--platform", platform]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(argv, cwd=root, env=env, capture_output=True,
                          text=True, timeout=900)


def rehearsal(proc):
    lines = proc.stdout.strip().splitlines()
    assert proc.returncode == 3, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert lines[-1].startswith("REHEARSAL "), lines[-1]
    return json.loads(lines[-1][len("REHEARSAL "):])


def digest(root):
    out = {}
    for base, _dirs, files in os.walk(os.path.join(root, "benchmark")):
        if "__pycache__" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha1(fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toyroot.build(str(tmp_path_factory.mktemp("toy") / "checkout"))


@pytest.mark.parametrize("workload,trace", [
    ("toy-serve.toy-closed", 1), ("toy-serve.toy-open", 0),
    ("toy-train.toy-steady", 1),
])
def test_cells_added_as_files_run(root, workload, trace):
    """A configuration, a mix, a cell and a per-layer metric, each added
    as new files plus one entry: no file the benchmark had is edited."""
    theirs = digest(toyroot.REPO)
    ours = digest(root)
    assert all(ours[path] == sha for path, sha in theirs.items()
               if not path.startswith("benchmark/tests/"))
    proc = run_cell(root, workload, 3_000_000_019, trace)
    result = rehearsal(proc)
    assert result["correct"] is True and result["failed"] == 0
    # every number compared beside its limit: the result's last key, and
    # the last lines of standard error
    assert list(result)[-1] == "compared" and result["compared"]
    said = proc.stderr.strip().splitlines()[-len(result["compared"]):]
    assert [line.split()[1] for line in said] == list(result["compared"])
    assert result["device"]["platform"] == "cpu"
    if trace and workload.startswith("toy-serve"):
        assert result["path_stat"] is None  # a CPU trace has no device plane
    if trace:
        assert result["metrics"]["toy_count"]["value"] > 0
        assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
        assert "breakdown" in result
        assert not any("compiles_in_window" in name and entry["value"]
                       for name, entry in result["metrics"].items())
    else:
        assert "busy_s" not in result["device"] and "breakdown" not in result


def test_off_the_chip_there_is_no_result(root):
    """The driver's own call on a machine without a TPU: another exit
    code than 0, and nothing that reads as a result."""
    proc = run_cell(root, "toy-serve.toy-closed", 5, 0, platform="")
    assert proc.returncode not in (0, 3)
    last = proc.stdout.strip().splitlines()[-1]
    assert "metrics" not in last and "REHEARSAL" not in last
    assert "needs 'tpu'" in last


def test_bare_directory_has_no_result(tmp_path):
    """Only BENCHMARK.json and the files under paths: no program."""
    bare = toyroot.build(str(tmp_path / "bare"))
    os.remove(os.path.join(bare, "containerpilot_tpu"))
    proc = run_cell(bare, "toy-serve.toy-closed", 5, 0, platform="")
    assert proc.returncode not in (0, 3) and not proc.stdout.strip()


def test_broken_timed_path_is_not_correct(tmp_path):
    """Tokens altered where the engine hands them to the stream."""
    broken = toyroot.build(str(tmp_path / "broken"),
                           serve_launcher="benchmark/tests/broken_replica.py")
    result = rehearsal(run_cell(broken, "toy-serve.toy-closed", 7, 0))
    assert result["correct"] is False
    assert result["failed"] == 0  # every stream was well-formed: only the check sees it


@pytest.mark.parametrize("seed", [11, 12, 14])
def test_int8_weights_control_is_not_correct(root, seed):
    """The program's own lower-precision path, at toy size: the mean
    logit gap passes its limit (toy readings, PR 23, CPU: sound
    1.4e-4..4.3e-4 over four seeds, int8 weights 8.6e-4..2.3e-3)."""
    result = rehearsal(run_cell(root, "toy-serve.toy-closed", seed, 0,
                                "--control", "int8-weights"))
    assert result["correct"] is False


@pytest.mark.parametrize("fault", ["no-update", "row-left-out"])
def test_broken_train_step_is_not_correct(tmp_path, fault):
    """The compiled step broken under the launcher's observer
    (broken_trainer.py): a step that returns its state unchanged, and
    one that trains on a part of the batch. Toy readings (CPU, PR 23):
    sound runs' norm gaps 0.002-0.004 against the limit 0.02; no
    update 1.0; a row left out 0.09-0.23."""
    broken = toyroot.build(str(tmp_path / fault), train_fault=fault)
    result = rehearsal(run_cell(broken, "toy-train.toy-steady", 31, 0))
    assert result["correct"] is False
