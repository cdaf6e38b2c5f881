"""The command end to end on the CPU, at the toy size of `rehearsal_tiny`.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/test_run.py -q

Shows: the last line has the contract's keys; a cell, configuration,
traffic mix, factory, check or metric that is named and has no file is an
error that names the file; a cell of `BENCHMARK.json` does not run off the chip; and no
device metric is printed from a CPU run. `rehearsal_tiny` came in as files
only, which is the proof that a new cell needs no edit to `run.py`.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEVICE_METRICS = {"step_device_ms", "mfu.train", "device_idle_share"}


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )


def _result(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract_line(trace):
    proc = _run("--workload", "rehearsal_tiny", "--seed", str(2**31 + 11),
                "--seconds", "3", "--trace", str(trace))
    result = _result(proc)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    assert list(result)[-1] == "checks"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert result["device"]["platform"] == "cpu"
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"}
    if trace:
        assert {"resume_s", "final_save_s", "compiles_in_window",
                "setup_compile_s", "step_wall_ms"} <= set(result["metrics"])
        assert not DEVICE_METRICS & set(result["metrics"])
        assert "busy_s" not in result["device"]
        # At iteration step 100 the program's log line runs `ema_losses`
        # op by op: six one-op programs that no warm-up reaches (PERF.md).
        if result["attempted"] < 90:
            assert result["metrics"]["compiles_in_window"]["value"] == 0
    else:
        assert set(result["metrics"]) == {"train_examples_per_s", "setup_s"}
    tail = proc.stderr.strip().splitlines()[-len(result["checks"]):]
    assert all(line.startswith("check ") for line in tail)


def test_a_cell_of_the_benchmark_does_not_run_off_the_chip():
    proc = _run("--workload", "nasnet.t0", "--seed", "1", "--seconds", "1",
                "--trace", "0")
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    assert not proc.stdout.strip().startswith("{")


@pytest.fixture
def copied(tmp_path, monkeypatch):
    """A copy of the benchmark's data files that a test may break."""
    from benchmarks import run

    here = tmp_path / "benchmarks"
    here.mkdir()
    for kind in ("workloads", "configs", "traffic"):
        shutil.copytree(os.path.join(run.HERE, kind), here / kind)
    for kind in ("metrics", "factories", "checks"):
        (here / kind).mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    monkeypatch.setattr(run, "HERE", str(here))
    return here


@pytest.mark.parametrize("missing", [
    "workloads/rehearsal_tiny.json",
    "configs/rehearsal_nasnet_tiny.json",
    "traffic/t0_b8.json",
    "factories/improve_nas.py",
    "checks/first_steps_t0.py",
    "metrics/resume_s.py",
])
def test_a_named_file_that_is_missing_is_an_error_that_names_it(
    copied, missing
):
    from benchmarks import run

    for kind in ("metrics", "factories", "checks"):
        for name in os.listdir(os.path.join(ROOT, "benchmarks", kind)):
            if name.endswith(".py"):
                (copied / kind / name).touch()
    os.remove(copied / missing)
    with pytest.raises(SystemExit) as err:
        run.main(["--workload", "rehearsal_tiny", "--seed", "1",
                  "--seconds", "1", "--trace", "1"])
    assert missing in str(err.value)
