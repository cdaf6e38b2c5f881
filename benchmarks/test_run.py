"""The command end to end on the CPU, at the toy size of `rehearsal_tiny`.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/test_run.py -q

Shows: the last line has the contract's keys; a cell, configuration,
traffic mix, factory, check or metric that is named and has no file is an
error that names the file; a cell of `BENCHMARK.json` does not run off the chip; and no
device metric is printed from a CPU run. `rehearsal_tiny` came in as files
only, which is the proof that a new cell needs no edit to `run.py`. The
window is a count of steps: it ends at the traffic file's `window_steps`
whatever the seed, a traffic file without one (or that traces past it) is
an error that names the file, the guard that `--seconds` arms makes a run
not correct, and a traced run on the chip whose trace holds no device
plane prints no result.
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
STUBBED = ("metrics", "factories", "checks", "feeds")
WINDOW_SPANS = {"resume.fsck", "checkpoint.restore", "train_window.first",
                "checkpoint.fetch", "checkpoint.write", "window_s"}


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
        # The window's last step is iteration step 100, whose log line
        # runs `ema_losses` op by op: six one-op programs that no warm-up
        # reaches, and nothing else (PERF.md, section 3).
        assert result["metrics"]["compiles_in_window"]["value"] <= 6
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
    for kind in STUBBED:
        (here / kind).mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    monkeypatch.setattr(run, "HERE", str(here))
    return here


@pytest.mark.parametrize("missing", [
    "workloads/rehearsal_tiny.json",
    "configs/rehearsal_nasnet_tiny.json",
    "traffic/t0_b8.json",
    "feeds/images.py",
    "factories/improve_nas.py",
    "checks/first_steps_t0.py",
    "metrics/resume_s.py",
])
def test_a_named_file_that_is_missing_is_an_error_that_names_it(
    copied, missing
):
    from benchmarks import run

    for kind in STUBBED:
        for name in os.listdir(os.path.join(ROOT, "benchmarks", kind)):
            if name.endswith(".py"):
                (copied / kind / name).touch()
    os.remove(copied / missing)
    with pytest.raises(SystemExit) as err:
        run.main(["--workload", "rehearsal_tiny", "--seed", "1",
                  "--seconds", "1", "--trace", "1"])
    assert missing in str(err.value)


def _lines(proc):
    return [
        json.loads(line) for line in proc.stdout.splitlines()
        if line.startswith("{")
    ]


def test_the_window_ends_at_window_steps_whatever_the_seed():
    from benchmarks import run

    steps = run.Cell("rehearsal_tiny").traffic["window_steps"]
    attempted = []
    for seed in (5, 2**31 + 6):
        proc = _run("--workload", "rehearsal_tiny", "--seed", str(seed),
                    "--seconds", "3", "--trace", "0")
        result = _result(proc)
        assert result["correct"] is True
        attempted.append(result["attempted"])
        # The spans of the window, one line before the result.
        before = _lines(proc)[-2]
        assert set(before) == {"window_spans"}
        assert WINDOW_SPANS <= set(before["window_spans"])
        assert all(
            before["window_spans"][name] > 0 for name in WINDOW_SPANS
        )
        assert _lines(proc)[-3]["model_dir_bytes"] > 0
    assert attempted == [steps, steps]


class _Timer:
    """`threading.Timer`'s signature on a clock of the test's own."""

    def __init__(self, interval, function):
        self.interval, self.function = interval, function
        self.started = self.cancelled = False

    def start(self):
        self.started = True

    def cancel(self):
        self.cancelled = True


@pytest.mark.parametrize("seconds, limit", [(3, 120.0), (30, 120.0),
                                            (45, 180.0)])
def test_the_guard_waits_four_times_the_seconds(seconds, limit):
    from benchmarks import run

    guard = run.Guard(seconds, lambda: None, timer=_Timer)
    assert guard.limit_s == guard._timer.interval == limit


def test_a_window_that_ends_itself_is_not_the_guards():
    from benchmarks import run

    stops = []
    guard = run.Guard(30, lambda: stops.append(1), timer=_Timer)
    guard.start()
    guard.cancel()
    assert guard._timer.started and guard._timer.cancelled
    assert not stops and guard.failure(93, 93) is None


def test_a_window_that_the_guard_ends_is_not_correct():
    from benchmarks import run

    stops = []
    guard = run.Guard(30, lambda: stops.append(1), timer=_Timer)
    guard.start()
    guard._timer.function()  # the clock runs out
    assert stops == [1]
    failure = guard.failure(17, 93)
    assert "guard" in failure and "pull 17 of 93" in failure
    # `main` puts it under `failure`, and a run with one is not correct.
    assert "120 s" in failure


@pytest.mark.parametrize("change, said", [
    (lambda t: t.pop("window_steps"), "states no window_steps"),
    (lambda t: t.update(window_steps=t["trace_skip_pulls"]
                        + t["trace_wall_steps"] + t["trace_steps"] - 1),
     "past its window_steps"),
])
def test_a_traffic_file_with_no_window_in_steps_is_an_error_that_names_it(
    copied, change, said
):
    from benchmarks import run

    path = copied / "traffic" / "t0_b8.json"
    traffic = json.loads(path.read_text())
    change(traffic)
    path.write_text(json.dumps(traffic))
    with pytest.raises(SystemExit) as err:
        run.main(["--workload", "rehearsal_tiny", "--seed", "1",
                  "--seconds", "1", "--trace", "1"])
    assert "traffic/t0_b8.json" in str(err.value)
    assert said in str(err.value)


def test_the_traced_pulls_may_end_with_the_window(copied):
    from benchmarks import run

    traffic = json.loads((copied / "traffic" / "t0_b8.json").read_text())
    traffic["window_steps"] = (
        traffic["trace_skip_pulls"] + traffic["trace_wall_steps"]
        + traffic["trace_steps"]
    )
    run.check_window(traffic, "traffic/t0_b8.json")


def test_a_traced_run_on_the_chip_with_no_device_plane_prints_no_result(
    tmp_path,
):
    from benchmarks import run, trace_reduce

    (tmp_path / "plugins").mkdir()
    (tmp_path / "plugins" / "host.trace").write_text("")
    trace = trace_reduce.reduce_dir(str(tmp_path))
    assert trace["device_planes"] == []
    clock = {"pulls": 9, "wall_start": 1.0}
    said = run.empty_trace_refusal("tpu", trace, str(tmp_path), clock)
    assert str(tmp_path) in said and '"pulls": 9' in said
    assert "plugins/host.trace" in said
    # Off the chip a rehearsal prints as it did; with a plane, so does a
    # chip run.
    assert run.empty_trace_refusal("cpu", trace, str(tmp_path), clock) is None
    assert run.empty_trace_refusal(
        "tpu", {"device_planes": ["/device:TPU:0"]}, str(tmp_path), clock
    ) is None
