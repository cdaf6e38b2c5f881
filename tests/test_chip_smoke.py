"""`chip_smoke.py`'s rehearsal option, end to end on the CPU at toy size.

The smoke is the driver's proof that the system starts on the chip; what
can be checked without one is that the script itself is sound: every
phase runs and reports, the last line names the device it REALLY ran on,
and a failed phase or a missing TPU is a non-zero exit with no result
line — never a pass.

Each case is one subprocess (the script owns its process's JAX: device
count and platform are set before the backend exists).
"""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args, **env_overrides):
    env = dict(os.environ)
    env.pop("ADANET_FAULTS", None)
    env.update(env_overrides)
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py"), *args],
        cwd=_REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=1200,
    )
    lines = [
        json.loads(line)
        for line in proc.stdout.splitlines()
        if line.startswith("{")
    ]
    return proc, lines


def _phases(lines):
    return {line["phase"]: line for line in lines if "phase" in line}


def test_rehearsal_runs_every_one_chip_phase():
    proc, lines = _run("--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    # Truthful device: the CPU it ran on, never "tpu".
    assert lines[-1] == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == lines[-1]
    phases = _phases(lines)
    assert list(phases) == ["setup", "kernels", "search", "serve"]
    for name in ("kernels", "search", "serve"):
        assert phases[name]["ok"] is True, phases[name]
        assert phases[name]["seconds"] > 0
        assert phases[name]["compile_seconds"] > 0
    setup = phases["setup"]
    assert setup["rehearsal"] is True
    assert setup["augment"] in ("native", "numpy")
    assert setup["compile_cache_dir"]

    kernels = phases["kernels"]
    assert kernels["interpret"] is True  # Pallas interpreted on the CPU
    assert {"sepconv", "cell", "combine"} <= set(kernels)
    assert "cache_hit" in kernels["second_identical_compile"]

    search = phases["search"]
    assert search["num_cells"] == 3 and search["batch"] == 16
    assert search["train_steps"] == 16
    assert [m["iteration_number"] for m in search["members"]] == [0, 1]
    assert search["state_platforms"] == ["cpu"]
    assert search["fsck_ok"] is True
    assert search["best"]["0"].startswith("t0_")
    assert search["best"]["1"].startswith("t1_")
    # The Estimator's default publication: generation 1 has a cascade.
    assert search["cascade"]["program"] and search["cascade"]["threshold"]

    serve = phases["serve"]
    assert serve["bit_identical"] is True
    assert serve["statuses"] == {"ok": len(serve["requests"])}
    assert len({r["bucket"] for r in serve["requests"]}) >= 2
    # ...and the default batcher answered through it.
    cascade = serve["cascade"]
    assert cascade["statuses"] == {"ok": len(cascade["levels"])}
    assert cascade["levels"] and set(cascade["levels"]) <= {0, 1}
    assert cascade["rollback"] is None


def test_rehearsal_of_the_four_chip_phase_on_four_virtual_devices():
    proc, lines = _run("--rehearse", "--chips", "4")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert lines[-1] == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 4},
    }
    phases = _phases(lines)
    # With the option: that path and what it is compared with, only.
    assert list(phases) == ["setup", "placement"]
    placement = phases["placement"]
    assert placement["ok"] is True, placement
    held = placement["devices_holding_state"]["round_robin"]["subnetworks"]
    chips = list(held.values())
    assert len(chips) == 2 and not set(chips[0]) & set(chips[1])
    assert (
        placement["subnetwork_loss_max_rel_diff"]
        <= placement["subnetwork_loss_rtol"]
    )


def test_a_failed_phase_is_a_nonzero_exit_without_a_result_line():
    # A non-transient fault at the first training-batch pull: `search`
    # fails before it compiles anything, `serve` then finds no model.
    proc, lines = _run("--rehearse", ADANET_FAULTS="data.pull:error")
    assert proc.returncode != 0
    assert not any(line.get("ok") and "device" in line for line in lines)
    phases = _phases(lines)
    assert phases["kernels"]["ok"] is True
    assert phases["search"]["ok"] is False
    assert "InjectedFault" in phases["search"]["error"]
    assert phases["serve"]["ok"] is False
    assert "search, serve" in proc.stderr


@pytest.mark.parametrize("args", [(), ("--chips", "4")], ids=["1", "4"])
def test_without_a_tpu_and_without_the_option_it_fails(args):
    proc, lines = _run(*args, JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert lines == []  # no result, no phase line
    assert "needs a TPU" in proc.stderr
