"""Driver-contract test for bench.py: one JSON line with honest fields."""

import json
import os
import subprocess
import sys

import pytest


def test_bench_measures_on_multichip_mesh(monkeypatch):
    """Round-3 verdict #8: the bench machinery must work the day >1 real
    chip appears. Runs `_measure_iteration` and `_measure_round_robin`
    in-process on the suite's 8-device virtual CPU mesh, checking the
    per-chip accounting and the multi-chip clock gating."""
    import jax

    assert jax.device_count() == 8  # the conftest virtual mesh

    import bench
    from adanet_tpu.examples.simple_cnn import CNNBuilder

    monkeypatch.setattr(bench, "WARMUP_STEPS", 1)
    monkeypatch.setattr(bench, "MEASURE_STEPS", 2)

    fused = bench._measure_iteration(
        [CNNBuilder(num_blocks=1, channels=8)], batch_size=4
    )
    # Per-chip throughput: positive, and the wall-clock-derived field is
    # reported alongside whichever clock is primary.
    assert fused["examples_per_sec_per_chip"] > 0
    assert fused["host_clock_examples_per_sec_per_chip"] > 0
    assert fused["clock"] in ("device", "host_fallback")
    if fused["clock"] == "device":
        assert fused["device_busy_examples_per_sec_per_chip"] > 0
    else:
        assert fused["device_busy_examples_per_sec_per_chip"] is None

    rr = bench._measure_round_robin(
        [
            CNNBuilder(num_blocks=1, channels=8),
            CNNBuilder(num_blocks=1, channels=12),
        ],
        batch_size=8,
    )
    assert rr["examples_per_sec_per_chip"] > 0
    # On >1 chip the submeshes run CONCURRENTLY: summed device-busy time
    # over device_count undercounts elapsed, so the primary number must
    # come from the wall clock (round-3 advisor).
    assert rr["clock"] in ("host_multichip", "host_fallback")
    assert rr["host_clock_examples_per_sec_per_chip"] > 0
    if rr["clock"] == "host_multichip":
        assert rr["examples_per_sec_per_chip"] == (
            rr["host_clock_examples_per_sec_per_chip"]
        )


@pytest.mark.slow
def test_bench_prints_one_json_line():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # bench.py enables the persistent XLA cache itself
    # (utils/compile_cache_dir.py): with the variable unset it is the
    # checkout's own topology-keyed directory, so repeat runs reuse the
    # subprocess's NASNet-A compiles, the dominant cost on CPU.
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    # NASNet steps take seconds each on CPU, and XLA:CPU needs >40 min to
    # compile the full windowed NASNet-A scan: shrink the timing loops AND
    # the NASNet model for the contract check (the TPU driver run uses
    # the full defaults).
    env["ADANET_BENCH_WARMUP_STEPS"] = "1"
    env["ADANET_BENCH_MEASURE_STEPS"] = "2"
    env["ADANET_BENCH_NASNET_CELLS"] = "3"
    env["ADANET_BENCH_NASNET_FILTERS"] = "8"
    # The replicated-fleet saturation ramp spawns replica subprocesses
    # and runs for minutes; tier-1 asserts its structured opt-out here
    # (the machinery is chaos-gated in tests/test_serving_fleet.py and
    # recorded in BENCH_serving_r02.json).
    env["ADANET_BENCH_FLEET_SERVING"] = "0"
    # The per-axis MFU-compare arms each recompile NASNet; the real
    # machinery runs in-process in test_roofline_compare_in_process and
    # this run asserts the structured opt-out.
    env["ADANET_BENCH_ROOFLINE_COMPARE"] = "0"
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")],
        cwd=repo,
        env=env,
        capture_output=True,
        text=True,
        timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, proc.stdout
    result = json.loads(lines[0])
    # Driver contract fields.
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in result, result
    # Honest-accounting fields (round-2 verdict).
    assert result["flops_model"].startswith("XLA")
    assert result["vs_baseline_note"]
    for config in ("nasnet_windowed", "nasnet", "cnn"):
        assert result[config]["examples_per_sec_per_chip"] > 0
        assert result[config]["flops_per_example"] is None or (
            result[config]["flops_per_example"] > 0
        )
        # Round-3 honesty: report which clock produced the number.
        assert result[config]["clock"] in ("device", "host_fallback")
        # Round-4: device-busy and wall-clock throughput are distinct
        # named fields; busy is None whenever the device clock failed.
        assert "device_busy_examples_per_sec_per_chip" in result[config]
        assert result[config]["host_clock_examples_per_sec_per_chip"] > 0
    # Round-4: the label is computed from the benched hyperparameters.
    assert result["nasnet_windowed"]["model_name"] == "NASNet-A (1@192)"
    # The RoundRobin executor path is benchmarked too (round-2 verdict:
    # per-submesh dispatch overhead must be measured).
    assert result["round_robin_cnn"]["examples_per_sec_per_chip"] > 0
    # The serving plane's closed-loop latency section rides the same
    # line (ISSUE 7): honest percentiles, zero 5xx-equivalents.
    assert result["serving_latency"]["p99_ms"] > 0
    assert result["serving_latency"]["error"] == 0
    # The fleet saturation section honored its structured opt-out.
    assert result["serving_fleet"] == {
        "skipped": "fleet_serving_bench_disabled_by_env"
    }
    # Warm-start accounting across runs sharing one artifact store
    # (ISSUE 10): the replayed run compiles and trains nothing.
    warm = result["warm_start"]
    assert "skipped" not in warm, warm
    assert warm["zero_compile_warm_start"] is True, warm
    assert warm["cold"]["xla_compiles"] > 0
    assert warm["shared_store_fresh"]["store_hits"] > 0
    assert warm["store"]["clean"] is True
    # Per-component roofline (ISSUE 12): step time attributed across
    # compile / input-pull / device-step / host-fetch, with an honest
    # clock label (CPU has no XLA Modules device lane -> host fallback).
    roofline = result["roofline"]
    assert "skipped" not in roofline, roofline
    for key in (
        "compile_secs",
        "input_pull_secs",
        "device_step_secs_per_step",
        "host_fetch_secs",
    ):
        assert roofline[key] >= 0, roofline
    assert roofline["compile_secs"] > 0
    assert roofline["device_step_secs_per_step"] > 0
    assert roofline["step_clock"] in ("device", "host_fallback")
    fractions = roofline["fractions"]
    assert set(fractions) == {"input_pull", "device_step", "host_fetch"}
    assert sum(fractions.values()) == pytest.approx(1.0, abs=0.01)
    # The MFU-compare section honored its structured opt-out.
    assert result["roofline_compare"] == {
        "skipped": "roofline_compare_disabled_by_env"
    }
    # A CPU run reports no MFU (there is no peak to divide by).
    for config in ("nasnet_windowed", "nasnet", "cnn"):
        assert result[config]["mfu"] is None


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform = platform
        self.device_kind = device_kind


@pytest.mark.parametrize(
    "platform,kind,want",
    [
        ("cpu", "cpu", None),
        ("tpu", "TPU v5 lite", 197e12),
        ("tpu", "TPU v99", ValueError),
    ],
    ids=["cpu_no_mfu", "known_kind", "unknown_kind_raises"],
)
def test_peak_flops_by_device_kind(monkeypatch, platform, kind, want):
    """A CPU run reports no MFU; an accelerator kind missing from the
    peaks table is an error, not a silent `None`."""
    import bench

    monkeypatch.setattr(
        bench.jax, "devices", lambda: [_FakeDevice(platform, kind)]
    )
    if want is ValueError:
        with pytest.raises(ValueError, match="TPU v99"):
            bench._peak_flops()
    else:
        assert bench._peak_flops() == want


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_timed_loop_host_clock_only_without_a_tpu(monkeypatch, platform):
    """With a TPU attached, a device clock that cannot be read raises;
    only a CPU run may report the host clock."""
    import bench
    from adanet_tpu.utils import device_timing

    def no_device_clock(*args, **kwargs):
        raise RuntimeError("no XLA Modules lane")

    monkeypatch.setattr(
        device_timing, "time_steps_on_device", no_device_clock
    )
    monkeypatch.setattr(
        bench.jax, "devices", lambda: [_FakeDevice(platform, platform)]
    )
    if platform == "tpu":
        with pytest.raises(RuntimeError, match="no XLA Modules lane"):
            bench._timed_loop(lambda state: state, 0)
    else:
        _, clock, host_elapsed, dispatches = bench._timed_loop(
            lambda state: state, 0
        )
        assert clock == "host_fallback"
        assert host_elapsed >= 0 and dispatches is None


def test_main_fails_without_a_tpu(monkeypatch):
    """No probe, no unavailable record: without a TPU (and without the
    contract test's explicit JAX_PLATFORMS=cpu) the bench exits non-zero
    before measuring anything."""
    import bench

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as exit_info:
        bench.main()
    assert "needs a TPU" in str(exit_info.value.code)


def test_roofline_compare_in_process(monkeypatch):
    """The MFU-campaign per-axis section (ISSUE 17): every arm reports
    the same roofline schema, deltas price each axis against the f32
    baseline, and the two CPU-unpriceable axes carry correctness
    verdicts (fused-cell bit-identity, autotune pure-store-hit)."""
    import bench
    from adanet_tpu.examples.simple_cnn import CNNBuilder

    monkeypatch.delenv("ADANET_BENCH_ROOFLINE_COMPARE", raising=False)
    monkeypatch.setattr(bench, "WARMUP_STEPS", 1)
    monkeypatch.setattr(bench, "MEASURE_STEPS", 2)

    result = bench._roofline_compare_section(
        lambda: [CNNBuilder(num_blocks=1, channels=8)],
        batch_size=4,
        model_name="cnn_tiny",
    )
    assert "skipped" not in result, result

    arms = result["arms"]
    assert set(arms) == {
        "baseline",
        "bf16",
        "overlap",
        "bf16_overlap",
        "fused_sepconv",
    }
    # No pallas builder was passed (and this is CPU): structured skip.
    assert arms["fused_sepconv"] == {"skipped": "fused_arm_requires_tpu"}
    for name in ("baseline", "bf16", "overlap", "bf16_overlap"):
        arm = arms[name]
        assert arm["device_step_secs_per_step"] > 0, (name, arm)
        assert arm["input_pull_secs"] >= 0, (name, arm)
    assert arms["baseline"]["step_compute_dtype"] is None
    assert arms["bf16"]["step_compute_dtype"] == "bfloat16"
    assert arms["overlap"]["overlap"] is True
    assert arms["overlap"]["step_clock"] == "host_overlap"
    assert arms["bf16_overlap"]["overlap"] is True

    deltas = result["deltas_vs_baseline"]
    assert set(deltas) == {"bf16", "overlap", "bf16_overlap"}
    for name, delta in deltas.items():
        assert delta["device_step_speedup"] > 0, (name, delta)

    # The fused-cell axis: interpret-mode kernel bit-identical to the
    # jitted unfused reference.
    oracle = result["fused_cell_oracle"]
    assert oracle["bit_identical"] is True, oracle
    assert oracle["max_abs_diff"] == 0.0

    # The autotune axis: run 1 sweeps (exit 1), run 2 is a pure store
    # hit (exit 0, zero re-searches).
    tune = result["autotune_store"]
    assert tune["first_run"]["exit_code"] == 1, tune
    assert tune["first_run"]["searched"] > 0
    assert tune["second_run"]["exit_code"] == 0, tune
    assert tune["second_run"]["searched"] == 0
    assert tune["second_run_pure_store_hit"] is True, tune
