"""The language-model cell's files end to end on the CPU, at the toy size
of `rehearsal_lm_tiny` (data files only: `configs/rehearsal_moe_lm_tiny`,
`traffic/t0_b2_s64`; in no list of `BENCHMARK.json`): the `moe_lm`
factory, the `token_ids` feed, the `lm_first_steps` check with its reference
and the `lm_reduce` readers, traced and untraced, in `test_run.py`'s way.
And the readers' arithmetic on rows made here."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import count_lm_flops, lm_reduce, run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LM_METRICS = {"step.attention_ms", "step.moe_route_ms", "step.moe_experts_ms",
              "step.lm_loss_ms", "roofline.moe_experts",
              "roofline.attention_core"}


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )


@pytest.mark.parametrize("trace", [0, 1])
def test_lm_rehearsal_prints_the_contract_line(trace):
    proc = _run("--workload", "rehearsal_lm_tiny", "--seed",
                str(2**31 + 34), "--seconds", "3", "--trace", str(trace))
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    steps = run.Cell("rehearsal_lm_tiny").traffic["window_steps"]
    assert result["attempted"] == steps
    assert result["device"]["platform"] == "cpu"
    checks = result["checks"]
    assert {"gradient_median", "gradient", "same_expert", "steps",
            "unsound_steps"} <= set(checks)
    assert all(value <= limit for value, limit in checks.values())
    # Read for whoever reads a roofline, never compared.
    assert "load_max_over_mean" not in checks
    assert "read load_max_over_mean" in proc.stderr
    assert "read held_pairs_per_token" in proc.stderr
    if trace:
        # No device plane off the chip: the readers find nothing and the
        # line leaves their metrics out.
        assert not LM_METRICS & set(result["metrics"])
        assert {"resume_s", "stop.write_s", "resume.restore_s"} <= set(
            result["metrics"]
        )
    else:
        assert set(result["metrics"]) == {"train_examples_per_s", "setup_s"}


def test_the_cell_of_the_benchmark_names_its_files():
    cell = run.Cell("mellum2.t0_s8192")
    (name,) = cell.cell["training"]
    sizes = cell.members[name]["sizes"]
    traffic = cell.traffic
    assert cell.flop_per_step() == traffic["batch"] * 3 * (
        count_lm_flops.forward_flops(
            sizes, cell.config["sizes"]["vocab_size"], traffic["seq"]
        )
    )
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [c for c in manifest["configs"]
                if c["name"] == cell.cell["config"]]
    assert entry["reduced"] == cell.config["reduced"]
    assert entry["source"] == cell.config["source"]
    for key in entry["reduced"]:
        assert cell.config[key] < cell.config["published"][key]
    listed = {m["name"] for m in manifest["per_layer"]
              if "mellum2.t0_s8192" in m.get("workloads", [])}
    assert listed == LM_METRICS


@pytest.mark.parametrize("path,scope", [
    (["candidate.m", "MoeLm", "layer_0", "lm.attention", "q", "dot"],
     "lm.attention"),
    (["candidate.m", "MoeLm", "layer_0", "attention", "lm.attention",
      "lm.attention_core", "exp"], "lm.attention_core"),
    (["candidate.m", "MoeLm", "layer_1", "moe", "lm.moe_route", "sort"],
     "lm.moe_route"),
    (["candidate.m", "MoeLm", "layer_1", "moe", "lm.moe_experts",
      "ragged_dot"], "lm.moe_experts"),
    (["candidate.m", "MoeLm", "lm.loss", "final_norm", "mul"], "lm.loss"),
    (["ensemble.e", "blocked_logits", "dot_general"], "blocked_logits"),
    (["candidate.m", "NasNetA", "cell_0"], None),
    ([], None),
])
def test_an_operation_counts_under_its_innermost_scope(path, scope):
    assert lm_reduce.scope_of(path) == scope


def test_a_roofline_is_the_larger_share_of_the_two_peaks():
    record = {
        "lm_reduce": {"scopes_ms": {"lm.moe_experts": 100.0}},
        "peaks": {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9},
    }
    # 10 TFLOP in 0.1 s is 50% of 200 TFLOP/s; 8 GB is 10% of 800 GB/s.
    assert lm_reduce.roofline(
        record, "lm.moe_experts", (10e12, 8e9)
    ) == pytest.approx(50.0)
    assert lm_reduce.roofline(
        record, "lm.moe_experts", (1e12, 40e9)
    ) == pytest.approx(50.0)
    # A program without the scopes, or a run off the chip, reads nothing.
    assert lm_reduce.roofline(
        dict(record, lm_reduce=None), "lm.moe_experts", (1, 1)
    ) is None
    assert lm_reduce.roofline(
        dict(record, peaks=None), "lm.moe_experts", (1, 1)
    ) is None


def test_the_kernels_work_is_counted_from_the_sizes():
    cell = run.Cell("mellum2.t0_s8192")
    (name,) = cell.cell["training"]
    sizes = cell.members[name]["sizes"]
    tokens = cell.traffic["batch"] * cell.traffic["seq"]
    flops, moved = count_lm_flops.experts_work(sizes, tokens)
    # One pair a token on the held experts, three products of
    # 2 x 2304 x 896 each, four layers, forward and two gradients.
    assert count_lm_flops.pairs_per_token(sizes) == 1.0
    assert flops == 3 * 4 * tokens * 3 * 2 * 2304 * 896
    assert moved > 0
    flops, _ = count_lm_flops.attention_core_work(
        sizes, cell.traffic["batch"], cell.traffic["seq"]
    )
    per_token = flops / 3 / tokens
    assert 14.2e6 < per_token < 14.4e6  # ISSUE 34: 14.29 MFLOP a token
