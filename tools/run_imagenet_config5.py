"""Run BASELINE.json config 5 to real numbers (round-4 verdict item 5).

ResNet-50 + EfficientNet-B0 at full 224x224 resolution on the synthetic
provider, through AutoEnsembleEstimator with RoundRobin candidate
placement over an 8-device virtual CPU mesh, for 60 REAL optimizer
steps (override via ADANET_CONFIG5_STEPS) — recording the per-step
adanet-loss trajectory and step time. This upgrades config 5 from
"builds at full res" (round 4's eval_shape structure tests) to "trains
at full res".

Writes IMAGENET_CONFIG5_r05.json at the repo root and prints it.

Usage: python tools/run_imagenet_config5.py  (CPU, no TPU needed;
       first run dominated by XLA:CPU compilation of both stems, then
       ~60-80s/step on one contended core)
"""

import json
import logging
import os
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax

jax.config.update("jax_platforms", "cpu")
try:
    jax.config.update("jax_num_cpu_devices", 8)
except AttributeError:
    # Pre-0.5 JAX: the XLA flag works because the CPU backend
    # has not initialized yet.
    os.environ["XLA_FLAGS"] = os.environ.get(
        "XLA_FLAGS", ""
    ) + " --xla_force_host_platform_device_count=%d" % (8)
from adanet_tpu.utils.compile_cache_dir import enable_persistent_cache

enable_persistent_cache()

# 20 steps demonstrates "runs + step time" but leaves the descent
# ambiguous; 60 steps gives RMSProp's TF-style warm-started accumulator
# (initial_scale=1.0) time to decay to the true gradient scale so
# EfficientNet's effective step size reaches steady state and the loss
# descent is unambiguous. The committed artifact is the 60-step run.
TRAIN_STEPS = int(os.environ.get("ADANET_CONFIG5_STEPS", "60"))
# ADANET_CONFIG5_ITERS=2 runs a real two-iteration AutoEnsemble SEARCH
# (t1 = frozen t0 winner + both candidates again) and records whether
# the t1 ensemble's adanet loss beats the frozen t0 winner's — the
# ImageNet-scale analogue of test_nasnet_search_improves_ensemble,
# written to IMAGENET_CONFIG5_SEARCH_r05.json so the single-iteration
# artifact is preserved.
ITERS = int(os.environ.get("ADANET_CONFIG5_ITERS", "1"))
BATCH_SIZE = 12  # divisible by every RoundRobin submesh size (3/3/2)
IMAGE_SIZE = 224


class _StepLogCapture(logging.Handler):
    """Captures the estimator's per-step adanet-loss EMA log records."""

    def __init__(self):
        super().__init__()
        self.records = []  # (wall_time, iteration, step, {candidate: ema})

    def emit(self, record):
        # Guarded against foreign records on the same logger: msg may be
        # a non-str object, and the estimator's log arity could change —
        # a handler must never raise (ADVICE r5).
        if (
            isinstance(record.msg, str)
            and "adanet_loss EMAs" in record.msg
            and isinstance(record.args, tuple)
            and len(record.args) == 4
        ):
            t, step, total, emas = record.args
            self.records.append(
                (time.time(), int(t), int(step), dict(emas))
            )


def main():
    from absl import flags

    from research.imagenet_autoensemble import trainer as t5

    FLAGS = flags.FLAGS
    FLAGS(
        [
            "config5",
            "--dataset=fake",
            "--image_size=%d" % IMAGE_SIZE,
            "--batch_size=%d" % BATCH_SIZE,
            "--train_steps=%d" % (TRAIN_STEPS * ITERS),
            "--boosting_iterations=%d" % ITERS,
            "--placement=round_robin",
            # Linear-scaling rule for the tiny synthetic batch: the
            # published recipe LRs (the trainer flag defaults) assume
            # batch 256 — unscaled, both candidates diverge (first tool
            # run: ResNet loss 5e3 -> 6e14 by step 20).
            "--resnet_lr=%g" % (FLAGS["resnet_lr"].default * BATCH_SIZE / 256.0),
            "--efficientnet_lr=%g"
            % (FLAGS["efficientnet_lr"].default * BATCH_SIZE / 256.0),
        ]
    )

    capture = _StepLogCapture()
    # core/estimator.py logs on the package logger ("adanet_tpu").
    est_logger = logging.getLogger("adanet_tpu")
    est_logger.addHandler(capture)
    est_logger.setLevel(logging.INFO)

    provider = t5._provider()
    model_dir = tempfile.mkdtemp(prefix="config5_")
    estimator = t5.build_estimator(provider, model_dir)
    estimator._log_every_steps = 1

    start = time.time()
    estimator.train(
        provider.get_input_fn("train"), max_steps=TRAIN_STEPS * ITERS
    )
    wall = time.time() - start

    assert capture.records, "no per-step loss records captured"
    # Per-candidate EMA series: candidates change across iterations
    # (t0_/t1_ name prefixes), so first/last must be tracked per name,
    # not taken from the first/last record dicts.
    series = {}
    for _, _, step, emas in capture.records:
        for name, v in emas.items():
            series.setdefault(name, []).append((step, v))
    first_emas = {n: s[0][1] for n, s in series.items()}
    last_emas = {n: s[-1][1] for n, s in series.items()}
    first_step = min(s[0][0] for s in series.values())
    last_step = max(s[-1][0] for s in series.values())
    # Step time from inter-record gaps, excluding the first (compile).
    gaps = [
        b[0] - a[0]
        for a, b in zip(capture.records[1:], capture.records[2:])
    ]
    gaps.sort()
    median_step = gaps[len(gaps) // 2] if gaps else None

    # Per-candidate selection records (persisted by default at every
    # iteration end).
    cand = estimator.candidate_metrics(ITERS - 1)

    decreasing = {
        name: last_emas[name] < first_emas[name] for name in last_emas
    }
    # Full per-step EMA trajectory so the artifact shows the descent
    # shape, not just the endpoints. The estimator logs the PER-ITERATION
    # step counter (it resets each boosting iteration), so keys are
    # "t<iteration>:<step>" to keep every iteration's records.
    curve = {
        "t%d:%d" % (t, step): {k: round(v, 4) for k, v in emas.items()}
        for _, t, step, emas in capture.records
    }
    result = {
        "config": "BASELINE.json config 5 (synthetic provider)",
        "candidates": sorted(last_emas),
        "image_size": IMAGE_SIZE,
        "batch_size": BATCH_SIZE,
        "train_steps_per_iteration": TRAIN_STEPS,
        "train_steps_total": TRAIN_STEPS * ITERS,
        "placement": "round_robin",
        "devices": jax.device_count(),
        "resnet_lr": float(FLAGS.resnet_lr),
        "efficientnet_lr": float(FLAGS.efficientnet_lr),
        "clip_gradients": float(FLAGS.clip_gradients),
        "loss_first": {k: round(v, 4) for k, v in first_emas.items()},
        "loss_first_step": first_step,
        "loss_last": {k: round(v, 4) for k, v in last_emas.items()},
        "loss_last_step": last_step,
        "loss_decreasing": decreasing,
        "all_decreasing": all(decreasing.values()),
        "loss_curve": curve,
        "median_step_secs": (
            round(median_step, 3) if median_step is not None else None
        ),
        "wall_secs_incl_compile": round(wall, 1),
        "best_candidate": next(
            name for name, entry in cand.items() if entry["best"]
        ),
        "platform": "cpu-virtual-8dev",
    }
    ok = result["all_decreasing"]
    if ITERS > 1:
        result["boosting_iterations"] = ITERS
        result["candidate_metrics_per_iteration"] = {
            **{
                str(t): estimator.candidate_metrics(t)
                for t in range(ITERS - 1)
            },
            str(ITERS - 1): cand,
        }
        # The search-improves criterion on the training objective the
        # estimator itself selects on: the winning grown ensemble's
        # adanet-loss EMA must beat the frozen previous winner's EMA,
        # both read from the final iteration's selection record.
        # Dead/NaN-quarantined candidates persist ema=null; exclude
        # them (a dead candidate can't win either side).
        final_prefix = "t%d_" % (ITERS - 1)
        t_new = [
            e["adanet_loss_ema"]
            for n, e in cand.items()
            if n.startswith(final_prefix)
            and e["adanet_loss_ema"] is not None
        ]
        t_prev = [
            e["adanet_loss_ema"]
            for n, e in cand.items()
            if not n.startswith(final_prefix)
            and e["adanet_loss_ema"] is not None
        ]
        if t_new and t_prev:
            best_new = min(t_new)
            prev_ema = min(t_prev)
            result["search_improves"] = bool(best_new < prev_ema)
            result["final_iter_best_adanet_loss_ema"] = best_new
            result["prev_frozen_winner_adanet_loss_ema"] = prev_ema
            ok = ok and result["search_improves"]
        else:
            result["search_improves"] = False
            ok = False
        out_name = "IMAGENET_CONFIG5_SEARCH_r05.json"
    else:
        out_name = "IMAGENET_CONFIG5_r05.json"
    out = os.path.join(_REPO, out_name)
    with open(out, "w") as f:
        json.dump(result, f, indent=1, sort_keys=True)
    print(json.dumps(result))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
