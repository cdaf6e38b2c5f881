"""Estimator lifecycle tests.

The analogue of the reference's single-process integration suite
(reference: adanet/core/estimator_test.py): full
train→evaluate→predict→export lifecycles, checkpoint/resume, replay,
force_grow, evaluator-based selection, and report round-trips.
"""

import json
import os

import numpy as np
import pytest
import optax

import adanet_tpu
from adanet_tpu import replay
from adanet_tpu.core.estimator import Estimator
from adanet_tpu.core.evaluator import Evaluator
from adanet_tpu.core.report_materializer import ReportMaterializer
from adanet_tpu.distributed import (
    ElasticWorkQueueStrategy,
    RoundRobinStrategy,
)
from adanet_tpu.ensemble import ComplexityRegularizedEnsembler
from adanet_tpu.subnetwork import SimpleGenerator

from helpers import DNNBuilder, linear_dataset


def _make_estimator(tmp_path, **kwargs):
    defaults = dict(
        head=adanet_tpu.RegressionHead(),
        subnetwork_generator=SimpleGenerator(
            [DNNBuilder("dnn", 1), DNNBuilder("deep", 2)]
        ),
        max_iteration_steps=8,
        ensemblers=[ComplexityRegularizedEnsembler(optimizer=optax.sgd(0.05))],
        model_dir=str(tmp_path / "model"),
        log_every_steps=0,
    )
    defaults.update(kwargs)
    return Estimator(**defaults)


def test_lifecycle(tmp_path):
    """train → evaluate → predict → export (reference: test_lifecycle)."""
    est = _make_estimator(tmp_path, max_iterations=2)
    est.train(linear_dataset(), max_steps=100)
    assert est.latest_iteration_number() == 2
    assert est.latest_global_step() == 16  # 2 iterations x 8 steps

    metrics = est.evaluate(linear_dataset())
    assert np.isfinite(metrics["average_loss"])
    assert metrics["global_step"] == 16

    preds = list(est.predict(linear_dataset()))
    assert len(preds) == 4  # 64 examples / batch 16
    assert preds[0]["predictions"].shape == (16, 1)

    sample = next(linear_dataset()())
    export_dir = est.export_saved_model(str(tmp_path / "export"), sample)
    assert os.path.exists(os.path.join(export_dir, "architecture.json"))
    assert os.path.exists(os.path.join(export_dir, "ensemble.msgpack"))

    # Architecture files exist per iteration with correct members.
    arch0 = json.load(open(os.path.join(est.model_dir, "architecture-0.json")))
    assert len(arch0["subnetworks"]) == 1
    arch1 = json.load(open(os.path.join(est.model_dir, "architecture-1.json")))
    assert len(arch1["replay_indices"]) == 2


def test_resume_from_checkpoint(tmp_path):
    """Stop/restart anywhere (reference: estimator_test.py:1659-1744)."""
    est = _make_estimator(tmp_path, max_iterations=2)
    # Stop mid-iteration-0 (max_steps=5 < 8 iteration steps).
    est.train(linear_dataset(), max_steps=5)
    assert est.latest_iteration_number() == 0
    assert est.latest_global_step() == 5

    # A fresh Estimator over the same model_dir resumes and finishes.
    est2 = _make_estimator(tmp_path, max_iterations=2)
    est2.train(linear_dataset(), max_steps=100)
    assert est2.latest_iteration_number() == 2
    assert est2.latest_global_step() == 16
    metrics = est2.evaluate(linear_dataset())
    assert np.isfinite(metrics["average_loss"])


def test_sigterm_checkpoints_and_resumes(tmp_path):
    """Preemption safety (SURVEY §5.3): SIGTERM mid-training checkpoints
    the live iteration state and exits cleanly; a fresh process resumes
    from exactly that step."""
    import signal
    import subprocess
    import sys
    import time

    from adanet_tpu.core import checkpoint as ckpt_lib

    tests_dir = os.path.dirname(os.path.abspath(__file__))
    model_dir = str(tmp_path / "model")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(tests_dir), tests_dir, env.get("PYTHONPATH", "")]
    )
    proc = subprocess.Popen(
        [
            sys.executable,
            os.path.join(tests_dir, "sigterm_runner.py"),
            model_dir,
        ],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )
    # Wait for training to actually start, then preempt it.
    deadline = time.time() + 120
    while time.time() < deadline:
        line = proc.stdout.readline()
        if "READY" in line:
            break
        if not line and proc.poll() is not None:  # crashed before READY
            raise AssertionError(proc.communicate()[0][-2000:])
    else:  # pragma: no cover
        proc.kill()
        raise AssertionError("runner never started training")
    time.sleep(1.0)  # let some steps run
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=120)
    assert proc.returncode == 0, out[-2000:]
    assert "STOPPED AT" in out, out[-2000:]

    info = ckpt_lib.read_manifest(model_dir)
    assert info is not None and info.global_step > 0
    assert info.iteration_state_file  # mid-iteration state persisted
    stopped_step = info.global_step

    # A fresh Estimator resumes from the preempted step and finishes.
    est = _make_estimator(
        tmp_path,
        subnetwork_generator=SimpleGenerator([DNNBuilder("dnn", 1)]),
        max_iteration_steps=stopped_step + 4,
        max_iterations=1,
    )
    est.train(linear_dataset(), max_steps=stopped_step + 4)
    assert est.latest_global_step() == stopped_step + 4
    assert est.latest_iteration_number() == 1


def test_stale_mid_iteration_checkpoints_are_pruned(tmp_path):
    """Superseded ckpt-<step>.msgpack files must not accumulate over long
    searches (ADVICE round 1): only the manifest's current state file may
    remain, and none after an iteration completes."""
    import glob

    est = _make_estimator(
        tmp_path, max_iterations=2, save_checkpoint_steps=2
    )
    # Stop mid-iteration: exactly the manifest's state file remains.
    est.train(linear_dataset(), max_steps=5)
    files = sorted(
        os.path.basename(p)
        for p in glob.glob(os.path.join(est.model_dir, "ckpt-*.msgpack"))
    )
    from adanet_tpu.core import checkpoint as ckpt_lib

    info = ckpt_lib.read_manifest(est.model_dir)
    assert files == [info.iteration_state_file]

    # Finish the search: completed iterations leave no mid-iteration state.
    _make_estimator(
        tmp_path, max_iterations=2, save_checkpoint_steps=2
    ).train(linear_dataset(), max_steps=100)
    assert glob.glob(os.path.join(est.model_dir, "ckpt-*.msgpack")) == []


def test_training_continues_decreasing_loss(tmp_path):
    est = _make_estimator(tmp_path, max_iterations=3, max_iteration_steps=20)
    est.train(linear_dataset(), max_steps=200)
    metrics = est.evaluate(linear_dataset())
    # Three boosting iterations of SGD on a linear problem: loss must be low.
    assert metrics["average_loss"] < 0.3


def test_force_grow_never_reselects_previous(tmp_path):
    est = _make_estimator(
        tmp_path,
        max_iterations=3,
        force_grow=True,
        # Learning rate 0 so new candidates never beat the previous ensemble
        # on merit; only force_grow makes the ensemble grow.
        subnetwork_generator=SimpleGenerator(
            [DNNBuilder("frozen", 1, learning_rate=0.0)]
        ),
    )
    est.train(linear_dataset(), max_steps=1000)
    arch = json.load(
        open(os.path.join(est.model_dir, "architecture-2.json"))
    )
    # With force_grow the winner at every t>0 must include a new member.
    assert len(arch["subnetworks"]) == 3


def test_evaluator_based_selection(tmp_path):
    est = _make_estimator(
        tmp_path,
        max_iterations=1,
        evaluator=Evaluator(input_fn=linear_dataset(), steps=2),
    )
    est.train(linear_dataset(), max_steps=8)
    assert est.latest_iteration_number() == 1
    metrics = est.evaluate(linear_dataset())
    assert np.isfinite(metrics["average_loss"])


def test_replay(tmp_path):
    """Replay reruns recorded choices without evaluation
    (reference: EstimatorReplayTest, estimator_test.py:3235)."""
    est = _make_estimator(tmp_path, max_iterations=2)
    est.train(linear_dataset(), max_steps=100)
    manifest = json.load(
        open(os.path.join(est.model_dir, "checkpoint.json"))
    )
    indices = manifest["replay_indices"]
    assert len(indices) == 2

    est2 = _make_estimator(
        tmp_path,
        model_dir=str(tmp_path / "replayed"),
        max_iterations=2,
        replay_config=replay.Config(best_ensemble_indices=indices),
    )
    est2.train(linear_dataset(), max_steps=100)
    manifest2 = json.load(
        open(os.path.join(est2.model_dir, "checkpoint.json"))
    )
    assert manifest2["replay_indices"] == indices


def test_report_round_trip(tmp_path):
    """Reports flow back into the generator
    (reference: EstimatorReportTest, estimator_test.py:2417-3001)."""
    seen = []

    class RecordingGenerator(SimpleGenerator):
        def generate_candidates(
            self,
            previous_ensemble,
            iteration_number,
            previous_ensemble_reports,
            all_reports,
            config=None,
        ):
            seen.append(
                (
                    iteration_number,
                    [r.name for r in previous_ensemble_reports],
                    len(all_reports),
                )
            )
            return super().generate_candidates(
                previous_ensemble,
                iteration_number,
                previous_ensemble_reports,
                all_reports,
                config,
            )

    est = _make_estimator(
        tmp_path,
        subnetwork_generator=RecordingGenerator(
            [
                DNNBuilder("dnn", 1, with_report=True),
                DNNBuilder("deep", 2, with_report=True),
            ]
        ),
        max_iterations=2,
        report_materializer=ReportMaterializer(
            input_fn=linear_dataset(), steps=2
        ),
    )
    est.train(linear_dataset(), max_steps=100)

    # Generator at iteration 1 must have seen iteration 0's reports.
    gen_calls = [c for c in seen if c[0] == 1]
    assert gen_calls
    assert any(c[1] for c in gen_calls)  # previous_ensemble_reports non-empty
    reports_file = os.path.join(
        est.model_dir, "report", "iteration_reports.json"
    )
    reports = json.load(open(reports_file))
    assert set(reports) == {"0", "1"}
    assert {r["name"] for r in reports["0"]} == {"dnn", "deep"}
    included = [
        r["name"] for r in reports["0"] if r["included_in_final_ensemble"]
    ]
    assert len(included) == 1
    assert "mean_logit" in reports["0"][0]["metrics"]
    assert "loss" in reports["0"][0]["metrics"]


def test_nan_candidate_quarantined_in_estimator(tmp_path):
    est = _make_estimator(
        tmp_path,
        subnetwork_generator=SimpleGenerator(
            [DNNBuilder("good", 1), DNNBuilder("nan", 1, nan_logits=True)]
        ),
        max_iterations=1,
    )
    est.train(linear_dataset(), max_steps=8)
    arch = json.load(open(os.path.join(est.model_dir, "architecture-0.json")))
    assert arch["subnetworks"][0]["builder_name"] == "good"


def test_max_iterations_stops_search(tmp_path):
    est = _make_estimator(tmp_path, max_iterations=1)
    est.train(linear_dataset(), max_steps=10_000)
    assert est.latest_iteration_number() == 1
    assert est.latest_global_step() == 8


def test_export_serving_program_round_trip(tmp_path):
    """The serialized StableHLO program predicts without any model code
    (the SavedModel-parity path; reference: estimator_test.py:2223-2416)."""
    from adanet_tpu.core.export import load_serving_program, serving_signature

    est = _make_estimator(tmp_path, max_iterations=1)
    est.train(linear_dataset(), max_steps=8)
    sample = next(linear_dataset()())
    export_dir = est.export_saved_model(str(tmp_path / "export"), sample)

    served = load_serving_program(export_dir)
    out = served(sample[0])
    assert out["predictions"].shape == (16, 1)
    # Must match the in-framework predict path.
    expected = next(iter(est.predict(linear_dataset())))
    np.testing.assert_allclose(
        np.asarray(out["predictions"]),
        expected["predictions"],
        rtol=1e-5,
        atol=1e-6,
    )
    signature = serving_signature(export_dir)
    assert signature["outputs"]["predictions"]["shape"] == ["batch", "1"]
    # Polymorphic batch: the served program accepts other batch sizes.
    out3 = served({"x": np.ones((3, 2), np.float32)})
    assert out3["predictions"].shape == (3, 1)


def test_multi_head_lifecycle(tmp_path):
    """Dict logits/labels through the full lifecycle
    (reference: estimator_test.py:1517 multi-head coverage)."""
    import flax.linen as nn
    import jax.numpy as jnp

    from adanet_tpu.subnetwork import Builder, Subnetwork

    head = adanet_tpu.MultiHead(
        [
            adanet_tpu.RegressionHead(name="reg"),
            adanet_tpu.MultiClassHead(3, name="cls"),
        ]
    )

    class _TwoHeadModule(nn.Module):
        dims: dict

        @nn.compact
        def __call__(self, features, training: bool = False):
            x = jnp.asarray(features["x"], jnp.float32)
            h = nn.relu(nn.Dense(8)(x))
            logits = {
                key: nn.Dense(dim, name="logits_%s" % key)(h)
                for key, dim in sorted(self.dims.items())
            }
            return Subnetwork(
                last_layer={key: h for key in self.dims},
                logits=logits,
                complexity=1.0,
            )

    class _TwoHeadBuilder(Builder):
        @property
        def name(self):
            return "two_head"

        def build_subnetwork(self, logits_dimension, previous_ensemble=None):
            return _TwoHeadModule(dims=logits_dimension)

        def build_train_optimizer(self, previous_ensemble=None):
            return optax.sgd(0.05)

    rng = np.random.RandomState(0)
    x = rng.randn(64, 4).astype(np.float32)
    labels = {
        "reg": x.sum(axis=1, keepdims=True).astype(np.float32),
        "cls": rng.randint(0, 3, size=(64,)),
    }

    def input_fn():
        for s in range(0, 64, 16):
            yield (
                {"x": x[s : s + 16]},
                {k: v[s : s + 16] for k, v in labels.items()},
            )

    est = _make_estimator(
        tmp_path,
        head=head,
        subnetwork_generator=SimpleGenerator([_TwoHeadBuilder()]),
        max_iterations=2,
    )
    est.train(input_fn, max_steps=100)
    assert est.latest_iteration_number() == 2
    metrics = est.evaluate(input_fn)
    assert np.isfinite(metrics["average_loss"])
    assert "cls/accuracy" in metrics
    preds = next(iter(est.predict(input_fn)))
    assert preds["reg/predictions"].shape == (16, 1)
    assert preds["cls/class_ids"].shape == (16,)

    # Multi-head serving export: the StableHLO program carries ALL heads'
    # dict outputs with a polymorphic batch, loadable with only jax
    # (reference exports all heads, estimator.py:1081-1118).
    from adanet_tpu.core.export import load_serving_program, serving_signature

    sample = next(input_fn())
    export_dir = est.export_saved_model(str(tmp_path / "export"), sample)
    serve = load_serving_program(export_dir)
    out = serve({"x": np.random.RandomState(1).randn(5, 4).astype(np.float32)})
    assert out["reg/predictions"].shape == (5, 1)
    assert out["cls/probabilities"].shape == (5, 3)
    assert out["cls/class_ids"].shape == (5,)
    signature = serving_signature(export_dir)
    assert set(signature["outputs"]) >= {
        "reg/predictions",
        "cls/probabilities",
        "cls/class_ids",
        "cls/logits",
    }


def test_multi_head_export_with_member_outputs(tmp_path):
    """export_subnetwork_logits/last_layer flags compose with multi-head
    dict outputs through predict AND the serialized serving program."""
    import flax.linen as nn
    import jax.numpy as jnp

    from adanet_tpu.core.export import load_serving_program
    from adanet_tpu.subnetwork import Builder, Subnetwork

    head = adanet_tpu.MultiHead(
        [
            adanet_tpu.RegressionHead(name="reg"),
            adanet_tpu.MultiClassHead(3, name="cls"),
        ]
    )

    class _B(Builder):
        @property
        def name(self):
            return "b"

        def build_subnetwork(self, logits_dimension, previous_ensemble=None):
            class M(nn.Module):
                @nn.compact
                def __call__(self, features, training=False):
                    h = nn.relu(
                        nn.Dense(8)(jnp.asarray(features["x"], jnp.float32))
                    )
                    return Subnetwork(
                        last_layer=h,
                        logits={
                            k: nn.Dense(d)(h)
                            for k, d in sorted(logits_dimension.items())
                        },
                        complexity=1.0,
                    )

            return M()

        def build_train_optimizer(self, previous_ensemble=None):
            return optax.sgd(0.05)

    rng = np.random.RandomState(0)

    def input_fn():
        for _ in range(4):
            x = rng.randn(16, 4).astype(np.float32)
            yield {"x": x}, {
                "reg": x.sum(axis=1, keepdims=True),
                "cls": np.zeros((16,), np.int32),
            }

    est = _make_estimator(
        tmp_path,
        head=head,
        subnetwork_generator=SimpleGenerator([_B()]),
        max_iterations=1,
        max_iteration_steps=4,
        export_subnetwork_logits=True,
        export_subnetwork_last_layer=True,
    )
    est.train(input_fn, max_steps=4)
    preds = next(iter(est.predict(input_fn)))
    assert set(preds["subnetwork_logits/0"]) == {"reg", "cls"}
    assert preds["subnetwork_last_layer/0"].shape == (16, 8)

    export_dir = est.export_saved_model(str(tmp_path / "export"), next(input_fn()))
    out = load_serving_program(export_dir)(
        {"x": np.zeros((3, 4), np.float32)}
    )
    assert out["subnetwork_logits/0"]["cls"].shape == (3, 3)
    assert out["subnetwork_last_layer/0"].shape == (3, 8)


def test_export_is_multi_platform(tmp_path):
    """The serving artifact carries cpu AND tpu lowerings (SavedModel-like
    portability): exported under one backend, it loads and declares both
    platforms."""
    from adanet_tpu.core.export import load_serving_program, serving_signature

    est = _make_estimator(tmp_path, max_iterations=1)
    est.train(linear_dataset(), max_steps=8)
    sample = next(linear_dataset()())
    export_dir = est.export_saved_model(str(tmp_path / "export"), sample)
    signature = serving_signature(export_dir)
    assert set(p.lower() for p in signature["platforms"]) >= {"cpu", "tpu"}
    out = load_serving_program(export_dir)(
        {"x": np.zeros((3, 2), np.float32)}
    )
    assert out["predictions"].shape == (3, 1)


def test_multiple_strategies_and_ensemblers_lifecycle(tmp_path):
    """Solo+Grow+All strategies x CRE+Mean ensemblers through the full
    search (the reference's candidates-per-iteration cross product,
    iteration.py:683-740)."""
    from adanet_tpu.ensemble import (
        AllStrategy,
        GrowStrategy,
        MeanEnsembler,
        SoloStrategy,
    )

    est = _make_estimator(
        tmp_path,
        ensemblers=[
            ComplexityRegularizedEnsembler(
                optimizer=optax.sgd(0.05), adanet_lambda=0.01
            ),
            MeanEnsembler(),
        ],
        ensemble_strategies=[
            GrowStrategy(),
            SoloStrategy(),
            AllStrategy(),
        ],
        max_iterations=2,
        max_iteration_steps=6,
    )
    est.train(linear_dataset(), max_steps=100)
    assert est.latest_iteration_number() == 2
    metrics = est.evaluate(linear_dataset())
    assert np.isfinite(metrics["average_loss"])
    # 2 builders x 3 strategies -> grow(2) + solo(2) + all(1) = 5 candidate
    # groups x 2 ensemblers = 10 candidates at t=0.
    it0 = est._build_iteration(0, next(linear_dataset()()))
    assert len(it0.candidate_names()) == 10
    arch = json.load(open(os.path.join(est.model_dir, "architecture-0.json")))
    assert arch["ensembler_name"] in ("complexity_regularized", "mean")


def test_iteration_cache_reuses_compiled_iteration(tmp_path):
    """Mid-iteration rebuilds reuse the jitted Iteration; completing the
    iteration drops it (releasing compiled programs and buffers)."""
    est = _make_estimator(tmp_path, max_iterations=1)
    est.train(linear_dataset(), max_steps=5)  # mid-iteration
    sample = next(linear_dataset()())
    it1 = est._build_iteration(0, sample)
    it2 = est._build_iteration(0, sample)
    assert it1 is it2
    est.train(linear_dataset(), max_steps=100)  # completes the search
    assert est._iteration_cache is None


def test_export_subnetwork_outputs_in_predict(tmp_path):
    """Per-member logits/last layers in predictions
    (reference ctor flags export_subnetwork_logits/last_layer)."""
    est = _make_estimator(
        tmp_path,
        max_iterations=2,
        export_subnetwork_logits=True,
        export_subnetwork_last_layer=True,
    )
    est.train(linear_dataset(), max_steps=100)
    preds = next(iter(est.predict(linear_dataset())))
    assert "subnetwork_logits/0" in preds
    assert "subnetwork_logits/1" in preds  # 2 members after 2 iterations
    assert preds["subnetwork_logits/0"].shape == (16, 1)
    assert preds["subnetwork_last_layer/0"].shape[0] == 16


def test_evaluate_and_predict_from_mid_iteration_checkpoint(tmp_path):
    """evaluate()/predict() work from a mid-iteration checkpoint: the
    current best candidate serves (reference keeps serving mid-iteration
    too, estimator.py:1055-1068 analogue)."""
    est = _make_estimator(tmp_path, max_iterations=2)
    # Stop mid-iteration-0: only live candidate state exists on disk.
    est.train(linear_dataset(), max_steps=5)
    assert est.latest_iteration_number() == 0
    info_metrics = est.evaluate(linear_dataset())
    assert np.isfinite(info_metrics["average_loss"])
    assert info_metrics["best_ensemble"].startswith("t0_")
    preds = list(est.predict(linear_dataset()))
    assert len(preds) == 4 and preds[0]["predictions"].shape == (16, 1)

    # A FRESH estimator over the same model_dir (no in-process cache)
    # serves from the mid-iteration checkpoint too.
    est2 = _make_estimator(tmp_path, max_iterations=2)
    again = est2.evaluate(linear_dataset())
    assert again["average_loss"] == pytest.approx(
        info_metrics["average_loss"], rel=1e-6
    )


def test_nondeterministic_generator_rebuild_error(tmp_path):
    """A generator that renames its builders between runs breaks the
    deterministic rebuild chain with an actionable error (reference
    requires deterministic generators for graph reconstruction,
    estimator.py:1785-1882)."""
    est = _make_estimator(tmp_path, max_iterations=1)
    est.train(linear_dataset(), max_steps=100)
    assert est.latest_iteration_number() == 1

    renamed = _make_estimator(
        tmp_path,
        max_iterations=2,
        subnetwork_generator=SimpleGenerator(
            [DNNBuilder("renamed", 1), DNNBuilder("deep", 2)]
        ),
    )
    with pytest.raises(ValueError, match="deterministic"):
        renamed.train(linear_dataset(), max_steps=200)


def test_metric_fn_adds_custom_eval_metrics(tmp_path):
    """metric_fn(logits, labels) -> extra metrics surfaced by evaluate()
    (the reference Estimator's `metric_fn` kwarg, estimator.py:604-759)."""
    import jax.numpy as jnp

    def metric_fn(logits, labels):
        return {"mean_abs_logit": jnp.mean(jnp.abs(logits))}

    est = _make_estimator(tmp_path, max_iterations=1, metric_fn=metric_fn)
    est.train(linear_dataset(), max_steps=100)
    metrics = est.evaluate(linear_dataset())
    assert "mean_abs_logit" in metrics
    assert np.isfinite(metrics["mean_abs_logit"])
    assert metrics["mean_abs_logit"] > 0


def test_metric_fn_weighted_form_sees_weights(tmp_path):
    """The 3-arg metric_fn form opts into example weights from the
    weight_key column (reference weight_column semantics,
    ensemble_builder.py:571-583)."""
    import jax.numpy as jnp

    def metric_fn(logits, labels, weights):
        return {"weight_total_mean": jnp.mean(weights)}

    def weighted_dataset():
        base = linear_dataset()

        def input_fn():
            for features, labels in base():
                features = dict(features)
                features["w"] = np.full(
                    (len(labels), 1), 2.0, dtype=np.float32
                )
                yield features, labels

        return input_fn

    est = _make_estimator(
        tmp_path, max_iterations=1, metric_fn=metric_fn, weight_key="w"
    )
    est.train(weighted_dataset(), max_steps=50)
    metrics = est.evaluate(weighted_dataset())
    assert metrics["weight_total_mean"] == pytest.approx(2.0)


def test_enable_summaries_false_writes_no_event_files(tmp_path):
    """With summaries disabled, no tfevents land anywhere under model_dir
    (the reference's summaries-off coverage, estimator_test.py:1796-2085)."""
    est = _make_estimator(
        tmp_path,
        max_iterations=1,
        enable_summaries=False,
        log_every_steps=2,
    )
    est.train(linear_dataset(), max_steps=100)
    event_files = [
        os.path.join(root, f)
        for root, _, files in os.walk(str(tmp_path / "model"))
        for f in files
        if "tfevents" in f
    ]
    assert event_files == []


# ------------------------------------------- resume through the template


def _auto_ensemble_with_initial_variables(root):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from adanet_tpu import AutoEnsembleEstimator, AutoEnsembleSubestimator

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, features, training: bool = False):
            x = nn.relu(nn.Dense(8)(jnp.asarray(features["x"], jnp.float32)))
            return nn.Dense(1)(x)

    module = MLP()
    pretrained = jax.device_get(
        module.init(
            jax.random.PRNGKey(99),
            {"x": np.zeros((2, 2), np.float32)},
            training=True,
        )
    )
    return AutoEnsembleEstimator(
        head=adanet_tpu.RegressionHead(),
        candidate_pool={
            "frozen": AutoEnsembleSubestimator(
                module, prediction_only=True, initial_variables=pretrained
            ),
            "finetune": AutoEnsembleSubestimator(
                module, optimizer=optax.sgd(0.05), initial_variables=pretrained
            ),
        },
        max_iteration_steps=8,
        max_iterations=2,
        model_dir=str(root / "model"),
        log_every_steps=0,
    )


def _placed(strategy_factory):
    return lambda root: _make_estimator(
        root, max_iterations=2, placement_strategy=strategy_factory()
    )


_RESUMED = {
    "fused": _placed(lambda: None),
    "round_robin": _placed(RoundRobinStrategy),
    "elastic": _placed(lambda: ElasticWorkQueueStrategy(window_steps=4)),
    "autoensemble_initial_variables": _auto_ensemble_with_initial_variables,
}


def _resume_counters():
    from adanet_tpu.observability import metrics as metrics_lib

    registry = metrics_lib.registry()
    return (
        registry.counter("estimator.resume.templates").value,
        registry.counter("estimator.resume.real_inits").value,
    )


@pytest.fixture
def module_inits(monkeypatch):
    """Every `flax.linen.Module.init` call from here on: True where it
    ran abstractly (its keys were tracers), False where it ran for real."""
    import flax.linen as nn
    import jax

    calls = []
    real = nn.Module.init

    def spy(self, rngs, *args, **kwargs):
        calls.append(
            all(
                isinstance(leaf, jax.core.Tracer)
                for leaf in jax.tree_util.tree_leaves(rngs)
            )
        )
        return real(self, rngs, *args, **kwargs)

    monkeypatch.setattr(nn.Module, "init", spy)
    return calls


@pytest.mark.parametrize("kind", sorted(_RESUMED))
def test_resume_through_template_saves_the_same_bytes(
    tmp_path, monkeypatch, module_inits, kind
):
    """A call that resumes restores over the state's template: it runs no
    `module.init` for real, and what it trains and saves from there is
    byte-equal to a resume that built the whole state first."""
    import shutil

    import jax

    from adanet_tpu.core.iteration import Iteration

    build = _RESUMED[kind]
    stopped = tmp_path / "stopped"
    est = build(stopped)
    del module_inits[:]  # a builder of the test's may have made weights
    est.train(linear_dataset(), max_steps=5)  # mid-iteration 0
    assert module_inits and not any(module_inits)  # a fresh start is real
    dirs = {}
    for side in ("template", "real"):
        dirs[side] = tmp_path / side
        shutil.copytree(stopped, dirs[side])

    templates, real_inits = _resume_counters()
    est = build(dirs["template"])
    del module_inits[:]
    est.train(linear_dataset(), max_steps=6)
    # A process that has not initialized traces the template once.
    assert module_inits and all(module_inits)
    assert _resume_counters() == (templates + 1, real_inits)
    del module_inits[:]
    est.train(linear_dataset(), max_steps=7)
    # The kept `Iteration` remembers it: no `module.init` of any kind.
    assert module_inits == []
    assert _resume_counters() == (templates + 2, real_inits)
    assert est._iteration_cache.state_template_traces == 1

    # The other side restores over a state it built for real, as every
    # resume did before the template.
    monkeypatch.setattr(
        Iteration,
        "state_template",
        lambda self, batch: self.init_state(jax.random.PRNGKey(0), batch),
    )
    est = build(dirs["real"])
    del module_inits[:]
    est.train(linear_dataset(), max_steps=6)
    est.train(linear_dataset(), max_steps=7)
    assert module_inits and not any(module_inits)

    saved = {
        side: (root / "model" / "ckpt-7.msgpack").read_bytes()
        for side, root in dirs.items()
    }
    assert saved["template"] == saved["real"]


def test_evaluate_and_predict_resume_through_the_template(
    tmp_path, module_inits
):
    """`evaluate()` and `predict()` on a mid-iteration checkpoint come
    through the same restore: a template, no real init."""
    _make_estimator(tmp_path, max_iterations=2).train(
        linear_dataset(), max_steps=5
    )
    del module_inits[:]
    templates, real_inits = _resume_counters()
    est = _make_estimator(tmp_path, max_iterations=2)
    metrics = est.evaluate(linear_dataset())
    assert np.isfinite(metrics["average_loss"])
    preds = list(est.predict(linear_dataset()))
    assert preds[0]["predictions"].shape == (16, 1)
    assert module_inits and all(module_inits)
    assert _resume_counters() == (templates + 2, real_inits)
    # One template for a batch with labels, one for a batch without.
    assert est._iteration_cache.state_template_traces == 2
    del module_inits[:]
    est.evaluate(linear_dataset())
    assert module_inits == []
    assert _resume_counters() == (templates + 3, real_inits)
