"""TPUEstimator tests: multi-step host loops, metric_fn, profiling.

The analogue of reference tpu_estimator_test.py (which runs the TPU code
path on CPU, reference: adanet/core/tpu_estimator_test.py) — here the same
engine runs everywhere, so these verify the host-loop batching semantics.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax

import adanet_tpu
from adanet_tpu import TPUEstimator
from adanet_tpu.ensemble import ComplexityRegularizedEnsembler
from adanet_tpu.subnetwork import SimpleGenerator

from helpers import DNNBuilder, linear_dataset


def _make(tmp_path, **kwargs):
    defaults = dict(
        head=adanet_tpu.RegressionHead(),
        subnetwork_generator=SimpleGenerator([DNNBuilder("dnn", 1)]),
        max_iteration_steps=8,
        ensemblers=[ComplexityRegularizedEnsembler(optimizer=optax.sgd(0.05))],
        max_iterations=2,
        model_dir=str(tmp_path / "model"),
        log_every_steps=0,
    )
    defaults.update(kwargs)
    return TPUEstimator(**defaults)


def test_multi_step_loop_matches_step_counts(tmp_path):
    est = _make(tmp_path, iterations_per_loop=4)
    est.train(linear_dataset(), max_steps=100)
    assert est.latest_iteration_number() == 2
    assert est.latest_global_step() == 16
    metrics = est.evaluate(linear_dataset())
    assert np.isfinite(metrics["average_loss"])


def test_loop_clipped_by_max_steps(tmp_path):
    # iterations_per_loop larger than the remaining budget must not
    # overshoot max_steps.
    est = _make(tmp_path, iterations_per_loop=16)
    est.train(linear_dataset(), max_steps=5)
    assert est.latest_global_step() == 5


def test_multi_step_equivalent_to_single_step(tmp_path):
    est_multi = _make(
        tmp_path, model_dir=str(tmp_path / "m"), iterations_per_loop=8
    )
    est_single = _make(
        tmp_path, model_dir=str(tmp_path / "s"), iterations_per_loop=1
    )
    est_multi.train(linear_dataset(), max_steps=16)
    est_single.train(linear_dataset(), max_steps=16)
    m = est_multi.evaluate(linear_dataset())
    s = est_single.evaluate(linear_dataset())
    np.testing.assert_allclose(
        m["average_loss"], s["average_loss"], rtol=1e-4
    )


def test_ragged_final_batch_falls_back(tmp_path):
    """A short final batch inside a multi-step window must not crash."""

    def ragged_input_fn():
        rng = np.random.RandomState(0)
        for size in (16, 16, 16, 7):  # last batch is ragged
            x = rng.randn(size, 2).astype(np.float32)
            yield {"x": x}, x.sum(axis=1, keepdims=True)

    est = _make(tmp_path, iterations_per_loop=4, max_iterations=1)
    est.train(ragged_input_fn, max_steps=8)
    assert est.latest_global_step() == 8


def test_checkpoint_interval_crossing_with_loops(tmp_path):
    """save_checkpoint_steps coprime to the loop size still checkpoints."""
    est = _make(
        tmp_path,
        iterations_per_loop=4,
        max_iterations=1,
        max_iteration_steps=8,
        save_checkpoint_steps=3,
    )
    est.train(linear_dataset(), max_steps=6)  # interrupted mid-iteration
    files = glob.glob(os.path.join(est.model_dir, "ckpt-*.msgpack"))
    assert files  # a mid-iteration checkpoint was written


def test_padded_predict_batching(tmp_path):
    """Fixed-size inference batching (the reference's inference-on-TPU
    batch config): ragged batches pad to one compiled shape and outputs
    slice back to true row counts, matching unpadded predictions."""
    est = _make(tmp_path, max_iterations=1, predict_batch_size=16)
    est.train(linear_dataset(), max_steps=8)

    def ragged_input_fn():
        rng = np.random.RandomState(1)
        for size in (16, 9, 3):
            x = rng.randn(size, 2).astype(np.float32)
            yield {"x": x}, x.sum(axis=1, keepdims=True)

    padded = list(est.predict(ragged_input_fn))
    assert [p["predictions"].shape[0] for p in padded] == [16, 9, 3]
    plain = list(est.predict(ragged_input_fn, predict_batch_size=0))
    for a, b in zip(padded, plain):
        np.testing.assert_allclose(
            a["predictions"], b["predictions"], rtol=1e-5
        )

    # Oversized batches are rejected with an actionable error.
    import pytest

    def oversized():
        yield {"x": np.zeros((17, 2), np.float32)}, None

    with pytest.raises(ValueError, match="exceeds"):
        list(est.predict(oversized, predict_batch_size=16))


def test_predict_on_cpu_matches_device_predict(tmp_path):
    """The TPUEmbedding-inference analogue (reference:
    adanet/core/tpu_estimator.py:180-227): `embedding_tables_on_host`
    auto-routes predict() to the host CPU backend — parameters commit to
    one CPU device instead of the accelerator mesh — with identical
    predictions."""
    import jax

    est = _make(
        tmp_path, max_iterations=1, embedding_tables_on_host=True
    )
    est.train(linear_dataset(), max_steps=8)

    def input_fn():
        rng = np.random.RandomState(2)
        for _ in range(2):
            x = rng.randn(8, 2).astype(np.float32)
            yield {"x": x}, x.sum(axis=1, keepdims=True)

    host = list(est.predict(input_fn))  # auto on_cpu via constructor flag
    device = list(est.predict(input_fn, on_cpu=False))
    assert len(host) == 2
    for a, b in zip(host, device):
        np.testing.assert_allclose(
            a["predictions"], b["predictions"], rtol=1e-5
        )

    # Padded batching composes with the CPU route.
    padded = list(est.predict(input_fn, predict_batch_size=16))
    for a, b in zip(padded, host):
        np.testing.assert_allclose(
            a["predictions"], b["predictions"], rtol=1e-5
        )

    # The route really goes through the CPU commit inside predict():
    # record device_put targets during an on_cpu run vs a device run.
    cpu0 = jax.local_devices(backend="cpu")[0]
    import adanet_tpu.core.estimator as est_mod

    real_device_put = jax.device_put
    cpu_commits = []

    def recording_device_put(tree, device=None, *args, **kwargs):
        if device == cpu0:
            cpu_commits.append(device)
        return real_device_put(tree, device, *args, **kwargs)

    orig = est_mod.jax.device_put
    est_mod.jax.device_put = recording_device_put
    try:
        list(est.predict(input_fn, on_cpu=True))
        assert cpu_commits, "predict(on_cpu=True) never committed to CPU"
        cpu_commits.clear()
        list(est.predict(input_fn, on_cpu=False))
        assert not cpu_commits, "on_cpu=False must not commit to cpu:0"
    finally:
        est_mod.jax.device_put = orig


def test_metric_fn(tmp_path):
    def metric_fn(logits, labels):
        return {
            "mean_abs_error": jnp.mean(
                jnp.abs(logits - jnp.asarray(labels, jnp.float32))
            )
        }

    est = _make(tmp_path, metric_fn=metric_fn, max_iterations=1)
    est.train(linear_dataset(), max_steps=8)
    metrics = est.evaluate(linear_dataset())
    assert "mean_abs_error" in metrics
    assert np.isfinite(metrics["mean_abs_error"])


def test_profile_trace_written(tmp_path):
    """The operator's path: a profile taken from OUTSIDE `train`."""
    est = _make(tmp_path, max_iterations=1)
    trace_dir = str(tmp_path / "profile")
    with jax.profiler.trace(trace_dir):
        est.train(linear_dataset(), max_steps=8)
    traces = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    assert traces  # a trace directory with files was produced
