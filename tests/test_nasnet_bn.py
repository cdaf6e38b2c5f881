"""Unit tests for the warmup-scheduled BatchNorm statistics
(models/nasnet.py `_DebiasedBatchNorm`) — the round-5 fix for the
round-4 flagship-gate failure (docs/nasnet_gate_rootcause.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adanet_tpu.models.nasnet import (
    NasNetA,
    _DebiasedBatchNorm,
    cifar_config,
)
from adanet_tpu.observability import metrics as metrics_lib


def _train_stats(momentum_updates, warmup=10.0, momentum=0.9997):
    """Replays the module's schedule over a sequence of scalar batch
    means; returns the EMA trajectory an oracle computes."""
    ema = 0.0
    for count, value in enumerate(momentum_updates):
        m = min(momentum, count / (count + warmup))
        ema = m * ema + (1.0 - m) * value
    return ema


def _apply_n(bn, variables, batches, training=True):
    for batch in batches:
        out, updates = bn.apply(
            variables, batch, training, mutable=["batch_stats"]
        )
        variables = {**variables, "batch_stats": updates["batch_stats"]}
    return out, variables


def test_eval_statistics_unbiased_from_first_update():
    """One training update must make eval statistics exactly the first
    batch's statistics (EMA weights sum to 1) — the property whose
    absence at momentum 0.9997 produced the 0.19-accuracy flagship gate."""
    bn = _DebiasedBatchNorm()
    rng = np.random.RandomState(0)
    x = jnp.asarray(5.0 + 2.0 * rng.randn(32, 4, 4, 3), jnp.float32)
    variables = bn.init(jax.random.PRNGKey(0), x, True)
    _, variables = _apply_n(bn, variables, [x])

    stats = variables["batch_stats"]
    np.testing.assert_allclose(
        np.asarray(stats["mean"]), np.mean(np.asarray(x), (0, 1, 2)),
        rtol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(stats["var"]), np.var(np.asarray(x), (0, 1, 2)),
        rtol=1e-4,
    )
    # Eval on the same batch is now ~zero-mean unit-var * scale + bias.
    y = bn.apply(variables, x, False)
    assert abs(float(jnp.mean(y))) < 1e-4
    assert abs(float(jnp.std(y)) - 1.0) < 1e-2


def test_eval_matches_recent_batches_on_short_runs():
    """After N << 33k updates the statistics track the recent window, not
    a 91%-initialization blend: eval output on the data distribution is
    normalized (the broken version left mean ~0.9*5=4.5 unnormalized)."""
    bn = _DebiasedBatchNorm()
    rng = np.random.RandomState(1)
    batches = [
        jnp.asarray(5.0 + 2.0 * rng.randn(16, 2, 2, 3), jnp.float32)
        for _ in range(50)
    ]
    variables = bn.init(jax.random.PRNGKey(0), batches[0], True)
    _, variables = _apply_n(bn, variables, batches)
    y = bn.apply(variables, batches[-1], False)
    assert abs(float(jnp.mean(y))) < 0.2
    assert abs(float(jnp.std(y)) - 1.0) < 0.2


def test_momentum_schedule_caps_at_reference_decay():
    """The per-update momentum converges to slim's 0.9997 for long
    schedules (count >= ~33k) — reference fidelity is preserved."""
    warmup, momentum = 10.0, 0.9997
    count = 40000.0
    assert min(momentum, count / (count + warmup)) == momentum
    count = 300.0
    assert min(momentum, count / (count + warmup)) < 0.97


def test_oracle_trajectory_matches_module():
    """The module's scalar EMA equals the python oracle replay."""
    bn = _DebiasedBatchNorm()
    values = [1.0, 3.0, -2.0, 0.5, 4.0]
    batches = [jnp.full((8, 2, 2, 1), v, jnp.float32) for v in values]
    variables = bn.init(jax.random.PRNGKey(0), batches[0], True)
    _, variables = _apply_n(bn, variables, batches)
    got = float(variables["batch_stats"]["mean"][0])
    want = _train_stats(values)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert float(variables["batch_stats"]["count"]) == len(values)


def test_eval_before_training_uses_init_stats():
    """Never-trained statistics fall back to (0, 1) like nn.BatchNorm."""
    bn = _DebiasedBatchNorm()
    x = jnp.asarray(np.random.RandomState(2).randn(4, 2, 2, 3), jnp.float32)
    variables = bn.init(jax.random.PRNGKey(0), x, True)
    y = bn.apply(variables, x, False)
    np.testing.assert_allclose(
        np.asarray(y),
        np.asarray(x) / np.sqrt(1.0 + 1e-3),
        rtol=1e-5,
        atol=1e-5,
    )


def test_bf16_input_float32_statistics():
    """bf16 activations keep f32 statistics (TPU-first dtype rule)."""
    bn = _DebiasedBatchNorm()
    x = jnp.asarray(
        np.random.RandomState(3).randn(8, 2, 2, 4), jnp.bfloat16
    )
    variables = bn.init(jax.random.PRNGKey(0), x, True)
    _, variables = _apply_n(bn, variables, [x])
    assert variables["batch_stats"]["mean"].dtype == jnp.float32
    assert variables["batch_stats"]["var"].dtype == jnp.float32
    y = bn.apply(variables, x, False)
    assert y.dtype == jnp.float32


@pytest.mark.parametrize(
    "mean, rtol", [(0.0, 1e-5), (5.0, 1e-4), (20.0, 1e-3)],
    ids=["ratio0", "ratio6.25", "ratio100"],
)
def test_one_pass_statistics_against_float64(mean, rtol):
    """The module's contract for its one-pass variance
    `max(E[x*x] - E[x]^2, 0)` in float32: against `np.var` in float64 the
    relative error stays under 1e-5 at mean^2/var 0, 1e-4 at 6.25 and
    1e-3 at 100 (std 2, means 0, 5 and 20); the mean is exact to 1e-6
    of the data's scale."""
    bn = _DebiasedBatchNorm()
    rng = np.random.RandomState(4)
    x = jnp.asarray(mean + 2.0 * rng.randn(256, 8, 8, 4), jnp.float32)
    variables = bn.init(jax.random.PRNGKey(0), x, True)
    _, variables = _apply_n(bn, variables, [x])
    stats = variables["batch_stats"]
    x64 = np.asarray(x, np.float64)  # the float32 values, exactly
    np.testing.assert_allclose(
        np.asarray(stats["mean"]),
        x64.mean((0, 1, 2)),
        rtol=0,
        atol=2e-6 * (1 + mean),
    )
    np.testing.assert_allclose(
        np.asarray(stats["var"]), x64.var((0, 1, 2)), rtol=rtol
    )


def test_constant_input_has_zero_variance_not_negative():
    """Where every value is the same, `E[x*x] - E[x]^2` is rounding
    noise of either sign, small beside the mean's square: the clamp
    keeps the variance at 0 or above, and the output finite."""
    bn = _DebiasedBatchNorm()
    x = jnp.full((64, 4, 4, 3), 3.1, jnp.float32)
    variables = bn.init(jax.random.PRNGKey(0), x, True)
    y, variables = _apply_n(bn, variables, [x])
    var = np.asarray(variables["batch_stats"]["var"])
    assert (var >= 0).all() and (var < 1e-4 * 3.1**2).all()
    assert np.isfinite(np.asarray(y)).all()


def test_gradient_equals_the_two_pass_formula():
    """Autodiff of the one-pass statistics is the gradient of the
    two-pass batch norm written out here (`mean((x - mean)^2)`): the
    term that the one-pass form drops is `sum(x - mean)`, zero."""
    bn = _DebiasedBatchNorm()
    rng = np.random.RandomState(5)
    x = jnp.asarray(5.0 + 2.0 * rng.randn(256, 8, 8, 4), jnp.float32)
    weights = jnp.asarray(rng.randn(256, 8, 8, 4), jnp.float32)
    variables = bn.init(jax.random.PRNGKey(0), x, True)
    params = {
        "scale": jnp.asarray(1.0 + 0.1 * rng.randn(4), jnp.float32),
        "bias": jnp.asarray(0.1 * rng.randn(4), jnp.float32),
    }

    def module(params, x):
        y, _ = bn.apply(
            {**variables, "params": params}, x, True, mutable=["batch_stats"]
        )
        return jnp.sum(y * weights)

    def two_pass(params, x):
        mean = jnp.mean(x, (0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
        y = (x - mean) * jax.lax.rsqrt(var + bn.epsilon)
        return jnp.sum((y * params["scale"] + params["bias"]) * weights)

    got = jax.grad(module, argnums=(0, 1))(params, x)
    want = jax.grad(two_pass, argnums=(0, 1))(params, x)
    for g, w in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    ):
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=0, atol=1e-5 * scale
        )


@pytest.mark.parametrize("training, per_batch_norm", [(True, 1), (False, 0)])
def test_train_sites_counter_counts_training_batch_norms(
    training, per_batch_norm
):
    """`nasnet.batch_norm.train_sites` rises by one for every batch norm
    traced in training mode, which is every one the parameter tree holds
    (246 for the benchmark's NASNet-A 6@768: all of a `NasNetA`
    normalise in every trace), and by none in eval mode."""
    model = NasNetA(cifar_config())
    images = jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32)
    rngs = {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)}
    variables = jax.eval_shape(
        lambda: model.init(rngs, jnp.zeros(images.shape), training=True)
    )
    batch_norms = sum(
        path[-1].key == "scale"
        for path, _ in jax.tree_util.tree_leaves_with_path(
            variables["params"]
        )
    )
    assert batch_norms == 246

    counter = metrics_lib.registry().counter("nasnet.batch_norm.train_sites")
    before = counter.value
    jax.eval_shape(
        lambda v, x: model.apply(
            v,
            x,
            training=training,
            mutable=["batch_stats", "schedule"],
            rngs={"dropout": rngs["dropout"]},
        ),
        variables,
        images,
    )
    assert counter.value - before == per_batch_norm * batch_norms
