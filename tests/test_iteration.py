"""Engine tests: iteration build/train/eval/select/freeze.

Covers the behavior the reference exercises in
adanet/core/iteration_test.py and candidate_test.py, re-cast for the
functional engine.
"""

import jax
import numpy as np
import optax
import pytest

from adanet_tpu.core.heads import RegressionHead
from adanet_tpu.core.iteration import IterationBuilder
from adanet_tpu.ensemble import (
    AllStrategy,
    ComplexityRegularizedEnsembler,
    GrowStrategy,
    MeanEnsembler,
    SoloStrategy,
)

from helpers import DNNBuilder, linear_dataset


def _builder_factory(decay=0.9, ensemblers=None, strategies=None):
    return IterationBuilder(
        head=RegressionHead(),
        ensemblers=ensemblers
        or [ComplexityRegularizedEnsembler(optimizer=optax.sgd(0.05))],
        ensemble_strategies=strategies or [GrowStrategy()],
        adanet_loss_decay=decay,
    )


def _sample_batch():
    return next(linear_dataset()())


def test_build_iteration_names_and_members():
    it = _builder_factory(
        strategies=[GrowStrategy(), SoloStrategy(), AllStrategy()]
    ).build_iteration(
        0, [DNNBuilder("dnn", 1), DNNBuilder("deep", 2)], None
    )
    names = it.candidate_names()
    assert names == [
        "t0_dnn_grow_complexity_regularized",
        "t0_deep_grow_complexity_regularized",
        "t0_dnn_solo_complexity_regularized",
        "t0_deep_solo_complexity_regularized",
        "t0_all_complexity_regularized",
    ]
    all_spec = it.ensemble_specs[-1]
    assert len(all_spec.members) == 2


def test_train_step_reduces_loss():
    it = _builder_factory().build_iteration(0, [DNNBuilder("dnn", 1)], None)
    state = it.init_state(jax.random.PRNGKey(0), _sample_batch())
    batches = list(linear_dataset()())
    first_loss = None
    metrics = None
    for _ in range(20):
        for batch in batches:
            state, metrics = it.train_step(state, batch)
            if first_loss is None:
                first_loss = float(metrics["adanet_loss/t0_dnn_grow_complexity_regularized"])
    final_loss = float(metrics["adanet_loss/t0_dnn_grow_complexity_regularized"])
    assert final_loss < first_loss
    assert int(state.iteration_step) == 20 * len(batches)
    assert int(state.subnetworks["dnn"].step) == 20 * len(batches)


def test_best_candidate_selection_and_freeze():
    it = _builder_factory(strategies=[GrowStrategy()]).build_iteration(
        0, [DNNBuilder("good", 2), DNNBuilder("nan", 1, nan_logits=True)], None
    )
    state = it.init_state(jax.random.PRNGKey(0), _sample_batch())
    for batch in linear_dataset()():
        state, _ = it.train_step(state, batch)
    emas = it.ema_losses(state)
    assert emas["t0_nan_grow_complexity_regularized"] == float("inf")  # quarantined
    assert np.isfinite(emas["t0_good_grow_complexity_regularized"])
    best = it.best_candidate_index(state)
    assert it.candidate_names()[best] == "t0_good_grow_complexity_regularized"

    frozen = it.freeze_candidate(state, "t0_good_grow_complexity_regularized", _sample_batch())
    assert frozen.iteration_number == 0
    assert len(frozen.weighted_subnetworks) == 1
    fs = frozen.weighted_subnetworks[0].subnetwork
    assert fs.name == "good"
    assert fs.shared == {"num_layers": 2}
    arch = frozen.architecture
    assert arch.subnetworks == ((0, "good"),)


def test_all_candidates_nan_raises():
    it = _builder_factory().build_iteration(
        0, [DNNBuilder("nan", 1, nan_logits=True)], None
    )
    state = it.init_state(jax.random.PRNGKey(0), _sample_batch())
    for batch in linear_dataset()():
        state, _ = it.train_step(state, batch)
    with pytest.raises(FloatingPointError):
        it.best_candidate_index(state)


def test_second_iteration_grows_on_frozen_ensemble():
    builder_factory = _builder_factory()
    it0 = builder_factory.build_iteration(0, [DNNBuilder("dnn", 1)], None)
    state0 = it0.init_state(jax.random.PRNGKey(0), _sample_batch())
    for batch in linear_dataset()():
        state0, _ = it0.train_step(state0, batch)
    frozen = it0.freeze_candidate(state0, "t0_dnn_grow_complexity_regularized", _sample_batch())

    it1 = builder_factory.build_iteration(
        1, [DNNBuilder("dnn2", 2)], frozen
    )
    # Candidate 0 is the carried-over previous ensemble; the grow candidate
    # (frozen member + new builder) follows.
    assert it1.ensemble_specs[0].name == frozen.name
    assert not it1.ensemble_specs[0].track_ema
    spec = it1.ensemble_specs[1]
    assert spec.name == "t1_dnn2_grow_complexity_regularized"
    assert len(spec.members) == 2
    assert spec.architecture.subnetworks == ((0, "dnn"), (1, "dnn2"))

    state1 = it1.init_state(jax.random.PRNGKey(1), _sample_batch())
    for batch in linear_dataset()():
        state1, metrics = it1.train_step(state1, batch)
    assert np.isfinite(float(metrics["adanet_loss/t1_dnn2_grow_complexity_regularized"]))

    frozen1 = it1.freeze_candidate(state1, "t1_dnn2_grow_complexity_regularized", _sample_batch())
    assert [ws.subnetwork.name for ws in frozen1.weighted_subnetworks] == [
        "dnn",
        "dnn2",
    ]


def test_warm_start_skipped_across_different_ensemblers():
    """Weights learned by one ensembler must not warm-start another."""
    from adanet_tpu.ensemble import MixtureWeightType

    scalar = ComplexityRegularizedEnsembler(
        optimizer=optax.sgd(0.05), warm_start_mixture_weights=True
    )
    matrix = ComplexityRegularizedEnsembler(
        optimizer=optax.sgd(0.05),
        mixture_weight_type=MixtureWeightType.MATRIX,
        warm_start_mixture_weights=True,
        name="matrix",
    )
    fac = _builder_factory(ensemblers=[scalar, matrix])
    it0 = fac.build_iteration(0, [DNNBuilder("dnn", 1)], None)
    state0 = it0.init_state(jax.random.PRNGKey(0), _sample_batch())
    frozen = it0.freeze_candidate(
        state0, "t0_dnn_grow_complexity_regularized", _sample_batch()
    )

    it1 = fac.build_iteration(1, [DNNBuilder("dnn2", 1)], frozen)
    state1 = it1.init_state(jax.random.PRNGKey(1), _sample_batch())
    # The kept member's weight in the MATRIX spec must be a fresh 2-D init,
    # not the scalar learned by the previous (scalar) ensembler.
    w0 = state1.ensembles["t1_dnn2_grow_matrix"].params["weights"][0]
    assert w0.ndim == 2
    # The scalar spec does warm-start from the scalar previous weight.
    w0s = state1.ensembles["t1_dnn2_grow_complexity_regularized"].params[
        "weights"
    ][0]
    assert w0s.ndim == 0
    state1, metrics = it1.train_step(state1, _sample_batch())
    assert np.isfinite(float(metrics["adanet_loss/t1_dnn2_grow_matrix"]))


def test_eval_step_metrics():
    it = _builder_factory().build_iteration(0, [DNNBuilder("dnn", 1)], None)
    state = it.init_state(jax.random.PRNGKey(0), _sample_batch())
    results = it.eval_step(state, _sample_batch())
    assert "t0_dnn_grow_complexity_regularized" in results
    assert "average_loss" in results["t0_dnn_grow_complexity_regularized"]
    assert "subnetwork/dnn" in results


def test_mean_ensembler_and_multiple_ensemblers():
    it = _builder_factory(
        ensemblers=[
            ComplexityRegularizedEnsembler(optimizer=optax.sgd(0.05)),
            MeanEnsembler(),
        ]
    ).build_iteration(0, [DNNBuilder("dnn", 1)], None)
    names = it.candidate_names()
    assert "t0_dnn_grow_complexity_regularized" in names
    assert "t0_dnn_grow_mean" in names
    state = it.init_state(jax.random.PRNGKey(0), _sample_batch())
    state, metrics = it.train_step(state, _sample_batch())
    assert np.isfinite(float(metrics["adanet_loss/t0_dnn_grow_mean"]))


# ------------------------------------------------ the state's template


def _ensemblers():
    """Every ensembler of `adanet_tpu/ensemble/`, each mixture-weight
    type and the bias among them."""
    from adanet_tpu.ensemble import MixtureWeightType

    def weighted(weight_type, **kwargs):
        return ComplexityRegularizedEnsembler(
            optimizer=optax.adam(0.05),
            mixture_weight_type=weight_type,
            warm_start_mixture_weights=True,
            **kwargs,
        )

    return {
        "scalar": weighted(MixtureWeightType.SCALAR),
        "vector_bias": weighted(MixtureWeightType.VECTOR, use_bias=True),
        "matrix": weighted(MixtureWeightType.MATRIX),
        "no_optimizer": ComplexityRegularizedEnsembler(),
        "mean": MeanEnsembler(),
    }


def _template_of(state):
    return jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), state
    )


@pytest.mark.parametrize("ensembler", sorted(_ensemblers()))
def test_state_template_is_the_shape_of_init_state(ensembler):
    """`state_template` is `init_state` with every value left out: same
    tree, same shapes, same dtypes, and no `module.init` run for real."""
    fac = _builder_factory(
        ensemblers=[_ensemblers()[ensembler]],
        strategies=[GrowStrategy(), AllStrategy()],
    )
    builders = [DNNBuilder("dnn", 1), DNNBuilder("deep", 2)]
    traced = fac.build_iteration(0, builders, None)
    template = traced.state_template(_sample_batch())
    assert traced.state_template_traces == 1
    assert all(
        isinstance(leaf, jax.ShapeDtypeStruct)
        for leaf in jax.tree_util.tree_leaves(template)
    )
    # Remembered by the batch's shapes and dtypes.
    assert traced.state_template(_sample_batch()) is template
    assert traced.state_template_traces == 1

    real = fac.build_iteration(0, builders, None)
    state = real.init_state(jax.random.PRNGKey(0), _sample_batch())
    assert template == _template_of(state)
    # A real init records the template of what it returned: an instance
    # that initialized for real never traces.
    assert real.state_template(_sample_batch()) == template
    assert real.state_template_traces == 0


def test_state_template_with_frozen_member_and_warm_started_weights():
    """Iteration 1: a frozen member's variables ride in the state, the
    kept member's mixture weight and the bias are warm-started from the
    previous ensemble, and the carried-over candidate's EMA is seeded."""
    from adanet_tpu.ensemble import MixtureWeightType

    fac = _builder_factory(
        ensemblers=[
            ComplexityRegularizedEnsembler(
                optimizer=optax.sgd(0.05),
                mixture_weight_type=MixtureWeightType.VECTOR,
                warm_start_mixture_weights=True,
                use_bias=True,
            )
        ]
    )
    it0 = fac.build_iteration(0, [DNNBuilder("dnn", 1)], None)
    state0 = it0.init_state(jax.random.PRNGKey(0), _sample_batch())
    for batch in linear_dataset()():
        state0, _ = it0.train_step(state0, batch)
    frozen = it0.freeze_candidate(
        state0, "t0_dnn_grow_complexity_regularized", _sample_batch()
    )
    assert frozen.ensembler_params["weights"]  # something to warm-start

    traced = fac.build_iteration(1, [DNNBuilder("dnn2", 2)], frozen)
    template = traced.state_template(_sample_batch())
    real = fac.build_iteration(1, [DNNBuilder("dnn2", 2)], frozen)
    state1 = real.init_state(jax.random.PRNGKey(1), _sample_batch())
    assert len(state1.frozen) == 1
    assert template == _template_of(state1)
    # Another batch shape is another template.
    half = jax.tree_util.tree_map(lambda x: x[:8], _sample_batch())
    assert traced.state_template(half) == template  # no leaf holds a batch
    assert traced.state_template_traces == 2


def test_state_template_names_the_builder_that_cannot_trace():
    """No silent fall-back to the eager init: an initializer that reads
    a value is an error that names its builder."""
    import flax.linen as nn
    import jax.numpy as jnp

    from adanet_tpu.subnetwork import Subnetwork

    class Concretizing(nn.Module):
        @nn.compact
        def __call__(self, features, training: bool = False):
            x = jnp.asarray(features["x"], jnp.float32)
            scale = float(jnp.mean(x))  # reads a value
            logits = nn.Dense(1)(x) * scale
            return Subnetwork(last_layer=x, logits=logits, complexity=1.0)

    class Bad(DNNBuilder):
        def build_subnetwork(self, logits_dimension, previous_ensemble=None):
            return Concretizing()

    it = _builder_factory().build_iteration(0, [Bad("reads_values")], None)
    with pytest.raises(TypeError, match="builder 'reads_values'"):
        it.state_template(_sample_batch())
    assert it.state_template_traces == 0
