"""Shared tiny fleet configuration for the fleet tests and runner.

One 2-trial fleet config used by the tier-1 fleet gate, the chaos
runner, and the parent test's oracle/resume runs, so "a SIGKILLed fleet
resumes to the oracle fleet's winner and champion architecture" is a
meaningful assertion. Import-side-effect free (no jax config): the
runner configures its own backend first, in-process tests ride
conftest's.

The two trials share the generator, seed, and step budget and differ
ONLY in adanet lambda/beta: `reg_lo` is unregularized, `reg_hi` is
heavily over-regularized (its mixture-weight training is dominated by
the L1 penalty). Under the fleet's uniform comparator `reg_lo` wins
deterministically — and `reg_hi` doubles as the "a-priori single
search" baseline config for the equal-budget gate.
"""

import os

import numpy as np
import optax

import adanet_tpu
from adanet_tpu.ensemble import ComplexityRegularizedEnsembler
from adanet_tpu.examples import simple_dnn
from adanet_tpu.fleet import Comparator, FleetController, TrialSpec
from adanet_tpu.subnetwork import SimpleGenerator

from helpers import DNNBuilder
from multihost_rr_runner import full_batches  # noqa: F401  (re-export)

#: Per-iteration step budget and the cumulative rung schedule.
MAX_ITERATION_STEPS = 6
RUNGS = (1, 2)

#: Uniform comparator strengths (applied to every trial alike).
COMPARATOR_LAMBDA = 0.01
COMPARATOR_BETA = 0.001

#: The over-regularized baseline trial's strengths.
HI_LAMBDA = 2.0
HI_BETA = 0.5


def input_fn():
    return iter(full_batches())


def _make_generator():
    return SimpleGenerator([DNNBuilder("a", 1), DNNBuilder("b", 2)])


def _trial(
    trial_id: str,
    adanet_lambda: float,
    adanet_beta: float,
    make_generator=_make_generator,
    generator_id: str = "tests.helpers/dnn_a1_b2",
    max_iteration_steps: int = MAX_ITERATION_STEPS,
    random_seed: int = 42,
):
    return TrialSpec(
        trial_id=trial_id,
        make_head=adanet_tpu.RegressionHead,
        make_generator=make_generator,
        generator_id=generator_id,
        max_iteration_steps=max_iteration_steps,
        random_seed=random_seed,
        adanet_lambda=adanet_lambda,
        adanet_beta=adanet_beta,
        make_ensembler_optimizer=lambda: optax.sgd(0.05),
    )


def make_trials():
    return [
        _trial("reg_hi", HI_LAMBDA, HI_BETA),
        _trial("reg_lo", 0.0, 0.0),
    ]


def make_comparator(eval_steps: int = 4):
    return Comparator(
        input_fn,
        eval_steps=eval_steps,
        adanet_lambda=COMPARATOR_LAMBDA,
        adanet_beta=COMPARATOR_BETA,
    )


def build_fleet(work_dir: str, **kwargs) -> FleetController:
    defaults = dict(
        rung_iterations=RUNGS,
        survivor_fraction=0.5,
        comparator=make_comparator(),
        workers=1,
    )
    defaults.update(kwargs)
    return FleetController(
        make_trials(), input_fn, work_dir=work_dir, **defaults
    )


def build_single_search(model_dir: str, max_iterations: int, **kwargs):
    """The a-priori single search at the fleet's TOTAL step budget: the
    `reg_hi` config (what an operator would have launched without the
    fleet), trained for `max_iterations` iterations."""
    defaults = dict(
        head=adanet_tpu.RegressionHead(),
        subnetwork_generator=_make_generator(),
        max_iteration_steps=MAX_ITERATION_STEPS,
        ensemblers=[
            ComplexityRegularizedEnsembler(
                optimizer=optax.sgd(0.05),
                adanet_lambda=HI_LAMBDA,
                adanet_beta=HI_BETA,
            )
        ],
        max_iterations=max_iterations,
        model_dir=model_dir,
        log_every_steps=0,
    )
    defaults.update(kwargs)
    return adanet_tpu.Estimator(**defaults)


# ---------------------------------------------- the full gate (RUN_SLOW)

FULL_GATE_ITERATION_STEPS = 8


def build_full_gate(root: str):
    """The 4-trial fleet of the slow acceptance gate, its comparator, and
    a factory for the a-priori single search (`lam_hi`'s config) over
    the same data: `(controller, comparator, make_single, input_fn)`.

    Trials vary only (lambda, beta) over one `simple_dnn` search space;
    rungs 1 -> 2 iterations with half culled at the boundary."""
    rng = np.random.RandomState(0)
    features = rng.randn(512, 8).astype(np.float32)
    labels = features @ rng.randn(8, 1).astype(np.float32)

    def gate_input_fn():
        i = 0
        while True:
            lo = (i * 64) % 512
            yield features[lo : lo + 64], labels[lo : lo + 64]
            i += 1

    def make_generator():
        return simple_dnn.Generator(
            optimizer_fn=lambda: optax.sgd(0.02), layer_size=16
        )

    gate = dict(
        make_generator=make_generator,
        generator_id="simple_dnn/layer_size=16/lr=0.02",
        max_iteration_steps=FULL_GATE_ITERATION_STEPS,
        random_seed=1,
    )
    comparator = Comparator(
        gate_input_fn,
        eval_steps=8,
        adanet_lambda=COMPARATOR_LAMBDA,
        adanet_beta=COMPARATOR_BETA,
    )
    controller = FleetController(
        [
            _trial("lam_hi", HI_LAMBDA, HI_BETA, **gate),
            _trial("lam_mid", 0.1, 0.01, **gate),
            _trial("lam_lo", 0.0, 0.0, **gate),
            _trial("lam_tiny", 0.01, 0.001, **gate),
        ],
        gate_input_fn,
        work_dir=os.path.join(root, "fleet"),
        rung_iterations=RUNGS,
        survivor_fraction=0.5,
        comparator=comparator,
        workers=1,
    )

    def make_single(max_iterations: int):
        return build_single_search(
            os.path.join(root, "single"),
            max_iterations,
            subnetwork_generator=make_generator(),
            max_iteration_steps=FULL_GATE_ITERATION_STEPS,
            random_seed=1,
        )

    return controller, comparator, make_single, gate_input_fn
