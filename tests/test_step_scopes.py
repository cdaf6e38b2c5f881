"""The named scopes of the train step: what a profile is split by.

`core/iteration.py` opens a `jax.named_scope` around each candidate's
loss (`candidate.<name>`), its optimizer update (`optimizer.<name>`),
each ensemble's loss and update (`ensemble.<name>`,
`ensemble_optimizer.<name>`), each frozen member's forward
(`frozen.t<i>_<name>`) and the EMA tail (`step.metrics`). The toy
iteration's step, lowered on the CPU, must carry them in its `op_name`s,
each as ONE path component, and must be the same program but for those
names (`benchmarks/scope_reduce.py` reads them back from a profile).
"""

import contextlib
import re

import jax
import optax
import pytest

from adanet_tpu.core import iteration as iteration_lib
from adanet_tpu.core.heads import RegressionHead
from adanet_tpu.core.iteration import IterationBuilder, scope_name
from adanet_tpu.ensemble import ComplexityRegularizedEnsembler, GrowStrategy

from helpers import DNNBuilder, linear_dataset

# A builder's name is the user's: one with every separator in it.
NAMES = ("dnn", "deep (2x)/v:1")


def _lowered(iteration_number=0):
    """The toy iteration's step (at iteration 1: one frozen member
    beside two candidates), lowered on the CPU."""
    factory = IterationBuilder(
        head=RegressionHead(),
        ensemblers=[ComplexityRegularizedEnsembler(optimizer=optax.sgd(0.05))],
        ensemble_strategies=[GrowStrategy()],
    )
    batch = next(linear_dataset()())
    frozen = None
    for t in range(iteration_number + 1):
        builders = [DNNBuilder(NAMES[0], 1), DNNBuilder(NAMES[1], 2)]
        iteration = factory.build_iteration(t, builders, frozen)
        state = iteration.init_state(jax.random.PRNGKey(t), batch)
        if t < iteration_number:
            state, _ = iteration.train_step(state, batch)
            frozen = iteration.freeze_candidate(
                state, iteration.candidate_names()[0], batch
            )
    return iteration, iteration._train_step._jit.lower(state, batch, {})


def _op_names(lowered):
    text = lowered.as_text(debug_info=True)
    return sorted(set(re.findall(r'loc\("(jit\([^"]+)"', text)))


@pytest.fixture(scope="module")
def op_names():
    return {t: _op_names(_lowered(t)[1]) for t in (0, 1)}


def _components(op_names):
    return {part for name in op_names for part in name.split("/")}


@pytest.mark.parametrize("kind, wrapped", [
    ("candidate", "jvp(%s)"),
    ("candidate", "transpose(jvp(%s))"),
    ("optimizer", "%s"),
    ("ensemble", "jvp(%s)"),
    ("ensemble_optimizer", "%s"),
])
def test_step_op_names_carry_the_scope(op_names, kind, wrapped):
    iteration, _ = _lowered(0)
    names = (
        NAMES if kind in ("candidate", "optimizer")
        else iteration.candidate_names()
    )
    parts = _components(op_names[0])
    assert "jit(adanet_train_step)" in parts
    for name in names:
        assert wrapped % scope_name(kind, name) in parts


def test_frozen_members_and_metrics_tail_are_scoped(op_names):
    parts = _components(op_names[1])
    assert scope_name("frozen", "t0_" + NAMES[0]) in parts
    assert "step.metrics" in parts
    # The previous ensemble is re-scored, not re-trained: a forward only.
    assert any(p.startswith("ensemble.t0_") for p in parts)


@pytest.mark.parametrize("name", NAMES + ("a/b", "x(y)", "k:v", "t0_ok-1.2"))
def test_a_scope_is_one_path_component(name):
    scope = scope_name("candidate", name)
    assert scope.startswith("candidate.")
    assert not set("/():") & set(scope)
    assert scope_name("candidate", "t0_ok-1.2") == "candidate.t0_ok-1.2"


def test_scopes_change_nothing_but_op_names(monkeypatch):
    """The program text without debug info (what JAX's compile cache
    keys on) is the same with every scope taken away."""
    scoped = _lowered(1)[1].as_text()
    monkeypatch.setattr(
        iteration_lib.jax, "named_scope",
        lambda name: contextlib.nullcontext(),
    )
    bare = _lowered(1)[1]
    assert not [n for n in _op_names(bare) if "candidate." in n]
    assert bare.as_text() == scoped
