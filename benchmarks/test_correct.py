"""That `correct` can come out false, at the toy size on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/test_correct.py -q

The control: the reference put in the program's place and computed in the
nearest precision below the one the configuration states (scaled float8
operands for bfloat16 convolutions) fails the limits that sound runs pass. And the
harness, driven whole with the timed path broken underneath, reports
`correct: false`: once with a step that returns its state unchanged, once
with half of the batch left out and the mean taken over the rest. (One
chip: there is no exchange to leave out; training: no token to alter.)
"""

from __future__ import annotations

import json

import jax
import pytest

from benchmarks import check, run, weights
from benchmarks.checks import first_steps_t0
from benchmarks.feed import Feed

ARGS = ["--workload", "rehearsal_tiny", "--seed", "77", "--seconds", "4",
        "--trace", "0"]


def _readings(arith, seed):
    cell = run.Cell("rehearsal_tiny")
    (name,) = cell.cell["training"]
    shapes = {
        "nasnet/" + path if not path.startswith("nasnet/") else path: shape
        for path, shape in cell.factory.weight_shapes(
            cell.config, name
        ).items()
    }
    feed = Feed(seed, cell.traffic, cell.config["sizes"])
    planted = weights.make(seed, 0, shapes)
    return first_steps_t0.follow(
        cell.members[name], planted, feed, arith=arith
    ), cell.cell["limits"]


def _numbers(program, reference, limits):
    return check.limited(
        first_steps_t0.compare(program, reference), limits,
        first_steps_t0.EXACT,
    )


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_in_lower_precision_is_not_correct(seed):
    reference, limits = _readings("f32", seed)
    control, _ = _readings("fp8", seed)
    witness, _ = _readings("bf16", seed)
    assert check.passed(_numbers(witness, reference, limits))
    numbers = _numbers(control, reference, limits)
    assert not check.passed(numbers), numbers
    # The batch statistics alone tell it from the stated precision.
    assert numbers["stats_var_median"][0] > numbers["stats_var_median"][1]


def _last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_sound_run_is_correct(capsys):
    assert run.main(ARGS) == 0
    assert _last_line(capsys)["correct"] is True


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
    capsys, monkeypatch
):
    from adanet_tpu.core.iteration import Iteration

    sound = Iteration.train_step

    def unchanged(self, state, batch, extra_batches=None):
        _, metrics = sound(self, state, batch, extra_batches)
        return state, metrics

    monkeypatch.setattr(Iteration, "train_step", unchanged)
    assert run.main(ARGS) == 0
    result = _last_line(capsys)
    assert result["correct"] is False
    assert result["checks"]["steps"][0] > 0


def test_half_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    from adanet_tpu.core.iteration import Iteration

    sound = Iteration.train_step

    def half(self, state, batch, extra_batches=None):
        batch = jax.tree_util.tree_map(lambda x: x[: len(x) // 2], batch)
        return sound(self, state, batch, extra_batches)

    monkeypatch.setattr(Iteration, "train_step", half)
    assert run.main(ARGS) == 0
    result = _last_line(capsys)
    assert result["correct"] is False
