"""The plain references against the program's own modules, at small sizes
on the CPU, on the benchmark's seeded weights.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/test_reference.py -q

NASNet-A's forward pass in float32 agrees to rounding, and so do the
statistics of every batch norm's batch, which the staged pass hands out,
with those the program keeps after one update. And its gradients, which
the reference computes stage by stage, equal `jax.grad` of the whole.
"""

from __future__ import annotations

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import ckpt_io, weights
from benchmarks.reference import nasnet_a
from benchmarks.reference.layers import cross_entropy


def _planted(module, images, seed=7):
    key = jax.random.PRNGKey(0)
    variables = module.init(
        {"params": key, "dropout": key}, images, training=True
    )
    tree = flax.core.unfreeze(variables["params"])
    made = weights.make(
        seed, 0, {k: v.shape for k, v in ckpt_io.flatten(tree).items()}
    )
    for path, value in made.items():
        ckpt_io.set_leaf(tree, path, value)
    return {**variables, "params": tree}, made


def _images(batch, size):
    return np.random.default_rng(0).standard_normal(
        (batch, size, size, 3), dtype=np.float32
    )


@pytest.mark.parametrize("cells,filters,size", [(3, 4, 16), (6, 8, 32)])
def test_nasnet_forward_agrees_with_the_program(cells, filters, size):
    from adanet_tpu.models.nasnet import NasNetA, NasNetConfig

    module = NasNetA(NasNetConfig(
        num_cells=cells, num_conv_filters=filters,
        compute_dtype=jnp.float32, drop_path_keep_prob=1.0,
    ))
    images = _images(8, size)
    variables, made = _planted(module, images)
    (logits, aux, _), held = module.apply(
        variables, images, training=True,
        rngs={"dropout": jax.random.PRNGKey(2)},
        mutable=["batch_stats", "schedule"],
    )
    sizes = {"num_cells": cells, "num_conv_filters": filters,
             "label_smoothing": 0.1, "aux_head_weight": 0.4}
    with jax.default_matmul_precision("highest"):
        ours, ours_aux = nasnet_a.forward(
            {"nasnet/" + k: jnp.asarray(v) for k, v in made.items()},
            images, sizes, "f32",
        )
    np.testing.assert_allclose(ours, logits, atol=2e-5)
    if aux is not None:
        np.testing.assert_allclose(ours_aux, aux, atol=5e-5)
    # The program's first update keeps the batch's statistics outright.
    kept = ckpt_io.flatten(flax.core.unfreeze(held["batch_stats"]))
    with jax.default_matmul_precision("highest"):
        _, _, stats = nasnet_a.loss_and_gradients(
            {"nasnet/" + k: jnp.asarray(v) for k, v in made.items()},
            images, np.zeros((8,), np.int32), sizes, "f32",
        )
    assert {k[len("nasnet/"):] + "/mean" for k in stats} == {
        k for k in kept if k.endswith("/mean")
    }
    for name, (mean, var) in stats.items():
        name = name[len("nasnet/"):]
        np.testing.assert_allclose(mean, kept[name + "/mean"], atol=2e-5)
        np.testing.assert_allclose(
            var, kept[name + "/var"], rtol=1e-4, atol=1e-6
        )


def test_nasnet_gradients_by_stage_equal_those_of_the_whole():
    sizes = {"num_cells": 6, "num_conv_filters": 8, "label_smoothing": 0.1,
             "aux_head_weight": 0.4}
    from adanet_tpu.models.nasnet import NasNetA, NasNetConfig

    images = _images(8, 32)
    labels = np.arange(8, dtype=np.int32) % 10
    _, made = _planted(
        NasNetA(NasNetConfig(num_cells=6, num_conv_filters=8)), images
    )
    made = {"nasnet/" + k: jnp.asarray(v) for k, v in made.items()}

    def whole(w):
        logits, aux = nasnet_a.forward(w, images, sizes, "f32")
        return cross_entropy(logits, labels, 0.1) + 0.4 * cross_entropy(
            aux, labels, 0.1
        )

    # Jitted like the stages: the gradients of some leaves (a batch norm's
    # bias ahead of another batch norm) cancel so nearly that XLA's eager
    # and compiled float32 differ by percents on them.
    expected = jax.jit(jax.grad(whole))(made)
    logits, staged, _ = nasnet_a.loss_and_gradients(
        made, images, labels, sizes, "f32"
    )
    assert set(staged) == set(expected)
    for key, value in expected.items():
        np.testing.assert_allclose(
            staged[key], value, atol=1e-4 * float(jnp.max(jnp.abs(value)))
        )
    np.testing.assert_allclose(
        logits, nasnet_a.forward(made, images, sizes, "f32")[0], atol=1e-5
    )
