"""The published training recipe, plainly: what happens to a gradient
between `jax.grad` and the weights.

"momentum" (improve_nas; NASNet-A on CIFAR): clip by global norm, L2 on
kernels, heavy-ball momentum, single-period cosine decay of the rate.

`first_gradient` recovers, from what the program's optimizer holds after
one step, the norm of each leaf of the clipped gradient it was given.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def _only_momentum(sizes):
    if sizes["optimizer"] != "momentum":
        raise SystemExit(
            "benchmarks: reference/optimizers.py has no %r recipe"
            % sizes["optimizer"]
        )


def learning_rate(sizes, count):
    rate = sizes["initial_learning_rate"]
    if sizes.get("cosine_decay_steps"):
        progress = min(count, sizes["cosine_decay_steps"]) / float(
            sizes["cosine_decay_steps"]
        )
        rate *= 0.5 * (1.0 + math.cos(math.pi * progress))
    return rate


def init(weights, sizes):
    _only_momentum(sizes)
    return {"moment": {k: jnp.zeros_like(v) for k, v in weights.items()}}


def _clip(grads, sizes):
    if not sizes.get("clip_gradients"):
        return grads
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    factor = jnp.minimum(1.0, sizes["clip_gradients"] / norm)
    return {k: g * factor for k, g in grads.items()}


def update(weights, grads, state, rate, sizes):
    """One step. Returns (weights, state, the clipped gradients)."""
    clipped = _clip(grads, sizes)
    new_weights, moment = {}, {}
    for key, grad in clipped.items():
        if key.endswith("/kernel") and sizes.get("weight_decay"):
            grad = grad + sizes["weight_decay"] * weights[key]
        moment[key] = grad + sizes["momentum"] * state["moment"][key]
        new_weights[key] = weights[key] - rate * moment[key]
    return new_weights, {"moment": moment}, clipped


def first_gradient(held, start, sizes):
    """{path: norm of the clipped gradient} from the program's optimizer
    state after its first step: `held["trace"]` is its momentum, which
    optax keeps as the gradient with the kernels' L2 term added."""
    _only_momentum(sizes)
    out = {}
    for path, moment in held["trace"].items():
        if path.endswith("/kernel") and sizes.get("weight_decay"):
            moment = moment - sizes["weight_decay"] * start[path]
        out[path] = float(np.linalg.norm(moment))
    return out
