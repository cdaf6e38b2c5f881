"""AdamW under a global-norm clip, plainly: the recipe of a language-model
member (`sizes`: `learning_rate` reached by a linear warm-up over
`warmup_steps` updates, update n at n / `warmup_steps` of it; `adam_b1`,
`adam_b2`, `adam_eps`; `weight_decay` on every leaf of two dimensions or
more; `clip_norm`).

The state is the two moments and the count; `update` works a leaf at a
time and returns what replaces its arguments, so that the reference never
holds more than two copies of the parameters beside the moments."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init(weights):
    zeros = {k: jnp.zeros_like(v) for k, v in weights.items()}
    return {"mu": zeros, "nu": dict(zeros), "count": 0}


def clip_factor(grads, sizes):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    return jnp.minimum(1.0, sizes["clip_norm"] / jnp.maximum(norm, 1e-30))


@jax.jit
def _leaf(weight, grad, mu, nu, factor, count, rate, b1, b2, eps, decay):
    grad = grad * factor
    mu = b1 * mu + (1.0 - b1) * grad
    nu = b2 * nu + (1.0 - b2) * jnp.square(grad)
    step = (mu / (1.0 - b1**count)) / (
        jnp.sqrt(nu / (1.0 - b2**count)) + eps
    )
    return weight - rate * (step + decay * weight), mu, nu


def update(weights, grads, state, sizes):
    """One step, in place of its arguments: (weights, state). `grads` is
    emptied as it is used."""
    factor = clip_factor(grads, sizes)
    count = state["count"] + 1
    rate = sizes["learning_rate"] * min(1.0, count / sizes["warmup_steps"])
    for key in list(weights):
        decay = sizes["weight_decay"] if weights[key].ndim >= 2 else 0.0
        weights[key], state["mu"][key], state["nu"][key] = _leaf(
            weights[key], grads.pop(key), state["mu"][key], state["nu"][key],
            factor, jnp.float32(count), rate,
            sizes["adam_b1"], sizes["adam_b2"], sizes["adam_eps"], decay,
        )
    state["count"] = count
    return weights, state
