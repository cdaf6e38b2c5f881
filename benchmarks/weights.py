"""The benchmark's own weights: every leaf from `--seed`, made on the
device in one jitted call, in float32 as the program trains them.

Kernels are normal with variance 1/fan_in; batch-norm scales are 1 + 0.1 n
and every bias 0.1 n (not the usual ones and zeros, so that a scale or a
bias wired to the wrong place shows in the comparison).

Up to `FLAT_DRAW` values the leaves are slices of ONE draw, as they have
been since the first cell. Past it every leaf is a draw of its own from a
key folded with the leaf's index: one draw of 600M normals asks the chip
for 15.2 GB of temporaries (my chip run 3, PR 33), a leaf's draw for a few
times the leaf.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

FLAT_DRAW = 1 << 27


def seed_key(seed: int, stream: int):
    """A key from any whole number, however large."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


def _plan(shapes):
    plan, offset = [], 0
    for path in sorted(shapes):
        shape = tuple(int(d) for d in shapes[path])
        size = int(np.prod(shape)) if shape else 1
        leaf = path.rsplit("/", 1)[-1]
        if leaf == "kernel":
            fan_in = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
            if len(shape) == 4 and shape[2] == 1:  # depthwise: one input
                fan_in = shape[0] * shape[1]
            mean, std = 0.0, 1.0 / math.sqrt(max(fan_in, 1))
        elif leaf == "scale":
            mean, std = 1.0, 0.1
        else:
            mean, std = 0.0, 0.1
        plan.append((path, shape, offset, size, mean, std))
        offset += size
    return plan, offset


def make(seed: int, stream: int, shapes):
    """{path: shape} -> {path: float32 numpy array}."""
    plan, total = _plan(shapes)

    @jax.jit
    def generate(key):
        if total <= FLAT_DRAW:
            flat = jax.random.normal(key, (total,), jnp.float32)
            draws = [
                flat[offset : offset + size].reshape(shape)
                for _, shape, offset, size, _, _ in plan
            ]
        else:
            draws = [
                jax.random.normal(
                    jax.random.fold_in(key, index), shape, jnp.float32
                )
                for index, (_, shape, _, _, _, _) in enumerate(plan)
            ]
        return {
            path: mean + std * draw
            for (path, _, _, _, mean, std), draw in zip(plan, draws)
        }

    return jax.device_get(generate(seed_key(seed, stream)))
