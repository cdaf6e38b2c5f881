"""A grouped matrix product that the chip runs well, forward and backward.

`grouped_dot(lhs [M, K], rhs [G, K, N], sizes [G])`: consecutive row
groups of `lhs`, `sizes[g]` rows each, times their own `rhs[g]`; rows past
the last group belong to no product and come back zero or unwritten (the
caller masks them).

With `kernel=True` (the caller's choice: `models/moe_lm.py` resolves it
once, in `MoeLmConfig.kernels`) all three products (forward, the gradient
to `lhs`, and the gradient to `rhs`, which contracts over the RAGGED
dimension) are the TPU's grouped kernels of
`jax.experimental.pallas.ops.tpu.megablox`, whose cost follows the groups.
`jax.lax.ragged_dot` is what runs otherwise, and what the kernels were
measured against on the chip at [40960, 2304] x
[8, 2304, 896] (my chip run 3, PR 34): XLA:TPU's own forward 3.65 ms
against 1.62 ms, and forward with both gradients 12.07 ms where XLA
expands the ragged contraction, 7.94 ms with that one product replaced.

`rhs` stays float32 and is rounded to `lhs`'s dtype inside, so that its
gradient comes back in float32 from the kernel's accumulators.
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

_TILE_ROWS = 512
_TILES = (896, 768, 640, 512, 384, 256, 128)


def _kernels(kernel: bool, rows: int, *dims: int):
    """(megablox's module, a tile for each of `dims`) where the caller
    asked for the chip's kernels and they take these shapes, else None."""
    tiles = tuple(
        next((t for t in _TILES if dim % t == 0), None) for dim in dims
    )
    if not kernel or rows % _TILE_ROWS or not all(tiles):
        return None
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm"
    ), tiles


def _product(lhs, rhs, sizes, kernel, transpose_rhs=False):
    """lhs [M, K] x rhs[g] ([K, N], or [N, K] transposed) -> f32 [M, N]."""
    rhs = rhs.astype(lhs.dtype)
    width = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    found = _kernels(kernel, lhs.shape[0], lhs.shape[1], width)
    if found is None:
        if transpose_rhs:
            rhs = jnp.swapaxes(rhs, 1, 2)
        return jax.lax.ragged_dot(
            lhs, rhs, sizes, preferred_element_type=jnp.float32
        )
    megablox, tiles = found
    return megablox.gmm(
        lhs, rhs, sizes, preferred_element_type=jnp.float32,
        tiling=(_TILE_ROWS,) + tiles, transpose_rhs=transpose_rhs,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_dot(lhs, rhs, sizes, kernel=False):
    """float32 [M, N]."""
    return _product(lhs, rhs, sizes, kernel)


def _forward(lhs, rhs, sizes, kernel):
    return _product(lhs, rhs, sizes, kernel), (lhs, rhs, sizes)


def _backward(kernel, kept, upstream):
    lhs, rhs, sizes = kept
    upstream = upstream.astype(lhs.dtype)
    d_lhs = _product(upstream, rhs, sizes, kernel, transpose_rhs=True)
    found = _kernels(kernel, lhs.shape[0], lhs.shape[1], upstream.shape[1])
    if found is None:
        _, pull = jax.vjp(
            lambda r: _product(lhs, r, sizes, False), rhs
        )
        (d_rhs,) = pull(upstream.astype(jnp.float32))
    else:
        megablox, tiles = found
        d_rhs = megablox.tgmm(
            lhs.T, upstream, sizes, preferred_element_type=jnp.float32,
            tiling=(_TILE_ROWS,) + tiles,
        )
    return d_lhs.astype(lhs.dtype), d_rhs.astype(rhs.dtype), None


grouped_dot.defvjp(_forward, _backward)
