"""Pallas TPU kernel: fused mixture-weight combine.

The hot inner op of the AdaNet objective — `bias + sum_n w_n * logits_n`
over stacked member logits — fused into a single VMEM-resident kernel with
a custom VJP so it stays differentiable for the mixture-weight solve
(the op the reference leaves to TF's executor; see SURVEY.md §2.9's
"mixture-weight + complexity-reg solve" Pallas note).

XLA already fuses this pattern well; the kernel exists to (a) guarantee the
fusion (one HBM read of the stacked logits, no [N, B, C] intermediates) and
(b) serve as the repo's pattern for Pallas ops. On non-TPU backends the
kernel runs in interpret mode (`kernel_is_interpreted`); the jnp
reference implementation is the source of truth for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _combine_reference(stacked_logits, weights, bias):
    """jnp source of truth: bias + sum_n w_n * logits_n.

    stacked_logits: [N, B, C]; weights: [N] (scalar-per-member) or [N, C]
    (vector-per-member); bias: [C] or None.
    """
    if weights.ndim == 1:
        w = weights[:, None, None]
    else:
        w = weights[:, None, :]
    out = jnp.sum(stacked_logits * w, axis=0)
    if bias is not None:
        out = out + bias
    return out


def _combine_kernel(logits_ref, weights_ref, bias_ref, out_ref):
    """One batch-tile: accumulate the weighted member logits in VMEM."""
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    num_members = logits_ref.shape[0]
    for n in range(num_members):  # static unroll over members
        member = jnp.asarray(logits_ref[n], jnp.float32)
        w = jnp.asarray(weights_ref[n], jnp.float32)
        if w.ndim == 0:
            acc = acc + member * w
        else:
            acc = acc + member * w[None, :]
    acc = acc + jnp.asarray(bias_ref[...], jnp.float32)
    out_ref[...] = acc.astype(out_ref.dtype)


def _combine_pallas(stacked_logits, weights, bias, interpret: bool):
    n, b, c = stacked_logits.shape
    if bias is None:
        bias = jnp.zeros((c,), jnp.float32)
    block_b = min(b, 512)
    grid = (pl.cdiv(b, block_b),)
    return pl.pallas_call(
        _combine_kernel,
        out_shape=jax.ShapeDtypeStruct((b, c), stacked_logits.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, block_b, c), lambda i: (0, i, 0)),
            pl.BlockSpec(weights.shape, lambda i: (0,) * weights.ndim),
            pl.BlockSpec((c,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_b, c), lambda i: (i, 0)),
        interpret=interpret,
    )(stacked_logits, weights, bias)


def kernel_is_interpreted() -> bool:
    """Whether `fused_weighted_combine` interprets its kernel in this
    process: everywhere but on a TPU backend (the CPU tests have no
    Mosaic). `chip_smoke.py` asserts this is False on the chip and that
    the compiled program holds the kernel."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_weighted_combine(
    stacked_logits, weights, bias, use_pallas: bool = True
):
    """bias + sum_n w_n * logits_n, fused on TPU.

    Args:
      stacked_logits: [N, B, C] member logits.
      weights: [N] scalar or [N, C] vector mixture weights.
      bias: [C] or None.
      use_pallas: run the Pallas kernel (interpret mode off-TPU); False
        uses the jnp reference implementation.
    """
    if not use_pallas:
        return _combine_reference(stacked_logits, weights, bias)
    return _combine_pallas(
        stacked_logits, weights, bias, kernel_is_interpreted()
    )


def _fwd(stacked_logits, weights, bias, use_pallas):
    out = fused_weighted_combine(stacked_logits, weights, bias, use_pallas)
    return out, (stacked_logits, weights, bias is not None)


def _bwd(use_pallas, residuals, g):
    stacked_logits, weights, has_bias = residuals
    g = jnp.asarray(g, jnp.float32)
    logits_f = jnp.asarray(stacked_logits, jnp.float32)
    if weights.ndim == 1:
        d_weights = jnp.einsum("nbc,bc->n", logits_f, g)
        d_logits = weights[:, None, None] * g[None]
    else:
        d_weights = jnp.einsum("nbc,bc->nc", logits_f, g)
        d_logits = weights[:, None, :] * g[None]
    d_bias = jnp.sum(g, axis=0) if has_bias else None
    return (
        d_logits.astype(stacked_logits.dtype),
        d_weights.astype(weights.dtype),
        d_bias,
    )


fused_weighted_combine.defvjp(_fwd, _bwd)
