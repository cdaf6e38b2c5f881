"""The AdaNet ensemble (Cortes et al., arXiv:1607.01097, eq. 4) plainly:
mixture-weighted sum of member logits, the complexity-regularised
objective, and the zero-debiased moving average that ranks candidates.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def objective(member_logits, mixture, complexities, labels, sizes):
    """F(w) = loss(sum_j w_j h_j) + sum_j (lambda r(h_j) + beta) |w_j|."""
    logits = sum(
        w * h.astype(jnp.float32) for w, h in zip(mixture, member_logits)
    )
    logp = jax.nn.log_softmax(logits)
    picked = jnp.take_along_axis(logp, labels.reshape(-1, 1), axis=-1)
    penalty = sum(
        (sizes["adanet_lambda"] * r + sizes["adanet_beta"]) * abs(w)
        for w, r in zip(mixture, complexities)
    )
    return -jnp.mean(picked) + penalty


def biased_average(losses, decay):
    """The running sum before zero-debiasing, as candidates store it."""
    total = 0.0
    for loss in losses:
        total = decay * total + (1.0 - decay) * loss
    return total
