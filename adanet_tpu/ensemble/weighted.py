"""The AdaNet complexity-regularized ensembler.

TPU-native re-design of the reference mixture-weight ensembler
(reference: adanet/ensemble/weighted.py:150-617). Implements the AdaNet
objective, Equation (4) of https://arxiv.org/abs/1607.01097:

    F(w) = (1/m) sum_i Phi(sum_j w_j h_j(x_i), y_i)
           + sum_j (lambda * r(h_j) + beta) * |w_j|_1

Mixture weights live in a flat parameter pytree (not graph variables); the
weighted combine is a stack-matmul that XLA fuses onto the MXU/VPU, and the
L1 complexity penalty is a pure function of the params so the whole
mixture-weight solve jit-compiles into the candidate train step.
"""

from __future__ import annotations

import enum
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
from flax import struct

from adanet_tpu.core.heads import BlockedLogits
from adanet_tpu.ensemble.ensembler import Ensemble, Ensembler


class MixtureWeightType(str, enum.Enum):
    """Mixture weight types (reference: adanet/ensemble/weighted.py:27-40)."""

    SCALAR = "scalar"
    VECTOR = "vector"
    MATRIX = "matrix"


@struct.dataclass
class WeightedSubnetwork:
    """A subnetwork with its mixture weight and weighted logits.

    Analogue of reference `adanet.ensemble.WeightedSubnetwork`
    (reference: adanet/ensemble/weighted.py:43-101).
    """

    subnetwork: Any  # adanet_tpu.subnetwork.Subnetwork output pytree
    weight: Any  # mixture weight array (or dict for multi-head)
    logits: Any  # weighted logits (or dict for multi-head)


@struct.dataclass
class ComplexityRegularized(Ensemble):
    """An AdaNet-weighted ensemble output.

    Analogue of reference `adanet.ensemble.ComplexityRegularized`
    (reference: adanet/ensemble/weighted.py:104-147).

    Attributes:
      weighted_subnetworks: members, ordered first (oldest) to most recent.
      bias: bias term applied to the ensemble logits (zeros when
        `use_bias=False`).
      logits: ensemble logits = bias + sum of weighted member logits.
      complexity_regularization: scalar `sum_j (lambda r(h_j) + beta)|w_j|_1`.
    """

    weighted_subnetworks: List[WeightedSubnetwork]
    bias: Any
    logits: Any
    complexity_regularization: Any

    @property
    def subnetworks(self):
        return [ws.subnetwork for ws in self.weighted_subnetworks]


def _sorted_keys(maybe_dict):
    return sorted(maybe_dict) if isinstance(maybe_dict, dict) else None


def _lookup(maybe_dict, key):
    return maybe_dict[key] if key is not None else maybe_dict


class ComplexityRegularizedEnsembler(Ensembler):
    """Learns mixture weights minimizing the complexity-regularized loss.

    Analogue of reference `adanet.ensemble.ComplexityRegularizedEnsembler`
    (reference: adanet/ensemble/weighted.py:150-617), with the same
    semantics: SCALAR/VECTOR weights multiply member logits elementwise and
    are initialized to 1/N (uniform average); MATRIX weights right-multiply
    the member's last layer and are zero-initialized; an optional trainable
    bias; warm-started weights for members kept from the previous ensemble;
    and L1 complexity regularization `(lambda * r(h) + beta) * |w|_1` added
    to the mixture-weight training loss.

    Args:
      optimizer: optax `GradientTransformation`, or a zero-arg callable
        returning one, or None. None means the mixture weights are never
        updated (staying at their uniform-average init), matching the
        reference's `tf.no_op()` train op (weighted.py:606-617).
      mixture_weight_type: a `MixtureWeightType`.
      mixture_weight_initializer: optional `fn(rng, shape, dtype) -> array`
        overriding the default initializer.
      warm_start_mixture_weights: whether to initialize weights of kept
        members from their previously learned values.
      adanet_lambda: lambda >= 0, scales the complexity r(h) in the penalty.
      adanet_beta: beta >= 0, uniform L1 penalty on all members.
      use_bias: whether to add a trainable bias term to the ensemble logits.
      name: optional name, defaults to "complexity_regularized".
      use_fused_combine: use the Pallas fused weighted-combine kernel for
        SCALAR/VECTOR weights over same-shape member logits (single-head).
        The per-member weighted logits are then not materialized
        (`WeightedSubnetwork.logits` is None); ensemble logits and
        gradients are identical to the unfused path.
    """

    def __init__(
        self,
        optimizer=None,
        mixture_weight_type: MixtureWeightType = MixtureWeightType.SCALAR,
        mixture_weight_initializer=None,
        warm_start_mixture_weights: bool = False,
        adanet_lambda: float = 0.0,
        adanet_beta: float = 0.0,
        use_bias: bool = False,
        name: Optional[str] = None,
        use_fused_combine: bool = False,
    ):
        self._optimizer = optimizer
        self._mixture_weight_type = MixtureWeightType(mixture_weight_type)
        self._mixture_weight_initializer = mixture_weight_initializer
        self._warm_start_mixture_weights = warm_start_mixture_weights
        self._adanet_lambda = float(adanet_lambda)
        self._adanet_beta = float(adanet_beta)
        self._use_bias = use_bias
        self._name = name
        self._use_fused_combine = use_fused_combine

    @property
    def name(self) -> str:
        return self._name or "complexity_regularized"

    # ------------------------------------------------------------------ init

    def _default_init(self, num_subnetworks, shape, dtype=jnp.float32):
        """Default initializer (reference: weighted.py:371-377)."""
        if self._mixture_weight_type in (
            MixtureWeightType.SCALAR,
            MixtureWeightType.VECTOR,
        ):
            return jnp.full(shape, 1.0 / num_subnetworks, dtype=dtype)
        return jnp.zeros(shape, dtype=dtype)

    def _weight_shape(self, subnetwork, key=None):
        """Weight shape per type (reference: weighted.py:417-426)."""
        logits = _lookup(subnetwork.logits, key)
        logits_size = logits.shape[-1]
        if self._mixture_weight_type == MixtureWeightType.SCALAR:
            return ()
        if self._mixture_weight_type == MixtureWeightType.VECTOR:
            return (logits_size,)
        last_layer = _lookup(subnetwork.last_layer, key)
        if last_layer is None:
            raise ValueError(
                "MATRIX mixture weights require subnetworks to expose "
                "last_layer."
            )
        return (last_layer.shape[-1], logits_size)

    def _init_one_weight(self, rng, subnetwork, num_subnetworks, key=None):
        shape = self._weight_shape(subnetwork, key)
        if self._mixture_weight_initializer is not None:
            return self._mixture_weight_initializer(rng, shape, jnp.float32)
        return self._default_init(num_subnetworks, shape)

    def init_ensemble(self, rng, subnetworks, previous_params=None):
        """Returns `{"weights": [...], "bias": ...}` mixture-weight params.

        `previous_params["weights"]` is aligned with `subnetworks`; non-None
        entries warm-start that member's weight when
        `warm_start_mixture_weights=True` (reference: weighted.py:259-283).
        The bias is warm-started from `previous_params["bias"]` only when the
        engine passes one — the engine withholds it when the previous
        ensemble was pruned, mirroring reference weighted.py:304-320.
        """
        n = len(subnetworks)
        prev_weights = None
        prev_bias = None
        if previous_params is not None:
            prev_weights = previous_params.get("weights")
            prev_bias = previous_params.get("bias")

        weights = []
        for i, subnetwork in enumerate(subnetworks):
            rng, sub_rng = jax.random.split(rng)
            prev = None
            if (
                self._warm_start_mixture_weights
                and prev_weights is not None
                and i < len(prev_weights)
            ):
                prev = prev_weights[i]
            keys = _sorted_keys(subnetwork.logits)
            if keys is None:
                if prev is not None:
                    weights.append(jnp.asarray(prev))
                else:
                    weights.append(
                        self._init_one_weight(sub_rng, subnetwork, n)
                    )
            else:
                w = {}
                for key in keys:
                    if prev is not None:
                        w[key] = jnp.asarray(prev[key])
                    else:
                        rng, k_rng = jax.random.split(rng)
                        w[key] = self._init_one_weight(
                            k_rng, subnetwork, n, key=key
                        )
                weights.append(w)

        params: Dict[str, Any] = {"weights": weights}
        if self._use_bias:
            first = subnetworks[0]
            keys = _sorted_keys(first.logits)
            if keys is None:
                params["bias"] = self._init_bias(first.logits, prev_bias)
            else:
                params["bias"] = {
                    key: self._init_bias(
                        first.logits[key],
                        None if prev_bias is None else prev_bias[key],
                    )
                    for key in keys
                }
        return params

    def _init_bias(self, logits, prev):
        """Bias init: zeros or warm-started prior (reference: weighted.py:490-516)."""
        if prev is not None and self._warm_start_mixture_weights:
            return jnp.asarray(prev)
        dim = 1 if logits.ndim == 1 else logits.shape[-1]
        return jnp.zeros((dim,), dtype=jnp.float32)

    # ----------------------------------------------------------------- apply

    def _weighted_logits(self, weight, subnetwork, key=None):
        """One member's weighted logits (reference: weighted.py:400-454)."""
        logits = _lookup(subnetwork.logits, key)
        if self._mixture_weight_type != MixtureWeightType.MATRIX:
            return logits * weight
        last_layer = _lookup(subnetwork.last_layer, key)
        ndims = last_layer.ndim
        if ndims > 3:
            raise NotImplementedError(
                "Last layers with more than 3 dimensions are not supported "
                "with matrix mixture weights."
            )
        # The combine is tiny relative to the member forward passes; run it
        # at full float32 precision so selection isn't perturbed by the
        # default (fast, low-precision) matmul mode.
        if ndims == 3:
            # [batch, timesteps, d] -> [batch*timesteps, d] for the MXU
            # matmul, then back (reference: weighted.py:434-451).
            b, t, d = last_layer.shape
            out = jnp.matmul(
                jnp.reshape(last_layer, (-1, d)),
                weight,
                precision=jax.lax.Precision.HIGHEST,
            )
            return jnp.reshape(out, (b, t, weight.shape[-1]))
        return jnp.matmul(
            last_layer, weight, precision=jax.lax.Precision.HIGHEST
        )

    def _can_fuse(self, weights, subnetworks, keys) -> bool:
        if not self._use_fused_combine or keys is not None:
            return False
        if self._mixture_weight_type == MixtureWeightType.MATRIX:
            return False
        if any(isinstance(s.logits, BlockedLogits) for s in subnetworks):
            # Never stacked: the combine stays a sum that a head
            # evaluates a block of rows at a time.
            return False
        shape = subnetworks[0].logits.shape
        return all(s.logits.shape == shape for s in subnetworks)

    def _build_fused(self, weights, subnetworks, bias):
        """Pallas fused combine path (see `use_fused_combine`)."""
        from adanet_tpu.ops.ensemble_kernels import fused_weighted_combine

        stacked = jnp.stack(
            [jnp.asarray(s.logits, jnp.float32) for s in subnetworks]
        )
        wstack = jnp.stack([jnp.asarray(w, jnp.float32) for w in weights])
        logits = fused_weighted_combine(stacked, wstack, bias)
        weighted_subnetworks = [
            WeightedSubnetwork(subnetwork=s, weight=w, logits=None)
            for w, s in zip(weights, subnetworks)
        ]
        return ComplexityRegularized(
            weighted_subnetworks=weighted_subnetworks,
            bias=bias,
            logits=logits,
            complexity_regularization=self._complexity_regularization(
                weights, subnetworks
            ),
        )

    def build_ensemble(self, params, subnetworks, previous_ensemble=None):
        del previous_ensemble  # unused, matching reference build_ensemble
        weights = params["weights"]
        if len(weights) != len(subnetworks):
            raise ValueError(
                "Got %d weights for %d subnetworks"
                % (len(weights), len(subnetworks))
            )
        keys = _sorted_keys(subnetworks[0].logits)
        if self._can_fuse(weights, subnetworks, keys):
            return self._build_fused(
                weights,
                subnetworks,
                params.get("bias") if self._use_bias else None,
            )

        weighted_subnetworks = []
        for weight, subnetwork in zip(weights, subnetworks):
            if keys is None:
                w_logits = self._weighted_logits(weight, subnetwork)
            else:
                w_logits = {
                    key: self._weighted_logits(weight[key], subnetwork, key)
                    for key in keys
                }
            weighted_subnetworks.append(
                WeightedSubnetwork(
                    subnetwork=subnetwork, weight=weight, logits=w_logits
                )
            )

        bias = params.get("bias") if self._use_bias else None
        if keys is None:
            logits = self._sum_logits(
                [ws.logits for ws in weighted_subnetworks], bias
            )
            complexity_regularization = self._complexity_regularization(
                weights, subnetworks
            )
        else:
            logits = {
                key: self._sum_logits(
                    [ws.logits[key] for ws in weighted_subnetworks],
                    None if bias is None else bias[key],
                )
                for key in keys
            }
            complexity_regularization = sum(
                self._complexity_regularization(weights, subnetworks, key)
                for key in keys
            )

        return ComplexityRegularized(
            weighted_subnetworks=weighted_subnetworks,
            bias=bias,
            logits=logits,
            complexity_regularization=complexity_regularization,
        )

    def _sum_logits(self, member_logits, bias):
        """bias + sum of weighted logits (reference: weighted.py:544-556)."""
        total = member_logits[0]
        for logits in member_logits[1:]:
            total = total + logits
        if bias is not None:
            total = total + bias
        return total

    def _adanet_gamma(self, complexity):
        """lambda * r(h) + beta (reference: weighted.py:363-369)."""
        if self._adanet_lambda == 0.0:
            return self._adanet_beta
        return (
            self._adanet_lambda * jnp.asarray(complexity, jnp.float32)
            + self._adanet_beta
        )

    def _complexity_regularization(self, weights, subnetworks, key=None):
        """sum_j (lambda r(h_j) + beta) |w_j|_1 (reference: weighted.py:563-604)."""
        if self._adanet_lambda == 0.0 and self._adanet_beta == 0.0:
            return jnp.asarray(0.0, jnp.float32)
        total = jnp.asarray(0.0, jnp.float32)
        for weight, subnetwork in zip(weights, subnetworks):
            w = _lookup(weight, key)
            l1 = jnp.sum(jnp.abs(jnp.asarray(w, jnp.float32)))
            total = total + self._adanet_gamma(subnetwork.complexity) * l1
        return total

    def build_train_optimizer(self):
        optimizer = self._optimizer
        if callable(optimizer) and not hasattr(optimizer, "update"):
            optimizer = optimizer()
        return optimizer
