"""Heads: task abstractions mapping logits to loss, predictions, metrics.

The reference delegates loss/metric/prediction construction to
`tf.estimator` canned heads (used throughout
adanet/core/ensemble_builder.py:571-583 via `head.create_estimator_spec`).
This module is the TPU-native equivalent: a `Head` is a small, pure-function
object whose methods are called inside jit-compiled train/eval steps. Labels
and logits are `jnp` arrays (or dicts of them for `MultiHead`).
"""

from __future__ import annotations

import abc
import functools
from typing import Any, Dict, Mapping, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import optax
from flax import struct

from adanet_tpu.observability import metrics as metrics_lib


class Head(abc.ABC):
    """Computes loss, predictions, and eval metrics from logits."""

    def __init__(self, name: str = "head"):
        self._name = name

    @property
    def name(self) -> str:
        return self._name

    @property
    @abc.abstractmethod
    def logits_dimension(self) -> Union[int, Dict[str, int]]:
        """Logits dimension subnetworks must produce (dict for multi-head)."""

    @abc.abstractmethod
    def loss(self, logits, labels, weights=None):
        """Scalar mean training loss (the Phi in AdaNet's Equation 4)."""

    @abc.abstractmethod
    def predictions(self, logits) -> Dict[str, Any]:
        """Dict of prediction arrays from logits."""

    def eval_metrics(self, logits, labels, weights=None) -> Dict[str, Any]:
        """Dict of per-batch scalar metrics; engines average over batches."""
        return {"average_loss": self.loss(logits, labels, weights)}


def _weighted_mean(values, weights):
    if weights is None:
        return jnp.mean(values)
    weights = jnp.asarray(weights, values.dtype)
    # Accept [batch] and [batch, 1] weight conventions alike.
    while weights.ndim > values.ndim and weights.shape[-1] == 1:
        weights = jnp.squeeze(weights, -1)
    weights = jnp.broadcast_to(weights, values.shape)
    return jnp.sum(values * weights) / jnp.maximum(jnp.sum(weights), 1e-12)


def _binary_auc(probabilities, labels, weights=None):
    """Per-batch ROC AUC via the tie-corrected Mann-Whitney statistic.

    AUC = P(score(pos) > score(neg)) with ties counted half, optionally
    example-weighted. Computed in O(n log n) by sorting scores and, for
    each positive, accumulating the negative weight strictly below it plus
    half the tied negative weight (identical to the all-pairs statistic
    without any n^2 buffer). Engines average per-batch values
    example-weighted, which approximates the reference's streamed
    `tf.metrics.auc`; batches lacking one of the classes contribute
    chance (0.5).
    """
    p = jnp.reshape(jnp.asarray(probabilities, jnp.float32), (-1,))
    y = jnp.reshape(jnp.asarray(labels, jnp.float32), (-1,))
    if weights is None:
        w = jnp.ones_like(p)
    else:
        w = jnp.reshape(jnp.asarray(weights, jnp.float32), (-1,))
    pos_w = w * jnp.asarray(y > 0.5, jnp.float32)
    neg_w = w - pos_w
    order = jnp.argsort(p)
    sorted_p = p[order]
    sorted_pos_w = pos_w[order]
    sorted_neg_w = neg_w[order]
    # S[k] = total negative weight in the first k sorted entries.
    neg_below = jnp.concatenate(
        [jnp.zeros((1,), jnp.float32), jnp.cumsum(sorted_neg_w)]
    )
    left = jnp.searchsorted(sorted_p, sorted_p, side="left")
    right = jnp.searchsorted(sorted_p, sorted_p, side="right")
    strict = neg_below[left]
    tied = neg_below[right] - neg_below[left]
    numerator = jnp.sum(sorted_pos_w * (strict + 0.5 * tied))
    n_pos = jnp.sum(pos_w)
    n_neg = jnp.sum(neg_w)
    defined = (n_pos > 0) & (n_neg > 0)
    return jnp.where(
        defined, numerator / jnp.maximum(n_pos * n_neg, 1e-12), 0.5
    )


def _precision_recall(predicted, labels, weights=None):
    """(precision, recall) over {0,1} arrays, optionally example-weighted;
    0 when undefined (the reference's `tf.metrics.precision/recall`
    zero-denominator behavior)."""
    predicted = jnp.asarray(predicted, jnp.float32)
    labels = jnp.asarray(labels, jnp.float32)
    w = (
        jnp.ones_like(predicted)
        if weights is None
        else jnp.asarray(weights, jnp.float32)
    )
    true_pos = jnp.sum(w * predicted * labels)
    pred_pos = jnp.sum(w * predicted)
    actual_pos = jnp.sum(w * labels)
    precision = jnp.where(
        pred_pos > 0, true_pos / jnp.maximum(pred_pos, 1e-12), 0.0
    )
    recall = jnp.where(
        actual_pos > 0, true_pos / jnp.maximum(actual_pos, 1e-12), 0.0
    )
    return precision, recall


def _broadcast_weights(weights, target):
    """Per-example weights broadcast to a [batch, ...] target shape."""
    if weights is None:
        return None
    w = jnp.asarray(weights, jnp.float32)
    while w.ndim < target.ndim:
        w = w[..., None]
    return jnp.broadcast_to(w, target.shape)


@struct.dataclass
class BlockedLogits:
    """Logits that are never held whole: `sum_m scale_m * (hidden_m @
    kernel_m) + bias`, of `rows x classes`, computed `block` rows at a
    time.

    A subnetwork whose [rows, classes] float32 logits would not fit (a
    language model's [tokens, vocabulary]) returns this in their place.
    It takes `* weight`, `+ other` and `+ bias` as an array does, which
    is all an ensembler with scalar or vector mixture weights asks of
    logits, and stays unevaluated; a head then reduces it block by block
    (`reduce_rows`), so that no block outlives its own loss. A loss that
    is differentiated (`_blocked_loss`) computes its gradients in the
    same pass over the blocks, and its backward pass only scales them.
    Whoever wants the array calls `materialize`.
    """

    hiddens: Any  # tuple of [rows, width_m]
    kernels: Any  # tuple of [width_m, classes]
    scales: Any  # tuple of None, a scalar or [classes]
    bias: Any = None  # None or [classes]
    block: int = struct.field(pytree_node=False, default=4096)
    compute_dtype: Any = struct.field(pytree_node=False, default=jnp.bfloat16)

    @classmethod
    def of(cls, hidden, kernel, block=4096, compute_dtype=jnp.bfloat16):
        return cls((hidden,), (kernel,), (None,), None, block, compute_dtype)

    @property
    def shape(self):
        return (self.hiddens[0].shape[0], self.kernels[0].shape[-1])

    @property
    def ndim(self):
        return 2

    @property
    def dtype(self):
        return jnp.dtype(jnp.float32)

    def __mul__(self, weight):
        if jnp.ndim(weight) > 1:
            raise NotImplementedError(
                "blocked logits take a scalar or per-class weight, not one "
                "of shape %s" % (jnp.shape(weight),)
            )
        scaled = tuple(
            weight if scale is None else scale * weight
            for scale in self.scales
        )
        return self.replace(
            scales=scaled,
            bias=None if self.bias is None else self.bias * weight,
        )

    __rmul__ = __mul__

    def __add__(self, other):
        if isinstance(other, BlockedLogits):
            if other.shape != self.shape:
                raise ValueError(
                    "blocked logits of shapes %s and %s do not add"
                    % (self.shape, other.shape)
                )
            biases = [b for b in (self.bias, other.bias) if b is not None]
            return self.replace(
                hiddens=self.hiddens + other.hiddens,
                kernels=self.kernels + other.kernels,
                scales=self.scales + other.scales,
                bias=sum(biases[1:], biases[0]) if biases else None,
            )
        if jnp.ndim(other) > 1:
            raise NotImplementedError(
                "blocked logits take a scalar or per-class bias, not one of "
                "shape %s" % (jnp.shape(other),)
            )
        return self.replace(
            bias=other if self.bias is None else self.bias + other
        )

    __radd__ = __add__

    def _parts(self, hiddens):
        """Each member's unscaled `hidden_m @ kernel_m` over the rows
        whose hidden states are `hiddens`."""
        return [
            jnp.dot(
                hidden.astype(self.compute_dtype),
                kernel.astype(self.compute_dtype),
                preferred_element_type=jnp.float32,
            )
            for hidden, kernel in zip(hiddens, self.kernels)
        ]

    def _combine(self, parts):
        total = None
        for part, scale in zip(parts, self.scales):
            if scale is not None:
                part = part * scale
            total = part if total is None else total + part
        return total if self.bias is None else total + self.bias

    def _rows(self, hiddens):
        """The logits of the rows whose hidden states are `hiddens`."""
        return self._combine(self._parts(hiddens))

    def materialize(self):
        return self._rows(self.hiddens)

    def _blocks(self, *per_row):
        """The hidden rows and `per_row` arrays cut into a whole number
        of blocks, stacked on a new leading axis."""
        rows = self.shape[0]
        block = min(self.block, rows)
        if rows % block:
            raise ValueError(
                "%d rows are not a whole number of blocks of %d"
                % (rows, block)
            )
        metrics_lib.registry().counter("blocked_logits.row_blocks").inc(
            rows // block
        )

        def blocks(array):
            return array.reshape((rows // block, block) + array.shape[1:])

        return (
            tuple(blocks(hidden) for hidden in self.hiddens),
            tuple(blocks(array) for array in per_row),
        )

    def reduce_rows(self, fn, *per_row):
        """Sum over all rows of `fn(logits of a block, *per_row of the
        block)`, which returns a pytree of per-block sums: one product a
        block, and nothing kept for a backward pass (a loss that is
        differentiated goes through `_blocked_loss`)."""

        def one(hiddens, rest):
            return fn(self._rows(hiddens), *rest)

        def step(carry, xs):
            part = one(*xs)
            return jax.tree_util.tree_map(jnp.add, carry, part), None

        xs = self._blocks(*per_row)
        first = jax.tree_util.tree_map(lambda x: x[0], xs)
        zero = jax.tree_util.tree_map(
            jnp.zeros_like, jax.eval_shape(one, *first)
        )
        with jax.named_scope("blocked_logits"):
            total, _ = jax.lax.scan(step, zero, xs)
        return total


def _blocked_rows(logits, labels, weights):
    """Labels and example weights as [rows] arrays (weights of one where
    there are none)."""
    rows = logits.shape[0]
    labels = jnp.reshape(jnp.asarray(labels, jnp.int32), (rows,))
    if weights is None:
        weights = jnp.ones((rows,), jnp.float32)
    weights = jnp.reshape(
        jnp.broadcast_to(jnp.asarray(weights, jnp.float32), (rows,)), (rows,)
    )
    return labels, weights


def _weight_sum(weights):
    return jnp.maximum(jnp.sum(weights), 1e-12)


def _blocked_weighted_mean(logits, per_row_fn, labels, weights):
    """`_weighted_mean(per_row_fn(logits, labels), weights)` of blocked
    logits, a block at a time, for a mean that is not differentiated."""
    labels, weights = _blocked_rows(logits, labels, weights)
    total = logits.reduce_rows(
        lambda block, ids, w: jnp.sum(per_row_fn(block, ids) * w),
        labels,
        weights,
    )
    return total / _weight_sum(weights)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _blocked_loss(per_row_fn, logits, labels, weights):
    """`_blocked_weighted_mean` of a loss over [rows] labels and weights.
    Undifferentiated, the same scan; differentiated, `_blocked_loss_fwd`
    computes the gradients in that scan."""
    metrics_lib.registry().counter("blocked_logits.forward_only_sites").inc()
    return _blocked_weighted_mean(logits, per_row_fn, labels, weights)


def _sum_to(x, shape):
    """`x` summed over the axes that broadcasting `shape` to it added."""
    x = jnp.sum(x, axis=tuple(range(x.ndim - len(shape))))
    kept = tuple(
        axis for axis, size in enumerate(shape) if size == 1 < x.shape[axis]
    )
    return jnp.sum(x, axis=kept, keepdims=True) if kept else x


def _blocked_loss_fwd(per_row_fn, logits, labels, weights):
    """One scan over the blocks: a block's logits, its losses and their
    cotangent under the per-row weight `w / sum(w)`, then the gradients
    of the operands that are differentiated (`perturbed`): the rows'
    and the kernels' through the transposed products that JAX would
    write for `BlockedLogits._parts` (same operands, casts and float32
    products), the scales' and the bias's as sums over the rows. The
    residuals are these gradients, for a unit cotangent."""
    wrt = jax.tree_util.tree_map(lambda p: p.perturbed, (logits, weights))
    logits, labels, weights = jax.tree_util.tree_map(
        lambda p: p.value, (logits, labels, weights)
    )
    wrt_logits, wrt_weights = wrt
    metrics_lib.registry().counter(
        "blocked_logits.grad_in_forward_sites"
        if any(jax.tree_util.tree_leaves(wrt))
        else "blocked_logits.forward_only_sites"
    ).inc()
    # Cast once, outside the scan (`astype` to its own dtype is a no-op
    # in `_parts`).
    cast = logits.replace(
        kernels=tuple(k.astype(logits.compute_dtype) for k in logits.kernels)
    )
    total_weight = _weight_sum(weights)

    def zeros_where(flags, arrays):
        return tuple(
            jnp.zeros(jnp.shape(a), jnp.float32) if flag else None
            for flag, a in zip(flags, arrays)
        )

    def step(carry, xs):
        loss, dkernels, dscales, dbias = carry
        hiddens, (ids, w) = xs
        parts = cast._parts(hiddens)
        values, pull = jax.vjp(
            lambda block: per_row_fn(block, ids), cast._combine(parts)
        )
        (dlogits,) = pull(w / total_weight)
        loss = loss + jnp.sum(values * w)
        dhiddens, new_dkernels, new_dscales = [], [], []
        for m, (hidden, kernel, scale, part) in enumerate(
            zip(hiddens, cast.kernels, cast.scales, parts)
        ):
            dpart = dlogits if scale is None else dlogits * scale
            dhidden = dkernel = dscale = None
            if wrt_logits.hiddens[m]:
                dhidden = jax.lax.dot_general(
                    dpart, kernel, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ).astype(cast.compute_dtype).astype(hidden.dtype)
            if wrt_logits.kernels[m]:
                dkernel = dkernels[m] + jax.lax.dot_general(
                    dpart, hidden.astype(cast.compute_dtype),
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ).T.astype(cast.compute_dtype).astype(jnp.float32)
            if dscales[m] is not None:
                dscale = dscales[m] + _sum_to(
                    dlogits * part, jnp.shape(scale)
                )
            dhiddens.append(dhidden)
            new_dkernels.append(dkernel)
            new_dscales.append(dscale)
        if dbias is not None:
            dbias = dbias + _sum_to(dlogits, jnp.shape(cast.bias))
        carry = (loss, tuple(new_dkernels), tuple(new_dscales), dbias)
        return carry, (tuple(dhiddens), values if wrt_weights else None)

    with jax.named_scope("blocked_logits"):
        zero = (
            jnp.zeros((), jnp.float32),
            zeros_where(wrt_logits.kernels, logits.kernels),
            zeros_where(wrt_logits.scales, logits.scales),
            zeros_where((wrt_logits.bias,), (logits.bias,))[0],
        )
        (total, dkernels, dscales, dbias), (dhiddens, values) = jax.lax.scan(
            step, zero, logits._blocks(labels, weights)
        )
        loss = total / total_weight
        grads = logits.replace(
            hiddens=tuple(
                None if d is None else d.reshape(h.shape)
                for d, h in zip(dhiddens, logits.hiddens)
            ),
            kernels=tuple(
                None if d is None else d.astype(k.dtype)
                for d, k in zip(dkernels, logits.kernels)
            ),
            scales=tuple(
                None if d is None else d.astype(jnp.result_type(s))
                for d, s in zip(dscales, logits.scales)
            ),
            bias=None if dbias is None else dbias.astype(
                jnp.result_type(logits.bias)
            ),
        )
        # d(sum(v w) / sum(w)) / dw = (v - loss) / sum(w).
        dweights = (
            None if values is None
            else (values.reshape(weights.shape) - loss) / total_weight
        )
    return loss, (grads, dweights)


def _blocked_loss_bwd(per_row_fn, residuals, cotangent):
    del per_row_fn
    grads, dweights = residuals
    with jax.named_scope("blocked_logits"):
        grads, dweights = jax.tree_util.tree_map(
            lambda g: (g * cotangent).astype(g.dtype), (grads, dweights)
        )
    return grads, None, dweights


_blocked_loss.defvjp(_blocked_loss_fwd, _blocked_loss_bwd, symbolic_zeros=True)


def _check_logits_dimension(logits, expected: int, head_name: str) -> None:
    """Trace-time shape validation: logits shapes are static under jit, so a
    plain Python check catches mismatched subnetwork output widths instead
    of silently mis-training (e.g. XLA clamps out-of-range label gathers).
    Rank-1 `(batch,)` logits (squeezed single-output) are accepted as-is."""
    if logits.ndim >= 2 and logits.shape[-1] != expected:
        raise ValueError(
            "%s expects logits with last dimension %d, got shape %s"
            % (head_name, expected, tuple(logits.shape))
        )


class RegressionHead(Head):
    """Mean squared error regression head."""

    def __init__(self, label_dimension: int = 1, name: str = "regression_head"):
        super().__init__(name)
        self._label_dimension = label_dimension

    @property
    def logits_dimension(self) -> int:
        return self._label_dimension

    def loss(self, logits, labels, weights=None):
        _check_logits_dimension(logits, self._label_dimension, self.name)
        labels = jnp.reshape(
            jnp.asarray(labels, jnp.float32), logits.shape
        )
        per_example = jnp.mean(
            jnp.square(jnp.asarray(logits, jnp.float32) - labels), axis=-1
        )
        return _weighted_mean(per_example, weights)

    def predictions(self, logits):
        return {"predictions": logits}

    def eval_metrics(self, logits, labels, weights=None):
        return {"average_loss": self.loss(logits, labels, weights)}


class _SigmoidHead(Head):
    """Shared sigmoid cross-entropy body (per-dimension independent labels)."""

    def __init__(self, logits_dimension: int, name: str):
        super().__init__(name)
        self._logits_dimension = logits_dimension

    @property
    def logits_dimension(self) -> int:
        return self._logits_dimension

    def loss(self, logits, labels, weights=None):
        logits = jnp.asarray(logits, jnp.float32)
        _check_logits_dimension(logits, self._logits_dimension, self.name)
        labels = jnp.reshape(jnp.asarray(labels, jnp.float32), logits.shape)
        per_example = jnp.mean(
            optax.sigmoid_binary_cross_entropy(logits, labels), axis=-1
        )
        return _weighted_mean(per_example, weights)

    def eval_metrics(self, logits, labels, weights=None):
        """Reference canned-head metric set (accuracy, AUC, precision,
        recall, label/prediction means; reference:
        adanet/core/ensemble_builder.py:571-583 via head.create_estimator_
        spec). For multi-label heads AUC/precision/recall are
        micro-averaged over the flattened (example, class) pairs."""
        logits = jnp.asarray(logits, jnp.float32)
        labels_f = jnp.reshape(jnp.asarray(labels, jnp.float32), logits.shape)
        probabilities = jax.nn.sigmoid(logits)
        predicted = jnp.asarray(logits > 0.0, jnp.float32)
        accuracy = _weighted_mean(
            jnp.mean(
                jnp.asarray(predicted == labels_f, jnp.float32), axis=-1
            ),
            weights,
        )
        w_full = _broadcast_weights(weights, labels_f)
        precision, recall = _precision_recall(predicted, labels_f, w_full)
        label_mean = _weighted_mean(jnp.mean(labels_f, axis=-1), weights)
        return {
            "average_loss": self.loss(logits, labels, weights),
            "accuracy": accuracy,
            "auc": _binary_auc(probabilities, labels_f, w_full),
            "precision": precision,
            "recall": recall,
            "label/mean": label_mean,
            "prediction/mean": _weighted_mean(
                jnp.mean(probabilities, axis=-1), weights
            ),
            # Accuracy of always predicting the majority class.
            "accuracy_baseline": jnp.maximum(label_mean, 1.0 - label_mean),
        }


class BinaryClassificationHead(_SigmoidHead):
    """Sigmoid cross-entropy binary classification head (logits dim 1)."""

    def __init__(self, name: str = "binary_head"):
        super().__init__(1, name)

    def predictions(self, logits):
        probabilities = jax.nn.sigmoid(jnp.asarray(logits, jnp.float32))
        return {
            "logits": logits,
            "logistic": probabilities,
            "probabilities": jnp.concatenate(
                [1.0 - probabilities, probabilities], axis=-1
            ),
            "class_ids": jnp.asarray(probabilities > 0.5, jnp.int32),
        }


class MultiClassHead(Head):
    """Softmax cross-entropy head over `n_classes` with integer labels."""

    def __init__(
        self,
        n_classes: int,
        name: str = "multiclass_head",
        top_k: Optional[int] = None,
    ):
        """Args:
          n_classes: number of classes (logits dimension).
          top_k: emit a `top_<k>_accuracy` eval metric. Defaults to 5 when
            `n_classes > 5` (the ImageNet-style convention), disabled
            otherwise; pass an explicit k to override.
        """
        super().__init__(name)
        if n_classes < 2:
            raise ValueError("n_classes must be >= 2, got %d" % n_classes)
        self._n_classes = n_classes
        if top_k is None:
            top_k = 5 if n_classes > 5 else 0
        # k == n_classes is permitted (the metric is trivially 1.0),
        # matching tf.math.in_top_k semantics (ADVICE r2).
        if top_k < 0 or top_k > n_classes:
            raise ValueError(
                "top_k=%d must be in [0, n_classes=%d]" % (top_k, n_classes)
            )
        self._top_k = int(top_k)

    @property
    def logits_dimension(self) -> int:
        return self._n_classes

    def loss(self, logits, labels, weights=None):
        if isinstance(logits, BlockedLogits):
            _check_logits_dimension(logits, self._n_classes, self.name)
            return _blocked_loss(
                optax.softmax_cross_entropy_with_integer_labels,
                logits,
                *_blocked_rows(logits, labels, weights),
            )
        logits = jnp.asarray(logits, jnp.float32)
        _check_logits_dimension(logits, self._n_classes, self.name)
        labels = jnp.reshape(jnp.asarray(labels, jnp.int32), (-1,))
        per_example = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        )
        return _weighted_mean(per_example, weights)

    def predictions(self, logits):
        if isinstance(logits, BlockedLogits):
            logits = logits.materialize()
        logits = jnp.asarray(logits, jnp.float32)
        probabilities = jax.nn.softmax(logits, axis=-1)
        return {
            "logits": logits,
            "probabilities": probabilities,
            "class_ids": jnp.argmax(logits, axis=-1),
        }

    def eval_metrics(self, logits, labels, weights=None):
        if isinstance(logits, BlockedLogits):
            return {
                "average_loss": self.loss(logits, labels, weights),
                "accuracy": _blocked_weighted_mean(
                    logits,
                    lambda block, ids: jnp.asarray(
                        jnp.argmax(block, axis=-1) == ids, jnp.float32
                    ),
                    labels,
                    weights,
                ),
            }
        logits = jnp.asarray(logits, jnp.float32)
        labels_i = jnp.reshape(jnp.asarray(labels, jnp.int32), (-1,))
        accuracy = _weighted_mean(
            jnp.asarray(
                jnp.argmax(logits, axis=-1) == labels_i, jnp.float32
            ),
            weights,
        )
        out = {
            "average_loss": self.loss(logits, labels, weights),
            "accuracy": accuracy,
        }
        if self._top_k:
            # Label's logit must be among the k largest: count strictly
            # larger logits (ties resolved optimistically, matching
            # tf.math.in_top_k).
            label_logit = jnp.take_along_axis(
                logits, labels_i[:, None], axis=-1
            )
            n_larger = jnp.sum(
                jnp.asarray(logits > label_logit, jnp.float32), axis=-1
            )
            out["top_%d_accuracy" % self._top_k] = _weighted_mean(
                jnp.asarray(n_larger < self._top_k, jnp.float32), weights
            )
        return out


class MultiLabelHead(_SigmoidHead):
    """Independent sigmoid cross-entropy over `n_classes` labels.

    Labels are multi-hot arrays of shape [batch, n_classes]; the equivalent
    of `tf.estimator.MultiLabelHead` that reference users plug in.
    """

    def __init__(self, n_classes: int, name: str = "multilabel_head"):
        if n_classes < 2:
            raise ValueError("n_classes must be >= 2, got %d" % n_classes)
        super().__init__(n_classes, name)

    def predictions(self, logits):
        logits = jnp.asarray(logits, jnp.float32)
        probabilities = jax.nn.sigmoid(logits)
        return {
            "logits": logits,
            "probabilities": probabilities,
            "class_ids": jnp.asarray(probabilities > 0.5, jnp.int32),
        }


class MultiHead(Head):
    """Combines several heads over dict logits/labels.

    Equivalent of `tf.estimator.MultiHead` as exercised by the reference's
    multi-head tests (reference: adanet/core/estimator_test.py:1517). Logits
    and labels are dicts keyed by each sub-head's name; the training loss is
    the (optionally weighted) sum of sub-head losses.
    """

    def __init__(
        self,
        heads: Sequence[Head],
        head_weights: Optional[Sequence[float]] = None,
        name: str = "multi_head",
    ):
        super().__init__(name)
        if not heads:
            raise ValueError("heads must be non-empty")
        names = [h.name for h in heads]
        if len(set(names)) != len(names):
            raise ValueError("Sub-head names must be unique, got %s" % names)
        if head_weights is not None and len(head_weights) != len(heads):
            raise ValueError("head_weights must align with heads")
        self._heads = list(heads)
        self._head_weights = (
            list(head_weights) if head_weights is not None else [1.0] * len(heads)
        )

    @property
    def heads(self) -> Sequence[Head]:
        return tuple(self._heads)

    @property
    def logits_dimension(self) -> Dict[str, int]:
        return {h.name: h.logits_dimension for h in self._heads}

    def loss(self, logits: Mapping[str, Any], labels, weights=None):
        total = 0.0
        for head, w in zip(self._heads, self._head_weights):
            total = total + w * head.loss(
                logits[head.name],
                labels[head.name],
                None if weights is None else weights.get(head.name),
            )
        return total

    def predictions(self, logits: Mapping[str, Any]):
        out = {}
        for head in self._heads:
            for key, value in head.predictions(logits[head.name]).items():
                out["%s/%s" % (head.name, key)] = value
        return out

    def eval_metrics(self, logits: Mapping[str, Any], labels, weights=None):
        out = {"average_loss": self.loss(logits, labels, weights)}
        for head in self._heads:
            sub = head.eval_metrics(
                logits[head.name],
                labels[head.name],
                None if weights is None else weights.get(head.name),
            )
            for key, value in sub.items():
                out["%s/%s" % (head.name, key)] = value
        return out
