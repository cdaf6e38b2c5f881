"""The few layers every reference is made of, and the arithmetic they
are computed in.

`arith`: "f32" is the reference (float32, `Precision.HIGHEST`). "bf16"
rounds as the configurations state (bfloat16 convolutions between float32
batch norms; float32 dense heads at the chip's default precision) and is
kept as a second witness. "fp8" is the control, the nearest precision
below as a later PR would use it (Micikevicius et al., arXiv:2209.05433):
"bf16" with every convolution's two operands rounded to float8_e4m3 and
the gradient that comes back into it to float8_e5m2, each under one scale
a tensor that puts its largest magnitude at the type's largest; products
accumulate in float32 as on the chip.

A batch norm also notes the statistics of its batch in `stats`, where one
is handed in: {name: (mean, variance)}.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

ARITHS = ("f32", "bf16", "fp8")


def _scaled(x, dtype):
    """`x` rounded to the float8 `dtype` under one scale for the tensor."""
    top = float(jnp.finfo(dtype).max)
    largest = jnp.max(jnp.abs(x)).astype(jnp.float32)
    scale = jnp.where(largest > 0, largest / top, 1.0)
    rounded = (x.astype(jnp.float32) / scale).astype(dtype)
    return (rounded.astype(jnp.float32) * scale).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_conv(conv, x, kernel):
    return conv(
        _scaled(x, jnp.float8_e4m3fn), _scaled(kernel, jnp.float8_e4m3fn)
    )


def _fp8_conv_forward(conv, x, kernel):
    kept = (
        _scaled(x, jnp.float8_e4m3fn), _scaled(kernel, jnp.float8_e4m3fn)
    )
    return conv(*kept), kept


def _fp8_conv_backward(conv, kept, upstream):
    _, pull = jax.vjp(conv, *kept)
    return pull(_scaled(upstream, jnp.float8_e5m2))


_fp8_conv.defvjp(_fp8_conv_forward, _fp8_conv_backward)


class Net:
    """The weights under one name scope, and the arithmetic in use."""

    def __init__(self, weights, scope, arith, bn_epsilon, act=None,
                 stats=None):
        if arith not in ARITHS:
            raise ValueError("arith must be one of %s" % (ARITHS,))
        self.weights, self.scope, self.arith = weights, scope, arith
        self.bn_epsilon, self.stats = bn_epsilon, stats
        # What a batch norm hands on: bfloat16 where the configuration
        # keeps activations so, float32 where only convolutions are cut.
        if act is None:
            act = jnp.float32 if arith == "f32" else jnp.bfloat16
        self.act = act

    def path(self, name):
        return "%s/%s" % (self.scope, name) if self.scope else name

    def sub(self, name):
        return Net(
            self.weights, self.path(name), self.arith, self.bn_epsilon,
            self.act, self.stats,
        )

    def w(self, name):
        return self.weights[self.path(name)]

    def low(self, x):
        """`x` as the arithmetic keeps what lies between two float32
        islands."""
        return x.astype(jnp.float32 if self.arith == "f32" else jnp.bfloat16)

    def conv(self, x, name, stride=1, padding="SAME", groups=1):
        def plain(lhs, rhs):
            return lax.conv_general_dilated(
                lhs, rhs, (stride, stride), padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=groups,
                precision=(
                    lax.Precision.HIGHEST if self.arith == "f32" else None
                ),
            )

        x, kernel = self.low(x), self.low(self.w(name + "/kernel"))
        if self.arith == "fp8":
            return _fp8_conv(plain, x, kernel)
        return plain(x, kernel)

    def bn(self, x, name):
        """Training-mode batch norm: float32 statistics of this batch."""
        x = x.astype(jnp.float32)
        mean = jnp.mean(x, (0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
        if self.stats is not None:
            self.stats[self.path(name)] = (mean, var)
        y = (x - mean) * lax.rsqrt(var + self.bn_epsilon)
        y = y * self.w(name + "/scale") + self.w(name + "/bias")
        return y.astype(self.act) if self.act == jnp.float32 else self.low(y)

    def dense(self, x, name):
        return (
            jnp.dot(
                x.astype(jnp.float32),
                self.w(name + "/kernel"),
                precision=(
                    lax.Precision.HIGHEST if self.arith == "f32" else None
                ),
            )
            + self.w(name + "/bias")
        )


def pool(x, kind, window, stride, padding):
    dims, strides = (1, window, window, 1), (1, stride, stride, 1)
    # Literal identities: jax differentiates these two monoids only.
    if kind == "max":
        return lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, padding)
    total = lax.reduce_window(x, 0.0, lax.add, dims, strides, padding)
    return total / jnp.asarray(window * window, x.dtype)


def cross_entropy(logits, labels, smoothing=0.0):
    classes = logits.shape[-1]
    target = jax.nn.one_hot(labels.reshape(-1), classes)
    target = target * (1.0 - smoothing) + smoothing / classes
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return jnp.mean(-jnp.sum(target * logp, -1))


