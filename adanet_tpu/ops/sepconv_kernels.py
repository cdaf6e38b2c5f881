"""Pallas TPU kernel: fused relu → depthwise → pointwise separable conv.

The NASNet-A hot loop is the stacked separable convolution
(reference: research/improve_nas/trainer/nasnet_utils.py:183-211): every
cell applies relu → k×k depthwise conv → 1×1 pointwise conv (→ bn) two to
four times per branch. On TPU the depthwise conv is VPU work (per-channel
spatial filtering — no MXU contraction) and XLA lowers the
depthwise→pointwise pair as two ops with an HBM round-trip of the
[B, H, W, C] intermediate between them.

This kernel fuses the triple into one VMEM-resident pass per batch tile:

    HBM reads:  x (once), dw [k,k,1,C], pw [C,F]
    in VMEM:    relu → k² shifted multiply-accumulates (VPU, f32 acc)
                → one [bb·H'·W', C] × [C, F] matmul (MXU)
    HBM write:  out (once)

i.e. one HBM read + one HBM write instead of three reads + two writes —
the sep-conv stack is bandwidth-bound, so that is the available win.

Differentiability: `fused_sep_conv` carries a custom VJP whose backward
pass re-derives gradients from the jnp reference implementation (the
rematerialization trade the rest of the framework already makes; see
NasNetConfig.remat). The reference implementation is also the test oracle
(interpret mode on CPU), following the `ensemble_kernels.py` pattern.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Per-tile VMEM budget for choosing the batch block (bytes). Conservative:
# input tile + f32 staging copy + f32 accumulator + output tile must fit
# alongside the kernels in ~16 MB of VMEM.
_VMEM_BUDGET = 6 * 1024 * 1024


def _same_pads(size: int, kernel: int, stride: int):
    """TF/Flax 'SAME' padding (lo, hi) for one spatial dim."""
    out = -(-size // stride)
    total = max((out - 1) * stride + kernel - size, 0)
    lo = total // 2
    return out, lo, total - lo


def sep_conv_reference(x, dw, pw, stride: int):
    """jnp source of truth: relu → SAME depthwise → 1x1 pointwise.

    x: [B, H, W, C]; dw: [k, k, 1, C] (Flax depthwise layout);
    pw: [1, 1, C, F]. Computed in the dtypes given (bf16 in, f32 out of
    batch-norm land happens outside this op, as in models/nasnet.py).
    """
    c = x.shape[-1]
    y = jax.nn.relu(x)
    y = jax.lax.conv_general_dilated(
        y,
        dw.astype(y.dtype),
        (stride, stride),
        "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c,
    )
    return jax.lax.conv_general_dilated(
        y,
        pw.astype(y.dtype),
        (1, 1),
        "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _sepconv_kernel(
    x_ref, dw_ref, pw_ref, o_ref, relu_ref, *, kernel, stride, h_out, w_out
):
    """One batch tile: relu + depthwise MACs in f32, pointwise on the MXU.

    The relu'd tile is staged once in an f32 VMEM scratch and every
    shifted patch is a (strided) LOAD from that ref: Mosaic has strided
    loads for 32-bit refs, but refuses a strided slice of a value
    (`vector.extract_strided_slice` takes unit strides only), which is
    what stride 2 would otherwise need.
    """
    relu_ref[...] = jnp.maximum(x_ref[...], 0).astype(jnp.float32)
    bb, c = relu_ref.shape[0], relu_ref.shape[-1]
    acc = jnp.zeros((bb, h_out, w_out, c), jnp.float32)
    for i in range(kernel):  # static unroll: k² shifted MACs on the VPU
        for j in range(kernel):
            patch = relu_ref[
                :,
                pl.ds(i, h_out, stride=stride),
                pl.ds(j, w_out, stride=stride),
                :,
            ]
            acc = acc + patch * dw_ref[i, j, 0, :].astype(jnp.float32)
    # Pointwise: one MXU contraction over channels for the whole tile.
    pw = pw_ref[0, 0].astype(jnp.float32)  # [C, F]
    out = jax.lax.dot_general(
        acc.reshape(bb * h_out * w_out, c),
        pw,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = out.reshape(bb, h_out, w_out, -1).astype(o_ref.dtype)


def _sepconv_tune_spec(x, dw, pw, stride: int):
    """The autotuner's workload identity for one sep-conv signature."""
    return {
        "x_shape": list(x.shape),
        "dtype": str(x.dtype),
        "kernel": int(dw.shape[0]),
        "filters": int(pw.shape[-1]),
        "stride": int(stride),
    }


def _pallas_forward(x, dw, pw, stride: int, interpret: bool, block_b=None):
    b, h, w, c = x.shape
    k = dw.shape[0]
    f = pw.shape[-1]
    h_out, pt, pb = _same_pads(h, k, stride)
    w_out, pl_, pr = _same_pads(w, k, stride)
    xp = jnp.pad(x, ((0, 0), (pt, pb), (pl_, pr), (0, 0)))
    hp, wp = xp.shape[1], xp.shape[2]

    if block_b is None:
        bytes_per_example = 4 * (hp * wp * c + h_out * w_out * (c + f))
        block_b = max(1, min(b, _VMEM_BUDGET // max(1, bytes_per_example)))
        # Store-persisted autotuner override (ops/tuning.py): a measured
        # winner for this exact (shape, dtype, stride, environment) beats
        # the static VMEM heuristic. Trace-time host work only.
        from adanet_tpu.ops import tuning

        tuned = tuning.lookup(
            "sepconv", _sepconv_tune_spec(x, dw, pw, stride)
        )
        if tuned:
            candidate = int(tuned.get("block_b", 0))
            if 0 < candidate <= b and b % candidate == 0:
                block_b = candidate
    while b % block_b:  # grid must tile the batch exactly
        block_b -= 1

    kern = functools.partial(
        _sepconv_kernel, kernel=k, stride=stride, h_out=h_out, w_out=w_out
    )
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((b, h_out, w_out, f), x.dtype),
        grid=(b // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, hp, wp, c), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((k, k, 1, c), lambda i: (0, 0, 0, 0)),
            pl.BlockSpec((1, 1, c, f), lambda i: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (block_b, h_out, w_out, f), lambda i: (i, 0, 0, 0)
        ),
        scratch_shapes=[pltpu.VMEM((block_b, hp, wp, c), jnp.float32)],
        interpret=interpret,
    )(xp, dw, pw)


def kernel_takes(x_shape, kernel: int, filters: int, stride: int) -> bool:
    """The static rule for which shapes the Pallas kernel takes.

    The batch axis is the kernel's only grid dimension, so ONE example's
    f32 working set (padded input + accumulator + output) has to fit the
    VMEM budget; a larger example — e.g. an early ImageNet-resolution
    cell with wide channels — goes to XLA. Every kernel size and stride
    the NASNet cells use is inside the rule, and a shape inside it that
    the TPU compiler refuses is an error, not a fallback
    (tests/test_chip_compile.py compiles the real widths for a v5e).
    """
    h, w, c = x_shape[1], x_shape[2], x_shape[3]
    out_hw = -(-h // stride) * -(-w // stride)
    bytes_per_example = 4 * (
        (h + kernel) * (w + kernel) * c + out_hw * (c + filters)
    )
    return bytes_per_example <= _VMEM_BUDGET


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_sep_conv_p(x, dw, pw, stride, interpret):
    return _pallas_forward(x, dw, pw, stride, interpret)


def _fused_fwd(x, dw, pw, stride, interpret):
    return _pallas_forward(x, dw, pw, stride, interpret), (x, dw, pw)


def _fused_bwd(stride, interpret, residuals, g):
    x, dw, pw = residuals
    # Backward via the reference implementation's VJP (one extra forward
    # — the same FLOPs-for-HBM trade as NasNetConfig.remat).
    _, vjp = jax.vjp(
        lambda a, b, c: sep_conv_reference(a, b, c, stride), x, dw, pw
    )
    return vjp(g)


_fused_sep_conv_p.defvjp(_fused_fwd, _fused_bwd)


def fused_sep_conv(
    x,
    dw,
    pw,
    stride: int = 1,
    *,
    use_pallas: bool = True,
    interpret: bool = False,
):
    """relu → depthwise(k×k, SAME, `stride`) → pointwise(1×1).

    Shapes: x [B, H, W, C]; dw [k, k, 1, C]; pw [1, 1, C, F] → out
    [B, H', W', F]. Takes the Pallas kernel exactly when the shape is
    inside `kernel_takes` (and `use_pallas`); otherwise the XLA
    reference, by that rule and never by a caught compiler error.
    `interpret=True` runs the kernel in interpreter mode (the CPU
    equivalence-test path). The TPU-vs-other choice is made PER LOWERING
    PLATFORM (`jax.lax.platform_dependent`), not from the default
    backend: lowered for a TPU the program always holds the kernel, and
    the same traced program serves the predict-on-CPU path
    (core/estimator.py `predict(on_cpu=True)`) through the reference.
    """
    if not (
        use_pallas
        and kernel_takes(x.shape, dw.shape[0], pw.shape[-1], stride)
    ):
        return sep_conv_reference(x, dw, pw, stride)
    if interpret:
        return _fused_sep_conv_p(x, dw, pw, stride, True)
    return jax.lax.platform_dependent(
        x,
        dw,
        pw,
        tpu=lambda a, b, c_: _fused_sep_conv_p(a, b, c_, stride, False),
        default=lambda a, b, c_: sep_conv_reference(a, b, c_, stride),
    )
