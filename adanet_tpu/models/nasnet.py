"""NASNet-A in Flax, TPU-first.

From-scratch re-implementation of the NASNet-A search-space cells and the
CIFAR/ImageNet network skeletons that the reference's improve_nas workload
uses (reference: research/improve_nas/trainer/nasnet.py:300-555 and
nasnet_utils.py:250-532 — themselves forked from slim). Behavior follows the
published NASNet-A architecture: normal/reduction cells with the fixed
operation lists, factorized reduction, drop-path with the v3 schedule
(scaled by both layer depth and training progress), auxiliary head, and the
CIFAR stem.

TPU-first choices: NHWC layout, bfloat16 convolution compute with float32
batch-norm statistics and logits, static shapes throughout (cell wiring is
Python-level, traced once), and the drop-path progress tracked as a model
variable so the whole network stays a single jittable function of
(params, batch).

The 1x1 projections of the cells' inputs (`beginning_1x1` of the cell
that continues a tensor, `prev_1x1` of the cell after it) are convolved
by `NasNetA`, not by the cells, a tensor at a time for all its readers
(`cell_specs`, `projection_sites`, `_project_1x1`): the backward pass
then makes the tensor's gradient in ONE convolution by its readers'
kernels concatenated on the output axis, where each reader's own
convolution would write a gradient of the whole tensor. The cells own
the kernels and the batch norms, so the parameter tree is the one of a
network whose cells convolve for themselves.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from adanet_tpu.observability import metrics as metrics_lib

# NASNet-A cell specifications (reference: nasnet_utils.py:483-532).
_NORMAL_OPERATIONS = (
    "separable_5x5_2",
    "separable_3x3_2",
    "separable_5x5_2",
    "separable_3x3_2",
    "avg_pool_3x3",
    "none",
    "avg_pool_3x3",
    "avg_pool_3x3",
    "separable_3x3_2",
    "none",
)
_NORMAL_HIDDENSTATE_INDICES = (0, 1, 1, 1, 0, 1, 1, 1, 0, 0)
_NORMAL_USED_HIDDENSTATES = (1, 0, 0, 0, 0, 0, 0)

_REDUCTION_OPERATIONS = (
    "separable_5x5_2",
    "separable_7x7_2",
    "max_pool_3x3",
    "separable_7x7_2",
    "avg_pool_3x3",
    "separable_5x5_2",
    "none",
    "avg_pool_3x3",
    "separable_3x3_2",
    "max_pool_3x3",
)
_REDUCTION_HIDDENSTATE_INDICES = (0, 1, 0, 1, 0, 1, 3, 2, 2, 0)
_REDUCTION_USED_HIDDENSTATES = (1, 1, 1, 0, 0, 0, 0)


@dataclasses.dataclass(frozen=True)
class NasNetConfig:
    """Hyperparameters (reference: nasnet.py cifar_config, 47-65)."""

    num_classes: int = 10
    num_cells: int = 18
    num_conv_filters: int = 32
    stem_multiplier: float = 3.0
    filter_scaling_rate: float = 2.0
    num_reduction_layers: int = 2
    drop_path_keep_prob: float = 0.6
    dense_dropout_keep_prob: float = 1.0
    use_aux_head: bool = True
    aux_head_weight: float = 0.4
    total_training_steps: int = 937500
    stem_type: str = "cifar"  # or "imagenet"
    compute_dtype: Any = jnp.bfloat16
    # Rematerialize each cell in the backward pass (jax.checkpoint): the
    # classic TPU HBM-for-FLOPs trade — activation memory drops from
    # O(cells) to O(1) cells, enabling much larger batches (better MXU
    # tiling), at the cost of one extra forward per cell in backward.
    remat: bool = False
    # Route every separable conv through the fused Pallas kernel
    # (ops/sepconv_kernels.py: relu + depthwise + pointwise in one
    # VMEM-resident pass; parameters are layout-identical to the Flax
    # path, so checkpoints interchange). No-op on non-TPU backends.
    use_pallas_sep_conv: bool = False


def cifar_config(**overrides) -> NasNetConfig:
    """NASNet-A (6@768)-family CIFAR preset (reference: nasnet.py
    cifar_config) — these ARE `NasNetConfig`'s defaults."""
    return dataclasses.replace(NasNetConfig(), **overrides)


def mobile_imagenet_config(**overrides) -> NasNetConfig:
    """NASNet-A Mobile ImageNet preset (reference: nasnet.py
    mobile_imagenet_config via build_nasnet_mobile)."""
    base = NasNetConfig(
        num_classes=1001,
        num_cells=12,
        num_conv_filters=44,
        stem_multiplier=1.0,
        drop_path_keep_prob=1.0,
        dense_dropout_keep_prob=0.5,
        total_training_steps=250000,
        stem_type="imagenet",
    )
    return dataclasses.replace(base, **overrides)


def large_imagenet_config(**overrides) -> NasNetConfig:
    """NASNet-A Large ImageNet preset (reference: nasnet.py
    large_imagenet_config via build_nasnet_large)."""
    base = NasNetConfig(
        num_classes=1001,
        num_cells=18,
        num_conv_filters=168,
        stem_multiplier=3.0,
        drop_path_keep_prob=0.7,
        dense_dropout_keep_prob=0.5,
        total_training_steps=250000,
        stem_type="imagenet",
    )
    return dataclasses.replace(base, **overrides)


def calc_reduction_layers(
    num_cells: int, num_reduction_layers: int
) -> List[int]:
    """Which cell indices get reduction cells (reference: nasnet_utils.py:52-59)."""
    return [
        int(float(pool_num) / (num_reduction_layers + 1) * num_cells)
        for pool_num in range(1, num_reduction_layers + 1)
    ]


_CELL_KINDS = {
    "normal": (
        _NORMAL_OPERATIONS,
        _NORMAL_HIDDENSTATE_INDICES,
        _NORMAL_USED_HIDDENSTATES,
    ),
    "reduction": (
        _REDUCTION_OPERATIONS,
        _REDUCTION_HIDDENSTATE_INDICES,
        _REDUCTION_USED_HIDDENSTATES,
    ),
}


@dataclasses.dataclass(frozen=True)
class CellSpec:
    """One cell of the network and the two tensors it reads, by the name
    of the module that wrote them ("stem" for the stem's output)."""

    name: str
    kind: str  # a key of _CELL_KINDS
    filters: int
    cell_num: int  # position among all cells: the drop-path depth
    net: str
    prev: Optional[str]
    net_channels: int
    # Channels of `prev` where the cell projects it with `prev_1x1`: it
    # has the resolution of `net` and another width than the cell's. None
    # where `prev` is absent, factorized-reduced or taken as it is.
    prev_channels: Optional[int]


def _stem_shape(config: NasNetConfig, image_width: int) -> Tuple[int, int]:
    """(width, channels) of the stem convolution's output: the CIFAR
    stem's 3x3 keeps the width, the ImageNet stem's is VALID at stride 2
    (reference: nasnet.py:260-297)."""
    if config.stem_type == "cifar":
        return image_width, int(
            config.num_conv_filters * config.stem_multiplier
        )
    if config.stem_type == "imagenet":
        return (image_width - 3) // 2 + 1, int(32 * config.stem_multiplier)
    raise ValueError(
        "stem_type must be 'cifar' or 'imagenet', got %r"
        % (config.stem_type,)
    )


def cell_specs(config: NasNetConfig, image_width: int = 32) -> List[CellSpec]:
    """The cells in the order they run, for images `image_width` wide.

    Widths and channels follow the modules below: a reduction cell
    (stride 2, SAME) gives `ceil(w / 2)`, and a cell writes `filters`
    channels for each hidden state it concatenates.
    """
    cfg = config
    stem = _stem_shape(cfg, image_width)
    imagenet = cfg.stem_type == "imagenet"
    reduction_indices = calc_reduction_layers(
        cfg.num_cells, cfg.num_reduction_layers
    )
    cells = []  # (kind, filters, name)
    if imagenet:
        # Two stride-2 stem reduction cells with sub-unit filter scaling
        # (reference: nasnet.py:260-286).
        for stem_num, scaling in enumerate(
            (1.0 / cfg.filter_scaling_rate**2, 1.0 / cfg.filter_scaling_rate)
        ):
            cells.append((
                "reduction",
                max(1, int(cfg.num_conv_filters * scaling)),
                "cell_stem_%d" % stem_num,
            ))
    filter_scaling = 1.0
    for cell_num in range(cfg.num_cells):
        if cell_num in reduction_indices:
            filter_scaling *= cfg.filter_scaling_rate
            cells.append((
                "reduction",
                int(cfg.num_conv_filters * filter_scaling),
                "reduction_cell_%d" % reduction_indices.index(cell_num),
            ))
        cells.append((
            "normal",
            int(cfg.num_conv_filters * filter_scaling),
            "cell_%d" % cell_num,
        ))

    # (name, width, channels) of the tensors written so far.
    written = [("stem",) + stem]
    specs = []
    for cell_num, (kind, filters, name) in enumerate(cells):
        net, width, channels = written[-1]
        prev, prev_width, prev_channels = (
            written[-2] if len(written) > 1 else (None, None, None)
        )
        projects_prev = (
            prev is not None and prev_width == width
            and prev_channels != filters
        )
        specs.append(CellSpec(
            name=name, kind=kind, filters=filters, cell_num=cell_num,
            net=net, prev=prev, net_channels=channels,
            prev_channels=prev_channels if projects_prev else None,
        ))
        written.append((
            name,
            -(-width // 2) if kind == "reduction" else width,
            filters * _CELL_KINDS[kind][2].count(0),
        ))
    return specs


def projection_sites(
    config: NasNetConfig, image_width: int = 32
) -> Dict[str, Tuple[Tuple[str, str], ...]]:
    """{tensor: its readers through a plain relu -> 1x1 -> bn projection},
    each reader a (cell, kernel) pair such as `("cell_2", "prev_1x1")`.
    `NasNetA` projects a tensor for all of them at once (`_project_1x1`):
    a tensor with two readers is a shared site, whose gradient comes out
    of one convolution; one with a single reader is a single site.
    """
    return _sites(cell_specs(config, image_width))


def _sites(specs: Sequence[CellSpec]):
    sites: Dict[str, List[Tuple[str, str]]] = {}
    for spec in specs:
        sites.setdefault(spec.net, []).append((spec.name, "beginning_1x1"))
        if spec.prev_channels is not None:
            sites.setdefault(spec.prev, []).append((spec.name, "prev_1x1"))
    return {tensor: tuple(readers) for tensor, readers in sites.items()}


def _convolve_1x1(x, kernel, dtype):
    """relu, then what `nn.Conv(f, (1, 1), use_bias=False, dtype=dtype)`
    computes with `kernel`: the operands in `dtype`."""
    return jax.lax.conv_general_dilated(
        jnp.asarray(nn.relu(x), dtype),
        jnp.asarray(kernel, dtype),
        window_strides=(1, 1),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _project_1x1(x, kernels, dtype):
    """relu, then a 1x1 convolution of `x` by each of `kernels`.

    Forward, a convolution a kernel, as `nn.Conv` makes them: each fuses
    with the batch norm that reads it. (ONE convolution by the
    concatenated kernels was measured, PERF.md section 6, PR 30: some of
    the convolutions that then read a slice of its output read all of
    it, and the forward pass lost more than the convolution had gained.)

    Backward, ONE convolution: the transpose of the convolution by the
    kernels concatenated on the output axis. `x`'s gradient is written
    once and `x` read once for the relu, and the kernels' gradients take
    one read of `relu(x)`, where a convolution a kernel writes a gradient
    of `x` each and adds them. Every gradient is the same sum of
    products, rounded to `dtype` once and not once a kernel. A
    `custom_vjp` differentiates in reverse mode only, which is all that
    trains a candidate here.
    """
    return [_convolve_1x1(x, kernel, dtype) for kernel in kernels]


def _project_1x1_fwd(x, kernels, dtype):
    return _project_1x1(x, kernels, dtype), (x, kernels)


def _project_1x1_bwd(dtype, residuals, cotangents):
    def together(x, kernels):
        y = _convolve_1x1(x, jnp.concatenate(kernels, axis=-1), dtype)
        ends = list(itertools.accumulate(k.shape[-1] for k in kernels))
        return jnp.split(y, ends[:-1], axis=-1)

    _, transpose = jax.vjp(together, *residuals)
    return transpose(list(cotangents))


_project_1x1.defvjp(_project_1x1_fwd, _project_1x1_bwd)


class _DebiasedBatchNorm(nn.Module):
    """BatchNorm with warmup-scheduled, initialization-free statistics.

    slim's NASNet arg scope pins decay 0.9997 (the paper default) —
    calibrated for ~1M-step schedules. With zero-initialized EMAs, a
    short run's eval-mode statistics stay ~at initialization
    (0.9997^300 ≈ 0.91), which is exactly the round-4 flagship-gate
    failure: eval accuracy 0.19 while the same parameters scored 0.95
    under batch statistics (docs/nasnet_gate_rootcause.md).

    Fix: per-update effective momentum
    `m_t = min(momentum, count / (count + warmup))` — the first update
    replaces the statistics outright, so the EMA weights sum to one by
    induction (unbiased at ANY step budget, no divisor needed), the
    averaging horizon tracks `count/warmup` recent steps while training
    is short (statistics stay fresh relative to the moving parameters),
    and the schedule converges to the reference 0.9997 decay for long
    runs (count ≥ warmup·momentum/(1−momentum) ≈ 33k steps).

    Parameters are named scale/bias like `nn.BatchNorm`; statistics live
    in the standard `batch_stats` collection (mean/var + the update
    `count`). NASNet checkpoints written before round 5 lack the count
    leaf; strict restore (`core/checkpoint.py:restore_pytree`) migrates
    them in flight, injecting `legacy_batch_stats_count()` — the
    statistics were accumulated under the fixed long-run decay, so
    "converged" is the faithful reading (ADVICE r5). Statistics
    and the normalization itself are float32 regardless of the compute
    dtype (the TPU-first bf16 rule: bf16 matmuls, f32 statistics).

    `out_dtype` closes the other half of that rule: without it the BN
    OUTPUT is f32, so everything downstream of every BN — branch adds,
    relus, pools, concats, and the NEXT conv's input — silently runs
    f32 and the "bf16 compute" policy only covers the convs themselves.
    Setting `out_dtype` (the model's compute dtype) downcasts the
    normalized result after the f32 affine, keeping the inter-op
    tensors bf16 end-to-end. None preserves the legacy f32 output.

    The batch variance is the one-pass `max(E[x*x] - E[x]^2, 0)` in
    float32: both sums are siblings of whatever writes `x`, so XLA puts
    them in that producer's fusion, where the two-pass `E[(x - E[x])^2]`
    costs a read of every normalised tensor forward and another backward
    (the cotangent of the mean inside it, a sum that is zero) on a chip
    bound by HBM bytes. It accepts float32 cancellation, a relative
    error in the variance of the order of 1e-7 * (1 + mean^2 / var)
    (tests/test_nasnet_bn.py holds it), where the bfloat16 convolutions
    around it round at 1e-3.
    """

    momentum: float = 0.9997
    epsilon: float = 1e-3
    warmup: float = 10.0
    out_dtype: Any = None

    @nn.compact
    def __call__(self, x, training: bool):
        feat = x.shape[-1]
        mean_ema = self.variable(
            "batch_stats",
            "mean",
            lambda: jnp.zeros((feat,), jnp.float32),
        )
        var_ema = self.variable(
            "batch_stats",
            "var",
            lambda: jnp.zeros((feat,), jnp.float32),
        )
        count = self.variable(
            "batch_stats", "count", lambda: jnp.zeros((), jnp.float32)
        )
        scale = self.param(
            "scale", nn.initializers.ones, (feat,), jnp.float32
        )
        bias = self.param(
            "bias", nn.initializers.zeros, (feat,), jnp.float32
        )

        xf = jnp.asarray(x, jnp.float32)
        axes = tuple(range(xf.ndim - 1))
        if training:
            metrics_lib.registry().counter(
                "nasnet.batch_norm.train_sites"
            ).inc()
            mean = jnp.mean(xf, axes)
            var = jnp.maximum(
                jnp.mean(xf * xf, axes) - mean * mean, 0.0
            )
            if not self.is_initializing():
                m = jnp.minimum(
                    self.momentum, count.value / (count.value + self.warmup)
                )
                mean_ema.value = m * mean_ema.value + (1.0 - m) * mean
                var_ema.value = m * var_ema.value + (1.0 - m) * var
                count.value = count.value + 1.0
        else:
            trained = count.value > 0
            mean = jnp.where(trained, mean_ema.value, 0.0)
            var = jnp.where(trained, var_ema.value, 1.0)
        y = (xf - mean) * jax.lax.rsqrt(var + self.epsilon)
        y = y * scale + bias
        if self.out_dtype is not None:
            y = y.astype(self.out_dtype)
        return y


def legacy_batch_stats_count() -> float:
    """The `count` injected when restoring a pre-round-5 checkpoint.

    The smallest count at which the warmup schedule
    `m_t = min(momentum, count / (count + warmup))` has converged to the
    fixed `momentum` those legacy statistics were actually accumulated
    under (~33k steps at the defaults): restored models keep the exact
    eval-mode behavior they were trained with, and further training
    updates at the long-run decay instead of restarting the warmup.
    Consumed by `core/checkpoint.py`'s restore shim.
    """
    momentum = _DebiasedBatchNorm.momentum
    warmup = _DebiasedBatchNorm.warmup
    return warmup * momentum / (1.0 - momentum)


def _batch_norm(x, training: bool, name: str, dtype=None):
    # slim arg scope: decay 0.9997, epsilon 0.001 (NASNet paper defaults),
    # with warmup-scheduled statistics (see _DebiasedBatchNorm). `dtype`
    # is the caller's compute dtype: statistics and the affine stay f32,
    # only the OUTPUT is downcast so the ops between BNs run bf16 too.
    return _DebiasedBatchNorm(name=name, out_dtype=dtype)(x, training)


class _ConvKernel(nn.Module):
    """Bare conv kernel parameter, scope-compatible with `nn.Conv`: the
    param path is `<name>/kernel` with Flax's default initializer, so the
    fused and unfused sep-conv paths share checkpoints."""

    shape: Tuple[int, ...]

    @nn.compact
    def __call__(self):
        return self.param(
            "kernel", nn.initializers.lecun_normal(), self.shape
        )


class _SepConv(nn.Module):
    """Stacked relu -> depthwise+pointwise conv -> bn, repeated
    (reference: nasnet_utils.py:183-211). With `use_pallas` the
    relu+depthwise+pointwise triple runs as one fused VMEM-resident
    Pallas kernel (ops/sepconv_kernels.py)."""

    filters: int
    kernel: int
    stride: int
    num_layers: int
    compute_dtype: Any
    use_pallas: bool = False

    @nn.compact
    def __call__(self, x, training: bool):
        from adanet_tpu.ops.sepconv_kernels import fused_sep_conv

        stride = self.stride
        for layer in range(self.num_layers):
            in_ch = x.shape[-1]
            if self.use_pallas:
                dw = _ConvKernel(
                    (self.kernel, self.kernel, 1, in_ch),
                    name="depthwise_%d" % layer,
                )()
                pw = _ConvKernel(
                    (1, 1, in_ch, self.filters),
                    name="pointwise_%d" % layer,
                )()
                x = fused_sep_conv(
                    jnp.asarray(x, self.compute_dtype),
                    jnp.asarray(dw, self.compute_dtype),
                    jnp.asarray(pw, self.compute_dtype),
                    stride,
                )
            else:
                x = nn.relu(x)
                x = nn.Conv(
                    features=in_ch,
                    kernel_size=(self.kernel, self.kernel),
                    strides=(stride, stride),
                    feature_group_count=in_ch,
                    use_bias=False,
                    dtype=self.compute_dtype,
                    name="depthwise_%d" % layer,
                )(x)
                x = nn.Conv(
                    features=self.filters,
                    kernel_size=(1, 1),
                    use_bias=False,
                    dtype=self.compute_dtype,
                    name="pointwise_%d" % layer,
                )(x)
            x = _batch_norm(
                x, training, "bn_%d" % layer, dtype=self.compute_dtype
            )
            stride = 1
        return x


class _FactorizedReduction(nn.Module):
    """Stride-2 reduction without information loss
    (reference: nasnet_utils.py:92-134)."""

    filters: int
    stride: int
    compute_dtype: Any

    @nn.compact
    def __call__(self, x, training: bool):
        if self.stride == 1:
            x = nn.Conv(
                self.filters,
                (1, 1),
                use_bias=False,
                dtype=self.compute_dtype,
                name="path_conv",
            )(x)
            return _batch_norm(
                x, training, "path_bn", dtype=self.compute_dtype
            )
        # Path 1: stride-2 avg pool (1x1 window) + 1x1 conv.
        path1 = nn.avg_pool(x, (1, 1), strides=(self.stride, self.stride))
        path1 = nn.Conv(
            self.filters // 2,
            (1, 1),
            use_bias=False,
            dtype=self.compute_dtype,
            name="path1_conv",
        )(path1)
        # Path 2: shift by one pixel, then the same.
        path2 = jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0)))[:, 1:, 1:, :]
        path2 = nn.avg_pool(
            path2, (1, 1), strides=(self.stride, self.stride)
        )
        path2 = nn.Conv(
            self.filters // 2 + self.filters % 2,
            (1, 1),
            use_bias=False,
            dtype=self.compute_dtype,
            name="path2_conv",
        )(path2)
        out = jnp.concatenate([path1, path2], axis=-1)
        return _batch_norm(
            out, training, "final_path_bn", dtype=self.compute_dtype
        )


def _drop_path(x, keep_prob, rng):
    """Drops a whole example's residual branch
    (reference: nasnet_utils.py:137-148)."""
    batch = x.shape[0]
    mask = jnp.floor(
        keep_prob + jax.random.uniform(rng, (batch, 1, 1, 1), jnp.float32)
    )
    return x * jnp.asarray(1.0 / keep_prob, x.dtype) * jnp.asarray(
        mask, x.dtype
    )


class _NasNetCell(nn.Module):
    """One NASNet-A cell (reference: nasnet_utils.py:250-480).

    The cell owns the kernels of its input projections (`beginning_1x1`,
    and `prev_1x1` where `prev_channels` says it has one) but does not
    convolve with them: `NasNetA` reads them through `projection_kernel`,
    projects each tensor for all its readers together (`_project_1x1`),
    and calls the cell with what came out, which the cell normalizes.
    """

    operations: Sequence[str]
    hiddenstate_indices: Sequence[int]
    used_hiddenstates: Sequence[int]
    filters: int
    stride: int
    cell_num: int
    total_num_cells: int
    drop_path_keep_prob: float
    compute_dtype: Any
    net_channels: int
    prev_channels: Optional[int] = None
    use_pallas_sep_conv: bool = False

    def setup(self):
        self.beginning_1x1 = _ConvKernel(
            (1, 1, self.net_channels, self.filters)
        )
        if self.prev_channels is not None:
            self.prev_1x1 = _ConvKernel(
                (1, 1, self.prev_channels, self.filters)
            )

    def projection_kernel(self, name):
        """The kernel of `beginning_1x1` or `prev_1x1`."""
        return getattr(self, name)()

    def _apply_operation(
        self, x, operation, stride, is_original_input, training, progress, name
    ):
        input_filters = x.shape[-1]
        if stride > 1 and not is_original_input:
            stride = 1
        if "separable" in operation:
            parts = operation.split("_")
            kernel = int(parts[1].split("x")[0])
            num_layers = int(parts[2])
            x = _SepConv(
                filters=self.filters,
                kernel=kernel,
                stride=stride,
                num_layers=num_layers,
                compute_dtype=self.compute_dtype,
                use_pallas=self.use_pallas_sep_conv,
                name="%s_sep" % name,
            )(x, training)
        elif operation == "none":
            if stride > 1 or input_filters != self.filters:
                x = nn.relu(x)
                x = nn.Conv(
                    self.filters,
                    (1, 1),
                    strides=(stride, stride),
                    use_bias=False,
                    dtype=self.compute_dtype,
                    name="%s_1x1" % name,
                )(x)
                x = _batch_norm(
                    x, training, "%s_bn1" % name, dtype=self.compute_dtype
                )
        elif "pool" in operation:
            pool_type = operation.split("_")[0]
            window = int(operation.split("_")[-1].split("x")[0])
            pool = nn.max_pool if pool_type == "max" else nn.avg_pool
            x = pool(
                x,
                (window, window),
                strides=(stride, stride),
                padding="SAME",
            )
            if input_filters != self.filters:
                x = nn.Conv(
                    self.filters,
                    (1, 1),
                    use_bias=False,
                    dtype=self.compute_dtype,
                    name="%s_1x1" % name,
                )(x)
                x = _batch_norm(
                    x, training, "%s_bn1" % name, dtype=self.compute_dtype
                )
        else:
            raise ValueError("Unimplemented operation %r" % operation)

        if operation != "none" and training and self.drop_path_keep_prob < 1.0:
            # v3 schedule: scale keep prob by layer depth AND training
            # progress (reference: nasnet_utils.py:436-480).
            layer_ratio = (self.cell_num + 1) / float(self.total_num_cells)
            keep_prob = 1.0 - layer_ratio * (
                1.0 - self.drop_path_keep_prob
            )
            keep_prob = 1.0 - progress * (1.0 - keep_prob)
            x = _drop_path(x, keep_prob, self.make_rng("dropout"))
        return x

    def _reduce_prev_layer(self, prev_layer, curr_layer, training):
        """Matches prev layer dims to curr (reference: nasnet_utils.py:283-301)."""
        if self.prev_channels is not None:
            # Projected by `prev_1x1` already.
            prev_layer = _batch_norm(
                prev_layer, training, "prev_bn", dtype=self.compute_dtype
            )
        elif prev_layer.shape[2] != curr_layer.shape[2]:
            prev_layer = nn.relu(prev_layer)
            prev_layer = _FactorizedReduction(
                filters=self.filters,
                stride=2,
                compute_dtype=self.compute_dtype,
                name="reduce_prev",
            )(prev_layer, training)
        return prev_layer

    @nn.compact
    def __call__(self, x, prev_layer, training: bool, progress):
        """`x`: the cell's input through `beginning_1x1`. `prev_layer`:
        the input of the cell before through `prev_1x1` where the cell
        has one, else that input itself (this cell's own for the first
        cell, which has none before it)."""
        prev_layer = self._reduce_prev_layer(prev_layer, x, training)
        x = _batch_norm(
            x, training, "beginning_bn", dtype=self.compute_dtype
        )

        states = [x, prev_layer]
        for block in range(5):
            left_idx = self.hiddenstate_indices[2 * block]
            right_idx = self.hiddenstate_indices[2 * block + 1]
            h1 = self._apply_operation(
                states[left_idx],
                self.operations[2 * block],
                self.stride,
                left_idx < 2,
                training,
                progress,
                "block%d_left" % block,
            )
            h2 = self._apply_operation(
                states[right_idx],
                self.operations[2 * block + 1],
                self.stride,
                right_idx < 2,
                training,
                progress,
                "block%d_right" % block,
            )
            states.append(h1 + h2)

        # Concat unused states, factorized-reducing shape mismatches
        # (reference: nasnet_utils.py:404-431).
        final = states[-1]
        to_combine = []
        for idx, used in enumerate(self.used_hiddenstates):
            state = states[idx]
            if used:
                continue
            mismatch = (
                state.shape[2] != final.shape[2]
                or state.shape[-1] != final.shape[-1]
            )
            if mismatch:
                stride = 2 if state.shape[2] != final.shape[2] else 1
                state = _FactorizedReduction(
                    filters=final.shape[-1],
                    stride=stride,
                    compute_dtype=self.compute_dtype,
                    name="reduction_%d" % idx,
                )(state, training)
            to_combine.append(state)
        return jnp.concatenate(to_combine, axis=-1)


class _AuxHead(nn.Module):
    """Auxiliary classifier (reference: nasnet.py:235-258)."""

    num_classes: int
    compute_dtype: Any

    @nn.compact
    def __call__(self, x, training: bool):
        x = nn.relu(x)
        x = nn.avg_pool(x, (5, 5), strides=(3, 3), padding="VALID")
        x = nn.Conv(
            128, (1, 1), use_bias=False, dtype=self.compute_dtype, name="proj"
        )(x)
        x = _batch_norm(x, training, "aux_bn0", dtype=self.compute_dtype)
        x = nn.relu(x)
        x = nn.Conv(
            768,
            (x.shape[1], x.shape[2]),
            padding="VALID",
            use_bias=False,
            dtype=self.compute_dtype,
            name="full",
        )(x)
        x = _batch_norm(x, training, "aux_bn1", dtype=self.compute_dtype)
        x = nn.relu(x)
        x = x.reshape((x.shape[0], -1))
        return nn.Dense(
            self.num_classes, dtype=jnp.float32, name="aux_logits"
        )(jnp.asarray(x, jnp.float32))


class NasNetA(nn.Module):
    """The full NASNet-A network (reference: nasnet.py:460-555).

    `__call__(images, training)` returns `(logits, aux_logits, pooled)`;
    `aux_logits` is None outside training or when disabled.
    """

    config: NasNetConfig

    @nn.compact
    def __call__(self, images, training: bool = False):
        cfg = self.config
        x = jnp.asarray(images, cfg.compute_dtype)

        # Drop-path progress = step / total_training_steps, tracked as a
        # model variable so the network stays a pure function of
        # (variables, batch) — the analogue of the reference reading the
        # global step (nasnet_utils.py:455-466).
        step = self.variable(
            "schedule", "step", lambda: jnp.zeros((), jnp.float32)
        )
        progress = jnp.minimum(
            step.value / float(cfg.total_training_steps), 1.0
        )
        if training and not self.is_initializing():
            step.value = step.value + 1.0

        specs = cell_specs(cfg, images.shape[2])
        sites = _sites(specs)
        _, stem_filters = _stem_shape(cfg, images.shape[2])
        if cfg.stem_type == "imagenet":
            # ImageNet stem: stride-2 VALID conv to halve the input, then
            # two stride-2 stem reduction cells (the first two of
            # `cell_specs`): 8x spatial reduction before the main stack.
            net = nn.Conv(
                stem_filters,
                (3, 3),
                strides=(2, 2),
                padding="VALID",
                use_bias=False,
                dtype=cfg.compute_dtype,
                name="conv0",
            )(x)
            net = _batch_norm(
                net, training, "conv0_bn", dtype=cfg.compute_dtype
            )
        else:
            # CIFAR stem: plain 3x3 conv + bn (reference: nasnet.py:288-297).
            net = nn.Conv(
                stem_filters,
                (3, 3),
                use_bias=False,
                dtype=cfg.compute_dtype,
                name="stem_conv",
            )(x)
            net = _batch_norm(
                net, training, "stem_bn", dtype=cfg.compute_dtype
            )

        # How often the shared projection engaged, for a flight dump.
        readers = [len(site) for site in sites.values()]
        registry = metrics_lib.registry()
        registry.counter("nasnet.shared_1x1.sites").inc(readers.count(2))
        registry.counter("nasnet.single_1x1.sites").inc(readers.count(1))
        total_num_cells = (
            cfg.num_cells
            + cfg.num_reduction_layers
            + (2 if cfg.stem_type == "imagenet" else 0)
        )
        # static_argnums counts self: (self, x, prev, training, progress)
        # -> `training` (a Python bool steering module structure) is
        # index 3.
        cell_cls = (
            nn.remat(_NasNetCell, static_argnums=(3,))
            if cfg.remat
            else _NasNetCell
        )
        cells = {
            spec.name: cell_cls(
                operations=_CELL_KINDS[spec.kind][0],
                hiddenstate_indices=_CELL_KINDS[spec.kind][1],
                used_hiddenstates=_CELL_KINDS[spec.kind][2],
                filters=spec.filters,
                stride=2 if spec.kind == "reduction" else 1,
                cell_num=spec.cell_num,
                total_num_cells=total_num_cells,
                drop_path_keep_prob=cfg.drop_path_keep_prob,
                compute_dtype=cfg.compute_dtype,
                net_channels=spec.net_channels,
                prev_channels=spec.prev_channels,
                use_pallas_sep_conv=cfg.use_pallas_sep_conv,
                name=spec.name,
            )
            for spec in specs
        }

        written = {}
        projected = {}

        def write(name, tensor):
            """Keeps a cell's input-to-be, and projects it for every
            cell that reads it through a 1x1 convolution."""
            written[name] = tensor
            readers = sites.get(name, ())
            if readers:
                kernels = [
                    cells[cell].projection_kernel(kernel)
                    for cell, kernel in readers
                ]
                with jax.named_scope(
                    "shared_1x1" if len(readers) > 1 else "single_1x1"
                ):
                    outputs = _project_1x1(tensor, kernels, cfg.compute_dtype)
                projected.update(zip(readers, outputs))

        write("stem", net)
        aux_logits = None
        reduction_indices = calc_reduction_layers(
            cfg.num_cells, cfg.num_reduction_layers
        )
        aux_cell = (
            "cell_%d" % (reduction_indices[1] - 1)
            if len(reduction_indices) >= 2
            else None
        )
        for spec in specs:
            prev_layer = projected.get((spec.name, "prev_1x1"))
            if prev_layer is None:
                prev_layer = written[spec.prev or spec.net]
            net = cells[spec.name](
                projected[(spec.name, "beginning_1x1")],
                prev_layer,
                training,
                progress,
            )
            if (
                cfg.use_aux_head
                and spec.name == aux_cell
                and cfg.num_classes
                and training
                # The aux head needs room for its 5x5/stride-3 pool; on
                # tiny inputs (tests) it is skipped rather than producing
                # a zero-sized feature map.
                and net.shape[1] >= 5
                and net.shape[2] >= 5
            ):
                aux_logits = _AuxHead(
                    num_classes=cfg.num_classes,
                    compute_dtype=cfg.compute_dtype,
                    name="aux_head",
                )(net, training)
            write(spec.name, net)

        # Final classifier (reference: nasnet.py:541-555).
        net = nn.relu(net)
        pooled = jnp.asarray(jnp.mean(net, axis=(1, 2)), jnp.float32)
        out = pooled
        if cfg.dense_dropout_keep_prob < 1.0:
            out = nn.Dropout(
                rate=1.0 - cfg.dense_dropout_keep_prob,
                deterministic=not training,
            )(out)
        logits = nn.Dense(
            cfg.num_classes, dtype=jnp.float32, name="logits"
        )(out)
        return logits, aux_logits, pooled
