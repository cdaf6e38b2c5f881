"""The Pallas kernels compiled for a DESCRIBED v5e chip (no chip attached).

Interpret mode cannot see what the TPU's compiler refuses (a strided
slice of a value, too much VMEM, a misaligned slice); the compiler is
installed here and compiles for a topology that is described, not
attached (on-chip-measurement guide, section 2). These cases pin the
kernels' static rules (`kernel_takes`) to what really compiles, at the
widths NASNet-A (6@768) runs: every later PR is held to them at no chip
time.

The topology is described inside a module-scoped fixture of THIS file
and nowhere else: only one process may load the TPU's library, and with
several pytest workers only the worker that is given this file runs the
fixture. So: one file, never at import or collection, never in a child.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from adanet_tpu.models import nasnet
from adanet_tpu.ops import cell_kernels, ensemble_kernels, sepconv_kernels


@pytest.fixture(scope="module")
def one_chip():
    """A sharding on one chip of a described `v5e:2x2`, with the
    persistent compile cache off around the compiles (a compile for a
    described chip is written to the cache but cannot be read back
    without the chip, and the next one would warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # Raises where the TPU's compiler is missing: it is part of the one
    # supported installation, and a skip here would be a hidden pass.
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()


def _compile_xla(fn, sharding, *shapes):
    """Compiles `fn` for the described chip; `shapes` are pytrees of
    (shape, dtype) leaves. Raises what the chip's compiler would."""
    args = jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=sharding
        ),
        shapes,
    )
    return jax.jit(fn).lower(*args).compile()


def _compile(fn, sharding, *shapes):
    """`_compile_xla` of a program that has to hold a Pallas kernel."""
    compiled = _compile_xla(fn, sharding, *shapes)
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# (batch, h, w, c, kernel, filters, stride): NASNet-A's own stages
# (32x32x32, 16x16x64, 8x8x128 and the 96-channel stem output), the
# mobile-ImageNet 44-filter width (not a multiple of anything Mosaic
# likes), the 768-wide deep cell, and both strides.
SEPCONV_SHAPES = [
    (8, 32, 32, 96, 3, 32, 1),
    (8, 32, 32, 32, 5, 32, 1),
    (8, 16, 16, 64, 5, 64, 1),
    (4, 16, 16, 44, 3, 44, 1),
    (2, 8, 8, 768, 3, 768, 1),
    (128, 32, 32, 32, 3, 32, 1),
    (128, 8, 8, 128, 5, 128, 1),
    (8, 32, 32, 32, 7, 64, 2),  # the first reduction cell's 7x7
    (128, 16, 16, 64, 3, 64, 2),
]


@pytest.mark.parametrize(
    "shape", SEPCONV_SHAPES, ids=lambda s: "x".join(map(str, s))
)
def test_sep_conv_kernel_compiles_for_v5e(one_chip, shape):
    b, h, w, c, k, f, stride = shape
    assert sepconv_kernels.kernel_takes((b, h, w, c), k, f, stride)
    _compile(
        functools.partial(
            sepconv_kernels._pallas_forward, stride=stride, interpret=False
        ),
        one_chip,
        _sds((b, h, w, c), jnp.bfloat16),
        _sds((k, k, 1, c), jnp.bfloat16),
        _sds((1, 1, c, f), jnp.bfloat16),
    )


@pytest.mark.parametrize(
    "shape", [(4, 128, 10), (8, 256, 1000)], ids=lambda s: "x".join(map(str, s))
)
def test_combine_kernel_compiles_for_v5e(one_chip, shape):
    n, b, c = shape
    _compile(
        functools.partial(ensemble_kernels._combine_pallas, interpret=False),
        one_chip,
        _sds((n, b, c), jnp.float32),
        _sds((n,), jnp.float32),
        _sds((c,), jnp.float32),
    )


def _cell_args(spec, b, hw, channels, filters):
    params = jax.eval_shape(
        lambda: cell_kernels.init_cell_params(
            jax.random.PRNGKey(0), spec, channels, channels, filters,
            jnp.bfloat16,
        )
    )
    x = _sds((b, hw, hw, channels), jnp.bfloat16)
    return x, x, params


# The first and the last stage of NASNet-A (6@768) at batch 128.
@pytest.mark.parametrize(
    "shape",
    [(128, 32, 192, 32), (128, 8, 768, 128)],
    ids=lambda s: "x".join(map(str, s)),
)
def test_normal_cell_kernel_compiles_for_v5e(one_chip, shape):
    b, hw, channels, filters = shape
    spec = cell_kernels.NORMAL_CELL
    prev, cur, params = _cell_args(spec, b, hw, channels, filters)
    assert cell_kernels.kernel_takes(prev.shape, cur.shape, filters, spec)
    _compile(
        functools.partial(
            cell_kernels._pallas_forward, spec=spec, interpret=False
        ),
        one_chip,
        prev,
        cur,
        params,
    )


def test_reduction_cell_goes_to_the_reference_by_rule():
    """The dispatcher's half of the stride-2 rule (needs no compiler):
    a reduction cell is outside `kernel_takes`."""
    spec = cell_kernels.REDUCTION_CELL
    shape = (128, 32, 32, 192)
    assert not cell_kernels.kernel_takes(shape, shape, 64, spec)


@pytest.mark.xfail(
    strict=True,
    reason="vector.extract_strided_slice takes unit strides only: the "
    "reduction cell's stride-2 value slices do not compile. The day this "
    "passes, take `spec.stride == 1` out of cell_kernels.kernel_takes.",
)
def test_reduction_cell_kernel_compiles_for_v5e(one_chip):
    spec = cell_kernels.REDUCTION_CELL
    prev, cur, params = _cell_args(spec, 128, 32, 192, 64)
    _compile(
        functools.partial(
            cell_kernels._pallas_forward, spec=spec, interpret=False
        ),
        one_chip,
        prev,
        cur,
        params,
    )


# One instruction of the compiled module's text: its name, the type it
# writes (a tuple's in parentheses), its operation and operands, and the
# `op_name` XLA kept for it (a fusion carries ONE of its operations').
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>\S+) = (?P<type>\(.*?\)|\S+) "
    r"(?P<op>[\w-]+)\((?P<operands>[^)]*)\)"
    r"(?:.*?op_name=\"(?P<op_name>[^\"]*)\")?"
)


def test_sep_conv_batch_norm_statistics_ride_in_the_convolution(one_chip):
    """A training batch norm's two sums are written by the fusion of the
    convolution that writes its input: XLA's program for a two-layer 5x5
    `_SepConv` (NASNet-A 6@768's first stage at the benchmark's batch)
    has no pass over the activation for the statistics alone, forward,
    and moves at most 36 passes of it, forward and backward (35.0 with
    the one-pass variance, 39.0 with `jnp.var`: PERF.md section 5)."""
    shape, filters = (1024, 32, 32, 32), 32
    model = nasnet._SepConv(
        filters=filters,
        kernel=5,
        stride=1,
        num_layers=2,
        compute_dtype=jnp.bfloat16,
    )
    x = _sds(shape, jnp.bfloat16)
    variables = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros(shape, jnp.bfloat16), True
        )
    )

    def loss(params, batch_stats, x, weights):
        y, updates = model.apply(
            {"params": params, "batch_stats": batch_stats},
            x,
            True,
            mutable=["batch_stats"],
        )
        return jnp.sum(jnp.float32(y) * jnp.float32(weights)), updates

    compiled = _compile_xla(
        jax.value_and_grad(loss, argnums=(0, 2), has_aux=True),
        one_chip,
        variables["params"],
        variables["batch_stats"],
        x,
        x,
    )
    text = compiled.as_text()
    assert "jit(_var)" not in text

    instructions = [
        match.groupdict()
        for match in map(_INSTRUCTION.match, text.splitlines())
        if match
    ]
    types = {i["name"]: i["type"] for i in instructions}
    activation = "bf16[%s]" % ",".join(map(str, shape))
    statistic = "f32[%d]" % filters
    fused_sums = 0
    for i in instructions:
        op_name = i["op_name"] or ""
        if i["op"] != "fusion" or "/jvp(_SepConv)/" not in op_name:
            continue
        written = re.findall(r"\w+\[[\d,]*\]", i["type"])
        reads_activation = any(
            types.get(operand.strip().lstrip("%"), "").startswith(activation)
            for operand in i["operands"].split(",")
        )
        if not reads_activation:
            continue
        # A statistics pass of its own reads the activation and writes
        # per-channel sums only.
        assert any(w != statistic for w in written), (i["name"], op_name)
        if "/pointwise_" in op_name:
            assert written.count(statistic) == 2, (i["name"], written)
            fused_sums += 1
    assert fused_sums == 2  # one fusion a layer, both sums in it

    passes = compiled.cost_analysis()["bytes accessed"] / (
        2 * shape[0] * shape[1] * shape[2] * shape[3]
    )
    assert passes <= 36, passes


# The language-model candidate's two kernels (`models/moe_lm.py`) at the
# widths of one chip's share of Mellum2 and a chunk of 4 sequences, forward
# and backward, with `kernel=True` as `MoeLmConfig.kernels` resolves it on
# the chip, and the cell's own attention block.


@pytest.mark.parametrize("window", [1024, None], ids=["sliding", "full"])
def test_attention_kernel_compiles_at_mellum2_widths(one_chip, window):
    from adanet_tpu.ops.block_attention import block_attention

    def step(q, k, v):
        out = block_attention(q, k, v, window, 1024, kernel=True)
        return jnp.sum(out * out)

    compiled = _compile(
        jax.grad(step, argnums=(0, 1, 2)), one_chip,
        _sds((4, 8192, 4, 128), jnp.bfloat16),
        _sds((4, 8192, 1, 128), jnp.bfloat16),
        _sds((4, 8192, 1, 128), jnp.bfloat16),
    )
    # The kernel forward and its fused backward, not the blockwise path.
    assert compiled.as_text().count("tpu_custom_call") >= 2


@pytest.mark.parametrize("inner,outer", [(2304, 896), (896, 2304)])
def test_grouped_dot_kernels_compile_at_mellum2_widths(
    one_chip, inner, outer
):
    from adanet_tpu.ops.grouped_dot import grouped_dot

    def step(lhs, rhs, sizes):
        out = grouped_dot(lhs, rhs, sizes, True)
        return jnp.sum(out * out)

    compiled = _compile(
        jax.grad(step, argnums=(0, 1)), one_chip,
        _sds((40960, inner), jnp.bfloat16),
        _sds((8, inner, outer), jnp.float32), _sds((8,), jnp.int32),
    )
    # Forward and both gradients are the grouped kernels: XLA's own
    # ragged product (and its expansion of the ragged contraction) is
    # what runs off the chip only.
    assert compiled.as_text().count("tpu_custom_call") >= 3
    assert "ragged-dot" not in compiled.as_text()


# The two row sums of the same dispatch (`ops/row_combine.py`), at a
# chunk's shape: 40,960 rows of 2,304 into 32,768 tokens, 8 of 64 experts
# held. The kernel's choice to interpret follows the backend, which is the
# CPU here: the tests steer it, the program has no option for it.


@pytest.fixture
def compiled_row_kernel(monkeypatch):
    from adanet_tpu.ops import row_combine

    monkeypatch.setattr(row_combine, "_interpreted", lambda: False)
    return row_combine


_DISPATCH = (
    _sds((40960,), jnp.int32), _sds((32768, 8), jnp.int32),
    _sds((8,), jnp.int32),
)


def _scatters_of_tokens(text):
    """XLA's scatters whose result is a chunk's [tokens, hidden], alone
    or as the root of a fusion."""
    return [
        line for line in text.splitlines()
        if re.search(r"= \w+\[32768,2304\]", line)
        and (re.search(r"\} scatter\(", line) or '/scatter-add"' in line)
    ]


def _row_kernels(text):
    return re.findall(r"%\S*row_combine\S* = \S+ custom-call\(", text)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_row_combine_compiles_at_mellum2_widths(
    one_chip, compiled_row_kernel, dtype
):
    def combine(rows, weight, token, local, sizes):
        return compiled_row_kernel.row_combine(
            rows, weight, token, local, sizes, True
        )

    compiled = _compile(
        combine, one_chip, _sds((40960, 2304), dtype),
        _sds((40960,), jnp.float32), *_DISPATCH,
    )
    assert len(_row_kernels(compiled.as_text())) == 1
    assert not _scatters_of_tokens(compiled.as_text())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_transpose_compiles_at_mellum2_widths(
    one_chip, compiled_row_kernel, dtype
):
    def transpose(x, upstream, token, local, sizes):
        _, pull = jax.vjp(
            lambda x: compiled_row_kernel.take_rows(
                x, token, local, sizes, True
            ),
            x,
        )
        return pull(upstream)[0]

    compiled = _compile(
        transpose, one_chip, _sds((32768, 2304), dtype),
        _sds((40960, 2304), dtype), *_DISPATCH,
    )
    assert len(_row_kernels(compiled.as_text())) == 1
    assert not _scatters_of_tokens(compiled.as_text())


def test_expert_layer_gradient_holds_the_row_kernel(
    one_chip, compiled_row_kernel
):
    """The gradient of `moe_forward` at the chunk's shape, as the chip
    runs it (`kernels=True`): megablox's products, the row kernel for the
    combine and for the gather's transpose, and no scatter whose result
    is [32768, 2304] in either branch of the `cond`."""
    import dataclasses
    import json

    from adanet_tpu.models import moe_lm
    from benchmarks.factories import moe_lm as factory

    with open("benchmarks/configs/mellum2_12b_ep8_4l.json") as handle:
        sizes = json.load(handle)["members"]["mellum2_ep8_4l"]["sizes"]
    config = factory.model_config(sizes, 12288)
    assert config.kernels is False  # resolved by the backend: the CPU
    config = dataclasses.replace(config, kernels=True)

    def step(x, router, gate, up, down):
        out, _ = moe_lm.moe_forward(x, router, gate, up, down, config)
        return jnp.sum(out * out)

    compiled = _compile(
        jax.grad(step, argnums=(0, 1, 2, 3, 4)), one_chip,
        _sds((32768, 2304), jnp.float32), _sds((2304, 64), jnp.float32),
        _sds((8, 2304, 896), jnp.float32), _sds((8, 2304, 896), jnp.float32),
        _sds((8, 896, 2304), jnp.float32),
    )
    text = compiled.as_text()
    assert len(_row_kernels(text)) == 2
    assert not _scatters_of_tokens(text)


def test_head_gradient_is_three_vocabulary_products(one_chip):
    """The gradient of the blocked head's loss at the Mellum2 share's
    widths (131,072 bfloat16 rows of 2,304, a float32 kernel to 12,288
    classes, blocks of 4,096): the forward's product and the two
    gradients' in one loop over the blocks, and no fourth product over
    the vocabulary (a recomputed forward)."""
    from adanet_tpu.core.heads import BlockedLogits, MultiClassHead

    head = MultiClassHead(12288, top_k=0)

    def loss(hidden, kernel, labels):
        logits = BlockedLogits.of(hidden, kernel, 4096, jnp.bfloat16)
        return head.loss(logits, labels)

    compiled = _compile_xla(
        jax.value_and_grad(loss, (0, 1)), one_chip,
        _sds((131072, 2304), jnp.bfloat16), _sds((2304, 12288), jnp.float32),
        _sds((131072,), jnp.int32),
    )
    # Every product of this program is over the vocabulary: the rows'
    # gradient contracts it, the other two write it.
    products = re.findall(r" (?:convolution|dot)\(", compiled.as_text())
    assert len(products) == 3
