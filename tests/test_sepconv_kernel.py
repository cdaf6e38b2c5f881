"""Equivalence tests for the fused separable-conv Pallas kernel.

The jnp reference implementation (`sep_conv_reference`, itself validated
against the Flax `_SepConv` layer the NASNet cells use) is the oracle;
the Pallas kernel runs in interpret mode on CPU — the
`ensemble_kernels.py` testing pattern.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adanet_tpu.ops.sepconv_kernels import (
    fused_sep_conv,
    sep_conv_reference,
)


def _random_inputs(b, h, w, c, f, k, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(b, h, w, c), dtype)
    dw = jnp.asarray(rng.randn(k, k, 1, c) * 0.2, dtype)
    pw = jnp.asarray(rng.randn(1, 1, c, f) * 0.2, dtype)
    return x, dw, pw


@pytest.mark.parametrize(
    "shape,kernel,stride",
    [
        ((4, 8, 8, 16), 3, 1),
        ((4, 8, 8, 16), 3, 2),
        ((2, 9, 9, 8), 5, 1),  # odd spatial, SAME padding asymmetry
        ((2, 9, 9, 8), 5, 2),
        ((3, 8, 8, 8), 7, 2),  # the reduction-cell 7x7
    ],
)
def test_kernel_matches_reference(shape, kernel, stride):
    x, dw, pw = _random_inputs(*shape, f=24, k=kernel)
    want = sep_conv_reference(x, dw, pw, stride)
    got = fused_sep_conv(
        x, dw, pw, stride, use_pallas=True, interpret=True
    )
    assert got.shape == want.shape
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_kernel_matches_reference_bf16():
    x, dw, pw = _random_inputs(2, 8, 8, 16, f=16, k=3, dtype=jnp.bfloat16)
    want = sep_conv_reference(x, dw, pw, 1)
    got = fused_sep_conv(x, dw, pw, 1, use_pallas=True, interpret=True)
    assert got.dtype == jnp.bfloat16
    # The kernel accumulates in f32 where the reference multiplies in
    # bf16, so agreement is at bf16 resolution.
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(want, np.float32),
        rtol=0.05,
        atol=0.05,
    )


def test_kernel_gradients_match_reference():
    x, dw, pw = _random_inputs(2, 8, 8, 8, f=12, k=3, seed=3)

    def loss_ref(x, dw, pw):
        return jnp.sum(sep_conv_reference(x, dw, pw, 1) ** 2)

    def loss_pallas(x, dw, pw):
        return jnp.sum(
            fused_sep_conv(x, dw, pw, 1, use_pallas=True, interpret=True)
            ** 2
        )

    want = jax.grad(loss_ref, argnums=(0, 1, 2))(x, dw, pw)
    got = jax.grad(loss_pallas, argnums=(0, 1, 2))(x, dw, pw)
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-4
        )


def test_reference_matches_flax_sepconv_layer():
    """The oracle itself reproduces one relu→depthwise→pointwise layer of
    the Flax `_SepConv` stack (models/nasnet.py:143-177) given the same
    kernels — so kernel-path results are transitively NASNet-exact."""
    b, h, w, c, f, k, stride = 2, 8, 8, 8, 16, 3, 2
    x = jnp.asarray(np.random.RandomState(5).randn(b, h, w, c), jnp.float32)

    dw_layer = nn.Conv(
        features=c,
        kernel_size=(k, k),
        strides=(stride, stride),
        feature_group_count=c,
        use_bias=False,
        dtype=jnp.float32,
    )
    pw_layer = nn.Conv(
        features=f, kernel_size=(1, 1), use_bias=False, dtype=jnp.float32
    )
    dw_vars = dw_layer.init(jax.random.PRNGKey(0), jax.nn.relu(x))
    mid = dw_layer.apply(dw_vars, jax.nn.relu(x))
    pw_vars = pw_layer.init(jax.random.PRNGKey(1), mid)
    want = pw_layer.apply(pw_vars, mid)

    got = sep_conv_reference(
        x,
        dw_vars["params"]["kernel"],
        pw_vars["params"]["kernel"],
        stride,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_lowered_for_cpu_takes_the_reference_branch():
    """`platform_dependent` picks per LOWERING platform: the same traced
    program holds the kernel for a TPU and the XLA path for the CPU."""
    x, dw, pw = _random_inputs(2, 8, 8, 8, f=8, k=3)
    got = fused_sep_conv(x, dw, pw, 1, use_pallas=True, interpret=False)
    want = sep_conv_reference(x, dw, pw, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_nasnet_pallas_flag_preserves_params_and_outputs():
    """`use_pallas_sep_conv=True` must keep the checkpoint layout and the
    math: identical param trees (the `_ConvKernel` scopes mirror
    `nn.Conv`'s `<name>/kernel`) and identical outputs given the same
    parameters (on CPU the fused op falls back to the XLA reference, so
    this pins structure + routing; kernel math is pinned above)."""
    from adanet_tpu.models.nasnet import NasNetA, NasNetConfig

    common = dict(
        num_classes=10,
        num_cells=3,
        num_conv_filters=8,
        use_aux_head=False,
        drop_path_keep_prob=1.0,
        dense_dropout_keep_prob=1.0,
        compute_dtype=jnp.float32,
    )
    images = jnp.asarray(
        np.random.RandomState(0).randn(2, 16, 16, 3), jnp.float32
    )
    base = NasNetA(NasNetConfig(**common))
    fused = NasNetA(NasNetConfig(use_pallas_sep_conv=True, **common))

    base_vars = base.init(jax.random.PRNGKey(0), images, training=False)
    fused_vars = fused.init(jax.random.PRNGKey(0), images, training=False)
    base_shapes = jax.tree_util.tree_map(jnp.shape, base_vars)
    fused_shapes = jax.tree_util.tree_map(jnp.shape, fused_vars)
    assert base_shapes == fused_shapes

    want, _, _ = base.apply(base_vars, images, training=False)
    got, _, _ = fused.apply(base_vars, images, training=False)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_remat_composes_with_pallas_flag():
    """NasNetConfig(remat=True, use_pallas_sep_conv=True): the
    custom-VJP op must compose with nn.remat's checkpointing."""
    from adanet_tpu.models.nasnet import NasNetA, NasNetConfig

    model = NasNetA(
        NasNetConfig(
            num_classes=10,
            num_cells=3,
            num_conv_filters=8,
            use_aux_head=False,
            drop_path_keep_prob=1.0,
            dense_dropout_keep_prob=1.0,
            compute_dtype=jnp.float32,
            remat=True,
            use_pallas_sep_conv=True,
        )
    )
    images = jnp.asarray(
        np.random.RandomState(1).randn(2, 16, 16, 3), jnp.float32
    )
    variables = model.init(jax.random.PRNGKey(0), images, training=False)

    def loss(params):
        logits, _, _ = model.apply(
            {**variables, "params": params}, images, training=False
        )
        return jnp.sum(logits**2)

    grads = jax.grad(loss)(variables["params"])
    flat = jax.tree_util.tree_leaves(grads)
    assert flat and all(np.isfinite(np.asarray(g)).all() for g in flat)


def test_oversized_example_falls_back_to_xla(monkeypatch):
    """One example bigger than the VMEM budget cannot tile on the batch
    axis (the kernel's only grid dim): the op must route to XLA instead
    of emitting an uncompilable tile (round-4 review)."""
    from adanet_tpu.ops import sepconv_kernels

    def boom(*args, **kwargs):
        raise AssertionError("pallas path must not be taken")

    monkeypatch.setattr(sepconv_kernels, "_pallas_forward", boom)
    x, dw, pw = _random_inputs(1, 64, 64, 512, f=512, k=3)
    assert not sepconv_kernels.kernel_takes(x.shape, 3, 512, 1)
    got = sepconv_kernels.fused_sep_conv(
        x, dw, pw, 1, use_pallas=True, interpret=True
    )
    want = sep_conv_reference(x, dw, pw, 1)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_compiler_refusal_inside_the_rule_raises(monkeypatch):
    """No catch-and-fall-back: a kernel the rule takes and the compiler
    refuses is an error the caller sees, not a silent XLA path."""
    from adanet_tpu.ops import sepconv_kernels

    def refuse(*args, **kwargs):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(sepconv_kernels, "_pallas_forward", refuse)
    x, dw, pw = _random_inputs(2, 8, 8, 8, f=8, k=3)
    assert sepconv_kernels.kernel_takes(x.shape, 3, 8, 2)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        sepconv_kernels.fused_sep_conv(x, dw, pw, 2, interpret=True)


def test_batch_not_divisible_by_block_still_works():
    """block_b shrinks until it tiles the batch exactly (prime batch)."""
    x, dw, pw = _random_inputs(7, 8, 8, 8, f=8, k=3, seed=9)
    want = sep_conv_reference(x, dw, pw, 1)
    got = fused_sep_conv(x, dw, pw, 1, use_pallas=True, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )
