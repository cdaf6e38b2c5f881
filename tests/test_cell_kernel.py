"""Oracle tests for the fused NASNet-A cell Pallas kernel (ISSUE 17).

The bit-identity contract (ops/cell_kernels.py): the interpret-mode
kernel runs the *identical* helper functions as the unfused
`cell_reference`, so its output must be bit-for-bit equal to the
JIT-COMPILED reference — the form production actually runs. (Eager
op-by-op dispatch can differ from any fused XLA program at the 1-ulp
level, so the oracle compares jitted-to-jitted.)
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adanet_tpu.ops import cell_kernels as ck
from adanet_tpu.ops.cell_kernels import (
    NORMAL_CELL,
    REDUCTION_CELL,
    CellSpec,
    cell_reference,
    fused_cell,
    init_cell_params,
    output_shape,
)

TINY_CELL = CellSpec(
    operations=("separable_3x3_1", "none", "avg_pool_3x3", "max_pool_3x3"),
    hiddenstate_indices=(0, 1, 1, 0),
    used_hiddenstates=(1, 1, 0, 0),
    stride=1,
)
TINY_REDUCTION = CellSpec(
    operations=("separable_3x3_1", "max_pool_3x3", "none", "avg_pool_3x3"),
    hiddenstate_indices=(0, 1, 0, 1),
    used_hiddenstates=(0, 1, 0, 0),
    stride=2,
)


def _inputs(spec, b=2, h=8, w=8, c_prev=8, c_cur=8, filters=8, seed=0,
            dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = init_cell_params(keys[0], spec, c_prev, c_cur, filters)
    prev = jax.random.normal(keys[1], (b, h, w, c_prev), dtype)
    cur = jax.random.normal(keys[2], (b, h, w, c_cur), dtype)
    return prev, cur, params


def _jitted_reference(spec):
    return jax.jit(functools.partial(cell_reference, spec=spec))


def _kernel(prev, cur, params, spec):
    """The interpret-mode kernel itself, past the dispatcher's rule: the
    rule sends reduction cells to the reference (the TPU compiler refuses
    them), but the interpreter still checks their shared branch math."""
    return ck._fused_cell_p(prev, cur, params, spec, True)


@pytest.mark.parametrize(
    "spec,filters",
    [
        (TINY_CELL, 8),
        (TINY_REDUCTION, 8),
        (NORMAL_CELL, 4),
        (REDUCTION_CELL, 4),
    ],
    ids=["tiny_normal", "tiny_reduction", "nasnet_normal",
         "nasnet_reduction"],
)
def test_interpret_kernel_bit_identical_to_jitted_reference(spec, filters):
    prev, cur, params = _inputs(spec, filters=filters)
    want = _jitted_reference(spec)(prev, cur, params)
    got = _kernel(prev, cur, params, spec)
    assert got.shape == output_shape(
        spec, prev.shape[0], prev.shape[1], prev.shape[2], filters
    )
    assert got.shape == want.shape
    assert np.array_equal(np.asarray(got), np.asarray(want)), (
        "max diff %g"
        % np.max(np.abs(np.asarray(got) - np.asarray(want)))
    )


def test_prev_projection_taken_when_channels_mismatch():
    """C_prev != filters exercises the `prev` 1x1 projection leg."""
    prev, cur, params = _inputs(TINY_CELL, c_prev=12, filters=8)
    assert "prev" in params
    want = _jitted_reference(TINY_CELL)(prev, cur, params)
    got = fused_cell(prev, cur, params, TINY_CELL, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_reduction_cell_factorized_reduction_edge():
    """A stride-2 cell must factorized-reduce every UNUSED full-
    resolution state before the concat — the shape-mismatch edge."""
    prev, cur, params = _inputs(TINY_REDUCTION, h=9, w=9)
    # used_hiddenstates marks state 0 (the begin projection, full
    # resolution) as unused: the reduction params must exist.
    assert "0" in params["reductions"]
    want = _jitted_reference(TINY_REDUCTION)(prev, cur, params)
    got = _kernel(prev, cur, params, TINY_REDUCTION)
    # Odd spatial input: ceil-div output resolution.
    assert got.shape[1] == 5 and got.shape[2] == 5
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_bf16_inputs_match_reference():
    prev, cur, params = _inputs(TINY_CELL, dtype=jnp.bfloat16)
    want = _jitted_reference(TINY_CELL)(prev, cur, params)
    got = fused_cell(prev, cur, params, TINY_CELL, interpret=True)
    assert got.dtype == jnp.bfloat16
    # Shared branch math computes in f32 and downcasts once at the
    # output in both paths: still bit-identical.
    assert np.array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32)
    )


def test_vjp_matches_reference_gradients():
    prev, cur, params = _inputs(TINY_CELL)

    def loss_fused(p, c, par):
        return jnp.sum(
            fused_cell(p, c, par, TINY_CELL, interpret=True) ** 2
        )

    def loss_ref(p, c, par):
        return jnp.sum(cell_reference(p, c, par, TINY_CELL) ** 2)

    got = jax.jit(jax.grad(loss_fused, argnums=(0, 1, 2)))(
        prev, cur, params
    )
    want = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(
        prev, cur, params
    )
    for g, w in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    ):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-5
        )


def test_vjp_reduction_cell():
    prev, cur, params = _inputs(TINY_REDUCTION)

    def loss(p, c, par):
        return jnp.sum(_kernel(p, c, par, TINY_REDUCTION))

    grads = jax.jit(jax.grad(loss, argnums=2))(prev, cur, params)
    leaves = jax.tree_util.tree_leaves(grads)
    assert leaves and all(np.all(np.isfinite(np.asarray(l))) for l in leaves)


def test_sepconv_branch_matches_conv_general_dilated():
    """Anchor the shared shifted-MAC sep-conv math to the framework's
    convolution semantics (the same anchor sepconv_kernels carries)."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 8, 8, 8), jnp.float32)
    layer = {
        "dw": jnp.asarray(rng.randn(3, 3, 1, 8) * 0.2, jnp.float32),
        "pw": jnp.asarray(rng.randn(1, 1, 8, 8) * 0.2, jnp.float32),
        "scale": jnp.ones((8,), jnp.float32),
        "bias": jnp.zeros((8,), jnp.float32),
    }
    got = ck._sepconv_layer(x, layer, stride=1)
    y = jnp.maximum(x, 0.0)
    depthwise = jax.lax.conv_general_dilated(
        y,
        layer["dw"],
        window_strides=(1, 1),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=8,
    )
    want = jax.lax.conv_general_dilated(
        depthwise,
        layer["pw"],
        window_strides=(1, 1),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_pool_branch_matches_flax_pooling():
    """The shifted-read pools share flax's SAME semantics:
    count_include_pad avg (divide by the FULL window) and -inf-padded
    max."""
    import flax.linen as nn

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 9, 9, 4), jnp.float32)
    for stride in (1, 2):
        got_avg = ck._pool(x, "avg", stride)
        want_avg = nn.avg_pool(
            x, (3, 3), strides=(stride, stride), padding="SAME"
        )
        np.testing.assert_allclose(
            np.asarray(got_avg), np.asarray(want_avg), rtol=1e-6, atol=1e-6
        )
        got_max = ck._pool(x, "max", stride)
        want_max = nn.max_pool(
            x, (3, 3), strides=(stride, stride), padding="SAME"
        )
        np.testing.assert_allclose(
            np.asarray(got_max), np.asarray(want_max), rtol=1e-6, atol=1e-6
        )


def test_non_pallas_path_falls_back_to_reference():
    prev, cur, params = _inputs(TINY_CELL)
    want = cell_reference(prev, cur, params, TINY_CELL)
    got = fused_cell(prev, cur, params, TINY_CELL, use_pallas=False)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_spatial_mismatch_falls_back_to_reference():
    """prev at a different resolution is the model's job to resolve
    (`_reduce_prev_layer`); the kernel declines rather than mis-tiles."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    params = init_cell_params(keys[0], TINY_CELL, 8, 8, 8)
    prev = jax.random.normal(keys[1], (2, 16, 16, 8), jnp.float32)
    cur = jax.random.normal(keys[2], (2, 8, 8, 8), jnp.float32)
    with pytest.raises(Exception):
        # The reference itself cannot combine mismatched resolutions
        # for this spec (state 0/1 both concat-eligible only via
        # reductions) — both paths must agree on *refusing* too.
        fused_cell(prev, cur, params, TINY_CELL, interpret=True)


@pytest.mark.parametrize(
    "spec,budget",
    [(TINY_CELL, 1), (TINY_REDUCTION, None)],
    ids=["oversized_example", "reduction_cell"],
)
def test_outside_the_rule_takes_the_reference(monkeypatch, spec, budget):
    """`kernel_takes` is the only thing that sends a cell to the XLA
    reference: one example over the VMEM budget, or a stride-2 cell."""
    prev, cur, params = _inputs(spec)
    if budget is not None:
        monkeypatch.setattr(ck, "_VMEM_BUDGET", budget)
    assert not ck.kernel_takes(prev.shape, cur.shape, 8, spec)

    def boom(*args, **kwargs):
        raise AssertionError("pallas path must not be taken")

    monkeypatch.setattr(ck, "_pallas_forward", boom)
    want = cell_reference(prev, cur, params, spec)
    got = fused_cell(prev, cur, params, spec, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_compiler_refusal_inside_the_rule_raises(monkeypatch):
    """No catch-and-fall-back: a kernel the rule takes and the compiler
    refuses is an error the caller sees."""
    prev, cur, params = _inputs(TINY_CELL)

    def refuse(*args, **kwargs):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(ck, "_pallas_forward", refuse)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        fused_cell(prev, cur, params, TINY_CELL, interpret=True)


def test_batch_not_divisible_by_block_still_works():
    prev, cur, params = _inputs(TINY_CELL, b=3)
    want = _jitted_reference(TINY_CELL)(prev, cur, params)
    got = jax.jit(
        functools.partial(
            ck._pallas_forward, spec=TINY_CELL, interpret=True, block_b=2
        )
    )(prev, cur, params)
    # block_b=2 does not tile batch 3: the forward demotes to a divisor.
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_tuned_block_size_is_consulted(tmp_path):
    """A published `tune/` ref overrides the static VMEM heuristic at
    trace time (the autotune integration seam)."""
    from adanet_tpu.ops import tuning
    from adanet_tpu.store import ArtifactStore

    prev, cur, params = _inputs(TINY_CELL, b=4)
    store = ArtifactStore(str(tmp_path / "store"))
    spec_dict = ck._tune_spec(prev, cur, params, TINY_CELL)
    tuning.clear_cache()
    try:
        tuning.record(
            store,
            "cell",
            spec_dict,
            {"block_b": 2},
            [{"block_b": 2, "secs": 0.001}],
        )
        tuning.set_default_store(store)
        want = _jitted_reference(TINY_CELL)(prev, cur, params)
        got = fused_cell(prev, cur, params, TINY_CELL, interpret=True)
        assert np.array_equal(np.asarray(got), np.asarray(want))
        assert (
            tuning.lookup("cell", spec_dict, store=store)["block_b"] == 2
        )
    finally:
        tuning.set_default_store(None)
        tuning.clear_cache()


def test_cell_spec_validation():
    with pytest.raises(ValueError):
        CellSpec(
            operations=("none",),  # odd: cannot pair into blocks
            hiddenstate_indices=(0,),
            used_hiddenstates=(1, 1, 0),
        )
    with pytest.raises(ValueError):
        CellSpec(
            operations=("none", "none"),
            hiddenstate_indices=(0, 1),
            used_hiddenstates=(1, 1),  # must cover 2 inputs + 1 block
        )
