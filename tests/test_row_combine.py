"""`ops/row_combine.py`: the kernel (interpreted, uninitialised memory
reading NaN) against the plain `.at[token].add` path and against JAX's own
transpose of the scatter and the gather, on routings built to reach each
corner of the copies: forward and both custom gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adanet_tpu.observability import metrics as metrics_lib
from adanet_tpu.ops import row_combine as rc

HIDDEN, COUNT, K = 128, 8, 8


def _spread(tokens, picks, seed):
    """Each token's `picks` distinct experts of 16, 8 of them held."""
    rng = np.random.default_rng(seed)
    chosen = np.argsort(rng.random((tokens, 16)), axis=1)[:, :K]
    chosen[:, picks:] = -1
    return chosen


def _one_expert(tokens):
    chosen = np.full((tokens, K), -1)
    chosen[:, 2] = 5
    return chosen


def _every_expert(tokens):
    """Every eighth token chose all 8 held experts, two tiles apart in
    their load: the first tile's tokens chose nothing else."""
    chosen = np.full((tokens, K), -1)
    chosen[::8] = np.arange(COUNT)[::-1]
    chosen[tokens // 2 + 1 :: 2, 0] = 3
    return chosen


def _full_buffer(tokens):
    """Every token chose all 8 held experts: every pair held, `sum(sizes)`
    equal to the rows, and a tile of 4,096 rows (16 chunks)."""
    return np.tile(np.arange(COUNT), (tokens, 1))


# name -> (tokens, the choices [tokens, K] (held: 0-7), rows)
ROUTINGS = {
    "balanced": (1024, _spread(1024, 8, 0), 4608),
    "one_expert_takes_every_row": (1024, _one_expert(1024), 1280),
    "an_expert_with_no_row": (
        512, np.where(_spread(512, 8, 1) == 4, -1, _spread(512, 8, 1)), 2304
    ),
    "tokens_with_all_eight": (1024, _every_expert(1024), 1536),
    "rows_equal_to_the_pairs": (512, _full_buffer(512), 4096),
    "far_under_the_rows": (512, _spread(512, 2, 2), 2048),
}


def _dispatch(name):
    """(local, sizes, token, rows, total) as `_experts_sorted` makes them."""
    tokens, chosen, rows = ROUTINGS[name]
    held = (chosen >= 0) & (chosen < COUNT)
    key = np.where(held, chosen, COUNT).reshape(-1)
    sizes = np.array([(chosen == e).sum() for e in range(COUNT)], np.int32)
    order = np.argsort(key, kind="stable")[:rows]
    assert sizes.sum() <= rows and rc.kernel_takes(rows, tokens, HIDDEN)
    return (
        jnp.asarray(chosen, jnp.int32), jnp.asarray(sizes),
        jnp.asarray(order // K, jnp.int32), rows, int(sizes.sum()),
    )


def _rows(rows, total, dtype, seed=0):
    """Rows with NaN planted in every one past the last group."""
    data = jax.random.normal(jax.random.PRNGKey(seed), (rows, HIDDEN))
    return data.at[total:].set(jnp.nan).astype(dtype)


def _scatter(data, weight, token, tokens, total):
    """JAX's own: what the parent's `_experts_sorted` wrote."""
    live = (jnp.arange(data.shape[0]) < total)[:, None]
    return jnp.zeros((tokens, HIDDEN), jnp.float32).at[token].add(
        jnp.where(live, data * weight[:, None], 0.0)
    )


@pytest.mark.parametrize("name", sorted(ROUTINGS))
def test_combine_forward_and_gradients(name):
    local, sizes, token, rows, total = _dispatch(name)
    tokens = local.shape[0]
    data = _rows(rows, total, jnp.float32)
    weight = jax.random.uniform(jax.random.PRNGKey(1), (rows,))
    probe = jax.random.normal(jax.random.PRNGKey(2), (tokens, HIDDEN))

    def ours(data, weight, kernel):
        return rc.row_combine(data, weight, token, local, sizes, kernel)

    got = ours(data, weight, True)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, ours(data, weight, False), atol=2e-6)
    np.testing.assert_allclose(
        got, _scatter(data, weight, token, tokens, total), atol=2e-6
    )
    grads = [
        jax.grad(lambda d, w: jnp.sum(fn(d, w) * probe), (0, 1))(data, weight)
        for fn in (
            lambda d, w: ours(d, w, True),
            lambda d, w: ours(d, w, False),
            lambda d, w: _scatter(d, w, token, tokens, total),
        )
    ]
    # JAX's own transpose multiplies the planted NaN by 0 past the last
    # group; the custom gradients are 0 there.
    for other in grads[1:]:
        for a, b in zip(grads[0], other):
            assert bool(jnp.all(jnp.isfinite(a))) and not a[total:].any()
            np.testing.assert_allclose(
                a[:total], b[:total], rtol=1e-5, atol=1e-5
            )


@pytest.mark.parametrize("weighted", [True, False])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernel_never_reads_what_no_copy_wrote(dtype, weighted):
    """Small tiles and chunks (16 tiles, several chunks a tile, ranges that
    straddle chunks) under the TPU interpreter, whose uninitialised
    buffers read NaN, as the rows past the last group do here."""
    from jax.experimental.pallas import tpu as pltpu

    local, sizes, token, rows, total = _dispatch("balanced")
    data = _rows(rows, total, dtype)
    weight = None
    if weighted:
        weight = jax.random.uniform(jax.random.PRNGKey(1), (rows,))
    got = rc._sum_rows(
        data, weight, token,
        rc.plan(local, sizes, rows, rc._block(dtype), tile=64),
        tile=64, chunk=32,
        interpret=pltpu.InterpretParams(),
    )
    want = rc._sum(data, weight, token, local, sizes, False)
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
    # Weighted bfloat16 rows (no site of the model): the products are
    # float32, and a sum that fuses them otherwise may round to the next
    # bfloat16.
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32),
        atol=2e-6 if dtype == "float32" else 0,
        rtol=2.0 ** -7 if weighted and dtype == "bfloat16" else 1e-7,
    )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(ROUTINGS))
def test_take_rows_forward_and_gradient(name, dtype):
    local, sizes, token, rows, total = _dispatch(name)
    tokens = local.shape[0]
    x = jax.random.normal(jax.random.PRNGKey(3), (tokens, HIDDEN)).astype(
        dtype
    )
    live = (jnp.arange(rows) < total)[:, None]
    # The cotangent of the rows past the last group is whatever the
    # grouped products left there.
    probe = _rows(rows, total, dtype, seed=4)

    def pull(kernel):
        taken, back = jax.vjp(
            lambda x: rc.take_rows(x, token, local, sizes, kernel), x
        )
        return taken, back(probe)[0]

    (taken, got), (plain_taken, plain) = pull(True), pull(False)
    want_taken = jnp.where(live, x[token], 0)
    np.testing.assert_array_equal(taken, want_taken)
    np.testing.assert_array_equal(plain_taken, want_taken)
    assert got.dtype == x.dtype and bool(jnp.all(jnp.isfinite(got)))
    # In float32 whatever the dtype, rounded once.
    want = (
        jnp.zeros((tokens, HIDDEN), jnp.float32)
        .at[token]
        .add(jnp.where(live, probe, 0).astype(jnp.float32))
    )
    atol = 2e-6 if dtype == "float32" else 0
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(dtype).astype(jnp.float32),
        atol=atol,
    )
    np.testing.assert_allclose(
        got.astype(jnp.float32), plain.astype(jnp.float32), atol=atol
    )


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "plain"])
def test_bfloat16_rows_are_summed_in_float32_and_rounded_once(kernel):
    """A token's 8 rows, 1 and seven times 2^-9: a bfloat16 running sum
    in the rows' order stays at 1, the float32 sum rounds to 1 + 2^-6."""
    local, sizes, token, rows, total = _dispatch("tokens_with_all_eight")
    tokens = local.shape[0]
    x = jnp.zeros((tokens, HIDDEN), jnp.bfloat16)
    small = jnp.full((rows, HIDDEN), 2.0 ** -9, jnp.bfloat16)
    seen = jnp.zeros((tokens,), bool).at[token[:total]].set(True)
    # The first row of each token in the rows' order holds the 1.
    earliest = jnp.full((tokens,), rows).at[token[:total]].min(
        jnp.arange(total)
    )
    probe = small.at[jnp.where(seen, earliest, rows)].set(1.0, mode="drop")
    _, back = jax.vjp(
        lambda x: rc.take_rows(x, token, local, sizes, kernel), x
    )
    got = np.asarray(back(probe)[0].astype(jnp.float32))
    running = np.zeros((tokens, HIDDEN), jnp.bfloat16)
    for row, t in enumerate(np.asarray(token[:total])):
        running[t] = running[t] + np.asarray(probe[row])
    running = running.astype(np.float32)
    eight = np.asarray(jnp.sum(local >= 0, axis=1) == COUNT)
    assert eight.sum() == tokens // 8
    np.testing.assert_array_equal(got[eight], 1.0 + 2.0 ** -6)
    np.testing.assert_array_equal(running[eight], 1.0)


@pytest.mark.parametrize(
    "rows,tokens,hidden",
    [(768, 512, 96), (600, 512, 128), (768, 256, 128)],
    ids=["hidden", "rows", "tokens"],
)
def test_a_shape_the_rule_refuses_takes_the_plain_path(rows, tokens, hidden):
    assert not rc.kernel_takes(rows, tokens, hidden)
    chosen = _one_expert(tokens)
    sizes = jnp.asarray([0, 0, 0, 0, 0, tokens, 0, 0], jnp.int32)
    token = jnp.asarray(
        np.concatenate([np.arange(tokens), np.zeros(rows - tokens)]),
        jnp.int32,
    )
    local = jnp.asarray(chosen, jnp.int32)
    data = jax.random.normal(jax.random.PRNGKey(0), (rows, hidden))
    weight = jnp.ones((rows,))
    registry = metrics_lib.registry()
    names = ["moe.row_combine.%s_sites" % k for k in ("kernel", "plain")]
    before = [registry.counter(name).value for name in names]
    got = rc.row_combine(data, weight, token, local, sizes, True)
    taken = rc.take_rows(got, token, local, sizes, True)
    after = [registry.counter(name).value for name in names]
    assert [b - a for a, b in zip(before, after)] == [0, 2]
    np.testing.assert_array_equal(got, data[:tokens])
    np.testing.assert_array_equal(taken[:tokens], data[:tokens])
    assert not np.asarray(taken[tokens:]).any()


def test_the_plan_finds_each_tile_s_rows_where_the_sort_put_them():
    """`plan`'s range of (tile, expert) holds exactly the rows the stable
    sort gave the tile's tokens on that expert, in the tokens' order, and
    the tile's blocks cover them."""
    local, sizes, token, rows, total = _dispatch("balanced")
    block, tile = 8, rc._TILE
    meta = np.asarray(rc.plan(local, sizes, rows, block))
    assert meta.shape == (local.shape[0] // tile, 4 * COUNT + 1)
    token, sizes = np.asarray(token), np.asarray(sizes)
    offsets = np.cumsum(sizes) - sizes
    expert_of = np.repeat(np.arange(COUNT), sizes)
    seen = 0
    for at, line in enumerate(meta):
        first, before = line[:COUNT], line[COUNT : 2 * COUNT + 1]
        begin, length = line[2 * COUNT + 1 : 3 * COUNT + 1], line[3 * COUNT + 1 :]
        for expert in range(COUNT):
            mine = np.flatnonzero(
                (expert_of == expert) & (token[:total] // tile == at)
            )
            assert length[expert] == mine.size
            seen += mine.size
            if not mine.size:
                assert before[expert + 1] == before[expert]
                continue
            assert offsets[expert] <= begin[expert] == mine[0]
            assert (np.diff(mine) == 1).all()
            assert (np.diff(token[mine]) > 0).all()
            assert first[expert] * block <= mine[0]
            covered = (before[expert + 1] - before[expert]) * block
            assert mine[-1] < first[expert] * block + covered
    assert seen == total
