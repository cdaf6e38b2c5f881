"""Each token's sum over its experts' rows, without a scatter.

The sparse layer's dispatch (`models/moe_lm.py::_experts_sorted`) sorts
its (token, expert) pairs by expert and, within an expert, by token, and
a token chooses an expert at most once. Two sums over such rows are a
scatter-add to XLA, which cannot know what the order gives:

- the combine, `y[t] = sum of w[r] * rows[r]` over the rows `r` of token
  `t` (`row_combine`; float32 rows, float32 sum);
- the transpose of the dispatch gather `x[token]` (`take_rows`), the same
  sum without weights over bfloat16 cotangents, accumulated in float32
  and rounded once.

For a tile of consecutive tokens the rows of one expert are ONE
contiguous range, whose start and length are counts of the routing
(`plan`: a compare and a sum a tile, cumulative sums over tiles and
experts; no sort, gather or scatter). The kernel (`kernel=True`; the
caller's choice, `MoeLmConfig.kernels`) keeps a tile's float32 sum in VMEM,
copies the tile's ranges from HBM into one buffer, blocks of whole
sublane tiles at a time and a chunk ahead of the adds, and adds each row
of a range to its token's row of the sum: no two rows of a range share a
token, the rows of a token are added in the rows' order (as XLA's sorted
scatter adds them), and rows outside the ranges, the rows past
`sum(sizes)` among them, are never read from the buffer.

With `kernel=False`, or shapes the tiles do not divide, the plain
`.at[token].add` runs: every CPU run, and the tests' oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adanet_tpu.observability import metrics as metrics_lib

_TILE = 512  # tokens a grid step
_CHUNK = 256  # rows of a tile's buffer copied ahead of the adds
# Rows whose token and weight the kernel's scalar memory holds (8 bytes a
# row; the cell's 40,960 compile for the v5e and run on it).
_MOST_ROWS = 1 << 16


def _interpreted() -> bool:
    """Whether the kernel is interpreted in this process: everywhere but
    on a TPU backend (the CPU tests have no Mosaic)."""
    return jax.default_backend() != "tpu"


def _block(dtype) -> int:
    """Rows of one sublane tile: what a copy from HBM is aligned to."""
    return 32 // jnp.dtype(dtype).itemsize


def kernel_takes(rows: int, tokens: int, hidden: int) -> bool:
    """The static rule on the shapes; anything else is the plain path."""
    return (
        hidden % 128 == 0
        and rows % _CHUNK == 0
        and rows <= _MOST_ROWS
        and tokens % _TILE == 0
    )


def plan(local, sizes, rows: int, block: int, tile: int = _TILE):
    """int32 [tiles, 4 * count + 1]: for each held expert the first block
    of `block` rows that holds rows of the tile's tokens; the blocks of
    the tile's buffer before the expert's, and after the last; the first
    such row; their number.

    `local` [tokens, k]: the held expert (0 .. count-1) of each choice,
    anything else where the expert is not held; `sizes` [count] their
    pairs; `rows` the rows of the dispatch buffer."""
    count = sizes.shape[0]
    chosen = jnp.any(local[:, :, None] == jnp.arange(count), axis=1)
    length = jnp.sum(
        chosen.reshape(-1, tile, count).astype(jnp.int32), axis=1
    )
    starts = jnp.cumsum(sizes) - sizes + jnp.cumsum(length, axis=0) - length
    first = jnp.minimum(starts // block, rows // block - 1)
    last = jnp.minimum(-(-(starts + length) // block), rows // block)
    blocks = jnp.where(length > 0, jnp.maximum(last - first, 0), 0)
    after = jnp.cumsum(blocks, axis=1)
    return jnp.concatenate(
        [first, after - blocks, after[:, -1:], starts, length], axis=1
    ).astype(jnp.int32)


def _kernel(meta, token, *rest, count, block, chunk, tile, weighted, wide):
    rest = list(rest)
    weight = rest.pop(0) if weighted else None
    rows, out, buffer = rest[:3]
    widened = rest[3] if wide else None
    total, slot_of, arrived = rest[-3:]
    tile_id, tiles = pl.program_id(0), pl.num_programs(0)
    stride, per_chunk = 4 * count + 1, chunk // block

    def ranges(tile_at, index, body):
        """`body(expert, shift, low, high)` for each expert: its first
        block less the blocks of the buffer before it, and the blocks of
        chunk `index` of `tile_at`'s buffer that hold its rows. A loop,
        not `count` copies of `body`: the kernel is lowered at every
        site, in every process."""

        def one(expert, carry):
            before = meta[tile_at * stride + count + expert]
            after = meta[tile_at * stride + count + expert + 1]
            body(
                expert,
                meta[tile_at * stride + expert] - before,
                jnp.maximum(before, index * per_chunk),
                jnp.minimum(after, (index + 1) * per_chunk),
            )
            return carry

        jax.lax.fori_loop(0, count, one, None)

    def start(tile_at, index, slot):
        def blocks(_, shift, low, high):
            def one(at, carry):
                source = pl.multiple_of((at + shift) * block, block)
                target = pl.multiple_of(
                    (at - index * per_chunk) * block, block
                )
                pltpu.make_async_copy(
                    rows.at[pl.ds(source, block)],
                    buffer.at[slot, pl.ds(target, block)],
                    arrived.at[slot],
                ).start()
                return carry

            jax.lax.fori_loop(low, high, one, None)

        ranges(tile_at, index, blocks)

    def wait(tile_at, index, slot):
        def one(_, carry):
            # Every copy is one block: any block's descriptor waits for one.
            # jaxlint: disable=JL009(a DMA semaphore inside the kernel, signalled by copies this kernel started on its own chip: no peer, no coordinator)
            pltpu.make_async_copy(
                rows.at[pl.ds(0, block)],
                buffer.at[slot, pl.ds(0, block)],
                arrived.at[slot],
            ).wait()
            return carry

        copied = meta[tile_at * stride + 2 * count] - index * per_chunk
        jax.lax.fori_loop(0, jnp.clip(copied, 0, per_chunk), one, None)

    @pl.when(tile_id == 0)
    def _():
        slot_of[0] = 0
        start(0, 0, 0)

    total[...] = jnp.zeros_like(total)
    chunks = jnp.maximum(
        pl.cdiv(meta[tile_id * stride + 2 * count], per_chunk), 1
    )

    def one_chunk(index, carry):
        slot = slot_of[0]

        # The next chunk's copies, this tile's or the next tile's first,
        # run under this chunk's adds.
        more = index + 1 < chunks

        @pl.when(more | (tile_id + 1 < tiles))
        def _():
            start(
                jnp.where(more, tile_id, tile_id + 1),
                jnp.where(more, index + 1, 0),
                1 - slot,
            )

        wait(tile_id, index, slot)
        if wide:
            widened[...] = buffer[slot].astype(jnp.float32)

        def adds(expert, shift, low, high):
            begin = meta[tile_id * stride + 2 * count + 1 + expert]
            length = meta[tile_id * stride + 3 * count + 1 + expert]
            # The place in this chunk of row `at`.
            place = (shift + index * per_chunk) * block

            def one(at, carry):
                here = pl.ds(at - place, 1)
                row = widened[here, :] if wide else buffer[slot, here, :]
                if weighted:
                    row = row * weight[at]
                total[pl.ds(token[at] - tile_id * tile, 1), :] += row
                return carry

            jax.lax.fori_loop(
                jnp.maximum(begin, (low + shift) * block),
                jnp.minimum(begin + length, (high + shift) * block),
                one,
                None,
            )

        ranges(tile_id, index, adds)
        slot_of[0] = 1 - slot
        return carry

    jax.lax.fori_loop(0, chunks, one_chunk, None)
    out[...] = total[...].astype(out.dtype)


# Jitted so that a program traces the kernel's body and lowers it to Mosaic
# once a signature, not once a site (16 sites in a run of the Mellum2 cell,
# each outside any compile cache: PERF.md section 6, PR 35).
@functools.partial(jax.jit, static_argnames=("tile", "chunk", "interpret"))
def _sum_rows(
    rows, weight, token, meta, tile=_TILE, chunk=_CHUNK, interpret=False
):
    """The kernel: [tokens, hidden] in `rows`' dtype from `rows` [R,
    hidden], float32 or bfloat16, `weight` [R] float32 or None, and
    `meta` the `plan` of tiles of `tile` tokens."""
    tokens, hidden = meta.shape[0] * tile, rows.shape[1]
    weighted = weight is not None
    # A row of a packed dtype is not read alone: the chunk is widened.
    wide = rows.dtype != jnp.float32
    scalars = [meta.reshape(-1), token] + [weight] * weighted
    return pl.pallas_call(
        functools.partial(
            _kernel, count=(meta.shape[1] - 1) // 4,
            block=_block(rows.dtype), chunk=chunk, tile=tile,
            weighted=weighted, wide=wide,
        ),
        out_shape=jax.ShapeDtypeStruct((tokens, hidden), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(tokens // tile,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, hidden), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, chunk, hidden), rows.dtype)]
            + [pltpu.VMEM((chunk, hidden), jnp.float32)] * wide
            + [
                pltpu.VMEM((tile, hidden), jnp.float32),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            # A step starts the next step's first copies.
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20,
        ),
        interpret=interpret,
        name="row_combine",
    )(*scalars, rows)


def _live(rows: int, sizes):
    """[rows, 1]: the rows that belong to a group."""
    return (jnp.arange(rows) < jnp.sum(sizes))[:, None]


def _sum(rows, weight, token, local, sizes, taken):
    """[tokens, hidden] in `rows`' dtype, summed in float32: the kernel
    where `taken`, else `.at[token].add`; rows past `sum(sizes)` count
    for nothing in either."""
    if taken:
        meta = plan(local, sizes, rows.shape[0], _block(rows.dtype))
        return _sum_rows(rows, weight, token, meta, interpret=_interpreted())
    live = _live(rows.shape[0], sizes)
    scaled = rows.astype(jnp.float32)
    if weight is not None:
        scaled = scaled * weight[:, None]
    return (
        jnp.zeros((local.shape[0], rows.shape[1]), jnp.float32)
        .at[token]
        .add(jnp.where(live, scaled, 0.0))
        .astype(rows.dtype)
    )


def _site(kernel: bool, rows: int, tokens: int, hidden: int) -> bool:
    """Whether this site takes the kernel; counts it either way."""
    taken = bool(kernel) and kernel_takes(rows, tokens, hidden)
    metrics_lib.registry().counter(
        "moe.row_combine.%s_sites" % ("kernel" if taken else "plain")
    ).inc()
    return taken


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _combine(rows, weight, token, local, sizes, taken):
    return _sum(rows, weight, token, local, sizes, taken)


def _combine_forward(rows, weight, token, local, sizes, taken):
    return (
        _sum(rows, weight, token, local, sizes, taken),
        (rows, weight, token, sizes),
    )


def _combine_backward(taken, kept, upstream):
    rows, weight, token, sizes = kept
    live = _live(rows.shape[0], sizes)
    pulled = upstream[token]
    return (
        jnp.where(live, pulled * weight[:, None], 0.0).astype(rows.dtype),
        jnp.sum(jnp.where(live, pulled * rows, 0.0), axis=-1),
        None, None, None,
    )


_combine.defvjp(_combine_forward, _combine_backward)


def row_combine(rows, weight, token, local, sizes, kernel=False):
    """float32 [tokens, hidden]: `y[t]` the sum of `weight[r] * rows[r]`
    over the rows `r < sum(sizes)` with `token[r] == t`.

    `rows` [R, hidden] and `weight` [R] float32, sorted by (expert,
    token) as `local` [tokens, k] (the held expert of each choice of a
    token, 0 .. count-1, anything else where it is not held) and `sizes`
    [count] (the pairs on each held expert) say."""
    taken = _site(kernel, rows.shape[0], local.shape[0], rows.shape[1])
    return _combine(rows, weight, token, local, sizes, taken)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _take(x, token, local, sizes, taken):
    return jnp.where(
        _live(token.shape[0], sizes), x[token], jnp.zeros((), x.dtype)
    )


def _take_forward(x, token, local, sizes, taken):
    return _take(x, token, local, sizes, taken), (token, local, sizes)


def _take_backward(taken, kept, upstream):
    token, local, sizes = kept
    # The transpose of the forward's select, as JAX itself would write it:
    # the kernel reads none of these rows, but XLA fuses the select into
    # the sum of the cotangents that reaches here, which so keeps this
    # scope in a profile (a fusion carries the name of one of its ops).
    upstream = jnp.where(
        _live(token.shape[0], sizes), upstream, jnp.zeros((), upstream.dtype)
    )
    return _sum(upstream, None, token, local, sizes, taken), None, None, None


_take.defvjp(_take_forward, _take_backward)


def take_rows(x, token, local, sizes, kernel=False):
    """`x[token]` [R, hidden], zero in the rows past `sum(sizes)`; its
    transpose is the sum above without weights, in float32 whatever `x`'s
    dtype and rounded once."""
    taken = _site(kernel, token.shape[0], x.shape[0], x.shape[1])
    return _take(x, token, local, sizes, taken)
