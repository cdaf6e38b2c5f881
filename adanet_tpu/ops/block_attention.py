"""Causal grouped-query attention a block of queries at a time.

The production path of a sequence candidate (`models/moe_lm.py`): no
S x S array exists. Queries go in blocks of `block` rows; a block meets
only the keys it may see, a static slice: all keys up to its last row
for a full layer, the last `window` of them for a sliding layer, so a
sliding layer's work grows with S x (window + block) and a full layer's
with S^2 / 2. Each block is a `jax.checkpoint`: the backward pass
recomputes its scores and holds one block of them at a time.

Matrix products run in the operands' dtype (bfloat16 on the chip) with
float32 accumulation; masking and softmax statistics are float32.
`parallel/ring_attention.py::full_attention` stays the oracle.

With `kernel=True` (the caller's choice: `models/moe_lm.py` resolves it
once, in `MoeLmConfig.kernels`), at head sizes and lengths its tiles
divide, the same function runs JAX's own Pallas kernel for this masked
attention on the TPU instead (`_kernel_attention`), in blocks of `block`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def key_span(index: int, block: int, window: Optional[int]):
    """[lo, hi) of the keys that query block `index` may see, `lo` on a
    block boundary. Query i sees keys j with i - window < j <= i."""
    hi = (index + 1) * block
    if window is None:
        return 0, hi
    first_key = index * block - window + 1
    return max(0, first_key // block * block), hi


@functools.partial(jax.checkpoint, static_argnums=(3, 4, 5))
def _block(q, k, v, q_start, k_start, window):
    """q [B, Q, KV, G, D], k and v [B, K, KV, D] -> [B, Q, KV, G, D]."""
    depth = q.shape[-1]
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32
    ) * (1.0 / depth**0.5)
    rows = q_start + jnp.arange(q.shape[1])[:, None]
    cols = k_start + jnp.arange(k.shape[1])[None, :]
    keep = cols <= rows
    if window is not None:
        keep &= cols > rows - window
    scores = jnp.where(keep, scores, _NEG_INF)
    top = jnp.max(scores, axis=-1, keepdims=True)
    weights = jnp.exp(scores - top)
    total = jnp.sum(weights, axis=-1)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", weights.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out / jnp.transpose(total, (0, 3, 1, 2))[..., None]


def _kernel_fits(block: int, depth: int) -> bool:
    """Whether the TPU kernel's tiles divide these sizes; where not, the
    blockwise path above runs."""
    return depth % 128 == 0 and block % 128 == 0


@functools.lru_cache(maxsize=None)
def _splash(seq, groups, window, block):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel,
        splash_attention_mask as masks,
    )

    one = (
        masks.CausalMask((seq, seq)) if window is None
        else masks.LocalMask((seq, seq), (window - 1, 0), 0)
    )
    sizes = kernel.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        use_fused_bwd_kernel=True,
    )
    # Built once and kept: its mask tables must be values, not the
    # tracers of whichever trace asked first.
    with jax.ensure_compile_time_eval():
        return kernel.make_splash_mqa_single_device(
            masks.MultiHeadMask([one] * groups), block_sizes=sizes
        )


def _kernel_attention(q, k, v, window, block):
    """The same attention by the Pallas kernel that JAX ships for the TPU
    (`splash_attention`): scores never leave the chip's fast memory and
    blocks outside the mask are skipped, forward and backward. At 4
    sequences of 8,192, 4 query heads over 1, forward and backward, in
    blocks of 1,024 (my chip run 3, PR 34): window 1,024: 5.01 ms against
    7.76 ms for the blocks above at 512; full: 8.62 ms against 37.58 ms."""
    batch, seq, heads, depth = q.shape
    kv_heads = k.shape[2]
    run = _splash(seq, heads // kv_heads, window, block)
    q = (q * (1.0 / depth**0.5)).astype(q.dtype).reshape(
        batch, seq, kv_heads, heads // kv_heads, depth
    )
    out = jax.vmap(jax.vmap(run))(
        jnp.transpose(q, (0, 2, 3, 1, 4)),
        jnp.transpose(k, (0, 2, 1, 3)),
        jnp.transpose(v, (0, 2, 1, 3)),
    )  # [B, KV, G, S, D]
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(
        batch, seq, heads, depth
    ).astype(jnp.float32)


def block_attention(
    q, k, v, window: Optional[int] = None, block: int = 512,
    kernel: bool = False,
):
    """Causal attention. q [B, S, H, D]; k, v [B, S, KV, D] with KV
    dividing H (query head h reads key-value head h // (H / KV)).
    `window` None: every key j <= i; else keys i - window < j <= i.
    `kernel`: the TPU's Pallas kernel where its tiles divide `block` and D.
    Returns float32 [B, S, H, D]."""
    batch, seq, heads, depth = q.shape
    kv_heads = k.shape[2]
    if heads % kv_heads:
        raise ValueError(
            "%d query heads do not divide over %d key-value heads"
            % (heads, kv_heads)
        )
    block = min(block, seq)
    if seq % block:
        raise ValueError(
            "sequence length %d is not a whole number of blocks of %d"
            % (seq, block)
        )
    if kernel and _kernel_fits(block, depth):
        return _kernel_attention(q, k, v, window, block)
    q = q.reshape(batch, seq, kv_heads, heads // kv_heads, depth)
    outs = []
    for index in range(seq // block):
        lo, hi = key_span(index, block, window)
        outs.append(
            _block(
                q[:, index * block : hi], k[:, lo:hi], v[:, lo:hi],
                index * block, lo, window,
            )
        )
    return jnp.concatenate(outs, axis=1).reshape(batch, seq, heads, depth)
