"""The Mellum2 decoder share (`adanet_tpu/models/moe_lm.py`), plainly.

Layer l: `h = x + Attn_l(RMSNorm(x))`, `y = h + MoE_l(RMSNorm(h))`, no
biases, eps from `sizes`. Attention: grouped-query heads of `head_dim`
with rotary q and k, masked S x S softmax a block of queries at a time;
`sliding_attention` sees keys i - window < j <= i under plain RoPE,
`full_attention` all j <= i under YaRN as Hugging Face computes it (a
blend of `base^(-2i/d)` and the same over `factor` by a linear ramp
between the dimensions `beta_fast` and `beta_slow` give, cos and sin
times `attention_factor`). MoE: softmax over the whole router, the k
largest, renormalised; EVERY held expert applied to every token and
masked by the weight the token gave it. Loss: mean next-token
cross-entropy plus `balance_loss_weight * E * sum_e f_e P_e` a layer.

The candidate holds a share of each layer (`num_heads` query heads over
`num_kv_heads`, experts `experts_held`, the ids of its embedding); what it
computes is that share's partial result, here as there.

Loss and gradients come a sequence at a time: a first pass counts the
pairs on every expert of every layer over the whole batch (f_e is the
batch's), a second takes `jax.grad` of each sequence's part.

`arith`: "f32" is the reference (float32, `Precision.HIGHEST`); "bf16"
rounds the matrix products' operands as the configuration states; "fp8"
is the control: "bf16" with both operands of every matrix product rounded
to scaled float8_e4m3 and the gradient coming back to float8_e5m2
(`reference/layers.py`). Router, softmax, norms and loss are float32 in
all three.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import layers

QUERY_BLOCK = 1024


def _matmul(a, b, arith):
    if arith == "f32":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    def low(x, y):
        return jnp.matmul(
            x.astype(jnp.bfloat16), y.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )

    if arith == "fp8":
        return layers._fp8_conv(low, a, b)
    return low(a, b)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope_tables(group, dim, seq):
    """(cos, sin) [seq, dim] of one `rope_parameters` group."""
    base = float(group["rope_theta"])
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inv_freq, factor = 1.0 / pos_freqs, 1.0
    if group["rope_type"] == "yarn":
        def correction(rotations):
            return dim * math.log(
                group["original_max_position_embeddings"]
                / (rotations * 2 * math.pi)
            ) / (2 * math.log(base))

        low = max(math.floor(correction(group["beta_fast"])), 0)
        high = min(math.ceil(correction(group["beta_slow"])), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
        inv_freq = (1.0 / (group["factor"] * pos_freqs)) * ramp + (
            1.0 / pos_freqs
        ) * (1.0 - ramp)
        factor = group["attention_factor"]
    elif group["rope_type"] != "default":
        raise SystemExit("benchmarks: no rope_type %r" % group["rope_type"])
    angles = np.arange(seq)[:, None] * inv_freq[None, :]
    angles = np.concatenate([angles, angles], -1)
    return (jnp.asarray(np.cos(angles) * factor, jnp.float32),
            jnp.asarray(np.sin(angles) * factor, jnp.float32))


def _rotate(x, cos, sin):
    """x [S, H, D]."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def attention(w, prefix, x, kind, sizes, arith):
    """x [S, hidden] normed -> the held heads' part of the output."""
    seq, depth = x.shape[0], sizes["head_dim"]
    heads, kv_heads = sizes["num_heads"], sizes["num_kv_heads"]
    q = _matmul(x, w[prefix + "/q/kernel"], arith).reshape(seq, heads, depth)
    k = _matmul(x, w[prefix + "/k/kernel"], arith).reshape(
        seq, kv_heads, depth)
    v = _matmul(x, w[prefix + "/v/kernel"], arith).reshape(
        seq, kv_heads, depth)
    cos, sin = rope_tables(sizes["rope_parameters"][kind], depth, seq)
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    cols = jnp.arange(seq)[None, :]
    outs = []
    for start in range(0, seq, QUERY_BLOCK):
        rows = jnp.arange(start, min(start + QUERY_BLOCK, seq))[:, None]
        keep = cols <= rows
        if kind == "sliding_attention":
            keep &= cols > rows - sizes["sliding_window"]
        scores = jnp.swapaxes(_matmul(
            jnp.swapaxes(q[start:start + QUERY_BLOCK], 0, 1),
            jnp.transpose(k, (1, 2, 0)), arith,
        ), 0, 1) / math.sqrt(depth)  # [rows, H, S]
        scores = jnp.where(keep[:, None, :], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.swapaxes(_matmul(
            jnp.swapaxes(probs, 0, 1), jnp.swapaxes(v, 0, 1), arith
        ), 0, 1))
    out = jnp.concatenate(outs, 0).reshape(seq, heads * depth)
    return _matmul(out, w[prefix + "/o/kernel"], arith)


def route(w, prefix, x, sizes):
    """(p [S, E], chosen p renormalised [S, k], chosen experts [S, k])."""
    logits = jnp.matmul(
        x, w[prefix + "/router/kernel"], precision=jax.lax.Precision.HIGHEST
    )
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, sizes["num_experts_per_tok"])
    if sizes["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    return probs, top_p, top_e


def experts(w, prefix, x, top_p, top_e, sizes, arith):
    """Every held expert on every token, masked by its weight."""
    first, count = sizes["experts_held"]
    total = jnp.zeros_like(x)
    for index in range(count):
        weight = jnp.sum(jnp.where(top_e == first + index, top_p, 0.0), -1)
        hidden = jax.nn.silu(
            _matmul(x, w[prefix + "/gate/kernel"][index], arith)
        ) * _matmul(x, w[prefix + "/up/kernel"][index], arith)
        total = total + weight[:, None] * _matmul(
            hidden, w[prefix + "/down/kernel"][index], arith
        )
    return total


def layer(w, index, kind, x, sizes, arith):
    """(y, p [S, E], chosen experts [S, k]) of layer `index` on x [S, D]."""
    prefix = "layer_%d" % index
    eps = sizes["rms_norm_eps"]
    x = x + attention(
        w, prefix + "/attention",
        _rms_norm(x, w[prefix + "/attention_norm/scale"], eps),
        kind, sizes, arith,
    )
    normed = _rms_norm(x, w[prefix + "/moe_norm/scale"], eps)
    probs, top_p, top_e = route(w, prefix + "/moe", normed, sizes)
    out = experts(w, prefix + "/moe", normed, top_p, top_e, sizes, arith)
    return x + out, probs, top_e


def forward(w, tokens, sizes, arith="f32", remat=True):
    """tokens [S] -> (logits [S, V], [p [S, E]] a layer, [chosen [S, k]])."""
    x = w["embedding"][tokens]
    all_probs, chosen = [], []
    for index, kind in enumerate(sizes["layer_types"]):
        def step(w, x, index=index, kind=kind):
            return layer(w, index, kind, x, sizes, arith)

        x, probs, top_e = (jax.checkpoint(step) if remat else step)(w, x)
        all_probs.append(probs)
        chosen.append(top_e)
    hidden = _rms_norm(x, w["final_norm/scale"], sizes["rms_norm_eps"])
    return _matmul(hidden, w["lm_head/kernel"], arith), all_probs, chosen


def _pairs(chosen, sizes):
    """[layers, E]: the token-expert pairs on every expert."""
    return jnp.stack([
        jnp.sum(jax.nn.one_hot(top_e, sizes["router_width"]), axis=(0, 1))
        for top_e in chosen
    ])


@functools.partial(jax.jit, static_argnames=("sizes", "arith"))
def _count(w, tokens, sizes, arith):
    _, _, chosen = forward(w, tokens, sizes.value, arith, remat=False)
    return _pairs(chosen, sizes.value), jnp.stack(chosen)


@functools.partial(jax.jit, static_argnames=("sizes", "arith"))
def _sequence(w, tokens, labels, share, total, sizes, arith):
    """One sequence's part of the batch's loss, and its gradient: its
    tokens' cross-entropy over `total` tokens, and its part of P_e against
    the batch's share f_e of pairs [layers, E]."""

    def part(w):
        logits, all_probs, _ = forward(w, tokens, sizes.value, arith)
        logp = jax.nn.log_softmax(logits)
        entropy = -jnp.sum(
            jnp.take_along_axis(logp, labels[:, None], axis=-1)
        ) / total
        balance = sum(
            jnp.sum(share[index] * jnp.sum(probs, 0) / total)
            for index, probs in enumerate(all_probs)
        ) * sizes.value["balance_loss_weight"] * sizes.value["router_width"]
        return entropy + balance, entropy

    (_, entropy), grads = jax.value_and_grad(part, has_aux=True)(w)
    return entropy, grads


class _Static:
    """A dict of sizes as a jit-static argument."""

    def __init__(self, value):
        self.value = value
        self._key = repr(sorted(value.items(), key=lambda kv: kv[0]))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return self._key == other._key


def loss_and_gradients(w, tokens, labels, sizes, arith="f32"):
    """tokens, labels [B, S] -> (mean cross-entropy, {path: gradient} of
    cross-entropy plus balance loss, chosen experts [B, layers, S, k],
    pairs on every expert [layers, E])."""
    static = _Static(sizes)
    counted = [_count(w, row, static, arith) for row in tokens]
    pairs = sum(c[0] for c in counted)
    share = pairs / jnp.sum(pairs, axis=-1, keepdims=True)
    total = float(tokens.shape[0] * tokens.shape[1])
    entropy, grads = 0.0, None
    for row, want in zip(tokens, labels):
        value, part = _sequence(w, row, want, share, total, static, arith)
        entropy = entropy + value
        grads = part if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, part
        )
    return entropy, grads, jnp.stack([c[1] for c in counted]), pairs
