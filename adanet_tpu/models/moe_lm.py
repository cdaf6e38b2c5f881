"""A sparse decoder language model as an AdaNet candidate.

Decoder layers that differ by kind (sliding-window or full attention,
each with its own rotary parameterisation) over a mixture-of-experts
feed-forward with no shared expert, as Mellum2 publishes them. The
candidate may hold a SHARE of every layer, as one chip of a group that
shares each layer would: some query heads with their key-value heads,
some of the experts, some of the vocabulary. It is then told which
experts it holds, routes over all of them, and computes its own experts'
part without dropping a token; the attention output over the held heads
and the experts' sum over the held experts are partial results and go on
to the next layer as they are. No exchange runs.

Per layer: `h = x + Attn(RMSNorm(x))`, `y = h + MoE(RMSNorm(h))`, no
biases. Parameters are float32; matrix products run in `compute_dtype`
(bfloat16) with float32 accumulation; router logits, softmax statistics,
norms, the residual stream and the loss are float32.

Production paths: attention a block of queries at a time
(`ops/block_attention.py`); sort-based dispatch, a grouped matrix
product over the held experts and each token's sum over its experts' rows
(`moe_forward`, `ops/grouped_dot.py`, `ops/row_combine.py`); logits that
are never held whole (`core/heads.py::BlockedLogits`), chosen by their
shape.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from adanet_tpu.core.heads import BlockedLogits
from adanet_tpu.observability import metrics as metrics_lib
from adanet_tpu.ops.block_attention import block_attention
from adanet_tpu.ops.grouped_dot import grouped_dot
from adanet_tpu.ops.row_combine import row_combine, take_rows
from adanet_tpu.subnetwork import Builder, SimpleGenerator, Subnetwork

SLIDING, FULL = "sliding_attention", "full_attention"
_PAIR_TILE = 512
# Rows of the dispatch buffer over the pairs that balanced routing puts on
# the held experts; a load past it takes the dense path.
_PAIR_CAPACITY = 1.25
# Sequences a layer takes at a time (the batch's common divisor with it):
# routing is a token's own, so the chunks change no result, and what a
# layer holds beside its input is a chunk's and not the batch's.
_LAYER_BATCH_CHUNK = 4


@dataclasses.dataclass(frozen=True)
class Rope:
    """One layer kind's rotary parameters, as Hugging Face names them."""

    rope_type: str = "default"
    rope_theta: float = 10000.0
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class MoeLmConfig:
    vocab_size: int  # ids held: the embedding's rows, the head's columns
    hidden_size: int
    layer_types: Tuple[str, ...]
    num_heads: int  # query heads held
    num_kv_heads: int  # key-value heads held
    head_dim: int
    sliding_window: int
    rope: Mapping[str, Rope]
    num_experts: int  # the router's width: every expert of the layer
    experts_held: Tuple[int, int]  # (first, count)
    num_experts_per_tok: int
    expert_width: int
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    balance_loss_weight: float = 0.001
    compute_dtype: Any = jnp.bfloat16
    attention_block: int = 512
    loss_block: int = 4096
    # Logits of more elements than this are never held whole (1 GiB of
    # float32): the choice is by their shape.
    whole_logits_limit: int = 1 << 28
    # Whether attention, the experts' grouped products and the sums of
    # their rows run the TPU's Pallas kernels (`ops/block_attention.py`,
    # `ops/grouped_dot.py`, `ops/row_combine.py`) or plain XLA. None is
    # resolved here, once: the kernels on a TPU.
    kernels: Optional[bool] = None

    def __post_init__(self):
        if self.kernels is None:
            object.__setattr__(
                self, "kernels", jax.default_backend() == "tpu"
            )


# ------------------------------------------------------------------ rotary


def rope_inv_freq(rope: Rope, dim: int):
    """(inv_freq [dim / 2], factor on cos and sin), as Hugging Face's
    `rope_type` "default" and "yarn" compute them."""
    exponents = np.arange(0, dim, 2, dtype=np.float64) / dim
    pos_freqs = rope.rope_theta ** exponents
    if rope.rope_type == "default":
        return 1.0 / pos_freqs, 1.0
    if rope.rope_type != "yarn":
        raise ValueError("rope_type %r is not known" % rope.rope_type)

    def correction_dim(rotations):
        return (
            dim
            * math.log(
                rope.original_max_position_embeddings
                / (rotations * 2 * math.pi)
            )
        ) / (2 * math.log(rope.rope_theta))

    low = max(math.floor(correction_dim(rope.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rope.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip(
        (np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1
    )
    extrapolation = 1.0 - ramp
    inv_freq = (1.0 / (rope.factor * pos_freqs)) * (1 - extrapolation) + (
        1.0 / pos_freqs
    ) * extrapolation
    factor = rope.attention_factor
    if factor is None:
        factor = 0.1 * math.log(rope.factor) + 1.0
    return inv_freq, factor


def rope_tables(rope: Rope, dim: int, seq: int):
    """cos and sin, float32 [seq, dim]."""
    inv_freq, factor = rope_inv_freq(rope, dim)
    angles = np.arange(seq, dtype=np.float64)[:, None] * inv_freq[None, :]
    angles = np.concatenate([angles, angles], axis=-1)
    return (
        jnp.asarray(np.cos(angles) * factor, jnp.float32),
        jnp.asarray(np.sin(angles) * factor, jnp.float32),
    )


def apply_rope(x, cos, sin):
    """x [B, S, H, D] float32; the second half of D rotates the first."""
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + rotated * sin[None, :, None, :]


# ----------------------------------------------------------------- experts


def route(x, router_kernel, cfg: MoeLmConfig):
    """(p over all experts [T, E], the chosen p [T, k] renormalised, the
    chosen experts [T, k]); float32 throughout, softmax before top-k."""
    logits = jnp.dot(
        x.astype(jnp.float32),
        router_kernel.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return probs, top_p, top_e


def balance_loss(pairs, probs_sum, tokens, cfg: MoeLmConfig):
    """`weight * E * sum_e f_e P_e` over all E experts: f_e the share of
    token-expert pairs on e (`pairs` [E] counts them), P_e the mean of p_e
    over the `tokens` tokens (`probs_sum` [E] sums it)."""
    share = jax.lax.stop_gradient(pairs / jnp.sum(pairs))
    return cfg.balance_loss_weight * cfg.num_experts * jnp.sum(
        share * probs_sum / tokens
    )


def _expert(x, gate, up, down, dtype):
    hidden = jax.nn.silu(
        jnp.dot(x, gate.astype(dtype), preferred_element_type=jnp.float32)
    ) * jnp.dot(x, up.astype(dtype), preferred_element_type=jnp.float32)
    return jnp.dot(
        hidden.astype(dtype), down.astype(dtype),
        preferred_element_type=jnp.float32,
    )


def _experts_dense(x, top_p, local, gate, up, down, dtype):
    """Every held expert applied to every token and weighted by the p the
    token gave it (0 where it did not choose it): what the dispatch
    computes, at `count` times the work. The path of a load past the
    dispatch buffer, so that no token is ever dropped."""

    # Recomputed in the backward pass an expert at a time: nothing of an
    # expert's [tokens, width] products is kept.
    @jax.checkpoint
    def part(index, g, u, d):
        with jax.named_scope("lm.moe_route"):
            weight = jnp.sum(jnp.where(local == index, top_p, 0.0), axis=-1)
        with jax.named_scope("lm.moe_experts"):
            return weight[:, None] * _expert(x, g, u, d, dtype)

    def one(total, expert):
        return total + part(*expert), None

    total, _ = jax.lax.scan(
        one,
        jnp.zeros(x.shape, jnp.float32),
        (jnp.arange(gate.shape[0]), gate, up, down),
    )
    return total


def _experts_sorted(
    x, top_p, local, sizes, gate, up, down, dtype, rows, kernel
):
    """Sort-based dispatch: the pairs on held experts, sorted by expert
    and within an expert by token, gathered into `rows` rows; three
    grouped products; each row weighted by its p and added to its token
    (`ops/row_combine.py`, as is the gather's transpose)."""
    count, k = gate.shape[0], local.shape[-1]
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count).reshape(-1)
    with jax.named_scope("lm.moe_route"):
        order = jnp.argsort(key, stable=True)[:rows]
        token = order // k
        weight = top_p.reshape(-1)[order]
        # Rows past the last group belong to no product: zero going in,
        # unread coming out, so that neither pass reads what nobody wrote.
        xs = take_rows(x, token, local, sizes, kernel)
    with jax.named_scope("lm.moe_experts"):
        hidden = jax.nn.silu(
            grouped_dot(xs, gate, sizes, kernel)
        ) * grouped_dot(xs, up, sizes, kernel)
        out = grouped_dot(hidden.astype(dtype), down, sizes, kernel)
    with jax.named_scope("lm.moe_route"):
        return row_combine(out, weight, token, local, sizes, kernel)


def pair_rows(tokens: int, cfg: MoeLmConfig) -> int:
    """Rows of the dispatch buffer for `tokens` tokens."""
    count = cfg.experts_held[1]
    balanced = tokens * cfg.num_experts_per_tok * count / cfg.num_experts
    rows = math.ceil(_PAIR_CAPACITY * balanced / _PAIR_TILE) * _PAIR_TILE
    return int(min(rows, tokens * min(cfg.num_experts_per_tok, count)))


def moe_forward(x, router_kernel, gate, up, down, cfg, rows=None):
    """x [T, D] float32 -> (the held experts' part of the layer's output
    [T, D] float32, {"pairs": the pairs on every expert [E], "probs": the
    sum of p [E], "chosen": the experts each token chose [T, k]})."""
    first, count = cfg.experts_held
    dtype = cfg.compute_dtype
    with jax.named_scope("lm.moe_route"):
        probs, top_p, top_e = route(x, router_kernel, cfg)
        pairs = jnp.sum(
            jax.nn.one_hot(top_e, cfg.num_experts, dtype=jnp.float32),
            axis=(0, 1),
        )
        local = top_e - first
        sizes = pairs[first : first + count].astype(jnp.int32)
        xc = x.astype(dtype)
    rows = pair_rows(x.shape[0], cfg) if rows is None else rows
    out = jax.lax.cond(
        jnp.sum(sizes) > rows,
        lambda: _experts_dense(xc, top_p, local, gate, up, down, dtype),
        lambda: _experts_sorted(
            xc, top_p, local, sizes, gate, up, down, dtype, rows,
            cfg.kernels,
        ),
    )
    return out, {
        "pairs": pairs, "probs": jnp.sum(probs, axis=0), "chosen": top_e
    }


# ----------------------------------------------------------------- modules


class _Kernel(nn.Module):
    """A float32 parameter `<name>/kernel`."""

    shape: Tuple[int, ...]
    init: Any = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self):
        return self.param("kernel", self.init, self.shape, jnp.float32)


class _Linear(nn.Module):
    features: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (x.shape[-1], self.features), jnp.float32,
        )
        return jnp.dot(
            x.astype(self.dtype), kernel.astype(self.dtype),
            preferred_element_type=jnp.float32,
        )


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", nn.initializers.ones, (x.shape[-1],), jnp.float32
        )
        x = x.astype(jnp.float32)
        variance = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(variance + self.eps) * scale


class Attention(nn.Module):
    """The held heads' part of the layer's attention output."""

    config: MoeLmConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        batch, seq, _ = x.shape
        dtype, depth = cfg.compute_dtype, cfg.head_dim
        with jax.named_scope("lm.attention"):
            q = _Linear(cfg.num_heads * depth, dtype, name="q")(x)
            k = _Linear(cfg.num_kv_heads * depth, dtype, name="k")(x)
            v = _Linear(cfg.num_kv_heads * depth, dtype, name="v")(x)
            cos, sin = rope_tables(cfg.rope[self.kind], depth, seq)
            q = apply_rope(q.reshape(batch, seq, -1, depth), cos, sin)
            k = apply_rope(k.reshape(batch, seq, -1, depth), cos, sin)
            v = v.reshape(batch, seq, -1, depth)
            with jax.named_scope("lm.attention_core"):
                out = block_attention(
                    q.astype(dtype), k.astype(dtype), v.astype(dtype),
                    window=(
                        cfg.sliding_window if self.kind == SLIDING else None
                    ),
                    block=cfg.attention_block,
                    kernel=cfg.kernels,
                )
            return _Linear(cfg.hidden_size, dtype, name="o")(
                out.reshape(batch, seq, -1)
            )


class Experts(nn.Module):
    """The held experts' part of the layer's feed-forward output."""

    config: MoeLmConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        count, width = cfg.experts_held[1], cfg.expert_width
        stacked = nn.initializers.lecun_normal(batch_axis=(0,))
        router = _Kernel(
            (cfg.hidden_size, cfg.num_experts), name="router"
        )()
        kernels = [
            _Kernel(shape, stacked, name=name)()
            for name, shape in (
                ("gate", (count, cfg.hidden_size, width)),
                ("up", (count, cfg.hidden_size, width)),
                ("down", (count, width, cfg.hidden_size)),
            )
        ]
        out, sums = moe_forward(
            x.reshape(-1, x.shape[-1]), router, *kernels, cfg
        )
        return out.reshape(x.shape), sums


class Layer(nn.Module):
    """One decoder layer over some of the batch's sequences: the body of
    the scan that `MoeLm` makes over the batch, so that what a layer holds
    beside its input (scores, dispatch buffers, the experts' products) is
    a chunk's and not the batch's."""

    config: MoeLmConfig
    kind: str

    @nn.compact
    def __call__(self, carry, x):
        cfg = self.config
        with jax.named_scope("lm.attention"):
            normed = RMSNorm(cfg.rms_norm_eps, name="attention_norm")(x)
        x = x + Attention(cfg, self.kind, name="attention")(normed)
        with jax.named_scope("lm.moe_route"):
            normed = RMSNorm(cfg.rms_norm_eps, name="moe_norm")(x)
        out, sums = Experts(cfg, name="moe")(normed)
        return carry, (x + out, sums)


class MoeLm(nn.Module):
    """Token ids [batch, seq] -> a `Subnetwork` over batch x seq rows."""

    config: MoeLmConfig
    logits_dimension: int

    @nn.compact
    def __call__(self, features, training: bool = False):
        cfg = self.config
        del training  # no dropout, no statistics
        tokens = (
            features["tokens"] if isinstance(features, dict) else features
        )
        if self.logits_dimension != cfg.vocab_size:
            raise ValueError(
                "the head asks for %d logits, the candidate holds %d ids"
                % (self.logits_dimension, cfg.vocab_size)
            )
        registry = metrics_lib.registry()
        for kind in (SLIDING, FULL):
            registry.counter(
                "moe_lm.layers.%s" % kind.split("_")[0]
            ).inc(cfg.layer_types.count(kind))
        registry.counter("moe.experts_held").inc(cfg.experts_held[1])
        embedding = self.param(
            "embedding", nn.initializers.normal(1.0),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32,
        )
        x = embedding[tokens]
        batch, seq = tokens.shape
        chunk = math.gcd(batch, _LAYER_BATCH_CHUNK)
        layer = nn.scan(
            nn.remat(Layer),
            variable_broadcast="params",
            split_rngs={"params": False},
        )
        first, count = cfg.experts_held
        balance, loads, chosen = 0.0, [], []
        for index, kind in enumerate(cfg.layer_types):
            _, (x, sums) = layer(cfg, kind, name="layer_%d" % index)(
                (), x.reshape(batch // chunk, chunk, seq, cfg.hidden_size)
            )
            x = x.reshape(batch, seq, cfg.hidden_size)
            pairs = jnp.sum(sums["pairs"], axis=0)
            balance = balance + balance_loss(
                pairs, jnp.sum(sums["probs"], axis=0), batch * seq, cfg
            )
            loads.append(pairs[first : first + count])
            chosen.append(sums["chosen"].reshape(batch * seq, -1))
        with jax.named_scope("lm.loss"):
            hidden = RMSNorm(cfg.rms_norm_eps, name="final_norm")(x)
            hidden = hidden.reshape(-1, cfg.hidden_size)
            kernel = _Kernel(
                (cfg.hidden_size, cfg.vocab_size), name="lm_head"
            )()
            # The product's operand, as the product rounds it: the rows
            # are kept once, in the width the blocks read.
            logits = BlockedLogits.of(
                hidden.astype(cfg.compute_dtype), kernel, cfg.loss_block,
                cfg.compute_dtype,
            )
            if hidden.shape[0] * cfg.vocab_size <= cfg.whole_logits_limit:
                logits = logits.materialize()
        loads = jnp.stack(loads)
        return Subnetwork(
            last_layer=hidden,
            logits=logits,
            complexity=math.sqrt(len(cfg.layer_types)),
            extras={
                "balance_loss": balance,
                # [layers, tokens, k], for whoever checks the routing;
                # nothing of the step reads it.
                "chosen": jnp.stack(chosen),
                # Pairs on the fullest held expert over the mean, worst
                # layer; and the held experts' pairs a token.
                "load_max_over_mean": jnp.max(
                    jnp.max(loads, axis=-1)
                    / jnp.maximum(jnp.mean(loads, axis=-1), 1.0)
                ),
                "held_pairs_per_token": jnp.mean(
                    jnp.sum(loads, axis=-1)
                ) / (batch * seq),
            },
        )


class MoeLmBuilder(Builder):
    """AdaNet builder of one `MoeLm`: the head's loss plus the layers'
    balance losses, AdamW with decay on matrices under a global-norm
    clip, the rate warmed up linearly."""

    # One jitted program initialises the candidate (`core/iteration.py`):
    # run eagerly, Flax's init would be a forward pass op by op.
    jit_init = True
    # Builder summaries that also set a gauge at every log line.
    gauge_summaries = ("moe.load_max_over_mean",)

    def __init__(
        self,
        config: MoeLmConfig,
        learning_rate: float = 3e-4,
        warmup_steps: int = 1000,
        weight_decay: float = 0.1,
        clip_norm: float = 1.0,
        name: Optional[str] = None,
    ):
        self._config = config
        self._learning_rate = learning_rate
        self._warmup_steps = warmup_steps
        self._weight_decay = weight_decay
        self._clip_norm = clip_norm
        self._name = name

    @property
    def name(self) -> str:
        cfg = self._config
        return self._name or "moe_lm_%dl_%dd_%de" % (
            len(cfg.layer_types), cfg.hidden_size, cfg.experts_held[1]
        )

    def build_subnetwork(self, logits_dimension, previous_ensemble=None):
        return MoeLm(self._config, logits_dimension)

    def build_train_optimizer(self, previous_ensemble=None):
        import optax

        def rate(count):
            # Linear warm-up, the (count + 1)-th update at (count + 1) /
            # warmup_steps of the rate. At the full rate from the first
            # update a fresh router collapses within 15 steps (PERF.md
            # section 6, PR 34), and the step's length then swings with
            # the load.
            return self._learning_rate * jnp.minimum(
                1.0, (count + 1.0) / self._warmup_steps
            )

        return optax.chain(
            optax.clip_by_global_norm(self._clip_norm),
            optax.adamw(
                rate, b1=0.9, b2=0.95, eps=1e-8,
                weight_decay=self._weight_decay,
                mask=lambda params: jax.tree_util.tree_map(
                    lambda leaf: leaf.ndim >= 2, params
                ),
            ),
        )

    def build_subnetwork_loss(self, subnetwork, labels, head, context):
        del context
        return (
            head.loss(subnetwork.logits, labels)
            + subnetwork.extras["balance_loss"]
        )

    def build_subnetwork_summaries(self, subnetwork, features, labels):
        del features, labels
        return {
            "moe.load_max_over_mean": subnetwork.extras["load_max_over_mean"],
            "moe.held_pairs_per_token": subnetwork.extras[
                "held_pairs_per_token"
            ],
        }


def generator(config: MoeLmConfig, **builder_kwargs) -> SimpleGenerator:
    return SimpleGenerator([MoeLmBuilder(config, **builder_kwargs)])
