"""The sparse decoder candidate (`adanet_tpu/models/moe_lm.py`) and the
paths it forced, at a toy size on the CPU, against the plain reference
(`benchmarks/reference/mellum2_moe.py`) on seeded weights."""

import dataclasses
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import adanet_tpu
from adanet_tpu.core.heads import BlockedLogits, MultiClassHead
from adanet_tpu.ensemble import ComplexityRegularizedEnsembler
from adanet_tpu.models import moe_lm
from adanet_tpu.ops.block_attention import block_attention, key_span
from adanet_tpu.subnetwork import Subnetwork
from benchmarks import count_lm_flops, weights
from benchmarks.factories import moe_lm as factory
from benchmarks.reference import mellum2_moe as reference

VOCAB, SEQ = 64, 32
ROPE = {
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782,
    },
}


def sizes_of(**changes):
    sizes = {
        "hidden_size": 32, "head_dim": 8, "num_heads": 2, "num_kv_heads": 1,
        "layer_types": ["sliding_attention", "full_attention"],
        "sliding_window": 8, "rope_parameters": ROPE, "router_width": 16,
        "experts_held": [4, 4], "num_experts_per_tok": 4, "expert_width": 16,
        "norm_topk_prob": True, "rms_norm_eps": 1e-6,
        "balance_loss_weight": 0.001, "compute_dtype": "float32",
        "attention_block": 8, "loss_block": 16,
        "whole_logits_limit": 1 << 28,
    }
    sizes.update(changes)
    return sizes


def planted(sizes, seed=3):
    """(flat reference weights, the program's nested params)."""
    config = factory.model_config(sizes, VOCAB)
    module = moe_lm.MoeLm(config, VOCAB)
    shapes = jax.eval_shape(
        lambda: module.init(
            jax.random.PRNGKey(0), {"tokens": jnp.zeros((2, SEQ), jnp.int32)}
        )
    )["params"]
    flat = {
        "/".join(k.key for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]
    }
    made = weights.make(seed, 0, flat)
    count = sizes["experts_held"][1]
    for path in made:
        if path.split("/")[-2:-1] in (["gate"], ["up"], ["down"]):
            made[path] = made[path] * np.float32(math.sqrt(count))
        elif path == "embedding":
            made[path] = made[path] * np.float32(10.0)
    nested = {}
    for path, value in made.items():
        node = nested
        *parents, leaf = path.split("/")
        for part in parents:
            node = node.setdefault(part, {})
        node[leaf] = jnp.asarray(value)
    return module, {k: jnp.asarray(v) for k, v in made.items()}, nested


def tokens_of(batch=2, seed=0):
    ids = np.random.default_rng(seed).integers(0, VOCAB, (batch, SEQ + 1))
    return jnp.asarray(ids[:, :-1], jnp.int32), jnp.asarray(
        ids[:, 1:], jnp.int32
    )


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_layer_kind_matches_reference(kind):
    sizes = sizes_of(layer_types=[kind])
    module, flat, nested = planted(sizes)
    tokens, _ = tokens_of()
    out = module.apply({"params": nested}, {"tokens": tokens})
    for row in range(tokens.shape[0]):
        logits, _, chosen = reference.forward(flat, tokens[row], sizes)
        np.testing.assert_allclose(
            out.logits[row * SEQ : (row + 1) * SEQ], logits,
            rtol=2e-4, atol=2e-4,
        )
        np.testing.assert_array_equal(
            np.sort(out.extras["chosen"][0, row * SEQ : (row + 1) * SEQ]),
            np.sort(chosen[0]),
        )


@pytest.mark.parametrize("rows", [None, 8], ids=["sorted", "overflow_dense"])
def test_expert_layer_matches_reference(rows):
    """The dispatch, and the dense path that a load past the buffer takes,
    against every held expert applied densely; gradients to the router
    through p and to the experts included."""
    sizes = sizes_of()
    config = factory.model_config(sizes, VOCAB)
    _, flat, _ = planted(sizes)
    prefix = "layer_0/moe"
    x = jax.random.normal(jax.random.PRNGKey(1), (SEQ * 2, 32), jnp.float32)
    names = ("router", "gate", "up", "down")
    kernels = tuple(flat["%s/%s/kernel" % (prefix, n)] for n in names)

    def ours(x, kernels):
        out, _ = moe_lm.moe_forward(x, *kernels, config, rows=rows)
        return out

    def theirs(x, kernels):
        w = {"%s/%s/kernel" % (prefix, n): k for n, k in zip(names, kernels)}
        _, top_p, top_e = reference.route(w, prefix, x, sizes)
        return reference.experts(w, prefix, x, top_p, top_e, sizes, "f32")

    np.testing.assert_allclose(
        ours(x, kernels), theirs(x, kernels), rtol=1e-4, atol=1e-5
    )
    probe = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * probe), (0, 1))(x, kernels)
    want = jax.grad(lambda *a: jnp.sum(theirs(*a) * probe), (0, 1))(
        x, kernels
    )
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5)


def _dense_attention(q, k, v, window):
    groups = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, groups, 2), jnp.repeat(v, groups, 2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    rows = jnp.arange(q.shape[1])[:, None]
    cols = jnp.arange(q.shape[1])[None, :]
    keep = cols <= rows
    if window is not None:
        keep &= cols > rows - window
    probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@pytest.mark.parametrize("window", [8, 5, None])
@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_block_attention_matches_dense(window, what):
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (2, SEQ, 4, 8))
    k = jax.random.normal(keys[1], (2, SEQ, 2, 8))
    v = jax.random.normal(keys[2], (2, SEQ, 2, 8))
    probe = jax.random.normal(keys[3], q.shape)
    if what == "forward":
        np.testing.assert_allclose(
            block_attention(q, k, v, window, block=8),
            _dense_attention(q, k, v, window), rtol=1e-5, atol=1e-5,
        )
        return
    got = jax.grad(
        lambda *a: jnp.sum(block_attention(*a, window, block=8) * probe),
        (0, 1, 2),
    )(q, k, v)
    want = jax.grad(
        lambda *a: jnp.sum(_dense_attention(*a, window) * probe), (0, 1, 2)
    )(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("index,window,want", [
    (0, None, (0, 8)), (3, None, (0, 32)), (0, 8, (0, 8)), (1, 8, (0, 16)),
    (3, 8, (16, 32)), (3, 9, (16, 32)), (3, 10, (8, 32)),
])
def test_key_span_skips_blocks_outside_the_window(index, window, want):
    assert key_span(index, 8, window) == want


_SITES = tuple(
    "blocked_logits.%s_sites" % kind
    for kind in ("grad_in_forward", "forward_only")
)


def _sites():
    from adanet_tpu.observability import metrics as metrics_lib

    return [metrics_lib.registry().counter(name).value for name in _SITES]


# Which operands the gradient is taken to (the arguments of `loss` below):
# the member's rows and kernel (a candidate's loss), the ensemble's scale
# and bias (an ensemble whose mixture weights train), or all of them and
# the example weights.
_WRT = {"rows_and_kernel": (1, 2), "scales_and_bias": (0,),
        "all": (0, 1, 2, 3)}


@pytest.mark.parametrize("wrt", sorted(_WRT))
@pytest.mark.parametrize("weighted", [False, True])
def test_blocked_loss_through_the_ensembler(weighted, wrt):
    """`w * logits + bias` and the loss over blocks of rows against the
    whole array: values and gradients to the ensemble's parameters, to
    the member's hidden rows and kernel and to the example weights,
    whichever are differentiated; the gradients come from the forward
    pass (`blocked_logits.grad_in_forward_sites`), an undifferentiated
    loss is one scan (`forward_only_sites`)."""
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    hidden = jax.random.normal(keys[0], (64, 16))
    kernel = jax.random.normal(keys[1], (16, VOCAB)) * 0.3
    labels = jax.random.randint(keys[2], (64,), 0, VOCAB)
    example_weights = (
        jax.random.uniform(keys[3], (64,)) if weighted else None
    )
    argnums = _WRT[wrt][: 3 if example_weights is None else None]
    head = MultiClassHead(VOCAB, top_k=0)
    ensembler = ComplexityRegularizedEnsembler(use_bias=True)

    def loss(params, hidden, kernel, example_weights, blocked):
        logits = BlockedLogits.of(hidden, kernel, 16, jnp.float32)
        if not blocked:
            logits = logits.materialize()
        member = Subnetwork(last_layer=hidden, logits=logits, complexity=1.0)
        ensemble = ensembler.build_ensemble(params, [member])
        assert isinstance(ensemble.logits, BlockedLogits) == blocked
        return head.loss(ensemble.logits, labels, example_weights)

    params = {"weights": [jnp.float32(0.7)], "bias": jnp.linspace(-1, 1, VOCAB)}
    args = (params, hidden, kernel, example_weights)
    before = _sites()
    got = jax.value_and_grad(loss, argnums)(*args, True)
    assert [b - a for a, b in zip(before, _sites())] == [1, 0]
    want = jax.value_and_grad(loss, argnums)(*args, False)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)
    before = _sites()
    metrics = head.eval_metrics(
        BlockedLogits.of(hidden, kernel, 16, jnp.float32), labels,
        example_weights,
    )
    assert [b - a for a, b in zip(before, _sites())] == [0, 1]
    whole = head.eval_metrics(hidden @ kernel, labels, example_weights)
    for name in ("average_loss", "accuracy"):
        np.testing.assert_allclose(metrics[name], whole[name], rtol=1e-5)


def _vocabulary_products(jaxpr, classes):
    """The `dot_general`s of `jaxpr` and every jaxpr inside it with a
    dimension of `classes` in an operand or the result."""
    from jax.extend import core as jax_core

    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            count += any(
                classes in var.aval.shape
                for var in (*eqn.invars, *eqn.outvars)
            )
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else [param]:
                if isinstance(sub, jax_core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax_core.Jaxpr):
                    count += _vocabulary_products(sub, classes)
    return count


@pytest.mark.parametrize("wrt,products", [
    ((0, 1), 3), ((2,), 1), ((), 1),
])
def test_blocked_loss_gradient_is_three_products_a_block(wrt, products):
    """A block's products over the vocabulary, as the cell runs them
    (bfloat16 rows, a float32 kernel): the forward's, then the rows' and
    the kernel's gradients in the same scan, and no recomputation; the
    forward's alone where only the scale is differentiated, or nothing."""
    hidden = jnp.ones((64, 16), jnp.bfloat16)
    kernel = jnp.ones((16, VOCAB), jnp.float32)
    labels = jnp.zeros((64,), jnp.int32)
    head = MultiClassHead(VOCAB, top_k=0)

    def loss(hidden, kernel, scale):
        logits = BlockedLogits.of(hidden, kernel, 16, jnp.bfloat16)
        return head.loss(logits * scale, labels)

    fn = jax.value_and_grad(loss, wrt) if wrt else loss
    jaxpr = jax.make_jaxpr(fn)(hidden, kernel, jnp.float32(0.5)).jaxpr
    assert _vocabulary_products(jaxpr, VOCAB) == products


def test_logits_are_blocked_by_shape_alone():
    sizes = sizes_of()
    tokens, _ = tokens_of()
    for limit, blocked in ((1 << 28, False), (2 * SEQ * VOCAB - 1, True)):
        module, _, nested = planted(sizes_of(whole_logits_limit=limit))
        out = module.apply({"params": nested}, {"tokens": tokens})
        assert isinstance(out.logits, BlockedLogits) == blocked
    del sizes


def test_the_shares_add_up():
    """The 8 shares' partial attention and expert outputs of one layer
    (share j: query heads 2j, 2j+1 over key-value head j // 2, experts 2j,
    2j+1) sum to the uncut reference layer's."""
    uncut = sizes_of(num_heads=16, num_kv_heads=4, experts_held=[0, 16])
    depth, hidden = uncut["head_dim"], uncut["hidden_size"]
    keys = jax.random.split(jax.random.PRNGKey(5), 9)
    w = {
        "a/q/kernel": jax.random.normal(keys[0], (hidden, 16 * depth)) * 0.2,
        "a/k/kernel": jax.random.normal(keys[1], (hidden, 4 * depth)) * 0.2,
        "a/v/kernel": jax.random.normal(keys[2], (hidden, 4 * depth)) * 0.2,
        "a/o/kernel": jax.random.normal(keys[3], (16 * depth, hidden)) * 0.2,
        "m/router/kernel": jax.random.normal(keys[4], (hidden, 16)) * 0.2,
        "m/gate/kernel": jax.random.normal(keys[5], (16, hidden, 16)) * 0.2,
        "m/up/kernel": jax.random.normal(keys[6], (16, hidden, 16)) * 0.2,
        "m/down/kernel": jax.random.normal(keys[7], (16, 16, hidden)) * 0.2,
    }
    x = jax.random.normal(keys[8], (SEQ, hidden))
    for kind in uncut["layer_types"]:
        whole = reference.attention(w, "a", x, kind, uncut, "f32")
        parts = 0.0
        for share in range(8):
            held = factory.model_config(sizes_of(), VOCAB)
            q = slice(share * 2 * depth, (share + 1) * 2 * depth)
            kv = slice(share // 2 * depth, (share // 2 + 1) * depth)
            params = {
                "q": {"kernel": w["a/q/kernel"][:, q]},
                "k": {"kernel": w["a/k/kernel"][:, kv]},
                "v": {"kernel": w["a/v/kernel"][:, kv]},
                "o": {"kernel": w["a/o/kernel"][q]},
            }
            parts = parts + moe_lm.Attention(held, kind).apply(
                {"params": params}, x[None]
            )[0]
        np.testing.assert_allclose(parts, whole, rtol=1e-4, atol=1e-5)
    _, top_p, top_e = reference.route(w, "m", x, uncut)
    whole = reference.experts(w, "m", x, top_p, top_e, uncut, "f32")
    parts = 0.0
    for share in range(8):
        held = factory.model_config(
            sizes_of(experts_held=[2 * share, 2]), VOCAB
        )
        mine = slice(2 * share, 2 * share + 2)
        out, _ = moe_lm.moe_forward(
            x, w["m/router/kernel"], w["m/gate/kernel"][mine],
            w["m/up/kernel"][mine], w["m/down/kernel"][mine], held,
        )
        parts = parts + out
    np.testing.assert_allclose(parts, whole, rtol=1e-4, atol=1e-5)


def test_loss_and_balance_match_reference():
    """Cross-entropy plus the layers' balance losses, and their gradient,
    against the reference a sequence at a time."""
    sizes = sizes_of()
    module, flat, nested = planted(sizes)
    tokens, labels = tokens_of()
    builder = moe_lm.MoeLmBuilder(factory.model_config(sizes, VOCAB))
    head = MultiClassHead(VOCAB, top_k=0)

    def loss(params):
        out = module.apply({"params": params}, {"tokens": tokens})
        return builder.build_subnetwork_loss(out, labels, head, None)

    grads = jax.grad(loss)(nested)
    entropy, want, _, _ = reference.loss_and_gradients(
        dict(flat), tokens, labels, sizes
    )
    out = module.apply({"params": nested}, {"tokens": tokens})
    np.testing.assert_allclose(
        head.loss(out.logits, labels), entropy, rtol=1e-5
    )
    assert float(out.extras["balance_loss"]) > 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(grads)[0]:
        name = "/".join(k.key for k in path)
        np.testing.assert_allclose(
            leaf, want[name], rtol=2e-3, atol=2e-6, err_msg=name
        )


def test_three_steps_through_estimator_train_against_reference():
    """The rehearsal fixture's check, held tight: the program's first
    three steps through `Estimator.train` (blocked loss, dispatch, sharded
    or whole state as saved) against the float32 reference with AdamW."""
    from benchmarks import run

    cell = run.Cell("rehearsal_lm_tiny")
    search = run.Search(cell, 11)
    try:
        cell.check.prepare(search)
        search.free()
        read = cell.check.read(search)
    finally:
        search.close()
    assert read["steps"] == 0
    for name in ("loss1", "loss2", "loss3", "average"):
        assert read[name] < 2e-3, (name, read)
    assert read["gradient_median"] < 0.02, read
    assert read["change_median"] < 0.02, read
    assert read["same_expert"] < 0.05, read


def test_token_ids_feed_is_the_same_bytes_for_a_seed():
    feeds = importlib.import_module("benchmarks.feeds.token_ids")
    traffic = {"batch": 3, "seq": 16, "ring": 2}
    make = lambda seed: feeds.ring(
        np.random.default_rng([seed, 0xFEED]), traffic, {"vocab_size": 97}
    )
    first, again, other = make(7), make(7), make(8)
    for (fa, la), (fb, lb) in zip(first, again):
        assert fa["tokens"].tobytes() == fb["tokens"].tobytes()
        assert la.tobytes() == lb.tobytes()
    assert first[0][0]["tokens"].tobytes() != other[0][0]["tokens"].tobytes()
    tokens, labels = first[0][0]["tokens"], first[0][1]
    assert tokens.shape == labels.shape == (3, 16)
    assert tokens.dtype == np.int32 and tokens.max() < 97
    np.testing.assert_array_equal(tokens[:, 1:], labels[:, :-1])


@pytest.mark.parametrize("window", [None, 8, 40])
def test_visible_keys_counts_the_mask(window):
    rows = np.arange(SEQ)[:, None]
    cols = np.arange(SEQ)[None, :]
    keep = cols <= rows
    if window is not None:
        keep &= cols > rows - window
    assert count_lm_flops.visible_keys(SEQ, window) == int(keep.sum())


def test_count_lm_flops_against_a_lowered_reference_forward():
    """The count's terms against XLA's own count of the lowered reference
    forward, where the two compute the same products: every expert chosen
    by every token (k = E = held: balanced IS dense) and the reference's
    whole S x S scores in place of the keys a query may see."""
    sizes = sizes_of(
        router_width=4, experts_held=[0, 4], num_experts_per_tok=4,
        hidden_size=128, expert_width=64, head_dim=32,
    )
    _, flat, _ = planted(sizes)
    shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in flat.items()}
    lowered = jax.jit(
        lambda w, t: reference.forward(w, t, sizes, remat=False)[0]
    ).lower(shapes, jax.ShapeDtypeStruct((SEQ,), jnp.int32))
    counted = count_lm_flops.forward_flops(sizes, VOCAB, SEQ)
    whole_scores = (
        len(sizes["layer_types"]) * 4 * sizes["head_dim"]
        * sizes["num_heads"] * SEQ * SEQ
    )
    expected = (
        counted - count_lm_flops.attention_core_flops(sizes, SEQ)
        + whole_scores
    )
    assert lowered.cost_analysis()["flops"] == pytest.approx(
        expected, rel=0.05
    )
    assert count_lm_flops.forward_flops(
        sizes_of(**{
            k: v for k, v in __import__("json").load(open(
                "benchmarks/configs/mellum2_12b_ep8_4l.json"
            ))["members"]["mellum2_ep8_4l"]["sizes"].items()
        }), 12288, 8192,
    ) == 1189717475328


def test_counters_count_each_trace():
    from adanet_tpu.observability import metrics as metrics_lib

    registry = metrics_lib.registry()
    names = ("moe_lm.layers.sliding", "moe_lm.layers.full",
             "moe.experts_held", "blocked_logits.row_blocks") + _SITES
    module, _, nested = planted(sizes_of(whole_logits_limit=0))
    tokens, labels = tokens_of()
    before = [registry.counter(name).value for name in names]
    out = module.apply({"params": nested}, {"tokens": tokens})
    MultiClassHead(VOCAB, top_k=0).loss(out.logits, labels)
    after = [registry.counter(name).value for name in names]
    assert [b - a for a, b in zip(before, after)] == [1, 1, 4, 4, 0, 1]


# ---------------------------------------------- the row sums' kernel path

_ROW_SITES = tuple(
    "moe.row_combine.%s_sites" % kind for kind in ("kernel", "plain")
)


def _row_sites():
    from adanet_tpu.observability import metrics as metrics_lib

    registry = metrics_lib.registry()
    return np.array([registry.counter(name).value for name in _ROW_SITES])


def _wide(**changes):
    """Sizes the row sums' kernel takes: 128 wide, and every expert held
    (so 512 tokens x 4 choices are 2,048 pairs, whatever the router)."""
    return sizes_of(
        hidden_size=128, head_dim=32, experts_held=[0, 16], **changes
    )


@pytest.mark.parametrize(
    "rows", [2304, 2048, 256], ids=["below", "at", "past_dense"]
)
def test_expert_layer_kernel_path_matches_plain(rows):
    """`moe_forward` with the row sums' kernel (interpreted) against the
    plain path, value and gradients to x, the router and the three expert
    kernels, with the pairs below, at and past the buffer's rows (the last
    takes the dense branch in both)."""
    plain = factory.model_config(_wide(), VOCAB)
    assert plain.kernels is False
    kernel = dataclasses.replace(plain, kernels=True)
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(keys[0], (512, 128), jnp.float32)
    weights = (
        jax.random.normal(keys[1], (128, 16)) * 0.3,
        jax.random.normal(keys[2], (16, 128, 16)) * 0.1,
        jax.random.normal(keys[3], (16, 128, 16)) * 0.1,
        jax.random.normal(keys[4], (16, 16, 128)) * 0.1,
    )
    probe = jax.random.normal(keys[5], x.shape)

    def run(config):
        before = _row_sites()
        value, grads = jax.value_and_grad(
            lambda x, w: jnp.sum(
                moe_lm.moe_forward(x, *w, config, rows=rows)[0] * probe
            ),
            (0, 1),
        )(x, weights)
        return value, grads, list(_row_sites() - before)

    got, got_grads, kernel_sites = run(kernel)
    want, want_grads, plain_sites = run(plain)
    # Both branches of the `cond` are traced whatever the load: one
    # dispatch, two sites (the gather and the combine).
    assert kernel_sites == [2, 0] and plain_sites == [0, 2]
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def _count_eqns(jaxpr, found):
    """`found(eqn)` summed over a jaxpr and every jaxpr inside it."""
    total = 0
    for eqn in jaxpr.eqns:
        total += found(eqn)
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    total += _count_eqns(inner, found)
    return total


# Sites counted by one trace of the gradient below: JAX traces each of the
# 2 layers' dispatch twice (the scan's body under `nn.remat`, and again
# for its linearisation), two sites each.
ROW_SITES_A_GRADIENT = 8


@pytest.mark.parametrize("kernels", [True, False], ids=["kernel", "plain"])
def test_gradient_holds_the_row_kernel_twice_a_layer(kernels):
    """One `jax.make_jaxpr` of the candidate's gradient (2 layers, each
    under `nn.remat` in a scan over chunks of 512 tokens): the kernel
    stands twice a layer (the combine, and the gather's transpose; the
    recomputed combine is dead) where XLA's two scatter-adds of [tokens,
    hidden] stood, and the counters count two sites a trace of a layer."""
    sizes = _wide(whole_logits_limit=0)
    config = dataclasses.replace(
        factory.model_config(sizes, VOCAB), kernels=kernels
    )
    module = moe_lm.MoeLm(config, VOCAB)
    tokens = jnp.zeros((2, 256), jnp.int32)
    params = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), {"tokens": tokens})
    )["params"]
    head = MultiClassHead(VOCAB, top_k=0)

    def loss(params):
        out = module.apply({"params": params}, {"tokens": tokens})
        return head.loss(out.logits, tokens) + out.extras["balance_loss"]

    before = _row_sites()
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    sites = list(_row_sites() - before)
    calls = _count_eqns(
        jaxpr.jaxpr,
        lambda eqn: eqn.primitive.name == "pallas_call"
        and "row_combine" in str(eqn.params.get("name", ""))
        + str(eqn.params.get("name_and_src_info", "")),
    )
    scatters = _count_eqns(
        jaxpr.jaxpr,
        lambda eqn: eqn.primitive.name == "scatter-add"
        and eqn.outvars[0].aval.shape == (512, 128),
    )
    layers = len(config.layer_types)
    if kernels:
        assert (calls, scatters) == (2 * layers, 0)
        assert sites[1] == 0 and sites[0] == ROW_SITES_A_GRADIENT
    else:
        assert (calls, scatters) == (0, 2 * layers)
        assert sites[0] == 0 and sites[1] == ROW_SITES_A_GRADIENT
