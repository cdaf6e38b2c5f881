"""NASNet-A for CIFAR (Zoph et al., arXiv:1707.07012, Fig. 4 and App. A.7;
the 6@768 network of improve_nas, arXiv:1903.06236), written plainly.

The benchmark's yardstick for the `nasnet_a_*` configurations: forward,
loss, gradients and the momentum update in straightforward `jax.numpy`,
float32 with `Precision.HIGHEST`, no kernels. It imports nothing of the
program and is handed only what the benchmark itself made from the seed
(weights as a flat `{"a/b/c": array}` dict, batches). Parameter names are
the checkpoint's, so that one dict feeds both sides.

Departures from the published description, each because the program under
test does the same and the comparison is of arithmetic, not of design:
average pooling divides by the full window at the border (slim excludes
the padding); batch norm uses epsilon 1e-3; drop-path is left out: its keep
probability is `1 - step/total_steps * ...`, exactly 1 at the first step
and above 0.9999 at the third, so the few examples it drops there are part
of the measured gap (PERF.md).

`arith` selects the arithmetic (`layers.py`): "f32" is the reference,
"bf16" the configuration's own as a second witness, "fp8" the control.
Every forward stage also returns the statistics of each batch norm's
batch, {name: (mean, variance)}, which the program keeps after its first
step as its running ones.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference.layers import Net, cross_entropy, pool

NORMAL = dict(
    ops=(
        "separable_5x5_2", "separable_3x3_2", "separable_5x5_2",
        "separable_3x3_2", "avg_pool_3x3", "none", "avg_pool_3x3",
        "avg_pool_3x3", "separable_3x3_2", "none",
    ),
    inputs=(0, 1, 1, 1, 0, 1, 1, 1, 0, 0),
    used=(1, 0, 0, 0, 0, 0, 0),
)
REDUCTION = dict(
    ops=(
        "separable_5x5_2", "separable_7x7_2", "max_pool_3x3",
        "separable_7x7_2", "avg_pool_3x3", "separable_5x5_2", "none",
        "avg_pool_3x3", "separable_3x3_2", "max_pool_3x3",
    ),
    inputs=(0, 1, 0, 1, 0, 1, 3, 2, 2, 0),
    used=(1, 1, 1, 0, 0, 0, 0),
)
BN_EPSILON = 1e-3


def _factorized_reduction(net, x, filters, stride, names):
    if stride == 1:
        return net.bn(net.conv(x, names[0]), names[1])
    path1 = net.conv(x[:, ::2, ::2, :], "path1_conv")
    shifted = jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0)))[:, 1:, 1:, :]
    path2 = net.conv(shifted[:, ::2, ::2, :], "path2_conv")
    assert path1.shape[-1] == filters // 2
    assert path2.shape[-1] == filters - filters // 2
    return net.bn(jnp.concatenate([path1, path2], -1), "final_path_bn")


def _operation(net, x, op, stride, filters, name):
    in_filters = x.shape[-1]
    if op.startswith("separable"):
        _, size, layers = op.split("_")
        size, sep = int(size[0]), net.sub(name + "_sep")
        for layer in range(int(layers)):
            x = jax.nn.relu(x)
            x = sep.conv(
                x, "depthwise_%d" % layer, stride if layer == 0 else 1,
                groups=x.shape[-1],
            )
            x = sep.conv(x, "pointwise_%d" % layer)
            x = sep.bn(x, "bn_%d" % layer)
        return x
    if op == "none":
        if stride > 1 or in_filters != filters:
            x = net.conv(jax.nn.relu(x), name + "_1x1", stride)
            x = net.bn(x, name + "_bn1")
        return x
    kind, _, size = op.split("_")
    x = pool(x, kind, int(size[0]), stride, "SAME")
    if in_filters != filters:
        x = net.bn(net.conv(x, name + "_1x1"), name + "_bn1")
    return x


def _cell(net, spec, x, prev, filters, stride):
    if prev is None:
        prev = x
    elif prev.shape[2] != x.shape[2]:
        prev = _factorized_reduction(
            net.sub("reduce_prev"), jax.nn.relu(prev), filters, 2, None
        )
    elif prev.shape[-1] != filters:
        prev = net.bn(net.conv(jax.nn.relu(prev), "prev_1x1"), "prev_bn")
    x = net.bn(net.conv(jax.nn.relu(x), "beginning_1x1"), "beginning_bn")

    states = [x, prev]
    for block in range(5):
        sides = []
        for side, slot in (("left", 2 * block), ("right", 2 * block + 1)):
            source = spec["inputs"][slot]
            sides.append(
                _operation(
                    net,
                    states[source],
                    spec["ops"][slot],
                    # Only the cell's own two inputs are strided.
                    stride if source < 2 else 1,
                    filters,
                    "block%d_%s" % (block, side),
                )
            )
        states.append(sides[0] + sides[1])

    last, out = states[-1], []
    for index, used in enumerate(spec["used"]):
        if used:
            continue
        state = states[index]
        if state.shape[2] != last.shape[2]:
            state = _factorized_reduction(
                net.sub("reduction_%d" % index), state, last.shape[-1], 2,
                None,
            )
        elif state.shape[-1] != last.shape[-1]:
            state = _factorized_reduction(
                net.sub("reduction_%d" % index), state, last.shape[-1], 1,
                ("path_conv", "path_bn"),
            )
        out.append(state)
    return jnp.concatenate(out, -1)


def _aux_head(net, x):
    x = pool(jax.nn.relu(x), "avg", 5, 3, "VALID")
    x = jax.nn.relu(net.bn(net.conv(x, "proj"), "aux_bn0"))
    x = jax.nn.relu(net.bn(net.conv(x, "full", padding="VALID"), "aux_bn1"))
    return net.dense(x.reshape(x.shape[0], -1), "aux_logits")


def _stages(sizes):
    """The network as a chain: (scope, what, width, stride) a stage. Every
    stage but the stem maps (x, the x before it) to the next x."""
    num_cells, filters = sizes["num_cells"], sizes["num_conv_filters"]
    reductions = [int(k / 3.0 * num_cells) for k in (1, 2)]
    chain, scale = [], 1
    for index in range(num_cells):
        if index in reductions:
            scale *= 2
            chain.append((
                "reduction_cell_%d" % reductions.index(index), "reduction",
                filters * scale, 2,
            ))
        chain.append(("cell_%d" % index, "normal", filters * scale, 1))
    return chain, "cell_%d" % (reductions[1] - 1)


def _under(weights, scope):
    """The weights below one scope, under their names within it."""
    return {
        key[len(scope) + 1:]: value for key, value in weights.items()
        if key.startswith(scope + "/")
    }


def _stage(weights, x, prev, what, width, stride, arith, stats=None):
    spec = NORMAL if what == "normal" else REDUCTION
    return _cell(Net(weights, "", arith, BN_EPSILON, stats=stats), spec, x,
                 prev, width, stride)


def _stem(weights, images, arith, stats=None):
    net = Net(weights, "", arith, BN_EPSILON, stats=stats)
    return net.bn(net.conv(images.astype(net.act), "stem_conv"), "stem_bn")


def _aux_loss(weights, x, labels, smoothing, arith):
    stats = {}
    logits = _aux_head(
        Net(weights, "aux_head", arith, BN_EPSILON, stats=stats), x
    )
    return cross_entropy(logits, labels, smoothing), stats


def _head(weights, x, labels, smoothing, arith):
    net = Net(weights, "", arith, BN_EPSILON)
    pooled = jnp.mean(jax.nn.relu(x), (1, 2)).astype(jnp.float32)
    logits = net.dense(pooled, "logits")
    return cross_entropy(logits, labels, smoothing), logits


def forward(weights, images, sizes, arith="f32", scope="nasnet"):
    """Training-mode logits and auxiliary logits for a batch of images."""
    weights = _under(weights, scope)
    chain, aux_after = _stages(sizes)
    x, prev, aux = _stem(weights, images, arith), None, None
    for name, what, width, stride in chain:
        x, prev = _stage(
            _under(weights, name), x, prev, what, width, stride, arith
        ), x
        if name == aux_after:
            aux = _aux_head(Net(weights, "aux_head", arith, BN_EPSILON), x)
    labels = jnp.zeros((images.shape[0],), jnp.int32)
    return _head(weights, x, labels, 0.0, arith)[1], aux


# One compiled program a distinct stage shape, forward and backward: the
# float32 pass of a whole batch is run stage by stage (batch norm couples
# the rows, so it cannot be cut into blocks of rows), keeps only what
# passes between stages, and its programs stay small.
_STATIC = ("what", "width", "stride", "arith")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _stage_forward(weights, x, prev, what, width, stride, arith):
    stats = {}
    return _stage(weights, x, prev, what, width, stride, arith, stats), stats


@functools.partial(jax.jit, static_argnames="arith")
def _stem_forward(weights, images, arith):
    stats = {}
    return _stem(weights, images, arith, stats), stats


@functools.partial(jax.jit, static_argnames=_STATIC)
def _stage_backward(weights, x, prev, upstream, what, width, stride, arith):
    _, pull = jax.vjp(
        lambda w, a, b: _stage(w, a, b, what, width, stride, arith),
        weights, x, prev,
    )
    return pull(upstream)


@functools.partial(jax.jit, static_argnames="arith")
def _stem_backward(weights, images, upstream, arith):
    _, pull = jax.vjp(lambda w: _stem(w, images, arith), weights)
    return pull(upstream)[0]


@functools.partial(jax.jit, static_argnames=("smoothing", "arith"))
def _head_backward(weights, x, labels, smoothing, arith):
    (_, logits), grads = jax.value_and_grad(_head, (0, 1), has_aux=True)(
        weights, x, labels, smoothing, arith
    )
    return logits, grads


@functools.partial(jax.jit, static_argnames=("smoothing", "arith"))
def _aux_backward(weights, x, labels, weight, smoothing, arith):
    def weighted(w, a):
        loss, stats = _aux_loss(w, a, labels, smoothing, arith)
        return weight * loss, stats

    return jax.grad(weighted, (0, 1), has_aux=True)(weights, x)


def _pick(weights, prefix):
    return {k: v for k, v in weights.items() if k.startswith(prefix)}


def loss_and_gradients(weights, images, labels, sizes, arith="f32",
                       rng=None, scope="nasnet"):
    """(the member's plain logits, the gradients of the loss it trains on:
    smoothed cross-entropy plus the weighted auxiliary head, the batch
    statistics of every batch norm). `rng` is not used: drop-path is left
    out (see the head of this file)."""
    local = _under(weights, scope)
    smoothing = sizes["label_smoothing"]
    chain, aux_after = _stages(sizes)
    stats = {}

    def note(found, under=""):
        stats.update({
            "%s/%s%s" % (scope, under, k): v for k, v in found.items()
        })

    # states[i] is what stage i is given: (x, the x before it).
    x, found = _stem_forward(_pick(local, "stem_"), images, arith=arith)
    note(found)
    states = [(x, None)]
    for name, what, width, stride in chain:
        x, prev = states[-1]
        out, found = _stage_forward(
            _under(local, name), x, prev, what=what, width=width,
            stride=stride, arith=arith,
        )
        note(found, name + "/")
        states.append((out, x))

    logits, (grads, upstream) = _head_backward(
        _pick(local, "logits/"), states[-1][0], labels,
        smoothing=smoothing, arith=arith,
    )
    grads = dict(grads)
    owed = None  # what the stage after owes this stage's x as its `prev`
    for index in reversed(range(len(chain))):
        name, what, width, stride = chain[index]
        if name == aux_after:
            (d_aux, extra), found = _aux_backward(
                _pick(local, "aux_head/"), states[index + 1][0], labels,
                jnp.float32(sizes["aux_head_weight"]),
                smoothing=smoothing, arith=arith,
            )
            note(found)
            grads.update(d_aux)
            upstream = upstream + extra
        x, prev = states[index]
        d_weights, d_x, d_prev = _stage_backward(
            _under(local, name), x, prev, upstream, what=what, width=width,
            stride=stride, arith=arith,
        )
        grads.update({"%s/%s" % (name, k): v for k, v in d_weights.items()})
        upstream = d_x if owed is None else d_x + owed
        owed = d_prev
    grads.update(
        _stem_backward(_pick(local, "stem_"), images, upstream, arith=arith)
    )
    return (
        logits, {"%s/%s" % (scope, k): v for k, v in grads.items()}, stats
    )
