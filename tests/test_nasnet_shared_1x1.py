"""A cell output read by two 1x1 projections is projected for both at
once (`adanet_tpu/models/nasnet.py`: `cell_specs`, `projection_sites`,
`_project_1x1`): its gradient comes out of one convolution, and nothing
else about the network changes.

`testdata/nasnet_trees.json` was recorded at commit 3d5bb43, the last
whose cells convolved for themselves, by this file:

    JAX_PLATFORMS=cpu PYTHONPATH=<that checkout> \
        python tests/test_nasnet_shared_1x1.py tests/testdata/nasnet_trees.json

It holds, for each of CONFIGS, every variable's path, shape and dtype;
for the toys a digest of every initial value under PRNGKey(7); and for
`toy3` in float32 that commit's logits and gradient norms.
"""

from __future__ import annotations

import collections
import hashlib
import json
import os
import statistics
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from flax import traverse_util

from adanet_tpu.models import nasnet
from adanet_tpu.observability import metrics as metrics_lib

RECORDED = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "testdata", "nasnet_trees.json"
)
# name -> (configuration, image side)
CONFIGS = {
    "cifar18": (lambda **kw: nasnet.cifar_config(**kw), 32),
    "mobile_imagenet": (lambda **kw: nasnet.mobile_imagenet_config(**kw), 224),
    "toy2": (
        lambda **kw: nasnet.cifar_config(
            num_cells=2, num_conv_filters=4, **kw
        ),
        16,
    ),
    # cell_0, reduction_cell_0, cell_1, reduction_cell_1, cell_2: two
    # shared sites (one of 4 + 8 filters), three single, two factorized.
    "toy3": (
        lambda **kw: nasnet.cifar_config(
            num_cells=3, num_conv_filters=4, **kw
        ),
        16,
    ),
    "toy_imagenet": (
        lambda **kw: nasnet.mobile_imagenet_config(
            num_cells=3, num_conv_filters=8, num_classes=5, **kw
        ),
        64,
    ),
}
TOYS = ("toy2", "toy3")
# The 17 tensors of the benchmark's NASNet-A (6@768) with two readers
# (ISSUE 30), and the three with one.
SHARED_18 = (
    ["stem"]
    + ["cell_%d" % k for k in (0, 1, 2, 3, 4)]
    + ["reduction_cell_0"]
    + ["cell_%d" % k for k in (6, 7, 8, 9, 10)]
    + ["reduction_cell_1"]
    + ["cell_%d" % k for k in (12, 13, 14, 15)]
)
SINGLE_18 = ["cell_5", "cell_11", "cell_16"]


def _flat(tree):
    return traverse_util.flatten_dict(dict(tree), sep="/")


def _listing(variables):
    return {
        path: [list(leaf.shape), str(leaf.dtype)]
        for path, leaf in sorted(_flat(variables).items())
    }


def _digests(variables):
    return {
        path: hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest()[:16]
        for path, leaf in sorted(_flat(variables).items())
    }


def _model(name, **overrides):
    make, side = CONFIGS[name]
    return nasnet.NasNetA(make(**overrides)), side


def _init(name, abstract=False, **overrides):
    model, side = _model(name, **overrides)
    images = jnp.zeros((2, side, side, 3), jnp.float32)

    def init():
        return model.init(jax.random.PRNGKey(7), images, training=True)

    return jax.eval_shape(init) if abstract else jax.jit(init)()


def _images(side, batch=4):
    return jnp.asarray(
        np.random.RandomState(0).randn(batch, side, side, 3), jnp.float32
    )


def _run(model, variables, images, training):
    """(logits, aux logits, updated collections, gradients of a loss that
    reads both heads) of one jitted forward and backward."""

    def loss(params):
        (logits, aux, _), updates = model.apply(
            dict(variables, params=params),
            images,
            training=training,
            mutable=["batch_stats", "schedule"],
            rngs={"dropout": jax.random.PRNGKey(3)},
        )
        value = jnp.sum(logits**2)
        if aux is not None:
            value = value + jnp.sum(aux**2)
        return value, (logits, aux, updates)

    (_, (logits, aux, updates)), grads = jax.jit(
        jax.value_and_grad(loss, has_aux=True)
    )(variables["params"])
    return logits, aux, updates, grads


def _separately(x, kernels, dtype):
    """What the cells did for themselves: relu, then `nn.Conv(f, (1, 1),
    use_bias=False, dtype=dtype)`'s convolution, one kernel at a time."""
    x = jnp.asarray(nn.relu(x), dtype)
    return [
        jax.lax.conv_general_dilated(
            x,
            jnp.asarray(kernel, dtype),
            window_strides=(1, 1),
            padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        for kernel in kernels
    ]


def _relative(a, b):
    """{path: |a - b| / |b|} over two trees of arrays."""
    a, b = _flat(a), _flat(b)
    assert a.keys() == b.keys()
    return {
        path: float(
            jnp.linalg.norm(jnp.asarray(a[path] - b[path], jnp.float32))
            / (1e-12 + jnp.linalg.norm(jnp.asarray(b[path], jnp.float32)))
        )
        for path in a
    }


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def toy3_variables():
    """Parameters are float32 whatever the compute dtype."""
    return _init("toy3")


@pytest.mark.parametrize("name", list(CONFIGS))
def test_variable_trees_are_the_recorded_ones(recorded, name):
    assert _listing(_init(name, abstract=True)) == recorded[name]["tree"]


@pytest.mark.parametrize("name", TOYS)
def test_initial_values_are_the_recorded_ones_bit_for_bit(recorded, name):
    assert _digests(_init(name)) == recorded[name]["values"]


def test_float32_toy_gives_the_recorded_logits_and_gradients(
    recorded, toy3_variables
):
    model, side = _model("toy3", compute_dtype=jnp.float32)
    logits, aux, _, grads = _run(model, toy3_variables, _images(side), True)
    want = recorded["toy3"]["float32"]
    np.testing.assert_allclose(logits, want["logits"], rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(aux, want["aux_logits"], rtol=2e-4, atol=1e-5)
    norms = {
        path: float(jnp.linalg.norm(leaf))
        for path, leaf in _flat(grads).items()
    }
    assert norms.keys() == want["gradient_norms"].keys()
    for path, norm in want["gradient_norms"].items():
        assert norms[path] == pytest.approx(norm, rel=2e-3, abs=1e-6), path


@pytest.mark.parametrize(
    "remat,training", [(False, True), (True, True), (False, False)]
)
def test_float32_equals_separate_convolutions(
    monkeypatch, toy3_variables, remat, training
):
    model, side = _model("toy3", compute_dtype=jnp.float32, remat=remat)
    images = _images(side)
    got = _run(model, toy3_variables, images, training)
    monkeypatch.setattr(nasnet, "_project_1x1", _separately)
    want = _run(model, toy3_variables, images, training)
    assert (got[1] is None) == (want[1] is None) == (not training)
    for ours, theirs in zip(got, want):
        if theirs is None:
            continue
        for path, error in _relative({"x": ours}, {"x": theirs}).items():
            assert error < 1e-4, (path, error)


def test_bfloat16_equals_separate_convolutions_as_far_as_bfloat16_goes(
    monkeypatch, toy3_variables
):
    """A toy of 4 filters and a batch of 4 in bfloat16 is far from its own
    float32 self (a third of each gradient's norm at six cells), so the
    tolerance is that distance: the shared convolution is no further from
    float32 than separate ones are, and as near to them as they are to
    float32."""
    model, side = _model("toy3")
    images = _images(side)
    exact_model, _ = _model("toy3", compute_dtype=jnp.float32)
    exact = _run(exact_model, toy3_variables, images, True)
    got = _run(model, toy3_variables, images, True)
    monkeypatch.setattr(nasnet, "_project_1x1", _separately)
    want = _run(model, toy3_variables, images, True)
    for index in (0, 1):  # logits, aux logits
        scale = float(jnp.max(jnp.abs(exact[index])))
        assert float(jnp.max(jnp.abs(got[index] - want[index]))) < 0.06 * scale
    for index in (2, 3):  # batch statistics, gradients
        noise = statistics.median(_relative(want[index], exact[index]).values())
        ours = statistics.median(_relative(got[index], exact[index]).values())
        apart = statistics.median(_relative(got[index], want[index]).values())
        assert ours < 1.5 * noise + 0.01, (ours, noise)
        assert apart < 1.5 * noise + 0.01, (apart, noise)


def test_sites_of_the_benchmarks_configuration():
    sites = nasnet.projection_sites(nasnet.cifar_config())
    shared = [t for t, readers in sites.items() if len(readers) == 2]
    single = [t for t, readers in sites.items() if len(readers) == 1]
    assert shared == SHARED_18 and single == SINGLE_18
    assert sites["cell_4"] == (
        ("cell_5", "beginning_1x1"),
        ("reduction_cell_0", "prev_1x1"),
    )
    assert sites["cell_5"] == (("reduction_cell_0", "beginning_1x1"),)
    assert "cell_17" not in sites  # read by the classifier alone


@pytest.mark.parametrize(
    "config", [nasnet.mobile_imagenet_config(), nasnet.large_imagenet_config()]
)
def test_imagenet_stems_first_sites_stay_single(config):
    """The stem reduction cells halve the width: what follows reads the
    tensor before through a factorized reduction, not a 1x1."""
    sites = nasnet.projection_sites(config, 224)
    assert sites["stem"] == (("cell_stem_0", "beginning_1x1"),)
    assert sites["cell_stem_0"] == (("cell_stem_1", "beginning_1x1"),)
    assert sites["cell_stem_1"] == (
        ("cell_0", "beginning_1x1"),
        ("cell_1", "prev_1x1"),
    )
    specs = {spec.name: spec for spec in nasnet.cell_specs(config, 224)}
    assert specs["cell_stem_0"].prev is None
    assert specs["cell_0"].prev_channels is None  # `reduce_prev`


def _convolutions_1x1(jaxpr):
    """[(input channels, output channels)] of the 1x1 convolutions over
    images in a jaxpr: a forward convolution, or one that gives an
    input's gradient."""
    found = collections.Counter()
    # The backward rule transposes ONE convolution, which it therefore
    # traces; nothing reads what that one gives, and XLA drops it as
    # this does.
    needed = {id(var) for var in jaxpr.outvars}
    live = []
    for eqn in reversed(jaxpr.eqns):
        if any(id(var) in needed for var in eqn.outvars):
            live.append(eqn)
            needed.update(id(var) for var in eqn.invars)
    for eqn in live:
        for inner in eqn.params.values():  # a `custom_vjp_call`'s body
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                found += _convolutions_1x1(inner)
        if eqn.primitive.name != "conv_general_dilated":
            continue
        lhs, rhs = eqn.invars
        (out,) = eqn.outvars
        if (
            rhs.aval.shape[:2] == (1, 1)
            and eqn.params["feature_group_count"] == 1
            and tuple(eqn.params["window_strides"]) == (1, 1)
            and out.aval.shape[:3] == lhs.aval.shape[:3]
        ):
            found[(lhs.aval.shape[-1], out.aval.shape[-1])] += 1
    return found


def test_one_backward_convolution_a_shared_site_where_separate_ones_make_two(
    monkeypatch,
):
    """The benchmark's network: forward, a convolution a reader either
    way; backward, each shared site's 6F-channel gradient comes out of
    one convolution of its readers' F + F' cotangent channels, where
    separate convolutions write one gradient each (and add them)."""
    model, side = _model("cifar18")
    variables = _init("cifar18", abstract=True)
    images = jax.ShapeDtypeStruct((1, side, side, 3), jnp.float32)

    def loss():
        # A function of its own each time: a trace is remembered by it.
        def of(params, rest, x):
            logits, _, _ = model.apply(dict(rest, params=params), x)
            return jnp.sum(logits)

        return of

    params = variables["params"]
    rest = {k: v for k, v in variables.items() if k != "params"}
    registry = metrics_lib.registry()
    before = {
        name: registry.counter("nasnet.%s_1x1.sites" % name).value
        for name in ("shared", "single")
    }
    forward = _convolutions_1x1(jax.make_jaxpr(loss())(params, rest, images).jaxpr)
    assert {
        name: registry.counter("nasnet.%s_1x1.sites" % name).value - count
        for name, count in before.items()
    } == {"shared": len(SHARED_18), "single": len(SINGLE_18)}
    both = _convolutions_1x1(
        jax.make_jaxpr(jax.grad(loss()))(params, rest, images).jaxpr
    )
    monkeypatch.setattr(nasnet, "_project_1x1", _separately)
    assert forward == _convolutions_1x1(
        jax.make_jaxpr(loss())(params, rest, images).jaxpr
    )
    separate = _convolutions_1x1(
        jax.make_jaxpr(jax.grad(loss()))(params, rest, images).jaxpr
    )

    specs = {spec.name: spec for spec in nasnet.cell_specs(model.config)}
    sites = nasnet.projection_sites(model.config)
    wanted = collections.Counter()
    for tensor in SHARED_18:
        channels = specs[sites[tensor][0][0]].net_channels
        filters = [specs[cell].filters for cell, _ in sites[tensor]]
        wanted[(sum(filters), channels)] += 1
        wanted.subtract((width, channels) for width in filters)
    both.subtract(separate)
    assert {k: n for k, n in both.items() if n} == {
        k: n for k, n in wanted.items() if n
    }


@pytest.mark.parametrize("scope", ["shared_1x1", "single_1x1"])
def test_the_benchmark_counts_the_projection_as_a_candidates_1x1(scope):
    """`benchmarks/scope_reduce.py` reads the scope the convolution runs
    under, and `step.conv1x1_ms` its kind."""
    from benchmarks import run, scope_reduce

    tf_op = (
        "jit(adanet_train_step)/jit(main)/transpose(jvp(candidate.NasNet_A))"
        "/_NasNetSubnetworkModule/nasnet/%s/conv_general_dilated:" % scope
    )
    direction, path = scope_reduce.split(tf_op)
    assert scope_reduce.group(direction, path) == "candidate_bwd"
    assert scope_reduce.kind(path) == "1x1"
    reader = run.load_module("metrics", "step.conv1x1_ms")
    scopes = {"kinds_ms": [("sep.conv", 198.4), ("1x1", 74.9)]}
    assert reader.read({"scope_reduce": {"scopes": scopes}}) == 74.9
    assert reader.read({"scope_reduce": {"scopes": {"kinds_ms": []}}}) is None
    assert reader.read({"scope_reduce": None}) is None


def _record(path):
    out = {}
    for name, (_, side) in CONFIGS.items():
        entry = {
            "image_side": side,
            "tree": _listing(_init(name, abstract=True)),
        }
        if name in TOYS:
            entry["values"] = _digests(_init(name))
        out[name] = entry
    model, side = _model("toy3", compute_dtype=jnp.float32)
    logits, aux, _, grads = _run(model, _init("toy3"), _images(side), True)
    out["toy3"]["float32"] = {
        "logits": np.asarray(logits).tolist(),
        "aux_logits": np.asarray(aux).tolist(),
        "gradient_norms": {
            path: float(jnp.linalg.norm(leaf))
            for path, leaf in _flat(grads).items()
        },
    }
    with open(path, "w") as f:
        json.dump(out, f, separators=(",", ":"), sort_keys=True)


if __name__ == "__main__":
    _record(sys.argv[1])
