"""The benchmark's own weights from `--seed`, at either size of total.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/test_weights.py -q

Up to `FLAT_DRAW` values the leaves are slices of one draw, and give the
values they gave before a second path existed (the digests are of the
parent's, c27f8fd); past it each leaf is a draw of its own, the same for
the same seed, with the moments the leaf's kind asks for.
"""

from __future__ import annotations

import hashlib

import numpy as np
import pytest

from benchmarks import weights

SHAPES = {"a/kernel": (3, 3, 1, 8), "a/scale": (8,), "b/kernel": (40, 70),
          "b/bias": (70,), "c/kernel": (1, 1, 8, 16)}
BEFORE = {
    3: "eccf044d92aaa0d809c32acd50c04b73283c83bba546a9b5dc54fd1cd1476d5c",
    2**31 + 11:
        "1c7959ee649b54f78f5c4aeae9f11f746981f40321f536efdf037cd80f9fb2b1",
}


def _digest(made):
    digest = hashlib.sha256()
    for path in sorted(made):
        digest.update(made[path].tobytes())
    return digest.hexdigest()


@pytest.mark.parametrize("seed", sorted(BEFORE))
def test_one_flat_draw_gives_the_values_it_gave_before(seed):
    assert _digest(weights.make(seed, 0, SHAPES)) == BEFORE[seed]


def test_past_the_flat_draw_every_leaf_is_a_draw_of_its_own(monkeypatch):
    flat = weights.make(3, 0, SHAPES)
    monkeypatch.setattr(weights, "FLAT_DRAW", 10)
    made, again = weights.make(3, 0, SHAPES), weights.make(3, 0, SHAPES)
    other = weights.make(4, 0, SHAPES)
    for path, shape in SHAPES.items():
        assert made[path].shape == shape and made[path].dtype == np.float32
        np.testing.assert_array_equal(made[path], again[path])
        assert not np.array_equal(made[path], other[path])
        assert not np.array_equal(made[path], flat[path])
    kernel = made["b/kernel"]  # fan-in 40: variance 1/40
    assert abs(kernel.mean()) < 0.02
    assert kernel.std() == pytest.approx(40 ** -0.5, rel=0.1)
    assert made["a/scale"].mean() == pytest.approx(1.0, abs=0.15)
    assert abs(made["b/bias"].mean()) < 0.05
