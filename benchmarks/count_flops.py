"""Counts a configuration's forward FLOP per example, once.

    JAX_PLATFORMS=cpu python -m benchmarks.count_flops <config>

Lowers (does not compile) each member's plain reference forward for one
example, in float32 with no kernels, and prints `cost_analysis()["flops"]`.
The number goes into the configuration's file by hand, with the commit it
was taken at; runs read it from there and never count again, so that no
change to the program moves the yardstick. Model FLOP per step is batch x
3 x forward for every candidate in training (backward counted as twice the
forward, recomputation not counted) plus 1 x forward for a frozen member.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp


def forward_flops(member, image, shapes):
    import importlib

    module = importlib.import_module(
        "benchmarks.reference." + member["reference"]
    )
    weights = {
        path: jax.ShapeDtypeStruct(shape, jnp.float32)
        for path, shape in shapes.items()
    }
    images = jax.ShapeDtypeStruct((1,) + tuple(image), jnp.float32)
    lowered = jax.jit(
        lambda w, x: module.forward(w, x, member["sizes"])
    ).lower(weights, images)
    return float(lowered.cost_analysis()["flops"])


def main(argv):
    from benchmarks.run import load_json, load_module

    config = load_json("configs", argv[1])
    factory = load_module("factories", config["factory"])
    for name, member in config["members"].items():
        member["sizes"] = {**config["sizes"], **member["sizes"]}
        # Shapes alone are taken from the program's modules (abstractly);
        # the operations counted are the plain reference's.
        shapes = factory.weight_shapes(config, name)
        print(name, forward_flops(member, config["sizes"]["image"], shapes))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
