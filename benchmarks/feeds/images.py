"""Feed `images`: float32 normal images of `sizes["image"]` with one
uniform label an example, `traffic["batch"]` examples a batch."""

import numpy as np


def ring(rng, traffic, sizes):
    height, width, channels = sizes["image"]
    batch = traffic["batch"]
    batches = []
    for _ in range(traffic["ring"]):
        images = rng.standard_normal(
            (batch, height, width, channels), dtype=np.float32
        )
        labels = rng.integers(
            0, sizes["num_classes"], (batch,), dtype=np.int32
        )
        batches.append(({"image": images}, labels))
    return batches
