"""Feed `token_ids` (the name `tokens` is taken: `benchmarks/test_feed.py` uses
it for a feed that has no file): `traffic["batch"]` sequences of `traffic["seq"]` token
ids a batch, uniform over the `sizes["vocab_size"]` ids the candidate
holds; every sequence one document; the labels are the ids shifted by
one (a sequence is drawn one id longer than it is fed)."""

import numpy as np


def ring(rng, traffic, sizes):
    batches = []
    for _ in range(traffic["ring"]):
        ids = rng.integers(
            0, sizes["vocab_size"], (traffic["batch"], traffic["seq"] + 1),
            dtype=np.int32,
        )
        batches.append(
            ({"tokens": np.ascontiguousarray(ids[:, :-1])},
             np.ascontiguousarray(ids[:, 1:]))
        )
    return batches
