"""The one generator of training traffic: a ring of distinct host batches
drawn from `--seed`, of the sizes a traffic file states. What a batch
holds is a file of its own, `feeds/<name>.py`, named by the configuration's
`"feed"` (absent: `images`): `ring(rng, traffic, sizes)` returns the
ring's `[(features, labels)]`; position, pull hook and spans are here.

The program receives only what this yields. Every seed gives the same
sizes in the same order; only the values differ. The harness hangs its
clocks, the profiler's start and stop and the stop signal on `on_pull`,
which runs on the train loop's own thread at every batch it asks for.
"""

from __future__ import annotations

import contextlib

import numpy as np


class Feed:
    def __init__(self, seed, traffic, sizes, annotate=False, ring=None):
        if ring is None:
            from benchmarks.feeds.images import ring
        self.ring = ring(
            np.random.default_rng([seed, 0xFEED]), traffic, sizes
        )
        self.position = 0  # index of the next batch, counted from step 0
        self.on_pull = None
        self._annotate = annotate
        self._outside = None

    def batch_at(self, step):
        return self.ring[step % len(self.ring)]

    def input_fn(self):
        return self

    def __iter__(self):
        return self

    def _span(self, name):
        if not self._annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def close_span(self):
        if self._outside is not None:
            self._outside.__exit__(None, None, None)
            self._outside = None

    def __next__(self):
        self.close_span()
        if self.on_pull is not None:
            self.on_pull()
        with self._span("pull_batch"):
            batch = self.batch_at(self.position)
            self.position += 1
        # Whatever the program does until it asks again is `train_call`.
        self._outside = self._span("train_call")
        self._outside.__enter__()
        return batch
