"""`compile_reduce` against a hand-made ring: set-up's calls are every
`search` span but the last, a `compile` span's stages are its children,
JAX's work (`jax.s`, the union of its kinds) counts only outside every
`compile` span, and a ring with no `compile` span (a program without
them) reads None.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/test_compile_reduce.py -q

`tests/test_benchmark_readers.py` takes this file whole into the
repository's own tests: its names are its own.
"""

from __future__ import annotations

import itertools
import types

import pytest

from benchmarks import compile_reduce
from benchmarks.run import load_module


class _Ring:
    """Builds closed spans the way the tracer records them."""

    def __init__(self):
        self.events, self._ids = [], itertools.count(1)

    def span(self, name, start, end, parent=None, **attrs):
        event = types.SimpleNamespace(
            name=name, span_id=next(self._ids), start=start, end=end,
            parent_id=parent.span_id if parent else None, attrs=attrs,
        )
        self.events.append(event)
        return event

    def compile(self, at, parent, stages, outcome):
        """A `compile` span at `at` with its stages one after another."""
        span = self.span("compile", at, at, parent, program="step",
                         outcome=outcome)
        for name, length in stages:
            self.span(name, at, at + length, span,
                      **{"jax.trace_s": length, "jax.s": length}
                      if name == "compile.trace" else {})
            at += length
        span.end = at
        return span


def _compile_ring():
    ring = _Ring()
    # Set-up call 1: the real init's one-op programs, and a builder's
    # `jit_init` through the compile cache inside it. Its traces lie
    # inside its lowerings: the union of the kinds is 13.5 s, not 15.
    first = ring.span("search", 0.0, 30.0)
    init = ring.span("iteration.init_state", 1.0, 25.0, first,
                     **{"jax.trace_s": 2.0, "jax.lower_s": 3.0,
                        "jax.compile_s": 10.0, "jax.s": 13.5,
                        "jax.compiles": 280})
    ring.compile(2.0, init, [("compile.trace", 1.0),
                             ("compile.lower", 0.5),
                             ("compile.key", 0.25),
                             ("compile.backend", 4.0)], "backend")
    # Set-up call 2: the step's first window, and a template's trace.
    second = ring.span("search", 100.0, 180.0,
                       **{"jax.trace_s": 0.5, "jax.s": 0.5})
    ring.span("iteration.init_state", 100.0, 101.0, second,
              **{"jax.trace_s": 0.75, "jax.s": 0.75})
    window = ring.span("train_window", 101.0, 171.0, second, steps=1,
                       first=True)
    ring.compile(101.0, window, [("compile.trace", 13.0),
                                 ("compile.lower", 3.0),
                                 ("compile.key", 0.5),
                                 ("compile.store_load", 0.125),
                                 ("compile.backend", 16.0),
                                 ("compile.store_save", 1.0)], "backend")
    ring.span("train_window", 171.0, 171.5, second, steps=1, first=False)
    # The window's call: a memory hit and the log line's programs.
    last = ring.span("search", 200.0, 240.0)
    window = ring.span("train_window", 201.0, 214.0, last, steps=1,
                       first=True)
    ring.compile(201.0, window, [("compile.trace", 12.0),
                                 ("compile.lower", 0.5),
                                 ("compile.key", 0.5)], "memory")
    ring.span("train.log", 220.0, 230.0, last,
              **{"jax.compile_s": 0.25, "jax.s": 0.25, "jax.compiles": 6})
    return ring.events


#: Each metric's reading of `_compile_ring`: set-up's two calls only.
_EXPECTED = {
    "setup.step_trace_s": 1.0 + 13.0,
    "setup.step_lower_s": 0.5 + 3.0,
    "setup.step_key_s": 0.25 + 0.5,
    "setup.step_load_s": 4.0 + 0.125 + 16.0,
    "setup.jax_other_s": 13.5 + 0.5 + 0.75,
}


@pytest.mark.parametrize("name", sorted(_EXPECTED))
def test_compile_reduce_metric_reads_setup_calls_only(name):
    record = {"compile_reduce": compile_reduce.reduce_events(
        _compile_ring()
    )}
    reader = load_module("metrics", name)
    assert reader.UNIT == "s"
    assert reader.read(record) == pytest.approx(_EXPECTED[name])


def test_compile_reduce_splits_each_setup_call():
    first, second = compile_reduce.reduce_events(_compile_ring())["setup"]
    assert first["compiles"] == 1 and first["first_window"] is None
    assert first["jax_other_by_name"] == {"iteration.init_state": 13.5}
    assert second["outcomes"] == ["step:backend"]
    assert second["jax_other_by_name"] == {
        "search": 0.5, "iteration.init_state": 0.75,
    }
    # The first window against the compile spans beneath it: 70 s, of
    # which the compile is 33.625 s.
    assert second["first_window"] == pytest.approx(70.0)
    assert second["first_window_compile"] == pytest.approx(33.625)
    assert second["compile_s"] == pytest.approx(33.625)
    assert second["least_covered"] == pytest.approx(1.0)


def test_compile_reduce_prints_the_first_window_identity(capsys):
    compile_reduce.report(compile_reduce.reduce_events(_compile_ring()))
    err = capsys.readouterr().err
    assert (
        "compile_reduce identity: set-up call 2 first train_window "
        "70.0000 s, compile spans 33.6250 s, remainder 51.964%"
    ) in err
    assert "set-up call 1" not in err


@pytest.mark.parametrize("name", sorted(_EXPECTED))
def test_compile_reduce_reads_none_without_compile_spans(name):
    """A program that records no `compile` span and no `jax.*` attribute
    (a parent of this reader) gives no number, and raises nothing."""
    ring = _Ring()
    for at in (0.0, 100.0):
        search = ring.span("search", at, at + 50.0)
        ring.span("train_window", at + 1.0, at + 40.0, search, first=True)
    record = {"compile_reduce": compile_reduce.reduce_events(ring.events)}
    assert load_module("metrics", name).read(record) is None
    assert load_module("metrics", name).read(
        {"compile_reduce": compile_reduce.reduce_events([])}
    ) is None
