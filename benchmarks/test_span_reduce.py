"""`span_reduce` against a hand-made ring: the last `search` span is the
window's call, those before it are set-up's, children hang by
`parent_id`, and a call without a span reads None there.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/test_span_reduce.py -q

`tests/test_benchmark_readers.py` takes this file whole into the
repository's own tests, and a PR of the benchmark may not edit it: so the
harness's other fast cases (the guard, the window's statement, the
refusal, the feed, the weights) ride in at the bottom, and run with
those tests too.
"""

from __future__ import annotations

import itertools
import types

import pytest

from benchmarks import span_reduce


class Ring:
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


def _call(ring, at, restore=True, steps=4, wait=0.002):
    """One `Estimator.train` call starting at `at`: fsck 0.1, first pull
    0.05, build 0.2, init_state 1.0, restore 0.5 (if any), first window
    2.0, then steps of 0.01 each after a pull of `wait`, a save of 3.0
    fetch and 0.25 write, and 0.125 more before the return."""
    search = ring.span("search", at, at)
    t = at
    for name, length, attrs in (
        ("resume.fsck", 0.1, {"verdict": "clean"}),
        ("input.next_batch", 0.05, {}),
        ("iteration.build", 0.2, {"candidates": 1}),
        ("iteration.init_state", 1.0, {}),
    ) + ((("checkpoint.restore", 0.5, {"bytes": 10}),) if restore else ()):
        ring.span(name, t, t + length, search, **attrs)
        t += length
    for step in range(steps):
        # The sample batch comes again before the first window.
        ring.span("input.next_batch", t, t + (wait if step else 1e-5), search)
        t += wait if step else 1e-5
        ring.span("input.place_batch", t, t, search, stacked=False)
        length = 0.01 if step else 2.0
        ring.span("train_window", t, t + length, search, steps=1,
                  first=step == 0)
        t += length
    save = ring.span("checkpoint.save", t, t + 3.25, search)
    ring.span("checkpoint.fetch", t, t + 3.0, save, bytes=100)
    ring.span("checkpoint.write", t + 3.0, t + 3.25, save, bytes=90)
    search.end = t + 3.25 + 0.125
    return search


@pytest.fixture
def ring():
    ring = Ring()
    _call(ring, 0.0, restore=False, steps=1)       # set-up: from nothing
    _call(ring, 100.0, steps=2)                    # set-up: one step
    ring.span("store.get", 150.0, 150.5)           # nobody's child
    _call(ring, 200.0, steps=6, wait=0.004)        # the window
    return ring.events


def test_the_last_search_span_is_the_window(ring):
    out = span_reduce.reduce_events(ring)
    assert len(out["setup"]) == 2
    window = out["window"]
    assert window["resume.fsck"] == pytest.approx(0.1)
    assert window["iteration.build"] == pytest.approx(0.2)
    assert window["iteration.init_state"] == pytest.approx(1.0)
    assert window["checkpoint.restore"] == pytest.approx(0.5)
    assert window["first_step"] == pytest.approx(2.0)
    assert window["first_pull"] == pytest.approx(0.05)
    assert window["fetch"] == pytest.approx(3.0)
    assert window["write"] == pytest.approx(0.25)
    assert window["after_save"] == pytest.approx(0.125)
    assert len(window["pulls"]) == 1 + 6
    # What no child names is what is left of the call.
    assert window["self"] == pytest.approx(0.125)
    assert window["call"] == pytest.approx(
        sum(window["by_name"].values()) + window["self"]
    )


@pytest.mark.parametrize("key, value", [
    ("resume.fsck", 0.1), ("iteration.build", 0.2),
    ("iteration.init_state", 1.0), ("checkpoint.restore", 0.5),
    ("first_step", 2.0), ("fetch", 3.0), ("write", 0.25),
])
def test_metrics_read_the_window_call(ring, key, value):
    record = {"span_reduce": span_reduce.reduce_events(ring)}
    assert span_reduce.window(record, key) == pytest.approx(value)


def test_a_call_with_no_restore_reads_none(ring):
    first = span_reduce.reduce_events(ring)["setup"][0]
    assert first["checkpoint.restore"] is None
    assert span_reduce.resume_sum(first) == pytest.approx(0.1 + 0.2 + 1 + 2)


def test_input_wait_is_the_median_of_the_steady_pulls(ring):
    record = {"span_reduce": span_reduce.reduce_events(ring)}
    # Pulls: sample 0.05, its replay 1e-5, then five of 0.004.
    assert span_reduce.input_wait_ms(record) == pytest.approx(4.0)


def test_setup_sums_run_over_the_calls_before_the_window(ring):
    record = {"span_reduce": span_reduce.reduce_events(ring)}
    calls = span_reduce.setup(record, lambda phases: phases["call"])
    first = 0.1 + 0.05 + 0.2 + 1.0 + 1e-5 + 2.0 + 3.25 + 0.125
    second = first + 0.5 + 0.002 + 0.01
    assert calls == pytest.approx(first + second)
    assert span_reduce.setup(record, span_reduce.resume_sum) == (
        pytest.approx(2 * (0.1 + 0.2 + 1.0 + 2.0) + 0.5)
    )


def test_a_ring_of_the_program_before_the_spans_reads_none():
    """The parent commit records `search` with `train_window` and
    `checkpoint.save` alone: every new phase is None, nothing raises."""
    ring = Ring()
    for at in (0.0, 10.0):
        search = ring.span("search", at, at + 5.0)
        ring.span("train_window", at + 1, at + 2, search, steps=1)
        ring.span("checkpoint.save", at + 3, at + 4, search)
    record = {"span_reduce": span_reduce.reduce_events(ring.events)}
    for key in span_reduce.RESUME + ("first_step", "fetch", "write"):
        assert span_reduce.window(record, key) is None
    assert span_reduce.input_wait_ms(record) is None
    assert span_reduce.setup(record, span_reduce.resume_sum) is None
    assert span_reduce.setup(
        record, lambda phases: phases["call"]
    ) == pytest.approx(5.0)
    assert span_reduce.reduce_events([]) is None
    assert span_reduce.window({"span_reduce": None}, "fetch") is None


@pytest.mark.parametrize("key, value", [
    ("resume.fsck", 0.1), ("checkpoint.restore", 0.5),
    ("train_window.first", 2.0), ("checkpoint.fetch", 3.0),
    ("checkpoint.write", 0.25), ("train.log", None), ("window_s", 7.5),
])
def test_the_window_spans_a_run_prints(ring, key, value):
    record = {"span_reduce": span_reduce.reduce_events(ring),
              "window_start": 1000.0, "window_end": 1007.5}
    spans = span_reduce.window_spans(record)
    assert spans[key] == (None if value is None else pytest.approx(value))


def test_a_run_with_no_call_prints_no_window_spans():
    assert span_reduce.window_spans({"span_reduce": None}) is None


# The harness's fast cases, for `tests/test_benchmark_readers.py` (above).
from benchmarks.test_feed import *  # noqa: E402,F401,F403
from benchmarks.test_run import (  # noqa: E402,F401
    copied,
    test_a_traced_run_on_the_chip_with_no_device_plane_prints_no_result,
    test_a_traffic_file_with_no_window_in_steps_is_an_error_that_names_it,
    test_a_window_that_ends_itself_is_not_the_guards,
    test_a_window_that_the_guard_ends_is_not_correct,
    test_the_guard_waits_four_times_the_seconds,
    test_the_traced_pulls_may_end_with_the_window,
)
from benchmarks.test_weights import *  # noqa: E402,F401,F403
