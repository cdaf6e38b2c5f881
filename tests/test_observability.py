"""Telemetry plane: spans, metrics, flight recorder, trace export.

Unit layers run against a MOCKED clock (no sleeps): span nesting and
correlation inheritance, histogram bucket boundaries, ring-buffer
wraparound, registry snapshots, scoped child counters. The chaos layer
proves the flight recorder's crash contract in subprocesses: a SIGKILL
mid-dump-write (armed `flightrec.dump:kill`) leaves the prior dump
intact with no readable partial, and a searcher SIGKILLed
mid-checkpoint-write by the armed `checkpoint.write:torn` fault leaves
a dump narrating everything up to the trip. The overhead gate asserts
the disabled-tracing contract on the instrumented step path: ZERO
clock reads (counted, not wall-timed). The acceptance gate renders a
Perfetto-loadable Chrome trace from a REAL 2-iteration search via
`tools/trace_view.py`.
"""

import json
import glob
import os
import signal
import subprocess
import sys

import pytest

from adanet_tpu.observability import (
    FlightRecorder,
    install,
    installed,
    install_default,
    uninstall,
)
from adanet_tpu.observability.export import chrome_trace
from adanet_tpu.observability.metrics import (
    Histogram,
    MetricsRegistry,
)
from adanet_tpu.observability import metrics as metrics_lib
from adanet_tpu.observability import spans as spans_lib
from adanet_tpu.observability.spans import Tracer
from adanet_tpu.robustness import faults

from chaos_common import build_estimator, input_fn

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Isolate the process-wide recorder and fault registry per test."""
    uninstall()
    faults.disarm()
    yield
    uninstall()
    faults.disarm()


class FakeClock:
    def __init__(self, start=100.0):
        self.now = start
        self.reads = 0

    def __call__(self):
        self.reads += 1
        return self.now

    def advance(self, secs):
        self.now += secs


# ------------------------------------------------------------------ spans


def test_span_nesting_and_correlation_inheritance():
    clock = FakeClock()
    tracer = Tracer(capacity=16, clock=clock)
    with tracer.span("search", correlation={"search_id": "s1"}) as root:
        clock.advance(1.0)
        with tracer.span(
            "iteration", correlation={"iteration": 3}, steps=4
        ) as child:
            clock.advance(0.5)
            tracer.instant("fault.trip", site="store.get")
        clock.advance(0.25)
    events = {e.name: e for e in tracer.events()}
    assert set(events) == {"search", "iteration", "fault.trip"}
    search, iteration = events["search"], events["iteration"]
    instant = events["fault.trip"]
    # Nesting: parent ids chain child -> parent -> None.
    assert search.parent_id is None
    assert iteration.parent_id == search.span_id
    assert instant.parent_id == iteration.span_id
    # Correlation flows DOWN and merges.
    assert search.correlation == {"search_id": "s1"}
    assert iteration.correlation == {"search_id": "s1", "iteration": 3}
    assert instant.correlation == {"search_id": "s1", "iteration": 3}
    # Mocked-clock durations, exact.
    assert search.duration == pytest.approx(1.75)
    assert iteration.duration == pytest.approx(0.5)
    assert instant.is_instant
    # Span-local attrs are not inherited.
    assert iteration.attrs == {"steps": 4}
    assert "steps" not in instant.attrs
    del root, child


def test_span_records_error_attr_on_exception():
    tracer = Tracer(capacity=4, clock=FakeClock())
    with pytest.raises(ValueError):
        with tracer.span("boom"):
            raise ValueError("nope")
    [event] = tracer.events()
    assert event.attrs["error"] == "ValueError"


def test_ring_buffer_wraparound_keeps_newest():
    clock = FakeClock()
    tracer = Tracer(capacity=4, clock=clock)
    for i in range(10):
        with tracer.span("s%d" % i):
            clock.advance(0.1)
    names = [e.name for e in tracer.events()]
    assert names == ["s6", "s7", "s8", "s9"]  # oldest evicted, order kept


class _FakeAnnotation:
    """Stands in for `jax.profiler.TraceAnnotation`: records what the
    tracer builds and enters."""

    built = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs, self.state = name, kwargs, "built"
        _FakeAnnotation.built.append(self)

    def __enter__(self):
        self.state = "entered"
        return self

    def __exit__(self, *exc):
        self.state = "exited"


@pytest.fixture
def annotations(monkeypatch):
    import jax

    monkeypatch.setattr(_FakeAnnotation, "built", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    return _FakeAnnotation.built


@pytest.mark.parametrize("enabled", [True, False])
def test_span_mirrors_itself_as_a_trace_annotation(annotations, enabled):
    """An enabled span enters a `TraceAnnotation` of its own name, with
    its correlation and attributes as keyword arguments, and leaves it
    when it closes; a disabled one builds none. Instants stay in the
    ring alone."""
    tracer = Tracer(capacity=8, clock=FakeClock(), enabled=enabled)
    with tracer.span("search", correlation={"search_id": "s1"}):
        with tracer.span(
            "train_window", correlation={"iteration": 2}, steps=1
        ):
            assert [a.state for a in annotations] == (
                ["entered", "entered"] if enabled else []
            )
            tracer.instant("fault.trip", site="x")
    if not enabled:
        assert annotations == []
        return
    assert [(a.name, a.kwargs, a.state) for a in annotations] == [
        ("search", {"search_id": "s1"}, "exited"),
        (
            "train_window",
            {"search_id": "s1", "iteration": 2, "steps": 1},
            "exited",
        ),
    ]


def test_disabled_tracer_reads_no_clock_and_records_nothing():
    clock = FakeClock()
    tracer = Tracer(capacity=4, clock=clock, enabled=False)
    with tracer.span("hot", correlation={"iteration": 0}) as span:
        span.set(extra=1)
        tracer.instant("inside")
    assert clock.reads == 0
    assert tracer.clock_reads == 0
    assert tracer.events() == []


# ------------------------------------------------ JAX's compile work


_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def test_jax_events_add_the_union_of_their_intervals_to_the_span():
    """Nested intervals count once; disjoint ones add; each kind keeps
    its own union and `jax.s` the union of all three, so a trace inside
    a lowering counts once there; a backend compile also counts and
    names itself. Events arrive in the order they end, as JAX reports
    them."""
    tracer = Tracer(capacity=4, clock=FakeClock())
    with tracer.span("init_state"):
        tracer.jax_event(_TRACE, 10.0, 11.0, fun_name="inner")
        tracer.jax_event(_TRACE, 12.0, 13.0, fun_name="inner2")
        tracer.jax_event(_TRACE, 9.5, 14.0, fun_name="outer")
        tracer.jax_event(_COMPILE, 14.0, 15.0, fun_name="jit(a)")
        tracer.jax_event(_TRACE, 16.25, 16.75, fun_name="in_lowering")
        tracer.jax_event(_LOWER, 16.0, 17.0, fun_name="lowered")
        tracer.jax_event(_COMPILE, 17.5, 19.5, fun_name="jit(b)")
        tracer.jax_event(_TRACE, 20.0, 20.5, fun_name="later")
        tracer.jax_event("/jax/unrelated", 0.0, 99.0)
    [event] = tracer.events()
    assert event.attrs == {
        "jax.trace_s": pytest.approx(5.5),
        "jax.lower_s": pytest.approx(1.0),
        "jax.compile_s": pytest.approx(3.0),
        "jax.s": pytest.approx(5.5 + 1.0 + 2.0 + 0.5),
        "jax.compiles": 2,
        "jax.programs": ["jit(a)", "jit(b)"],
    }
    kinds = sum(event.attrs[k] for k in
                ("jax.trace_s", "jax.lower_s", "jax.compile_s"))
    assert event.attrs["jax.s"] < kinds


def test_jax_programs_keeps_the_first_names_and_counts_every_compile():
    """`jax.programs` names the first `JAX_PROGRAMS_KEPT` outermost
    compiles; `jax.compiles` counts all of them, and a compile that
    swallows the ones nested in it counts once."""
    tracer = Tracer(capacity=4, clock=FakeClock())
    kept = spans_lib.JAX_PROGRAMS_KEPT
    with tracer.span("init_state"):
        for n in range(kept + 3):
            tracer.jax_event(_COMPILE, 2.0 * n, 2.0 * n + 1.0,
                             fun_name="jit(op%d)" % n)
        at = 2.0 * (kept + 3)
        tracer.jax_event(_COMPILE, at + 0.25, at + 0.5, fun_name="inner")
        tracer.jax_event(_COMPILE, at, at + 1.0, fun_name="outer")
    [event] = tracer.events()
    assert event.attrs["jax.compiles"] == kept + 4
    assert event.attrs["jax.programs"] == [
        "jit(op%d)" % n for n in range(kept)
    ]
    assert event.attrs["jax.compile_s"] == pytest.approx(kept + 4.0)
    assert event.attrs["jax.s"] == event.attrs["jax.compile_s"]


def test_jit_inside_jit_counts_its_trace_once_under_one_span():
    """Under a real nested jit, the span's `jax.trace_s` is no larger
    than the span and smaller than the sum of JAX's nested events."""
    import jax
    import jax.numpy as jnp
    from jax import monitoring

    seen = []

    def listen(event, start, end, **_):
        if event == _TRACE:
            seen.append(end - start)

    def leaf(x):
        return jnp.tanh(x) * 3.0

    def middle(x):
        return jax.jit(leaf)(x) + 1.0

    def top(x):
        return jax.jit(middle)(x) * 2.0

    tracer = spans_lib.tracer()
    was_enabled = tracer.enabled
    tracer.enable()
    tracer.clear()
    monitoring.register_event_time_span_listener(listen)
    try:
        with tracer.span("nested"):
            jax.jit(top).trace(jnp.ones((3,), jnp.float32))
        [event] = [e for e in tracer.events() if e.name == "nested"]
    finally:
        monitoring.unregister_event_time_span_listener(listen)
        tracer.clear()
        if not was_enabled:
            tracer.disable()
    assert len(seen) >= 3
    assert 0 < event.attrs["jax.trace_s"] <= event.duration
    assert event.attrs["jax.trace_s"] < sum(seen)
    assert event.attrs["jax.trace_s"] <= event.attrs["jax.s"]
    assert event.attrs["jax.s"] <= event.duration


def test_each_thread_adds_to_its_own_innermost_span():
    import threading

    tracer = Tracer(capacity=8, clock=FakeClock())
    opened, reported = threading.Barrier(2), threading.Barrier(2)

    def work(name, length):
        with tracer.span(name):
            with tracer.span(name + ".inner"):
                opened.wait(timeout=10)
                tracer.jax_event(_TRACE, 1.0, 1.0 + length)
                reported.wait(timeout=10)

    threads = [
        threading.Thread(target=work, args=("a", 2.0)),
        threading.Thread(target=work, args=("b", 0.5)),
    ]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join(timeout=10)
        assert not thread.is_alive()
    attrs = {e.name: e.attrs for e in tracer.events()}
    assert attrs == {
        "a.inner": {"jax.trace_s": 2.0, "jax.s": 2.0},
        "b.inner": {"jax.trace_s": 0.5, "jax.s": 0.5},
        "a": {},
        "b": {},
    }


def test_jax_event_with_no_open_span_is_dropped():
    tracer = Tracer(capacity=4, clock=FakeClock())
    tracer.jax_event(_COMPILE, 1.0, 2.0, fun_name="jit(f)")
    assert tracer.events() == []
    with tracer.span("later"):
        pass
    [event] = tracer.events()
    assert event.attrs == {}


# ---------------------------------------------------------------- metrics


def test_histogram_bucket_boundaries_are_upper_inclusive():
    h = Histogram(boundaries=[0.1, 1.0, 10.0])
    for value in (0.05, 0.1, 0.2, 1.0, 5.0, 100.0):
        h.observe(value)
    # buckets: <=0.1, <=1.0, <=10.0, overflow
    assert h.bucket_counts() == [2, 2, 1, 1]
    assert h.count == 6
    assert h.sum == pytest.approx(106.35)


def test_scoped_child_counters_propagate_to_aggregate():
    reg = MetricsRegistry()
    parent = reg.counter("cc.hits")
    a, b = parent.child(), parent.child()
    a.inc(3)
    b.inc()
    assert (a.value, b.value) == (3, 1)
    assert parent.value == 4
    snap = reg.snapshot()
    assert snap["counters"]["cc.hits"] == 4


def test_registry_snapshot_is_json_and_kind_collisions_raise():
    reg = MetricsRegistry()
    reg.counter("x").inc()
    reg.gauge("g").set(1.5)
    reg.histogram("h", boundaries=[1.0]).observe(0.5)
    json.dumps(reg.snapshot())  # JSON-able, no numpy leaks
    with pytest.raises(ValueError):
        reg.gauge("x")
    with pytest.raises(ValueError):
        reg.counter("h")


def test_compile_cache_counters_ride_the_registry():
    """Satellite: the cache's attribute API is a thin read of registry-
    owned child counters — per-instance exactness AND a process-wide
    aggregate from one write path."""
    from adanet_tpu.core.compile_cache import CompileCache

    before = metrics_lib.registry().snapshot()["counters"].get(
        "compile_cache.misses", 0
    )
    cache = CompileCache(max_entries=4)
    import jax
    import numpy as np

    jitted = jax.jit(lambda x: x + 1)
    x = np.zeros((2,), np.float32)
    cache.compile(jitted, x)
    cache.compile(jitted, x)
    assert (cache.misses, cache.hits) == (1, 1)
    after = metrics_lib.registry().snapshot()["counters"][
        "compile_cache.misses"
    ]
    assert after == before + 1


def test_blobstore_counters_ride_the_registry(tmp_path):
    from adanet_tpu.store import ArtifactStore

    store = ArtifactStore(str(tmp_path / "store"))
    digest = store.put(b"payload")
    assert store.get(digest) == b"payload"
    assert (store.puts, store.gets) == (1, 1)
    # Rot the blob in place: read -> quarantine, no heal source -> raise.
    with open(store.blob_path(digest), "wb") as f:
        f.write(b"rotten")
    from adanet_tpu.store.blobstore import BlobCorruptError

    with pytest.raises(BlobCorruptError):
        store.get(digest)
    assert store.quarantines == 1
    assert store.unrecoverable == 1
    # put() heals (fresh bytes) after the quarantine path.
    store.put(b"payload")
    assert store.get(digest) == b"payload"


# ----------------------------------------------------------- flight dumps


def test_flight_dump_roundtrip_and_reason_history(tmp_path):
    recorder = FlightRecorder(str(tmp_path / "fr"), clock=FakeClock())
    tracer = recorder.tracer
    with tracer.span("search", correlation={"search_id": "s"}):
        pass
    first = recorder.dump("first")
    second = recorder.dump("second", extra={"note": 7})
    assert first == second  # stable per-process path, replaced atomically
    from adanet_tpu.observability.flightrec import load_dump

    doc = load_dump(second)
    assert doc["reason"] == "second"
    assert doc["reasons"] == ["first", "second"]
    assert doc["extra"] == {"note": 7}
    assert any(e["name"] == "search" for e in doc["events"])
    assert "counters" in doc["metrics"]


def test_fault_trip_dumps_through_installed_recorder(tmp_path):
    recorder = install(FlightRecorder(str(tmp_path / "fr")))
    faults.arm("store.get", "transient")
    with pytest.raises(OSError):
        faults.trip("store.get")
    from adanet_tpu.observability.flightrec import load_dump

    doc = load_dump(recorder.dump_path)
    assert doc["reason"] == "fault:store.get:transient"
    trips = [e for e in doc["events"] if e["name"] == "fault.trip"]
    assert trips and trips[-1]["attrs"]["site"] == "store.get"
    # The armed-spec census rides along for forensics.
    assert doc["armed_faults"]["store.get"]["mode"] == "transient"


def test_install_default_shares_per_dir_and_rebinds_on_new_dir(tmp_path):
    a = install_default(str(tmp_path / "a"))
    same = install_default(str(tmp_path / "a"))
    assert same is a  # searcher + pool over one model dir share
    b = install_default(str(tmp_path / "b"))
    assert b is not a and installed() is b  # the active consumer owns
    assert b.directory.endswith("b")


def test_sweep_spares_live_writers_stages(tmp_path):
    """A live concurrent dumper's in-flight stage file must survive the
    sweep (unlinking it would lose that process's dump at rename);
    dead-writer and own-pid strays are reclaimed."""
    directory = str(tmp_path / "fr")
    recorder = FlightRecorder(directory)
    live = os.path.join(directory, ".stage-%d-live" % os.getpid())
    # Own pid: reclaimable (the lock serializes same-process dumps, so
    # an own-pid stray can only be a dead prior incarnation's).
    open(live, "w").write("x")
    dead = os.path.join(directory, ".stage-999999999-dead")
    open(dead, "w").write("x")
    other_pid = 1  # init: alive, not ours
    other = os.path.join(directory, ".stage-%d-inflight" % other_pid)
    open(other, "w").write("x")
    recorder.dump("sweep_test")
    assert not os.path.exists(live)
    assert not os.path.exists(dead)
    assert os.path.exists(other)  # live foreign writer untouched


def test_flight_dump_survives_sigkill_mid_write(tmp_path):
    """Chaos gate: the second dump is SIGKILLed between stage and
    rename (`flightrec.dump:kill:after=1`); the prior dump must stay
    intact at the final path with no readable partial."""
    directory = str(tmp_path / "fr")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(TESTS_DIR), TESTS_DIR, env.get("PYTHONPATH", "")]
    )
    env["ADANET_FAULTS"] = "flightrec.dump:kill:after=1"
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(TESTS_DIR, "flightrec_chaos_runner.py"),
            directory,
        ],
        env=env,
        capture_output=True,
        timeout=120,
    )
    out = proc.stdout.decode()
    assert proc.returncode == -signal.SIGKILL, out[-2000:]
    assert "FIRST DUMP OK" in out
    assert "UNEXPECTED SECOND DUMP COMPLETION" not in out
    dumps = glob.glob(os.path.join(directory, "flight-*.json"))
    assert len(dumps) == 1
    from adanet_tpu.observability.flightrec import load_dump

    doc = load_dump(dumps[0])  # parseable = intact, not partial
    assert doc["reason"] == "first"
    # The second dump died mid-write: its marker never reached a
    # readable dump, only the abandoned stage stray records the crash.
    assert not any(
        e["name"] == "second.marker" for e in doc["events"]
    )
    strays = [
        name
        for name in os.listdir(directory)
        if name.startswith(".stage-")
    ]
    assert strays, "SIGKILL mid-write should abandon a stage stray"
    # A later dump in a fresh recorder sweeps the strays.
    rec = FlightRecorder(directory)
    rec.dump("post")
    assert not [
        name
        for name in os.listdir(directory)
        if name.startswith(".stage-")
    ]


# ----------------------------------------------------------- overhead gate


def test_overhead_gate_disabled_tracing_reads_no_clock(
    tmp_path, annotations
):
    """ISSUE 12 satellite: with tracing disabled, the instrumented step
    path must cost ZERO tracer clock reads (counted — wall-time noise
    proves nothing), append nothing to the ring and build no profiler
    annotation."""
    tracer = spans_lib.tracer()
    was_enabled = tracer.enabled
    try:
        tracer.disable()
        reads_before = tracer.clock_reads
        events_before = len(tracer.events())
        est = build_estimator(str(tmp_path / "off"), max_iterations=1)
        est.train(input_fn, max_steps=6)
        assert tracer.clock_reads == reads_before
        assert len(tracer.events()) == events_before
        assert annotations == []
        # The control: the SAME path with tracing enabled reads the
        # clock and records spans — proving the gate watches a real
        # instrumentation seam, not dead code.
        tracer.enable()
        est2 = build_estimator(str(tmp_path / "on"), max_iterations=1)
        est2.train(input_fn, max_steps=6)
        assert tracer.clock_reads > reads_before
        new = [
            e.name
            for e in tracer.events()[events_before:]
        ]
        assert "train_window" in new and "search" in new
        assert {"train_window", "search"} <= {a.name for a in annotations}
    finally:
        if was_enabled:
            tracer.enable()
        else:
            tracer.disable()


def test_overhead_gate_disabled_tracing_compiles_read_no_clock(annotations):
    """A `CachedStep` compile and a plain `jax.jit` compile under a
    disabled tracer read no clock, build no annotation and leave no
    attribute, even on a span left open from before."""
    import jax
    import numpy as np

    from adanet_tpu.core.compile_cache import CachedStep, CompileCache

    def gated_step(x):
        return x * 5.0 + 2.0

    def gated_plain(x):
        return x * 7.0 - 3.0

    tracer = spans_lib.tracer()
    was_enabled = tracer.enabled
    tracer.enable()
    tracer.clear()
    try:
        with tracer.span("open") as span:
            tracer.disable()
            reads_before, built_before = tracer.clock_reads, len(annotations)
            x = np.ones((11,), np.float32)
            CachedStep(gated_step, CompileCache())(x)
            jax.jit(gated_plain)(x).block_until_ready()
            assert tracer.clock_reads == reads_before
            assert len(annotations) == built_before
            tracer.enable()
        assert span.attrs == {}
        assert [e.name for e in tracer.events()] == ["open"]
    finally:
        tracer.clear()
        if not was_enabled:
            tracer.disable()


# ------------------------------------------- the spans of one train call

#: Every span one resumed `Estimator.train` call leaves (docs/
#: observability.md, "Spans of one `Estimator.train` call").
TRAIN_CALL_SPANS = {
    "search",
    "resume.fsck",
    "input.next_batch",
    "input.place_batch",
    "iteration.build",
    "iteration.init_state",
    "checkpoint.restore",
    "train_window",
    "train.log",
    "checkpoint.save",
    "checkpoint.fetch",
    "checkpoint.write",
    "iteration.complete",
}


@pytest.fixture(scope="module")
def resumed_search_events(tmp_path_factory):
    """The ring after a search stopped mid-iteration (3 of 6 steps) and
    resumed to its end (2 iterations) by a fresh estimator."""
    tracer = spans_lib.tracer()
    was_enabled = tracer.enabled
    tracer.enable()
    tracer.clear()
    try:
        model_dir = str(tmp_path_factory.mktemp("spans") / "model")
        build_estimator(model_dir, log_every_steps=2).train(
            input_fn, max_steps=3
        )
        est = build_estimator(model_dir, log_every_steps=2)
        est.train(input_fn, max_steps=100)
        assert est.latest_iteration_number() == 2
        return tracer.events()
    finally:
        tracer.clear()
        if not was_enabled:
            tracer.disable()


def _calls(events):
    searches = [e for e in events if e.name == "search"]
    assert len(searches) == 2
    return [
        (search, [e for e in events if e.parent_id == search.span_id])
        for search in searches
    ]


@pytest.mark.parametrize("name", sorted(TRAIN_CALL_SPANS))
def test_resumed_train_call_leaves_every_span(resumed_search_events, name):
    search, children = _calls(resumed_search_events)[1]
    inside = {search.span_id} | {e.span_id for e in children}
    names = {search.name} | {
        e.name for e in resumed_search_events if e.parent_id in inside
    }
    assert name in names
    for event in resumed_search_events:
        if event.name == name:
            assert event.correlation["search_id"]
            assert event.end >= event.start


def test_train_call_spans_nest_and_carry_their_sizes(resumed_search_events):
    first, resumed = _calls(resumed_search_events)
    # A call that starts from nothing restores nothing.
    assert "checkpoint.restore" not in {e.name for e in first[1]}
    search, children = resumed
    by_name = {}
    for event in children:
        by_name.setdefault(event.name, []).append(event)
    # `search` is the whole call: it opens before the fsck pass.
    fsck = by_name["resume.fsck"][0]
    assert search.start <= fsck.start and fsck.attrs["verdict"] == "clean"
    assert search.attrs["max_steps"] == 100
    restore = by_name["checkpoint.restore"][0]
    assert restore.attrs["global_step"] == 3 and restore.attrs["bytes"] > 0
    assert restore.correlation["iteration"] == 0
    assert by_name["iteration.build"][0].attrs["candidates"] >= 2
    # The first window of each iteration says so, and no other.
    windows = by_name["train_window"]
    assert [w.correlation["iteration"] for w in windows if w.attrs["first"]] \
        == [0, 1]
    assert len(windows) == 3 + 6
    assert {e.attrs["stacked"] for e in by_name["input.place_batch"]} == {
        False
    }
    assert by_name["train.log"][0].attrs["global_step"] == 4
    # Every save is a fetch then a write, both inside it, both sized.
    for save in by_name["checkpoint.save"]:
        inside = [
            e for e in resumed_search_events if e.parent_id == save.span_id
        ]
        assert [e.name for e in inside] == [
            "checkpoint.fetch",
            "checkpoint.write",
        ]
        fetch, write = inside
        assert save.start <= fetch.start <= fetch.end <= write.start
        assert write.end <= save.end
        assert fetch.attrs["bytes"] > 0 and write.attrs["bytes"] > 0
        assert fetch.correlation["iteration"] == save.correlation["iteration"]
    # The resume phases of one call follow one another.
    resume = sorted(
        [
            fsck,
            by_name["input.next_batch"][0],
            by_name["iteration.build"][0],
            by_name["iteration.init_state"][0],
            restore,
            windows[0],
        ],
        key=lambda e: e.start,
    )
    assert [e.name for e in resume] == [
        "resume.fsck",
        "input.next_batch",
        "iteration.build",
        "iteration.init_state",
        "checkpoint.restore",
        "train_window",
    ]
    for before, after in zip(resume, resume[1:]):
        assert before.end <= after.start


def test_init_state_spans_say_which_init_ran(resumed_search_events):
    """`iteration.init_state` is around whichever of the two ran: the
    state's template (`abstract`, and `cached` where the `Iteration`
    remembered it) before a restore, the real init (with its `reason`)
    where the values are used."""
    first, resumed = _calls(resumed_search_events)

    def inits(call):
        # Less JAX's own work on the span (`jax.*`), asserted below.
        return [
            dict(
                {k: v for k, v in e.attrs.items() if not k.startswith("jax.")},
                iteration=e.correlation["iteration"],
            )
            for e in call[1]
            if e.name == "iteration.init_state"
        ]

    [fresh] = [e for e in first[1] if e.name == "iteration.init_state"]
    assert fresh.attrs["jax.trace_s"] > 0
    assert inits(first) == [
        {"iteration": 0, "abstract": False, "cached": False,
         "reason": "fresh"}
    ]
    # A fresh estimator resumes iteration 0 over a template it has to
    # trace, then starts iteration 1 for real.
    assert inits(resumed) == [
        {"iteration": 0, "abstract": True, "cached": False},
        {"iteration": 1, "abstract": False, "cached": False,
         "reason": "new_iteration"},
    ]


def test_trace_view_renders_resume_attributes_and_counters(tmp_path, capsys):
    """The operator's view of a resume: the `abstract` / `cached` /
    `reason` arguments of `iteration.init_state` in the exported trace,
    and the mechanism's two counters in the text summary."""
    from adanet_tpu.observability import dump_installed

    tracer = spans_lib.tracer()
    was_enabled = tracer.enabled
    tracer.enable()
    tracer.clear()
    try:
        model_dir = str(tmp_path / "model")
        est = build_estimator(model_dir)
        est.train(input_fn, max_steps=2)
        est.train(input_fn, max_steps=4)  # resumes, template remembered
        assert dump_installed("post_resume")
    finally:
        tracer.clear()
        if not was_enabled:
            tracer.disable()

    sys.path.insert(0, os.path.dirname(TESTS_DIR))
    from tools import trace_view

    export = str(tmp_path / "trace.json")
    assert trace_view.main([model_dir, "--export", export]) == 0
    text = capsys.readouterr().out
    counters = dict(
        line.split()
        for line in text.splitlines()
        if line.strip().startswith("estimator.resume.")
    )
    assert int(counters["estimator.resume.templates"]) >= 1
    assert int(counters["estimator.resume.real_inits"]) >= 1
    inits = [
        e["args"]
        for e in json.load(open(export))["traceEvents"]
        if e.get("ph") == "X" and e["name"] == "iteration.init_state"
    ]
    assert [
        (a["abstract"], a["cached"], a.get("reason")) for a in inits
    ] == [(False, False, "fresh"), (True, True, None)]


# ------------------------------------------------- trace_view / acceptance


def test_trace_view_renders_perfetto_trace_from_real_search(tmp_path):
    """Acceptance: a real 2-iteration search -> flight dump ->
    `tools/trace_view.py --export` -> Perfetto-loadable Chrome trace
    with both iterations' spans, plus a faithful text/JSON summary."""
    tracer = spans_lib.tracer()
    was_enabled = tracer.enabled
    tracer.enable()
    tracer.clear()
    try:
        model_dir = str(tmp_path / "model")
        est = build_estimator(model_dir)
        est.train(input_fn, max_steps=100)
        assert est.latest_iteration_number() == 2
        from adanet_tpu.observability import dump_installed

        dump = dump_installed("post_search")
        assert dump and os.path.dirname(dump).startswith(model_dir)
    finally:
        if not was_enabled:
            tracer.disable()

    sys.path.insert(0, os.path.dirname(TESTS_DIR))
    from tools import trace_view

    export = str(tmp_path / "trace.json")
    rc = trace_view.main([model_dir, "--json", "--export", export])
    assert rc == 0

    doc = json.load(open(export))
    trace_events = doc["traceEvents"]
    assert trace_events, "empty trace"
    # Perfetto/chrome-trace shape: complete spans with us timestamps,
    # thread metadata, and queryable args.
    complete = [e for e in trace_events if e.get("ph") == "X"]
    metadata = [e for e in trace_events if e.get("ph") == "M"]
    assert complete and metadata
    for event in complete:
        assert set(event) >= {"name", "pid", "tid", "ts", "dur", "args"}
        assert event["ts"] >= 0
    names = {e["name"] for e in complete}
    assert {"search", "train_window", "iteration.complete"} <= names
    # Both iterations of the 2-iteration search are present and tagged.
    iterations = {
        e["args"].get("iteration")
        for e in complete
        if "iteration" in e["args"]
    }
    assert {0, 1} <= iterations
    search_ids = {
        e["args"].get("search_id")
        for e in complete
        if "search_id" in e["args"]
    }
    assert len(search_ids) == 1


def test_trace_view_usage_errors(tmp_path):
    sys.path.insert(0, os.path.dirname(TESTS_DIR))
    from tools import trace_view

    assert trace_view.main([str(tmp_path / "nope")]) == 64


def test_chrome_trace_rebases_timestamps_and_names_threads():
    clock = FakeClock(start=5000.0)
    tracer = Tracer(capacity=8, clock=clock)
    with tracer.span("a"):
        clock.advance(0.002)
    doc = chrome_trace(tracer.events(), pid=7, process_name="p")
    [span] = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert span["ts"] == 0.0  # rebased to the earliest event
    assert span["dur"] == pytest.approx(2000.0)  # us
    thread_names = [
        e
        for e in doc["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    ]
    assert thread_names and thread_names[0]["pid"] == 7


# -------------------------------------------------------- serving signals


def test_frontend_exports_watermark_gauges_and_shed_counters():
    """Satellite: the backpressure signals ROADMAP item 2's replica
    balancer consumes are registry gauges, not private stats."""
    from adanet_tpu.serving.frontend import (
        AdmissionController,
        FrontendConfig,
        ServingFrontend,
    )

    class _StubBatcher:
        max_batch = 8
        pool = type(
            "P",
            (),
            {
                "active": None,
                "stats": lambda self: {},
                "poll": lambda self: False,
            },
        )()

    reg = metrics_lib.registry()
    shed_before = reg.snapshot()["counters"].get(
        "serving.frontend.status.unavailable", 0
    )
    frontend = ServingFrontend(_StubBatcher(), FrontendConfig())
    import numpy as np

    result = frontend.submit_async(
        {"x": np.zeros((1, 2), np.float32)}
    ).wait(1.0)
    assert result.status == "unavailable"
    snap = reg.snapshot()
    assert (
        snap["counters"]["serving.frontend.status.unavailable"]
        == shed_before + 1
    )
    del AdmissionController


def test_batcher_bucket_occupancy_histogram(tmp_path):
    from adanet_tpu.serving.batcher import Batcher, BatcherConfig
    from adanet_tpu.serving.model_pool import ModelPool, PoolConfig

    import numpy as np

    pool = ModelPool(str(tmp_path))
    record = type(
        "R",
        (),
        {
            "iteration_number": 0,
            "program": staticmethod(lambda batch: batch),
            "path": str(tmp_path),
        },
    )()
    pool._active = record
    batcher = Batcher(
        pool, BatcherConfig(bucket_sizes=(4, 8), jit=False)
    )
    h = batcher._h_occupancy
    count_before = h.count
    features = {"x": np.ones((3, 2), np.float32)}
    batcher.execute([features])
    assert h.count == count_before + 1
    # 3 rows into the 4-bucket: occupancy 0.75 lands in the 0.75 bucket.
    assert h.bucket_counts()[h.boundaries.index(0.75)] >= 1
    del PoolConfig
