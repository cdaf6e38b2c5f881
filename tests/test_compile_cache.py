"""Signature-keyed AOT compile cache (SURVEY §7 hard part (a)).

Iteration t+1's structurally-identical programs must reuse iteration t's
XLA executables instead of recompiling — the gap the reference never pays
because it keeps one live graph per iteration.
"""

import jax
import numpy as np
import optax
import pytest

from adanet_tpu.core.compile_cache import CachedStep, CompileCache
from adanet_tpu.core.heads import RegressionHead
from adanet_tpu.core.iteration import IterationBuilder
from adanet_tpu.distributed import RoundRobinExecutor, RoundRobinStrategy
from adanet_tpu.ensemble import ComplexityRegularizedEnsembler, GrowStrategy
from adanet_tpu.observability import spans as spans_lib
from adanet_tpu.store import ArtifactStore

from helpers import DNNBuilder, linear_dataset


def test_cached_step_reuses_executable():
    cache = CompileCache()

    def f(x):
        return x * 2.0

    def g(x):
        return x * 2.0

    a = CachedStep(f, cache)
    b = CachedStep(g, cache)  # distinct function, identical program
    x = np.ones((4,), np.float32)
    np.testing.assert_array_equal(a(x), 2 * x)
    assert (cache.hits, cache.misses) == (0, 1)
    np.testing.assert_array_equal(b(x), 2 * x)
    assert (cache.hits, cache.misses) == (1, 1)
    # Same instance re-call: memoized locally, no extra lowering/hit.
    b(x)
    assert (cache.hits, cache.misses) == (1, 1)
    # Different shape: new program.
    b(np.ones((8,), np.float32))
    assert cache.misses == 2


def test_cached_step_without_cache_is_plain_jit():
    step = CachedStep(lambda x: x + 1.0, cache=None)
    np.testing.assert_array_equal(
        step(np.zeros((2,), np.float32)), np.ones((2,))
    )


def test_rebuilt_iteration_skips_recompilation():
    """A rebuilt same-structure iteration (restart / evaluate-after-train
    flows) reuses the first build's fused executables."""
    cache = CompileCache()
    factory = IterationBuilder(
        head=RegressionHead(),
        ensemblers=[ComplexityRegularizedEnsembler(optimizer=optax.sgd(0.05))],
        ensemble_strategies=[GrowStrategy()],
        compile_cache=cache,
    )
    builders = [DNNBuilder("a", 1)]
    sample = next(linear_dataset()())

    it0 = factory.build_iteration(0, builders, None)
    st = it0.init_state(jax.random.PRNGKey(0), sample)
    st, _ = it0.train_step(st, sample)
    assert (cache.hits, cache.misses) == (0, 1)

    it0b = factory.build_iteration(0, builders, None)
    st_b = it0b.init_state(jax.random.PRNGKey(0), sample)
    it0b.train_step(st_b, sample)
    assert (cache.hits, cache.misses) == (1, 1)


def test_round_robin_candidate_programs_reuse_across_iterations():
    """Under RoundRobin, a same-architecture candidate regenerated at
    iteration t+1 reuses t's compiled subnetwork-step executable."""
    cache = CompileCache()
    factory = IterationBuilder(
        head=RegressionHead(),
        ensemblers=[ComplexityRegularizedEnsembler(optimizer=optax.sgd(0.05))],
        ensemble_strategies=[GrowStrategy()],
        compile_cache=cache,
    )
    sample = next(linear_dataset()())

    def run_iteration(t, previous):
        builders = [DNNBuilder("a", 1), DNNBuilder("b", 2)]
        it = factory.build_iteration(t, builders, previous)
        executor = RoundRobinExecutor(it, RoundRobinStrategy())
        st = executor.init_state(jax.random.PRNGKey(t), sample)
        st, _ = executor.train_step(st, sample)
        best = it.candidate_names()[it.best_candidate_index(st)]
        return it.freeze_candidate(executor.gather(st), best, sample)

    frozen = run_iteration(0, None)
    hits_t0 = cache.hits
    run_iteration(1, frozen)
    # At t=1 the regenerated candidates 'a' and 'b' lower to the same
    # StableHLO on the same submeshes -> at least their two subnetwork
    # step programs hit (the ensemble program differs: frozen member).
    assert cache.hits >= hits_t0 + 2, (cache.hits, cache.misses)


def test_estimator_search_reuses_candidate_programs(tmp_path):
    """End-to-end: a 2-iteration RoundRobin search records cache hits for
    iteration 1's regenerated candidate programs."""
    import adanet_tpu
    from adanet_tpu.subnetwork import SimpleGenerator

    est = adanet_tpu.Estimator(
        head=RegressionHead(),
        subnetwork_generator=SimpleGenerator(
            [DNNBuilder("a", 1), DNNBuilder("b", 2)]
        ),
        max_iteration_steps=4,
        ensemblers=[ComplexityRegularizedEnsembler(optimizer=optax.sgd(0.05))],
        max_iterations=2,
        model_dir=str(tmp_path / "model"),
        log_every_steps=0,
        placement_strategy=RoundRobinStrategy(),
    )
    est.train(linear_dataset(), max_steps=100)
    assert est.latest_iteration_number() == 2
    assert est._compile_cache.hits >= 2, (
        est._compile_cache.hits,
        est._compile_cache.misses,
    )


# ------------------------------------------------- the compile path's spans


@pytest.fixture
def traced():
    """The process tracer, enabled and emptied; restored after."""
    tracer = spans_lib.tracer()
    was_enabled = tracer.enabled
    tracer.enable()
    tracer.clear()
    try:
        yield tracer
    finally:
        tracer.clear()
        if not was_enabled:
            tracer.disable()


def _compiles(tracer):
    """[(the `compile` span, its children in order)] from the ring."""
    events = tracer.events()
    return [
        (event, sorted(
            (e for e in events if e.parent_id == event.span_id),
            key=lambda e: e.start,
        ))
        for event in events
        if event.name == "compile"
    ]


def _adanet_train_step(x):
    return x * 4.0 - 1.0


def test_compile_span_has_its_stages_as_children_in_order(traced):
    x = np.ones((5,), np.float32)
    CachedStep(_adanet_train_step, CompileCache())(x)
    [(span, children)] = _compiles(traced)
    assert [e.name for e in children] == [
        "compile.trace", "compile.lower", "compile.key", "compile.backend",
    ]
    assert span.attrs["program"] == "_adanet_train_step"
    assert span.attrs["chars"] > 0
    for before, after in zip(children, children[1:]):
        assert before.end <= after.start
    # JAX's own work lands on the stage that caused it.
    assert children[0].attrs["jax.trace_s"] > 0
    assert children[1].attrs["jax.lower_s"] > 0
    assert children[3].attrs["jax.compiles"] == 1
    assert children[3].attrs["jax.programs"] == ["jit(_adanet_train_step)"]


def test_compile_outcome_is_backend_then_memory(traced):
    """A second `CachedStep` of the same function reuses the executable,
    and still traces, lowers and keys: its span says so."""
    cache = CompileCache()
    x = np.ones((6,), np.float32)
    CachedStep(_adanet_train_step, cache)(x)
    CachedStep(_adanet_train_step, cache)(x)
    (first, _), (second, children) = _compiles(traced)
    assert (first.attrs["outcome"], second.attrs["outcome"]) == (
        "backend", "memory",
    )
    assert [e.name for e in children] == [
        "compile.trace", "compile.lower", "compile.key",
    ]
    assert second.attrs["chars"] == first.attrs["chars"]


def test_compile_store_load_span_appears_with_a_store(traced, tmp_path):
    store = ArtifactStore(str(tmp_path / "store"))
    x = np.arange(7, dtype=np.float32)
    CachedStep(_adanet_train_step, CompileCache(store=store))(x)
    fresh = CompileCache(store=store)
    CachedStep(_adanet_train_step, fresh)(x)
    assert fresh.store_hits == 1
    (first, published), (second, loaded) = _compiles(traced)
    assert [e.name for e in published] == [
        "compile.trace", "compile.lower", "compile.key",
        "compile.store_load", "compile.backend", "compile.store_save",
    ]
    assert first.attrs["outcome"] == "backend"
    assert [e.name for e in loaded] == [
        "compile.trace", "compile.lower", "compile.key",
        "compile.store_load",
    ]
    assert second.attrs["outcome"] == "store"


def test_compile_children_lie_within_the_span(traced):
    cache = CompileCache()
    for n in (3, 3, 9):
        CachedStep(_adanet_train_step, cache)(np.ones((n,), np.float32))
    compiles = _compiles(traced)
    assert len(compiles) == 3
    for span, children in compiles:
        assert all(
            span.start <= e.start <= e.end <= span.end for e in children
        )
        assert sum(e.duration for e in children) <= span.duration
