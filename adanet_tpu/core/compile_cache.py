"""Signature-keyed AOT compile cache: reuse XLA executables across
iterations.

SURVEY §7 hard part (a): every AdaNet iteration rebuilds its programs, and
jit's internal cache keys on function identity, so iteration t+1 re-pays
XLA compilation even for programs structurally identical to iteration t's
(e.g. the same-architecture candidate steps a `SimpleGenerator` produces
every round under RoundRobin placement, or a rebuilt iteration after
restart). The reference never pays this because it keeps one live TF graph
per iteration.

`CompileCache` closes the gap without any semantic risk: programs are
keyed by the HASH OF THEIR LOWERED StableHLO (which embeds shapes, dtypes,
shardings, and donation/aliasing) plus the argument device assignment —
i.e. two programs share an executable only when XLA would be handed
byte-identical input on the same devices. Tracing/lowering still runs once
per program instance (cheap); the XLA optimization pipeline — the
dominant cost — is skipped on a hit.

`CachedStep` is the call-site wrapper: it behaves like `jax.jit(fn)` but
routes compilation through a shared `CompileCache`, memoizing the
executable per argument spec so lowering is also amortized within an
instance.

With a content-addressed `ArtifactStore` attached (`store=`), the cache
gains a PERSISTENT tier: fresh compiles are serialized
(`jax.experimental.serialize_executable`) and published under a ref
keyed by (StableHLO hash, device assignment, pytree structures, env
fingerprint), so a separate search run — or a separate process —
sharing the store deserializes the executable instead of re-paying the
XLA pipeline. The env fingerprint (jax, jaxlib, backend, device count;
`store.keys.env_fingerprint`) gates deserialization exactly as
`utils/compile_cache_dir.py` gates the jax-internal persistent cache:
an executable from a different build or topology is unreachable, never
fatal. A blob that cannot be loaded or published degrades to a plain
compile with a warning, and `store_errors` counts it.
"""

from __future__ import annotations

import collections
import hashlib
import logging
import pickle
import re
from typing import Any, Optional, Tuple

import jax
import numpy as np

from adanet_tpu.observability import spans as spans_lib
from adanet_tpu.robustness import faults
from adanet_tpu.robustness.retry import with_retries

_LOG = logging.getLogger("adanet_tpu")

#: Ref kind under which serialized executables live in the store.
AOT_REF_KIND = "aot"


def _leaf_spec(leaf) -> Tuple:
    # Raw hashable objects, no repr strings: jax shardings hash their
    # mesh AND concrete devices, so the spec distinguishes equal-shaped
    # submeshes on different chips (an executable is device-bound).
    if isinstance(leaf, jax.Array):
        return (leaf.shape, leaf.dtype, leaf.sharding)
    arr = np.asarray(leaf)
    return (arr.shape, arr.dtype, None)


def arg_spec(args) -> Tuple:
    """Hashable structure/shape/dtype/sharding signature of call args."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (treedef, tuple(_leaf_spec(leaf) for leaf in leaves))


def _device_fingerprint(args) -> Tuple:
    """ORDERED device assignments of committed args (HLO text omits
    devices, and an executable is bound to them — including their order:
    two submeshes over the same device set in different orders must not
    collide; ADVICE r2). Distinct assignments are recorded once, in order
    of first appearance."""
    assignments = []
    seen = set()
    for leaf in jax.tree_util.tree_leaves(args):
        if not isinstance(leaf, jax.Array):
            continue
        sharding = leaf.sharding
        devices = getattr(sharding, "_device_assignment", None)
        if devices is None:
            devices = sorted(sharding.device_set, key=lambda d: d.id)
        ids = tuple(d.id for d in devices)
        if ids not in seen:
            seen.add(ids)
            assignments.append(ids)
    return tuple(assignments)


class CompileCache:
    """Shared executable store keyed by (StableHLO hash, devices).

    Bounded LRU: a long search compiles programs that can never hit again
    (each iteration's ensemble program embeds one more frozen member), so
    stale entries are evicted beyond `max_entries`. Live `CachedStep`
    instances keep their own references, so eviction never invalidates an
    executable in use.
    """

    def __init__(self, max_entries: int = 128, store=None):
        from adanet_tpu.observability import metrics as metrics_lib

        self._executables = collections.OrderedDict()
        self._max_entries = int(max_entries)
        self._store = store
        # Accounting lives on the process metrics registry
        # (`compile_cache.*` aggregates across every cache instance —
        # snapshots, flight dumps); each instance holds scoped
        # CHILD counters so the long-standing per-instance attribute API
        # below (`cache.hits`, `cache.store_hits`, ...) keeps its exact
        # semantics as thin reads.
        reg = metrics_lib.registry()
        self._m_hits = reg.counter("compile_cache.hits").child()
        self._m_misses = reg.counter("compile_cache.misses").child()
        #: Persistent-tier accounting: `store_hits` skipped an XLA
        #: compile entirely (deserialized from the shared store);
        #: `store_misses` compiled fresh (and, when serializable,
        #: published); `store_errors` counts degradations (a failed
        #: serialize/deserialize or a corrupt/unhealable blob) — those
        #: fall back to a plain compile with a warning.
        self._m_store_hits = reg.counter("compile_cache.store_hits").child()
        self._m_store_misses = reg.counter(
            "compile_cache.store_misses"
        ).child()
        self._m_store_errors = reg.counter(
            "compile_cache.store_errors"
        ).child()

    @property
    def hits(self) -> int:
        """In-memory executable reuses (per instance)."""
        return self._m_hits.value

    @property
    def misses(self) -> int:
        """XLA compiles paid by this instance."""
        return self._m_misses.value

    @property
    def store_hits(self) -> int:
        """Persistent-tier deserializations (no XLA pipeline)."""
        return self._m_store_hits.value

    @property
    def store_misses(self) -> int:
        """Fresh compiles that consulted the store first."""
        return self._m_store_misses.value

    @property
    def store_errors(self) -> int:
        """Persistent-tier degradations to a plain compile."""
        return self._m_store_errors.value

    def _store_ref_name(self, digest: str, device_fp, in_tree, out_tree):
        from adanet_tpu.store import keys as store_keys

        return store_keys.ref_name(
            store_keys.sha256_hex(
                "|".join(
                    [
                        digest,
                        repr(device_fp),
                        str(in_tree),
                        str(out_tree),
                    ]
                ).encode()
            ),
            store_keys.env_fingerprint()[:16],
        )

    def _store_load(self, ref_name: str, lowered):
        """Deserializes a previously published executable onto the
        devices `lowered` was lowered for, or None."""
        entry = self._store.get_ref(AOT_REF_KIND, ref_name)
        if entry is None:
            return None
        digest = entry.get("blobs", {}).get("executable")
        if digest is None:
            return None
        try:
            blob = self._store.get(digest)
            from jax.experimental import serialize_executable

            payload, in_tree, out_tree = pickle.loads(blob)
            # Without `execution_devices` the executable loads onto
            # EVERY device of the backend and then rejects its own
            # single-device arguments. `Lowered` has no public name for
            # its devices; should this one move, the load degrades like
            # any other failure here (and test_store's cases, which
            # hold `store_errors` to 0, say so).
            return serialize_executable.deserialize_and_load(
                payload,
                in_tree,
                out_tree,
                execution_devices=list(lowered._lowering._device_list),
            )
        except Exception as exc:
            # Unsupported backend, corrupt-and-unhealable blob, or a
            # pickle from an incompatible build that slipped the env
            # fingerprint: degrade to a plain compile. Executables are
            # pure cache (no heal sources, and re-serialized bytes are
            # not guaranteed byte-identical), so drop the set-once ref
            # too — the fresh compile below republishes under this name
            # with a new blob instead of leaving a permanently dangling
            # ref the store fsck would flag forever.
            self._m_store_errors.inc()
            try:
                self._store.delete_ref(AOT_REF_KIND, ref_name)
            except OSError:
                pass
            _LOG.warning(
                "Persistent compile tier: load failed (%s: %s); "
                "dropped the cache ref and recompiling.",
                type(exc).__name__,
                exc,
            )
            return None

    def _store_save(self, ref_name: str, executable) -> None:
        try:
            from jax.experimental import serialize_executable

            blob = pickle.dumps(serialize_executable.serialize(executable))
            digest = self._store.put(blob)
            self._store.put_ref(
                AOT_REF_KIND,
                ref_name,
                {"executable": digest},
                # `recreatable`: pure cache — fsck may prune the ref
                # when its blob is unrecoverable (a fresh compile
                # re-publishes) instead of reporting it dangling.
                meta={"bytes": len(blob), "recreatable": True},
            )
        except Exception as exc:
            self._m_store_errors.inc()
            _LOG.warning(
                "Persistent compile tier: publish failed (%s: %s); "
                "the executable stays process-local.",
                type(exc).__name__,
                exc,
            )

    def compile(self, jitted, *args):
        """Lower `jitted` for `args`; reuse an executable when the lowered
        program and device assignment match a previous compile.

        Each call is one `compile` span (`program`, `chars`, `outcome`:
        `memory`, `store` or `backend`) whose children are its stages in
        order: `compile.trace`, `compile.lower`, `compile.key`, then on a
        memory miss `compile.store_load` (with a store), `compile.backend`
        (without one, or on a store miss) and `compile.store_save` (the
        fresh executable published). A memory hit still traces, lowers and
        keys."""
        tracer = spans_lib.tracer()
        with tracer.span(
            "compile", program=getattr(jitted, "__name__", "")
        ) as span:
            with tracer.span("compile.trace"):
                traced = jitted.trace(*args)
            with tracer.span("compile.lower"):
                lowered = traced.lower()
            with tracer.span("compile.key"):
                text, key = self._key(lowered, args)
            span.set(chars=len(text))
            executable = self._executables.get(key)
            if executable is not None:
                span.set(outcome="memory")
                self._executables.move_to_end(key)
                self._m_hits.inc()
                return executable
            executable, outcome = self._load_or_compile(lowered, key)
            span.set(outcome=outcome)
            self._executables[key] = executable
            while len(self._executables) > self._max_entries:
                self._executables.popitem(last=False)
            return executable

    @staticmethod
    def _key(lowered, args):
        """(the canonical lowered text, the cache key) of one program."""
        # The module symbol carries the python function's name
        # (`module @jit_f`); canonicalize it so identical programs from
        # differently-named closures (each Iteration builds fresh ones)
        # hash equal.
        text = re.sub(
            r"^module @\S+", "module @m", lowered.as_text(), count=1
        )
        digest = hashlib.sha256(text.encode()).hexdigest()
        # Key the in/out pytree structures explicitly: current JAX embeds
        # them in the lowered text as arg/result metadata, but executable
        # identity must not ride on incidental text format (ADVICE r2) —
        # returning the right buffers under the wrong treedef would be a
        # silent output-structure corruption.
        in_tree = jax.tree_util.tree_structure(args)
        try:
            out_tree = jax.tree_util.tree_structure(lowered.out_info)
        except Exception:  # out_info unavailable on exotic stages
            out_tree = None
        return text, (digest, _device_fingerprint(args), in_tree, out_tree)

    def _load_or_compile(self, lowered, key):
        """(executable, outcome): from the persistent tier (`store`), else
        from XLA (`backend`; it may itself read its on-disk cache) and
        published."""
        tracer = spans_lib.tracer()
        ref_name = None
        if self._store is not None:
            # Persistent tier: another run sharing the store may have
            # already paid this compile.
            with tracer.span("compile.store_load"):
                ref_name = self._store_ref_name(*key)
                executable = self._store_load(ref_name, lowered)
            if executable is not None:
                self._m_store_hits.inc()
                return executable, "store"

        # The compile may read a persistent on-disk XLA cache (see
        # utils/compile_cache_dir.py): a transient I/O error there — or
        # at the `compile_cache.read` fault site chaos runs arm — is
        # retried with bounded deterministic backoff instead of killing
        # a multi-hour search over one EIO.
        def compile_once():
            faults.trip("compile_cache.read")
            return lowered.compile()

        with tracer.span("compile.backend"):
            executable = with_retries(compile_once, label="compile-cache read")
        self._m_misses.inc()
        if ref_name is not None:
            self._m_store_misses.inc()
            with tracer.span("compile.store_save"):
                self._store_save(ref_name, executable)
        return executable, "backend"

    def clear(self) -> None:
        self._executables.clear()


class CachedStep:
    """A jit-like callable whose compilation goes through a CompileCache.

    With `cache=None` it degrades to plain `jax.jit` (zero overhead for
    users who do not opt in). `name` names the compiled program
    (`jit_<name>` in a profile and in the compile cache's key) where
    the function's own name is not one to publish.
    """

    def __init__(
        self,
        fn,
        cache: Optional[CompileCache],
        donate_argnums=(),
        name: Optional[str] = None,
    ):
        if name is not None:
            inner = fn

            def fn(*args):
                return inner(*args)

            fn.__name__ = fn.__qualname__ = name
        self._jit = jax.jit(fn, donate_argnums=donate_argnums)
        self._cache = cache
        self._by_spec: dict = {}
        self._last: Optional[Any] = None

    def __call__(self, *args):
        if self._cache is None:
            return self._jit(*args)
        failed = original_error = None
        if self._last is not None:
            # Optimistic dispatch: steps are called with a stable spec, so
            # skip the per-call pytree flatten. The executable validates
            # input avals/shardings BEFORE running and raises TypeError/
            # ValueError on mismatch (new batch shape, re-placement), in
            # which case we fall through to the full lookup.
            try:
                return self._last(*args)
            except (TypeError, ValueError) as exc:
                failed, original_error = self._last, exc
        spec = arg_spec(args)
        executable = self._by_spec.get(spec)
        if executable is None:
            executable = self._cache.compile(self._jit, *args)
            self._by_spec[spec] = executable
        if executable is failed:
            # The full lookup resolved to the very executable that just
            # failed: the error is genuine (e.g. a donated buffer reused),
            # not a spec change — surface the original diagnostic instead
            # of a confusing secondary failure (ADVICE r2).
            raise original_error
        self._last = executable
        return executable(*args)
