"""Topology-keyed persistent XLA compilation cache directories.

Entries in jax's persistent compilation cache are only valid for the
jax/jaxlib build and device topology that produced them; deserializing
an executable written under a different one can crash the process
outright (segfault observed when a cache directory was shared between
1- and 8-device CPU runs across a jax upgrade). Keying the directory by
version and topology makes stale entries unreachable instead of fatal —
every (jax, jaxlib, backend, device-count) signature gets its own
subdirectory under the shared base.

The same failure class exists WITHIN one topology: jax's `LRUCache.put`
writes entry bytes directly at the final key path, so a process killed
mid-write (the chaos suites SIGKILL checkpoint/store writers by design,
and those subprocesses share this cache) leaves a TORN entry at a live
key — and the next process to deserialize it can segfault. Enabling the
cache through this module therefore also installs crash-atomic entry
writes (staged + fsync + rename, the artifact store's protocol), so a
kill at any instant leaves either no entry or a complete one.
"""

from __future__ import annotations

import os
import uuid

import jax


#: The one base directory of this checkout's compile cache (git-ignored).
#: Fixed on purpose: the directory is part of every entry's cache key, so
#: a temporary, pid- or time-derived path would never hit.
CHECKOUT_CACHE_BASE = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    "tests",
    ".jax_cache",
)


def versioned_cache_dir(base: str = CHECKOUT_CACHE_BASE) -> str:
    """`<base>/<jax>-<jaxlib>-<backend><ndevices>` for THIS process.

    Calling this initializes jax's backend: call it only after platform
    and device-count configuration (`jax_platforms`, `XLA_FLAGS` /
    `jax_num_cpu_devices`) is final.
    """
    import jaxlib

    tag = "%s-%s-%s%d" % (
        jax.__version__,
        jaxlib.__version__,
        jax.default_backend(),
        jax.device_count(),
    )
    return os.path.join(base, tag)


def _write_bytes_atomic(path: str, data: bytes) -> None:
    """Staged + fsync + rename: `path` either absent or complete, at
    every instant, even across SIGKILL."""
    tmp = "%s.tmp-%d-%s" % (path, os.getpid(), uuid.uuid4().hex[:8])
    try:
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            try:
                os.unlink(tmp)
            except OSError:
                pass


def install_atomic_cache_writes() -> bool:
    """Replaces jax's persistent-cache entry write with a crash-atomic
    one (see module docstring). Idempotent; returns True once the atomic
    path is installed. Written against the installed jax's
    `jax._src.lru_cache`: if those internals move, this raises rather
    than leaving torn-entry protection off in silence.
    """
    from jax._src import lru_cache as _lru

    cache_cls = _lru.LRUCache
    cache_suffix = _lru._CACHE_SUFFIX
    atime_suffix = _lru._ATIME_SUFFIX
    original_put = cache_cls.put
    if getattr(original_put, "_adanet_atomic", False):
        return True

    def put(self, key, val):
        try:
            root = os.fspath(self.path)
        except TypeError:
            root = None
        if root is None or not os.path.isdir(root):
            # Non-local backing (e.g. a cloud bucket path): rename-based
            # atomicity does not apply; keep upstream behavior.
            return original_put(self, key, val)
        if not key:
            raise ValueError("key cannot be empty")
        eviction = getattr(self, "eviction_enabled", False)
        if eviction and len(val) > self.max_size:
            # Same contract as upstream: oversized entries are dropped.
            return original_put(self, key, val)
        cache_path = os.path.join(root, "%s%s" % (key, cache_suffix))
        atime_path = os.path.join(root, "%s%s" % (key, atime_suffix))
        if eviction:
            self.lock.acquire(timeout=self.lock_timeout_secs)
        try:
            if os.path.exists(cache_path):
                return
            if eviction:
                self._evict_if_needed(additional_size=len(val))
            _write_bytes_atomic(cache_path, val)
            import time as _time

            _write_bytes_atomic(
                atime_path, _time.time_ns().to_bytes(8, "little")
            )
        finally:
            if eviction:
                self.lock.release()

    put._adanet_atomic = True
    cache_cls.put = put
    return True


def enable_persistent_cache() -> str:
    """Turns on jax's persistent compile cache; returns its directory.

    The directory is placed from OUTSIDE the program: when
    `JAX_COMPILATION_CACHE_DIR` is set (jax reads it into its config at
    import), that directory is used exactly as given and nothing here
    sets another. Only when none is configured does the cache go to the
    fixed `versioned_cache_dir()` inside this checkout. Every entry point
    (tests, `chip_smoke.py`, the trainer CLI, the benchmark) comes
    through here, so they all share one cache. Either way, entry writes
    become crash-atomic (`install_atomic_cache_writes`).
    """
    install_atomic_cache_writes()
    if jax.config.jax_compilation_cache_dir is not None:
        return jax.config.jax_compilation_cache_dir
    path = versioned_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return path
