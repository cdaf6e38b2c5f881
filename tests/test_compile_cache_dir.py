"""Crash-atomic persistent-compile-cache writes (ISSUE 17 hardening).

jax's `LRUCache.put` writes entry bytes directly at the final key path.
The chaos suites SIGKILL subprocess writers by design, and those
subprocesses share `tests/.jax_cache` — a kill landing mid-write leaves
a TORN entry at a live key, and the next process to deserialize it can
segfault (observed: tier-1 dying inside a compiled call after a chaos
round). `enable_persistent_cache` therefore installs staged+fsync+
rename entry writes; these tests pin the property that matters: the
final path is either absent or complete, at every instant.
"""

import os

import pytest

from adanet_tpu.utils import compile_cache_dir as ccd


def _make_cache(tmp_path, max_size=-1):
    from jax._src import lru_cache

    return lru_cache.LRUCache(str(tmp_path), max_size=max_size)


def test_atomic_put_installed_and_idempotent():
    # conftest already ran enable_persistent_cache; the seam is marked.
    assert ccd.install_atomic_cache_writes() is True
    from jax._src import lru_cache

    assert getattr(lru_cache.LRUCache.put, "_adanet_atomic", False)
    # Installing twice must not stack wrappers.
    before = lru_cache.LRUCache.put
    assert ccd.install_atomic_cache_writes() is True
    assert lru_cache.LRUCache.put is before


def test_put_get_roundtrip_and_no_staging_droppings(tmp_path):
    ccd.install_atomic_cache_writes()
    cache = _make_cache(tmp_path)
    cache.put("key1", b"payload-bytes")
    assert cache.get("key1") == b"payload-bytes"
    # Set-once, like upstream: a second put of the same key is a no-op.
    cache.put("key1", b"different")
    assert cache.get("key1") == b"payload-bytes"
    assert not [f for f in os.listdir(tmp_path) if ".tmp-" in f]


def test_interrupted_write_leaves_no_torn_entry(tmp_path, monkeypatch):
    """A crash at the worst instant (bytes written, rename pending) must
    leave NOTHING at the final path — a reader sees a miss and
    recompiles, never a truncated executable."""
    ccd.install_atomic_cache_writes()
    cache = _make_cache(tmp_path)

    real_replace = os.replace

    def exploding_replace(src, dst):
        raise OSError("simulated kill mid-publish")

    monkeypatch.setattr(os, "replace", exploding_replace)
    with pytest.raises(OSError, match="simulated kill"):
        cache.put("hot-key", b"x" * 4096)
    monkeypatch.setattr(os, "replace", real_replace)

    assert cache.get("hot-key") is None  # miss, not garbage
    assert not [f for f in os.listdir(tmp_path) if ".tmp-" in f]
    # The cache still works after the failed publish.
    cache.put("hot-key", b"y" * 4096)
    assert cache.get("hot-key") == b"y" * 4096


def test_enable_persistent_cache_reports_configured_dir():
    import jax

    # conftest configured the cache at import; a second enable is a
    # no-op on the directory but must still return the live setting.
    assert ccd.enable_persistent_cache() == (
        jax.config.jax_compilation_cache_dir
    )


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRINT_CACHE_DIR = """
import json, jax
from adanet_tpu.utils.compile_cache_dir import enable_persistent_cache
returned = enable_persistent_cache()
print(json.dumps([returned, jax.config.jax_compilation_cache_dir]))
"""


def _cache_dir_in_fresh_process(env_dir):
    """(returned, configured) cache directory of a fresh CPU process."""
    import json
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run(
        [sys.executable, "-c", _PRINT_CACHE_DIR],
        cwd=_REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_cache_dir_from_the_environment_is_used_as_given(tmp_path):
    """`JAX_COMPILATION_CACHE_DIR` places the cache from outside: that
    directory exactly (no versioned subdirectory), and nothing else is
    set in code."""
    given = str(tmp_path / "placed-from-outside")
    assert _cache_dir_in_fresh_process(given) == [given, given]


def test_cache_dir_unset_is_one_fixed_path_inside_the_checkout():
    """Without the variable: `<checkout>/tests/.jax_cache/<jax>-<jaxlib>-
    <backend><n>`, the same in every process (the path is part of the
    cache key: a temporary, pid- or time-derived one would never hit)."""
    import jax
    import jaxlib

    first = _cache_dir_in_fresh_process(None)
    second = _cache_dir_in_fresh_process(None)
    want = os.path.join(
        _REPO,
        "tests",
        ".jax_cache",
        "%s-%s-cpu1" % (jax.__version__, jaxlib.__version__),
    )
    assert first == second == [want, want]
