"""Artifact-store suite: content addressing, healing, leases, GC,
warm starts, and the shared-store chaos gate.

Proves the `adanet_tpu/store/` contract by doing, not inspecting:
blobs are torn/rotted on disk and reads must quarantine + heal from
duplicate referencers; GC races an active lease and must never evict a
reachable blob; two concurrent searches share one store under armed
`store.put` torn/rot faults plus a SIGKILL mid-publish and must reach
oracle-identical final architectures with the store fsck-clean; and a
second search run replays the first through the store with zero XLA
compiles and zero retraining (the ISSUE 10 warm-start gate).
"""

import json
import os
import signal
import subprocess
import sys
import threading

import pytest

from adanet_tpu import replay as replay_lib
from adanet_tpu.core import checkpoint as ckpt_lib
from adanet_tpu.robustness import faults
from adanet_tpu.store import (
    ArtifactStore,
    BlobCorruptError,
    BlobMissingError,
    collect,
    fsck_store,
    keys,
    leases,
)

from chaos_common import build_estimator, input_fn

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True)
def _disarm_faults():
    faults.disarm()
    yield
    faults.disarm()


def _subprocess_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(TESTS_DIR), TESTS_DIR, env.get("PYTHONPATH", "")]
    )
    return env


def _arch(model_dir, t):
    with open(
        os.path.join(model_dir, ckpt_lib.architecture_filename(t))
    ) as f:
        return json.load(f)


# ------------------------------------------------------------------ blobs


def test_blob_round_trip_and_dedupe(tmp_path):
    store = ArtifactStore(str(tmp_path / "store"))
    d1 = store.put(b"payload bytes")
    assert keys.is_digest(d1)
    assert store.put(b"payload bytes") == d1  # content-addressed dedupe
    assert store.get(d1) == b"payload bytes"
    assert store.has_blob(d1)
    assert [d for d, _ in store.iter_blobs()] == [d1]


def test_put_heals_torn_existing_blob(tmp_path):
    """A torn direct write at the final path (a crashed peer without
    atomic-rename semantics) is quarantined and replaced by the next
    put of the same content."""
    store = ArtifactStore(str(tmp_path / "store"))
    digest = store.put(b"x" * 1024)
    with open(store.blob_path(digest), "wb") as f:
        f.write(b"x" * 100)  # truncated prefix
    assert store.put(b"x" * 1024) == digest
    assert store.get(digest) == b"x" * 1024
    assert store.quarantined_blobs()


def test_get_quarantines_and_heals_from_ref_source(tmp_path):
    store = ArtifactStore(str(tmp_path / "store"))
    source = tmp_path / "local_copy.bin"
    source.write_bytes(b"frozen member payload")
    digest = store.put(b"frozen member payload")
    store.put_ref(
        "frozen",
        keys.ref_name(digest[:16], "spec0"),
        {"frozen.msgpack": digest},
        sources=[str(source)],
    )
    # Silent rot at the final path.
    with open(store.blob_path(digest), "r+b") as f:
        f.seek(3)
        f.write(b"\xff\xff")
    assert store.get(digest) == b"frozen member payload"
    assert any(
        name.startswith(digest) for name in store.quarantined_blobs()
    )
    # Healed in place: the next read takes the fast path.
    assert store.get(digest) == b"frozen member payload"


def test_get_unhealable_raises(tmp_path):
    store = ArtifactStore(str(tmp_path / "store"))
    digest = store.put(b"some bytes")
    with open(store.blob_path(digest), "wb") as f:
        f.write(b"rotted")
    with pytest.raises(BlobCorruptError):
        store.get(digest)
    missing = keys.sha256_hex(b"never stored")
    with pytest.raises(BlobMissingError):
        store.get(missing)
    # extra_sources heal a missing blob without any ref.
    source = tmp_path / "dup.bin"
    source.write_bytes(b"never stored")
    assert store.get(missing, extra_sources=[str(source)]) == b"never stored"


# ------------------------------------------------------------------- refs


def test_ref_set_once_claim(tmp_path):
    store = ArtifactStore(str(tmp_path / "store"))
    d1 = store.put(b"one")
    d2 = store.put(b"two")
    name = keys.ref_name("a" * 64, "spec")
    winner = store.put_ref("frozen", name, {"payload": d1}, meta={"n": 1})
    loser = store.put_ref("frozen", name, {"payload": d2}, meta={"n": 2})
    # The loser adopted the winner's document — set-once arbitration.
    assert loser["blobs"]["payload"] == d1
    assert loser["meta"] == {"n": 1}
    assert store.get_ref("frozen", name)["blobs"]["payload"] == d1
    assert winner["created_at"] >= 0


def test_wait_for_ref_bounded(tmp_path):
    store = ArtifactStore(str(tmp_path / "store"))
    with pytest.raises(TimeoutError):
        store.wait_for_ref("frozen", "absent-ref", 0.15)
    digest = store.put(b"z")
    store.put_ref("frozen", "present-ref", {"payload": digest})
    doc = store.wait_for_ref("frozen", "present-ref", 1.0)
    assert doc["blobs"]["payload"] == digest


def test_ref_name_rejects_unsafe_parts(tmp_path):
    with pytest.raises(ValueError):
        keys.ref_name("ok", "../escape")
    with pytest.raises(ValueError):
        keys.ref_name("")
    # All-dot components resolve upward out of the refs tree: both the
    # name helper and the store's own path validation must reject them.
    with pytest.raises(ValueError):
        keys.ref_name("..")
    store = ArtifactStore(str(tmp_path / "store"))
    for kind, name in ((".." , "x"), ("frozen", ".."), ("frozen", ".")):
        with pytest.raises(ValueError):
            store.ref_path(kind, name)


def test_put_dedupe_refreshes_blob_age(tmp_path):
    """A deduplicated put must re-arm the GC grace window: the new
    publication's ref has not landed yet, and an untouched mtime would
    let a concurrent sweep strand it dangling."""
    store = ArtifactStore(str(tmp_path / "store"))
    digest = store.put(b"shared artifact")
    os.utime(store.blob_path(digest), (1.0, 1.0))  # ancient
    assert store.put(b"shared artifact") == digest
    assert os.path.getmtime(store.blob_path(digest)) > 1.0
    report = collect(store, grace_secs=3600.0)
    assert digest not in report.removed


def test_fsck_repair_prunes_dangling_recreatable_refs(tmp_path):
    """Pure-cache refs (serialized executables) whose blob is gone are
    PRUNED by repair, not reported dangling forever — the consumer
    republishes on its next miss."""
    store = ArtifactStore(str(tmp_path / "store"))
    store.put_ref(
        "aot",
        keys.ref_name("d" * 64),
        {"executable": keys.sha256_hex(b"lost forever")},
        meta={"recreatable": True},
    )
    verify_only = fsck_store(store)
    assert verify_only["dangling_refs"] and not verify_only["clean"]
    repaired = fsck_store(store, repair=True)
    assert repaired["pruned_refs"] == ["aot/" + keys.ref_name("d" * 64)]
    assert repaired["dangling_refs"] == [] and repaired["clean"]
    assert store.get_ref("aot", keys.ref_name("d" * 64)) is None


# --------------------------------------------- mocked-clock leases and GC


def test_gc_grace_period_boundary(tmp_path):
    """An unreferenced blob survives while age < grace and is collected
    the moment age reaches it — no sleeps, injected clock."""
    now = [1000.0]
    store = ArtifactStore(str(tmp_path / "store"), clock=lambda: now[0])
    digest = store.put(b"unreferenced")
    os.utime(store.blob_path(digest), (900.0, 900.0))  # age = now - 900
    report = collect(store, grace_secs=101.0)  # age 100 < 101
    assert digest not in report.removed and report.in_grace == 1
    report = collect(store, grace_secs=100.0)  # age 100 >= 100
    assert digest in report.removed
    assert not store.has_blob(digest)


def test_gc_lease_expiry_boundary(tmp_path):
    """A lease pins exactly while now < expires_at; the lease file is
    pruned only one grace period after expiry."""
    now = [1000.0]
    store = ArtifactStore(str(tmp_path / "store"), clock=lambda: now[0])
    digest = store.put(b"pinned")
    os.utime(store.blob_path(digest), (0.0, 0.0))  # ancient: only the
    # lease protects it
    lease = leases.acquire(
        store, "search", ttl_secs=100.0, digests=[digest], lease_id="L1"
    )
    assert lease.expires_at == 1100.0
    report = collect(store, grace_secs=10.0)
    assert report.pinned == 1 and digest not in report.removed

    now[0] = 1099.9  # still live
    report = collect(store, grace_secs=10.0)
    assert digest not in report.removed and not report.pruned_leases

    now[0] = 1100.0  # expired exactly now: pin gone, file not yet pruned
    report = collect(store, grace_secs=10.0)
    assert digest in report.removed
    assert not report.pruned_leases  # 1100 + 10 > 1100

    now[0] = 1110.0  # expiry + grace reached: the lease file goes too
    report = collect(store, grace_secs=10.0)
    assert "L1" in report.pruned_leases
    assert not leases.iter_leases(store)


def test_gc_dry_run_removes_nothing_and_reports(tmp_path):
    store = ArtifactStore(str(tmp_path / "store"))
    digest = store.put(b"old and unreferenced")
    os.utime(store.blob_path(digest), (0.0, 0.0))
    report = collect(store, grace_secs=0.0, dry_run=True)
    assert report.dry_run and digest in report.would_remove
    assert not report.removed and store.has_blob(digest)


def test_gc_referenced_blob_never_removed(tmp_path):
    store = ArtifactStore(str(tmp_path / "store"))
    digest = store.put(b"referenced forever")
    os.utime(store.blob_path(digest), (0.0, 0.0))
    store.put_ref("frozen", keys.ref_name("f" * 64), {"payload": digest})
    report = collect(store, grace_secs=0.0)
    assert report.referenced == 1 and digest not in report.removed
    assert store.has_blob(digest)


def test_gc_racing_active_lease_never_evicts(tmp_path):
    """ISSUE acceptance: GC racing an active lease never deletes a
    reachable blob — a collector hammers the store while a reader holds
    a live lease and keeps fetching."""
    store = ArtifactStore(str(tmp_path / "store"))
    digest = store.put(b"live serving payload")
    os.utime(store.blob_path(digest), (0.0, 0.0))  # far past any grace
    lease = leases.acquire(
        store, "serving-pool", ttl_secs=300.0, digests=[digest]
    )
    wrongly_removed = []

    def collector():
        for _ in range(50):
            report = collect(store, grace_secs=0.0)
            if digest in report.removed:
                wrongly_removed.append(report)

    thread = threading.Thread(target=collector)
    thread.start()
    try:
        for _ in range(50):
            assert store.get(digest) == b"live serving payload"
    finally:
        thread.join(60.0)
    assert not wrongly_removed
    # Released + past grace, the same blob is finally collectable.
    leases.release(store, lease)
    report = collect(store, grace_secs=0.0)
    assert digest in report.removed


# ----------------------------------------------------------- fault sites


def test_store_put_transient_retried(tmp_path):
    store = ArtifactStore(str(tmp_path / "store"))
    spec = faults.arm("store.put", "transient", after=0, count=1)
    digest = store.put(b"retried payload")
    assert spec.trips == 1
    assert store.get(digest) == b"retried payload"


def test_store_get_rot_quarantines_and_heals(tmp_path):
    store = ArtifactStore(str(tmp_path / "store"))
    source = tmp_path / "dup.bin"
    source.write_bytes(b"rot me")
    digest = store.put(b"rot me")
    store.put_ref(
        "frozen", keys.ref_name(digest[:16]), {"payload": digest},
        sources=[str(source)],
    )
    faults.arm("store.get", "rot", after=0, count=1)
    assert store.get(digest) == b"rot me"  # rotted, caught, healed
    assert store.quarantined_blobs()


def test_store_gc_error_surfaces(tmp_path):
    store = ArtifactStore(str(tmp_path / "store"))
    faults.arm("store.gc", "error", after=0, count=1)
    with pytest.raises(faults.InjectedFault):
        collect(store, grace_secs=0.0)


# ------------------------------------------------------------ store fsck


def test_fsck_store_reports_dangling_and_would_gc(tmp_path):
    store = ArtifactStore(str(tmp_path / "store"))
    kept = store.put(b"kept")
    store.put_ref("frozen", keys.ref_name("a" * 64), {"payload": kept})
    dangling = keys.sha256_hex(b"gone")
    store.put_ref("frozen", keys.ref_name("b" * 64), {"payload": dangling})
    orphan = store.put(b"orphan blob")
    os.utime(store.blob_path(orphan), (0.0, 0.0))
    report = fsck_store(store, gc_dry_run=True)
    assert not report["clean"]
    assert any(dangling in entry for entry in report["dangling_refs"])
    assert report["blob_count"] == 2 and report["ref_count"] == 2
    assert report["would_gc"] == [orphan]
    assert report["bytes"] > 0


def test_fsck_store_repair_heals_rot(tmp_path):
    store = ArtifactStore(str(tmp_path / "store"))
    source = tmp_path / "dup.bin"
    source.write_bytes(b"heal via fsck")
    digest = store.put(b"heal via fsck")
    store.put_ref(
        "frozen", keys.ref_name(digest[:16]), {"payload": digest},
        sources=[str(source)],
    )
    with open(store.blob_path(digest), "r+b") as f:
        f.write(b"\x00\x00\x00")
    verify_only = fsck_store(store)
    assert verify_only["corrupt_blobs"] == [digest]
    assert not verify_only["clean"]
    repaired = fsck_store(store, repair=True)
    assert repaired["healed_blobs"] == [digest]
    assert repaired["clean"] and repaired["quarantined_blobs"]
    assert store.get(digest) == b"heal via fsck"


def test_ckpt_fsck_cli_store_section(tmp_path, capsys):
    """`ckpt_fsck --json --store ... --gc --dry-run` carries the store
    section without perturbing the checkpoint-chain exit code."""
    from tools import ckpt_fsck

    store = ArtifactStore(str(tmp_path / "store"))
    digest = store.put(b"blob")
    store.put_ref("frozen", keys.ref_name("c" * 64), {"payload": digest})
    model_dir = str(tmp_path / "model")
    os.makedirs(model_dir)
    rc = ckpt_fsck.main(
        [
            model_dir,
            "--json",
            "--store",
            str(tmp_path / "store"),
            "--gc",
            "--dry-run",
        ]
    )
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    section = report["store"]
    assert section["clean"] is True
    assert section["blob_count"] == 1 and section["ref_count"] == 1
    assert section["would_gc"] == []  # fresh blobs sit in the grace window


# ----------------------------------------------- manifest v3 read compat


def test_manifest_v2_read_compat(tmp_path):
    """A v2 manifest (no version/store_refs fields) parses cleanly and
    upgrades to v3 on its next write."""
    model_dir = str(tmp_path / "model")
    os.makedirs(model_dir)
    v2 = {
        "iteration_number": 2,
        "global_step": 12,
        "iteration_state_file": None,
        "replay_indices": [0, 1],
        "generation": 5,
        "digests": {},
        "history": [
            {"iteration_number": 0, "global_step": 6, "generation": 2},
            {"iteration_number": 1, "global_step": 12, "generation": 4},
        ],
    }
    v2["checksum"] = ckpt_lib.sha256_hex(
        json.dumps(v2, sort_keys=True).encode()
    )
    with open(os.path.join(model_dir, ckpt_lib.MANIFEST), "w") as f:
        json.dump(v2, f, sort_keys=True)
    info = ckpt_lib.read_manifest(model_dir)
    assert info.version == 2 and info.store_refs == {}
    assert info.iteration_number == 2 and info.replay_indices == [0, 1]

    info.store_refs["frozen-0.msgpack"] = "a" * 64
    ckpt_lib.write_manifest(model_dir, info)
    reread = ckpt_lib.read_manifest(model_dir)
    assert reread.version == 3
    assert reread.store_refs == {"frozen-0.msgpack": "a" * 64}


# ------------------------------------------------------- replay round trip


def test_replay_config_save_load_round_trip(tmp_path):
    config = replay_lib.Config(
        best_ensemble_indices=[0, 1, 1],
        architecture_hashes=["a" * 64, "b" * 64, "c" * 64],
    )
    path = str(tmp_path / "replay.json")
    config.save(path)
    loaded = replay_lib.Config.load(path)
    assert loaded.to_json() == config.to_json()
    assert loaded.get_best_ensemble_index(2) == 1
    assert loaded.get_best_ensemble_index(3) is None
    assert loaded.get_architecture_hash(1) == "b" * 64
    assert loaded.get_architecture_hash(7) is None
    # Hand-constructed configs (no hashes) still work everywhere.
    bare = replay_lib.Config(best_ensemble_indices=[1])
    assert bare.get_architecture_hash(0) is None
    assert replay_lib.Config.from_json(bare.to_json()).to_json() == (
        bare.to_json()
    )


# ------------------------------------------- persistent compile-cache tier


def test_compile_cache_persistent_tier_across_instances(tmp_path):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from adanet_tpu.core.compile_cache import CachedStep, CompileCache

    store = ArtifactStore(str(tmp_path / "store"))
    x = jnp.arange(8, dtype=jnp.float32)

    first = CompileCache(store=store)
    out = CachedStep(lambda v: v * 3 + 1, first)(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8) * 3 + 1)
    assert (first.misses, first.store_misses, first.store_hits) == (1, 1, 0)

    # A "separate run": fresh cache instance, same store — the XLA
    # compile is skipped entirely.
    second = CompileCache(store=store)
    out = CachedStep(lambda v: v * 3 + 1, second)(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8) * 3 + 1)
    assert (second.misses, second.store_hits) == (0, 1)
    assert second.store_errors == 0


def test_stored_executable_loads_onto_the_device_it_was_compiled_for(
    tmp_path,
):
    """A stored executable compiled for ONE of the eight devices (not
    device 0) loads onto that device and runs there, whatever the
    backend's device count."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from adanet_tpu.core.compile_cache import CachedStep, CompileCache

    device = jax.devices()[5]
    store = ArtifactStore(str(tmp_path / "store"))
    x = jax.device_put(jnp.arange(8, dtype=jnp.float32), device)

    CachedStep(lambda v: v * 2 - 1, CompileCache(store=store))(x)

    fresh = CompileCache(store=store)
    out = CachedStep(lambda v: v * 2 - 1, fresh)(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8) * 2 - 1)
    assert out.devices() == {device}
    assert (fresh.misses, fresh.store_hits, fresh.store_errors) == (0, 1, 0)


# ---------------------------------------------- serving closure publication


def test_publisher_ref_closure_set_once_and_pool_lease(tmp_path):
    from adanet_tpu.serving import publisher
    from adanet_tpu.serving.model_pool import GenerationRecord, ModelPool

    store = ArtifactStore(str(tmp_path / "store"))
    model_dir = str(tmp_path / "model")
    gen_dir = publisher.generation_dir(model_dir, 0)
    os.makedirs(gen_dir)
    with open(os.path.join(gen_dir, "serving.stablehlo"), "wb") as f:
        f.write(b"fake program bytes")
    with open(os.path.join(gen_dir, "serving_signature.json"), "w") as f:
        json.dump({"inputs": []}, f)
    publisher.write_generation_manifest(gen_dir, 0)

    ref = publisher.publish_ref_closure(store, model_dir, 0)
    assert set(ref["blobs"]) == {
        "generation.json",
        "serving.stablehlo",
        "serving_signature.json",
    }
    for digest in ref["blobs"].values():
        assert store.has_blob(digest)
    # Set-once: a second publication adopts the landed closure.
    assert publisher.publish_ref_closure(store, model_dir, 0) is None

    # The pool pins the promoted generation's closure under a lease.
    pool = ModelPool(model_dir, store=store)
    record = GenerationRecord(
        iteration_number=0,
        path=gen_dir,
        program=lambda features: features,
        signature={},
    )
    pool._pin_store_closure(record)
    live = leases.live_leases(store)
    assert len(live) == 1
    assert set(live[0].digests) == set(ref["blobs"].values())
    # GC with the lease live keeps every closure blob, however old.
    for digest in ref["blobs"].values():
        os.utime(store.blob_path(digest), (0.0, 0.0))
    report = collect(store, grace_secs=0.0)
    assert not report.removed
    pool.release_store_lease()
    assert not leases.live_leases(store)


# --------------------------------------------------------- warm-start gate


@pytest.fixture(scope="module")
def oracle_dir(tmp_path_factory):
    """An uninterrupted, store-less run of the shared chaos config."""
    d = str(tmp_path_factory.mktemp("oracle") / "model")
    est = build_estimator(d)
    est.train(input_fn, max_steps=100)
    assert est.latest_iteration_number() == 2
    return d


def test_warm_start_replay_zero_compiles_zero_retraining(
    oracle_dir, tmp_path
):
    """ISSUE acceptance (warm-start gate): a second search run sharing
    the store replays the first run's architecture with zero XLA
    compiles and zero retraining of unchanged frozen members."""
    store_root = str(tmp_path / "store")
    first_dir = str(tmp_path / "first")
    est1 = build_estimator(first_dir, artifact_store=store_root)
    est1.train(input_fn, max_steps=100)
    assert est1.latest_iteration_number() == 2
    # The store changes nothing about the search itself.
    assert _arch(first_dir, 1) == _arch(oracle_dir, 1)
    # Search end emitted the replay record.
    replay_path = os.path.join(first_dir, replay_lib.REPLAY_FILENAME)
    assert os.path.exists(replay_path)
    config = replay_lib.Config.load(replay_path)
    assert config.num_iterations == 2
    assert len(config.architecture_hashes) == 2

    streams_opened = [0]

    def counting_input_fn():
        streams_opened[0] += 1
        return input_fn()

    second_dir = str(tmp_path / "second")
    est2 = build_estimator(
        second_dir, artifact_store=store_root, replay_config=config
    )
    est2.train(counting_input_fn, max_steps=100)

    # Zero retraining: not one batch was pulled; zero compiles: the
    # compile cache never missed (in-memory or persistent).
    assert streams_opened[0] == 0
    cache = est2._compile_cache
    assert cache.misses == 0 and cache.store_misses == 0
    assert est2.latest_iteration_number() == 2
    assert est2.latest_global_step() == est1.latest_global_step()
    assert _arch(second_dir, 0) == _arch(oracle_dir, 0)
    assert _arch(second_dir, 1) == _arch(oracle_dir, 1)
    # The replayed payloads are byte-identical store grafts.
    info = ckpt_lib.read_manifest(second_dir)
    assert set(info.store_refs) == {
        "frozen-0.msgpack",
        "frozen-1.msgpack",
    }
    # And the store survives a full audit.
    report = fsck_store(ArtifactStore(store_root), gc_dry_run=True)
    assert report["clean"] and report["would_gc"] == []


def test_warm_start_of_reselected_winner_is_not_aliased(tmp_path):
    """A re-selected (non-grown) winner has the SAME structural hash as
    its previous iteration; the store ref key must still distinguish
    the two (found by end-to-end verification: structure-only keys
    grafted iteration 0's state in place of iteration 1's)."""
    store_root = str(tmp_path / "store")
    first_dir = str(tmp_path / "first")
    est1 = build_estimator(
        first_dir,
        artifact_store=store_root,
        # Index 0 at t=1 = the carried-over previous ensemble: same
        # structure as iteration 0's winner, different numeric state.
        replay_config=replay_lib.Config(best_ensemble_indices=[1, 0]),
    )
    est1.train(input_fn, max_steps=100)
    assert est1.latest_iteration_number() == 2
    a0, a1 = _arch(first_dir, 0), _arch(first_dir, 1)
    assert a0["subnetworks"] == a1["subnetworks"]  # re-selected
    # Two DISTINCT refs despite the identical structural hash.
    store = ArtifactStore(store_root)
    assert len(list(store.iter_refs("frozen"))) == 2

    config = replay_lib.Config.from_model_dir(first_dir)
    second_dir = str(tmp_path / "second")
    est2 = build_estimator(
        second_dir, artifact_store=store_root, replay_config=config
    )
    est2.train(input_fn, max_steps=100)
    assert est2._compile_cache.misses == 0
    assert est2.latest_global_step() == est1.latest_global_step()
    assert _arch(second_dir, 0) == a0
    assert _arch(second_dir, 1) == a1  # t=1's own state, not t=0's


# -------------------------------------------------------------- chaos gate


def test_store_chaos_two_searches_torn_rot_sigkill(oracle_dir, tmp_path):
    """ISSUE acceptance (chaos gate): two concurrent searches over one
    store with armed `store.put` torn+rot faults and a SIGKILL
    mid-publish both reach oracle-identical final architectures, and
    `ckpt_fsck --json` reports the store clean (healed quarantine
    allowed, verdict <= 1)."""
    store_root = str(tmp_path / "store")
    dir_a = str(tmp_path / "search_a")
    dir_b = str(tmp_path / "search_b")
    runner = os.path.join(TESTS_DIR, "store_chaos_runner.py")

    def spawn(name, model_dir, faults_spec):
        # Output to a file (nobody reads a pipe while the two run) and a
        # compile cache of the child's own: what a child publishes into
        # the shared STORE is then an executable it compiled itself. One
        # it had loaded from a warm persistent cache serializes into a
        # blob that fails at dispatch in the sibling that deserializes
        # it ("Function ... not found"), whichever of the two got there
        # second.
        env = _subprocess_env()
        env["ADANET_FAULTS"] = faults_spec
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / (name + "_cache"))
        with open(tmp_path / (name + ".log"), "wb") as log:
            return subprocess.Popen(
                [sys.executable, runner, model_dir, store_root],
                env=env,
                stdout=log,
                stderr=subprocess.STDOUT,
            )

    def output(name):
        return (tmp_path / (name + ".log")).read_bytes()

    # A: the 5th blob publication (serving gen-0's program, mid-closure
    # publish) is torn at its final content-addressed path + SIGKILL.
    # B: the 8th (iteration 1's frozen payload) silently bit-rots; B
    # runs to completion on the corrupted store none the wiser.
    proc_a = spawn("a", dir_a, "store.put:torn:after=4")
    proc_b = spawn("b", dir_b, "store.put:rot:after=7")
    try:
        proc_a.wait(timeout=300)
        proc_b.wait(timeout=300)
    finally:
        for proc in (proc_a, proc_b):
            if proc.poll() is None:
                proc.kill()
    out_a, out_b = output("a"), output("b")
    assert proc_a.returncode == -signal.SIGKILL, out_a.decode()[-2000:]
    assert b"DONE" not in out_a
    assert proc_b.returncode == 0, out_b.decode()[-2000:]
    assert b"DONE" in out_b

    # Resume A with no faults — in-process (no fault arming needed, and
    # a third cold jax subprocess would waste tier-1 budget): the
    # startup reconcile heals the torn blob from A's intact generation
    # dir and the search completes.
    est = build_estimator(
        dir_a, artifact_store=store_root, export_serving=True
    )
    est.train(input_fn, max_steps=100)
    assert est.latest_iteration_number() == 2

    # Oracle-identical final architectures on both searches.
    for t in (0, 1):
        assert _arch(dir_a, t) == _arch(oracle_dir, t)
        assert _arch(dir_b, t) == _arch(oracle_dir, t)

    # The full CLI audit: checkpoint chains verdict <= 1, store clean
    # (quarantined copies of the healed torn/rot blobs are allowed).
    from tools import ckpt_fsck

    for model_dir in (dir_a, dir_b):
        import io
        from contextlib import redirect_stdout

        buf = io.StringIO()
        with redirect_stdout(buf):
            rc = ckpt_fsck.main(
                [
                    model_dir,
                    "--json",
                    "--repair",
                    "--store",
                    store_root,
                    "--gc",
                    "--dry-run",
                ]
            )
        assert rc <= 1, buf.getvalue()
        report = json.loads(buf.getvalue())
        section = report["store"]
        assert section["clean"] is True, section
        assert section["dangling_refs"] == [], section
        assert section["would_gc"] == [], section
    # The chaos left quarantined copies behind — proof the heals were
    # real, not vacuous.
    assert ArtifactStore(store_root).quarantined_blobs()
