"""The sharded form of a saved state (`core/checkpoint.py`, "sharded
states"): what a state of gigabytes is written as, here with the
threshold lowered through `save_pytree`'s test-only argument."""

import hashlib
import os
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adanet_tpu.core import checkpoint as ckpt
from adanet_tpu.robustness import faults, integrity
from benchmarks import ckpt_shards
from tools import ckpt_fsck

NAME = "ckpt-7.msgpack"


def state_of(scale=1.0):
    return {
        "subnetworks": {
            "a": {
                "w": jnp.arange(1 << 18, dtype=jnp.float32).reshape(512, 512)
                * scale,
                "half": jnp.ones((300, 7), jnp.bfloat16) * scale,
                "opt": (),
                "step": jnp.asarray(3, jnp.int32),
            }
        },
        "moments": (jnp.full((256, 256), 0.5 * scale), {"k": jnp.ones((5,))}),
        "rng": jnp.asarray([1, 2], jnp.uint32),
        "dead": jnp.asarray(False),
    }


def template_of(state):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state
    )


def save(model_dir, state, name=NAME, step=7):
    digest = ckpt.save_pytree(
        model_dir, name, state, shard_threshold_bytes=1000
    )
    info = ckpt.read_manifest(model_dir) or ckpt.CheckpointInfo()
    info.global_step, info.iteration_state_file = step, name
    info.digests[name] = digest
    ckpt.write_manifest(model_dir, info)
    return digest


def same(a, b):
    return all(
        np.asarray(x).dtype == np.asarray(y).dtype
        and np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        )
    )


@pytest.fixture(autouse=True)
def small_shard_files(monkeypatch):
    # Several leaves a file and several files, at this toy size.
    monkeypatch.setattr(ckpt, "SHARD_FILE_BYTES", 300_000)
    yield
    faults.disarm()


def test_sharded_state_round_trips_bit_for_bit(tmp_path):
    model_dir, state = str(tmp_path), state_of()
    digest = save(model_dir, state)
    with open(os.path.join(model_dir, NAME), "rb") as f:
        data = f.read()
    assert data.startswith(ckpt.SHARD_MAGIC)
    assert hashlib.sha256(data).hexdigest() == digest
    assert ckpt.read_digest(model_dir, NAME) == digest
    (shards,) = ckpt.shard_directories(model_dir, NAME)
    assert len(os.listdir(os.path.join(model_dir, shards))) > 1
    restored = ckpt.restore_pytree(model_dir, NAME, template_of(state))
    assert jax.tree_util.tree_structure(restored) == (
        jax.tree_util.tree_structure(state)
    )
    assert same(restored, state)
    assert ckpt.verify_file(model_dir, NAME) is True
    stats = ckpt.shard_stats(model_dir, NAME)
    assert stats["leaves"] == 7 and stats["threads"] >= 1
    assert stats["bytes"] == sum(
        x.nbytes for x in jax.tree_util.tree_leaves(state)
    )
    assert integrity.fsck(model_dir, repair=False).verdict == "clean"


def test_a_flipped_byte_in_one_shard_is_named_and_quarantined(tmp_path):
    model_dir, state = str(tmp_path), state_of()
    save(model_dir, state)
    (shards,) = ckpt.shard_directories(model_dir, NAME)
    victim = os.path.join(model_dir, shards, "00001.bin")
    with open(victim, "r+b") as f:
        f.seek(100)
        byte = f.read(1)
        f.seek(100)
        f.write(bytes([byte[0] ^ 1]))
    with pytest.raises(ckpt.CheckpointCorruptionError) as caught:
        ckpt.restore_pytree(model_dir, NAME, template_of(state))
    assert caught.value.path == victim
    assert "SHA-256 mismatch in leaf" in caught.value.reason
    # The index and the layout are whole: the rot is in a leaf, which its
    # readers find, and the operator's fsck, which reads every leaf.
    assert ckpt.verify_file(model_dir, NAME) is True
    (message,) = ckpt.corrupt_shards(model_dir, NAME)
    assert victim in message
    assert integrity.rotted_sharded_states(model_dir) == [NAME]
    assert ckpt_fsck.main([model_dir, "--json"]) == integrity.EXIT_UNRECOVERABLE
    assert ckpt_fsck.main(
        [model_dir, "--repair", "--json"]
    ) == integrity.EXIT_UNRECOVERABLE
    report = integrity.fsck(model_dir)
    assert report.info.iteration_state_file is None
    assert NAME + ckpt.QUARANTINE_SUFFIX in os.listdir(model_dir)
    left = sorted(os.listdir(model_dir))
    assert NAME not in left and shards not in left
    assert shards + ckpt.QUARANTINE_SUFFIX in left  # kept, diagnosable


def test_a_shard_cut_short_is_corruption_too(tmp_path):
    model_dir, state = str(tmp_path), state_of()
    save(model_dir, state)
    (shards,) = ckpt.shard_directories(model_dir, NAME)
    victim = os.path.join(model_dir, shards, "00000.bin")
    os.truncate(victim, os.path.getsize(victim) - 10)
    with pytest.raises(ckpt.CheckpointCorruptionError) as caught:
        ckpt.restore_pytree(model_dir, NAME, template_of(state))
    assert caught.value.path == victim


@pytest.mark.parametrize("mode", ["error", "torn"])
def test_a_fault_between_shards_and_publish_keeps_the_previous(
    tmp_path, monkeypatch, mode
):
    """`faults.trip("checkpoint.write")` sits after the shards and before
    the index's rename: the previous generation stays what a reader
    finds, and fsck clears what the failed save left."""
    model_dir, first = str(tmp_path), state_of()
    save(model_dir, first, "ckpt-5.msgpack", step=5)
    later = "ckpt-9.msgpack"
    if mode == "torn":
        # A kill: no handler of the saving process runs. The torn index
        # lands at its final path and the shards stay.
        monkeypatch.setattr(
            "shutil.rmtree", lambda *a, **k: None, raising=True
        )
        monkeypatch.setattr(
            faults.os, "kill",
            lambda *a: (_ for _ in ()).throw(faults.InjectedFault("killed")),
        )
    faults.arm("checkpoint.write", mode, frac=0.5)
    try:
        with pytest.raises(faults.InjectedFault):
            ckpt.save_pytree(
                model_dir, later, state_of(2.0), shard_threshold_bytes=1000
            )
    finally:
        faults.disarm("checkpoint.write")
        monkeypatch.undo()
    info = ckpt.read_manifest(model_dir)
    assert info.iteration_state_file == "ckpt-5.msgpack"
    restored = ckpt.restore_pytree(
        model_dir, info.iteration_state_file, template_of(first)
    )
    assert same(restored, first)
    report = integrity.fsck(model_dir, repair=True)
    assert report.info.iteration_state_file == "ckpt-5.msgpack"
    assert report.info.global_step == 5
    assert not ckpt.shard_directories(model_dir, later)
    assert integrity.fsck(model_dir, repair=False).verdict == "clean"
    assert same(
        ckpt.restore_pytree(
            model_dir, "ckpt-5.msgpack", template_of(first)
        ), first,
    )


def test_a_state_under_the_threshold_is_the_parents_file(tmp_path):
    """Below 1 GiB the bytes on disk are what they were before sharding
    existed: the digest of this fixed state is pinned from the parent
    commit's `save_pytree`."""
    model_dir = str(tmp_path)
    state = {
        "a": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4)},
        "n": jnp.asarray(3, jnp.int32),
        "opt": (),
    }
    digest = ckpt.save_pytree(model_dir, NAME, state)
    assert not ckpt.shard_directories(model_dir, NAME)
    with open(os.path.join(model_dir, NAME), "rb") as f:
        data = f.read()
    assert not data.startswith(ckpt.SHARD_MAGIC)
    assert hashlib.sha256(data).hexdigest() == digest
    assert digest == PINNED_DIGEST
    assert ckpt.SHARD_THRESHOLD_BYTES == 1 << 30


PINNED_DIGEST = (
    "b11fa33c9f0f16cd8db3c76fae520201351ddae7264f76f62bcfa17d49de2570"
)


def test_peak_host_memory_of_a_sharded_save_is_a_few_leaves(tmp_path):
    """Leaf by leaf: the save holds a bounded number of leaves on the
    host, not copies of the state (the one-file form holds the fetched
    state and its serialization at once)."""
    leaves = {
        "leaf_%02d" % i: jnp.full((1 << 20,), float(i), jnp.float32)
        for i in range(16)
    }
    total = sum(x.nbytes for x in leaves.values())
    largest = max(x.nbytes for x in leaves.values())
    jax.block_until_ready(leaves)

    def peak_of(threshold, where):
        tracemalloc.start()
        try:
            ckpt.save_pytree(
                str(where), NAME, leaves, shard_threshold_bytes=threshold
            )
            return tracemalloc.get_traced_memory()[1]
        finally:
            tracemalloc.stop()

    sharded = peak_of(1000, tmp_path / "sharded")
    whole = peak_of(None, tmp_path / "whole")
    assert sharded < 4 * largest + (1 << 20), (sharded, largest)
    assert sharded < total + 4 * largest
    assert whole > total, (whole, total)


def test_the_benchmark_reads_and_plants_the_sharded_form(tmp_path):
    model_dir, state = str(tmp_path), state_of()
    save(model_dir, state)
    wanted = ckpt_shards.read_leaves(
        model_dir, lambda path: path.endswith("/w") or path == "rng"
    )
    assert sorted(wanted) == ["rng", "subnetworks/a/w"]
    assert wanted["subnetworks/a/w"].tobytes() == np.asarray(
        state["subnetworks"]["a"]["w"]
    ).tobytes()
    planted = np.full((512, 512), 0.25, np.float32)
    ckpt_shards.write_leaves(
        model_dir, {"subnetworks/a/w": planted,
                    "rng": np.asarray([9, 9], np.uint32)},
    )
    assert integrity.fsck(model_dir, repair=False).verdict == "clean"
    restored = ckpt.restore_pytree(model_dir, NAME, template_of(state))
    np.testing.assert_array_equal(restored["subnetworks"]["a"]["w"], planted)
    np.testing.assert_array_equal(restored["rng"], [9, 9])
    assert same(restored["moments"], state["moments"])
    assert len(ckpt.shard_directories(model_dir, NAME)) == 1


def test_the_benchmark_reads_the_one_file_form_through_the_same_calls(
    tmp_path,
):
    model_dir, state = str(tmp_path), state_of()
    digest = ckpt.save_pytree(model_dir, NAME, state)
    info = ckpt.CheckpointInfo(
        global_step=7, iteration_state_file=NAME, digests={NAME: digest}
    )
    ckpt.write_manifest(model_dir, info)
    assert ckpt_shards.read_index(model_dir) is None
    got = ckpt_shards.read_leaves(model_dir, lambda path: path == "rng")
    np.testing.assert_array_equal(got["rng"], [1, 2])
    ckpt_shards.write_leaves(model_dir, {"rng": np.asarray([4, 5], np.uint32)})
    restored = ckpt.restore_pytree(model_dir, NAME, template_of(state))
    np.testing.assert_array_equal(restored["rng"], [4, 5])


def test_a_resume_hashes_a_sharded_state_once(tmp_path):
    """`fsck` leaves a sharded state's leaves to the restore that follows
    it (which verifies every one, and whose failure quarantines and rolls
    back as fsck's would), but still sees a shard that is gone or cut
    short; the operator's pass (`rotted_sharded_states`) reads every
    leaf and condemns the state."""
    model_dir, state = str(tmp_path), state_of()
    save(model_dir, state)
    (shards,) = ckpt.shard_directories(model_dir, NAME)
    victim = os.path.join(model_dir, shards, "00001.bin")
    with open(victim, "r+b") as f:
        f.seek(100)
        byte = f.read(1)
        f.seek(100)
        f.write(bytes([byte[0] ^ 1]))
    assert integrity.fsck(model_dir, repair=False).verdict == "clean"
    assert integrity.fsck(
        model_dir, repair=False,
        condemned=integrity.rotted_sharded_states(model_dir),
    ).verdict != "clean"
    with pytest.raises(ckpt.CheckpointCorruptionError):
        ckpt.restore_pytree(model_dir, NAME, template_of(state))
    os.truncate(victim, os.path.getsize(victim) - 1)
    assert integrity.fsck(model_dir, repair=False).verdict != "clean"


def test_a_rotted_shard_at_resume_rolls_the_search_back(
    tmp_path, monkeypatch
):
    """Through `Estimator.train`: the state is saved sharded, a shard
    rots, and the next call's restore (not its fsck, which leaves the
    leaves to it) names the shard, quarantines the state with its shards
    and restarts the iteration from its first step."""
    import flax.linen as nn
    import optax

    import adanet_tpu

    monkeypatch.setattr(ckpt, "SHARD_THRESHOLD_BYTES", 100)

    class Builder(adanet_tpu.Builder):
        name = "dense"

        def build_subnetwork(self, logits_dimension, previous_ensemble=None):
            class Module(nn.Module):
                @nn.compact
                def __call__(self, features, training=False):
                    hidden = nn.relu(nn.Dense(8)(features["x"]))
                    return adanet_tpu.Subnetwork(
                        last_layer=hidden,
                        logits=nn.Dense(logits_dimension)(hidden),
                        complexity=1.0,
                    )

            return Module()

        def build_train_optimizer(self, previous_ensemble=None):
            return optax.sgd(0.1)

    rng = np.random.default_rng(0)
    batch = ({"x": rng.standard_normal((8, 4)).astype(np.float32)},
             rng.standard_normal((8, 1)).astype(np.float32))

    def estimator():
        return adanet_tpu.Estimator(
            head=adanet_tpu.RegressionHead(),
            subnetwork_generator=adanet_tpu.SimpleGenerator([Builder()]),
            max_iteration_steps=50, model_dir=str(tmp_path),
            log_every_steps=0, export_serving=False,
        )

    def feed():
        while True:
            yield batch

    estimator().train(feed, max_steps=3)
    info = ckpt.read_manifest(str(tmp_path))
    assert info.global_step == 3
    (shards,) = ckpt.shard_directories(
        str(tmp_path), info.iteration_state_file
    )
    victim = os.path.join(str(tmp_path), shards, "00000.bin")
    with open(victim, "r+b") as f:
        byte = f.read(1)
        f.seek(0)
        f.write(bytes([byte[0] ^ 0x40]))
    estimator().train(feed, max_steps=5)
    left = sorted(os.listdir(str(tmp_path)))
    assert info.iteration_state_file + ckpt.QUARANTINE_SUFFIX in left
    assert shards + ckpt.QUARANTINE_SUFFIX in left
    # Rolled back to the iteration's first step, then trained five.
    assert ckpt.read_manifest(str(tmp_path)).global_step == 5
