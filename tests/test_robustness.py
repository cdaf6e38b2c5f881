"""Robustness suite: fault injection, self-healing checkpoints,
hang-proof multihost.

Proves the `adanet_tpu/robustness/` contract by doing, not inspecting:
checkpoints are torn/bit-flipped/truncated on disk and a writer is
SIGKILLed mid-write, then restore must quarantine (`*.corrupt`), roll
back to the newest intact generation, and reach the SAME final
architecture as an uninterrupted run; a multi-host peer dies
mid-iteration and the chief must raise `PeerLostError` within the
watchdog deadline, finish the iteration with the survivors, and stop
cleanly (no hang).
"""

import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from adanet_tpu.core import checkpoint as ckpt_lib
from adanet_tpu.robustness import faults, retry, watchdog
from adanet_tpu.robustness.integrity import fsck

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


# --------------------------------------------------------------- registry


def test_fault_registry_determinism():
    spec = faults.arm("data.pull", "error", after=2, count=2)
    faults.trip("data.pull")
    faults.trip("data.pull")
    for _ in range(2):
        with pytest.raises(faults.InjectedFault):
            faults.trip("data.pull")
    faults.trip("data.pull")  # count exhausted: clean again
    assert spec.hits == 5 and spec.trips == 2

    with pytest.raises(ValueError):
        faults.arm("no.such.site", "error")
    with pytest.raises(ValueError):
        faults.arm("data.pull", "no-such-mode")
    with pytest.raises(ValueError):
        faults.load_env("data.pull:error:bogus=1")

    assert faults.load_env("manifest.read:transient:after=1") == 1
    assert faults.armed()["manifest.read"].after == 1


def test_retry_bounded_and_deterministic():
    delays = []
    calls = [0]

    def flaky():
        calls[0] += 1
        if calls[0] < 4:
            raise faults.InjectedTransientError("hiccup")
        return "ok"

    assert (
        retry.with_retries(flaky, attempts=4, sleep=delays.append) == "ok"
    )
    assert delays == [0.05, 0.1, 0.2]  # exponential, no jitter

    # Non-transient errors are never absorbed.
    def broken():
        raise FileNotFoundError("gone")

    with pytest.raises(FileNotFoundError):
        retry.with_retries(broken, sleep=delays.append)

    # The bound is hard: a persistent transient error surfaces.
    with pytest.raises(faults.InjectedTransientError):
        retry.with_retries(
            lambda: (_ for _ in ()).throw(
                faults.InjectedTransientError("forever")
            ),
            attempts=2,
            sleep=lambda s: None,
        )
    assert not retry.is_transient(ckpt_lib.CheckpointCorruptionError("p", "r"))


def test_retry_backoff_schedule_caps_at_max_delay():
    """ISSUE 6 satellite: the full deterministic backoff schedule under a
    mocked sleep — exponential doubling capped at `max_delay`, identical
    on every run (no jitter), honoring a custom `retry_on`."""
    delays = []
    calls = [0]

    def always_flaky():
        calls[0] += 1
        raise faults.InjectedTransientError("hiccup %d" % calls[0])

    with pytest.raises(faults.InjectedTransientError):
        retry.with_retries(always_flaky, attempts=8, sleep=delays.append)
    # 7 sleeps between 8 attempts; the cap flattens the tail.
    assert delays == [0.05, 0.1, 0.2, 0.4, 0.8, 1.6, 2.0]
    assert calls[0] == 8

    # Custom schedule knobs are respected exactly.
    delays.clear()
    calls[0] = 0
    with pytest.raises(faults.InjectedTransientError):
        retry.with_retries(
            always_flaky,
            attempts=4,
            base_delay=1.0,
            multiplier=3.0,
            max_delay=5.0,
            sleep=delays.append,
        )
    assert delays == [1.0, 3.0, 5.0]

    # A custom retry_on can widen the transient set; the bound holds.
    delays.clear()
    with pytest.raises(KeyError):
        retry.with_retries(
            lambda: (_ for _ in ()).throw(KeyError("x")),
            attempts=3,
            retry_on=lambda exc: isinstance(exc, KeyError),
            sleep=delays.append,
        )
    assert len(delays) == 2

    with pytest.raises(ValueError):
        retry.with_retries(lambda: None, attempts=0)


def test_heartbeat_staleness_threshold_boundary(tmp_path, monkeypatch):
    """ISSUE 6 satellite: the staleness comparison under a mocked clock —
    a heartbeat EXACTLY at the threshold is still live (strict `>`), one
    tick past it declares the chief lost. No sleeps, no wall-clock
    flake: `watchdog.time` is a fake namespace and the beat file's mtime
    is set explicitly."""
    import types

    from adanet_tpu.distributed import coordination

    d = str(tmp_path)
    path = watchdog.heartbeat_path(d)
    with open(path, "w") as f:
        f.write("{}")

    now = [1_000_000.0]
    monkeypatch.setattr(
        watchdog,
        "time",
        types.SimpleNamespace(
            time=lambda: now[0], monotonic=time.monotonic
        ),
    )
    beat = now[0] - 30.0
    os.utime(path, (beat, beat))
    assert watchdog.heartbeat_age(d) == pytest.approx(30.0)

    # Age == threshold: NOT stale — the plain countdown runs out instead.
    with pytest.raises(coordination.WorkerWaitTimeout):
        coordination.wait_for_iteration(
            d,
            1,
            timeout_secs=0.15,
            poll_interval_secs=0.05,
            heartbeat_timeout_secs=30.0,
        )

    # One tick past the threshold: PeerLostError, immediately.
    now[0] += 0.5
    with pytest.raises(watchdog.PeerLostError) as err:
        coordination.wait_for_iteration(
            d,
            1,
            timeout_secs=60.0,
            poll_interval_secs=0.05,
            heartbeat_timeout_secs=30.0,
        )
    assert err.value.source_process == 0

    # A fresh beat (renewal) re-arms the threshold — the lease-renewal
    # analogue: heartbeats bound staleness, not total duration.
    now[0] += 1000.0
    os.utime(path, (now[0] - 1.0, now[0] - 1.0))
    with pytest.raises(coordination.WorkerWaitTimeout):
        coordination.wait_for_iteration(
            d,
            1,
            timeout_secs=0.15,
            poll_interval_secs=0.05,
            heartbeat_timeout_secs=30.0,
        )


def test_lease_renew_interval_tracks_ttl():
    """The scheduler's heartbeat period is TTL/3 with a 50ms floor, so a
    single missed beat never expires a live worker's lease."""
    from adanet_tpu.distributed import WorkQueueConfig

    assert WorkQueueConfig(lease_ttl_secs=15.0).renew_interval_secs == 5.0
    assert WorkQueueConfig(lease_ttl_secs=0.01).renew_interval_secs == 0.05


# ------------------------------------------------------------- checkpoints


def test_payload_digest_verify_and_quarantine(tmp_path):
    d = str(tmp_path)
    ckpt_lib.save_payload(d, "frozen-0.msgpack", {"w": np.arange(8.0)})
    assert os.path.exists(os.path.join(d, "frozen-0.msgpack.sha256"))
    assert ckpt_lib.verify_file(d, "frozen-0.msgpack") is True

    with open(os.path.join(d, "frozen-0.msgpack"), "r+b") as f:
        f.seek(3)
        f.write(b"\xff")  # single bit-rot-style flip
    assert ckpt_lib.verify_file(d, "frozen-0.msgpack") is False
    with pytest.raises(ckpt_lib.CheckpointCorruptionError):
        ckpt_lib.restore_payload(d, "frozen-0.msgpack")

    name = ckpt_lib.quarantine_file(d, "frozen-0.msgpack")
    assert name == "frozen-0.msgpack.corrupt"
    assert os.path.exists(os.path.join(d, name))
    assert not os.path.exists(os.path.join(d, "frozen-0.msgpack"))
    # The digest sidecar rides along for post-mortems.
    assert os.path.exists(os.path.join(d, name + ".sha256"))


def test_manifest_checksum_prev_fallback(tmp_path):
    d = str(tmp_path)
    info = ckpt_lib.CheckpointInfo(iteration_number=1, global_step=6)
    ckpt_lib.write_manifest(d, info)
    info.global_step = 12
    ckpt_lib.write_manifest(d, info)
    assert info.generation == 2

    # Bit-flipped manifest: checksum rejects it, .prev recovers.
    path = os.path.join(d, ckpt_lib.MANIFEST)
    with open(path) as f:
        raw = f.read()
    with open(path, "w") as f:
        f.write(raw.replace('"global_step": 12', '"global_step": 99'))
    got = ckpt_lib.read_manifest(d)
    assert got.global_step == 6  # the previous generation
    assert os.path.exists(path + ".corrupt")


def test_read_manifest_dry_run_does_not_quarantine(tmp_path):
    """fsck without --repair must report, never rename (the chief's
    repair pass owns the quarantine for every process)."""
    d = str(tmp_path)
    info = ckpt_lib.CheckpointInfo(iteration_number=0, global_step=6)
    ckpt_lib.write_manifest(d, info)
    info.global_step = 12
    ckpt_lib.write_manifest(d, info)
    path = os.path.join(d, ckpt_lib.MANIFEST)
    with open(path) as f:
        raw = f.read()
    with open(path, "w") as f:
        f.write(raw.replace('"global_step": 12', '"global_step": 99'))

    got = ckpt_lib.read_manifest(d, quarantine=False)
    assert got.global_step == 6  # .prev recovered it
    assert os.path.exists(path)  # ...without touching the corrupt main
    assert not os.path.exists(path + ".corrupt")

    report = fsck(d)  # report-only
    assert any("would quarantine" in issue for issue in report.issues)
    assert os.path.exists(path)
    assert not os.path.exists(path + ".corrupt")

    report = fsck(d, repair=True)
    assert os.path.exists(path + ".corrupt")  # repair quarantines...
    assert os.path.exists(path)  # ...and rewrites the recovered manifest
    assert ckpt_lib.read_manifest(d).global_step == 6


class _FakeKV:
    """In-memory stand-in for the jax coordination-service KV client."""

    def __init__(self):
        self.store = {}

    def key_value_set(self, key, value):
        self.store[key] = value

    key_value_set_bytes = key_value_set

    def key_value_delete(self, key):
        self.store.pop(key, None)

    def blocking_key_value_get(self, key, timeout_ms):
        return self.store[key]

    blocking_key_value_get_bytes = blocking_key_value_get


def test_kv_gc_byte_budget(monkeypatch):
    """Once retained broadcast bytes exceed the budget, GC tightens to
    the min lag instead of parking 64 blobs in the coordinator."""
    from adanet_tpu.distributed import multihost

    fake = _FakeKV()
    monkeypatch.setattr(multihost, "_kv_client", lambda: fake)
    monkeypatch.setattr(multihost, "_broadcast_seq", [0])
    monkeypatch.setattr(multihost, "_kv_keys_set", [])
    monkeypatch.setattr(multihost, "_kv_bytes_retained", [0])
    monkeypatch.setenv("ADANET_KV_GC_BYTES", "100")
    monkeypatch.setenv("ADANET_KV_GC_MIN_LAG", "2")

    payload = {"w": np.zeros(64, np.uint8)}  # 64-byte blob per call
    for _ in range(3):
        multihost._broadcast_tree(payload, is_source=True)
    # seq 0 aged past the tightened lag with the budget exceeded...
    assert "adanet/bcast/0/0" not in fake.store
    assert "adanet/bcast/0/n" not in fake.store
    # ...while everything within the min lag is retained.
    assert "adanet/bcast/1/0" in fake.store
    assert "adanet/bcast/2/0" in fake.store


def test_allgather_host_flag(monkeypatch):
    from adanet_tpu.distributed import multihost

    # Single process (no coordination service): the local value.
    assert multihost.allgather_host_flag(1).tolist() == [1]

    # Two processes over the KV store: every peer's value, in order.
    fake = _FakeKV()
    fake.store["adanet/flag/0/1"] = "1"  # the peer already published
    monkeypatch.setattr(multihost, "_kv_client", lambda: fake)
    monkeypatch.setattr(multihost, "_flag_seq", [0])
    monkeypatch.setattr(multihost.jax, "process_count", lambda: 2)
    monkeypatch.setattr(multihost.jax, "process_index", lambda: 0)
    assert multihost.allgather_host_flag(0).tolist() == [0, 1]


def test_fault_site_checkpoint_write_torn(tmp_path, monkeypatch):
    """`torn` mode leaves a truncated payload at the FINAL path and
    SIGKILLs — here the kill is stubbed to observe the torn bytes."""
    d = str(tmp_path)
    killed = []
    monkeypatch.setattr(os, "kill", lambda pid, sig: killed.append(sig))
    faults.arm("checkpoint.write", "torn", frac=0.25)
    with pytest.raises(faults.InjectedFault):
        ckpt_lib.save_payload(d, "ckpt-2.msgpack", {"w": np.arange(32.0)})
    assert killed == [signal.SIGKILL]
    torn = os.path.join(d, "ckpt-2.msgpack")
    assert os.path.exists(torn)
    # No digest sidecar (death before it was written) and undecodable.
    assert ckpt_lib.read_digest(d, "ckpt-2.msgpack") is None
    with pytest.raises(ckpt_lib.CheckpointCorruptionError):
        ckpt_lib.restore_payload(d, "ckpt-2.msgpack")


@pytest.mark.parametrize("abstract", [False, True])
def test_legacy_batch_stats_count_migration(tmp_path, abstract):
    """Pre-round-5 NASNet checkpoints lack the batch_stats `count` leaf;
    strict restore injects it as converged instead of failing
    (ADVICE r5). The injection reads the target's STRUCTURE: a target of
    `jax.ShapeDtypeStruct`s (a resume's template) guides it as well."""
    import flax.linen as nn
    import jax.numpy as jnp

    from adanet_tpu.models.nasnet import (
        _DebiasedBatchNorm,
        legacy_batch_stats_count,
    )

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, training: bool = False):
            return _DebiasedBatchNorm(name="bn")(x, training)

    x = jnp.ones((2, 3))
    variables = Tiny().init(jax.random.PRNGKey(0), x)
    legacy = jax.device_get(variables)
    # Simulate the legacy layout: no count leaf.
    legacy["batch_stats"]["bn"] = {
        k: v
        for k, v in legacy["batch_stats"]["bn"].items()
        if k != "count"
    }
    d = str(tmp_path)
    ckpt_lib.save_pytree(d, "legacy.msgpack", legacy)

    target = variables
    if abstract:
        from adanet_tpu.core.iteration import abstract_state

        target = abstract_state(variables)
    restored = ckpt_lib.restore_pytree(d, "legacy.msgpack", target)
    count = restored["batch_stats"]["bn"]["count"]
    assert float(count) == pytest.approx(legacy_batch_stats_count())
    # The migrated model applies in eval mode (strict variable lookup).
    y = Tiny().apply(restored, x, training=False)
    assert np.all(np.isfinite(np.asarray(y)))
    # An nn.BatchNorm-style stats dict (no count in the template) is
    # never touched: template-guided injection only.
    plain_template = {"batch_stats": {"bn": {"mean": np.zeros(3), "var": np.ones(3)}}}
    ckpt_lib.save_pytree(d, "plain.msgpack", plain_template)
    out = ckpt_lib.restore_pytree(d, "plain.msgpack", plain_template)
    assert set(out["batch_stats"]["bn"]) == {"mean", "var"}


def test_compile_cache_read_transient_retried():
    from adanet_tpu.core.compile_cache import CachedStep, CompileCache

    faults.arm("compile_cache.read", "transient", count=2)
    cache = CompileCache()
    step = CachedStep(lambda x: x * 2.0, cache)
    out = step(np.float32(3.0))
    assert float(out) == 6.0
    assert cache.misses == 1
    assert faults.armed()["compile_cache.read"].trips == 2


def test_data_pull_transient_reopens_pipeline(tmp_path):
    est = build_estimator(str(tmp_path / "m"))
    faults.arm("data.pull", "transient", count=2)
    batch, data_iter = est._next_batch(input_fn, None)
    assert batch is not None and data_iter is not None
    # A persistent (non-transient) fault still surfaces.
    faults.arm("data.pull", "error", count=1)
    with pytest.raises(faults.InjectedFault):
        est._next_batch(input_fn, data_iter)


# ------------------------------------------------------- watchdog/heartbeat


def test_watchdog_deadline_and_transport_death():
    t0 = time.monotonic()
    with pytest.raises(watchdog.PeerLostError) as err:
        watchdog.call_with_deadline(
            lambda: time.sleep(30), 0.4, "member sync a", source_process=3
        )
    assert time.monotonic() - t0 < 5.0  # seconds, not ~45 minutes
    assert err.value.source_process == 3
    assert "member sync a" in str(err.value)

    def reset():
        raise RuntimeError("Connection reset by peer")

    with pytest.raises(watchdog.PeerLostError):
        watchdog.call_with_deadline(reset, 5.0, "gather b")

    # Non-transport errors propagate unchanged.
    def boom():
        raise ValueError("genuine bug")

    with pytest.raises(ValueError):
        watchdog.call_with_deadline(boom, 5.0, "gather c")
    assert watchdog.call_with_deadline(lambda: 41 + 1, 5.0, "quick") == 42


def test_heartbeat_writer_and_stale_chief_detection(tmp_path):
    from adanet_tpu.distributed import coordination

    d = str(tmp_path)
    with watchdog.HeartbeatWriter(d, interval_secs=0.1):
        time.sleep(0.05)
        age = watchdog.heartbeat_age(d)
        assert age is not None and age < 5.0

    # Stale heartbeat: the worker declares the chief lost in seconds
    # instead of burning the full worker_wait_timeout.
    old = time.time() - 120
    os.utime(watchdog.heartbeat_path(d), (old, old))
    t0 = time.monotonic()
    with pytest.raises(watchdog.PeerLostError):
        coordination.wait_for_iteration(
            d,
            1,
            timeout_secs=60.0,
            poll_interval_secs=0.05,
            heartbeat_timeout_secs=1.0,
        )
    assert time.monotonic() - t0 < 5.0
    # No heartbeat file at all: plain countdown semantics are kept.
    with pytest.raises(coordination.WorkerWaitTimeout):
        coordination.wait_for_iteration(
            str(tmp_path / "empty"),
            1,
            timeout_secs=0.2,
            poll_interval_secs=0.05,
            heartbeat_timeout_secs=1.0,
        )


# ----------------------------------------------------- executor degradation


def test_round_robin_executor_quarantines_faulted_candidate():
    """A candidate whose dispatch faults is marked dead and the
    iteration finishes with the survivors (the NaN-quarantine path,
    extended to placement-layer faults)."""
    import optax

    from adanet_tpu import RegressionHead
    from adanet_tpu.core.iteration import IterationBuilder
    from adanet_tpu.distributed import RoundRobinStrategy
    from adanet_tpu.distributed.executor import RoundRobinExecutor
    from adanet_tpu.ensemble import ComplexityRegularizedEnsembler
    from adanet_tpu.ensemble.strategy import GrowStrategy

    from helpers import DNNBuilder
    from multihost_rr_runner import full_batches

    factory = IterationBuilder(
        head=RegressionHead(),
        ensemblers=[
            ComplexityRegularizedEnsembler(optimizer=optax.sgd(0.05))
        ],
        ensemble_strategies=[GrowStrategy()],
    )
    it = factory.build_iteration(
        0, [DNNBuilder("a", 1), DNNBuilder("b", 2)], None
    )
    executor = RoundRobinExecutor(it, RoundRobinStrategy())
    sample = full_batches()[0]
    state = executor.init_state(jax.random.PRNGKey(0), sample)

    orig = executor._sub_steps["a"]
    calls = [0]

    def flaky(*args):
        calls[0] += 1
        if calls[0] >= 3:
            raise faults.InjectedFault("submesh fault at call 3")
        return orig(*args)

    executor._sub_steps["a"] = flaky
    for batch in full_batches():
        state, _ = executor.train_step(state, batch)

    assert "a" in executor.dead_subnetworks()
    dead = executor.dead_candidate_names()
    assert any("a" in name for name in dead)
    assert all("b" not in name.split("_")[1] for name in dead)

    from adanet_tpu.core.estimator import _force_candidates_dead

    gathered = _force_candidates_dead(executor.gather(state), dead)
    best = it.best_candidate_index(gathered)
    assert "b" in it.candidate_names()[best]
    frozen = it.freeze_candidate(
        gathered, it.candidate_names()[best], sample
    )
    assert frozen.weighted_subnetworks


# ----------------------------------------------- corruption: roll back/resume


@pytest.fixture(scope="module")
def oracle_dir(tmp_path_factory):
    """An uninterrupted run of the shared chaos config (2 iterations)."""
    d = str(tmp_path_factory.mktemp("oracle") / "model")
    est = build_estimator(d)
    est.train(input_fn, max_steps=100)
    assert est.latest_iteration_number() == 2
    return d


def _arch(model_dir, t):
    with open(
        os.path.join(model_dir, ckpt_lib.architecture_filename(t))
    ) as f:
        return json.load(f)


def test_fsck_clean_on_healthy_dir(oracle_dir, tmp_path):
    d = str(tmp_path / "m")
    shutil.copytree(oracle_dir, d)
    report = fsck(d, repair=True)
    assert report.ok and not report.quarantined
    # CLI agrees (exit 0, machine-readable).
    from tools import ckpt_fsck

    assert ckpt_fsck.main([d, "--json"]) == 0


def test_fsck_rolls_back_corrupt_frozen_generation(oracle_dir, tmp_path):
    """Bit rot in `frozen-1.msgpack`: the chain rolls back to iteration
    1 and a resumed search reaches the oracle's final architecture."""
    d = str(tmp_path / "m")
    shutil.copytree(oracle_dir, d)
    path = os.path.join(d, "frozen-1.msgpack")
    with open(path, "r+b") as f:
        f.seek(10)
        f.write(b"\x00\x01\x02\x03")

    from tools import ckpt_fsck

    # Verify-only reports the damage and exits nonzero...
    assert ckpt_fsck.main([d]) == 1
    # ...repair quarantines and rolls the manifest back.
    report = fsck(d, repair=True)
    assert report.rolled_back_to_iteration == 1
    assert any("frozen-1" in name for name in report.quarantined)
    info = ckpt_lib.read_manifest(d)
    assert info.iteration_number == 1
    assert info.global_step == _arch(oracle_dir, 0)["global_step"]

    # Resume: iteration 1 retrains and the final architecture matches
    # the uninterrupted oracle exactly.
    est = build_estimator(d)
    est.train(input_fn, max_steps=100)
    assert est.latest_iteration_number() == 2
    assert _arch(d, 1) == _arch(oracle_dir, 1)


def test_fsck_exit_codes_and_json_verdict(oracle_dir, tmp_path, capsys):
    """The CLI contract CI and the scheduler's pre-restore check consume:
    0 clean / 1 healed / 2 unrecoverable (64 usage), with the same
    answer in the --json report's verdict/exit_code fields, identical
    with and without --repair."""
    from tools import ckpt_fsck

    # Healed: frozen-1 rots; iteration 0's generation survives.
    d = str(tmp_path / "healed")
    shutil.copytree(oracle_dir, d)
    with open(os.path.join(d, "frozen-1.msgpack"), "r+b") as f:
        f.seek(10)
        f.write(b"\x00\x01\x02\x03")
    assert ckpt_fsck.main([d, "--json"]) == 1
    report = json.loads(capsys.readouterr().out)
    assert (report["verdict"], report["exit_code"]) == ("healed", 1)
    assert ckpt_fsck.main([d, "--repair", "--json"]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["verdict"] == "healed" and report["manifest_rewritten"]
    assert ckpt_fsck.main([d]) == 0  # repair converged: now clean
    capsys.readouterr()  # drain the non-JSON "clean:" line

    # Unrecoverable: frozen-0 rots -> rollback to iteration 0, step 0.
    d = str(tmp_path / "lost")
    shutil.copytree(oracle_dir, d)
    with open(os.path.join(d, "frozen-0.msgpack"), "r+b") as f:
        f.seek(10)
        f.write(b"\x00\x01\x02\x03")
    assert ckpt_fsck.main([d, "--json"]) == 2
    report = json.loads(capsys.readouterr().out)
    assert (report["verdict"], report["exit_code"]) == ("unrecoverable", 2)
    assert report["rolled_back_to_iteration"] == 0

    # Usage errors exit 64, never colliding with "unrecoverable".
    with pytest.raises(SystemExit) as exc:
        ckpt_fsck.main(["--no-such-flag"])
    assert exc.value.code == 64


def test_truncated_mid_iteration_state_rolls_back(oracle_dir, tmp_path):
    """A truncated `ckpt-*` the manifest points at degrades to "restart
    the iteration", not a crash — and the search still completes."""
    d = str(tmp_path / "m")
    est = build_estimator(d)
    est.train(input_fn, max_steps=4)  # stop mid-iteration 0
    info = ckpt_lib.read_manifest(d)
    assert info.iteration_state_file
    path = os.path.join(d, info.iteration_state_file)
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size // 2)

    est2 = build_estimator(d)
    est2.train(input_fn, max_steps=100)
    assert est2.latest_iteration_number() == 2
    assert os.path.exists(path + ".corrupt")
    assert _arch(d, 1) == _arch(oracle_dir, 1)


def _corrupt_for_restore(model_dir, filename, cause, monkeypatch):
    """Makes the mid-iteration restore fail AFTER the pre-train fsck
    would have passed the file, one way for each cause."""
    path = os.path.join(model_dir, filename)
    if cause == "bit_rot":  # the sidecar digest no longer matches
        with open(path, "r+b") as f:
            f.seek(os.path.getsize(path) // 2)
            byte = f.read(1)
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([byte[0] ^ 0xFF]))
    elif cause == "wrong_shape":  # intact bytes of another architecture
        state = ckpt_lib.restore_payload(model_dir, filename)
        kernel = state["subnetworks"]["a"]["variables"]["params"][
            "dense_0"
        ]["kernel"]
        state["subnetworks"]["a"]["variables"]["params"]["dense_0"][
            "kernel"
        ] = np.concatenate([kernel, kernel], axis=0)
        ckpt_lib.save_pytree(model_dir, filename, state)
    elif cause == "peer_failed":  # this read is sound, a peer's is not
        from adanet_tpu.distributed import multihost

        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(
            multihost,
            "allgather_host_flag",
            lambda flag, label=None: np.asarray([flag, 1]),
        )


@pytest.mark.parametrize("cause", ["bit_rot", "wrong_shape", "peer_failed"])
def test_failed_restore_ends_on_the_real_deterministic_init(
    tmp_path, monkeypatch, cause
):
    """A resume restores over the state's TEMPLATE, so a restore that
    fails (here, or on a peer: the verdict is collective) has no state
    yet: it rolls the iteration back and runs the real init, the same
    deterministic one on every process."""
    from adanet_tpu.observability import metrics as metrics_lib
    from adanet_tpu.observability import spans as spans_lib

    d = str(tmp_path / "m")
    build_estimator(d).train(input_fn, max_steps=4)  # mid-iteration 0
    info = ckpt_lib.read_manifest(d)
    stale = info.iteration_state_file
    assert stale and info.global_step == 4
    _corrupt_for_restore(d, stale, cause, monkeypatch)

    est = build_estimator(d)
    sample = next(input_fn())
    iteration = est._build_iteration(0, sample)
    registry = metrics_lib.registry()
    templates = registry.counter("estimator.resume.templates").value
    real_inits = registry.counter("estimator.resume.real_inits").value
    tracer = spans_lib.tracer()
    was_enabled = tracer.enabled
    tracer.enable()
    tracer.clear()
    try:
        state = est._init_or_restore_state(iteration, sample, info)
        events = tracer.events()
    finally:
        tracer.clear()
        if not was_enabled:
            tracer.disable()

    # Rolled back to the iteration's first step, the file set aside.
    assert info.iteration_state_file is None and info.global_step == 0
    assert os.path.exists(os.path.join(d, stale + ".corrupt"))
    assert ckpt_lib.read_manifest(d).iteration_state_file is None
    # The state is the real init from the estimator's own key.
    fresh = iteration.init_state(est._iteration_rng(0), sample)
    assert int(state.iteration_step) == 0
    got, want = (jax.tree_util.tree_leaves(s) for s in (state, fresh))
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # One template (no resume served by it), then one real init.
    inits = [e for e in events if e.name == "iteration.init_state"]
    assert [e.attrs["abstract"] for e in inits] == [True, False]
    assert inits[1].attrs["reason"] == "restore_failed"
    assert registry.counter("estimator.resume.templates").value == templates
    assert (
        registry.counter("estimator.resume.real_inits").value
        == real_inits + 1
    )


@pytest.fixture(scope="module")
def torn_model_dir(tmp_path_factory):
    """Phase A: a subprocess writer SIGKILLed mid-checkpoint-write by the
    armed `checkpoint.write:torn` fault, leaving a torn orphan payload."""
    d = str(tmp_path_factory.mktemp("torn") / "model")
    env = _subprocess_env()
    env["ADANET_FAULTS"] = "checkpoint.write:torn:after=2"
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(TESTS_DIR, "chaos_ckpt_runner.py"),
            d,
        ],
        env=env,
        capture_output=True,
        timeout=300,
    )
    assert proc.returncode == -signal.SIGKILL, proc.stdout.decode()[-2000:]
    assert b"UNEXPECTED COMPLETION" not in proc.stdout
    # ISSUE 12: the trip hook flight-dumped BEFORE the SIGKILL — the
    # armed torn fault leaves an intact prior dump (staged+fsync+rename;
    # no partial file at a readable dump path), narrating the search up
    # to the trip inside its span tree.
    import glob as glob_lib

    from adanet_tpu.observability.flightrec import load_dump

    [dump_path] = glob_lib.glob(
        os.path.join(d, "flightrec", "flight-*.json")
    )
    dump = load_dump(dump_path)  # parseable = intact, never partial
    assert dump["reason"] == "fault:checkpoint.write:torn"
    [trip] = [
        e for e in dump["events"] if e["name"] == "fault.trip"
    ]
    assert trip["attrs"]["site"] == "checkpoint.write"
    assert trip["attrs"]["mode"] == "torn"
    assert "search_id" in trip["correlation"]
    assert {"train_window", "checkpoint.save"} <= {
        e["name"] for e in dump["events"]
    }
    # The torn orphan is at the final path; the manifest still points at
    # the last intact generation.
    assert os.path.exists(os.path.join(d, "ckpt-6.msgpack"))
    info = ckpt_lib.read_manifest(d)
    assert info.iteration_state_file == "ckpt-4.msgpack"
    assert info.global_step == 4
    return d


def test_sigkill_mid_write_resumes_to_oracle_architecture(
    torn_model_dir, oracle_dir, tmp_path
):
    """ISSUE acceptance: SIGKILL a writer mid-checkpoint; resume must
    quarantine the torn file, restore the newest intact generation, and
    reach the same final architecture as an uninterrupted run."""
    d = str(tmp_path / "m")
    shutil.copytree(torn_model_dir, d)
    est = build_estimator(d)
    est.train(input_fn, max_steps=100)
    assert est.latest_iteration_number() == 2
    assert est.latest_global_step() == 12
    assert os.path.exists(os.path.join(d, "ckpt-6.msgpack.corrupt"))
    assert not os.path.exists(os.path.join(d, "ckpt-6.msgpack"))
    assert _arch(d, 0) == _arch(oracle_dir, 0)
    assert _arch(d, 1) == _arch(oracle_dir, 1)


def test_chaos_multihost_peer_death(torn_model_dir, tmp_path):
    """ISSUE acceptance: ≥3 distinct fault sites in one run — the model
    dir phase A TORE (checkpoint.write), a TRANSIENT compile-cache read
    fault on the chief, and a peer whose collective participation DIES
    mid-iteration. The chief must quarantine the torn file, absorb the
    transient fault, declare the peer lost within the watchdog deadline
    (no hang), finish the iteration with the surviving candidate, and
    persist it."""
    d = str(tmp_path / "m")
    shutil.copytree(torn_model_dir, d)
    with socket.socket() as sock:
        sock.bind(("localhost", 0))
        port = sock.getsockname()[1]

    def spawn(index, extra_env):
        env = _subprocess_env()
        env.pop("JAX_PLATFORMS", None)
        env.pop("XLA_FLAGS", None)
        env["ADANET_COLLECTIVE_TIMEOUT_SECS"] = "3"
        env["ADANET_HEARTBEAT_INTERVAL_SECS"] = "1"
        env.update(extra_env)
        return subprocess.Popen(
            [
                sys.executable,
                os.path.join(TESTS_DIR, "chaos_multihost_runner.py"),
                d,
                str(index),
                "2",
                "4",
                str(port),
            ],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )

    chief = spawn(
        0,
        {"ADANET_FAULTS": "compile_cache.read:transient:after=1:count=2"},
    )
    peer = spawn(
        1, {"ADANET_FAULTS": "collective.entry:hang:after=2:delay=600"}
    )
    try:
        out, _ = chief.communicate(timeout=240)
    finally:
        peer.kill()
        peer.wait(timeout=60)
    text = out.decode()
    if chief.returncode == -signal.SIGABRT and "preamble" in text:
        pytest.skip(
            "gloo unframed-pair abort (jaxlib<0.5 scheduling flake, "
            "see test_distributed._GLOO_UNFRAMED_PAIR)"
        )
    assert chief.returncode == 0, text[-3000:]
    line = [
        l for l in text.splitlines() if l.startswith("CHAOS CHIEF DONE")
    ]
    assert line, text[-3000:]
    record = json.loads(line[0].split("CHAOS CHIEF DONE ", 1)[1])

    # No hang: the whole resume (restore + 2 steps + watchdog deadline +
    # local bookkeeping) finished in seconds, not the 600s the dead peer
    # would otherwise impose.
    assert record["peer_lost"] is True
    assert record["wall_secs"] < 120.0
    # The transient compile-cache fault was absorbed by bounded retry.
    assert record["compile_cache_fault_trips"] >= 1
    # The iteration COMPLETED with the survivors: durable artifacts show
    # the surviving candidate 'b' won (the lost peer owned 'a').
    assert record["iteration_number"] == 1
    arch = _arch(d, 0)
    members = [e["builder_name"] for e in arch["subnetworks"]]
    assert members == ["b"]
    # The torn phase-A orphan was quarantined during the resume's heal.
    assert os.path.exists(os.path.join(d, "ckpt-6.msgpack.corrupt"))
    # The dead candidate is on the durable quarantine record.
    metrics = json.load(
        open(os.path.join(d, ckpt_lib.candidate_metrics_filename(0)))
    )
    dead_entries = [
        name for name, entry in metrics.items() if entry["dead"]
    ]
    assert any("a" in name for name in dead_entries)


def test_elastic_wq_worker_sigkill_mid_unit(tmp_path):
    """ISSUE 6 acceptance: SIGKILL a worker mid-work-unit. The armed
    `workunit.execute:kill` fault SIGKILLs process 1 on its second
    claimed unit; its lease expires after the 2s TTL, the unit re-issues
    to the surviving chief, and the elastic search completes the full
    2-iteration search alone — reaching the lockstep RoundRobin oracle's
    final ensemble architecture (with one device per process the
    candidate submeshes and the unit submeshes are the same 1-device
    mesh, so the drives train the same trajectory)."""
    d = str(tmp_path / "m")
    os.makedirs(d)
    runner = os.path.join(TESTS_DIR, "elastic_wq_runner.py")
    with socket.socket() as sock:
        sock.bind(("localhost", 0))
        port = sock.getsockname()[1]

    def spawn(index, extra_env):
        env = _subprocess_env()
        env.pop("JAX_PLATFORMS", None)
        env.pop("XLA_FLAGS", None)
        env["TEST_LEASE_TTL"] = "2"
        env.update(extra_env)
        return subprocess.Popen(
            [
                sys.executable,
                runner,
                d,
                "chaos",
                str(index),
                str(port),
                "2",
                "-1",
            ],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )

    chief = spawn(0, {})
    worker = spawn(
        1, {"ADANET_FAULTS": "workunit.execute:kill:after=1"}
    )
    try:
        out, _ = chief.communicate(timeout=420)
    finally:
        worker.kill()
        worker.wait(timeout=60)
    assert chief.returncode == 0, out.decode()[-3000:]
    assert worker.returncode == -signal.SIGKILL
    with open(os.path.join(d, "chaos.json")) as f:
        record = json.load(f)
    # No round blocked on the dead peer: the chief finished the WHOLE
    # search (2 iterations x 20 steps) with the worker gone.
    assert record["final_step"] == 40
    assert record["final_iteration"] == 2
    assert np.isfinite(record["loss"])

    # Lockstep oracle: the same search under RoundRobin placement.
    d_oracle = str(tmp_path / "oracle")
    os.makedirs(d_oracle)
    env = _subprocess_env()
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    env["TEST_PLACEMENT"] = "rr"
    proc = subprocess.run(
        [sys.executable, runner, d_oracle, "oracle", "0", "0", "1", "-1"],
        env=env,
        capture_output=True,
        timeout=420,
    )
    assert proc.returncode == 0, proc.stdout.decode()[-3000:]
    with open(os.path.join(d_oracle, "oracle.json")) as f:
        oracle = json.load(f)
    assert record["selection"] == oracle["selection"], (
        record["selection"],
        oracle["selection"],
    )
