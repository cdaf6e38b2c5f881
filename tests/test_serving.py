"""Serving-plane tests (ISSUE 7 tentpole): admission, deadlines, and
canary gates against a mocked clock; fault-site chaos (bit rot at
`serving.flip`, load failures, queue saturation); SIGTERM drain; and
the serve-while-search integration gate — a live multi-iteration
search publishing generations under a serving front-end that must keep
answering from the incumbent through a searcher SIGKILL mid-write and
a bit-rotted flip, with zero 5xx-equivalent responses.
"""

import glob
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from adanet_tpu.core import checkpoint as ckpt_lib
from adanet_tpu.robustness import faults, integrity
from adanet_tpu.serving import (
    AdmissionController,
    Batcher,
    BatcherConfig,
    ExecBudget,
    FrontendConfig,
    ModelPool,
    PoolConfig,
    ServingFrontend,
    publisher,
)
from adanet_tpu.serving import batcher as batcher_lib


TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


class FakeClock:
    def __init__(self, start: float = 1000.0):
        self.now = start

    def __call__(self) -> float:
        return self.now

    def advance(self, secs: float) -> None:
        self.now += secs


# ----------------------------------------------------------- fixtures


def _write_fake_generation(model_dir, t, payload=None):
    """A published generation without a real export: arbitrary program
    bytes under the full digest/manifest contract."""
    gen = publisher.generation_dir(model_dir, t)
    os.makedirs(gen)
    with open(os.path.join(gen, "serving.stablehlo"), "wb") as f:
        f.write(payload if payload is not None else b"program-%d" % t)
    with open(os.path.join(gen, "serving_signature.json"), "w") as f:
        json.dump(
            {"inputs": {"x": {"shape": ["batch", "3"], "dtype": "float32"}}},
            f,
        )
    publisher.write_generation_manifest(gen, t)
    return gen


def _stub_loader(gen_dir):
    """Loads a fake generation as `y = x * (t + 1)` (host numpy)."""
    with open(
        os.path.join(gen_dir, integrity.GENERATION_MANIFEST)
    ) as f:
        t = int(json.load(f)["iteration_number"])

    def program(features):
        return {"y": np.asarray(features["x"], np.float32) * (t + 1)}

    with open(os.path.join(gen_dir, "serving_signature.json")) as f:
        return program, json.load(f)


def _stub_pool(model_dir, generations=(0,), **config_kwargs):
    for t in generations:
        _write_fake_generation(model_dir, t)
    pool = ModelPool(
        model_dir,
        PoolConfig(canary_requests=3, **config_kwargs),
        loader=_stub_loader,
    )
    return pool


# ------------------------------------------------- batching state machines


def test_bucketing_pads_and_splits_round_trip():
    assert batcher_lib.bucket_for(1, (1, 2, 4)) == 1
    assert batcher_lib.bucket_for(2, (1, 2, 4)) == 2
    assert batcher_lib.bucket_for(3, (1, 2, 4)) == 4
    with pytest.raises(ValueError):
        batcher_lib.bucket_for(5, (1, 2, 4))

    requests = [
        {"x": np.ones((2, 3), np.float32)},
        {"x": np.full((1, 3), 2.0, np.float32)},
    ]
    padded, total = batcher_lib.pad_batch(requests, 4)
    assert padded["x"].shape == (4, 3) and total == 3
    assert np.all(padded["x"][3] == 0)  # zero padding rows
    split = batcher_lib.split_rows({"y": padded["x"] * 2}, [2, 1])
    assert split[0]["y"].shape == (2, 3)
    np.testing.assert_array_equal(split[1]["y"], np.full((1, 3), 4.0))


def test_admission_depth_hysteresis():
    config = FrontendConfig(
        max_queue_depth=10,
        shed_high_watermark=0.8,
        shed_low_watermark=0.3,
    )
    admission = AdmissionController(config)
    assert admission.admit(7)  # below high watermark
    assert not admission.admit(8)  # enters shedding at >= 8
    # Hysteresis: still shedding anywhere above the LOW watermark, so
    # the decision cannot flap once per request at the boundary.
    assert not admission.admit(7)
    assert not admission.admit(4)
    assert admission.admit(3)  # == low watermark -> recovers
    assert admission.admit(5)  # and stays open below high


def test_admission_latency_watermark():
    config = FrontendConfig(
        max_queue_depth=100,
        latency_high_watermark_secs=0.5,
        latency_low_watermark_secs=0.1,
        latency_decay=0.0,  # EWMA == last observation
    )
    admission = AdmissionController(config)
    assert admission.admit(1)
    admission.observe_wait(0.9)  # queue wait blew the watermark
    assert not admission.admit(1)  # sheds on latency despite depth 1
    admission.observe_wait(0.3)  # better, but above the LOW watermark
    assert not admission.admit(1)
    admission.observe_wait(0.05)
    assert admission.admit(1)


def test_deadline_budget_mocked_clock():
    clock = FakeClock()
    budget = ExecBudget(decay=0.5)
    # No estimate yet: nothing is preemptively expired.
    assert not budget.expired(deadline=clock.now + 0.001, now=clock.now)
    budget.observe(0.2)
    assert budget.estimate == pytest.approx(0.2)
    # Remaining budget below one execution -> reject without executing.
    assert budget.expired(clock.now + 0.1, clock.now)
    assert not budget.expired(clock.now + 0.3, clock.now)
    clock.advance(0.25)
    assert budget.expired(clock.now + 0.1, clock.now)
    budget.observe(0.05)  # EWMA decays toward faster batches
    assert budget.estimate == pytest.approx(0.125)
    assert not budget.expired(clock.now + 0.15, clock.now)


# ------------------------------------------------------- canary decisions


def test_canary_window_promotes_after_healthy_batches(tmp_path):
    clock = FakeClock()
    pool = _stub_pool(str(tmp_path), generations=(0,))
    pool._clock = clock
    assert pool.poll()  # bootstrap flip: verify + load + smoke
    assert pool.stats()["active_generation"] == 0

    _write_fake_generation(str(tmp_path), 1)
    assert pool.poll()
    assert pool.stats()["canary_generation"] == 1
    for _ in range(2):
        pool.report_canary(ok=True)
        assert pool.stats()["active_generation"] == 0  # window open
    pool.report_canary(ok=True)  # third healthy batch: promote
    stats = pool.stats()
    assert stats["active_generation"] == 1
    assert stats["canary_generation"] is None
    assert stats["flips"] == 2 and stats["rollbacks"] == 0


def test_canary_rollback_on_unhealthy_batches(tmp_path):
    pool = _stub_pool(str(tmp_path), generations=(0, 1))
    assert pool.poll()  # newest-first: bootstraps straight onto gen 1
    assert pool.stats()["active_generation"] == 1
    _write_fake_generation(str(tmp_path), 2)
    assert pool.poll()
    pool.report_canary(ok=True)
    pool.report_canary(ok=False)  # max_canary_failures=0: one strike
    stats = pool.stats()
    assert stats["active_generation"] == 1  # rollback to incumbent
    assert stats["canary_generation"] is None
    assert stats["rollbacks"] == 1
    assert glob.glob(
        os.path.join(str(tmp_path), "serving", "gen-2.corrupt*")
    )
    # The quarantined directory is never retried...
    assert not pool.poll()
    # ...but a FRESH publish of the same iteration is.
    _write_fake_generation(str(tmp_path), 2)
    assert pool.poll()
    for _ in range(3):
        pool.report_canary(ok=True)
    assert pool.stats()["active_generation"] == 2


def test_canary_divergence_watermark(tmp_path):
    pool = _stub_pool(str(tmp_path), generations=(0,), max_divergence=0.5)
    pool.poll()
    _write_fake_generation(str(tmp_path), 1)
    pool.poll()
    pool.report_canary(ok=True, divergence=0.9)  # finite but divergent
    assert pool.stats()["active_generation"] == 0
    assert pool.stats()["rollbacks"] == 1


# ------------------------------------------------------ verify-on-load


def test_bit_rot_rejected_before_load(tmp_path):
    pool = _stub_pool(str(tmp_path), generations=(0,))
    pool.poll()
    gen = _write_fake_generation(str(tmp_path), 1)
    # Bit-rot the payload AFTER publication (digest sidecar now stale).
    with open(os.path.join(gen, "serving.stablehlo"), "r+b") as f:
        f.write(b"\xff")
    assert pool.poll()
    stats = pool.stats()
    assert stats["active_generation"] == 0 and stats["rollbacks"] == 1


def test_serving_flip_rot_fault_site(tmp_path, caplog):
    """The `serving.flip` chaos seam: armed `rot` corrupts the payload
    mid-flip and the verify-on-load gate must roll back."""
    pool = _stub_pool(str(tmp_path), generations=(0,))
    pool.poll()
    _write_fake_generation(str(tmp_path), 1)
    faults.arm("serving.flip", "rot")
    try:
        pool.poll()
    finally:
        faults.disarm()
    assert pool.stats()["active_generation"] == 0
    assert pool.stats()["rollbacks"] == 1
    assert any(e["event"] == "rollback" for e in pool.events)


def test_serving_flip_raising_fault_rejects_not_escapes(tmp_path):
    """A RAISING fault at `serving.flip` (transient/error) must resolve
    as a rollback — escaping the gate would leave the generation
    attempted-but-unquarantined and wedge the chain silently."""
    pool = _stub_pool(str(tmp_path), generations=(0,))
    pool.poll()
    _write_fake_generation(str(tmp_path), 1)
    faults.arm("serving.flip", "transient")
    try:
        pool.poll()
    finally:
        faults.disarm()
    stats = pool.stats()
    assert stats["active_generation"] == 0 and stats["rollbacks"] == 1
    assert any(e["event"] == "rollback" for e in pool.events)


def test_rot_mode_rejected_at_write_sites():
    """`rot` at a write site would be overwritten by the clean write
    that follows the trip — a vacuously green chaos run, so arming it
    is an error."""
    with pytest.raises(ValueError, match="rot mode is read/file-site"):
        faults.arm("checkpoint.write", "rot")


def test_generation_manifest_checksum_required(tmp_path):
    """A manifest with the checksum stripped (and digests possibly
    rewritten) must be INELIGIBLE, not quietly trusted."""
    gen = _write_fake_generation(str(tmp_path), 0)
    manifest = os.path.join(gen, integrity.GENERATION_MANIFEST)
    with open(manifest) as f:
        obj = json.load(f)
    del obj["checksum"]
    with open(manifest, "w") as f:
        json.dump(obj, f)
    assert integrity.verify_serving_generation(gen) == [
        "generation manifest missing checksum"
    ]


def test_oversized_request_is_invalid_argument_not_error(tmp_path):
    """A request larger than the largest bucket is the CLIENT's fault:
    an orderly admission rejection, never the 5xx-equivalent."""
    pool = _stub_pool(str(tmp_path), generations=(0,))
    pool.poll()
    frontend = ServingFrontend(
        Batcher(pool, BatcherConfig(bucket_sizes=(2, 4), jit=False))
    ).start()
    try:
        result = frontend.submit({"x": np.ones((9, 3), np.float32)})
        assert result.status == "invalid_argument"
        assert "exceeds the largest bucket" in result.error
        empty = frontend.submit({})
        assert empty.status == "invalid_argument"
        # The plane itself stayed healthy.
        assert frontend.submit({"x": np.ones((2, 3), np.float32)}).ok
        assert frontend.stats().get("error", 0) == 0
    finally:
        frontend.drain(timeout=10.0)


def test_serving_model_load_fault_site(tmp_path):
    pool = _stub_pool(str(tmp_path), generations=(0,))
    pool.poll()
    _write_fake_generation(str(tmp_path), 1)
    faults.arm("serving.model_load", "error")
    try:
        pool.poll()
    finally:
        faults.disarm()
    assert pool.stats()["active_generation"] == 0
    assert pool.stats()["rollbacks"] == 1


def test_serving_batch_execute_fault_is_orderly_error(tmp_path):
    """Chaos coverage for `serving.batch_execute` (jaxlint JL015): a
    compiled program failing under live traffic answers the in-flight
    request as the orderly 5xx-equivalent — and the plane survives, so
    the very next dispatch succeeds."""
    pool = _stub_pool(str(tmp_path), generations=(0,))
    pool.poll()
    frontend = ServingFrontend(
        Batcher(pool, BatcherConfig(bucket_sizes=(2, 4), jit=False))
    ).start()
    faults.arm("serving.batch_execute", "error", after=0, count=1)
    try:
        result = frontend.submit({"x": np.ones((2, 3), np.float32)})
        assert result.status == "error"
        assert "InjectedFault" in result.error
        # The plane stayed healthy: the next batch executes cleanly.
        ok = frontend.submit({"x": np.ones((2, 3), np.float32)})
        assert ok.ok
    finally:
        faults.disarm()
        frontend.drain(timeout=10.0)


def test_fsck_json_reports_serving_eligibility(tmp_path, capsys):
    """`ckpt_fsck --json` flags which generation the serving plane
    would select (`serving_eligible` per generation)."""
    from tools import ckpt_fsck

    model_dir = str(tmp_path)
    _write_fake_generation(model_dir, 0)
    gen1 = _write_fake_generation(model_dir, 1)
    with open(os.path.join(gen1, "serving.stablehlo"), "r+b") as f:
        f.write(b"\xff")  # newest generation is rotten
    rc = ckpt_fsck.main([model_dir, "--json"])
    assert rc == integrity.EXIT_CLEAN
    report = json.loads(capsys.readouterr().out)
    serving = report["serving"]
    by_iter = {
        g["iteration_number"]: g for g in serving["generations"]
    }
    assert by_iter[0]["serving_eligible"] is True
    assert by_iter[1]["serving_eligible"] is False
    assert by_iter[1]["issues"]
    # The pool would skip the rotten newest generation.
    assert serving["selected_generation"] == 0


# -------------------------------------------------------- export fallback


def test_export_records_multi_platform_fallback_reason(
    tmp_path, monkeypatch
):
    """The satellite fix: a multi-platform export that silently became
    single-platform now records WHY in the signature."""
    from adanet_tpu.core import export as export_lib

    real = export_lib.jax_export

    class FailsMultiPlatform:
        def __getattr__(self, name):
            return getattr(real, name)

        @staticmethod
        def export(jitted, **kwargs):
            if kwargs.get("platforms"):
                raise ValueError(
                    "lowering is specialized to cpu; multi-platform "
                    "serialization unsupported for this op"
                )
            return real.export(jitted, **kwargs)

    monkeypatch.setattr(export_lib, "jax_export", FailsMultiPlatform())

    import jax.numpy as jnp

    export_lib.export_serving_program(
        str(tmp_path / "export"),
        lambda features: {"y": jnp.tanh(features["x"])},
        {"x": np.zeros((2, 3), np.float32)},
    )
    signature = export_lib.serving_signature(str(tmp_path / "export"))
    reason = signature["multi_platform_fallback_reason"]
    assert reason is not None
    assert "multi-platform serialization unsupported" in reason
    assert signature["requested_platforms"] == ["cpu", "tpu"]
    assert signature["platforms"] == ["cpu"]
    # The batch dimension still exported polymorphic: only the
    # platform capability degraded, and only it carries a reason.
    assert signature["polymorphic_fallback_reason"] is None


# ------------------------------------------------------- queue saturation


def test_queue_saturation_sheds_with_retry_after_then_recovers(tmp_path):
    """Chaos: flood past the watermark. Excess load is rejected with a
    retry_after hint (429-equivalent, never 5xx), accepted work is
    answered, and admission recovers once the queue drains."""
    pool = _stub_pool(str(tmp_path), generations=(0,))
    pool.poll()

    record = pool.active_record()
    fast = record.program

    def slow_program(features):
        time.sleep(0.005)
        return fast(features)

    record.program = slow_program
    frontend = ServingFrontend(
        Batcher(pool, BatcherConfig(bucket_sizes=(4,), jit=False)),
        FrontendConfig(
            max_queue_depth=16,
            shed_high_watermark=0.5,
            shed_low_watermark=0.25,
            default_deadline_secs=30.0,
            batch_wait_secs=0.0,
        ),
    ).start()
    try:
        pending = [
            frontend.submit_async({"x": np.ones((1, 3), np.float32)})
            for _ in range(200)
        ]
        results = [p.wait(timeout=30.0) for p in pending]
        statuses = {r.status for r in results}
        sheds = [r for r in results if r.status == "shed"]
        assert sheds, "the flood never hit the watermark"
        assert all(r.retry_after > 0 for r in sheds)
        assert statuses <= {"ok", "shed"}  # zero 5xx-equivalents
        assert sum(r.ok for r in results) > 0
        # Recovery: with the queue drained, admission re-opens.
        deadline = time.time() + 10
        while time.time() < deadline:
            if frontend.submit(
                {"x": np.ones((1, 3), np.float32)}, timeout=10.0
            ).ok:
                break
            time.sleep(0.01)
        else:
            pytest.fail("admission never recovered after the flood")
        assert frontend.stats().get("error", 0) == 0
    finally:
        frontend.drain(timeout=10.0)


# ----------------------------------------------------------- SIGTERM drain


def _spawn(script, *args, env_extra=None, log_path=None):
    """Starts a runner. Its output goes to a pipe the caller must keep
    reading (`_wait_for_line`, `communicate`), or, with `log_path`, to
    that file: a child nobody reads from blocks once the pipe is full
    (one multi-KB XLA line per program loaded from a warm cache)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [
            os.path.dirname(TESTS_DIR),
            TESTS_DIR,
            env.get("PYTHONPATH", ""),
        ]
    )
    env.pop("ADANET_FAULTS", None)
    env.update(env_extra or {})
    cmd = [sys.executable, os.path.join(TESTS_DIR, script)] + list(args)
    if log_path is None:
        return subprocess.Popen(
            cmd,
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
    with open(log_path, "ab") as log:
        return subprocess.Popen(
            cmd, env=env, stdout=log, stderr=subprocess.STDOUT
        )


def _read_log(log_path):
    with open(log_path, errors="replace") as f:
        return f.read()


def _wait_for_line(proc, token, timeout=120):
    deadline = time.time() + timeout
    lines = []
    while time.time() < deadline:
        line = proc.stdout.readline()
        lines.append(line)
        if token in line:
            return lines
        if not line and proc.poll() is not None:
            raise AssertionError(
                "runner exited before %r:\n%s" % (token, "".join(lines))
            )
    proc.kill()
    raise AssertionError("runner never printed %r" % token)


def test_sigterm_drains_in_flight_requests(tmp_path):
    """SIGTERM mid-traffic: the front-end stops admitting, answers every
    accepted request, and exits 0 (the serving analogue of the
    estimator's sigterm_runner contract)."""
    proc = _spawn(
        "serving_sigterm_runner.py", str(tmp_path / "model")
    )
    _wait_for_line(proc, "READY")
    time.sleep(0.5)  # keep requests in flight at signal time
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=120)
    assert proc.returncode == 0, out[-2000:]
    assert "DRAINED drained=True" in out, out[-2000:]


# ------------------------------------------- serve-while-search (the gate)


def test_serve_while_search_chaos_flips_and_bit_identity(tmp_path):
    """The acceptance gate: a live 3-iteration search publishes
    generations under steady traffic while (a) the searcher is
    SIGKILLed mid-checkpoint-write by an armed torn fault and
    restarted, and (b) one flip is bit-rotted at the `serving.flip`
    seam. The server must answer EVERY request from the incumbent
    (zero drops, zero 5xx), log an automatic rollback, complete >= 2
    health-gated flips, and its final responses must be bit-identical
    to offline `load_serving_program` evaluation."""
    model_dir = str(tmp_path / "model")

    # The pool's install_default must own this test's flight dir (an
    # earlier test's pool may hold the process-wide slot).
    from adanet_tpu.observability import flightrec

    flightrec.uninstall()
    pool = ModelPool(model_dir, PoolConfig(canary_requests=2))
    batcher = Batcher(pool, BatcherConfig(bucket_sizes=(4, 8)))
    frontend = ServingFrontend(
        batcher,
        FrontendConfig(
            default_deadline_secs=30.0,
            poll_interval_secs=0.05,
            batch_wait_secs=0.0,
        ),
    ).start()
    features = {"x": np.ones((2, 2), np.float32)}
    results = []

    def send():
        results.append(frontend.submit(features, timeout=60.0))

    # Iteration 1's frozen-payload write (the second checkpoint.write
    # hit) is torn mid-write + SIGKILL; gen-1's eventual flip (the
    # second serving.flip hit, after gen-0's bootstrap) is bit-rotted.
    faults.arm("serving.flip", "rot", after=1)
    # The searchers write to files and compile into a cache of their
    # own: nothing they print can block them, and nothing another test
    # left in the shared cache changes what they load.
    child_env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")}
    log = str(tmp_path / "search.log")
    proc = _spawn(
        "serving_search_runner.py",
        model_dir,
        "3",
        env_extra=dict(
            child_env, ADANET_FAULTS="checkpoint.write:torn:after=1"
        ),
        log_path=log,
    )
    try:
        deadline = time.time() + 240
        while pool.active is None and time.time() < deadline:
            time.sleep(0.05)
        assert pool.active is not None, "gen-0 never became servable"

        # Steady traffic until the armed fault SIGKILLs the searcher.
        while proc.poll() is None and time.time() < deadline:
            send()
            time.sleep(0.02)
        assert proc.returncode == -signal.SIGKILL, _read_log(log)[-2000:]

        # The searcher is DEAD; the serving plane keeps answering.
        for _ in range(10):
            send()
        assert results and all(r.ok for r in results[-10:])

        # Restart the searcher clean: fsck heals the torn write,
        # retrains iteration 1, and finishes the 3-iteration search.
        proc = _spawn(
            "serving_search_runner.py",
            model_dir,
            "3",
            env_extra=child_env,
            log_path=log,
        )
        while proc.poll() is None and time.time() < deadline:
            send()
            time.sleep(0.02)
        out2 = _read_log(log)
        assert proc.returncode == 0, out2[-2000:]
        assert "SEARCH DONE 3" in out2

        # Keep traffic flowing until the final generation's canary
        # window completes and the flip lands.
        while (
            pool.stats()["active_generation"] != 2
            and time.time() < deadline
        ):
            send()
            time.sleep(0.02)
        # The flip loop exits the instant gen-2 becomes incumbent, so
        # every response so far may predate it: send a few more that
        # must be answered BY the final generation.
        for _ in range(5):
            send()
    finally:
        faults.disarm()
        if proc.poll() is None:
            proc.kill()
        frontend.drain(timeout=10.0)

    # Zero dropped requests, zero 5xx-equivalents: every submitted
    # request resolved ok from whichever generation was incumbent.
    assert results
    assert all(r.ok for r in results), {
        r.status for r in results if not r.ok
    }
    assert frontend.stats().get("error", 0) == 0

    stats = pool.stats()
    assert stats["active_generation"] == 2
    assert stats["flips"] >= 2, pool.events
    assert stats["rollbacks"] >= 1, pool.events
    assert any(e["event"] == "rollback" for e in pool.events)
    # The bit-rotted generation was quarantined, then republished fresh
    # by the restarted searcher.
    assert glob.glob(
        os.path.join(model_dir, "serving", "gen-1.corrupt*")
    )

    # ISSUE 12 acceptance: the rot-rejected flip left a flight-recorder
    # dump in THIS (serving) process — the `serving.flip` trip hook
    # dumped at the fault, and the digest rejection dumped again with
    # the rollback instant, so chaos forensics read as a trace.
    from adanet_tpu.observability.flightrec import load_dump

    dump_path = os.path.join(
        model_dir, "flightrec", "flight-%d.json" % os.getpid()
    )
    assert os.path.exists(dump_path), os.listdir(
        os.path.join(model_dir, "flightrec")
    )
    dump = load_dump(dump_path)
    assert any(
        r.startswith("fault:serving.flip:rot") for r in dump["reasons"]
    ), dump["reasons"]
    assert any(
        r.startswith("serving_rollback") for r in dump["reasons"]
    ), dump["reasons"]
    rollbacks = [
        e for e in dump["events"] if e["name"] == "serving.rollback"
    ]
    assert rollbacks and rollbacks[-1]["attrs"]["generation"] == 1

    # Served responses answered during gen-0 incumbency differ from
    # gen-2's: each response's `generation` tags its source, and every
    # tag corresponds to a generation that passed the health gate.
    flipped = {
        e["iteration_number"] for e in pool.events if e["event"] == "flip"
    }
    assert {r.generation for r in results} <= flipped

    # Bit-identical to offline evaluation: the served answer equals
    # `load_serving_program` on the same padded bucket shape.
    from adanet_tpu.core.export import load_serving_program

    gen2 = publisher.generation_dir(model_dir, 2)
    offline = load_serving_program(gen2)
    padded, _ = batcher_lib.pad_batch([features], 4)
    expected = jax.device_get(offline(padded))
    served = [
        r for r in results if r.generation == 2
    ][-1]
    np.testing.assert_array_equal(
        np.asarray(served.outputs["predictions"]),
        np.asarray(expected["predictions"])[:2],
    )
