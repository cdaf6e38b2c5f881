"""Fleet suite: trial fingerprints, comparator ranking, transfer
planning, the controller state machine (mocked clock, no sleeps), the
tier-1 tiny 2-trial fleet gate, and the promotion-SIGKILL chaos gate.

The integration gates prove the ISSUE contract by doing: a fleet at
equal total step budget reaches F(w) <= the a-priori single search's,
the champion rebuild grafts the winner's iterations from the shared
store with zero retraining (cross-search store hits), a culled trial's
partial `replay.json` exists (the incremental-persistence bugfix), and
a fleet SIGKILLed at the promotion seam resumes to the oracle fleet's
winner with the store fsck-clean.
"""

import json
import os
import signal
import subprocess
import sys

import pytest

from adanet_tpu import replay as replay_lib
from adanet_tpu.core import checkpoint as ckpt_lib
from adanet_tpu.fleet import (
    Comparator,
    FleetController,
    Score,
    TrialSpec,
    load_status,
    plan_graft,
    rank,
)
from adanet_tpu.robustness import faults

import fleet_common

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True)
def _disarm_faults():
    faults.disarm()
    yield
    faults.disarm()


def _spec(trial_id="t0", **kwargs):
    defaults = dict(
        trial_id=trial_id,
        make_head=lambda: None,
        make_generator=lambda: None,
        generator_id="g0",
        max_iteration_steps=4,
    )
    defaults.update(kwargs)
    return TrialSpec(**defaults)


def _arch(model_dir, t):
    with open(
        os.path.join(model_dir, ckpt_lib.architecture_filename(t))
    ) as f:
        return json.load(f)


# ------------------------------------------------------------ trial specs


def test_trial_spec_fingerprint_covers_numeric_ingredients():
    base = _spec()
    assert base.spec_fingerprint() == _spec().spec_fingerprint()
    for variant in (
        _spec(adanet_lambda=0.1),
        _spec(adanet_beta=0.01),
        _spec(random_seed=7),
        _spec(max_iteration_steps=8),
        _spec(generator_id="g1"),
        _spec(extra_spec={"lr": 0.5}),
    ):
        assert variant.spec_fingerprint() != base.spec_fingerprint()
    # estimator_kwargs are declared non-numeric: same fingerprint.
    assert (
        _spec(estimator_kwargs={"save_checkpoint_steps": 2}).spec_fingerprint()
        == base.spec_fingerprint()
    )


def test_trial_spec_fingerprint_matches_estimator_ref_keys(tmp_path):
    """The graft-safety contract: TrialSpec and the Estimator it builds
    derive the SAME spec fingerprint, so 'fingerprints agree' means
    'store refs collide exactly when payloads are bit-identical'."""
    spec = fleet_common.make_trials()[0]
    est = spec.build_estimator(
        str(tmp_path / "m"), None, max_iterations=1
    )
    assert est._store_spec_fingerprint() == spec.spec_fingerprint()
    # The Estimator fails FAST on a base-key-shadowing extra (not at
    # the first publication, hours into a search).
    import adanet_tpu

    with pytest.raises(ValueError, match="shadows"):
        adanet_tpu.Estimator(
            head=adanet_tpu.RegressionHead(),
            subnetwork_generator=fleet_common._make_generator(),
            max_iteration_steps=4,
            model_dir=str(tmp_path / "bad"),
            store_spec_extra={"random_seed": 7},
        )


def test_trial_spec_validation():
    with pytest.raises(ValueError):
        _spec(trial_id="bad/slash")
    with pytest.raises(ValueError):
        _spec(trial_id="")
    with pytest.raises(ValueError):
        _spec(adanet_lambda=-1.0)
    with pytest.raises(ValueError):
        _spec(max_iteration_steps=0)
    with pytest.raises(TypeError):
        _spec(extra_spec={"fn": lambda: None})
    # extra_spec shadowing a derived fingerprint ingredient would alias
    # two numerically-different trials under one fingerprint.
    with pytest.raises(ValueError, match="shadow"):
        _spec(adanet_lambda=0.5, extra_spec={"adanet_lambda": 0.0})
    with pytest.raises(ValueError, match="shadow"):
        _spec(extra_spec={"random_seed": 7})
    # estimator_kwargs overriding a spec-managed argument would key
    # store refs the declared fingerprint never matches.
    with pytest.raises(ValueError, match="spec-managed"):
        _spec(estimator_kwargs={"random_seed": 7})
    with pytest.raises(ValueError, match="spec-managed"):
        _spec(estimator_kwargs={"ensemblers": []})


# ------------------------------------------------------------- comparator


def _score(trial_id, objective, members=1):
    return Score(
        trial_id=trial_id,
        objective=objective,
        loss=objective,
        complexity_regularization=0.0,
        num_members=members,
        iterations=1,
        global_step=4,
    )


def test_rank_orders_by_objective_then_complexity_then_id():
    scores = [
        _score("big", 1.0, members=3),
        _score("tie_b", 1.0, members=2),
        _score("tie_a", 1.0, members=2),
        _score("best", 0.5, members=5),
        _score("nan", float("nan")),
    ]
    ordered = [s.trial_id for s in rank(scores)]
    # Lower objective first; equal objectives prefer FEWER members,
    # then lexicographic id; non-finite always last.
    assert ordered == ["best", "tie_a", "tie_b", "big", "nan"]


def test_comparator_mode_validation():
    with pytest.raises(ValueError):
        Comparator(lambda: iter(()), adanet_lambda=0.1)  # beta missing
    with pytest.raises(ValueError):
        Comparator(lambda: iter(()), eval_steps=0)


# --------------------------------------------------------------- transfer


def _write_replay(model_dir, indices, hashes):
    os.makedirs(model_dir, exist_ok=True)
    replay_lib.Config(
        best_ensemble_indices=indices, architecture_hashes=hashes
    ).save(os.path.join(model_dir, replay_lib.REPLAY_FILENAME))


def test_plan_graft_longest_compatible_prefix(tmp_path):
    recipient = _spec("r")
    twin = _spec("twin")  # same fingerprint as the recipient
    other = _spec("other", adanet_lambda=0.5)  # different fingerprint
    short_dir = str(tmp_path / "short")
    long_dir = str(tmp_path / "long")
    alien_dir = str(tmp_path / "alien")
    _write_replay(short_dir, [0], ["h0"])
    _write_replay(long_dir, [0, 1], ["h0", "h1"])
    _write_replay(alien_dir, [0, 1, 1], ["x0", "x1", "x2"])
    plan = plan_graft(
        recipient,
        [(twin, short_dir), (twin, long_dir), (other, alien_dir)],
    )
    # Longest FINGERPRINT-COMPATIBLE donor wins; the alien's longer
    # record is ignored — there is no "close enough" tier.
    assert plan is not None
    assert plan.donor_dir == long_dir and plan.iterations == 2
    assert plan.config.architecture_hashes == ["h0", "h1"]


def test_plan_graft_truncates_to_hashed_prefix_and_excludes_self(tmp_path):
    recipient = _spec("r")
    twin = _spec("twin")
    donor_dir = str(tmp_path / "donor")
    # 3 recorded selections but only 1 architecture hash: only 1
    # iteration is graftable through the store.
    _write_replay(donor_dir, [0, 1, 0], ["h0"])
    plan = plan_graft(recipient, [(twin, donor_dir)])
    assert plan is not None and plan.iterations == 1
    assert plan.config.best_ensemble_indices == [0]
    # The recipient's own dir is not a donor.
    assert (
        plan_graft(recipient, [(twin, donor_dir)], exclude_dir=donor_dir)
        is None
    )
    # No compatible donors at all -> no plan, no attempt.
    assert plan_graft(recipient, []) is None


def test_plan_graft_fault_site_degrades(tmp_path):
    """`fleet.graft` armed with error fails planning (the controller
    degrades to plain training — graft loss costs compute, never
    correctness)."""
    twin = _spec("twin")
    donor_dir = str(tmp_path / "donor")
    _write_replay(donor_dir, [0], ["h0"])
    faults.arm("fleet.graft", "error")
    with pytest.raises(faults.InjectedFault):
        plan_graft(_spec("r"), [(twin, donor_dir)])
    faults.disarm()
    assert plan_graft(_spec("r"), [(twin, donor_dir)]) is not None


# ----------------------------------------- controller (mocked clock, fake
# trial runner: the rung/promotion state machine without any jax work)


class _FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        self.now += 1.0
        return self.now


def _fake_fleet(tmp_path, objectives, rungs=(1, 2), **kwargs):
    """A controller whose trial runs and scoring are pure bookkeeping:
    `objectives` maps trial_id -> comparator objective."""
    trials = [_spec(trial_id) for trial_id in sorted(objectives)]
    controller = FleetController(
        trials,
        input_fn=lambda: iter(()),
        work_dir=str(tmp_path / "fleet"),
        rung_iterations=rungs,
        clock=_FakeClock(),
        build_champion=False,
        **kwargs,
    )
    runs = []

    def fake_run_trial(record, rung, target):
        started = controller._clock()
        runs.append((record.spec.trial_id, rung, target))
        record.steps_trained += (
            target - record.iterations
        ) * record.spec.max_iteration_steps
        record.iterations = target
        record.rung = rung
        record.train_secs += controller._clock() - started

    def fake_score_trial(record):
        return _score(
            record.spec.trial_id, objectives[record.spec.trial_id]
        )

    controller._run_trial = fake_run_trial
    controller._score_trial = fake_score_trial
    return controller, runs


def test_successive_halving_culls_promotes_and_picks_winner(tmp_path):
    objectives = {"a": 0.9, "b": 0.2, "c": 0.5, "d": 0.7}
    controller, runs = _fake_fleet(
        tmp_path, objectives, rungs=(1, 2, 3)
    )
    report = controller.run()
    assert report.complete and report.winner_id == "b"
    states = {t: e["state"] for t, e in report.trials.items()}
    # Rung 0 culls the worst half (a, d); rung 1 culls c; b survives.
    assert states == {
        "a": "culled",
        "b": "live",
        "c": "culled",
        "d": "culled",
    }
    # Rung work: all 4 at rung 0, survivors only afterwards — culled
    # capacity re-packed, never re-trained.
    assert sorted(r[0] for r in runs if r[1] == 0) == [
        "a", "b", "c", "d"
    ]
    assert sorted(r[0] for r in runs if r[1] == 1) == ["b", "c"]
    assert [r[0] for r in runs if r[1] == 2] == ["b"]
    # Equal-budget accounting: steps = trained iterations * step budget.
    assert report.total_steps_trained == (4 * 1 + 2 * 1 + 1 * 1) * 4
    # Mocked-clock bookkeeping: every run booked a positive duration
    # from the injected clock — no wall clock, no sleeps.
    assert all(
        e["train_secs"] > 0 for e in report.trials.values()
    )


def test_rung_boundary_is_cumulative_not_incremental(tmp_path):
    controller, runs = _fake_fleet(
        tmp_path, {"a": 0.1, "b": 0.2}, rungs=(2, 5)
    )
    controller.run()
    # Rung targets are CUMULATIVE iteration budgets.
    assert ("a", 0, 2) in runs and ("a", 1, 5) in runs


def test_resume_skips_completed_work(tmp_path):
    objectives = {"a": 0.3, "b": 0.6}
    controller, runs = _fake_fleet(tmp_path, objectives)
    first = controller.run()
    assert first.winner_id == "a"
    # A fresh controller over the same work dir adopts the durable
    # state: nothing re-runs, the winner stands.
    controller2, runs2 = _fake_fleet(tmp_path, objectives)
    report2 = controller2.run()
    assert runs2 == []
    assert report2.winner_id == "a" and report2.complete
    # Changing the rung schedule on resume is refused loudly.
    controller3, _ = _fake_fleet(tmp_path, objectives, rungs=(1, 3))
    with pytest.raises(ValueError):
        controller3.run()


def test_trial_failure_is_isolated_then_respawned(tmp_path):
    objectives = {"a": 0.3, "b": 0.6}
    controller, _ = _fake_fleet(
        tmp_path, objectives, max_trial_attempts=2
    )
    real_run = controller._run_trial
    fails = {"b": 1}

    def flaky_run(record, rung, target):
        if fails.get(record.spec.trial_id, 0) > 0:
            fails[record.spec.trial_id] -= 1
            raise RuntimeError("injected trial death")
        real_run(record, rung, target)

    controller._run_trial = flaky_run
    report = controller.run()
    # b died at rung 0, was isolated (a's rung completed), respawned
    # into a FRESH dir at rung 1, and caught up.
    assert report.complete and report.winner_id == "a"
    entry = report.trials["b"]
    assert entry["attempt"] == 1
    assert entry["model_dir"].endswith("b.a1")
    assert entry["state"] == "live"
    assert entry["iterations"] == 2


def test_exhausted_attempts_stay_failed(tmp_path):
    objectives = {"a": 0.3, "b": 0.6}
    controller, _ = _fake_fleet(
        tmp_path, objectives, max_trial_attempts=1
    )

    def dead_run(record, rung, target):
        if record.spec.trial_id == "b":
            raise RuntimeError("unrecoverable")
        record.iterations = target
        record.rung = rung

    controller._run_trial = dead_run
    report = controller.run()
    assert report.winner_id == "a"
    assert report.trials["b"]["state"] == "failed"
    assert "unrecoverable" in report.trials["b"]["error"]


def test_controller_validation(tmp_path):
    with pytest.raises(ValueError):
        FleetController([], lambda: iter(()), str(tmp_path / "f"))
    with pytest.raises(ValueError):
        FleetController(
            [_spec("a"), _spec("a")], lambda: iter(()),
            str(tmp_path / "f"),
        )
    with pytest.raises(ValueError):
        FleetController(
            [_spec("a")], lambda: iter(()), str(tmp_path / "f"),
            rung_iterations=(2, 2),
        )
    with pytest.raises(ValueError):
        FleetController(
            [_spec("a")], lambda: iter(()), str(tmp_path / "f"),
            survivor_fraction=0.0,
        )


# ------------------------------------------------- tier-1 tiny fleet gate


@pytest.fixture(scope="module")
def tiny_fleet(tmp_path_factory):
    """The 2-trial fleet run shared by the gate assertions and the
    chaos test's oracle comparison."""
    work_dir = str(tmp_path_factory.mktemp("fleet") / "work")
    controller = fleet_common.build_fleet(work_dir)
    report = controller.run()
    return work_dir, report


def test_tiny_fleet_gate(tiny_fleet, tmp_path):
    """ISSUE acceptance (tier-1 scale): the fleet completes, culls the
    over-regularized trial, grafts the champion from the store with
    zero retraining, and beats the a-priori single search on F(w) at
    equal total step budget."""
    work_dir, report = tiny_fleet
    assert report.complete
    assert report.winner_id == "reg_lo"
    trials = report.trials
    assert trials["reg_hi"]["state"] == "culled"
    assert trials["reg_lo"]["state"] == "live"
    # Equal-budget accounting: reg_hi trained 1 iteration, reg_lo 2.
    steps = fleet_common.MAX_ITERATION_STEPS
    assert report.total_steps_trained == 3 * steps

    # Satellite bugfix proof: the CULLED trial never reached search end
    # yet its replay.json records its one completed iteration — the
    # incremental persistence the transfer path depends on.
    culled_replay = replay_lib.load_partial(trials["reg_hi"]["model_dir"])
    assert culled_replay.num_iterations == 1
    assert len(culled_replay.architecture_hashes) == 1

    # Champion: rebuilt purely from store grafts — zero retraining —
    # and architecture-identical to the winner.
    champion = report.champion_dir
    assert champion and os.path.isdir(champion)
    assert report.graft_attempts >= 1
    assert report.graft_hits >= 2  # both winner iterations grafted
    winner_dir = trials["reg_lo"]["model_dir"]
    for t in (0, 1):
        assert _arch(champion, t) == _arch(winner_dir, t)

    # The acceptance comparison: a single search of the a-priori config
    # at the fleet's TOTAL trained budget, scored by the same
    # comparator, must not beat the fleet.
    single_dir = str(tmp_path / "single")
    single = fleet_common.build_single_search(
        single_dir, max_iterations=3
    )
    single.train(fleet_common.input_fn)
    assert single.latest_global_step() == report.total_steps_trained
    single_score = fleet_common.make_comparator().score(
        single, "single"
    )
    assert report.winner_score.objective <= single_score.objective

    # Durable state round-trips for fleetctl.
    state = load_status(work_dir)
    assert state["complete"] is True and state["winner"] == "reg_lo"

    # The shared store survives a full audit.
    from adanet_tpu.store import ArtifactStore, fsck_store

    audit = fsck_store(
        ArtifactStore(os.path.join(work_dir, "store")), gc_dry_run=True
    )
    assert audit["clean"] and audit["would_gc"] == []


def test_fleetctl_spec_builders():
    """`fleetctl launch`'s spec -> TrialSpec / dataset wiring (the
    launch path itself runs a real fleet; this covers the parsing layer
    cheaply)."""
    from tools import fleetctl

    spec = {
        "max_iteration_steps": 4,
        "trials": [
            {
                "id": "t1",
                "adanet_lambda": 0.1,
                "adanet_beta": 0.01,
                "random_seed": 7,
                "layer_size": 8,
                "learning_rate": 0.05,
            },
            {"id": "t2"},
        ],
    }
    trials = fleetctl._build_trials(spec)
    assert [t.trial_id for t in trials] == ["t1", "t2"]
    assert trials[0].adanet_lambda == 0.1
    assert trials[0].random_seed == 7
    assert "layer_size=8" in trials[0].generator_id
    assert "lr=0.05" in trials[0].generator_id
    # Different generator configs -> different fingerprints.
    assert trials[0].spec_fingerprint() != trials[1].spec_fingerprint()
    trials[0].make_generator()  # the factory builds without error
    input_fn = fleetctl._dataset_input_fn(
        {"dataset": {"n": 8, "dim": 2, "batch_size": 4, "seed": 1}}
    )
    features, labels = next(input_fn())
    assert features.shape == (4, 2) and labels.shape == (4, 1)


def test_fleetctl_status_and_report(tiny_fleet, capsys):
    from tools import fleetctl

    work_dir, _report = tiny_fleet
    assert fleetctl.main(["status", work_dir]) == 0
    out = capsys.readouterr().out
    assert "reg_lo" in out and "culled" in out
    assert fleetctl.main(["report", work_dir, "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["winner"] == "reg_lo"
    assert report["store"]["clean"] is True
    assert report["exit_code"] == 0
    # Unreadable state is the exit-2 contract.
    assert fleetctl.main(["status", work_dir + ".missing"]) == 2
    with pytest.raises(SystemExit) as exc:
        fleetctl.main(["bogus-subcommand"])
    assert exc.value.code == 64


# ------------------------------------------------------------- chaos gate


def test_fleet_sigkill_at_promotion_resumes_to_oracle(
    tiny_fleet, tmp_path
):
    """ISSUE chaos gate: a fleet SIGKILLed at the promotion seam
    (armed `fleet.promote:kill` in a subprocess) resumes in-process to
    the oracle fleet's winner with an oracle-identical champion
    architecture and a clean `ckpt_fsck --store` audit."""
    oracle_dir, oracle_report = tiny_fleet
    work_dir = str(tmp_path / "chaos_fleet")
    runner = os.path.join(TESTS_DIR, "fleet_chaos_runner.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(TESTS_DIR), TESTS_DIR, env.get("PYTHONPATH", "")]
    )
    env["ADANET_FAULTS"] = "fleet.promote:kill"
    proc = subprocess.run(
        [sys.executable, runner, work_dir],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        timeout=300,
    )
    assert proc.returncode == -signal.SIGKILL, proc.stdout.decode()[-2000:]
    assert b"DONE" not in proc.stdout
    # Rung 0 trained and persisted; the promotion decision did not.
    state = load_status(work_dir)
    assert state is not None and state["next_rung"] == 0
    assert not state["complete"]

    # Resume the SAME work dir in-process, no faults armed.
    report = fleet_common.build_fleet(work_dir).run()
    assert report.complete
    assert report.winner_id == oracle_report.winner_id
    for t in (0, 1):
        assert _arch(report.champion_dir, t) == _arch(
            oracle_report.champion_dir, t
        )

    # Full CLI audit over the champion + shared store.
    import io
    from contextlib import redirect_stdout

    from tools import ckpt_fsck

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = ckpt_fsck.main(
            [
                report.champion_dir,
                "--json",
                "--store",
                os.path.join(work_dir, "store"),
            ]
        )
    assert rc <= 1, buf.getvalue()
    fsck_report = json.loads(buf.getvalue())
    assert fsck_report["store"]["clean"] is True, fsck_report["store"]


# --------------------------------------------------- full gate (RUN_SLOW)


@pytest.mark.slow
def test_full_fleet_beats_best_single_search(tmp_path):
    """The full ISSUE acceptance gate: a 4-trial fleet at equal total
    step budget reaches F(w) <= the best single search's with >= 1
    cross-trial store hit."""
    controller, comparator, make_single, input_fn = (
        fleet_common.build_full_gate(str(tmp_path))
    )
    report = controller.run()
    # Successive halving spends 4+2 iterations; the a-priori single
    # search gets the same total.
    single = make_single(
        report.total_steps_trained
        // fleet_common.FULL_GATE_ITERATION_STEPS
    )
    single.train(input_fn)
    single_score = comparator.score(single, "single_baseline")
    assert single.latest_global_step() == report.total_steps_trained
    assert report.winner_score.objective <= single_score.objective
    assert report.graft_hits >= 1, report
