"""`correct` for a cell at iteration 0 with one candidate in training: the
program's first three steps through `Estimator.train`, read from the state
it saved after each, against the plain reference following the same three
batches from the same weights.

Numbers read: the ensemble objective at each step and its moving average
after the third; the norm of the first gradient as the optimizer state
holds it, and the norm of the parameters' change over the three steps
(`check.norm_gaps`); the statistics of every batch norm's first batch,
which the program's batch norm keeps outright as its running ones after
its first update (`check.stat_gaps`); the steps the candidate counted.

A cell of another kind (frozen members, several candidates, several
chips) brings a check of its own beside this one and names it.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check, ckpt_io, weights
from benchmarks.reference import ensemble, optimizers

STEPS = 3
EXACT = ("steps",)


def _member(cell):
    """(name, sizes and all) of the one candidate in training."""
    training, frozen = cell.cell["training"], cell.cell["frozen"]
    if len(training) != 1 or frozen or cell.traffic["iteration"] != 0:
        raise SystemExit(
            "benchmarks: check first_steps_t0 follows one candidate at "
            "iteration 0; cell %s trains %s beside %s at iteration %d"
            % (cell.name, training, frozen, cell.traffic["iteration"])
        )
    return training[0], cell.members[training[0]]


def _reference(name):
    try:
        return importlib.import_module("benchmarks.reference." + name)
    except ModuleNotFoundError as exc:
        raise SystemExit(
            "benchmarks: no reference benchmarks/reference/%s.py (%s)"
            % (name, exc)
        )


def snapshot(model_dir, name):
    """What the check reads of the state the program just saved."""
    state = ckpt_io.read_state(model_dir)
    sub = state["subnetworks"][name]
    candidates = [
        value for key, value in state["candidates"].items()
        if ("_%s_" % name) in key
    ]
    if len(candidates) != 1:
        raise SystemExit(
            "benchmarks: %d ensemble candidates hold only %r, want 1: %s"
            % (len(candidates), name, sorted(state["candidates"]))
        )
    held = ckpt_io.flatten(sub["variables"].get("batch_stats", {}))
    return {
        "params": ckpt_io.flatten(sub["variables"]["params"]),
        "trace": ckpt_io.flatten(
            ckpt_io.find_subtree(sub["opt_state"], "trace") or {}
        ),
        "stats": {
            path[: -len("/mean")]: (
                value, held[path[: -len("/mean")] + "/var"]
            )
            for path, value in held.items() if path.endswith("/mean")
        },
        "steps": int(sub["step"]),
        "iteration_step": int(state["iteration_step"]),
        "loss": float(candidates[0]["adanet_loss"]),
        "average": float(candidates[0]["ema_biased"]),
    }


def plant(model_dir, seed, name):
    """Overwrites every parameter the program initialised, and the key it
    draws from, with the benchmark's own. Returns the parameters."""
    state = ckpt_io.read_state(model_dir)
    key = np.asarray(weights.seed_key(seed, 0xD1CE)).astype(
        state["rng"].dtype
    )
    assert key.shape == state["rng"].shape, (key.shape, state["rng"].shape)
    state["rng"] = key
    params = state["subnetworks"][name]["variables"]["params"]
    flat = ckpt_io.flatten(params)
    planted = weights.make(seed, 0, {k: v.shape for k, v in flat.items()})
    for path, value in planted.items():
        ckpt_io.set_leaf(params, path, value.astype(flat[path].dtype))
    ckpt_io.write_state(model_dir, state)
    return planted


def prepare(search, steps=STEPS):
    """The program builds and initialises its state and, stopped at its
    first batch, saves it at step 0; the benchmark plants its own weights
    and key there; then the first steps go through the window's own call
    and feed, the state read after each (the first's optimizer state, the
    last's parameters, every one's losses and counts)."""
    name, _ = _member(search.cell)
    search.train(0, search.far, on_pull=search.stop)
    search.planted = plant(search.model_dir, search.seed, name)
    search.after, search.taken = [], {}
    for step in range(1, steps + 1):
        search.train(step - 1, step)
        held = snapshot(search.model_dir, name)
        # Kept only where `_program` reads them: a state of gigabytes is
        # not held three times over on the host.
        if step > 1:
            held["trace"] = None
        if step < steps:
            held["params"] = None
        search.after.append(held)


def _program(after, start, sizes):
    """The program's side of the comparison, from its snapshots."""
    first, last = after[0], after[-1]
    return {
        "losses": [snap["loss"] for snap in after],
        "average": last["average"],
        "gradient": optimizers.first_gradient(first, start, sizes),
        "change": {
            path: float(np.linalg.norm(last["params"][path] - start[path]))
            for path in start
        },
        "stats": first["stats"],
        "steps": last["steps"],
    }


def follow(member, start, feed, steps=STEPS, arith="f32", batch_share=1.0):
    """The reference's first steps for the candidate trained alone under a
    one-member ensemble: what `_program` returns for the program.
    `batch_share` under 1 plants a fault: only that share of each batch's
    rows is used."""
    module = _reference(member["reference"])
    sizes = member["sizes"]

    @jax.jit
    def update(current, grads, state, logits, labels, rate):
        value = ensemble.objective(
            [logits], [1.0], [member.get("complexity", 1.0)], labels, sizes
        )
        return optimizers.update(current, grads, state, rate, sizes) + (
            value,
        )

    start = {k: jnp.asarray(v) for k, v in start.items()}
    current, state = start, optimizers.init(start, sizes)
    losses, first, stats = [], None, None
    for index in range(steps):
        features, labels = feed.batch_at(index)
        rows = int(round(len(labels) * batch_share))
        images, labels = features["image"][:rows], labels[:rows]
        logits, grads, found = module.loss_and_gradients(
            current, images, labels, sizes, arith
        )
        current, state, clipped, value = update(
            current, grads, state, logits, labels,
            jnp.float32(optimizers.learning_rate(sizes, index)),
        )
        losses.append(float(value))
        if index == 0:
            first = {k: float(jnp.linalg.norm(v)) for k, v in clipped.items()}
            stats = jax.device_get(found)
    return {
        "losses": losses,
        "average": ensemble.biased_average(losses, sizes["ema_decay"]),
        "gradient": first,
        "change": {
            k: float(jnp.linalg.norm(current[k] - start[k])) for k in start
        },
        "stats": stats,
        "steps": steps,
    }


def compare(program, reference):
    """{name: value} of every number this check reads."""
    read = {}
    for index, ref in enumerate(reference["losses"]):
        read["loss%d" % (index + 1)] = (
            abs(program["losses"][index] - ref) / abs(ref)
        )
    read["average"] = abs(program["average"] - reference["average"]) / abs(
        reference["average"]
    )
    read["gradient"], read["gradient_median"] = check.norm_gaps(
        program["gradient"], reference["gradient"]
    )
    # A leaf whose gradient is nought to rounding in the reference moves
    # by round-off alone: left out of the change by that rule, not by name.
    median = float(np.median(list(reference["gradient"].values())))
    keep = {
        k for k, v in reference["gradient"].items() if v >= 1e-3 * median
    }
    read["change"], read["change_median"] = check.norm_gaps(
        program["change"], reference["change"], keep
    )
    read.update(check.stat_gaps(program["stats"], reference["stats"]))
    read["steps"] = float(abs(program["steps"] - reference["steps"]))
    return read


def _readings(search, arith, batch_share):
    name, member = _member(search.cell)
    key = (arith, batch_share)
    if key not in search.taken:
        if arith is None:
            search.taken[key] = _program(
                search.after, search.planted, member["sizes"]
            )
        else:
            search.taken[key] = follow(
                member, search.planted, search.feed, len(search.after),
                arith, batch_share,
            )
    return search.taken[key]


def read(search, arith=None, batch_share=1.0):
    """{name: value}: the program's first steps against the float32
    reference following them. With `arith` or `batch_share` set, the
    reference put in the program's place instead: the control and the
    planted fault."""
    return compare(
        _readings(search, arith, batch_share), _readings(search, "f32", 1.0)
    )


def judge(search):
    """{name: [value, limit]} of the numbers the cell compares."""
    return check.limited(read(search), search.cell.cell["limits"], EXACT)


def after_window(search):
    """{name: [value, 0]}: steps of the window that the search counted
    and its candidate did not train."""
    name, _ = _member(search.cell)
    final = snapshot(search.model_dir, name)
    return {
        "unsound_steps": [
            float(final["iteration_step"] - final["steps"]), 0.0
        ]
    }
