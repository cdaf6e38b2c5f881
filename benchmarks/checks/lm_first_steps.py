"""`correct` for a cell that trains one language-model candidate at
iteration 0: the program's first three steps through `Estimator.train`,
read from the state it saved after each (`ckpt_shards`: a state of
gigabytes is saved leaf by leaf, and only the leaves a number needs are
read), against the plain reference (`reference/<member's>.py` and
`reference/adamw.py`) following the same batches from the same weights.

Numbers read: the ensemble objective at each step (`loss1`-`loss3`: the
candidate alone under a mixture weight of 1 is its cross-entropy) and its
moving average; the norm of the first clipped gradient, which AdamW's
first moment holds as `(1 - b1) g` after one step, and the norm of the
parameters' change over the steps (`check.norm_gaps`: worst and median
leaf); `same_expert`: 1 - the share of token-expert pairs of the first
batch on which program and reference chose the same expert; the steps
counted. Read and never compared, so that a reader of a roofline share
sees the load it was counted for: `load_max_over_mean` (the fullest held
expert's pairs over the held experts' mean, worst layer) and
`held_pairs_per_token`, of the reference's routing of the first batch.

The weights are `weights.make`'s, with the stacked expert kernels rescaled
to variance 1/fan_in of ONE expert and the embedding to variance 1.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check, ckpt_shards, lm_reduce, weights
from benchmarks.checks import first_steps_t0
from benchmarks.reference import adamw, ensemble

STEPS = 3
EXACT = ("steps",)
NOT_COMPARED = ("load_max_over_mean", "held_pairs_per_token")


def _prefix(name):
    return "subnetworks/%s/" % name


def snapshot(model_dir, name, params=False, moment=False):
    """What the check reads of the state the program just saved."""
    prefix = _prefix(name)
    held = prefix + "variables/params/"

    def want(path):
        if path.startswith(held):
            return params
        if path.startswith(prefix + "opt_state/"):
            return moment and "/mu/" in path
        return path.startswith(("candidates/", prefix)) or (
            path == "iteration_step"
        )

    leaves = ckpt_shards.read_leaves(model_dir, want)
    candidates = sorted(
        path[: -len("/adanet_loss")] for path in leaves
        if path.startswith("candidates/") and path.endswith("/adanet_loss")
        and ("_%s_" % name) in path
    )
    if len(candidates) != 1:
        raise SystemExit(
            "benchmarks: %d ensemble candidates hold only %r, want 1"
            % (len(candidates), name)
        )
    return {
        "params": {
            path[len(held):]: value for path, value in leaves.items()
            if path.startswith(held)
        },
        "mu": {
            path.split("/mu/", 1)[1]: value for path, value in leaves.items()
            if "/mu/" in path
        },
        "steps": int(leaves[prefix + "step"]),
        "iteration_step": int(leaves["iteration_step"]),
        "loss": float(leaves[candidates[0] + "/adanet_loss"]),
        "average": float(leaves[candidates[0] + "/ema_biased"]),
    }


def plant(model_dir, seed, name, sizes):
    """Overwrites every parameter the program initialised, and the key it
    draws from, with the benchmark's own. Returns the parameters."""
    held = _prefix(name) + "variables/params/"
    start = ckpt_shards.read_leaves(
        model_dir, lambda path: path.startswith(held) or path == "rng"
    )
    shapes = {
        path[len(held):]: value.shape for path, value in start.items()
        if path.startswith(held)
    }
    planted = weights.make(seed, 0, shapes)
    count = sizes["experts_held"][1]
    for path in planted:
        if path.split("/")[-2:-1] in (["gate"], ["up"], ["down"]):
            planted[path] = planted[path] * np.float32(math.sqrt(count))
        elif path == "embedding":
            planted[path] = planted[path] * np.float32(10.0)
    key = np.asarray(weights.seed_key(seed, 0xD1CE)).astype(
        start["rng"].dtype
    )
    assert key.shape == start["rng"].shape, (key.shape, start["rng"].shape)
    ckpt_shards.write_leaves(
        model_dir,
        {"rng": key, **{held + path: v for path, v in planted.items()}},
    )
    return planted


def prepare(search, steps=STEPS):
    """The program builds and initialises its state and, stopped at its
    first batch, saves it at step 0; the benchmark plants its own weights
    and key there; then the first steps go through the window's own call
    and feed, the state read after each."""
    name, member = first_steps_t0._member(search.cell)
    lm_reduce.note_cell(search.cell)
    search.train(0, search.far, on_pull=search.stop)
    search.planted = plant(
        search.model_dir, search.seed, name, member["sizes"]
    )
    search.after, search.taken = [], {}
    for step in range(1, steps + 1):
        search.train(step - 1, step)
        search.after.append(snapshot(
            search.model_dir, name, params=step == steps, moment=step == 1
        ))


def _program_choices(search, member):
    """[layers, tokens, k]: the experts the PROGRAM's module chooses for
    the first batch from the planted weights, in the program's own
    arithmetic."""
    from benchmarks.factories import moe_lm as factory
    from adanet_tpu.models import moe_lm

    vocab = search.cell.config["sizes"]["vocab_size"]
    module = moe_lm.MoeLm(
        factory.model_config(member["sizes"], vocab), vocab
    )
    tree = {}
    for path, value in search.planted.items():
        node = tree
        *parents, leaf = path.split("/")
        for part in parents:
            node = node.setdefault(part, {})
        node[leaf] = value
    features, _ = search.feed.batch_at(0)

    @jax.jit
    def chosen(params, tokens):
        return module.apply(
            {"params": params}, {"tokens": tokens}
        ).extras["chosen"]

    return np.asarray(chosen(tree, features["tokens"]))


def _program(search, member):
    sizes = member["sizes"]
    first, last = search.after[0], search.after[-1]
    return {
        "losses": [snap["loss"] for snap in search.after],
        "average": last["average"],
        "gradient": {
            path: float(np.linalg.norm(moment)) / (1.0 - sizes["adam_b1"])
            for path, moment in first["mu"].items()
        },
        "change": {
            path: float(np.linalg.norm(last["params"][path] - value))
            for path, value in search.planted.items()
        },
        "chosen": _program_choices(search, member),
        "steps": last["steps"],
    }


def follow(member, start, feed, steps=STEPS, arith="f32", batch_share=1.0):
    """The reference's first steps: what `_program` returns for the
    program, and the load of its routing of the first batch.
    `batch_share` under 1 plants a fault: only that share of each batch's
    sequences is used."""
    module = first_steps_t0._reference(member["reference"])
    sizes = member["sizes"]
    current = {k: jnp.asarray(v) for k, v in start.items()}
    state = adamw.init(current)
    losses, first, chosen, pairs = [], None, None, None
    for index in range(steps):
        features, labels = feed.batch_at(index)
        rows = int(round(len(labels) * batch_share))
        value, grads, routed, counted = module.loss_and_gradients(
            current, jnp.asarray(features["tokens"][:rows]),
            jnp.asarray(labels[:rows]), sizes, arith,
        )
        losses.append(float(value))
        if index == 0:
            factor = float(adamw.clip_factor(grads, sizes))
            first = {
                k: factor * float(jnp.linalg.norm(g))
                for k, g in grads.items()
            }
            # [B, layers, S, k] -> [layers, B x S, k]
            routed = np.asarray(routed)
            chosen = np.moveaxis(routed, 0, 1).reshape(
                routed.shape[1], -1, routed.shape[-1]
            )
            pairs = np.asarray(counted)
        current, state = adamw.update(current, grads, state, sizes)
    return {
        "losses": losses,
        "average": ensemble.biased_average(losses, sizes["ema_decay"]),
        "gradient": first,
        "change": {
            k: float(jnp.linalg.norm(current[k] - jnp.asarray(start[k])))
            for k in start
        },
        "chosen": chosen,
        "pairs": pairs,
        "steps": steps,
    }


def _same_expert_gap(ours, theirs):
    """1 - the share of pairs of `theirs` whose expert `ours` chose too,
    over the tokens both routed."""
    tokens = min(ours.shape[1], theirs.shape[1])
    ours, theirs = ours[:, :tokens], theirs[:, :tokens]
    same = (ours[..., :, None] == theirs[..., None, :]).any(-2)
    return 1.0 - float(same.mean())


def compare(program, reference, sizes):
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
    read["change"], read["change_median"] = check.norm_gaps(
        program["change"], reference["change"]
    )
    read["same_expert"] = _same_expert_gap(
        program["chosen"], reference["chosen"]
    )
    first, count = sizes["experts_held"]
    held = reference["pairs"][:, first : first + count]
    read["load_max_over_mean"] = float(
        (held.max(-1) / np.maximum(held.mean(-1), 1.0)).max()
    )
    read["held_pairs_per_token"] = float(
        held.sum(-1).mean() / reference["chosen"].shape[1]
    )
    read["steps"] = float(abs(program["steps"] - reference["steps"]))
    return read


def _readings(search, arith, batch_share):
    name, member = first_steps_t0._member(search.cell)
    key = (arith, batch_share)
    if key not in search.taken:
        if arith is None:
            search.taken[key] = _program(search, member)
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
    _, member = first_steps_t0._member(search.cell)
    return compare(
        _readings(search, arith, batch_share),
        _readings(search, "f32", 1.0), member["sizes"],
    )


def judge(search):
    """{name: [value, limit]} of the numbers the cell compares."""
    numbers = read(search)
    for name in NOT_COMPARED:
        if name in search.cell.cell["limits"]:
            raise SystemExit(
                "benchmarks: %s is read for its reader, never limited" % name
            )
    return check.limited(numbers, search.cell.cell["limits"], EXACT)


def after_window(search):
    """{name: [value, 0]}: steps of the window that the search counted
    and its candidate did not train."""
    name, _ = first_steps_t0._member(search.cell)
    final = snapshot(search.model_dir, name)
    return {
        "unsound_steps": [
            float(final["iteration_step"] - final["steps"]), 0.0
        ]
    }
