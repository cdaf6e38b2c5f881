"""Diagnose the NASNet convergence-gate failure (round-5 VERDICT item 1).

Trains the gate's exact 3-cell/8-filter NasNetA on the synthetic digits
for 300 Adam steps, then evaluates THREE ways:
  1. eval mode (use_running_average=True)  — what the gate measures
  2. train mode stats (batch statistics)   — what training actually sees
  3. eval mode after re-estimating running stats with momentum 0.9
If (2) is high while (1) is at chance, the root cause is the slim-fidelity
BatchNorm momentum 0.9997, which needs ~10k steps for running statistics
to converge — at 300 steps they are ~91% initialization.
"""

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

# NOTE: jax is already imported, so setting JAX_COMPILATION_CACHE_DIR in
# os.environ here would be a silent no-op — the config must be updated
# directly (and the dir is topology-keyed; see compile_cache_dir).
from adanet_tpu.utils.compile_cache_dir import enable_persistent_cache

enable_persistent_cache()

import jax.numpy as jnp
import numpy as np
import optax

from adanet_tpu.examples.synthetic_digits import make_dataset
from adanet_tpu.models.nasnet import NasNetA, NasNetConfig


def main():
    xtr, ytr = make_dataset(8192, seed=7)
    xte, yte = make_dataset(2048, seed=8)

    cfg = NasNetConfig(
        num_classes=10,
        num_cells=3,
        num_conv_filters=8,
        use_aux_head=False,
        drop_path_keep_prob=1.0,
        dense_dropout_keep_prob=1.0,
    )
    model = NasNetA(cfg)
    rng = jax.random.PRNGKey(0)
    variables = model.init(rng, xtr[:2], training=False)
    params = variables["params"]
    state = {k: v for k, v in variables.items() if k != "params"}

    tx = optax.chain(
        optax.clip_by_global_norm(5.0),
        optax.adam(1e-3),
    )
    opt_state = tx.init(params)

    def loss_fn(params, state, batch_x, batch_y):
        out, new_state = model.apply(
            {"params": params, **state},
            batch_x,
            training=True,
            mutable=list(state.keys()),
        )
        logits, _, _ = out
        onehot = jax.nn.one_hot(batch_y, 10)
        loss = jnp.mean(
            optax.softmax_cross_entropy(
                jnp.asarray(logits, jnp.float32), onehot
            )
        )
        acc = jnp.mean(jnp.argmax(logits, -1) == batch_y)
        return loss, (new_state, acc)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, state, opt_state, bx, by):
        (loss, (new_state, acc)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params, state, bx, by)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_state, opt_state, loss, acc

    @jax.jit
    def eval_logits(params, state, bx):
        logits, _, _ = model.apply(
            {"params": params, **state}, bx, training=False
        )
        return logits

    @jax.jit
    def trainmode_logits(params, state, bx):
        out, _ = model.apply(
            {"params": params, **state},
            bx,
            training=True,
            mutable=list(state.keys()),
        )
        return out[0]

    batch = 128
    steps = 300
    n = xtr.shape[0]
    for step in range(steps):
        lo = (step * batch) % n
        bx = jnp.asarray(xtr[lo : lo + batch])
        by = jnp.asarray(ytr[lo : lo + batch])
        params, state, opt_state, loss, acc = train_step(
            params, state, opt_state, bx, by
        )
        if step % 50 == 0 or step == steps - 1:
            print(
                f"step {step} loss {float(loss):.4f} "
                f"train-batch acc {float(acc):.4f}",
                flush=True,
            )

    def accuracy(logit_fn, state):
        correct = 0
        for lo in range(0, xte.shape[0], 256):
            logits = logit_fn(
                params, state, jnp.asarray(xte[lo : lo + 256])
            )
            correct += int(
                np.sum(np.argmax(np.asarray(logits), -1) == yte[lo : lo + 256])
            )
        return correct / xte.shape[0]

    print("eval-mode (running stats, momentum 0.9997):", accuracy(eval_logits, state))
    print("train-mode (batch stats):", accuracy(trainmode_logits, state))

    # Re-estimate running stats with effective momentum 0.9 by replaying
    # 50 training batches through a BN-stat-update-only pass.
    # params is reused across calls here, so only the BN state carry is
    # donated.
    @functools.partial(jax.jit, donate_argnums=(1,))
    def stat_update(params, state, bx):
        _, new_state = model.apply(
            {"params": params, **state},
            bx,
            training=True,
            mutable=list(state.keys()),
        )
        return new_state

    # Real copy, not an identity map: stat_update donates its state arg,
    # and aliased leaves would invalidate `state` (still printed above).
    restate = jax.tree_util.tree_map(jnp.copy, state)
    # crude: run many passes so 0.9997-momentum stats converge anyway
    for rep in range(4):
        for lo in range(0, n, batch):
            restate = stat_update(
                params, restate, jnp.asarray(xtr[lo : lo + batch])
            )
    print(
        "eval-mode after ~%d extra stat updates:" % (4 * n // batch),
        accuracy(eval_logits, restate),
    )


if __name__ == "__main__":
    main()
