"""Long-context AdaNet: transformer candidates with ring attention.

The reference never scaled the sequence axis (SURVEY.md §5.7 — "absent");
this framework makes it first-class. The walkthrough runs an AdaNet
search whose candidates are transformer encoders processing sequences
LONGER than any single device's share: the mesh's `sp` axis shards the
sequence, and attention runs as an exact ring — kv blocks rotate around
the devices via `ppermute` over ICI while queries stay put — inside the
fused jitted train step (`adanet_tpu/parallel/ring_attention.py`).

The task is synthetic long-range retrieval: each sequence embeds a
marker token whose POSITION decides the label — first quarter = 0, third
quarter = 1 — so the signal never sits near the sequence end and a model
reading only the tail shard cannot shortcut. An AdaNet search grows an
ensemble of 1-layer and 2-layer transformer candidates.

Run (8 virtual devices):
  python -m adanet_tpu.examples.tutorials.long_context_ring_attention
"""

from __future__ import annotations

import argparse
import tempfile

import jax


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--seq_len", type=int, default=512)
    parser.add_argument("--batch_size", type=int, default=16)
    parser.add_argument("--max_steps", type=int, default=60)
    parser.add_argument("--iterations", type=int, default=2)
    parser.add_argument(
        "--devices",
        type=int,
        default=8,
        help="virtual CPU devices when no multi-chip backend is live",
    )
    args = parser.parse_args()

    # Provision a virtual mesh when the backend is uninitialized (the
    # tests/conftest.py pattern; on a real pod, skip this and use the
    # live devices).
    from jax._src import xla_bridge

    if not xla_bridge._backends:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.devices)

    import numpy as np
    import optax
    from jax.sharding import Mesh

    import adanet_tpu
    from adanet_tpu.ensemble import ComplexityRegularizedEnsembler
    from adanet_tpu.models.transformer import (
        TransformerBuilder,
        TransformerConfig,
    )
    from adanet_tpu.subnetwork import SimpleGenerator

    devices = jax.devices()
    if args.seq_len % len(devices) != 0:
        raise SystemExit(
            "seq_len=%d must be divisible by the %d devices forming the "
            "sp axis; pick --seq_len or --devices accordingly."
            % (args.seq_len, len(devices))
        )
    sp_mesh = Mesh(np.asarray(devices), axis_names=("sp",))
    print(
        "ring attention over %d devices (%s); seq_len=%d -> %d per device"
        % (
            len(devices),
            devices[0].platform,
            args.seq_len,
            args.seq_len // len(devices),
        )
    )

    vocab, marker = 64, 63

    def make_batches(seed, num_batches):
        rng = np.random.RandomState(seed)

        def fn():
            for _ in range(num_batches):
                tokens = rng.randint(
                    0, vocab - 1, size=(args.batch_size, args.seq_len)
                )
                # The marker lands in the first or third quarter — never
                # near the sequence end — so a model reading only the
                # tail shard cannot shortcut: the label must travel
                # across the ring.
                labels = rng.randint(0, 2, size=(args.batch_size,))
                quarter = args.seq_len // 4
                for row, label in enumerate(labels):
                    lo = 0 if label == 0 else 2 * quarter
                    tokens[row, rng.randint(lo, lo + quarter)] = marker
                yield {"tokens": tokens}, labels.astype(np.int32)

        return fn

    def candidate(num_layers):
        return TransformerBuilder(
            TransformerConfig(
                vocab_size=vocab,
                num_layers=num_layers,
                num_heads=4,
                model_dim=64,
                mlp_dim=128,
                max_seq_len=args.seq_len,
                compute_dtype=np.float32,
                sp_mesh=sp_mesh,
            ),
            optimizer=optax.adam(1e-3),
        )

    est = adanet_tpu.Estimator(
        head=adanet_tpu.MultiClassHead(n_classes=2),
        subnetwork_generator=SimpleGenerator(
            [candidate(1), candidate(2)]
        ),
        max_iteration_steps=args.max_steps // args.iterations or 1,
        max_iterations=args.iterations,
        ensemblers=[
            ComplexityRegularizedEnsembler(optimizer=optax.sgd(0.01))
        ],
        model_dir=tempfile.mkdtemp(prefix="adanet_ring_"),
        log_every_steps=10,
    )
    est.train(make_batches(0, 10), max_steps=args.max_steps)
    metrics = est.evaluate(make_batches(1, 4))
    print(
        "accuracy: %.3f | loss: %.4f | best: %s"
        % (
            metrics["accuracy"],
            metrics["average_loss"],
            metrics["best_ensemble"],
        )
    )
    print("OK: long-context search with ring attention")


if __name__ == "__main__":
    main()
