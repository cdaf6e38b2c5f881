"""Per-op device-time breakdown of the flagship NASNet-A train step.

Runs the benchmark iteration under the JAX profiler and aggregates the
trace's XLA Ops lane by op category (convolution / fusion / copy / ...),
printing the top entries by total device time: where the non-MXU time
goes (depthwise convs, batch-norm bandwidth, layout copies).

Usage (on the real TPU chip):
    python tools/profile_nasnet.py [--steps 10] [--batch 128]
        [--filters 32] [--cells 6]

The trace's device lanes are the device's own timeline (see
adanet_tpu/utils/device_timing.py).
"""

import argparse
import collections
import glob
import gzip
import json
import os
import re
import tempfile


def aggregate_ops(trace_dir):
    """Returns (total_device_us, {category: us}, {op_name: us}) from the
    XLA Ops lanes of every device process in the trace."""
    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True
    )
    if not paths:
        raise FileNotFoundError("no trace under %s" % trace_dir)
    data = json.loads(gzip.open(sorted(paths)[-1]).read())
    events = data.get("traceEvents", [])
    device_pids = set()
    op_lanes = set()
    for e in events:
        if e.get("ph") != "M":
            continue
        name = str(e.get("args", {}).get("name", ""))
        if e.get("name") == "process_name" and "device:" in name:
            device_pids.add(e["pid"])
        if e.get("name") == "thread_name" and name == "XLA Ops":
            op_lanes.add((e["pid"], e["tid"]))
    by_cat = collections.Counter()
    by_op = collections.Counter()
    total = 0.0
    for e in events:
        if e.get("ph") != "X":
            continue
        if (e.get("pid"), e.get("tid")) not in op_lanes:
            continue
        if e.get("pid") not in device_pids:
            continue
        dur = float(e.get("dur", 0.0))
        name = e.get("name", "?")
        total += dur
        # Strip SSA ids: "fusion.123" -> "fusion"; "%convolution.4" ->
        # "convolution".
        cat = re.sub(r"[%.]?(\d+)?$", "", name.split(".")[0]).lstrip("%")
        by_cat[cat or name] += dur
        by_op[name] += dur
    return total, by_cat, by_op


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--batch", type=int, default=128)
    parser.add_argument("--filters", type=int, default=32)
    parser.add_argument("--cells", type=int, default=18)
    parser.add_argument("--top", type=int, default=25)
    parser.add_argument(
        "--pallas_sepconv",
        action="store_true",
        help="profile with the fused Pallas sep-conv kernel "
        "(NasNetConfig.use_pallas_sep_conv)",
    )
    args = parser.parse_args()

    import numpy as np

    import jax
    import optax

    from adanet_tpu.core.heads import MultiClassHead
    from adanet_tpu.core.iteration import IterationBuilder
    from adanet_tpu.ensemble import (
        ComplexityRegularizedEnsembler,
        GrowStrategy,
    )
    from research.improve_nas.trainer.improve_nas import Builder, Hparams

    factory = IterationBuilder(
        head=MultiClassHead(n_classes=10),
        ensemblers=[
            ComplexityRegularizedEnsembler(
                optimizer=optax.sgd(0.01), adanet_lambda=0.001
            )
        ],
        ensemble_strategies=[GrowStrategy()],
        collect_summaries=False,
    )
    builder = Builder(
        optimizer_fn=lambda lr: optax.sgd(lr, momentum=0.9),
        hparams=Hparams(
            num_cells=args.cells,
            num_conv_filters=args.filters,
            use_aux_head=False,
            use_pallas_sep_conv=args.pallas_sepconv,
        ),
        seed=0,
    )
    iteration = factory.build_iteration(0, [builder], None)

    rng = np.random.RandomState(0)
    batch = (
        {"image": rng.randn(args.batch, 32, 32, 3).astype(np.float32)},
        rng.randint(0, 10, size=(args.batch,)),
    )
    state = iteration.init_state(jax.random.PRNGKey(0), batch)
    jitted = jax.jit(iteration._train_step_impl, donate_argnums=0)
    compiled = jitted.lower(state, batch, {}).compile()
    for _ in range(3):
        state, metrics = compiled(state, batch, {})
    jax.block_until_ready(metrics)

    trace_dir = tempfile.mkdtemp(prefix="nasnet_profile_")
    jax.profiler.start_trace(trace_dir)
    for _ in range(args.steps):
        state, metrics = compiled(state, batch, {})
    jax.block_until_ready(metrics)
    jax.profiler.stop_trace()

    total, by_cat, by_op = aggregate_ops(trace_dir)
    per_step = total / args.steps
    print(
        "device time: %.3f ms/step over %d steps (batch %d)"
        % (per_step / 1e3, args.steps, args.batch)
    )
    print("\n-- by category (us/step, % of device time) --")
    for cat, us in by_cat.most_common(args.top):
        print(
            "%-28s %10.1f  %5.1f%%"
            % (cat, us / args.steps, 100.0 * us / total)
        )
    print("\n-- top individual ops --")
    for name, us in by_op.most_common(args.top):
        print(
            "%-48s %10.1f  %5.1f%%"
            % (name[:48], us / args.steps, 100.0 * us / total)
        )
    print("\ntrace kept at %s" % trace_dir)


if __name__ == "__main__":
    main()
