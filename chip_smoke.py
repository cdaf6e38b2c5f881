"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, in ONE
process (a chip belongs to one process; nothing here starts a child that
needs it):

    python chip_smoke.py            one TPU chip: kernels, search, serve
    python chip_smoke.py --chips 4  four chips: the placement phase only

- `kernels`: each of the three Pallas kernels compiled (not interpreted)
  at a real NASNet-A (6@768) shape inside its rule, against its jnp
  reference, with the kernel present in the compiled program.
- `search`: the improve_nas trainer's own search (`trainer.build_search`)
  of NASNet-A (6@768) candidates (18 cells, 32 filters), batch 128 on the
  CIFAR-shaped fake provider, two boosting iterations of eight steps with
  `export_serving=True`, then `evaluate`.
- `serve`: `ServingFrontend(Batcher(ModelPool(model_dir)))` over the
  generation `search` published, cascade and all. The full ensemble
  (`BatcherConfig(cascade=False)`) answers requests landing in two AOT
  buckets bit-identically to the offline program on the same padded
  bucket; the default batcher then answers through the cascade.
- `placement` (`--chips 4` only): the two-candidate CNN search for one
  iteration under `RoundRobinStrategy()` and under default placement.

Every phase prints one JSON line (seconds, compile seconds, what was
asserted). A phase that fails is reported as failed, the remaining
phases still run for what they can tell, and the exit code is non-zero
with no result line. Only when every phase passed is the LAST line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reports it. Anything but a TPU is a failure,
except under `--rehearse`, which runs the same phases at toy size on the
CPU (Pallas interpreted, `--chips` virtual devices) and reports the
device it really ran on.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import sys
import tempfile
import time
import traceback

#: NASNet-A (6@768): 18 cells at 32 filters (trainer.py's defaults;
#: `NasNet_A_{cells/3}_{filters*24}` in the reference's naming). Nothing
#: is cut on the chip; a rehearsal runs the toy size.
NASNET_CELLS, NASNET_FILTERS, NASNET_BATCH = 18, 32, 128
TOY_CELLS, TOY_FILTERS, TOY_BATCH = 3, 4, 16
STEPS_PER_ITERATION = 8
BOOSTING_ITERATIONS = 2
#: Weights, data and requests are all made from this seed.
SEED = 0

#: RoundRobin trains every subnetwork on the fused path's batches and
#: updates (distributed/executor.py's staleness contract), so per-step
#: subnetwork losses agree to rounding: tests/test_distributed.py's
#: bound for float32 on the CPU. The chip needed no looser one (bfloat16
#: convolutions, the batch sharded over other device counts): the arms
#: agreed exactly there, as they do on four virtual devices (PERF.md).
SUBNETWORK_LOSS_RTOL = 2e-4
#: The ensemble's EMA signal runs one member-step ahead under RoundRobin
#: BY DESIGN, so its EMAs read lower while the loss still falls: by 2.1%
#: over the toy's 8 steps, by 0.48% over the chip's 24 (PERF.md). The
#: bounds sit at about twice and four times those readings; the repo's
#: own divergence bound (test_round_robin_fused_divergence_bounded) is
#: 10%.
EMA_REL_GAP_TOY, EMA_REL_GAP = 0.05, 0.02


class SmokeFailure(Exception):
    """A check of this script did not hold."""


def _check(condition, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


def _emit(obj) -> None:
    print(json.dumps(obj, sort_keys=True), flush=True)


class _CompileMeter:
    """Counts XLA compiles and persistent-cache traffic via jax.monitoring."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_seconds = 0.0
        self.cache_requests = 0
        self.cache_hits = 0
        self.cache_writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def snapshot(self):
        return dict(
            compiles=self.compiles,
            compile_seconds=self.compile_seconds,
            cache_requests=self.cache_requests,
            cache_hits=self.cache_hits,
            cache_writes=self.cache_writes,
        )


def _run_phase(name, fn, meter, failed) -> None:
    """Runs one phase and prints its line; a failure is recorded in
    `failed` (and so fails the run), never skipped."""
    before = meter.snapshot()
    start = time.perf_counter()
    line = {"phase": name}
    try:
        line.update(fn())
        line["ok"] = True
    except Exception as exc:
        traceback.print_exc()
        line["ok"] = False
        line["error"] = "%s: %s" % (type(exc).__name__, str(exc)[:2000])
        failed.append(name)
    after = meter.snapshot()
    line["seconds"] = round(time.perf_counter() - start, 3)
    for key, value in after.items():
        delta = value - before[key]
        line[key] = round(delta, 3) if isinstance(delta, float) else delta
    _emit(line)


@contextlib.contextmanager
def _spy(cls, method: str, record):
    """Reports every `(state, metrics)` a train-step method returns to
    `record(self, state, metrics)` while the block runs."""
    original = getattr(cls, method)

    def wrapper(self, *args, **kwargs):
        state, metrics = original(self, *args, **kwargs)
        record(self, state, metrics)
        return state, metrics

    setattr(cls, method, wrapper)
    try:
        yield
    finally:
        setattr(cls, method, original)


def _devices(tree):
    """The devices that hold any array of `tree`."""
    import jax

    return {
        device
        for leaf in jax.tree_util.tree_leaves(tree)
        if isinstance(leaf, jax.Array)
        for device in leaf.devices()
    }


def _device_ids(tree):
    return sorted(device.id for device in _devices(tree))


def _platforms(tree):
    return sorted({device.platform for device in _devices(tree)})


def _best(candidate_metrics) -> str:
    """The one candidate a `candidate-metrics-<t>.json` marks best."""
    (name,) = [n for n, e in candidate_metrics.items() if e["best"]]
    return name


def _all_finite(values) -> bool:
    return all(math.isfinite(float(v)) for v in values)


# ------------------------------------------------------------------ kernels


def _kernels_phase(meter, platform: str, interpret: bool, toy: bool):
    """The three Pallas kernels at one real NASNet shape each, against
    their jnp references within the interpret tests' tolerances."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from adanet_tpu.ops import cell_kernels, ensemble_kernels, sepconv_kernels

    rng = np.random.RandomState(0)
    report = {"interpret": interpret}

    def compile_and_run(fn, *args):
        """AOT-compiles `fn`, asserts the kernel is in the program when
        it is compiled for the chip, and returns (output, compile secs)."""
        start = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        secs = time.perf_counter() - start
        if not interpret:
            _check(
                "tpu_custom_call" in compiled.as_text(),
                "no tpu_custom_call in the compiled program of %r" % (fn,),
            )
        return jax.block_until_ready(compiled(*args)), secs

    # 1. Separable conv: the first reduction cell's stride-2 5x5 at the
    # 32x32 stage (filters 32 -> 64) — the shape stride 2 exists for.
    b, hw, c, k, f = (4, 8, 8, 5, 16) if toy else (NASNET_BATCH, 32, 32, 5, 64)
    _check(
        sepconv_kernels.kernel_takes((b, hw, hw, c), k, f, 2),
        "sep-conv smoke shape is outside the kernel's rule",
    )
    x = jnp.asarray(rng.randn(b, hw, hw, c), jnp.bfloat16)
    dw = jnp.asarray(rng.randn(k, k, 1, c) * 0.2, jnp.bfloat16)
    pw = jnp.asarray(rng.randn(1, 1, c, f) * 0.2, jnp.bfloat16)
    sep = functools.partial(
        sepconv_kernels.fused_sep_conv, stride=2, interpret=interpret
    )
    got, secs = compile_and_run(sep, x, dw, pw)
    want = sepconv_kernels.sep_conv_reference(x, dw, pw, 2)
    # The kernel accumulates in f32 and rounds once; the reference
    # multiplies in bf16. One bf16 ulp of the largest output apart at
    # most (what the interpreted kernel shows; compiled for the chip
    # the two agreed exactly, PERF.md), where
    # tests/test_sepconv_kernel.py allows 5e-2.
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32)))) or 1.0
    err = float(
        jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))
    )
    bound = 2.0**-7 * scale
    _check(got.shape == want.shape, "sep-conv output shape differs")
    _check(err <= bound, "sep-conv max abs error %g > %g" % (err, bound))
    report["sepconv"] = {
        "shape": [b, hw, hw, c, k, f, 2],
        "max_abs_err": err,
        "bound": bound,
        "compile_seconds": round(secs, 3),
    }

    # 2. Fused normal cell at the 32x32 stage: 192 channels in (6 x 32
    # concat), 32 filters. f32 in and out: bit-level math is the
    # interpret tests' contract, closeness is the chip's.
    b, hw, cin, f = (2, 8, 8, 4) if toy else (NASNET_BATCH, 32, 192, 32)
    spec = cell_kernels.NORMAL_CELL
    _check(
        cell_kernels.kernel_takes((b, hw, hw, cin), (b, hw, hw, cin), f, spec),
        "normal-cell smoke shape is outside the kernel's rule",
    )
    params = cell_kernels.init_cell_params(
        jax.random.PRNGKey(0), spec, cin, cin, f
    )
    prev = jnp.asarray(rng.randn(b, hw, hw, cin), jnp.float32)
    cur = jnp.asarray(rng.randn(b, hw, hw, cin), jnp.float32)
    cell = functools.partial(
        cell_kernels.fused_cell, spec=spec, interpret=interpret
    )
    got, secs = compile_and_run(cell, prev, cur, params)
    reference = functools.partial(cell_kernels.cell_reference, spec=spec)
    start = time.perf_counter()
    compiled_reference = (
        jax.jit(reference).lower(prev, cur, params).compile()
    )
    first_secs = time.perf_counter() - start
    want = compiled_reference(prev, cur, params)
    # The same (plain XLA) program compiled a second time, past jax's
    # in-memory caches: whether the persistent cache answered. It keeps
    # programs that took >= 1 s to compile, so a toy rehearsal misses.
    jax.clear_caches()
    hits_before = meter.cache_hits
    start = time.perf_counter()
    jax.jit(reference).lower(prev, cur, params).compile()
    report["second_identical_compile"] = {
        "cache_hit": meter.cache_hits > hits_before,
        "seconds": round(time.perf_counter() - start, 3),
        "first_seconds": round(first_secs, 3),
    }
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    # Interpreted, kernel and reference run the same helpers: identical.
    # Compiled for the chip they agreed exactly as well (PERF.md); the
    # bound leaves room for float32 rounding in another order and none
    # for a pass at lower precision (bfloat16 would be off by 4e-3).
    bound = 0.0 if interpret else 1e-5 * scale
    _check(got.shape == want.shape, "cell output shape differs")
    _check(err <= bound, "cell max abs error %g > %g" % (err, bound))
    report["cell"] = {
        "shape": [b, hw, hw, cin, f],
        "max_abs_err": err,
        "bound": bound,
        "compile_seconds": round(secs, 3),
    }
    _check(
        not cell_kernels.kernel_takes(
            (b, hw, hw, cin), (b, hw, hw, cin), f, cell_kernels.REDUCTION_CELL
        ),
        "the reduction cell is inside the rule; revisit the smoke",
    )

    # 3. Mixture-weight combine: two members' logits at the search's
    # batch and class count. The kernel picks interpret mode itself.
    _check(
        ensemble_kernels.kernel_is_interpreted() == (platform != "tpu"),
        "combine kernel's interpret choice does not match the platform",
    )
    n, b, c = 2, (16 if toy else NASNET_BATCH), 10
    logits = jnp.asarray(rng.randn(n, b, c), jnp.float32)
    weights = jnp.asarray(rng.rand(n), jnp.float32)
    bias = jnp.asarray(rng.randn(c), jnp.float32)
    got, secs = compile_and_run(
        ensemble_kernels.fused_weighted_combine, logits, weights, bias
    )
    want = ensemble_kernels._combine_reference(logits, weights, bias)
    err = float(jnp.max(jnp.abs(got - want)))
    _check(err <= 1e-5, "combine max abs error %g" % err)
    report["combine"] = {
        "shape": [n, b, c],
        "max_abs_err": err,
        "compile_seconds": round(secs, 3),
    }
    return report


# ------------------------------------------------------------------- search


def _search_phase(model_dir, platform: str, toy: bool):
    import jax

    from adanet_tpu.core.iteration import Iteration
    from adanet_tpu.serving import publisher
    from research.improve_nas.trainer import trainer
    from tools import ckpt_fsck

    num_cells, filters, batch = (
        (TOY_CELLS, TOY_FILTERS, TOY_BATCH)
        if toy
        else (NASNET_CELLS, NASNET_FILTERS, NASNET_BATCH)
    )
    train_steps = STEPS_PER_ITERATION * BOOSTING_ITERATIONS
    trainer.FLAGS(
        [
            "chip_smoke",
            "--model_dir=%s" % model_dir,
            "--dataset=fake",
            "--batch_size=%d" % batch,
            "--num_cells=%d" % num_cells,
            "--num_conv_filters=%d" % filters,
            "--boosting_iterations=%d" % BOOSTING_ITERATIONS,
            "--train_steps=%d" % train_steps,
            "--seed=%d" % SEED,
        ]
    )
    provider, estimator = trainer.build_search(export_serving=True)

    steps = []

    def record(iteration, state, metrics):
        steps.append(
            {
                "iteration": iteration.iteration_number,
                "frozen_members": len(iteration.frozen_subnetworks),
                "candidates": iteration.candidate_names(),
                "losses": {
                    k: float(v) for k, v in jax.device_get(metrics).items()
                },
                "state_platforms": _platforms(state),
            }
        )

    with _spy(Iteration, "train_step", record):
        estimator.train(
            provider.get_input_fn("train"), max_steps=train_steps
        )
    metrics = estimator.evaluate(provider.get_input_fn("test"))

    _check(len(steps) == train_steps, "saw %d train steps" % len(steps))
    for step in steps:
        _check(
            step["losses"] and _all_finite(step["losses"].values()),
            "non-finite loss at a train step: %r" % step,
        )
        _check(
            step["state_platforms"] == [platform],
            "train state on %r, not on the %s"
            % (step["state_platforms"], platform),
        )
    first, second = steps[0], steps[-1]
    _check(
        [s["iteration"] for s in steps]
        == [0] * STEPS_PER_ITERATION + [1] * STEPS_PER_ITERATION,
        "steps did not split into two iterations",
    )
    _check(first["frozen_members"] == 0, "iteration 0 had frozen members")
    # Iteration 1 trained on top of iteration 0's frozen winner: one
    # frozen member under it, and the previous ensemble among its
    # candidates.
    _check(
        second["frozen_members"] == 1
        and any(name.startswith("t0_") for name in second["candidates"])
        and any(name.startswith("t1_") for name in second["candidates"]),
        "iteration 1 did not train on iteration 0's winner: %r" % second,
    )
    candidate_metrics = {
        t: estimator.candidate_metrics(t) for t in range(BOOSTING_ITERATIONS)
    }
    for t, entries in candidate_metrics.items():
        _check(
            sum(1 for entry in entries.values() if entry["best"]) == 1,
            "iteration %d froze no single winner" % t,
        )
        _check(
            all(
                entry["adanet_loss_ema"] is not None and not entry["dead"]
                for entry in entries.values()
            ),
            "iteration %d has a dead or non-finite candidate" % t,
        )
    with open(os.path.join(model_dir, "architecture-1.json")) as f:
        architecture = json.load(f)
    _check(
        [m["iteration_number"] for m in architecture["subnetworks"]]
        == [0, 1],
        "architecture JSON does not list both members: %r" % architecture,
    )
    _check(
        _all_finite(
            v for v in metrics.values() if isinstance(v, (int, float))
        ),
        "non-finite evaluation metric: %r" % metrics,
    )

    # The serving export swallows its own failures by design (the search
    # must outlive the serving plane): hold it to account here.
    generations = [t for t, _ in publisher.list_generations(model_dir)]
    _check(generations == [0, 1], "published generations: %r" % generations)
    with open(
        os.path.join(
            publisher.generation_dir(model_dir, 1), "serving_signature.json"
        )
    ) as f:
        signature = json.load(f)
    _check(
        platform in signature["platforms"]
        and signature["multi_platform_fallback_reason"] is None
        and signature["polymorphic_fallback_reason"] is None,
        "serving export degraded: %r"
        % {k: v for k, v in signature.items() if "platform" in k or "reason" in k},
    )
    # The Estimator's default: a two-member generation publishes a
    # cascade (its first member, calibrated against the ensemble).
    cascade = signature.get("cascade")
    _check(cascade is not None, "generation 1 published no cascade")

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = ckpt_fsck.main([model_dir, "--json"])
    fsck = json.loads(out.getvalue().strip().splitlines()[-1])
    _check(rc == 0 and fsck["ok"], "ckpt_fsck: rc %r, %r" % (rc, fsck))
    _check(
        fsck["serving"]["selected_generation"] == 1,
        "fsck would serve generation %r"
        % fsck["serving"]["selected_generation"],
    )

    memory = jax.devices()[0].memory_stats() or {}
    return {
        "model": "NASNet-A (%d@%d)" % (num_cells // 3, filters * 24),
        "num_cells": num_cells,
        "batch": batch,
        "train_steps": train_steps,
        "first_losses": first["losses"],
        "last_losses": second["losses"],
        "best": {t: _best(r) for t, r in candidate_metrics.items()},
        "members": architecture["subnetworks"],
        "eval": {
            k: v for k, v in metrics.items() if isinstance(v, (int, float, str))
        },
        "state_platforms": second["state_platforms"],
        "fsck_ok": fsck["ok"],
        "cascade": {
            k: cascade.get(k) for k in ("program", "threshold", "source")
        },
        "device_peak_bytes": memory.get("peak_bytes_in_use"),
        "device_bytes_limit": memory.get("bytes_limit"),
    }


# -------------------------------------------------------------------- serve


def _serve_phase(model_dir):
    import functools

    import jax
    import numpy as np

    from adanet_tpu import serving
    from adanet_tpu.core import export as export_lib
    from adanet_tpu.serving.batcher import bucket_for, pad_batch

    _check(
        serving.list_generations(model_dir),
        "`search` published no generation to serve",
    )
    pool = serving.ModelPool(model_dir)
    rng = np.random.RandomState(SEED)

    def serve(batcher, row_counts, check):
        """Sends one request per row count through a frontend over
        `batcher`; `check(features, result)` judges each answer."""
        frontend = serving.ServingFrontend(batcher).start()
        try:
            deadline = time.monotonic() + 900.0
            while pool.active is None and time.monotonic() < deadline:
                time.sleep(0.05)
            _check(pool.active is not None, "no generation passed the gate")
            _check(
                pool.active.iteration_number == 1 and pool.rollbacks == 0,
                "pool serves generation %r after %d rollbacks: %r"
                % (pool.active.iteration_number, pool.rollbacks, pool.events),
            )
            for rows in row_counts:
                features = {
                    "image": rng.randn(rows, 32, 32, 3).astype(np.float32)
                }
                # A bucket's first request waits for its compile; the
                # default 2 s deadline is a latency budget, not this.
                result = frontend.submit(features, deadline_secs=900.0)
                _check(
                    result.ok and result.generation == 1,
                    "request of %d rows: %s %r"
                    % (rows, result.status, result.error),
                )
                check(features, result)
        finally:
            frontend.drain()
        counters = dict(frontend.counters)
        _check(counters.get("error", 0) == 0, "error statuses: %r" % counters)
        return counters

    # 1. The full ensemble, held to the offline program bit for bit. (A
    # cascade answers confident rows from its cheap member by design,
    # so this batcher is told not to use the one that was published.)
    full = serving.Batcher(pool, serving.BatcherConfig(cascade=False))
    served = []

    @functools.lru_cache(maxsize=None)
    def offline():
        return export_lib.load_serving_program(pool.active.path)

    def bit_identical(features, result):
        rows = features["image"].shape[0]
        bucket = bucket_for(rows, full.config.bucket_sizes)
        padded, _ = pad_batch([features], bucket)
        want = jax.device_get(offline()(padded))
        _check(
            jax.tree_util.tree_structure(want)
            == jax.tree_util.tree_structure(result.outputs),
            "served output tree differs from the offline program's",
        )
        for got_leaf, want_leaf in zip(
            jax.tree_util.tree_leaves(result.outputs),
            jax.tree_util.tree_leaves(want),
        ):
            _check(
                np.array_equal(
                    np.asarray(got_leaf), np.asarray(want_leaf)[:rows]
                ),
                "request of %d rows (bucket %d) is not bit-identical "
                "to the offline program" % (rows, bucket),
            )
        served.append({"rows": rows, "bucket": bucket})

    statuses = serve(full, (1, 3, 4, 1, 3), bit_identical)
    _check(
        len({s["bucket"] for s in served}) >= 2,
        "requests landed in fewer than two buckets",
    )

    # 2. The default batcher: the published cascade on the serve path.
    default = serving.Batcher(pool)
    stats = default.cascade_stats()
    _check(
        stats["published"] and stats["active"],
        "the default batcher serves no cascade: %r" % stats,
    )
    levels = []

    def answered_by_the_cascade(features, result):
        rows = features["image"].shape[0]
        _check(
            result.cascade_level in (0, 1),
            "cascade level %r" % (result.cascade_level,),
        )
        for leaf in jax.tree_util.tree_leaves(result.outputs):
            leaf = np.asarray(leaf)
            _check(
                leaf.shape[0] == rows and np.all(np.isfinite(leaf)),
                "cascade answer of shape %r for %d rows, or not finite"
                % (leaf.shape, rows),
            )
        levels.append(result.cascade_level)

    cascade_statuses = serve(default, (1, 4), answered_by_the_cascade)
    stats = default.cascade_stats()
    return {
        "generation": 1,
        "requests": served,
        "statuses": statuses,
        "bit_identical": True,
        "cascade": {
            "statuses": cascade_statuses,
            "levels": levels,
            "threshold": stats["threshold"],
            "row_fallthrough_rate": stats["row_fallthrough_rate"],
            "rollback": stats["rollback"],
        },
    }


# ---------------------------------------------------------------- placement


def _placement_phase(root, toy: bool):
    """One iteration of the two-candidate CNN search under RoundRobin
    and under default placement, same seed and batches."""
    import jax
    import numpy as np
    import optax

    import adanet_tpu
    from adanet_tpu.core.iteration import Iteration
    from adanet_tpu.distributed import RoundRobinStrategy
    from adanet_tpu.distributed.executor import RoundRobinExecutor
    from adanet_tpu.ensemble import ComplexityRegularizedEnsembler
    from adanet_tpu.examples.simple_cnn import CNNBuilder
    from adanet_tpu.subnetwork import SimpleGenerator

    batch, channels, steps, ema_rel_gap = (
        (16, 8, 8, EMA_REL_GAP_TOY) if toy else (256, 64, 24, EMA_REL_GAP)
    )
    rng = np.random.RandomState(SEED)
    # Learnable data (each class a fixed template under noise). What
    # separates the candidates is `adanet_lambda` below: over a few
    # dozen steps their losses differ by less than step noise, and
    # "same best candidate" would be a coin toss; the complexity
    # penalty (sqrt(2) vs sqrt(3) blocks) puts 0.16 between them.
    templates = rng.randn(10, 32, 32, 3).astype(np.float32)
    batches = []
    for _ in range(steps):
        labels = rng.randint(0, 10, size=(batch,)).astype(np.int32)
        images = templates[labels] + 0.5 * rng.randn(batch, 32, 32, 3)
        batches.append(({"image": images.astype(np.float32)}, labels))

    def run(name, placement_strategy, spied_cls):
        seen = []

        def record(_, state, metrics):
            seen.append(
                {
                    "subnetworks": {
                        n: _device_ids(s)
                        for n, s in state.subnetworks.items()
                    },
                    "ensembles": _device_ids(
                        (state.ensembles, state.candidates)
                    ),
                    "losses": {
                        k: float(v)
                        for k, v in jax.device_get(metrics).items()
                        if k.startswith("subnetwork_loss/")
                    },
                }
            )

        estimator = adanet_tpu.Estimator(
            head=adanet_tpu.MultiClassHead(10),
            subnetwork_generator=SimpleGenerator(
                [
                    CNNBuilder(num_blocks=2, channels=channels),
                    CNNBuilder(num_blocks=3, channels=channels),
                ]
            ),
            max_iteration_steps=steps,
            ensemblers=[
                ComplexityRegularizedEnsembler(
                    optimizer=optax.sgd(0.01), adanet_lambda=0.5
                )
            ],
            max_iterations=1,
            model_dir=os.path.join(root, name),
            random_seed=SEED,
            placement_strategy=placement_strategy,
        )
        with _spy(spied_cls, "train_step", record):
            estimator.train(lambda: iter(batches), max_steps=steps)
        _check(len(seen) == steps, "%s: saw %d steps" % (name, len(seen)))
        return seen, estimator.candidate_metrics(0)

    default_steps, default_metrics = run("default", None, Iteration)
    rr_steps, rr_metrics = run(
        "round_robin", RoundRobinStrategy(), RoundRobinExecutor
    )

    _check(
        set(default_metrics) == set(rr_metrics)
        and _best(default_metrics) == _best(rr_metrics),
        "arms chose different candidates: %r vs %r"
        % (default_metrics, rr_metrics),
    )
    emas = {}
    for name, entry in default_metrics.items():
        want, got = entry["adanet_loss_ema"], rr_metrics[name]["adanet_loss_ema"]
        _check(want is not None and got is not None, "non-finite EMA")
        _check(
            abs(want - got) <= ema_rel_gap * abs(want),
            "EMA of %s: default %g, RoundRobin %g" % (name, want, got),
        )
        emas[name] = {"default": want, "round_robin": got}
    worst = 0.0
    for d_step, r_step in zip(default_steps, rr_steps):
        for key, want in d_step["losses"].items():
            got = r_step["losses"][key]
            _check(
                math.isfinite(want) and math.isfinite(got),
                "non-finite subnetwork loss",
            )
            worst = max(worst, abs(want - got) / max(abs(want), 1e-6))
    _check(
        worst <= SUBNETWORK_LOSS_RTOL,
        "subnetwork losses differ by rel %g > %g"
        % (worst, SUBNETWORK_LOSS_RTOL),
    )
    rr_devices = rr_steps[-1]["subnetworks"]
    groups = list(rr_devices.values())
    _check(
        all(groups)
        and all(
            not set(a) & set(b)
            for i, a in enumerate(groups)
            for b in groups[i + 1 :]
        ),
        "RoundRobin candidates share chips: %r" % rr_devices,
    )
    return {
        "batch": batch,
        "steps": steps,
        "best": _best(default_metrics),
        "ema": emas,
        "ema_rel_gap_bound": ema_rel_gap,
        "subnetwork_loss_max_rel_diff": worst,
        "subnetwork_loss_rtol": SUBNETWORK_LOSS_RTOL,
        "devices_holding_state": {
            "default": {
                "subnetworks": default_steps[-1]["subnetworks"],
                "ensembles": default_steps[-1]["ensembles"],
            },
            "round_robin": {
                "subnetworks": rr_devices,
                "ensembles": rr_steps[-1]["ensembles"],
            },
        },
    }


# --------------------------------------------------------------------- main

def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips",
        type=int,
        choices=(1, 4),
        default=1,
        help="1: kernels, search, serve. 4: the placement phase only.",
    )
    parser.add_argument(
        "--rehearse",
        action="store_true",
        help="same phases at toy size on the CPU (--chips virtual "
        "devices, Pallas interpreted); reports the CPU as its device",
    )
    args = parser.parse_args(argv)

    import jax

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.chips)
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if not args.rehearse and device["platform"] != "tpu":
        sys.exit(
            "chip_smoke: needs a TPU, found %r (--rehearse runs the toy "
            "size on the CPU)" % (device,)
        )
    if device["count"] != args.chips:
        sys.exit(
            "chip_smoke: --chips %d but JAX reports %d devices"
            % (args.chips, device["count"])
        )

    from adanet_tpu.ops import native_augment
    from adanet_tpu.utils.compile_cache_dir import enable_persistent_cache

    meter = _CompileMeter()
    toy = args.rehearse
    _emit(
        {
            "phase": "setup",
            "device": device,
            "rehearsal": args.rehearse,
            "compile_cache_dir": enable_persistent_cache(),
            "compile_cache_dir_from_env": "JAX_COMPILATION_CACHE_DIR"
            in os.environ,
            # -1: no cap. Under a cap smaller than what one run writes
            # (`cache_writes` below), the next run hits nothing.
            "compile_cache_max_bytes": jax.config.jax_compilation_cache_max_size,
            "augment": (
                "native" if native_augment.get_lib() is not None else "numpy"
            ),
        }
    )

    failed = []
    platform = device["platform"]
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        if args.chips == 4:
            _run_phase(
                "placement",
                lambda: _placement_phase(root, toy),
                meter,
                failed,
            )
        else:
            model_dir = os.path.join(root, "model")
            _run_phase(
                "kernels",
                lambda: _kernels_phase(
                    meter, platform, platform != "tpu", toy
                ),
                meter,
                failed,
            )
            _run_phase(
                "search",
                lambda: _search_phase(model_dir, platform, toy),
                meter,
                failed,
            )
            _run_phase(
                "serve",
                lambda: _serve_phase(model_dir),
                meter,
                failed,
            )
    if failed:
        sys.exit("chip_smoke: failed phases: %s" % ", ".join(failed))
    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
