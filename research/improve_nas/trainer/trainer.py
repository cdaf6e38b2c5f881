"""improve_nas trainer CLI.

Analogue of the reference trainer entry point
(reference: research/improve_nas/trainer/trainer.py:42-181 and
adanet_improve_nas.py:111-222): absl flags configure the AdaNet NASNet
search (boosting iterations, adanet lambda/beta, knowledge distillation,
learned mixture weights, generator choice) and run
train -> evaluate on CIFAR-10/100 or fake data.

Example (fake data smoke run):
    python -m research.improve_nas.trainer.trainer \
        --dataset=fake --num_cells=3 --num_conv_filters=4 \
        --boosting_iterations=2 --train_steps=40 --batch_size=16
"""

from __future__ import annotations

import json

from absl import app, flags, logging

import optax

import adanet_tpu
from adanet_tpu.ensemble import (
    ComplexityRegularizedEnsembler,
    GrowStrategy,
    MixtureWeightType,
)
from adanet_tpu.utils.compile_cache_dir import enable_persistent_cache

from research.improve_nas.trainer import fake_data, improve_nas, optimizer

FLAGS = flags.FLAGS

flags.DEFINE_string("model_dir", "/tmp/improve_nas", "Model directory.")
flags.DEFINE_string(
    "dataset", "fake", "Dataset: cifar10, cifar100, or fake."
)
flags.DEFINE_string("data_dir", "", "Directory with the CIFAR archives.")
flags.DEFINE_integer("batch_size", 32, "Per-step batch size.")
flags.DEFINE_integer("train_steps", 10000, "Total training steps.")
flags.DEFINE_integer(
    "boosting_iterations", 10, "AdaNet boosting iterations."
)
flags.DEFINE_float("adanet_lambda", 0.0, "Complexity penalty lambda.")
flags.DEFINE_float("adanet_beta", 0.0, "Uniform L1 penalty beta.")
flags.DEFINE_bool(
    "learn_mixture_weights", False, "Train mixture weights."
)
flags.DEFINE_string(
    "knowledge_distillation",
    "none",
    "Distillation: none, adaptive, or born_again.",
)
flags.DEFINE_string(
    "generator", "simple", "Search space: simple or dynamic."
)
flags.DEFINE_integer("num_cells", 18, "NASNet cells (multiple of 3).")
flags.DEFINE_integer("num_conv_filters", 32, "NASNet base filters.")
flags.DEFINE_float("initial_learning_rate", 0.025, "Initial LR.")
flags.DEFINE_string(
    "optimizer", "momentum", "Optimizer: sgd, momentum, rmsprop, adam."
)
flags.DEFINE_string(
    "learning_rate_schedule", "cosine", "Schedule: constant or cosine."
)
flags.DEFINE_bool("force_grow", True, "Force ensemble growth.")
flags.DEFINE_integer("seed", 42, "Random seed.")


def _provider():
    if FLAGS.dataset == "fake":
        # CIFAR-shaped (32x32x3, 10 classes), so a fake-data run builds
        # the same programs a CIFAR-10 run does.
        return fake_data.FakeImageProvider(
            num_examples=max(64, FLAGS.batch_size * 4),
            image_size=32,
            num_classes=10,
            batch_size=FLAGS.batch_size,
            seed=FLAGS.seed,
        )
    if FLAGS.dataset == "cifar10":
        from research.improve_nas.trainer import cifar10

        return cifar10.Provider(FLAGS.data_dir, FLAGS.batch_size, FLAGS.seed)
    if FLAGS.dataset == "cifar100":
        from research.improve_nas.trainer import cifar100

        return cifar100.Provider(FLAGS.data_dir, FLAGS.batch_size, FLAGS.seed)
    raise ValueError("Unknown dataset %r" % FLAGS.dataset)


def build_search(**estimator_kwargs):
    """The data provider and the `Estimator` the parsed flags describe.

    `main` and `chip_smoke.py` both build the search here, so the smoke
    drives exactly what the CLI runs. `estimator_kwargs` reach the
    `Estimator` for options the CLI has no flag for (the smoke passes
    `export_serving=True`).
    """
    provider = _provider()
    max_iteration_steps = max(
        1, FLAGS.train_steps // FLAGS.boosting_iterations
    )

    hparams = improve_nas.Hparams(
        num_cells=FLAGS.num_cells,
        num_conv_filters=FLAGS.num_conv_filters,
        knowledge_distillation=improve_nas.KnowledgeDistillation(
            FLAGS.knowledge_distillation
        ),
        initial_learning_rate=FLAGS.initial_learning_rate,
        total_training_steps=FLAGS.train_steps,
    )
    optimizer_fn = optimizer.fn_with_name(
        FLAGS.optimizer,
        learning_rate_schedule=FLAGS.learning_rate_schedule,
        cosine_decay_steps=max_iteration_steps,
    )
    generator_cls = (
        improve_nas.DynamicGenerator
        if FLAGS.generator == "dynamic"
        else improve_nas.Generator
    )
    generator = generator_cls(
        optimizer_fn=optimizer_fn,
        hparams=hparams,
        seed=FLAGS.seed,
        num_classes=provider.num_classes,
    )

    mixture_optimizer = (
        optax.sgd(0.01) if FLAGS.learn_mixture_weights else None
    )
    estimator = adanet_tpu.Estimator(
        head=adanet_tpu.MultiClassHead(provider.num_classes),
        subnetwork_generator=generator,
        max_iteration_steps=max_iteration_steps,
        ensemblers=[
            ComplexityRegularizedEnsembler(
                optimizer=mixture_optimizer,
                mixture_weight_type=MixtureWeightType.SCALAR,
                adanet_lambda=FLAGS.adanet_lambda,
                adanet_beta=FLAGS.adanet_beta,
            )
        ],
        ensemble_strategies=[GrowStrategy()],
        max_iterations=FLAGS.boosting_iterations,
        force_grow=FLAGS.force_grow,
        model_dir=FLAGS.model_dir,
        random_seed=FLAGS.seed,
        **estimator_kwargs,
    )
    return provider, estimator


def main(argv):
    del argv
    logging.info("Compile cache: %s", enable_persistent_cache())
    provider, estimator = build_search()
    estimator.train(
        provider.get_input_fn("train"), max_steps=FLAGS.train_steps
    )
    metrics = estimator.evaluate(provider.get_input_fn("test"))
    logging.info("Final metrics: %s", metrics)
    print(
        json.dumps(
            {
                k: v
                for k, v in metrics.items()
                if isinstance(v, (int, float, str))
            }
        )
    )


if __name__ == "__main__":
    app.run(main)
