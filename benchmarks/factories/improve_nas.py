"""Builds the improve_nas search through the program's own factory,
`research.improve_nas.trainer.trainer.build_search`, with the CLI's flags
set from a configuration's file."""

from __future__ import annotations


def build(config, traffic, seed, model_dir):
    """(estimator, the last step a call may name without ending the
    iteration)."""
    from absl import flags

    from research.improve_nas.trainer import trainer

    if traffic["iteration"] != 0:
        raise SystemExit(
            "benchmarks: factory improve_nas enters the search at iteration "
            "0 only, not at %d" % traffic["iteration"]
        )
    values = dict(config["flags"])
    values.update(
        model_dir=model_dir, seed=seed, batch_size=traffic["batch"],
        dataset="fake",
    )
    if not flags.FLAGS.is_parsed():
        flags.FLAGS(["benchmarks"])
    for key, value in values.items():
        setattr(flags.FLAGS, key, value)
    _, estimator = trainer.build_search(
        export_serving=False,
        iterations_per_loop=traffic["iterations_per_loop"],
    )
    steps = values["train_steps"] // values["boosting_iterations"]
    return estimator, steps - 1


def weight_shapes(config, member_name):
    """{path: shape} of one member's parameters, from the program's
    module run abstractly (for `count_flops`; no run of a cell uses it)."""
    import jax
    import numpy as np

    from benchmarks import ckpt_io
    from research.improve_nas.trainer import improve_nas

    sizes = config["members"][member_name]["sizes"]
    hparams = improve_nas.Hparams(
        num_cells=sizes["num_cells"],
        num_conv_filters=sizes["num_conv_filters"],
    )
    module = improve_nas.Builder(
        None, hparams, num_classes=config["sizes"]["num_classes"]
    ).build_subnetwork(config["sizes"]["num_classes"])
    images = np.zeros((2,) + tuple(config["sizes"]["image"]), np.float32)
    key = jax.random.PRNGKey(0)
    variables = jax.eval_shape(
        lambda: module.init(
            {"params": key, "dropout": key}, images, training=True
        )
    )
    return {
        path: leaf.shape
        for path, leaf in ckpt_io.flatten(
            jax.tree_util.tree_map(lambda x: x, dict(variables["params"]))
        ).items()
    }
