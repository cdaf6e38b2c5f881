"""Builds a search over one `adanet_tpu.models.moe_lm` candidate, as
`nasnet.t0` is built over NASNet: `Estimator.train` at iteration 0, one
candidate, `GrowStrategy`, a scalar mixture weight that is not trained.
The candidate's sizes are the configuration's member's."""

from __future__ import annotations


def model_config(sizes, vocab_size):
    """The program's `MoeLmConfig` of a member's `sizes`."""
    import jax.numpy as jnp

    from adanet_tpu.models import moe_lm

    return moe_lm.MoeLmConfig(
        vocab_size=vocab_size,
        hidden_size=sizes["hidden_size"],
        layer_types=tuple(sizes["layer_types"]),
        num_heads=sizes["num_heads"],
        num_kv_heads=sizes["num_kv_heads"],
        head_dim=sizes["head_dim"],
        sliding_window=sizes["sliding_window"],
        rope={
            kind: moe_lm.Rope(**{
                key: value for key, value in group.items()
                if key in moe_lm.Rope.__dataclass_fields__
            })
            for kind, group in sizes["rope_parameters"].items()
        },
        num_experts=sizes["router_width"],
        experts_held=tuple(sizes["experts_held"]),
        num_experts_per_tok=sizes["num_experts_per_tok"],
        expert_width=sizes["expert_width"],
        norm_topk_prob=sizes["norm_topk_prob"],
        rms_norm_eps=sizes["rms_norm_eps"],
        balance_loss_weight=sizes["balance_loss_weight"],
        compute_dtype=jnp.dtype(sizes["compute_dtype"]),
        attention_block=sizes["attention_block"],
        loss_block=sizes["loss_block"],
        **{
            key: sizes[key] for key in ("whole_logits_limit",)
            if key in sizes
        },
    )


def build(config, traffic, seed, model_dir):
    """(estimator, the last step a call may name without ending the
    iteration)."""
    import adanet_tpu
    from adanet_tpu.ensemble import (
        ComplexityRegularizedEnsembler,
        GrowStrategy,
        MixtureWeightType,
    )
    from adanet_tpu.models import moe_lm

    if traffic["iteration"] != 0 or len(config["members"]) != 1:
        raise SystemExit(
            "benchmarks: factory moe_lm enters the search at iteration 0 "
            "with one candidate, not at %d with %d"
            % (traffic["iteration"], len(config["members"]))
        )
    (name, member), = config["members"].items()
    sizes, vocab = member["sizes"], config["sizes"]["vocab_size"]
    generator = moe_lm.generator(
        model_config(sizes, vocab),
        learning_rate=sizes["learning_rate"],
        warmup_steps=sizes["warmup_steps"],
        weight_decay=sizes["weight_decay"],
        clip_norm=sizes["clip_norm"],
        name=name,
    )
    ensemble, search = config["ensemble"], config["search"]
    estimator = adanet_tpu.Estimator(
        head=adanet_tpu.MultiClassHead(vocab, top_k=0),
        subnetwork_generator=generator,
        max_iteration_steps=search["max_iteration_steps"],
        ensemblers=[
            ComplexityRegularizedEnsembler(
                optimizer=None,
                mixture_weight_type=MixtureWeightType.SCALAR,
                adanet_lambda=ensemble["adanet_lambda"],
                adanet_beta=ensemble["adanet_beta"],
            )
        ],
        ensemble_strategies=[GrowStrategy()],
        max_iterations=search["max_iterations"],
        adanet_loss_decay=ensemble["ema_decay"],
        force_grow=True,
        model_dir=model_dir,
        random_seed=seed,
        iterations_per_loop=traffic["iterations_per_loop"],
        export_serving=False,
    )
    return estimator, search["max_iteration_steps"] - 1
