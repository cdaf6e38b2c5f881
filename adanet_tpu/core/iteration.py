"""The per-iteration engine: build candidates, jit one combined train step.

TPU-native re-design of the reference `_IterationBuilder`
(reference: adanet/core/iteration.py:506-816). The reference builds one big
TF graph holding every candidate and drives training through session hooks;
here each iteration compiles to **one jit-ed XLA program** containing every
candidate's forward/backward plus every ensemble's mixture-weight update.
XLA overlaps the independent candidate computations and fuses the
mixture-weight combine into the surrounding graph — the functional analogue
of training all candidates "in parallel in a single graph", with no hooks,
variable scoping, or monkey-patching (compare
adanet/core/ensemble_builder.py:143-209).

Key mappings:
- per-spec `iteration_step` variable -> `step` field in each train state
- `_TrainingLimitHook` / `_NanLossHook`  -> finite-guarded in-jit updates +
  host checks on the returned losses (quarantine, not crash)
- adanet-loss EMA variables            -> `CandidateState` pytree
- best-candidate muxing (`tf.stack`)   -> host-side argmin over fetched EMAs
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import re
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import flax
import jax
import jax.numpy as jnp
import optax

from flax import struct

from adanet_tpu.core import candidate as candidate_lib
from adanet_tpu.core.compile_cache import CachedStep
from adanet_tpu.core.architecture import Architecture
from adanet_tpu.core.frozen import (
    FrozenEnsemble,
    FrozenSubnetwork,
    FrozenWeightedSubnetwork,
)
from adanet_tpu.utils import precision
from adanet_tpu.utils.trees import tree_finite, tree_where

# Member references inside an ensemble spec: ("new", builder_name) for a
# subnetwork trained this iteration, ("frozen", index) for a previous member.
_NEW = "new"
_FROZEN = "frozen"


def scope_name(kind: str, name: str) -> str:
    """The `jax.named_scope` of one part of the step: `<kind>.<name>`.

    The scope lands in every device op's `op_name` (Flax supplies the
    module path beneath it), which is how a profile is split by
    candidate, ensemble and optimizer (`benchmarks/scope_reduce.py`). A
    scope is ONE path component: whatever would read as a separator or
    a transform wrapper (`/`, `(`, `)`, `:`) becomes `_`.
    """
    return "%s.%s" % (kind, re.sub(r"[^A-Za-z0-9_.\-]", "_", name))


@struct.dataclass
class SubnetworkTrainState:
    """Train state for one candidate subnetwork."""

    variables: Any  # full Flax variable collections ({"params": ..., ...})
    opt_state: Any
    step: jnp.ndarray
    dead: jnp.ndarray


@struct.dataclass
class EnsembleTrainState:
    """Train state for one ensemble candidate's ensembler params."""

    params: Any
    opt_state: Any


@struct.dataclass
class IterationState:
    """All device state for one AdaNet iteration (a single pytree).

    The analogue of the reference's per-iteration variable set + per-iteration
    `tf.train.Checkpoint` (reference: adanet/core/iteration.py:1188-1230).
    """

    subnetworks: Dict[str, SubnetworkTrainState]
    ensembles: Dict[str, EnsembleTrainState]
    candidates: Dict[str, candidate_lib.CandidateState]
    frozen: List[Any]  # variable collections of frozen members
    iteration_step: jnp.ndarray
    rng: Any


def abstract_state(state):
    """The state's template: every leaf as its shape and dtype alone.

    What `Iteration.state_template` returns and what a real `init_state`
    records of its result, through this one function, so the two compare
    equal (a weak type or a sharding is no part of a state's structure).
    """
    return jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(
            jnp.shape(leaf), jnp.result_type(leaf)
        ),
        state,
    )


def _batch_signature(sample_batch):
    """Hashable structure, shapes and dtypes of a sample batch."""
    leaves, treedef = jax.tree_util.tree_flatten(sample_batch)
    return treedef, tuple(
        (jnp.shape(leaf), str(jnp.result_type(leaf))) for leaf in leaves
    )


@contextlib.contextmanager
def _must_trace(kind: str, name: str):
    """Names whose initialization cannot run under `jax.eval_shape`.

    `state_template` traces the very code the real init runs, and does
    not fall back to the eager init when that fails: a `float(...)`,
    `np.asarray(...)` or `.item()` on a value of the state is an error
    that says where.
    """
    try:
        yield
    except jax.errors.JAXTypeError as exc:
        raise TypeError(
            "Initializing %s %r read the VALUE of a traced array. "
            "Iteration.state_template runs init_state under "
            "jax.eval_shape, so modules, initializers, optimizers and "
            "ensemblers may read shapes and dtypes only: %s"
            % (kind, name, exc)
        ) from exc


@dataclasses.dataclass(frozen=True)
class SubnetworkSpec:
    """Static (host-side) description of one subnetwork candidate."""

    name: str
    builder: Any
    module: Any
    tx: Any  # optax GradientTransformation


@dataclasses.dataclass(frozen=True)
class EnsembleSpec:
    """Static description of one ensemble candidate × ensembler.

    `track_ema=False` marks the carried-over previous-ensemble candidate: its
    loss EMA stays frozen at the value it finished the previous iteration
    with, matching the reference's rebuilt (read-only) moving average
    (reference: adanet/core/candidate.py:104-127 with rebuilding=True).
    `initial_params` carries the previous winner's learned ensembler params.
    """

    name: str
    candidate_name: str
    ensembler: Any
    tx: Optional[Any]
    members: Tuple[Tuple[str, Any], ...]  # (_NEW, name) | (_FROZEN, index)
    architecture: Architecture
    track_ema: bool = True
    initial_params: Optional[Any] = None
    initial_ema: Optional[float] = None


def _complexity_regularization(ensemble):
    """The ensemble's complexity penalty; 0 for parameterless ensembles."""
    return getattr(ensemble, "complexity_regularization", 0.0)


class _ModuleHandle:
    """Hashable-by-identity wrapper for a flax module.

    Modules carrying dict attributes (e.g. multi-head logits dims) are
    unhashable, so they cannot be jit static arguments directly. Identity
    semantics are exactly right here: jit's cache entry holds the handle,
    the handle holds the module, so the id stays valid for the cache's
    lifetime.
    """

    __slots__ = ("module",)

    def __init__(self, module):
        self.module = module

    def __hash__(self):
        return id(self.module)

    def __eq__(self, other):
        return (
            isinstance(other, _ModuleHandle)
            and other.module is self.module
        )


@functools.partial(jax.jit, static_argnums=0)
def _frozen_record_fields(handle, variables, features):
    """Replicated record fields (complexity, shared) of one subnetwork.

    Module-level with the flax module static (via `_ModuleHandle`) so
    jit's cache keys on a stable function identity: freezing N members
    across T iterations compiles once per module instead of once per
    call (JL003). The flip side of caching on a permanent function is
    retention: each distinct module object pins one cache entry (handle,
    module, small executable) until jax's global cache evicts it. That
    is one entry per freeze — bounded by the boosting iteration count —
    not per-batch state; call `_frozen_record_fields.clear_cache()` if a
    long-lived process ever needs to reclaim it.
    """
    out = handle.module.apply(variables, features, training=False)
    return out.complexity, out.shared


def split_example_weights(features, weight_key, require=True):
    """Splits per-example weights out of a features mapping.

    The analogue of the reference's `weight_column` on canned heads
    (reference: adanet/core/ensemble_builder.py:571-583, where
    `head.create_estimator_spec` extracts the weight column from features):
    when `weight_key` is set, `features` must be a mapping containing that
    key; the returned features have the key removed (weights never feed the
    model) and the weights ride alongside into every head loss/metric call.

    Returns `(model_features, weights)`; `weights` is None when
    `weight_key` is None. With `require=False` a missing key is tolerated
    (serving-time features carry no weights).
    """
    if weight_key is None:
        return features, None
    if not isinstance(features, Mapping) or weight_key not in features:
        if not require:
            return features, None
        raise ValueError(
            "weight_key=%r is set but the features batch %s; pass "
            "features as a dict holding the per-example weight column."
            % (
                weight_key,
                "is not a mapping"
                if not isinstance(features, Mapping)
                else "with keys %s does not contain it" % sorted(features),
            )
        )
    model_features = {k: v for k, v in features.items() if k != weight_key}
    return model_features, features[weight_key]


@struct.dataclass
class TrainLossContext:
    """Teacher signals available to `Builder.build_subnetwork_loss`.

    `previous_ensemble_logits`: the frozen previous ensemble's logits on the
    current batch (ADAPTIVE knowledge distillation; reference:
    research/improve_nas/trainer/improve_nas.py:166-172).
    `previous_subnetwork_logits`: the most recent frozen member's logits
    (BORN_AGAIN distillation; reference: improve_nas.py:174-180).
    """

    previous_ensemble_logits: Any = None
    previous_subnetwork_logits: Any = None


class Iteration:
    """One AdaNet iteration: candidates, jitted steps, and state management."""

    def __init__(
        self,
        iteration_number: int,
        subnetwork_specs: Sequence[SubnetworkSpec],
        ensemble_specs: Sequence[EnsembleSpec],
        frozen_subnetworks: Sequence[FrozenSubnetwork],
        head,
        adanet_loss_decay: float = 0.9,
        previous_ensemble: Optional[FrozenEnsemble] = None,
        collect_summaries: bool = True,
        compile_cache=None,
        weight_key: Optional[str] = None,
        step_compute_dtype=None,
    ):
        if not ensemble_specs:
            raise ValueError("An iteration needs at least one ensemble spec.")
        self.iteration_number = iteration_number
        self.subnetwork_specs = list(subnetwork_specs)
        self.ensemble_specs = list(ensemble_specs)
        self.frozen_subnetworks = list(frozen_subnetworks)
        self.head = head
        # weight_column analogue: per-example weights extracted from the
        # features mapping under this key feed every head loss/metric.
        self.weight_key = weight_key
        # End-to-end bf16 policy (utils/precision.py): when set, float
        # FEATURES are downcast to this dtype once at the train-step
        # boundary — models then run bf16 from the first conv without
        # re-casting per op. Labels/weights stay f32 (loss inputs), as
        # do params and optimizer state (they are never touched here).
        self.step_compute_dtype = precision.resolve_dtype(
            step_compute_dtype
        )
        self.adanet_loss_decay = float(adanet_loss_decay)
        # When False, builder summary hooks are traced out of the jitted
        # step entirely (no wasted device compute when nothing is written).
        self.collect_summaries = bool(collect_summaries)
        self.previous_ensemble = previous_ensemble
        self._spec_by_name = {s.name: s for s in self.ensemble_specs}

        # Signature-keyed executable reuse across rebuilt iterations
        # (SURVEY §7 hard part (a)); None = plain jit.
        self.compile_cache = compile_cache
        # The programs carry stable public names: a profile reader finds
        # `jit_adanet_train_step` after any refactor of this class.
        self._train_step = CachedStep(
            self._train_step_impl,
            compile_cache,
            donate_argnums=0,
            name="adanet_train_step",
        )
        self._train_multi_step = CachedStep(
            self._train_multi_step_impl,
            compile_cache,
            donate_argnums=0,
            name="adanet_train_steps",
        )
        self._eval_step = CachedStep(
            self._eval_step_impl, compile_cache, name="adanet_eval_step"
        )
        # The state's template by sample-batch signature, and how many
        # abstract traces of `_init_state` this instance has run.
        self._state_templates: Dict[Any, IterationState] = {}
        self.state_template_traces = 0

    # ------------------------------------------------------------------ init

    def init_state(self, rng, sample_batch) -> IterationState:
        """Initializes every candidate's parameters and optimizer state."""
        state = self._init_state(rng, sample_batch)
        # A process that initialized for real never traces the template.
        self._state_templates[_batch_signature(sample_batch)] = (
            abstract_state(state)
        )
        return state

    def state_template(self, sample_batch) -> IterationState:
        """The structure `init_state` would return, with no value in it.

        A pytree of `jax.ShapeDtypeStruct` from `jax.eval_shape` over the
        code `init_state` runs (one definition of the state's structure),
        remembered on the instance by the sample batch's shapes and
        dtypes. For a caller about to restore a checkpoint over every
        leaf: no op runs, on the device or eagerly on the host.
        """
        signature = _batch_signature(sample_batch)
        template = self._state_templates.get(signature)
        if template is None:
            template = abstract_state(
                jax.eval_shape(
                    lambda batch: self._init_state(
                        jax.random.PRNGKey(0), batch
                    ),
                    sample_batch,
                )
            )
            self.state_template_traces += 1
            self._state_templates[signature] = template
        return template

    def _init_state(self, rng, sample_batch) -> IterationState:
        features, _ = sample_batch
        features, _ = split_example_weights(
            features, self.weight_key, require=False
        )
        sub_states = {}
        sub_shapes = {}
        for spec in self.subnetwork_specs:
            rng, params_rng, dropout_rng = jax.random.split(rng, 3)
            with _must_trace("builder", spec.name):
                init, init_optimizer = spec.module.init, spec.tx.init
                if getattr(spec.builder, "jit_init", False):
                    # One program for the parameters and one for the
                    # optimizer's state, not a forward pass op by op: a
                    # builder asks for it where its model is too large
                    # to run eagerly.
                    init = jax.jit(init, static_argnames=("training",))
                    init_optimizer = jax.jit(init_optimizer)
                variables = init(
                    {"params": params_rng, "dropout": dropout_rng},
                    features,
                    training=True,
                )
                variables = self._graft_initial_variables(spec, variables)
                opt_state = init_optimizer(variables["params"])
            sub_states[spec.name] = SubnetworkTrainState(
                variables=variables,
                opt_state=opt_state,
                step=jnp.asarray(0, jnp.int32),
                dead=jnp.asarray(False),
            )
            sub_shapes[spec.name] = jax.eval_shape(
                lambda v, f, m=spec.module: m.apply(v, f, training=False),
                variables,
                features,
            )

        frozen_params = [fs.params for fs in self.frozen_subnetworks]
        frozen_shapes = [
            jax.eval_shape(
                lambda v, f, m=fs.module: m.apply(v, f, training=False),
                fs.params,
                features,
            )
            for fs in self.frozen_subnetworks
        ]

        ens_states = {}
        cand_states = {}
        for espec in self.ensemble_specs:
            rng, ens_rng = jax.random.split(rng)
            with _must_trace("ensemble", espec.name):
                if espec.initial_params is not None:
                    params = jax.tree_util.tree_map(
                        jnp.asarray, espec.initial_params
                    )
                else:
                    member_shapes = [
                        sub_shapes[ref]
                        if kind == _NEW
                        else frozen_shapes[ref]
                        for kind, ref in espec.members
                    ]
                    params = espec.ensembler.init_ensemble(
                        ens_rng,
                        member_shapes,
                        previous_params=self._warm_start_params(espec),
                    )
                opt_state = (
                    espec.tx.init(params) if espec.tx is not None else ()
                )
            ens_states[espec.name] = EnsembleTrainState(
                params=params, opt_state=opt_state
            )
            cstate = candidate_lib.initial_candidate_state()
            if espec.initial_ema is not None and math.isfinite(
                espec.initial_ema
            ):
                # Seed the frozen EMA so the carried-over previous ensemble
                # competes at the loss it finished iteration t-1 with.
                cstate = candidate_lib.CandidateState(
                    ema_biased=jnp.asarray(
                        espec.initial_ema * (1.0 - self.adanet_loss_decay),
                        jnp.float32,
                    ),
                    ema_count=jnp.asarray(1, jnp.int32),
                    adanet_loss=jnp.asarray(
                        espec.initial_ema, jnp.float32
                    ),
                    dead=jnp.asarray(False),
                )
            cand_states[espec.name] = cstate

        return IterationState(
            subnetworks=sub_states,
            ensembles=ens_states,
            candidates=cand_states,
            frozen=frozen_params,
            iteration_step=jnp.asarray(0, jnp.int32),
            rng=rng,
        )

    @staticmethod
    def _graft_initial_variables(spec, variables):
        """Grafts builder-supplied pretrained variables over random init.

        Builders exposing `initial_variables` (e.g. AutoEnsemble
        subestimators carrying pretrained weights — the analogue of the
        reference ensembling TF-Hub modules,
        customizing_adanet_with_tfhub.ipynb) replace matching collections
        wholesale; structure mismatches fail loudly here instead of as
        opaque apply errors later.
        """
        initial = getattr(spec.builder, "initial_variables", None)
        if not initial:
            return variables
        merged = dict(variables)
        for collection, value in initial.items():
            if collection not in merged:
                raise ValueError(
                    "initial_variables for builder %r carries collection "
                    "%r, but the built module has only %s."
                    % (spec.name, collection, sorted(merged))
                )
            value = jax.tree_util.tree_map(
                jnp.asarray, flax.core.unfreeze(value)
            )
            exp_leaves, exp_def = jax.tree_util.tree_flatten(
                flax.core.unfreeze(merged[collection])
            )
            got_leaves, got_def = jax.tree_util.tree_flatten(value)
            if exp_def != got_def or [
                tuple(l.shape) for l in exp_leaves
            ] != [tuple(l.shape) for l in got_leaves]:
                raise ValueError(
                    "initial_variables[%r] for builder %r does not match "
                    "the module's variable structure/shapes.\n"
                    "Expected: %s\nGot: %s"
                    % (collection, spec.name, exp_def, got_def)
                )
            merged[collection] = value
        return merged

    def _warm_start_params(self, espec: EnsembleSpec):
        """Previous mixture weights aligned with this spec's members.

        Mirrors reference warm-start semantics
        (adanet/ensemble/weighted.py:259-320): kept members reuse their
        learned weight; the bias prior is only passed when the previous
        ensemble was kept in full (not pruned).
        """
        prev = self.previous_ensemble
        if prev is None or prev.ensembler_params is None:
            return None
        # Warm starting only makes sense within the same ensembler: weights
        # learned by e.g. a SCALAR ensembler have the wrong shape for a
        # MATRIX one (the reference ties warm start to the ensembler that
        # owns the checkpointed variables, weighted.py:259-283).
        if espec.ensembler.name != prev.ensembler_name:
            return None
        prev_params = prev.ensembler_params
        prev_weights = (
            prev_params.get("weights")
            if isinstance(prev_params, dict)
            else None
        )
        if prev_weights is None:
            return None
        # Map frozen-subnetwork index -> index within the previous ensemble.
        prev_index = {
            id(ws.subnetwork): i
            for i, ws in enumerate(prev.weighted_subnetworks)
        }
        weights = []
        num_kept = 0
        for kind, ref in espec.members:
            if kind == _FROZEN:
                frozen = self.frozen_subnetworks[ref]
                idx = prev_index.get(id(frozen))
                if idx is not None and idx < len(prev_weights):
                    weights.append(prev_weights[idx])
                    num_kept += 1
                else:
                    weights.append(None)
            else:
                weights.append(None)
        kept_all = num_kept == len(prev.weighted_subnetworks)
        bias = prev_params.get("bias") if kept_all else None
        if not any(w is not None for w in weights) and bias is None:
            return None
        return {"weights": weights, "bias": bias}

    # ----------------------------------------------------------------- train

    def train_step(self, state: IterationState, batch, extra_batches=None):
        """One jitted step over every candidate. Returns (state, metrics).

        `batch` is the shared (features, labels) tuple; `extra_batches`
        optionally maps subnetwork names to dedicated (features, labels) —
        per-candidate training data is how AutoEnsemble implements bagging
        (reference: adanet/autoensemble/common.py:59-93).
        """
        return self._train_step(state, batch, dict(extra_batches or {}))

    def train_steps(self, state: IterationState, stacked_batch):
        """K fused train steps in ONE device dispatch via `lax.scan`.

        The host-loop batching analogue of TPUEstimator's
        `iterations_per_loop` (reference: adanet/core/tpu_estimator.py:91-178
        runs N steps per device loop via infeed): `stacked_batch` is a
        (features, labels) pytree whose leaves have a leading `K` dimension
        (K stacked batches). Returns (state, metrics-of-last-step). Host
        NaN/logging checks happen once per K steps, as on the reference TPU
        path.
        """
        return self._train_multi_step(state, stacked_batch)

    def _train_multi_step_impl(self, state, stacked_batch):
        def body(s, batch):
            new_s, metrics = self._train_step_impl(s, batch, {})
            return new_s, metrics

        state, metrics = jax.lax.scan(body, state, stacked_batch)
        # Report the last step's metrics (cheap; full series stays on device).
        return state, jax.tree_util.tree_map(lambda m: m[-1], metrics)

    def _apply_subnetwork(
        self, spec, variables, features, training, rngs=None
    ):
        if training:
            out, mutated = spec.module.apply(
                variables,
                features,
                training=True,
                rngs=rngs,
                mutable=flax.core.DenyList("params"),
            )
            return out, mutated
        return spec.module.apply(variables, features, training=False), None

    def build_loss_context(self, prev_ensembler_params, frozen_outs):
        """Distillation teacher signals from the frozen previous ensemble.

        Shared by the fused single-program path and the RoundRobin
        executor so teachers are defined in exactly one place. Returns
        None when there is no previous ensemble.
        """
        if not frozen_outs or self.previous_ensemble is None:
            return None
        prev_spec = self.ensemble_specs[0]
        prev_ensemble = prev_spec.ensembler.build_ensemble(
            prev_ensembler_params, frozen_outs
        )
        return TrainLossContext(
            previous_ensemble_logits=jax.lax.stop_gradient(
                prev_ensemble.logits
            ),
            previous_subnetwork_logits=jax.lax.stop_gradient(
                frozen_outs[-1].logits
            ),
        )

    def frozen_outputs(self, frozen_params, features):
        """Forward passes of the frozen members (callable inside jit)."""
        outs = []
        for fs, params in zip(self.frozen_subnetworks, frozen_params):
            scope = scope_name(
                "frozen", "t%d_%s" % (fs.iteration_number, fs.name)
            )
            with jax.named_scope(scope):
                outs.append(
                    fs.module.apply(params, features, training=False)
                )
        return outs

    def member_outputs(self, espec, sub_outs, frozen_outs):
        """Resolves an ensemble spec's member refs to concrete outputs."""
        return [
            sub_outs[ref] if kind == _NEW else frozen_outs[ref]
            for kind, ref in espec.members
        ]

    def subnetwork_update(
        self, spec, st, features, labels, dropout_rng, loss_context=None
    ):
        """One subnetwork's forward/backward/update (callable inside jit).

        The analogue of builder.build_subnetwork_train_op execution
        (reference: adanet/core/ensemble_builder.py:679-805), with the
        finite-guard quarantine. When the builder overrides
        `build_subnetwork_loss`, that custom loss trains the subnetwork
        (knowledge distillation, auxiliary heads, label smoothing, ...).

        `features` may still carry the `weight_key` column; it is split out
        here (once per trace) so every caller — the fused step and the
        RoundRobin executors — gets identical weighting semantics.
        """
        features, weights = split_example_weights(features, self.weight_key)

        def loss_fn(p):
            # Opened inside the differentiated function: the forward
            # pass arrives as `jvp(candidate.<name>)`, the backward as
            # `transpose(jvp(candidate.<name>))`.
            with jax.named_scope(scope_name("candidate", spec.name)):
                variables = {**st.variables, "params": p}
                out, mutated = self._apply_subnetwork(
                    spec, variables, features, True, {"dropout": dropout_rng}
                )
                loss = spec.builder.build_subnetwork_loss(
                    out, labels, self.head, loss_context
                )
                if loss is None:
                    loss = self.head.loss(out.logits, labels, weights)
                return loss, (out, mutated)

        (loss, (out, mutated)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(st.variables["params"])
        with jax.named_scope(scope_name("optimizer", spec.name)):
            updates, new_opt = spec.tx.update(
                grads, st.opt_state, st.variables["params"]
            )
            stepped_vars = {
                **st.variables,
                **(mutated or {}),
                "params": optax.apply_updates(
                    st.variables["params"], updates
                ),
            }
            ok = jnp.isfinite(loss) & tree_finite(grads) & ~st.dead
            new_st = SubnetworkTrainState(
                variables=tree_where(ok, stepped_vars, st.variables),
                opt_state=tree_where(ok, new_opt, st.opt_state),
                step=st.step + ok.astype(jnp.int32),
                dead=st.dead | ~jnp.isfinite(loss),
            )
        return new_st, out, loss

    def ensemble_update(
        self, espec, est, cstate, member_outs, labels, weights=None
    ):
        """One ensemble candidate's mixture-weight update (inside jit).

        Gradients are stopped at member outputs, the scoping analogue of
        reference adanet/core/ensemble_builder.py:301-568.
        """
        member_outs = [jax.lax.stop_gradient(o) for o in member_outs]

        def ensemble_loss(p):
            with jax.named_scope(scope_name("ensemble", espec.name)):
                ens = espec.ensembler.build_ensemble(p, member_outs)
                loss = self.head.loss(ens.logits, labels, weights)
                return loss + _complexity_regularization(ens), loss

        if espec.tx is None:
            adanet_loss, loss = ensemble_loss(est.params)
            new_est = est
        else:
            (adanet_loss, loss), grads = jax.value_and_grad(
                ensemble_loss, has_aux=True
            )(est.params)
            with jax.named_scope(
                scope_name("ensemble_optimizer", espec.name)
            ):
                updates, new_opt = espec.tx.update(
                    grads, est.opt_state, est.params
                )
                stepped = optax.apply_updates(est.params, updates)
                ok = jnp.isfinite(adanet_loss) & tree_finite(grads)
                new_est = EnsembleTrainState(
                    params=tree_where(ok, stepped, est.params),
                    opt_state=tree_where(ok, new_opt, est.opt_state),
                )
        if espec.track_ema:
            with jax.named_scope("step.metrics"):
                new_cstate = candidate_lib.update_candidate_state(
                    cstate, adanet_loss, self.adanet_loss_decay
                )
        else:
            new_cstate = cstate
        return new_est, new_cstate, adanet_loss, loss

    def builder_summary_metrics(self, spec, out, features, labels):
        """Metrics from `Builder.build_subnetwork_summaries` (inside jit).

        The reference's scoped `summary` argument re-cast functionally
        (reference: adanet/core/summary.py:41-199): scalars chart as
        scalars, arrays as histograms, under the candidate's namespace.
        Shared by the fused step and the RoundRobin executor so the key
        format and gating cannot diverge; traced out entirely when
        `collect_summaries` is off.
        """
        if not self.collect_summaries:
            return {}
        hook = getattr(spec.builder, "build_subnetwork_summaries", None)
        with jax.named_scope("step.metrics"):
            extra = hook(out, features, labels) if hook else None
        return {
            "summary/%s/%s" % (spec.name, tag): value
            for tag, value in (extra or {}).items()
        }

    def _train_step_impl(self, state: IterationState, batch, extra_batches):
        # bf16 step policy: one downcast of the float features at the
        # jit boundary (labels, example weights, and all state stay
        # f32 — see utils/precision.py for the full list of deliberate
        # f32 islands). No-op when step_compute_dtype is unset.
        if self.step_compute_dtype is not None:
            preserve = (self.weight_key,) if self.weight_key else ()
            batch = precision.cast_batch(
                batch, self.step_compute_dtype, preserve
            )
            extra_batches = {
                name: precision.cast_batch(
                    extra, self.step_compute_dtype, preserve
                )
                for name, extra in extra_batches.items()
            }
        features, labels = batch
        # weight_key split: models see the stripped features, heads see the
        # weights (reference weight_column, ensemble_builder.py:571-583).
        model_features, weights = split_example_weights(
            features, self.weight_key
        )
        rng, step_rng = jax.random.split(state.rng)
        metrics: Dict[str, Any] = {}

        # 0) Forward the frozen members once, shared by all candidates (the
        #    reference also builds each subnetwork once per graph), and
        #    derive the distillation teacher signals.
        frozen_outs = self.frozen_outputs(state.frozen, model_features)

        def make_loss_context(batch_features, shared_frozen_outs=None):
            if not self.frozen_subnetworks or self.previous_ensemble is None:
                return None
            outs = (
                shared_frozen_outs
                if shared_frozen_outs is not None
                else self.frozen_outputs(state.frozen, batch_features)
            )
            prev_name = self.ensemble_specs[0].name
            return self.build_loss_context(
                state.ensembles[prev_name].params, outs
            )

        loss_context = make_loss_context(model_features, frozen_outs)

        # 1) Train every new subnetwork on its own head loss (the analogue of
        #    builder.build_subnetwork_train_op; reference:
        #    adanet/core/ensemble_builder.py:679-805). Subnetworks with their
        #    own batch (bagging) train on it; their ensemble-facing forward
        #    uses the shared default batch.
        new_subnetworks = {}
        sub_outs = {}
        for i, spec in enumerate(self.subnetwork_specs):
            own_features, own_labels = extra_batches.get(
                spec.name, (features, labels)
            )
            # Bagged specs (own batch) get teacher signals recomputed on
            # their own features so distillation pairs matching examples.
            if spec.name in extra_batches:
                own_model, _ = split_example_weights(
                    own_features, self.weight_key
                )
                spec_context = make_loss_context(own_model)
            else:
                own_model = model_features
                spec_context = loss_context
            new_st, out, loss = self.subnetwork_update(
                spec,
                state.subnetworks[spec.name],
                own_features,
                own_labels,
                jax.random.fold_in(step_rng, i),
                loss_context=spec_context,
            )
            # Builder-visible summary hook, called with the forward that
            # was trained — the subnetwork's own (possibly bagged) batch.
            metrics.update(
                self.builder_summary_metrics(
                    spec, out, own_model, own_labels
                )
            )
            if spec.name in extra_batches:
                # Recompute the forward on the shared batch for ensembles.
                out, _ = self._apply_subnetwork(
                    spec,
                    new_st.variables,
                    model_features,
                    True,
                    {"dropout": jax.random.fold_in(step_rng, 1000 + i)},
                )
            new_subnetworks[spec.name] = new_st
            sub_outs[spec.name] = out
            metrics["subnetwork_loss/%s" % spec.name] = loss

        # 2) Train each ensemble candidate's mixture weights on
        #    loss + complexity_regularization, gradients stopped at member
        #    outputs (reference: adanet/core/ensemble_builder.py:301-568).
        new_ensembles = {}
        new_candidates = {}
        for espec in self.ensemble_specs:
            member_outs = self.member_outputs(espec, sub_outs, frozen_outs)
            new_est, new_cstate, adanet_loss, loss = self.ensemble_update(
                espec,
                state.ensembles[espec.name],
                state.candidates[espec.name],
                member_outs,
                labels,
                weights,
            )
            new_ensembles[espec.name] = new_est
            new_candidates[espec.name] = new_cstate
            metrics["adanet_loss/%s" % espec.name] = adanet_loss
            metrics["ensemble_loss/%s" % espec.name] = loss

        new_state = IterationState(
            subnetworks=new_subnetworks,
            ensembles=new_ensembles,
            candidates=new_candidates,
            frozen=state.frozen,
            iteration_step=state.iteration_step + 1,
            rng=rng,
        )
        return new_state, metrics

    # ------------------------------------------------------------------ eval

    def eval_step(self, state: IterationState, batch):
        """Jitted eval over every candidate: losses + head metrics."""
        features, labels = batch
        return self._eval_step(state, features, labels)

    def _eval_step_impl(self, state: IterationState, features, labels):
        features, weights = split_example_weights(features, self.weight_key)
        sub_outs = {
            spec.name: spec.module.apply(
                state.subnetworks[spec.name].variables,
                features,
                training=False,
            )
            for spec in self.subnetwork_specs
        }
        frozen_outs = self.frozen_outputs(state.frozen, features)
        results = {}
        for espec in self.ensemble_specs:
            member_outs = self.member_outputs(espec, sub_outs, frozen_outs)
            ens = espec.ensembler.build_ensemble(
                state.ensembles[espec.name].params, member_outs
            )
            loss = self.head.loss(ens.logits, labels, weights)
            out = {
                "loss": loss,
                "adanet_loss": loss + _complexity_regularization(ens),
            }
            out.update(self.head.eval_metrics(ens.logits, labels, weights))
            results[espec.name] = out
        for spec in self.subnetwork_specs:
            results["subnetwork/%s" % spec.name] = {
                "loss": self.head.loss(
                    sub_outs[spec.name].logits, labels, weights
                )
            }
        return results

    # ------------------------------------------------------- selection/freeze

    def candidate_names(self) -> List[str]:
        return [spec.name for spec in self.ensemble_specs]

    def ema_losses(self, state: IterationState) -> Dict[str, float]:
        """Host-side zero-debiased EMA per candidate (inf when dead/unset)."""
        values = jax.device_get(
            {
                name: candidate_lib.debiased_ema(
                    cstate, self.adanet_loss_decay
                )
                for name, cstate in state.candidates.items()
            }
        )
        return {name: float(v) for name, v in values.items()}

    def best_candidate_index(
        self,
        state: IterationState,
        override: Optional[int] = None,
        exclude_first: bool = False,
    ) -> int:
        """Argmin over candidate EMAs (reference: iteration.py:1011-1046).

        Non-finite candidates are quarantined (never selected); if every
        candidate is dead this raises, the analogue of TF's
        `NanLossDuringTrainingError`. `exclude_first=True` implements
        `force_grow` at t>0: the zero-th (previous-ensemble) candidate is
        ignored (reference: estimator.py:1447-1451, 1504-1511).
        """
        if override is not None:
            return int(override)
        emas = self.ema_losses(state)
        losses = [emas[spec.name] for spec in self.ensemble_specs]
        start = 1 if exclude_first and len(losses) > 1 else 0
        candidates = list(range(start, len(losses)))
        finite = [i for i in candidates if losses[i] != float("inf")]
        if not finite:
            raise FloatingPointError(
                "All %d ensemble candidates have non-finite AdaNet losses."
                % len(candidates)
            )
        return int(min(finite, key=lambda i: losses[i]))

    def ensemble_forward(
        self, state: IterationState, spec_name: str, features
    ):
        """Forward pass of one candidate ensemble (for predict/export)."""
        espec = self._spec_by_name[spec_name]
        # Serving-time features may or may not carry the weight column.
        features, _ = split_example_weights(
            features, self.weight_key, require=False
        )
        sub_outs = {
            s.name: s.module.apply(
                state.subnetworks[s.name].variables, features, training=False
            )
            for s in self.subnetwork_specs
        }
        frozen_outs = self.frozen_outputs(state.frozen, features)
        member_outs = self.member_outputs(espec, sub_outs, frozen_outs)
        return espec.ensembler.build_ensemble(
            state.ensembles[espec.name].params, member_outs
        )

    def serving_state(self, state: IterationState, spec_name: str):
        """Minimal pytree needed by `serving_forward` for one candidate.

        `ensemble_forward` takes the full `IterationState` (every
        candidate's parameters + optimizer state); serving one ensemble
        only needs its member subnetworks' variables, the frozen member
        variables, and its ensembler params — the narrow transfer matters
        when predict() commits parameters to another backend
        (estimator.predict(on_cpu=True))."""
        espec = self._spec_by_name[spec_name]
        new_refs = {ref for kind, ref in espec.members if kind == _NEW}
        return {
            "subnetworks": {
                name: st.variables
                for name, st in state.subnetworks.items()
                if name in new_refs
            },
            "frozen": state.frozen,
            "ensembler": state.ensembles[espec.name].params,
        }

    def serving_forward(self, narrow, spec_name: str, features):
        """`ensemble_forward` over a `serving_state` pytree: computes only
        the candidate's own member subnetworks, not every candidate's."""
        espec = self._spec_by_name[spec_name]
        features, _ = split_example_weights(
            features, self.weight_key, require=False
        )
        sub_outs = {
            s.name: s.module.apply(
                narrow["subnetworks"][s.name], features, training=False
            )
            for s in self.subnetwork_specs
            if s.name in narrow["subnetworks"]
        }
        frozen_outs = self.frozen_outputs(narrow["frozen"], features)
        member_outs = self.member_outputs(espec, sub_outs, frozen_outs)
        return espec.ensembler.build_ensemble(narrow["ensembler"], member_outs)

    def freeze_candidate(
        self, state: IterationState, spec_name: str, sample_batch
    ) -> FrozenEnsemble:
        """Freezes the winning candidate into host-side records.

        The functional analogue of the reference's checkpoint-overwrite
        graph-growing trick (reference: adanet/core/estimator.py:236-331):
        nothing is overwritten — the winner's modules and final params simply
        become the `previous_ensemble` for the next iteration.
        """
        espec = self._spec_by_name[spec_name]
        features, _ = sample_batch
        features, _ = split_example_weights(
            features, self.weight_key, require=False
        )
        # Stage every member's device values first, then pull them to the
        # host in ONE device_get: per-member fetches inside the loop
        # serialize N blocking round-trips (and stall the dispatch of the
        # next member's `_frozen_record_fields` program — jaxlint JL012);
        # one batched fetch overlaps all the record-field computes and
        # pays a single transfer latency at the freeze boundary.
        device_fetch = {"ensembler": state.ensembles[espec.name].params}
        member_plans = []
        for i, (kind, ref) in enumerate(espec.members):
            if kind == _FROZEN:
                device_fetch["member/%d" % i] = state.frozen[ref]
                member_plans.append((i, kind, self.frozen_subnetworks[ref]))
            else:
                spec = next(
                    s for s in self.subnetwork_specs if s.name == ref
                )
                device_variables = state.subnetworks[spec.name].variables
                # Record concrete complexity/shared for host-side consumers
                # (e.g. simple_dnn reading previous depth from `shared`);
                # jitted so freezing doesn't fall back to op-by-op eager
                # execution of the whole subnetwork.
                # Fetch only the replicated record fields — under
                # multi-host SPMD the batch-shaped outputs (last_layer,
                # logits) span non-addressable devices and must not be
                # device_get here.
                device_fetch["member/%d" % i] = device_variables
                device_fetch["record/%d" % i] = _frozen_record_fields(
                    _ModuleHandle(spec.module), device_variables, features
                )
                member_plans.append((i, kind, spec))
        host = jax.device_get(device_fetch)
        params = host["ensembler"]
        weights = None
        if isinstance(params, dict):
            weights = params.get("weights")

        weighted = []
        for i, kind, member in member_plans:
            if kind == _FROZEN:
                frozen = FrozenSubnetwork(
                    iteration_number=member.iteration_number,
                    name=member.name,
                    module=member.module,
                    params=host["member/%d" % i],
                    complexity=member.complexity,
                    shared=member.shared,
                )
            else:
                complexity, shared = host["record/%d" % i]
                frozen = FrozenSubnetwork(
                    iteration_number=self.iteration_number,
                    name=member.name,
                    module=member.module,
                    params=host["member/%d" % i],
                    complexity=complexity,
                    shared=shared,
                )
            weight = None
            if weights is not None and i < len(weights):
                weight = weights[i]
            weighted.append(
                FrozenWeightedSubnetwork(subnetwork=frozen, weight=weight)
            )

        return FrozenEnsemble(
            name=espec.name,
            iteration_number=self.iteration_number,
            weighted_subnetworks=weighted,
            ensembler_name=espec.ensembler.name,
            ensembler_params=params,
            architecture=espec.architecture,
            final_ema=self.ema_losses(state).get(espec.name),
        )


class IterationBuilder:
    """Builds `Iteration`s from builders, strategies, and ensemblers.

    The analogue of the reference `_IterationBuilder.build_iteration`
    (reference: adanet/core/iteration.py:506-816), minus the graph plumbing.
    """

    def __init__(
        self,
        head,
        ensemblers: Sequence[Any],
        ensemble_strategies: Sequence[Any],
        adanet_loss_decay: float = 0.9,
        collect_summaries: bool = True,
        compile_cache=None,
        weight_key: Optional[str] = None,
        step_compute_dtype=None,
    ):
        if not ensemblers:
            raise ValueError("At least one ensembler is required.")
        if not ensemble_strategies:
            raise ValueError("At least one ensemble strategy is required.")
        self._head = head
        self._ensemblers = list(ensemblers)
        self._strategies = list(ensemble_strategies)
        self._adanet_loss_decay = float(adanet_loss_decay)
        self._collect_summaries = bool(collect_summaries)
        self._compile_cache = compile_cache
        self._weight_key = weight_key
        # Validated here (fail at construction, not first step); the
        # Iteration re-resolves, which is idempotent.
        self._step_compute_dtype = precision.resolve_dtype(
            step_compute_dtype
        )

    def _ensembler_by_name(self, name: str):
        for ensembler in self._ensemblers:
            if ensembler.name == name:
                return ensembler
        raise ValueError(
            "Previous ensemble was built by ensembler %r which is not among "
            "this run's ensemblers %s."
            % (name, [e.name for e in self._ensemblers])
        )

    def build_iteration(
        self,
        iteration_number: int,
        subnetwork_builders: Sequence[Any],
        previous_ensemble: Optional[FrozenEnsemble] = None,
    ) -> Iteration:
        if not subnetwork_builders:
            raise ValueError("Need at least one subnetwork builder.")
        names = [b.name for b in subnetwork_builders]
        if len(set(names)) != len(names):
            raise ValueError("Builder names must be unique, got %s" % names)

        logits_dimension = self._head.logits_dimension
        frozen_members: List[FrozenSubnetwork] = (
            list(previous_ensemble.subnetworks) if previous_ensemble else []
        )
        frozen_index = {id(fs): i for i, fs in enumerate(frozen_members)}

        subnetwork_specs = []
        for builder in subnetwork_builders:
            module = builder.build_subnetwork(
                logits_dimension, previous_ensemble=previous_ensemble
            )
            tx = builder.build_train_optimizer(
                previous_ensemble=previous_ensemble
            )
            subnetwork_specs.append(
                SubnetworkSpec(
                    name=builder.name, builder=builder, module=module, tx=tx
                )
            )

        ensemble_specs = []
        seen = set()
        # At t>0 the zero-th candidate is always the carried-over previous
        # ensemble, competing at its frozen loss EMA with untrained (frozen)
        # params (reference: adanet/core/iteration.py:592-606,
        # estimator.py:1447-1451).
        if previous_ensemble is not None:
            ensembler = self._ensembler_by_name(
                previous_ensemble.ensembler_name
            )
            members = tuple(
                (_FROZEN, i) for i in range(len(frozen_members))
            )
            ensemble_specs.append(
                EnsembleSpec(
                    name=previous_ensemble.name,
                    candidate_name=previous_ensemble.name,
                    ensembler=ensembler,
                    tx=None,
                    members=members,
                    architecture=previous_ensemble.architecture,
                    track_ema=False,
                    initial_params=previous_ensemble.ensembler_params,
                    initial_ema=previous_ensemble.final_ema,
                )
            )
            seen.add(previous_ensemble.name)
        for strategy in self._strategies:
            candidates = strategy.generate_ensemble_candidates(
                subnetwork_builders, frozen_members or None
            )
            for cand in candidates:
                for ensembler in self._ensemblers:
                    # Reference naming: "t{}_{}_{}" with the ensembler name
                    # always appended (reference: iteration.py:694-697).
                    name = "t{}_{}_{}".format(
                        iteration_number, cand.name, ensembler.name
                    )
                    if name in seen:
                        raise ValueError(
                            "Duplicate ensemble candidate name %r" % name
                        )
                    seen.add(name)

                    members: List[Tuple[str, Any]] = []
                    architecture = Architecture(
                        ensemble_candidate_name=cand.name,
                        ensembler_name=ensembler.name,
                        iteration_number=iteration_number,
                        replay_indices=(
                            previous_ensemble.architecture.replay_indices
                            if previous_ensemble
                            else []
                        ),
                    )
                    for frozen in cand.previous_ensemble_subnetworks:
                        idx = frozen_index[id(frozen)]
                        members.append((_FROZEN, idx))
                        architecture.add_subnetwork(
                            frozen.iteration_number, frozen.name
                        )
                    for builder in cand.subnetwork_builders:
                        members.append((_NEW, builder.name))
                        architecture.add_subnetwork(
                            iteration_number, builder.name
                        )
                    ensemble_specs.append(
                        EnsembleSpec(
                            name=name,
                            candidate_name=cand.name,
                            ensembler=ensembler,
                            tx=ensembler.build_train_optimizer(),
                            members=tuple(members),
                            architecture=architecture,
                        )
                    )

        return Iteration(
            iteration_number=iteration_number,
            subnetwork_specs=subnetwork_specs,
            ensemble_specs=ensemble_specs,
            frozen_subnetworks=frozen_members,
            head=self._head,
            adanet_loss_decay=self._adanet_loss_decay,
            collect_summaries=self._collect_summaries,
            compile_cache=self._compile_cache,
            previous_ensemble=previous_ensemble,
            weight_key=self._weight_key,
            step_compute_dtype=self._step_compute_dtype,
        )
