"""Continuous padded batching over a small set of AOT-compiled shapes.

Requests carry independently-sized feature batches; XLA executables are
shape-specialized. Left unchecked, live traffic would trigger one
compile per distinct total batch size. The batcher closes the gap the
same way `TPUEstimator`'s padded eval batching does: concatenate the
waiting requests, pad up to the smallest **bucket** size, and execute —
so the whole serving lifetime touches only `len(bucket_sizes)` shapes
per generation, each compiled once and reused through the shared
`core/compile_cache.py` (structurally identical programs across
generations also share executables there).

Execution is donated-buffer inference: the padded device batch is
donated into the program (freeing HBM for the output buffers) on
backends that support donation; XLA:CPU ignores donation, so it is
skipped there to avoid a per-call warning.

The batcher also runs the canary mirror for `ModelPool`: while a
candidate generation is staged, each executed batch is replayed on the
candidate and its health verdict (clean execution, finite outputs,
divergence vs the incumbent) is reported back to the pool's gate.

Thread contract: `execute` is NOT thread-safe; the serving front-end's
single executor thread is the serializer.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax

from adanet_tpu.core.compile_cache import CachedStep, CompileCache
from adanet_tpu.observability import metrics as metrics_lib
from adanet_tpu.robustness import faults
from adanet_tpu.serving.model_pool import (
    GenerationRecord,
    ModelPool,
    outputs_finite,
)

_LOG = logging.getLogger("adanet_tpu")


@dataclasses.dataclass
class BatcherConfig:
    """`bucket_sizes` is the whole compiled-shape budget (sorted,
    ascending); the largest bucket is the maximum total rows per
    dispatch. `donate=None` donates the input batch wherever the
    backend implements donation (i.e. not XLA:CPU)."""

    bucket_sizes: Sequence[int] = (1, 2, 4, 8, 16, 32)
    donate: Optional[bool] = None
    #: Route execution through jit + the shared CompileCache (the
    #: production path for exported programs). False executes the
    #: generation's program as a plain callable — for host-side stub
    #: programs in tests and diagnostics.
    jit: bool = True
    #: Use the generation's cascade (cheap member first, fall through
    #: to the full ensemble below the calibrated confidence margin)
    #: when one was published. False always runs the full ensemble —
    #: the conservative choice for operators who have not validated
    #: the calibration.
    cascade: bool = True
    #: Per-ROW cascade splitting: rows that clear the margin are
    #: answered at level 0 and only the residual rows fall through to
    #: the full ensemble as a smaller re-bucketed batch. False
    #: restores the legacy per-batch rule (any unclear row sends the
    #: WHOLE padded batch to the full ensemble).
    split_rows: bool = True
    #: Shadow-canary cadence: every Nth cascade dispatch that answered
    #: rows at level 0 also runs the full ensemble on the same padded
    #: batch and scores argmax disagreement over the level-0 rows into
    #: the `serving.cascade.shadow_divergence` gauge. 0 disables the
    #: shadow (and with it the divergence auto-rollback).
    shadow_every: int = 8
    #: Minimum shadow-scored rows before divergence past the published
    #: bound may trigger the rollback to ensemble-only serving.
    shadow_min_rows: int = 64


def bucket_for(total_rows: int, bucket_sizes: Sequence[int]) -> int:
    """Smallest bucket holding `total_rows`; raises past the largest."""
    for size in bucket_sizes:
        if total_rows <= size:
            return size
    raise ValueError(
        "batch of %d rows exceeds the largest bucket (%d)"
        % (total_rows, max(bucket_sizes))
    )


def request_rows(features: Any) -> int:
    """Leading-dimension row count of a request's feature pytree."""
    leaves = jax.tree_util.tree_leaves(features)
    if not leaves:
        raise ValueError("request has no feature leaves")
    return int(np.asarray(leaves[0]).shape[0])


def pad_batch(
    features_list: Sequence[Any], bucket: int
) -> Tuple[Any, int]:
    """Concatenates request features and zero-pads rows to `bucket`.

    Returns (padded pytree, real row count). Padding rows are zeros;
    their outputs are computed and discarded — per-example independence
    of inference programs makes the real rows bit-identical to an
    unpadded evaluation at the same bucket shape.
    """

    def cat(*leaves):
        arrays = [np.asarray(leaf) for leaf in leaves]
        stacked = np.concatenate(arrays, axis=0)
        total = stacked.shape[0]
        if total > bucket:
            raise ValueError(
                "batch of %d rows exceeds bucket %d" % (total, bucket)
            )
        if total < bucket:
            pad = np.zeros(
                (bucket - total,) + stacked.shape[1:], stacked.dtype
            )
            stacked = np.concatenate([stacked, pad], axis=0)
        return stacked

    padded = jax.tree_util.tree_map(cat, *features_list)
    total = sum(request_rows(f) for f in features_list)
    return padded, total


def split_rows(outputs: Any, sizes: Sequence[int]) -> List[Any]:
    """Slices a batched output tree back into per-request trees."""
    outputs = jax.device_get(outputs)
    out: List[Any] = []
    offset = 0
    for size in sizes:
        lo, hi = offset, offset + size
        out.append(
            jax.tree_util.tree_map(lambda x: x[lo:hi], outputs)
        )
        offset = hi
    return out


def max_divergence(a: Any, b: Any) -> Optional[float]:
    """Max |a - b| over the float leaves of two output trees."""
    worst = None
    for la, lb in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    ):
        la, lb = np.asarray(la), np.asarray(lb)
        if not np.issubdtype(la.dtype, np.floating):
            continue
        delta = float(np.max(np.abs(la - lb))) if la.size else 0.0
        worst = delta if worst is None else max(worst, delta)
    return worst


class Batcher:
    """Padded-bucket executor over the pool's incumbent generation."""

    def __init__(
        self,
        pool: ModelPool,
        config: Optional[BatcherConfig] = None,
        compile_cache: Optional[CompileCache] = None,
    ):
        self.pool = pool
        self.config = config or BatcherConfig()
        if list(self.config.bucket_sizes) != sorted(
            set(self.config.bucket_sizes)
        ):
            raise ValueError(
                "bucket_sizes must be strictly ascending, got %r"
                % (self.config.bucket_sizes,)
            )
        self._cache = compile_cache or CompileCache(max_entries=32)
        #: (iteration_number, is_cascade) -> CachedStep.
        self._steps: Dict[Tuple[int, bool], CachedStep] = {}
        # Bucket occupancy (real rows / bucket rows per dispatch) tells
        # the replica balancer whether padding — i.e. the compiled-shape
        # budget — or traffic is wasting device time; canary divergence
        # mirrors the health signal the flip gate consumes.
        reg = metrics_lib.registry()
        self._h_occupancy = reg.histogram(
            "serving.batcher.bucket_occupancy",
            boundaries=(0.25, 0.5, 0.75, 0.9, 1.0),
        )
        self._m_dispatches = reg.counter("serving.batcher.dispatches")
        self._g_canary_divergence = reg.gauge(
            "serving.batcher.canary_divergence"
        )
        # Cascade accounting: cheap-tier answers vs fallthroughs, and
        # the running fallthrough rate as a gauge (the signal an
        # operator watches to judge whether the published threshold
        # still fits traffic).
        self._m_cascade_cheap = reg.counter("serving.cascade.cheap_answers")
        self._m_cascade_fall = reg.counter("serving.cascade.fallthroughs")
        self._g_fallthrough = reg.gauge("serving.cascade.fallthrough_rate")
        # Per-ROW accounting: the per-batch rate above saturates once
        # requests batch (one unclear row marks the whole batch); the
        # row-level gauge tracks the true margin-clearance rate — the
        # number the publish-time holdout predicted.
        self._m_rows_cheap = reg.counter("serving.cascade.row_cheap_answers")
        self._m_rows_fall = reg.counter("serving.cascade.row_fallthroughs")
        self._g_row_fallthrough = reg.gauge(
            "serving.cascade.row_fallthrough_rate"
        )
        # Shadow canary: running argmax-disagreement rate of level-0
        # answers vs the full ensemble, and rollbacks it triggered.
        self._g_shadow_divergence = reg.gauge(
            "serving.cascade.shadow_divergence"
        )
        self._m_cascade_rollbacks = reg.counter("serving.cascade.rollbacks")
        #: Cascade tier of the LAST dispatched batch (0 cheap, 1 full,
        #: None = no cascade ran); the frontend reads it right after
        #: `execute` on its single executor thread.
        self.last_cascade_level: Optional[int] = None
        #: Per-REAL-row answer provenance of the last dispatched batch
        #: (True = this row's answer came from the full ensemble), or
        #: None when no cascade ran. Read by the frontend to stamp
        #: per-REQUEST cascade levels; same thread contract as
        #: `last_cascade_level`.
        self.last_row_fallthrough: Optional[np.ndarray] = None
        #: Shadow-divergence rollback state: None while the cascade is
        #: healthy; a `{generation, reason, shadow_divergence, bound,
        #: shadow_rows}` dict once the shadow tripped the published
        #: bound — the batcher then serves ensemble-only for that
        #: generation until a new one flips in.
        self.cascade_rollback: Optional[Dict[str, Any]] = None
        self._cascade_seq = 0
        self._shadow_generation: Optional[int] = None
        self._shadow_rows = 0
        self._shadow_disagree = 0
        self._cascade_digests: Dict[int, Optional[str]] = {}

    @property
    def max_batch(self) -> int:
        return max(self.config.bucket_sizes)

    def _donate(self) -> bool:
        if self.config.donate is not None:
            return self.config.donate
        # XLA:CPU ignores donation (with a warning per call); every
        # other backend frees the padded input buffer for the outputs.
        return jax.default_backend() != "cpu"

    def _step_for(self, record: GenerationRecord, cascade: bool = False):
        program = (
            record.cascade_program if cascade else record.program
        )
        if not self.config.jit:
            return program
        key = (record.iteration_number, cascade)
        step = self._steps.get(key)
        if step is None or getattr(step, "_program", None) is not program:
            step = CachedStep(
                program,
                self._cache,
                donate_argnums=(0,) if self._donate() else (),
            )
            step._program = program
            self._steps[key] = step
            # Stale generations never run again; keep the map bounded.
            for old in [
                old
                for old in self._steps
                if old[0] < record.iteration_number - 2
            ]:
                del self._steps[old]
        return step

    def execute(
        self, features_list: Sequence[Any]
    ) -> Tuple[GenerationRecord, List[Any]]:
        """Executes one formed batch; returns (generation, per-request
        outputs). The generation is captured ONCE — a concurrent flip
        affects only subsequent batches.

        With a cascade-published generation (and `config.cascade`), the
        cheap level-0 program runs first and each real row is scored
        against the published margin. With `config.split_rows` (the
        default), clear rows are answered at level 0 and only the
        residual rows fall through to the full ensemble as a smaller
        re-bucketed batch; per-example independence makes every
        fallthrough row bit-identical to a cascade-free server's
        answer. `split_rows=False` keeps the legacy per-batch rule
        (any unclear row sends the whole padded batch to the full
        ensemble).
        """
        record = self.pool.active_record()
        sizes = [request_rows(f) for f in features_list]
        real_rows = sum(sizes)
        bucket = bucket_for(real_rows, self.config.bucket_sizes)
        padded, _ = pad_batch(features_list, bucket)
        self._m_dispatches.inc()
        self._h_occupancy.observe(real_rows / float(bucket))
        faults.trip("serving.batch_execute")
        self.last_cascade_level = None
        self.last_row_fallthrough = None
        outputs = None
        if self._cascade_active(record):
            outputs = self._execute_cascade(record, padded, real_rows)
        if outputs is None:
            outputs = self._step_for(record)(padded)
        split = split_rows(outputs, sizes)
        self._mirror_canary(padded, outputs)
        return record, split

    # -------------------------------------------------------------- cascade

    def _cascade_active(self, record: GenerationRecord) -> bool:
        """Cascade published, enabled, and not rolled back for `record`.

        getattr: duck-typed records (test stubs, older pickles) may
        predate the cascade fields.
        """
        if not self.config.cascade:
            return False
        if getattr(record, "cascade_program", None) is None:
            return False
        if getattr(record, "cascade", None) is None:
            return False
        rollback = self.cascade_rollback
        return not (
            rollback is not None
            and rollback.get("generation") == record.iteration_number
        )

    def _execute_cascade(
        self, record: GenerationRecord, padded: Any, real_rows: int
    ) -> Optional[Any]:
        """Runs the level-0 program and resolves the per-row cascade.

        Returns the finished host output tree, or None when the whole
        padded batch must run on the full ensemble (zero clear rows,
        unscoreable outputs, or per-batch mode with any unclear row) —
        the caller's full-program path, unchanged from a cascade-free
        server.
        """
        from adanet_tpu.serving.fleet import cascade as cascade_lib

        if self._shadow_generation != record.iteration_number:
            # New generation: the shadow starts a fresh verdict and a
            # prior rollback (which `_cascade_active` scoped to its
            # own generation) is forgotten.
            self._shadow_generation = record.iteration_number
            self._shadow_rows = 0
            self._shadow_disagree = 0
            self._cascade_seq = 0
            self.cascade_rollback = None
        cheap = jax.device_get(self._step_for(record, cascade=True)(padded))
        mask = cascade_lib.clear_mask(record.cascade, cheap, real_rows)
        rows_clear = int(mask.sum()) if mask is not None else 0
        rows_fall = real_rows - rows_clear
        # Row accounting measures margin CLEARANCE in both modes — in
        # per-batch mode an unclear neighbor still sends clear rows to
        # the ensemble, and the gap between this gauge and the
        # per-batch one is exactly what per-row splitting recovers.
        self._m_rows_cheap.inc(rows_clear)
        self._m_rows_fall.inc(rows_fall)
        scored = self._m_rows_cheap.value + self._m_rows_fall.value
        self._g_row_fallthrough.set(
            self._m_rows_fall.value / float(scored)
        )
        if mask is not None and rows_fall == 0:
            outputs: Optional[Any] = cheap
            self.last_cascade_level = 0
            self.last_row_fallthrough = np.zeros(real_rows, bool)
            self._m_cascade_cheap.inc()
        elif (
            mask is None
            or rows_clear == 0
            or not self.config.split_rows
        ):
            outputs = None
            self.last_cascade_level = 1
            self.last_row_fallthrough = np.ones(real_rows, bool)
            self._m_cascade_fall.inc()
        else:
            outputs = self._execute_residual(
                record, padded, cheap, mask, real_rows
            )
            if outputs is None:
                # Structure mismatch between the programs: serve the
                # whole batch from the ensemble rather than guess.
                self.last_cascade_level = 1
                self.last_row_fallthrough = np.ones(real_rows, bool)
            else:
                self.last_cascade_level = 1
                self.last_row_fallthrough = ~mask
            self._m_cascade_fall.inc()
        answered = (
            self._m_cascade_cheap.value + self._m_cascade_fall.value
        )
        self._g_fallthrough.set(
            self._m_cascade_fall.value / float(answered)
        )
        if (
            rows_clear
            and mask is not None
            and self.config.shadow_every > 0
        ):
            self._cascade_seq += 1
            if self._cascade_seq % self.config.shadow_every == 0:
                self._shadow_score(record, padded, cheap, mask)
                if self.cascade_rollback is not None:
                    # The shadow tripped ON this batch: its level-0
                    # rows were scored against the live ensemble and
                    # judged divergent — re-answer the whole batch
                    # from the full program the shadow already proved
                    # out, so no request is served from a condemned
                    # level 0.
                    self.last_cascade_level = 1
                    self.last_row_fallthrough = np.ones(real_rows, bool)
                    return None
        return outputs

    def _execute_residual(
        self,
        record: GenerationRecord,
        padded: Any,
        cheap: Any,
        mask: np.ndarray,
        real_rows: int,
    ) -> Optional[Any]:
        """Runs ONLY the unclear rows on the full ensemble and scatters
        their answers into the level-0 outputs.

        The residual rows are gathered from the padded batch (real
        rows are its prefix), re-bucketed to the smallest AOT bucket
        that holds them, zero-padded, and executed — the same padded
        dispatch a cascade-free server would form for a batch of that
        size, so per-example independence keeps each residual row's
        answer bit-identical to the oracle. Returns None when the two
        programs' output trees are not congruent (scatter impossible;
        flip-time gating rejects such cascades, this guards duck-typed
        stubs).
        """
        residual_idx = np.flatnonzero(~mask)
        residual = jax.tree_util.tree_map(
            lambda leaf: np.asarray(leaf)[residual_idx], padded
        )
        rbucket = bucket_for(len(residual_idx), self.config.bucket_sizes)
        rpadded, _ = pad_batch([residual], rbucket)
        self._h_occupancy.observe(len(residual_idx) / float(rbucket))
        full = jax.device_get(self._step_for(record)(rpadded))

        def scatter(cheap_leaf, full_leaf):
            out = np.asarray(cheap_leaf).copy()
            out[residual_idx] = np.asarray(full_leaf)[: len(residual_idx)]
            return out

        try:
            return jax.tree_util.tree_map(scatter, cheap, full)
        except (ValueError, TypeError) as exc:
            _LOG.error(
                "Cascade scatter failed for generation %d (output "
                "trees not congruent): %s; serving the batch from the "
                "full ensemble.",
                record.iteration_number,
                exc,
            )
            return None

    def _shadow_score(
        self,
        record: GenerationRecord,
        padded: Any,
        cheap: Any,
        mask: np.ndarray,
    ) -> None:
        """Scores this batch's level-0 rows against the full ensemble.

        The full program runs on the same padded batch (the shadow);
        argmax disagreement over the rows the cascade cleared folds
        into a decayed running rate on the
        `serving.cascade.shadow_divergence` gauge. Past the published
        bound — after `shadow_min_rows` of evidence — the cascade
        rolls back to ensemble-only serving for this generation.
        """
        from adanet_tpu.serving.fleet import cascade as cascade_lib

        spec = record.cascade
        try:
            full = jax.device_get(self._step_for(record)(padded))
        except Exception as exc:
            _LOG.error(
                "Cascade shadow execution failed for generation %d: "
                "%s: %s",
                record.iteration_number,
                type(exc).__name__,
                exc,
            )
            return
        key = spec.get("logits_key", cascade_lib.DEFAULT_LOGITS_KEY)
        cheap_logits = cascade_lib._logits_leaf(cheap, key)
        full_logits = cascade_lib._logits_leaf(full, key)
        if cheap_logits is None or full_logits is None:
            return
        idx = np.flatnonzero(mask)
        disagree = int(
            np.sum(
                cheap_logits[idx].argmax(axis=-1)
                != full_logits[idx].argmax(axis=-1)
            )
        )
        # Exponential forgetting: halve the window once it saturates,
        # so an old clean epoch cannot dilute fresh drift forever.
        if self._shadow_rows > 4096:
            self._shadow_rows //= 2
            self._shadow_disagree //= 2
        self._shadow_rows += len(idx)
        self._shadow_disagree += disagree
        rate = self._shadow_disagree / float(self._shadow_rows)
        self._g_shadow_divergence.set(rate)
        bound = float(
            spec.get(
                "shadow_divergence_bound",
                cascade_lib.shadow_divergence_bound(
                    spec.get("holdout_agreement", 1.0),
                    spec.get("target_agreement", 0.995),
                ),
            )
        )
        if self._shadow_rows >= self.config.shadow_min_rows and rate > bound:
            self._rollback_cascade(record, rate, bound)

    def _rollback_cascade(
        self, record: GenerationRecord, rate: float, bound: float
    ) -> None:
        """Disables the cascade for this generation: ensemble-only from
        the next dispatch, with the rollback instant + reason on the
        flight recorder (the forensic trail the flip gate's rollbacks
        already leave)."""
        from adanet_tpu.observability import flightrec
        from adanet_tpu.observability import spans as spans_lib

        t = record.iteration_number
        reason = (
            "shadow divergence %.4f past published bound %.4f "
            "over %d shadowed rows" % (rate, bound, self._shadow_rows)
        )
        self.cascade_rollback = {
            "generation": t,
            "reason": reason,
            "shadow_divergence": float(rate),
            "bound": float(bound),
            "shadow_rows": int(self._shadow_rows),
        }
        self._m_cascade_rollbacks.inc()
        _LOG.error(
            "CASCADE ROLLBACK: generation %d serves ensemble-only (%s).",
            t,
            reason,
        )
        spans_lib.tracer().instant(
            "serving.cascade.rollback", generation=t, reason=reason
        )
        flightrec.dump_installed("cascade_shadow_rollback:gen-%d" % t)

    def cascade_stats(self) -> Dict[str, Any]:
        """Operator-facing cascade snapshot (merged into the frontend's
        heartbeat payload; `servectl cascade` renders it fleet-wide).
        """
        try:
            record: Optional[GenerationRecord] = self.pool.active_record()
        except Exception:
            record = None
        spec = getattr(record, "cascade", None) if record else None
        published = (
            spec is not None
            and getattr(record, "cascade_program", None) is not None
        )
        out: Dict[str, Any] = {
            "enabled": bool(self.config.cascade),
            "mode": "row" if self.config.split_rows else "batch",
            "published": bool(published),
            "active": bool(
                record is not None and self._cascade_active(record)
                and published
            ),
            "generation": (
                record.iteration_number if record is not None else None
            ),
            "row_fallthrough_rate": self._g_row_fallthrough.value,
            "fallthrough_rate": self._g_fallthrough.value,
            "shadow_divergence": self._g_shadow_divergence.value,
            "shadow_rows": int(self._shadow_rows),
            "rollback": self.cascade_rollback,
        }
        if published:
            out.update(
                threshold=spec.get("threshold"),
                temperature=spec.get("temperature"),
                source=spec.get("source", "member"),
                shadow_divergence_bound=spec.get(
                    "shadow_divergence_bound"
                ),
                program_digest=self._cascade_digest(record),
            )
        return out

    def _cascade_digest(
        self, record: GenerationRecord
    ) -> Optional[str]:
        """Level-0 program digest from its publication sidecar, cached
        per generation (the publish path sealed it; no re-hash)."""
        t = record.iteration_number
        if t not in self._cascade_digests:
            digest = None
            path = getattr(record, "path", None)
            program = None
            cascade = getattr(record, "cascade", None)
            if cascade:
                program = cascade.get("program")
            if path and program:
                from adanet_tpu.core import checkpoint as ckpt

                sidecar = os.path.join(path, program + ckpt.DIGEST_SUFFIX)
                try:
                    with open(sidecar) as f:
                        digest = f.read().strip() or None
                except OSError:
                    digest = None
            self._cascade_digests[t] = digest
            for old in [k for k in self._cascade_digests if k < t - 2]:
                del self._cascade_digests[old]
        return self._cascade_digests[t]

    # --------------------------------------------------------------- canary

    def _mirror_canary(self, padded: Any, incumbent_outputs: Any) -> None:
        """Replays the batch on a staged candidate and reports health.

        `incumbent_outputs` may carry CASCADE level-0 answers (whole
        batch, or the clear rows of a per-row split); divergence
        against the candidate's full program would be calibration
        noise, not candidate health, so the divergence check is
        skipped whenever ANY row was answered cheap (finiteness still
        counts toward the canary window).
        """
        candidate = self.pool.canary_record()
        if candidate is None:
            return
        any_cheap = self.last_cascade_level == 0 or (
            self.last_row_fallthrough is not None
            and not bool(np.all(self.last_row_fallthrough))
        )
        try:
            mirrored = jax.device_get(
                self._step_for(candidate)(padded)
            )
            ok = outputs_finite(mirrored)
            divergence = (
                None
                if any_cheap
                else max_divergence(
                    jax.device_get(incumbent_outputs), mirrored
                )
            )
        except Exception as exc:
            _LOG.error(
                "Canary execution failed for generation %d: %s: %s",
                candidate.iteration_number,
                type(exc).__name__,
                exc,
            )
            ok, divergence = False, None
        if divergence is not None:
            self._g_canary_divergence.set(divergence)
        self.pool.report_canary(ok, divergence)
